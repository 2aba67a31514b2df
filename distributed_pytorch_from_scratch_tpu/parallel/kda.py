"""Kimi Delta Attention (Kimi Linear, arXiv:2510.26692), the linear-attention
mixer of Ling-3.0 (training form): a delta rule whose decay is a CHANNEL'S,
under a bounded gate.

For the normed activation `x` (b, t, d), with `H` heads of width `d_k` (q, k,
the decay) and `d_v` (v, the output):

    q, k, v = silu(conv(x W_q)), silu(conv(x W_k)), silu(conv(x W_v))
                                    (causal depthwise, `conv` taps, no bias)
    q <- q / |q|_2 / sqrt(d_k)      k <- k / |k|_2          (per head)
    beta = sigmoid(x W_beta)                                 (a head)
    g = lower_bound * sigmoid(exp(A_log) * (x W_f + dt_bias))   (float32)
    o = channel_delta_rule(q, k, v, g, beta)            (ops/delta_rule.py)
    y = concat_heads(w_o * o / rms(o) * sigmoid(x W_g)) W_out

`g` is a vector a head and token, `lower_bound < g < 0` (`kda_lower_bound`
-5: the published `kda_safe_gate`): `A_log` is a head's, `dt_bias` a
channel's, and `W_f` is full rank (`no_kda_lora`), as is the output gate
`W_g`. **The bound is load-bearing**: the chunked rule's sub-blocks multiply
by `exp(-(SUB - 1) g)` and rely on `g >= -5` to stay inside float32
(ops/delta_rule.py's docstring); `__post_init__` refuses a bound the
sub-blocks cannot hold.

**Parameter layout.** The published checkpoint's, a projection a leaf: `w_q`,
`w_k`, `w_f` (d, H, d_k), `w_v`, `w_g` (d, H, d_v), `w_beta` (d, H), the
three convolutions `conv_q`, `conv_k` (H, d_k, taps), `conv_v` (H, d_v,
taps), `A_log` (H,), `dt_bias` (H, d_k), `o_norm` (d_v,), `w_out` (H d_v,
d). Tensor parallelism shards the head axis and `w_out` by rows, the
Megatron pattern: one all-reduce after `w_out`.

**Where the rule is made.** `channel_delta_rule` picks from what it sees: on
a TPU at these widths (multiples of 128) three Pallas kernels
(ops/pallas/kda_rule.py: a sub-block's decay factors, the chunk's operands
and the walk all in VMEM, the backward by hand), the XLA text everywhere
else; the instant `kda_rule` on the program's tracer says which, once a
trace. On the kernel path a head keeps between forward and backward its
inputs (`q`, `k`, `v`, `g`, `beta`), the chunks' inverses `T` and one
float32 state a block of chunks: 0.27 GB a layer at 32 heads x 4096 tokens,
live only inside that layer's backward.

Scopes for a device trace: `kda` (everything but the rule, `kda/gate` the
decay's passes) and `kda_rule` (the kernels `kda_rule_pairs`, `kda_rule_fwd`,
`kda_rule_bwd` and XLA's cumsum and inverse around them; in the XLA text
`kda_rule/operands`, `kda_rule/walk`).
`apply` also hands back two counters of the decay it made: `kda_g_min` (the
most negative `g`: never under the bound) and `kda_g_spread` (the mean over
tokens and heads of the channels' standard deviation of `g`: 0 says the
decay is one scalar a head again).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ..ops.collectives import copy_to, reduce_from
from ..ops.conv import causal_depthwise_conv
from ..ops.delta_rule import CHUNK, SUB, channel_delta_rule
from ..runtime.prng import fold
from .linear import uniform_fan_in
from .norm import GatedRMSNorm

Params = Dict[str, Any]


def _inverse_softplus(dt: jax.Array) -> jax.Array:
    return dt + jnp.log(-jnp.expm1(-dt))


@dataclass(frozen=True)
class KimiDeltaAttention:
    d: int
    num_heads: int
    k_head_dim: int
    v_head_dim: int
    conv_kernel: int = 4
    lower_bound: float = -5.0
    eps: float = 1e-6
    chunk: int = CHUNK
    tp_size: int = 1
    tp_axis: str = "tp"

    def __post_init__(self):
        if self.num_heads % self.tp_size:
            raise ValueError(
                f"linear-attention heads {self.num_heads} not divisible by "
                f"tp_size {self.tp_size}")
        # exp(-(SUB - 1) * bound) is the largest factor a sub-block makes
        if not -(SUB - 1) * self.lower_bound < 87.0 or self.lower_bound >= 0:
            raise ValueError(
                f"kda_lower_bound {self.lower_bound} must be negative and "
                f"keep exp({SUB - 1} x its size) inside float32: the "
                f"chunked rule's sub-blocks rely on it")

    @property
    def o_norm(self) -> GatedRMSNorm:
        return GatedRMSNorm(self.v_head_dim, self.eps, gate="sigmoid")

    def num_params(self) -> int:
        H, dk, dv = self.num_heads, self.k_head_dim, self.v_head_dim
        return (self.d * H * (3 * dk + 2 * dv) + self.d * H
                + H * (2 * dk + dv) * self.conv_kernel + H + H * dk + dv
                + H * dv * self.d)

    # ---- init / specs ----

    def init(self, key: jax.Array) -> Params:
        d, H, dk, dv = (self.d, self.num_heads, self.k_head_dim,
                        self.v_head_dim)
        w = lambda name, shape, fan_in: uniform_fan_in(fold(key, name),
                                                       shape, fan_in)
        conv = lambda name, width: w(name, (H, width, self.conv_kernel),
                                     self.conv_kernel)
        return {
            "w_q": w("w_q", (d, H, dk), d), "w_k": w("w_k", (d, H, dk), d),
            "w_v": w("w_v", (d, H, dv), d),
            "conv_q": conv("conv_q", dk), "conv_k": conv("conv_k", dk),
            "conv_v": conv("conv_v", dv),
            "w_f": w("w_f", (d, H, dk), d),
            # the published implementation's: A = U(1, 16), and dt_bias
            # the inverse softplus of dt = exp(U(log 0.001, log 0.1)), so
            # that a fresh layer forgets slowly (g between about -0.5, at
            # A = 1, and 0)
            "A_log": jnp.log(jax.random.uniform(
                fold(key, "A_log"), (H,), jnp.float32, 1.0, 16.0)),
            "dt_bias": _inverse_softplus(jnp.exp(jax.random.uniform(
                fold(key, "dt_bias"), (H, dk), jnp.float32,
                math.log(1e-3), math.log(1e-1)))),
            "w_beta": w("w_beta", (d, H), d),
            "w_g": w("w_g", (d, H, dv), d),
            "o_norm": self.o_norm.init(key),
            "w_out": w("w_out", (H * dv, d), H * dv),
        }

    def specs(self) -> Params:
        tp = self.tp_axis
        proj, conv = P(None, tp, None), P(tp, None, None)
        return {"w_q": proj, "w_k": proj, "w_v": proj, "conv_q": conv,
                "conv_k": conv, "conv_v": conv, "w_f": proj, "A_log": P(tp),
                "dt_bias": P(tp, None), "w_beta": P(None, tp), "w_g": proj,
                "o_norm": self.o_norm.specs(), "w_out": P(tp, None)}

    # ---- forward (per-shard, inside shard_map) ----

    def apply(self, params: Params, x: jax.Array,
              compute_dtype: jnp.dtype = jnp.float32
              ) -> Tuple[jax.Array, Params]:
        """x (b, t, d), replicated over tp -> (the sublayer's output (b, t,
        d), reduced over tp; the decay's counters, scalars).

        What of the rule's inputs a backward makes again is the layer's
        remat rung's to say (`models/stack.remat_wrap`), and no checkpoint
        of the mixer's own: the layer's recompute makes them once and the
        backward transposes them where they stand."""
        b, t, d = x.shape
        with jax.named_scope("kda"):
            xd = copy_to(x.astype(compute_dtype), self.tp_axis)
            q, k, v, g, beta = self._rule_inputs(params, xd, compute_dtype)
            with jax.named_scope("gate"):
                counters = self._counters(g)
        with jax.named_scope("kda_rule"):
            o, _ = channel_delta_rule(q, k, v, g, beta, chunk=self.chunk)
        with jax.named_scope("kda"):
            z = jnp.einsum("btd,dhc->bthc", xd,
                           params["w_g"].astype(compute_dtype))
            o = self.o_norm.apply(params["o_norm"],
                                  o.transpose(0, 2, 1, 3), z)
            y = (o.reshape(b, t, -1).astype(compute_dtype)
                 @ params["w_out"].astype(compute_dtype))
            return reduce_from(y, self.tp_axis), counters

    def _counters(self, g: jax.Array) -> Params:
        """g (b, local heads, t, d_k) -> `kda_g_min`, `kda_g_spread` over
        this shard's rows and every head (module docstring)."""
        g = lax.stop_gradient(g)
        return {
            "kda_g_min": lax.pmin(jnp.min(g), self.tp_axis),
            "kda_g_spread": lax.pmean(jnp.mean(jnp.std(g, axis=-1)),
                                      self.tp_axis)}

    def _rule_inputs(self, params: Params, xd: jax.Array, compute_dtype):
        """q, k (b, local heads, t, d_k), v (b, local heads, t, d_v) in the
        compute dtype; g (b, local heads, t, d_k) and beta (b, local heads,
        t) float32, from the layer's input."""
        dk = self.k_head_dim
        f32 = jnp.float32
        project = lambda name: jnp.einsum(
            "btd,dh...->bth...", xd, params[name].astype(compute_dtype))
        conv = lambda name, u: jax.nn.silu(causal_depthwise_conv(
            u, params[name])).astype(u.dtype)
        # REMAT_LADDER's names, as the stack's own attention tags its
        # projections: rung `dots` keeps them and its recompute starts at
        # the convolutions
        q = conv("conv_q", checkpoint_name(project("w_q"), "q_proj"))
        k = conv("conv_k", checkpoint_name(project("w_k"), "k_proj"))
        v = conv("conv_v", checkpoint_name(project("w_v"), "v_proj"))
        l2 = lambda u: u.astype(f32) * lax.rsqrt(jnp.sum(
            jnp.square(u.astype(f32)), axis=-1, keepdims=True) + self.eps)
        q = l2(q) * (1.0 / math.sqrt(dk))
        k = l2(k)
        beta = jax.nn.sigmoid(project("w_beta").astype(f32))
        with jax.named_scope("gate"):
            a = project("w_f").astype(f32)
            g = self.lower_bound * jax.nn.sigmoid(
                jnp.exp(params["A_log"])[:, None] * (a + params["dt_bias"]))
        heads = lambda u: u.transpose(0, 2, 1, 3)
        return (heads(q.astype(compute_dtype)), heads(k.astype(compute_dtype)),
                heads(v), heads(g), beta.transpose(0, 2, 1))
