"""Gated DeltaNet, the linear-attention mixer of Qwen3-Next (training form).

For the normed activation `x` (b, t, d), with `H_k` key heads of width `d_k`
and `H_v` value heads of width `d_v` (`H_v / H_k` value heads share a key
head's q and k):

    [q | k | v | z] = x W_qkvz        [b | a] = x W_ba
    [q | k | v] <- silu(causal depthwise conv over time, `conv` taps, no bias)
    beta = sigmoid(b)     g = -exp(A_log) * softplus(a + dt_bias)   (float32)
    q <- q / |q|_2 / sqrt(d_k)         k <- k / |k|_2
    o = gated_delta_rule(q, k, v, g, beta)            (ops/delta_rule.py)
    y = concat_heads(w_o * o / rms(o) * silu(z)) W_out

**Parameter layout.** A key head's columns stand together, as in the
published checkpoint: `w_qkvz` is (d, H_k, 2 d_k + 2 r d_v) with a head's
columns `[q | k | v of its r value heads | z of its r value heads]`, `w_ba`
(d, H_k, 2 r) `[b | a]`, the convolution (H_k, 2 d_k + r d_v, taps) over a
head's `[q | k | v]` channels; `A_log`, `dt_bias` (H_v,) with value head
`h_k * r + j`; `w_out` (H_v d_v, d). Tensor parallelism shards the key-head
axis (a key head with its value heads, the convolution by channel with
them) and `w_out` by rows, the Megatron pattern: one all-reduce after
`w_out`.

Scopes for a device trace: `gdn` (everything but the rule) and `gdn_rule`.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.collectives import copy_to, reduce_from
from ..ops.conv import causal_depthwise_conv
from ..ops.delta_rule import CHUNK, gated_delta_rule
from ..runtime.prng import fold
from .linear import uniform_fan_in
from .norm import GatedRMSNorm

Params = Dict[str, Any]


@dataclass(frozen=True)
class GatedDeltaNet:
    d: int
    num_k_heads: int
    num_v_heads: int
    k_head_dim: int
    v_head_dim: int
    conv_kernel: int = 4
    eps: float = 1e-6
    chunk: int = CHUNK
    tp_size: int = 1
    tp_axis: str = "tp"

    def __post_init__(self):
        if self.num_v_heads % self.num_k_heads:
            raise ValueError(
                f"value heads {self.num_v_heads} must be a multiple of key "
                f"heads {self.num_k_heads}")
        if self.num_k_heads % self.tp_size:
            raise ValueError(
                f"linear-attention key heads {self.num_k_heads} not "
                f"divisible by tp_size {self.tp_size}")

    @property
    def ratio(self) -> int:
        return self.num_v_heads // self.num_k_heads

    @property
    def conv_channels(self) -> int:
        """[q | k | v] channels of one key head."""
        return 2 * self.k_head_dim + self.ratio * self.v_head_dim

    @property
    def head_columns(self) -> int:
        """[q | k | v | z] columns of one key head."""
        return self.conv_channels + self.ratio * self.v_head_dim

    @property
    def o_norm(self) -> GatedRMSNorm:
        return GatedRMSNorm(self.v_head_dim, self.eps)

    def num_params(self) -> int:
        Hk, Hv = self.num_k_heads, self.num_v_heads
        return (self.d * Hk * self.head_columns + self.d * 2 * Hv
                + Hk * self.conv_channels * self.conv_kernel + 2 * Hv
                + self.v_head_dim + Hv * self.v_head_dim * self.d)

    # ---- init / specs ----

    def init(self, key: jax.Array) -> Params:
        d, Hk, Hv = self.d, self.num_k_heads, self.num_v_heads
        w = lambda name, shape, fan_in: uniform_fan_in(fold(key, name),
                                                       shape, fan_in)

        return {
            "w_qkvz": w("w_qkvz", (d, Hk, self.head_columns), d),
            "w_ba": w("w_ba", (d, Hk, 2 * self.ratio), d),
            "conv": w("conv", (Hk, self.conv_channels, self.conv_kernel),
                      self.conv_kernel),
            # the published implementation's: A = U(0, 16), dt_bias = 1
            "A_log": jnp.log(jax.random.uniform(
                fold(key, "A_log"), (Hv,), jnp.float32, 1e-3, 16.0)),
            "dt_bias": jnp.ones((Hv,), jnp.float32),
            "o_norm": self.o_norm.init(key),
            "w_out": w("w_out", (Hv * self.v_head_dim, d),
                       Hv * self.v_head_dim),
        }

    def specs(self) -> Params:
        tp = self.tp_axis
        return {"w_qkvz": P(None, tp, None), "w_ba": P(None, tp, None),
                "conv": P(tp, None, None), "A_log": P(tp), "dt_bias": P(tp),
                "o_norm": self.o_norm.specs(), "w_out": P(tp, None)}

    # ---- forward (per-shard, inside shard_map) ----

    def apply(self, params: Params, x: jax.Array,
              compute_dtype: jnp.dtype = jnp.float32) -> jax.Array:
        """x (b, t, d), replicated over tp -> the sublayer's output (b, t,
        d), reduced over tp.

        The rule's inputs are made under a `jax.checkpoint` of their own:
        inside a rematerialised layer the backward would otherwise hold the
        projection, the convolution's float32 sums and the normed q and k
        (3 GB at 16k tokens) all through the rule's backward; this way it
        holds `x` and makes them again once the rule's is over (one more
        d x 6 d product a layer)."""
        b, t, d = x.shape
        with jax.named_scope("gdn"):
            xd = copy_to(x.astype(compute_dtype), self.tp_axis)
            q, k, v, z, g, beta = jax.checkpoint(
                lambda p, xd: self._rule_inputs(p, xd, compute_dtype))(
                    {n: params[n] for n in ("w_qkvz", "w_ba", "conv",
                                            "A_log", "dt_bias")}, xd)
        with jax.named_scope("gdn_rule"):
            o, _ = gated_delta_rule(q, k, v, g, beta, chunk=self.chunk)
        with jax.named_scope("gdn"):
            o = self.o_norm.apply(params["o_norm"],
                                  o.transpose(0, 2, 1, 3), z)
            y = (o.reshape(b, t, -1).astype(compute_dtype)
                 @ params["w_out"].astype(compute_dtype))
            return reduce_from(y, self.tp_axis)

    def _rule_inputs(self, params: Params, xd: jax.Array, compute_dtype):
        """q, k, v (b, local value heads, t, width) in the compute dtype, z
        (b, t, local value heads, d_v), g and beta (b, local value heads,
        t) float32, from the layer's input."""
        b, t, _ = xd.shape
        dk, dv, r = self.k_head_dim, self.v_head_dim, self.ratio
        f32 = jnp.float32
        hk = params["w_qkvz"].shape[1]              # local key heads
        proj = jnp.einsum("btd,dhc->bthc", xd,
                          params["w_qkvz"].astype(compute_dtype))
        ba = jnp.einsum("btd,dhc->bthc", xd,
                        params["w_ba"].astype(compute_dtype)).astype(f32)
        mixed = self._conv(params["conv"], proj[..., :self.conv_channels])
        z = proj[..., self.conv_channels:].reshape(b, t, hk * r, dv)
        q, k = mixed[..., :dk], mixed[..., dk:2 * dk]
        v = mixed[..., 2 * dk:].reshape(b, t, hk * r, dv)
        l2 = lambda u: u.astype(f32) * jax.lax.rsqrt(jnp.sum(
            jnp.square(u.astype(f32)), axis=-1, keepdims=True) + self.eps)
        q = l2(q) * (1.0 / math.sqrt(dk))
        k = l2(k)
        beta = jax.nn.sigmoid(ba[..., :r]).reshape(b, t, hk * r)
        a = ba[..., r:].reshape(b, t, hk * r)
        g = -jnp.exp(params["A_log"]) * jax.nn.softplus(a + params["dt_bias"])
        # (b, value heads, t, width); a key head's q and k serve its r
        # value heads
        heads = lambda u: jnp.repeat(
            u.astype(compute_dtype).transpose(0, 2, 1, 3), r, axis=1)
        return (heads(q), heads(k),
                v.astype(compute_dtype).transpose(0, 2, 1, 3), z,
                g.transpose(0, 2, 1), beta.transpose(0, 2, 1))

    def _conv(self, w: jax.Array, u: jax.Array) -> jax.Array:
        """The causal depthwise convolution over time (ops/conv.py) then
        SiLU: u (b, t, heads, channels), w (heads, channels, taps); handed
        on in u's dtype."""
        return jax.nn.silu(causal_depthwise_conv(u, w)).astype(u.dtype)
