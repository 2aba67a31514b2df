"""Gated grouped-query attention, the full-attention mixer of Qwen3-Next.

For the normed activation `x` (b, t, d), `H` query heads over `H_kv`
key-value heads (query head `h` reads key-value head `h // (H / H_kv)`), all
of width `head_dim`:

    [q | gate] = x W_q   per query head      k = x W_k      v = x W_v
    q = N(q)   k = N(k)  per head (zero-centred RMSNorm over head_dim)
    RoPE (half-split pairs) on the first `rotary_dim` dimensions of q and k
    o = causal softmax attention(q, k, v), scores / sqrt(head_dim)
    y = (concat_heads(o) * sigmoid(gate)) W_o

`wq` is (d, H, 2 head_dim) with a head's columns `[q | gate]`, `wk` / `wv`
(d, H_kv, head_dim), `wo` (H head_dim, d). Tensor parallelism shards the
head axes (a key-value head with its group of query heads: tp divides
`H_kv`) and `wo` by rows.

`qkv` and `project` are the two halves around the attention call, which is
the caller's (`models/gdn_moe.py`: the flash kernel with its native
grouping, or the XLA path). Scope: `gated_attn`.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ..ops.collectives import copy_to, reduce_from
from ..ops.rope import apply_rotary_leading
from ..runtime.prng import fold
from .linear import uniform_fan_in
from .norm import ZeroCenteredRMSNorm

Params = Dict[str, Any]


def gate_heads(o: jax.Array, gate: jax.Array) -> jax.Array:
    """The heads' outputs `o` (b, t, heads * width) times the sigmoid of
    the gate's logits, taken in float32: the output gate's text, shared
    with the stack's (q, k, v) dispatch (a layer that holds `wg`)."""
    return o * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(o.dtype)


@dataclass(frozen=True)
class GatedAttention:
    d: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rotary_dim: int
    eps: float = 1e-6
    tp_size: int = 1
    tp_axis: str = "tp"

    def __post_init__(self):
        if self.num_heads % self.num_kv_heads:
            raise ValueError(f"query heads {self.num_heads} must be a "
                             f"multiple of key-value heads "
                             f"{self.num_kv_heads}")
        if self.num_kv_heads % self.tp_size:
            raise ValueError(f"key-value heads {self.num_kv_heads} not "
                             f"divisible by tp_size {self.tp_size}")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError(f"rotary_dim {self.rotary_dim} must be even "
                             f"and at most head_dim {self.head_dim}")

    @property
    def head_norm(self) -> ZeroCenteredRMSNorm:
        return ZeroCenteredRMSNorm(self.head_dim, self.eps)

    def num_params(self) -> int:
        h = self.head_dim
        return (self.d * self.num_heads * 2 * h
                + 2 * self.d * self.num_kv_heads * h + 2 * h
                + self.num_heads * h * self.d)

    def init(self, key: jax.Array) -> Params:
        d, H, Hkv, h = self.d, self.num_heads, self.num_kv_heads, self.head_dim
        w = lambda name, shape, fan_in: uniform_fan_in(fold(key, name),
                                                       shape, fan_in)

        return {"wq": w("wq", (d, H, 2 * h), d),
                "wk": w("wk", (d, Hkv, h), d),
                "wv": w("wv", (d, Hkv, h), d),
                "q_norm": self.head_norm.init(key),
                "k_norm": self.head_norm.init(key),
                "wo": w("wo", (H * h, d), H * h)}

    def specs(self) -> Params:
        tp = self.tp_axis
        norm = self.head_norm.specs()
        return {"wq": P(None, tp, None), "wk": P(None, tp, None),
                "wv": P(None, tp, None), "q_norm": norm, "k_norm": norm,
                "wo": P(tp, None)}

    def qkv(self, params: Params, x: jax.Array, cos: jax.Array,
            sin: jax.Array, dtype
            ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
        """x (b, t, d), replicated over tp -> q (b, local heads, t, h), k, v
        (b, local key-value heads, t, h) and the output gate's logits (b,
        t, local heads * h). cos/sin: `ops/rope.rope_angles` of
        `rotary_dim`."""
        b, t, _ = x.shape
        h = self.head_dim
        with jax.named_scope("gated_attn"):
            xd = copy_to(x.astype(dtype), self.tp_axis)
            proj = lambda name: jnp.einsum("btd,dhc->bhtc", xd,
                                           params[name].astype(dtype))
            qg = checkpoint_name(proj("wq"), "q_proj")
            k = checkpoint_name(proj("wk"), "k_proj")
            v = checkpoint_name(proj("wv"), "v_proj")
            q, gate = qg[..., :h], qg[..., h:]
            q = self.head_norm.apply(params["q_norm"], q)
            k = self.head_norm.apply(params["k_norm"], k)
            q = apply_rotary_leading(q, cos, sin, self.rotary_dim)
            k = apply_rotary_leading(k, cos, sin, self.rotary_dim)
            gate = gate.transpose(0, 2, 1, 3).reshape(b, t, -1)
        return q, k, v, gate

    def project(self, params: Params, o: jax.Array, gate: jax.Array,
                dtype) -> jax.Array:
        """The heads' outputs o (b, local heads, t, h) gated and through
        `wo`, reduced over tp: (b, t, d)."""
        b, _, t, _ = o.shape
        with jax.named_scope("gated_attn"):
            o = o.transpose(0, 2, 1, 3).reshape(b, t, -1)
            o = gate_heads(o, gate)
            return reduce_from(o.astype(dtype) @ params["wo"].astype(dtype),
                               self.tp_axis)
