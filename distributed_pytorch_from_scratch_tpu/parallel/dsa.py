"""Grouped-query attention whose every layer CHOOSES its keys: the lightning
indexer and the attention module that owns it (DeepSeek-V3.2's sparse
attention at the sizes a configuration states; the Keye-VL-2.0 decoder).

For the normed activation `x` (b, t, d):

    q = x W_q (H heads)   k = x W_k   v = x W_v (H_kv heads), all `head_dim`
    q = N(q)  k = N(k)    per head (RMSNorm over head_dim), then RoPE
                          (half-split pairs over the whole head)
    the indexer, on stop_gradient(x):
        qI = x W_qI (J heads of c)      kI = LayerNorm(x W_kI) (ONE head of c)
        w  = (x W_w) J^-1/2 c^-1/2      (J weights a row, float32)
        RoPE over all c dimensions of qI and kI
    (o, sums) = ops/index_select.selected_attention(q, k, v, qI, kI, w, top_k)
    y = concat_heads(o) W_o

`wq` (d, H head_dim), `wk` / `wv` (d, H_kv head_dim), `wo` (H head_dim, d);
`indexer`: `wq` (d, J c), `wk` (d, c), `k_norm` (a LayerNorm's scale and
bias), `w_proj` (d, J). The attention's gradient reaches no leaf of
`indexer`, and the indexer's loss (`sums["dsa_index_kl"]`, the sum of the
rows' KL) no other leaf: the two stop-gradients are here and in
`selected_attention`.

Tensor parallelism over the heads is not written (the indexer's one key head
and its head-summed target belong to every rank): the family refuses it.
Scopes: `gqa_attn` (the main projections, norms, RoPE, `W_o`), `dsa_index`
(the indexer's), and the op's own `dsa_select`, `dsa_attend`,
`dsa_index_loss`.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ..ops.index_select import selected_attention, selection_probe
from ..ops.rope import apply_rotary_leading
from ..runtime.prng import fold
from .linear import uniform_fan_in
from .norm import LayerNorm, RMSNorm

Params = Dict[str, Any]


@dataclass(frozen=True)
class LightningIndexer:
    d: int
    num_heads: int
    head_dim: int
    eps: float = 1e-6

    @property
    def k_norm(self) -> LayerNorm:
        return LayerNorm(self.head_dim, self.eps)

    def num_params(self) -> int:
        c = self.head_dim
        return self.d * (self.num_heads * c + c + self.num_heads) + 2 * c

    def init(self, key: jax.Array) -> Params:
        d, J, c = self.d, self.num_heads, self.head_dim
        w = lambda name, shape: uniform_fan_in(fold(key, name), shape, d)
        return {"wq": w("index_wq", (d, J * c)), "wk": w("index_wk", (d, c)),
                "k_norm": self.k_norm.init(key),
                "w_proj": w("index_w", (d, J))}

    def specs(self) -> Params:
        return {"wq": P(None, None), "wk": P(None, None),
                "k_norm": self.k_norm.specs(), "w_proj": P(None, None)}

    def apply(self, params: Params, x: jax.Array, cos: jax.Array,
              sin: jax.Array, dtype):
        """x (b, t, d) -> qI (b, J, t, c), kI (b, t, c) in `dtype`, w (b, t,
        J) float32. cos/sin: `ops/rope.rope_angles` of `head_dim`."""
        b, t, _ = x.shape
        J, c = self.num_heads, self.head_dim
        xd = x.astype(dtype)
        q = (xd @ params["wq"].astype(dtype)).reshape(b, t, J, c)
        q = apply_rotary_leading(q.transpose(0, 2, 1, 3), cos, sin, c)
        k = self.k_norm.apply(params["k_norm"],
                              xd @ params["wk"].astype(dtype))
        k = apply_rotary_leading(k[:, None], cos, sin, c)[:, 0]
        w = (xd @ params["w_proj"].astype(dtype)).astype(jnp.float32)
        return q, k, w * (1.0 / math.sqrt(J * c))


@dataclass(frozen=True)
class SelectedAttention:
    d: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    top_k: int
    indexer: LightningIndexer
    eps: float = 1e-6

    def __post_init__(self):
        if self.num_heads % self.num_kv_heads:
            raise ValueError(f"query heads {self.num_heads} must be a "
                             f"multiple of key-value heads "
                             f"{self.num_kv_heads}")
        if self.top_k < 1:
            raise ValueError(f"top_k {self.top_k}: a row keeps itself at "
                             f"least")

    @property
    def head_norm(self) -> RMSNorm:
        return RMSNorm(self.head_dim, self.eps)

    def num_params(self) -> int:
        h = self.head_dim
        return (2 * self.d * self.num_heads * h
                + 2 * self.d * self.num_kv_heads * h + 2 * h
                + self.indexer.num_params())

    def init(self, key: jax.Array) -> Params:
        d, h = self.d, self.head_dim
        qd, kvd = self.num_heads * h, self.num_kv_heads * h
        w = lambda name, shape: uniform_fan_in(fold(key, name), shape,
                                               shape[0])
        return {"wq": w("wq", (d, qd)), "wk": w("wk", (d, kvd)),
                "wv": w("wv", (d, kvd)),
                "q_norm": self.head_norm.init(key),
                "k_norm": self.head_norm.init(key),
                "wo": w("wo", (qd, d)),
                "indexer": self.indexer.init(key)}

    def specs(self) -> Params:
        norm = self.head_norm.specs()
        return {"wq": P(None, None), "wk": P(None, None),
                "wv": P(None, None), "q_norm": norm, "k_norm": norm,
                "wo": P(None, None), "indexer": self.indexer.specs()}

    def qkv(self, params: Params, x: jax.Array, cos: jax.Array,
            sin: jax.Array, dtype):
        """q (b, H, t, h), k, v (b, H_kv, t, h), normed and rotated."""
        b, t, _ = x.shape
        h = self.head_dim
        with jax.named_scope("gqa_attn"):
            xd = x.astype(dtype)
            proj = lambda name: checkpoint_name(
                xd @ params[f"w{name}"].astype(dtype), f"{name}_proj")
            split = lambda z: z.reshape(b, t, -1, h).transpose(0, 2, 1, 3)
            q, k, v = split(proj("q")), split(proj("k")), split(proj("v"))
            q = self.head_norm.apply(params["q_norm"], q)
            k = self.head_norm.apply(params["k_norm"], k)
            return (apply_rotary_leading(q, cos, sin, h),
                    apply_rotary_leading(k, cos, sin, h), v)

    def project(self, params: Params, o: jax.Array, dtype) -> jax.Array:
        b, _, t, _ = o.shape
        with jax.named_scope("gqa_attn"):
            o = o.transpose(0, 2, 1, 3).reshape(b, t, -1)
            return o.astype(dtype) @ params["wo"].astype(dtype)

    def apply(self, params: Params, x: jax.Array, pos, dtype,
              impl: str = "auto", probe: bool = False):
        """x (b, t, d) -> (y (b, t, d), `ops/index_select.SUMS`). `pos`:
        (cos, sin) of the heads' width and (cos, sin) of the indexer's.
        `probe`: beside the sums, what `ops/index_select.selection_probe`
        writes out (`dsa_score_rows`, `dsa_live`), for a check."""
        cos, sin, cos_i, sin_i = pos
        q, k, v = self.qkv(params, x, cos, sin, dtype)
        with jax.named_scope("dsa_index"):
            q_idx, k_idx, w = self.indexer.apply(
                params["indexer"], lax.stop_gradient(x), cos_i, sin_i, dtype)
        o, sums = selected_attention(q, k, v, q_idx, k_idx, w, self.top_k,
                                     impl=impl)
        if probe:
            rows, chosen = selection_probe(q_idx, k_idx, w, self.top_k,
                                           impl=impl)
            sums = {**sums, "dsa_score_rows": rows, "dsa_live": chosen}
        return self.project(params, o, dtype), sums
