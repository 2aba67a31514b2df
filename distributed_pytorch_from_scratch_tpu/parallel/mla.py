"""Multi-head latent attention (DeepSeek-V2/V3), the training form.

Queries and keys/values are made through low-rank latents:

    c_q  = RMSNorm(x W_qa)                      (q_lora_rank)
    [q_nope | q_rope] = c_q W_qb                per head (nope | rope)
    [c_kv | k_r] = x W_kva                      (kv_lora_rank | rope)
    c_kv = RMSNorm(c_kv)
    [k_nope | v] = c_kv W_kvb                   per head (nope | v)

RoPE (interleaved pairs, `ops/rope.apply_rotary_interleaved`) turns `q_rope`
of every head and the ONE `k_r`, which all heads share. `q = [q_nope |
q_rope]`, `k = [k_nope | k_r]` are `qk_nope + qk_rope` wide, `v` is
`v_head_dim` wide: the attention kernels take the two widths
(ops/pallas/flash_attention.py), scale the scores by 1/sqrt(q's width) and
return v's; where the positions are YaRN's, q carries the rest of the
scale (`softmax_scale`). The heads' outputs go through `wo` (heads * v -> d).

Tensor parallelism: `wq_b` and `wkv_b` are column-parallel over heads (a
head's columns are contiguous) and `wo` row-parallel, the Megatron pattern;
`wq_a`, `wkv_a` and the two latent norms are replicated (the latents are a
few hundred wide and every head reads all of them). The shared `k_r` is
cast varying over 'tp' where it joins the local heads' keys, so its
gradient sums over the ranks.

This is the TRAINING form: k and v are materialised per head. Serving
keeps the latents in the cache instead and absorbs `W_kvb` into the query
and output sides; nothing here does that (ROADMAP, latent pages).
No biases anywhere.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.collectives import copy_to
from ..ops.rope import apply_rotary_interleaved
from .linear import (ColumnParallelLinear, RowParallelLinear,
                     _torch_linear_init)
from .norm import RMSNorm

Params = Dict[str, Any]


@dataclass(frozen=True)
class ReplicatedLinear:
    """y = x @ W with W whole on every device (no bias)."""

    idim: int
    odim: int

    def init(self, key: jax.Array) -> Params:
        return {"weight": _torch_linear_init(key, self.idim, self.odim)}

    def specs(self) -> Params:
        return {"weight": P(None, None)}

    def apply(self, params: Params, x: jax.Array,
              compute_dtype: jnp.dtype = jnp.float32) -> jax.Array:
        return x.astype(compute_dtype) @ params["weight"].astype(compute_dtype)


@dataclass(frozen=True)
class LatentAttention:
    """Static shape of one layer's latent attention; `modules()` are its
    per-layer modules by parameter key, `qkv` what they compute."""

    d: int
    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    eps: float = 1e-6
    # what the scores' 1/sqrt(q's width) is multiplied by (YaRN's mscale^2:
    # `ops/rope.YarnScaling.softmax_scale`). The attention kernels scale by
    # 1/sqrt(width) themselves, so q carries the rest
    softmax_scale: float = 1.0

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def modules(self) -> Dict[str, Any]:
        H = self.num_heads
        return {
            "wq_a": ReplicatedLinear(self.d, self.q_lora_rank),
            "q_norm": RMSNorm(self.q_lora_rank, self.eps),
            "wq_b": ColumnParallelLinear(self.q_lora_rank,
                                         H * self.qk_head_dim,
                                         add_bias=False, gather_output=False),
            "wkv_a": ReplicatedLinear(
                self.d, self.kv_lora_rank + self.qk_rope_head_dim),
            "kv_norm": RMSNorm(self.kv_lora_rank, self.eps),
            "wkv_b": ColumnParallelLinear(
                self.kv_lora_rank,
                H * (self.qk_nope_head_dim + self.v_head_dim),
                add_bias=False, gather_output=False),
            "wo": RowParallelLinear(H * self.v_head_dim, self.d,
                                    add_bias=False, split_input=False),
        }

    def num_params(self) -> int:
        H = self.num_heads
        return (self.d * self.q_lora_rank + self.q_lora_rank
                + self.q_lora_rank * H * self.qk_head_dim
                + self.d * (self.kv_lora_rank + self.qk_rope_head_dim)
                + self.kv_lora_rank
                + self.kv_lora_rank * H * (self.qk_nope_head_dim
                                           + self.v_head_dim)
                + H * self.v_head_dim * self.d)

    def qkv(self, mods: Dict[str, Any], lp: Params, y: jax.Array,
            cos: jax.Array, sin: jax.Array, dtype
            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """The normed activation y (b, t, d), replicated over 'tp' ->
        q, k (b, local heads, t, qk_head_dim) and v (b, local heads, t,
        v_head_dim), RoPE applied. cos/sin: `ops/rope.rope_angles`."""
        b, t, _ = y.shape
        nope, rope, vd = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                          self.v_head_dim)
        heads = lambda z, w: z.reshape(b, t, -1, w).transpose(0, 2, 1, 3)

        c_q = mods["q_norm"].apply(
            lp["q_norm"], mods["wq_a"].apply(lp["wq_a"], y, dtype))
        q = heads(mods["wq_b"].apply(lp["wq_b"], c_q, dtype), nope + rope)
        q_nope, q_rope = q[..., :nope], q[..., nope:]

        ckv = mods["wkv_a"].apply(lp["wkv_a"], y, dtype)
        c_kv, k_r = ckv[..., :self.kv_lora_rank], ckv[..., self.kv_lora_rank:]
        c_kv = mods["kv_norm"].apply(lp["kv_norm"], c_kv)
        kv = heads(mods["wkv_b"].apply(lp["wkv_b"], c_kv, dtype), nope + vd)
        k_nope, v = kv[..., :nope], kv[..., nope:]

        q_rope = apply_rotary_interleaved(q_rope, cos, sin)
        # one rotary key head, shared by every head; the local heads of
        # every tp rank read it, so its cotangent sums over 'tp'
        k_r = apply_rotary_interleaved(copy_to(k_r, "tp")[:, None], cos, sin)
        k_r = jnp.broadcast_to(k_r, k_nope.shape[:-1] + (rope,))
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate([k_nope, k_r], axis=-1)
        if self.softmax_scale != 1.0:
            q = q * jnp.asarray(self.softmax_scale, q.dtype)
        return q, k, v
