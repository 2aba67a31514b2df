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

Two facts a family may state (Ling-3.0's latent layers state both):
`q_lora_rank` None makes q with no latent, `[q_nope | q_rope] = x W_q`, one
column-parallel `wq` and no `wq_a` / `q_norm` / `wq_b`; `head_gate` multiplies
every head's output by `sigmoid(x W_gate)_h`, one scalar a head and token
(`w_gate`, d -> heads, column-parallel; `qkv` hands the gate's logits back
fourth), before `wo`. A family whose layers hold the attention as ONE module
(`_mods["mla"]`, beside another mixer) uses `init` / `specs` / `apply`; the
`mla_moe` family holds `modules()` flat in its layers and goes through the
stack's (q, k, v) dispatch.

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

from ..ops.attention import causal_attention
from ..ops.collectives import copy_to
from ..ops.rope import apply_rotary_interleaved
from ..runtime.prng import fold
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
    q_lora_rank: "int | None"       # None: q straight from x (`wq`)
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    eps: float = 1e-6
    # what the scores' 1/sqrt(q's width) is multiplied by (YaRN's mscale^2:
    # `ops/rope.YarnScaling.softmax_scale`). The attention kernels scale by
    # 1/sqrt(width) themselves, so q carries the rest
    softmax_scale: float = 1.0
    # a sigmoid gate a head on the heads' outputs, before `wo`
    head_gate: bool = False

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def modules(self) -> Dict[str, Any]:
        H = self.num_heads
        col = lambda idim, odim: ColumnParallelLinear(
            idim, odim, add_bias=False, gather_output=False)
        if self.q_lora_rank is None:
            q = {"wq": col(self.d, H * self.qk_head_dim)}
        else:
            q = {"wq_a": ReplicatedLinear(self.d, self.q_lora_rank),
                 "q_norm": RMSNorm(self.q_lora_rank, self.eps),
                 "wq_b": col(self.q_lora_rank, H * self.qk_head_dim)}
        gate = {"w_gate": col(self.d, H)} if self.head_gate else {}
        return {
            **q, **gate,
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
        H, r = self.num_heads, self.q_lora_rank
        q = (self.d * H * self.qk_head_dim if r is None
             else self.d * r + r + r * H * self.qk_head_dim)
        return (q + self.head_gate * self.d * H
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
        v_head_dim), RoPE applied; with `head_gate`, fourth, the gate's
        logits (b, local heads, t). cos/sin: `ops/rope.rope_angles`."""
        b, t, _ = y.shape
        nope, rope, vd = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                          self.v_head_dim)
        heads = lambda z, w: z.reshape(b, t, -1, w).transpose(0, 2, 1, 3)

        if self.q_lora_rank is None:
            q = heads(mods["wq"].apply(lp["wq"], y, dtype), nope + rope)
        else:
            c_q = mods["q_norm"].apply(
                lp["q_norm"], mods["wq_a"].apply(lp["wq_a"], y, dtype))
            q = heads(mods["wq_b"].apply(lp["wq_b"], c_q, dtype),
                      nope + rope)
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
        if not self.head_gate:
            return q, k, v
        with jax.named_scope("gate"):
            gate = mods["w_gate"].apply(lp["w_gate"], y, dtype)
        return q, k, v, gate.transpose(0, 2, 1)

    # ---- the attention as one module of a layer ----

    def init(self, key: jax.Array) -> Params:
        return {name: mod.init(fold(key, name))
                for name, mod in self.modules().items()}

    def specs(self) -> Params:
        return {name: mod.specs() for name, mod in self.modules().items()}

    def apply(self, params: Params, y: jax.Array, cos: jax.Array,
              sin: jax.Array, dtype, attn_impl: str = "auto") -> jax.Array:
        """The normed activation y (b, t, d), replicated over 'tp' -> the
        sublayer's output (b, t, d), reduced over 'tp': `qkv`, the causal
        attention call, the head gate, `wo`. The projections run under the
        scope `mla`, the kernel outside it (as the stack's dispatch has
        it)."""
        mods = self.modules()
        b, t, _ = y.shape
        with jax.named_scope("mla"):
            q, k, v, *gate = self.qkv(mods, params, y, cos, sin, dtype)
        o = causal_attention(q, k, v, impl=attn_impl)
        with jax.named_scope("mla"):
            if gate:
                with jax.named_scope("gate"):
                    o = (o * jax.nn.sigmoid(gate[0].astype(jnp.float32))
                         [..., None]).astype(o.dtype)
            o = o.transpose(0, 2, 1, 3).reshape(b, t, -1)
            return mods["wo"].apply(params["wo"], o, dtype)
