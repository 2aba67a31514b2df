"""GPT-2 model family: LayerNorm + GELU MLP + learned positions + TIED
vocab-parallel embeddings, on the same decoder stack as the LLaMA family.

The reference implements exactly one family (RoPE/RMSNorm/SwiGLU,
`/root/reference/models/model.py`); this one is a framework extension.
`GPT2Transformer` is a subclass of `models/stack.DecoderStack` and holds
only what differs from its sibling (`models/transformer.Transformer`): its
modules and parameter tree, the learned position table, the two-matrix
MLP, the tied head, and its facts. Fields, validation, the tp layout, the
remat ladder, the layer skeleton, the pipeline schedules, the losses and
the jitted entry points are the stack's, so context parallelism (ring /
Ulysses over 'cp'), Megatron sequence parallelism over 'tp', the rings,
the pipeline over 'pp', MoE and ZeRO-3 compose with this family exactly as
with the llama one.

Design notes:

* **Tied head, vocab-parallel both ways.** GPT-2 ties lm_head to the token
  embedding. The embedding is already row-sharded over 'tp'
  (`parallel/embedding.py`), so the tied head is simply
  `logits_local = x @ tok_emb_localᵀ` — the per-shard logits land in
  exactly the layout the vocab-parallel CE consumes. No extra collective,
  and the embedding weight receives BOTH gradient contributions (lookup and
  head) through plain autodiff.

* **Megatron TP pattern identical to the LLaMA family**: wq/wk/wv + fc are
  column-parallel (`gather_output=False`), wo + proj row-parallel
  (`split_input=False`) — one all-reduce per sublayer per direction.

* **No RoPE**: positions are learned and enter at the embedding, so the cp
  shards just index their position slice and a layer gets nothing.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ..config import ModelConfig
from ..parallel.embedding import VocabParallelEmbedding
from ..parallel.linear import ColumnParallelLinear, RowParallelLinear
from ..parallel.moe import MoEFFN
from ..parallel.norm import LayerNorm
from ..runtime.prng import fold
from .stack import DecoderStack, Params, TPSublayers

INIT_STD = 0.02  # GPT-2's embedding/projection init scale


@dataclass(frozen=True)
class GPT2Transformer(DecoderStack):
    """The GPT-2 family: learned positions, LayerNorm, a GELU MLP of two
    matrices, the head tied to the embedding, multi-head attention."""

    # what the stack, the decoder (models/decode.py), training/memory.py and
    # obs/attribution.py ask a family
    family = "gpt2"
    uses_rope = False         # learned position embeddings instead of RoPE
    attn_norm_key = "ln1"
    ffn_norm_key = "ln2"
    ffn_inputs = 1            # fc alone reads the MLP's input
    tied_head = True

    @staticmethod
    def num_params(cfg: ModelConfig) -> int:
        """Two-matrix MLP (or the shared SwiGLU experts and their router),
        a position table, LayerNorm biases, no head of its own."""
        d, f, L = cfg.attn_dim, cfg.ffn_dim, cfg.num_layers
        layer = 4 * d * d + 4 * d + 2 * d * f + f + d + 4 * d
        if cfg.num_experts:
            layer = (4 * d * d + 4 * d + 4 * d
                     + cfg.num_experts * 3 * d * f + d * cfg.num_experts)
        return cfg.vocab_size * d + cfg.maxlen * d + L * layer + 2 * d

    def __post_init__(self):
        if self.cfg.kv_heads != self.cfg.num_heads:
            raise ValueError("grouped-query attention (num_kv_heads) is a "
                             "llama-family feature; the gpt2 family is MHA "
                             "(real GPT-2 has none — documented choice)")
        super().__post_init__()

    @property
    def max_decode_positions(self) -> int:
        """Learned position embeddings hard-cap the sequence at maxlen —
        unlike RoPE, there is no table to extend (decode callers clamp
        their buffers; see evaluate.greedy_decode)."""
        return self.cfg.maxlen

    # ---- sub-module definitions ----

    @functools.cached_property
    def embedding(self) -> VocabParallelEmbedding:
        return VocabParallelEmbedding(self.cfg.vocab_size, self.d,
                                      tp_size=self.tp_size,
                                      init_std=INIT_STD)

    @functools.cached_property
    def _mods(self) -> Dict[str, Any]:
        d, f = self.d, self.cfg.ffn_dim
        ov = self._linear_overlap
        mods = {
            "ln1": LayerNorm(d),
            # wq/wk/wv (and fc) stay overlap='off': under ring overlap the
            # stack's fused ring covers them (TPSublayers.columns)
            "wq": ColumnParallelLinear(d, d, gather_output=False),
            "wk": ColumnParallelLinear(d, d, gather_output=False),
            "wv": ColumnParallelLinear(d, d, gather_output=False),
            "wo": RowParallelLinear(d, d, split_input=False, overlap=ov),
            "ln2": LayerNorm(d),
        }
        if self.is_moe:
            # The SAME routed-expert sublayer as the llama family
            # (parallel/moe.py). The experts are SwiGLU internally — a
            # deliberate reuse: the MoE machinery (router, capacity
            # dispatch, ep all_to_all, tp-sharded expert einsums, aux
            # losses) is activation-agnostic, and the trunk stays pure
            # GPT-2 (LayerNorm, learned positions, tied head).
            mods["moe"] = MoEFFN(
                d, f, self.cfg.num_experts, top_k=self.cfg.moe_top_k,
                capacity_factor=self.cfg.moe_capacity_factor,
                ep_size=self.ep_size, tp_size=self.tp_size)
        else:
            mods.update({
                "fc": ColumnParallelLinear(d, f, gather_output=False),
                "proj": RowParallelLinear(f, d, split_input=False,
                                          overlap=ov),
            })
        return mods

    # ---- init / specs ----

    def init(self, key: jax.Array) -> Params:
        layers = self._init_layers(key)
        return {
            "embedding": self.embedding.init(fold(key, "embedding")),
            "pos_embedding": {"weight": INIT_STD * jax.random.normal(
                fold(key, "pos"), (self.cfg.maxlen, self.d), jnp.float32)},
            "layers": layers,
            "norm": self.final_norm.init(fold(key, "norm")),
        }

    def specs(self) -> Params:
        return {
            "embedding": self.embedding.specs(),
            "pos_embedding": {"weight": P(None, None)},
            "layers": self._layer_specs(),
            "norm": self.final_norm.specs(),
        }

    # ---- what differs inside the forward (per-shard, inside shard_map) ----

    def _positions(self, params: Params, x: jax.Array,
                   position_ids: jax.Array, dtype):
        """The learned table's rows are added to the embedding; a layer
        gets nothing."""
        pos_emb = jnp.take(params["pos_embedding"]["weight"], position_ids,
                           axis=0, mode="clip")
        if self.sequence_parallel:
            # embedding output is seq-sharded; slice the position rows the
            # same way before the add
            tl = pos_emb.shape[1] // self.tp_size
            pos_emb = lax.dynamic_slice_in_dim(
                pos_emb, lax.axis_index("tp") * tl, tl, axis=1)
        return (x + pos_emb).astype(dtype), ()

    def _mlp(self, lp: Params, y: jax.Array, tp: TPSublayers,
             dtype) -> jax.Array:
        """proj(gelu_new(fc(y))): the tanh approximation, like GPT-2"""
        fc, = tp.columns(lp, ("fc",), y, dtype, **tp.ffn_order)
        fc = checkpoint_name(fc, "ffn_fc")
        return tp.row(lp, "proj", jax.nn.gelu(fc, approximate=True), dtype,
                      **tp.ffn_order)
