"""The `mla_moe` family: latent attention, a sigmoid-routed expert FFN with a
shared expert, leading dense layers and a multi-token-prediction module
(the DeepSeek-V3 architecture, which JoyAI-LLM-Flash's `config.json` also
describes), on the same decoder stack as the llama and GPT-2 families.

`LatentMoETransformer` is a subclass of `models/stack.DecoderStack` and
holds only what differs:

* **attention**: `parallel/mla.LatentAttention` (`_qkv`), interleaved RoPE on
  the rotary part of q and on the one shared rotary key; q/k and v of
  different widths go to the same attention kernels;
* **a layer pattern**: `cfg.latent_moe.first_k_dense_replace` leading layers
  with a dense SwiGLU (`params["dense_layers"]`), then expert layers
  (`params["layers"]`), one scan each over one `_layer_body`; what a layer's
  FFN is follows from what its parameters hold (`_ffn`);
* **the expert FFN**: `parallel/moe.SharedRoutedFFN`: the router scores all
  `cfg.num_experts` routed experts, the job holds
  `cfg.latent_moe.experts_held` of them (one chip's share of an
  expert-parallel deployment; None = all), no token is dropped, no
  auxiliary loss;
* **multi-token prediction** (DeepSeek-V3 report, section 2.2), depth
  `num_nextn_predict_layers` (0 or 1): `h' = W_eh [RMSNorm(h_i) ;
  RMSNorm(Emb(t_{i+1}))]`, one more expert layer with its own weights, its
  own final norm, the main model's embedding and head, predicting
  `t_{i+2}`; `loss = CE_main + mtp_loss_weight * CE_mtp` (`_extra_loss`,
  under the scope `mtp`). `h_i` is the last layer's output BEFORE the main
  final norm (the report's output head holds that norm), and the hidden
  state comes first in the concatenation (the report's order);
* an untied head, RMSNorm (eps `rms_norm_eps`), no bias anywhere.

What is not made to work is refused where the model is built, with a
message: pp > 1, cp > 1, ep > 1 (a job holds ONE share; the all-to-all
between shares is not written), sequence parallelism and its rings,
pad-aware bucketing, ZeRO 2/3 and the bucketed reducer
(`hand_reduced_grads`), `models/decode.py` and the serving engine
(`decodable`).

Named scopes inside the step, for a device trace's `op_name`: `mla`
(projections, latent norms, RoPE, the output projection), `moe_route`,
`moe_experts`, `moe_shared` (parallel/moe.py) and `mtp`.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from ..config import IGNORE_INDEX, ModelConfig
from ..ops.rope import rope_angles
from ..parallel.embedding import VocabParallelEmbedding
from ..parallel.linear import ColumnParallelLinear, RowParallelLinear
from ..parallel.mla import LatentAttention, ReplicatedLinear
from ..parallel.moe import SharedRoutedFFN
from ..parallel.norm import RMSNorm
from ..runtime.prng import fold
from .stack import DecoderStack, Params, TPSublayers
from .transformer import Transformer

ATTN = ("norm1", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo",
        "norm2")
DENSE = ATTN + ("gate_proj", "up_proj", "down_proj")
EXPERT = ATTN + ("moe",)


@dataclass(frozen=True)
class LatentMoETransformer(DecoderStack):
    """The mla_moe family (module docstring)."""

    uses_rope = True
    attn_norm_key = "norm1"
    ffn_norm_key = "norm2"
    ffn_inputs = 2            # gate and up both read the dense MLP's input
    tied_head = False
    decodable = False
    hand_reduced_grads = False
    config_extra = "latent_moe"
    _router_aux_losses = False

    def __post_init__(self):
        lm = self.cfg.latent_moe
        if lm is None:
            raise ValueError("the mla_moe family needs cfg.latent_moe "
                             "(config.LatentMoEConfig)")
        if not self.cfg.num_experts:
            raise ValueError("the mla_moe family needs cfg.num_experts > 0 "
                             "(the routed experts its router scores)")
        if not 0 <= lm.first_k_dense_replace < self.cfg.num_layers:
            raise ValueError(
                f"first_k_dense_replace {lm.first_k_dense_replace} must "
                f"leave an expert layer among {self.cfg.num_layers} layers")
        if lm.num_nextn_predict_layers not in (0, 1):
            raise ValueError("multi-token prediction is written for depth "
                             "0 or 1, got "
                             f"{lm.num_nextn_predict_layers}")
        refused = [
            (self.pp_size > 1, "pp_size > 1 (the pipeline splits one "
             "segment of identical layers; this family has a layer pattern "
             "and a multi-token-prediction module behind it)"),
            (self.cp_size > 1, "cp_size > 1 (the multi-token-prediction "
             "targets shift across sequence shards, and the ring kernels "
             "take one head width)"),
            (self.ep_size > 1, "ep_size > 1 (a job holds one share of the "
             "experts, cfg.latent_moe.experts_held; the all-to-all between "
             "shares is not written)"),
            (self.sequence_parallel is True, "sequence_parallel=True (the "
             "router and the latent projections read whole tokens)"),
            (self.attn_t_real is not None, "attn_t_real (pad tokens would "
             "be routed)"),
            (self.zero3_axis is not None, "ZeRO stage 3"),
        ]
        for bad, what in refused:
            if bad:
                raise ValueError(f"the mla_moe family does not run with "
                                 f"{what}")
        super().__post_init__()

    # ---- the layer pattern ----

    @property
    def _pattern(self):
        """Two segments: the leading dense layers, the expert layers."""
        first = self.cfg.latent_moe.first_k_dense_replace
        return ("dense_layers", "layers") if first else ("layers",)

    @property
    def _segments(self):
        """(parameter key, layers, module names) of every stacked segment,
        the multi-token-prediction module's layer last."""
        lm = self.cfg.latent_moe
        first = lm.first_k_dense_replace
        segs = [("dense_layers", first, DENSE)] if first else []
        segs.append(("layers", self.cfg.num_layers - first, EXPERT))
        if lm.num_nextn_predict_layers:
            segs.append(("mtp_layers", lm.num_nextn_predict_layers, EXPERT))
        return tuple(segs)

    # ---- facts for training/memory.py ----

    @property
    def stacked_layers(self) -> int:
        return (self.cfg.num_layers
                + self.cfg.latent_moe.num_nextn_predict_layers)

    @property
    def layer_extra_elems_per_token(self) -> float:
        """What a layer holds beside the d-wide tensors the dense skeleton
        counts: q and k at the wide head and v and the kernel's output at
        the narrow one, materialised per head (q/k alone are three times d
        here), and one chunk of the expert dispatch (its rows in and out
        and the experts' hidden activations; `SharedRoutedFFN.chunk_share`
        of a token's pairs). One reading: the benchmark's cell on a v5e counts 14.38
        GiB for a step this makes 13.99 (PERF.md section 5, PR 33)."""
        lm, moe = self.cfg.latent_moe, self._mods["moe"]
        attention = self.num_local_heads * 2.0 * (lm.qk_head_dim
                                                  + lm.v_head_dim)
        chunk_rows = moe.chunk_share * moe.top_k
        return attention + chunk_rows * (
            2 * self.d + 3 * lm.moe_intermediate_size / self.tp_size)

    # ---- sub-module definitions ----

    @functools.cached_property
    def attention(self) -> LatentAttention:
        lm = self.cfg.latent_moe
        return LatentAttention(
            self.d, self.cfg.num_heads, lm.q_lora_rank, lm.kv_lora_rank,
            lm.qk_nope_head_dim, lm.qk_rope_head_dim, lm.v_head_dim,
            lm.rms_norm_eps)

    @functools.cached_property
    def embedding(self) -> VocabParallelEmbedding:
        return VocabParallelEmbedding(self.cfg.vocab_size, self.d,
                                      tp_size=self.tp_size)

    @functools.cached_property
    def _mods(self) -> Dict[str, Any]:
        cfg, lm = self.cfg, self.cfg.latent_moe
        d, f = self.d, cfg.ffn_dim
        col = functools.partial(ColumnParallelLinear, add_bias=False,
                                gather_output=False)
        return {
            **self.attention.modules(),
            "norm1": RMSNorm(d, lm.rms_norm_eps),
            "norm2": RMSNorm(d, lm.rms_norm_eps),
            "gate_proj": col(d, f),
            "up_proj": col(d, f),
            "down_proj": RowParallelLinear(f, d, add_bias=False,
                                           split_input=False),
            "moe": SharedRoutedFFN(
                d, lm.moe_intermediate_size, cfg.num_experts,
                top_k=cfg.moe_top_k, held=lm.experts_held,
                offset=lm.expert_offset, n_shared=lm.n_shared_experts,
                scaling=lm.routed_scaling_factor, tp_size=self.tp_size),
        }

    @functools.cached_property
    def final_norm(self) -> RMSNorm:
        return RMSNorm(self.d, self.cfg.latent_moe.rms_norm_eps)

    @functools.cached_property
    def lm_head(self) -> ColumnParallelLinear:
        return ColumnParallelLinear(self.d, self.vocab_padded,
                                    add_bias=False, gather_output=False)

    @functools.cached_property
    def eh_proj(self) -> ReplicatedLinear:
        return ReplicatedLinear(2 * self.d, self.d)

    # ---- init / specs ----

    def init(self, key: jax.Array) -> Params:
        lm_head = self.lm_head.init(fold(key, "lm_head"))
        if self.vocab_padded != self.cfg.vocab_size:
            keep = jnp.arange(self.vocab_padded) < self.cfg.vocab_size
            lm_head["weight"] = jnp.where(keep[None, :], lm_head["weight"],
                                          0.0)
        params = {
            "embedding": self.embedding.init(fold(key, "embedding")),
            **{name: self._init_layers(key, name, count, names)
               for name, count, names in self._segments},
            "norm": self.final_norm.init(fold(key, "norm")),
            "lm_head": lm_head,
        }
        if self.cfg.latent_moe.num_nextn_predict_layers:
            k = fold(key, "mtp")
            params["mtp"] = {
                "hnorm": self.final_norm.init(k),
                "enorm": self.final_norm.init(k),
                "eh_proj": self.eh_proj.init(fold(k, "eh_proj")),
                "norm": self.final_norm.init(k),
            }
        return params

    def specs(self) -> Params:
        specs = {
            "embedding": self.embedding.specs(),
            **{name: self._layer_specs(names)
               for name, _, names in self._segments},
            "norm": self.final_norm.specs(),
            "lm_head": self.lm_head.specs(),
        }
        if self.cfg.latent_moe.num_nextn_predict_layers:
            norm = self.final_norm.specs()
            specs["mtp"] = {"hnorm": norm, "enorm": norm,
                            "eh_proj": self.eh_proj.specs(), "norm": norm}
        return specs

    @staticmethod
    def num_params(cfg: ModelConfig) -> int:
        return sum(param_counts(cfg).values())

    # ---- what differs inside the forward (per-shard, inside shard_map) ----

    def _positions(self, params: Params, x: jax.Array,
                   position_ids: jax.Array, dtype):
        """Nothing enters at the embedding; every layer gets the rotary
        pairs' (cos, sin) at `position_ids`."""
        lm = self.cfg.latent_moe
        return x.astype(dtype), rope_angles(
            position_ids, lm.qk_rope_head_dim, self.cfg.rope_theta)

    def _qkv(self, lp: Params, y: jax.Array, tp: TPSublayers, layer_pos,
             dtype, b: int, t: int):
        with jax.named_scope("mla"):
            return self.attention.qkv(self._mods, lp, y, *layer_pos, dtype)

    def _attn_project(self, lp: Params, o: jax.Array, tp: TPSublayers,
                      dtype) -> jax.Array:
        with jax.named_scope("mla"):
            return tp.row(lp, "wo", o, dtype)

    _mlp = Transformer._mlp                 # the dense layers' SwiGLU
    _head_logits = Transformer._head_logits

    def _ffn(self, lp: Params, y: jax.Array, tp: TPSublayers, dtype):
        if "moe" in lp:
            return self._mods["moe"].apply(lp["moe"], y, dtype)
        return self._mlp(lp, y, tp, dtype), None

    def _fold_aux(self, auxs):
        # the expert layers' counters stay one row a layer
        return auxs

    def _extra_loss(self, params: Params, loss: jax.Array, x: jax.Array,
                    aux, trunk, input_ids, target_ids, position_ids,
                    mode: str, batch_axes):
        counters = jax.tree.map(lambda a: lax.psum(a, batch_axes), aux)
        if not self.cfg.latent_moe.num_nextn_predict_layers:
            return loss, counters
        with jax.named_scope("mtp"):
            mp = params["mtp"]
            # position i: h_i with the embedding of token i+1 (its target)
            # predicts token i+2 (the next position's target); the last
            # position has none, nor has one whose next token is ignored
            known = target_ids != IGNORE_INDEX
            nxt = self.embedding.apply(params["embedding"],
                                       jnp.where(known, target_ids, 0))
            h = jnp.concatenate(
                [self.final_norm.apply(mp["hnorm"], x),
                 self.final_norm.apply(mp["enorm"], nxt.astype(trunk.dtype))],
                axis=-1)
            h = self.eh_proj.apply(mp["eh_proj"], h, trunk.dtype)
            h, mtp_aux = trunk.run(h, params["mtp_layers"])
            logits = self._head(params, mp["norm"], h, trunk.dtype,
                                scope=None)
            after = jnp.concatenate(
                [target_ids[:, 1:],
                 jnp.full_like(target_ids[:, :1], IGNORE_INDEX)], axis=1)
            after = jnp.where(known, after, IGNORE_INDEX)
            token_loss, valid = self._token_ce(logits, after, mode)
            total = lax.psum(jnp.sum(jnp.where(valid, token_loss, 0.0)),
                             batch_axes)
            count = lax.psum(jnp.sum(valid.astype(jnp.float32)), batch_axes)
            mtp_loss = total / jnp.maximum(count, 1.0)
        mtp_aux = jax.tree.map(lambda a: lax.psum(a, batch_axes), mtp_aux)
        counters = jax.tree.map(lambda a, m: jnp.concatenate([a, m]),
                                counters, mtp_aux)
        return (loss + self.cfg.latent_moe.mtp_loss_weight * mtp_loss,
                {**counters, "loss_mtp": mtp_loss})


def param_counts(cfg: ModelConfig) -> Dict[str, int]:
    """The family's parameters by part, as `init` makes them for `cfg` (the
    experts HELD, not the routed total): what `num_params` sums, and what
    the benchmark's own count is pinned against."""
    lm = cfg.latent_moe
    d = cfg.attn_dim
    attn = LatentAttention(
        d, cfg.num_heads, lm.q_lora_rank, lm.kv_lora_rank,
        lm.qk_nope_head_dim, lm.qk_rope_head_dim,
        lm.v_head_dim).num_params() + 2 * d          # + the layer's 2 norms
    expert = 3 * d * lm.moe_intermediate_size
    expert_layer = (attn + d * cfg.num_experts + cfg.num_experts
                    + (cfg.experts_held + lm.n_shared_experts) * expert)
    first = lm.first_k_dense_replace
    return {
        "embedding_and_head": 2 * cfg.vocab_size * d,
        "final_norm": d,
        "dense_layers": first * (attn + 3 * d * cfg.ffn_dim),
        "expert_layers": (cfg.num_layers - first) * expert_layer,
        "mtp": lm.num_nextn_predict_layers * (expert_layer + 2 * d * d
                                              + 3 * d),
    }
