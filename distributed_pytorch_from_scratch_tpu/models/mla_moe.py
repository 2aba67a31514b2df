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
* **YaRN** where `cfg.latent_moe.rope_scaling` says so: the blended
  frequencies (`ops/rope.yarn_inv_freq`, `_positions`) and the softmax
  scale's mscale^2 (`parallel/mla.LatentAttention.softmax_scale`); None is
  plain RoPE, the tables computed as they always were;
* an untied head, RMSNorm (eps `rms_norm_eps`), no bias anywhere.

`models/mhc_mla_moe.py` subclasses this family with the residual state as
hyper-connection streams (`DecoderStack.stream_mixer`): the
multi-token-prediction module here norms and projects whatever `x` holds,
one stream or several.

What is not made to work is refused with a message: where the model is
built (`refuses`), by ZeRO 2/3 and the bucketed reducer
(`hand_reduced_grads`), by `models/decode.py` and the serving engines
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
from ..parallel.linear import ColumnParallelLinear, RowParallelLinear
from ..parallel.mla import LatentAttention, ReplicatedLinear
from ..parallel.moe import SharedRoutedFFN
from ..ops.rope import rope_angles
from ..parallel.norm import RMSNorm
from ..runtime.prng import fold
from .stack import (DecoderStack, Params, TPSublayers, _rows_in_order,
                    idle_expert_params)

ATTN = ("norm1", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo",
        "norm2")
DENSE = ATTN + ("gate_proj", "up_proj", "down_proj")
EXPERT = ATTN + ("moe",)


class MultiTokenPrediction:
    """The multi-token-prediction module (module docstring) of a family
    whose facts (`config_extra`) hold `num_nextn_predict_layers` (0 or 1)
    and `mtp_loss_weight` and whose `_segments` end in `mtp_layers`: its own
    leaves beside the stack's tree and `_extra_loss`. `models/kda_mla_moe.py`
    takes it too, and `models/ssm_moe.py`, whose module is two layers of one
    sublayer each under two keys (`_mtp_keys`)."""

    # the stacked keys the module's layers stand under, in the order they run
    _mtp_keys = ("mtp_layers",)

    @property
    def _facts(self):
        return getattr(self.cfg, self.config_extra)

    @functools.cached_property
    def eh_proj(self) -> ReplicatedLinear:
        return ReplicatedLinear(2 * self.d, self.d)

    # ---- the module's own leaves, beside the stack's tree ----

    def _init_more(self, key: jax.Array) -> Params:
        if not self._facts.num_nextn_predict_layers:
            return {}
        k = fold(key, "mtp")
        # (the module's streams leave through an exit mixer of its own)
        leave = self._exit_leaves(lambda m: m.init(fold(k, "hc_exit")))
        return {"mtp": {
            "hnorm": self.final_norm.init(k),
            "enorm": self.final_norm.init(k),
            "eh_proj": self.eh_proj.init(fold(k, "eh_proj")),
            "norm": self.final_norm.init(k), **leave,
        }}

    def _more_specs(self) -> Params:
        if not self._facts.num_nextn_predict_layers:
            return {}
        norm = self.final_norm.specs()
        return {"mtp": {"hnorm": norm, "enorm": norm,
                        "eh_proj": self.eh_proj.specs(), "norm": norm,
                        **self._exit_leaves(lambda m: m.specs())}}

    def _extra_loss(self, params: Params, loss: jax.Array, x: jax.Array,
                    aux, trunk, input_ids, target_ids, position_ids,
                    mode: str, batch_axes):
        counters = self._counters(aux, batch_axes)
        if not self._facts.num_nextn_predict_layers:
            return loss, counters
        with jax.named_scope("mtp"):
            mp = params["mtp"]
            # position i: h_i with the embedding of token i+1 (its target)
            # predicts token i+2 (the next position's target); the last
            # position has none, nor has one whose next token is ignored
            known = target_ids != IGNORE_INDEX
            nxt = self.embedding.apply(params["embedding"],
                                       jnp.where(known, target_ids, 0))
            # (of residual streams, each is normed by the one `hnorm` and
            # projected with the embedding by the one matrix; the module's
            # layer mixes them and they leave through its own exit mixer)
            h = self.final_norm.apply(mp["hnorm"], x)
            e = self.final_norm.apply(mp["enorm"], nxt.astype(trunk.dtype))
            h = jnp.concatenate([h, jnp.broadcast_to(e, h.shape)], axis=-1)
            h = self.eh_proj.apply(mp["eh_proj"], h, trunk.dtype)
            auxs = []
            for key in self._mtp_keys:
                h, aux = trunk.run(h, params[key])
                auxs.append(aux)
            mtp_aux = auxs[0] if len(auxs) == 1 else _rows_in_order(auxs)
            logits = self._head(params, mp["norm"], h, trunk.dtype,
                                scope=None, exit_params=mp.get("hc_exit"))
            after = jnp.concatenate(
                [target_ids[:, 1:],
                 jnp.full_like(target_ids[:, :1], IGNORE_INDEX)], axis=1)
            after = jnp.where(known, after, IGNORE_INDEX)
            token_loss, valid = self._token_ce(logits, after, mode)
            total = lax.psum(jnp.sum(jnp.where(valid, token_loss, 0.0)),
                             batch_axes)
            count = lax.psum(jnp.sum(valid.astype(jnp.float32)), batch_axes)
            mtp_loss = total / jnp.maximum(count, 1.0)
        mtp_aux = self._counters(mtp_aux, batch_axes)
        # (the module's layer counts what an expert layer counts; a row of
        # a counter only other layers keep, a mixer's, has none to add)
        counters = {k: (jnp.concatenate([counters[k], mtp_aux[k]])
                        if k in mtp_aux else counters[k])
                    for k in sorted(counters)}
        return (loss + self._facts.mtp_loss_weight * mtp_loss,
                {**counters, "loss_mtp": mtp_loss})


@dataclass(frozen=True)
class LatentMoETransformer(MultiTokenPrediction, DecoderStack):
    """The mla_moe family (module docstring)."""

    family = "mla_moe"
    ffn_inputs = 2            # gate and up both read the dense MLP's input
    tied_head = False
    decodable = False
    hand_reduced_grads = False
    config_extra = "latent_moe"
    attn_scope = "mla"
    _router_aux_losses = False
    refuses = {
        "pp_size > 1": "the pipeline splits one segment of identical "
                       "layers; this family has a layer pattern and a "
                       "multi-token-prediction module behind it",
        "cp_size > 1": "the multi-token-prediction targets shift across "
                       "sequence shards, and the ring kernels take one head "
                       "width",
        "ep_size > 1": "a job holds one share of the experts, "
                       "cfg.latent_moe.experts_held; the all-to-all between "
                       "shares is not written",
        "sequence_parallel=True": "the router and the latent projections "
                                  "read whole tokens",
        "attn_t_real": "pad tokens would be routed",
        "ZeRO stage 3": "",
    }

    def _check_facts(self):
        lm = self.cfg.latent_moe
        if not 0 <= lm.first_k_dense_replace < self.cfg.num_layers:
            raise ValueError(
                f"first_k_dense_replace {lm.first_k_dense_replace} must "
                f"leave an expert layer among {self.cfg.num_layers} layers")
        if lm.num_nextn_predict_layers not in (0, 1):
            raise ValueError("multi-token prediction is written for depth "
                             "0 or 1, got "
                             f"{lm.num_nextn_predict_layers}")
        if not self.owns_facts(self.cfg):
            raise ValueError(
                f"cfg.latent_moe.hyper is {lm.hyper}: the mhc_mla_moe "
                f"family carries hyper-connection streams and needs it, "
                f"the mla_moe family carries one and refuses it; this is "
                f"{self.family}")

    @classmethod
    def owns_facts(cls, cfg: ModelConfig) -> bool:
        """This family and its subclass with residual streams
        (models/mhc_mla_moe.py) both read `latent_moe`: the facts with
        `hyper` are the family's that carries streams."""
        return (cfg.latent_moe.hyper is not None) == (
            cls.stream_mixer is not None)

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

    held_beyond_d = 18.07       # set from cell 5's chip reading (below)

    @property
    def layer_extra_elems_per_token(self) -> float:
        """What a layer holds beside the d-wide tensors the dense skeleton
        counts: q and k at the wide head and v and the kernel's output at
        the narrow one, materialised per head (q/k alone are three times d
        here), and one chunk of the expert dispatch (its rows in and out
        and the experts' hidden activations; `SharedRoutedFFN.chunk_share`
        of a token's pairs); and `held_beyond_d` more d-wide tensors a
        token, which is what the chip counts beyond those and is SET FROM
        ITS READING (the shared expert's hidden activations, the latents'
        up-projections and the module's layer are among it; not told
        apart): cell 5 on a v5e counts 14.229 GiB at rung `true`, the rung
        `auto` picks there, for a step this makes 14.41 (ledger, PR 61; my
        chip run, PR 62; without the term it made 13.28)."""
        lm, moe = self.cfg.latent_moe, self._mods["moe"]
        attention = self.num_local_heads * 2.0 * (lm.qk_head_dim
                                                  + lm.v_head_dim)
        chunk_rows = moe.chunk_share * moe.top_k
        return attention + chunk_rows * (
            2 * self.d + 3 * lm.moe_intermediate_size / self.tp_size
            ) + self.held_beyond_d * self.d / self.tp_size

    # ---- sub-module definitions ----

    @functools.cached_property
    def attention(self) -> LatentAttention:
        lm = self.cfg.latent_moe
        scaling = lm.rope_scaling
        return LatentAttention(
            self.d, self.cfg.num_heads, lm.q_lora_rank, lm.kv_lora_rank,
            lm.qk_nope_head_dim, lm.qk_rope_head_dim, lm.v_head_dim,
            lm.rms_norm_eps,
            softmax_scale=scaling.softmax_scale if scaling else 1.0)

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

    # ---- what differs inside the forward (per-shard, inside shard_map) ----

    @property
    def v_head_dim(self) -> int:
        return self.cfg.latent_moe.v_head_dim

    @property
    def rotary_dim(self) -> int:
        return self.cfg.latent_moe.qk_rope_head_dim

    def _positions(self, params: Params, x: jax.Array,
                   position_ids: jax.Array, dtype):
        scaling = self.cfg.latent_moe.rope_scaling
        if scaling is None:
            return super()._positions(params, x, position_ids, dtype)
        return x.astype(dtype), rope_angles(
            position_ids, self.rotary_dim, self.cfg.rope_theta, scaling)

    def _qkv(self, lp: Params, y: jax.Array, tp: TPSublayers, layer_pos,
             dtype, b: int, t: int):
        return self.attention.qkv(self._mods, lp, y, *layer_pos, dtype)

    @staticmethod
    def param_counts(cfg: ModelConfig) -> Dict[str, int]:
        """The family's parameters by part (`DecoderStack.num_params`)."""
        lm = cfg.latent_moe
        d = cfg.attn_dim
        attn = LatentAttention(
            d, cfg.num_heads, lm.q_lora_rank, lm.kv_lora_rank,
            lm.qk_nope_head_dim, lm.qk_rope_head_dim,
            lm.v_head_dim).num_params() + 2 * d      # + the layer's 2 norms
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

    @staticmethod
    def flops_per_step(cfg, batch, seqlen, num_params) -> float:
        """The held experts at a token's mean share of them; the shared
        expert, the latent projections and the module are in `num_params`
        whole; q/k and v have their own widths; the embedding's lookup is
        no matmul but the head runs once more for the module."""
        lm = cfg.latent_moe
        expert_layers = (cfg.num_layers - lm.first_k_dense_replace
                         + lm.num_nextn_predict_layers)
        n = num_params - idle_expert_params(cfg, expert_layers,
                                            lm.moe_intermediate_size)
        n += (lm.num_nextn_predict_layers - 1) * cfg.vocab_size * cfg.attn_dim
        attn_layers = cfg.num_layers + lm.num_nextn_predict_layers
        return (6 * n * batch * seqlen
                + 6 * attn_layers * batch * cfg.num_heads * seqlen * seqlen
                * (lm.qk_head_dim + lm.v_head_dim))
