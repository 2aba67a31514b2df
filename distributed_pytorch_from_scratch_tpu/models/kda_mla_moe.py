"""The `kda_mla_moe` family: Kimi Delta Attention layers (a delta rule whose
decay is a CHANNEL'S, under a bounded gate) with one head-gated
latent-attention layer closing every group, leading dense layers, a sigmoid
router whose selection is limited to groups of experts, a shared expert and a
multi-token-prediction module (the Ling-3.0 architecture, `bailing_hybrid`),
on the same decoder stack as the other families.

`KdaMlaMoETransformer` is a subclass of `models/stack.DecoderStack` and holds
only what differs:

* **the mixers** hand back their sublayer's output themselves
  (`_mix_counted`: a layer's parameters hold no `wo` of the stack's, and a
  delta layer counts its decay). Layer `i` is latent
  attention where `(i + 1) % layer_group_size == 0` and Kimi Delta Attention
  elsewhere: `parallel/kda.KimiDeltaAttention` (around
  `ops/delta_rule.channel_delta_rule`, the chunked rule with a decay a
  channel) and `parallel/mla.LatentAttention` with NO q latent and a sigmoid
  gate a head before `wo` (interleaved RoPE on the rotary part of q and the
  one shared rotary key; the attention call is
  `ops/attention.causal_attention`, so the flash kernel at 192 / 128 on the
  TPU). The delta layers take no positions;
* **a pattern of two leading segments and a period**: the published first
  group holds the leading dense layers inside it, so the blocks are
  `dense_layers` (`first_k_dense_replace` delta layers with a dense SwiGLU),
  `lead_kda_layers` (the first group's other delta layers, with experts),
  `lead_mla_layers` (its latent layer), then ONE scan over the further
  groups, each `(kda_layers x (layer_group_size - 1), mla_layers x 1)`
  (`DecoderStack._scan_periods`): a cut to the first group and the
  published depth are the same program. With no dense layer every group is
  a period;
* **the expert FFN**: `parallel/moe.SharedRoutedFFN` with sigmoid scores,
  the selection bias's leaf (no rule moves it: the configuration publishes
  no speed), `n_group` groups of which a token keeps `topk_group`
  (`select`), the weights normalised over the chosen and scaled; the job
  holds `cfg.kda_mla_moe.experts_held` of the experts the router scores;
* **multi-token prediction**: `models/mla_moe.MultiTokenPrediction`, the
  third family's module, its layer a latent expert layer (`mtp_use_kda`
  false); the loss weight is a fact (published 0);
* an untied head, RMSNorm (eps `rms_norm_eps`), no bias anywhere.

A delta layer counts its decay, a row a layer beside the expert layers'
rows: `kda_g_min` (never under `kda_lower_bound`) and `kda_g_spread`
(`parallel/kda.py`); an expert layer counts `groups_hit` beside the rest.

What is not made to work is refused with a message: where the model is
built (`refuses`), by ZeRO 2/3 and the bucketed reducer
(`hand_reduced_grads`), by `models/decode.py` and the serving engines
(`decodable`: a recurrent state a channel and latent pages side by side are
not in `serving/kv_manager.py`).

Named scopes inside the step, for a device trace's `op_name`: `kda`
(`kda/gate`), `kda_rule` (`kda_rule/operands`, `kda_rule/walk`), `mla`
(`mla/gate`), `moe_route` (`moe_route/groups`), `moe_experts`, `moe_shared`
(parallel/moe.py), `dense_ffn` and `mtp`.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict

import jax
from jax import lax

from ..config import ModelConfig
from ..ops.delta_rule import rule_flops_per_token
from ..parallel.kda import KimiDeltaAttention
from ..parallel.linear import ColumnParallelLinear, RowParallelLinear
from ..parallel.mla import LatentAttention
from ..parallel.moe import SharedRoutedFFN
from ..parallel.norm import RMSNorm
from .mla_moe import MultiTokenPrediction
from .stack import (DecoderStack, Params, TPSublayers, idle_expert_params)

FFN = ("gate_proj", "up_proj", "down_proj")
DENSE_KDA = ("norm1", "kda", "norm2") + FFN
EXPERT_KDA = ("norm1", "kda", "norm2", "moe")
EXPERT_MLA = ("norm1", "mla", "norm2", "moe")


def layer_counts(cfg: ModelConfig) -> Dict[str, int]:
    """Layers by parameter key, from the facts (the module's layer too)."""
    km = cfg.kda_mla_moe
    group, first = km.layer_group_size, km.first_k_dense_replace
    groups = cfg.num_layers // group
    lead = 1 if first else 0        # groups the leading segments hold
    return {"dense_layers": first,
            "lead_kda_layers": lead * (group - 1 - first),
            "lead_mla_layers": lead,
            "kda_layers": (groups - lead) * (group - 1),
            "mla_layers": groups - lead,
            "mtp_layers": km.num_nextn_predict_layers}


@dataclass(frozen=True)
class KdaMlaMoETransformer(MultiTokenPrediction, DecoderStack):
    """The kda_mla_moe family (module docstring)."""

    family = "kda_mla_moe"
    ffn_inputs = 2            # gate and up both read the dense MLP's input
    tied_head = False
    decodable = False
    hand_reduced_grads = False
    config_extra = "kda_mla_moe"
    _router_aux_losses = False
    # the decay's rows are a minimum and a mean over the tokens
    _counter_reduces = {"kda_g_min": lax.pmin, "kda_g_spread": lax.pmean}
    refuses = {
        "pp_size > 1": "the pipeline splits one segment of identical "
                       "layers; this family has two kinds of mixer, a "
                       "layer pattern and a multi-token-prediction module "
                       "behind it",
        "cp_size > 1": "the delta rule's state and the convolutions' taps "
                       "run along the whole sequence; no hand-over of either "
                       "between sequence shards is written",
        "ep_size > 1": "a job holds one share of the experts, "
                       "cfg.kda_mla_moe.experts_held; the all-to-all between "
                       "shares is not written",
        "sequence_parallel=True": "the router, the convolutions and the "
                                  "rule read whole sequences",
        "attn_t_real": "pad tokens would be routed and would move the state",
        "ZeRO stage 3": "",
    }

    def _check_facts(self):
        km = self.cfg.kda_mla_moe
        group = km.layer_group_size
        if group < 2 or self.cfg.num_layers % group:
            raise ValueError(
                f"num_layers {self.cfg.num_layers} must be whole groups of "
                f"layer_group_size {group} (>= 2) layers")
        if not 0 <= km.first_k_dense_replace < group:
            raise ValueError(
                f"first_k_dense_replace {km.first_k_dense_replace} must "
                f"leave the first group's latent layer its experts (a group "
                f"is {group} layers)")
        if km.num_nextn_predict_layers not in (0, 1):
            raise ValueError("multi-token prediction is written for depth "
                             f"0 or 1, got {km.num_nextn_predict_layers}")
        # (the mixer refuses a gate's bound its sub-blocks cannot hold)
        KimiDeltaAttention(self.d, self.cfg.num_heads, km.head_dim,
                           km.head_dim, lower_bound=km.kda_lower_bound)

    # ---- the layer pattern ----

    @property
    def _pattern(self):
        """The first group's segments where it holds dense layers, then one
        period that repeats."""
        n = layer_counts(self.cfg)
        group = self.cfg.kda_mla_moe.layer_group_size
        lead = tuple(key for key in ("dense_layers", "lead_kda_layers",
                                     "lead_mla_layers") if n[key])
        period = ((("kda_layers", group - 1), ("mla_layers", 1)),)
        return lead + (period if n["mla_layers"] else ())

    @property
    def _segments(self):
        """(parameter key, layers, module names) of every stacked key, the
        multi-token-prediction module's layer last."""
        n = layer_counts(self.cfg)
        names = {"dense_layers": DENSE_KDA, "lead_kda_layers": EXPERT_KDA,
                 "lead_mla_layers": EXPERT_MLA, "kda_layers": EXPERT_KDA,
                 "mla_layers": EXPERT_MLA, "mtp_layers": EXPERT_MLA}
        return tuple((key, n[key], names[key]) for key in names if n[key])

    # ---- facts for training/memory.py ----

    @property
    def stacked_layers(self) -> int:
        return (self.cfg.num_layers
                + self.cfg.kda_mla_moe.num_nextn_predict_layers)

    @property
    def head_dim(self) -> int:       # the delta layers' heads
        return self.cfg.kda_mla_moe.head_dim

    @property
    def tagged_layers(self) -> Dict[str, int]:
        """A delta layer tags its q, k and v projections with the ladder's
        names before their convolutions (parallel/kda.py), each at the
        heads' whole width; the latent layer's carry none."""
        delta = sum(layers for _, layers, names in self._segments
                    if "kda" in names)
        return {**super().tagged_layers,
                "q_proj": delta, "k_proj": delta, "v_proj": delta}

    @property
    def layer_extra_elems_per_token(self) -> float:
        """What a delta layer's backward holds at its fullest, beside the
        d-wide tensors the dense skeleton counts, in elements of the compute
        dtype a token. The mixer has no checkpoint of its own
        (`KimiDeltaAttention.apply`), so what the layer's recompute made of
        the rule's inputs stands until its transpose is reached, through the
        rule's backward (which runs a sequence at a time): the four
        projections q, k, v and the decay's, the three convolutions' float32
        sums (two elements a channel), q, k and v by head, the float32 decay
        (two elements a channel: d_k a head and token, where the third
        family's rule holds one scalar), the cotangents of those four as the
        rule's backward hands them over, and the output gate; and one chunk
        of the expert dispatch (`SharedRoutedFFN.chunk_share` of a token's
        pairs). The latent layer holds less. The last term takes 31.87 d a
        token back off and is SET FROM THE CHIP'S READING, which is barely
        over state, gradients and the layers' weights in bfloat16 (12.67
        GiB: what the estimate calls `cast` is not all held at once here,
        and the rule's kernels make their operands in VMEM; not told apart,
        PERF.md section 7): cell 12 on a v5e counts 12.937 GiB at rung
        `true`, 12.940 at `flash` and 13.400 at `dots`, the rung `auto`
        picks, for steps this makes 13.11, 13.23 and 13.70 (my chip runs, PR
        64: the floor's count + 1.3%, as PR 62 set it; without the term
        `true` made 13.73)."""
        km, moe = self.cfg.kda_mla_moe, self._mods["moe"]
        wide = self.num_local_heads * km.head_dim
        rule_inputs = (4 * wide              # the four projections
                       + 2 * 3 * wide        # the convolutions' sums
                       + 3 * wide            # q, k, v by head
                       + 2 * wide            # the float32 decay
                       + (3 + 2) * wide      # the rule's inputs' cotangents
                       + wide)               # the output gate
        chunk_rows = moe.chunk_share * moe.top_k
        return rule_inputs + chunk_rows * (
            2 * self.d + 3 * km.moe_intermediate_size / self.tp_size
            ) - 31.87 * self.d / self.tp_size

    # ---- sub-module definitions ----

    @functools.cached_property
    def _mods(self) -> Dict[str, Any]:
        cfg, km = self.cfg, self.cfg.kda_mla_moe
        d, f = self.d, cfg.ffn_dim
        col = functools.partial(ColumnParallelLinear, add_bias=False,
                                gather_output=False)
        return {
            "norm1": RMSNorm(d, km.rms_norm_eps),
            "norm2": RMSNorm(d, km.rms_norm_eps),
            "kda": KimiDeltaAttention(
                d, cfg.num_heads, km.head_dim, km.head_dim,
                km.short_conv_kernel_size, km.kda_lower_bound,
                km.rms_norm_eps, tp_size=self.tp_size),
            "mla": LatentAttention(
                d, cfg.num_heads, km.q_lora_rank, km.kv_lora_rank,
                km.qk_nope_head_dim, km.qk_rope_head_dim, km.v_head_dim,
                km.rms_norm_eps, head_gate=True),
            "gate_proj": col(d, f),
            "up_proj": col(d, f),
            "down_proj": RowParallelLinear(f, d, add_bias=False,
                                           split_input=False),
            "moe": SharedRoutedFFN(
                d, km.moe_intermediate_size, cfg.num_experts,
                top_k=cfg.moe_top_k, held=km.experts_held,
                offset=km.expert_offset, n_shared=km.n_shared_experts,
                scaling=km.routed_scaling_factor, tp_size=self.tp_size,
                n_group=km.n_group, topk_group=km.topk_group),
        }

    # ---- what differs inside the forward (per-shard, inside shard_map) ----

    @property
    def v_head_dim(self) -> int:
        return self.cfg.kda_mla_moe.v_head_dim

    @property
    def rotary_dim(self) -> int:     # the latent layers read it
        return self.cfg.kda_mla_moe.qk_rope_head_dim

    def _mix_counted(self, lp: Params, y: jax.Array, layer_pos, dtype):
        if "kda" in lp:     # (output, the decay's counters)
            return self._mods["kda"].apply(lp["kda"], y, dtype)
        return self._mods["mla"].apply(lp["mla"], y, *layer_pos, dtype,
                                       attn_impl=self.attn_impl), None

    def _mlp(self, lp: Params, y: jax.Array, tp: TPSublayers,
             dtype) -> jax.Array:
        with jax.named_scope("dense_ffn"):
            return super()._mlp(lp, y, tp, dtype)

    @staticmethod
    def param_counts(cfg: ModelConfig) -> Dict[str, int]:
        """The family's parameters by part (`DecoderStack.num_params`)."""
        km = cfg.kda_mla_moe
        d, n = cfg.attn_dim, layer_counts(cfg)
        kda = KimiDeltaAttention(
            d, cfg.num_heads, km.head_dim, km.head_dim,
            km.short_conv_kernel_size).num_params() + 2 * d  # + its 2 norms
        mla = LatentAttention(
            d, cfg.num_heads, km.q_lora_rank, km.kv_lora_rank,
            km.qk_nope_head_dim, km.qk_rope_head_dim, km.v_head_dim,
            head_gate=True).num_params() + 2 * d
        expert = 3 * d * km.moe_intermediate_size
        ffn = (d * cfg.num_experts + cfg.num_experts       # router, bias
               + (cfg.experts_held + km.n_shared_experts) * expert)
        return {
            "embedding_and_head": 2 * cfg.vocab_size * d,
            "final_norm": d,
            "dense_layers": n["dense_layers"] * (kda + 3 * d * cfg.ffn_dim),
            "kda_expert_layers": (n["lead_kda_layers"] + n["kda_layers"])
            * (kda + ffn),
            "mla_expert_layers": (n["lead_mla_layers"] + n["mla_layers"])
            * (mla + ffn),
            "mtp": n["mtp_layers"] * (mla + ffn + 2 * d * d + 3 * d),
        }

    @staticmethod
    def flops_per_step(cfg, batch, seqlen, num_params) -> float:
        """The held experts at a token's mean share of them; the embedding's
        lookup is no matmul but the head runs once more for the module;
        attention at the causal T^2 / 2 in the latent layers only (q/k and v
        at their own widths, as `mla_moe` counts them); the chunked rule's
        own products (`ops/delta_rule.rule_flops_per_token`: the decay a
        channel changes no product's size), forward and twice that
        backward."""
        km, n = cfg.kda_mla_moe, layer_counts(cfg)
        expert_layers = (cfg.num_layers - n["dense_layers"]
                         + n["mtp_layers"])
        params = num_params - idle_expert_params(cfg, expert_layers,
                                                 km.moe_intermediate_size)
        params += (n["mtp_layers"] - 1) * cfg.vocab_size * cfg.attn_dim
        latent = n["lead_mla_layers"] + n["mla_layers"] + n["mtp_layers"]
        rule = cfg.num_heads * rule_flops_per_token(km.head_dim, km.head_dim)
        return (6 * params * batch * seqlen
                + 6 * latent * batch * cfg.num_heads * seqlen * seqlen
                * (km.qk_head_dim + km.v_head_dim)
                + 3 * (cfg.num_layers - latent + n["mtp_layers"]) * rule
                * batch * seqlen)
