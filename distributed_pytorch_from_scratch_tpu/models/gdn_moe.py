"""The `gdn_moe` family: Gated DeltaNet linear-attention layers with one gated
grouped-query full-attention layer closing every period, and a routed expert
FFN with a gated shared expert in every layer (the Qwen3-Next architecture),
on the same decoder stack as the other families.

`GdnMoETransformer` is a subclass of `models/stack.DecoderStack` and holds
only what differs:

* **a pattern that repeats**: a period is `full_attention_interval - 1`
  Gated DeltaNet layers (`params["gdn_layers"]`) then one full-attention
  layer (`params["attn_layers"]`), each stacked (periods, layers a period,
  ...); the stack runs ONE scan over periods (`DecoderStack._scan_periods`)
  whose body scans the period's layers through the one layer skeleton and
  the one remat policy. Layer `i` is full attention where `(i + 1) %
  interval == 0`;
* **the mixers** hand back their sublayer's output themselves (`_mix`: a
  layer's parameters hold no `wo` of the stack's): `parallel/gdn.GatedDeltaNet` (the chunked gated delta rule
  of ops/delta_rule.py) and `parallel/gated_attention.GatedAttention` (q/k
  norms per head, RoPE on the leading quarter of a head, a sigmoid output
  gate; the attention call itself is `ops/attention.causal_attention`, so
  the flash kernel with its native grouping on the TPU);
* **the expert FFN**: `parallel/moe.SharedRoutedFFN` with softmax scores
  and a gated shared expert; the router scores all `cfg.num_experts`, the
  job holds `cfg.gdn_moe.experts_held` of them (one chip's share of an
  expert-parallel deployment; None = all); no token is dropped, no
  auxiliary loss, no selection bias;
* zero-centred RMSNorm (`x / rms * (1 + w)`) for both layer norms, the
  final norm and the q/k norms; an untied head; no bias anywhere; no
  multi-token-prediction module.

What is not made to work is refused with a message: where the model is
built (`refuses`), by ZeRO 2/3 and the bucketed reducer
(`hand_reduced_grads`), by `models/decode.py` and the serving engines
(`decodable`: a recurrent state is not in `serving/kv_manager.py`).

Named scopes inside the step, for a device trace's `op_name`: `gdn`,
`gdn_rule`, `gated_attn`, and `moe_route`, `moe_experts`, `moe_shared`
(parallel/moe.py).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict

import jax

from ..config import ModelConfig
from ..ops.attention import causal_attention
from ..ops.delta_rule import rule_flops_per_token
from ..parallel.gated_attention import GatedAttention
from ..parallel.gdn import GatedDeltaNet
from ..parallel.moe import SharedRoutedFFN
from ..parallel.norm import ZeroCenteredRMSNorm
from .stack import DecoderStack, Params, idle_expert_params

LINEAR = ("norm1", "gdn", "norm2", "moe")
FULL = ("norm1", "attn", "norm2", "moe")


@dataclass(frozen=True)
class GdnMoETransformer(DecoderStack):
    """The gdn_moe family (module docstring)."""

    family = "gdn_moe"
    ffn_inputs = 0            # no dense MLP: every layer's FFN is routed
    tied_head = False
    decodable = False
    hand_reduced_grads = False
    config_extra = "gdn_moe"
    _router_aux_losses = False
    refuses = {
        "pp_size > 1": "the pipeline splits one segment of identical "
                       "layers; this family scans periods of two kinds of "
                       "layer",
        "cp_size > 1": "the delta rule's state and the convolution's taps "
                       "run along the whole sequence; no exchange of either "
                       "between sequence shards is written",
        "ep_size > 1": "a job holds one share of the experts, "
                       "cfg.gdn_moe.experts_held; the all-to-all between "
                       "shares is not written",
        "sequence_parallel=True": "the router, the convolution and the "
                                  "rule read whole sequences",
        "attn_t_real": "pad tokens would be routed and would move the state",
        "ZeRO stage 3": "",
    }

    def _check_facts(self):
        gm = self.cfg.gdn_moe
        if (gm.full_attention_interval < 2
                or self.cfg.num_layers % gm.full_attention_interval):
            raise ValueError(
                f"num_layers {self.cfg.num_layers} must be whole periods of "
                f"full_attention_interval {gm.full_attention_interval} "
                f"(>= 2) layers")
        if gm.shared_expert_intermediate_size % gm.moe_intermediate_size:
            raise ValueError(
                "the shared expert's width must be a multiple of a routed "
                "expert's")

    # ---- the layer pattern ----

    @property
    def _pattern(self):
        """One period that repeats."""
        return ((("gdn_layers", self.cfg.gdn_moe.full_attention_interval - 1),
                 ("attn_layers", 1)),)

    @property
    def periods(self) -> int:
        return self.cfg.num_layers // self.cfg.gdn_moe.full_attention_interval

    @property
    def _segments(self):
        """(parameter key, layers, module names) of both stacked segments."""
        return tuple((key, self.periods * n, names) for (key, n), names
                     in zip(self._pattern[0], (LINEAR, FULL)))

    # ---- facts for training/memory.py ----

    @property
    def head_dim(self) -> int:       # the full-attention layers' heads
        return self.cfg.gdn_moe.head_dim

    @property
    def tagged_layers(self) -> Dict[str, float]:
        """The gated attention's `q_proj` is `[q | gate]` of one projection
        (parallel/gated_attention.py): twice the heads' width a layer."""
        tagged = super().tagged_layers
        return {**tagged, "q_proj": 2 * tagged["q_proj"]}

    @property
    def layer_extra_elems_per_token(self) -> float:
        """What a Gated DeltaNet layer's backward holds at its fullest,
        beside the d-wide tensors the dense skeleton counts, in elements of
        the compute dtype a token: the 2 (d_k H_k + d_v H_v)-wide projection
        and its cotangent, the convolution's float32 sums (two elements a
        channel), q, k, v and z at the value heads, and one chunk of the
        expert dispatch (`SharedRoutedFFN.chunk_share` of a token's pairs:
        rows in and out and the hidden activations). That is the pass that
        makes the rule's inputs again (`GatedDeltaNet.apply` keeps it apart
        from the rule's own backward, which runs a sequence at a time and
        holds 2 GB whatever the batch); the full-attention layer holds
        less. The last term, 22.63 d a token, is what the chip counts
        beyond those and is SET FROM ITS READING (the rule's own backward,
        a sequence at a time, is most of it; not told apart): cell 6 on a
        v5e counts 14.110 GiB at rung `true` and 14.110 at `flash`, the
        rung `auto` picks, for steps this makes 14.29 and 14.42 (ledger, PR
        61; my chip runs, PR 62; without the term `true` made 12.88)."""
        gm, gdn, moe = self.cfg.gdn_moe, self._mods["gdn"], self._mods["moe"]
        hk = gm.linear_num_key_heads / self.tp_size
        hv = gm.linear_num_value_heads / self.tp_size
        dk, dv = gm.linear_key_head_dim, gm.linear_value_head_dim
        rule_inputs = (2 * hk * gdn.head_columns + 2 * hk * gdn.conv_channels
                       + hv * (2 * dk + 2 * dv))
        chunk_rows = moe.chunk_share * moe.top_k
        return rule_inputs + chunk_rows * (
            2 * self.d + 3 * gm.moe_intermediate_size / self.tp_size
            ) + 22.63 * self.d / self.tp_size

    # ---- sub-module definitions ----

    def _norm(self) -> ZeroCenteredRMSNorm:
        return ZeroCenteredRMSNorm(self.d, self.cfg.gdn_moe.rms_norm_eps)

    @functools.cached_property
    def _mods(self) -> Dict[str, Any]:
        cfg, gm = self.cfg, self.cfg.gdn_moe
        return {
            "norm1": self._norm(),
            "norm2": self._norm(),
            "gdn": GatedDeltaNet(
                self.d, gm.linear_num_key_heads, gm.linear_num_value_heads,
                gm.linear_key_head_dim, gm.linear_value_head_dim,
                gm.linear_conv_kernel_dim, gm.rms_norm_eps,
                tp_size=self.tp_size),
            "attn": GatedAttention(
                self.d, cfg.num_heads, cfg.kv_heads, gm.head_dim,
                gm.rotary_dim, gm.rms_norm_eps, tp_size=self.tp_size),
            "moe": SharedRoutedFFN(
                self.d, gm.moe_intermediate_size, cfg.num_experts,
                top_k=cfg.moe_top_k, held=gm.experts_held,
                offset=gm.expert_offset,
                n_shared=(gm.shared_expert_intermediate_size
                          // gm.moe_intermediate_size),
                tp_size=self.tp_size, score="softmax", shared_gate=True),
        }

    # ---- what differs inside the forward (per-shard, inside shard_map) ----

    @property
    def rotary_dim(self) -> int:     # the full-attention layers read it
        return self.cfg.gdn_moe.rotary_dim

    def _mix(self, lp: Params, y: jax.Array, layer_pos, dtype) -> jax.Array:
        if "gdn" in lp:
            return self._mods["gdn"].apply(lp["gdn"], y, dtype)
        attn = self._mods["attn"]
        q, k, v, gate = attn.qkv(lp["attn"], y, *layer_pos, dtype)
        o = causal_attention(q, k, v, impl=self.attn_impl)
        return attn.project(lp["attn"], o, gate, dtype)

    @staticmethod
    def param_counts(cfg: ModelConfig) -> Dict[str, int]:
        """The family's parameters by part (`DecoderStack.num_params`)."""
        gm = cfg.gdn_moe
        d = cfg.attn_dim
        gdn = GatedDeltaNet(
            d, gm.linear_num_key_heads, gm.linear_num_value_heads,
            gm.linear_key_head_dim, gm.linear_value_head_dim,
            gm.linear_conv_kernel_dim).num_params()
        attn = GatedAttention(d, cfg.num_heads, cfg.kv_heads, gm.head_dim,
                              gm.rotary_dim).num_params()
        ffn = (d * cfg.num_experts                              # router
               + 3 * d * gm.shared_expert_intermediate_size + d  # shared, gate
               + cfg.experts_held * 3 * d * gm.moe_intermediate_size)
        full = cfg.num_layers // gm.full_attention_interval
        return {
            "embedding_and_head": 2 * cfg.vocab_size * d,
            "final_norm": d,
            "gdn_layers": (cfg.num_layers - full) * (gdn + ffn + 2 * d),
            "attn_layers": full * (attn + ffn + 2 * d),
        }

    @staticmethod
    def flops_per_step(cfg, batch, seqlen, num_params) -> float:
        """The held experts at a token's mean share of them; the
        embedding's lookup is no matmul; attention at the full T^2 in the
        full-attention layers only (q/k and v at `head_dim`); the chunked
        delta rule's own products (`ops/delta_rule.rule_flops_per_token`),
        forward and twice that backward."""
        gm = cfg.gdn_moe
        n = num_params - idle_expert_params(cfg, cfg.num_layers,
                                            gm.moe_intermediate_size)
        n -= cfg.vocab_size * cfg.attn_dim
        full = cfg.num_layers // gm.full_attention_interval
        rule = gm.linear_num_value_heads * rule_flops_per_token(
            gm.linear_key_head_dim, gm.linear_value_head_dim)
        return (6 * n * batch * seqlen
                + 12 * full * batch * cfg.num_heads * seqlen * seqlen
                * gm.head_dim
                + 3 * (cfg.num_layers - full) * rule * batch * seqlen)
