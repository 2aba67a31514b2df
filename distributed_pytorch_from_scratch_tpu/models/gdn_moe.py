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

What is not made to work is refused where the model is built, with a
message: pp > 1, cp > 1, ep > 1, sequence parallelism and its rings,
pad-aware bucketing, ZeRO 2/3 and the bucketed reducer
(`hand_reduced_grads`), `models/decode.py` and the serving engines
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
import jax.numpy as jnp
from jax import lax

from ..config import ModelConfig
from ..ops.attention import causal_attention
from ..ops.rope import rope_angles
from ..parallel.embedding import VocabParallelEmbedding
from ..parallel.gated_attention import GatedAttention
from ..parallel.gdn import GatedDeltaNet
from ..parallel.linear import ColumnParallelLinear
from ..parallel.moe import SharedRoutedFFN
from ..parallel.norm import ZeroCenteredRMSNorm
from ..runtime.prng import fold
from .stack import DecoderStack, Params, TPSublayers
from .transformer import Transformer

LINEAR = ("norm1", "gdn", "norm2", "moe")
FULL = ("norm1", "attn", "norm2", "moe")


@dataclass(frozen=True)
class GdnMoETransformer(DecoderStack):
    """The gdn_moe family (module docstring)."""

    uses_rope = True
    attn_norm_key = "norm1"
    ffn_norm_key = "norm2"
    ffn_inputs = 0            # no dense MLP: every layer's FFN is routed
    tied_head = False
    decodable = False
    hand_reduced_grads = False
    config_extra = "gdn_moe"
    _router_aux_losses = False

    def __post_init__(self):
        gm = self.cfg.gdn_moe
        if gm is None:
            raise ValueError("the gdn_moe family needs cfg.gdn_moe "
                             "(config.GdnMoEConfig)")
        if not self.cfg.num_experts:
            raise ValueError("the gdn_moe family needs cfg.num_experts > 0 "
                             "(the routed experts its router scores)")
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
        refused = [
            (self.pp_size > 1, "pp_size > 1 (the pipeline splits one "
             "segment of identical layers; this family scans periods of two "
             "kinds of layer)"),
            (self.cp_size > 1, "cp_size > 1 (the delta rule's state and the "
             "convolution's taps run along the whole sequence; no exchange "
             "of either between sequence shards is written)"),
            (self.ep_size > 1, "ep_size > 1 (a job holds one share of the "
             "experts, cfg.gdn_moe.experts_held; the all-to-all between "
             "shares is not written)"),
            (self.sequence_parallel is True, "sequence_parallel=True (the "
             "router, the convolution and the rule read whole sequences)"),
            (self.attn_t_real is not None, "attn_t_real (pad tokens would "
             "be routed and would move the state)"),
            (self.zero3_axis is not None, "ZeRO stage 3"),
        ]
        for bad, what in refused:
            if bad:
                raise ValueError(f"the gdn_moe family does not run with "
                                 f"{what}")
        super().__post_init__()

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
        less. One reading: the benchmark's cell on a v5e counts 13.68 GiB
        for a step this makes 13.42 (PERF.md section 5, PR 35)."""
        gm, gdn, moe = self.cfg.gdn_moe, self._mods["gdn"], self._mods["moe"]
        hk = gm.linear_num_key_heads / self.tp_size
        hv = gm.linear_num_value_heads / self.tp_size
        dk, dv = gm.linear_key_head_dim, gm.linear_value_head_dim
        rule_inputs = (2 * hk * gdn.head_columns + 2 * hk * gdn.conv_channels
                       + hv * (2 * dk + 2 * dv))
        chunk_rows = moe.chunk_share * moe.top_k
        return rule_inputs + chunk_rows * (
            2 * self.d + 3 * gm.moe_intermediate_size / self.tp_size)

    # ---- sub-module definitions ----

    @functools.cached_property
    def embedding(self) -> VocabParallelEmbedding:
        return VocabParallelEmbedding(self.cfg.vocab_size, self.d,
                                      tp_size=self.tp_size)

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

    @functools.cached_property
    def final_norm(self) -> ZeroCenteredRMSNorm:
        return self._norm()

    @functools.cached_property
    def lm_head(self) -> ColumnParallelLinear:
        return ColumnParallelLinear(self.d, self.vocab_padded,
                                    add_bias=False, gather_output=False)

    # ---- init / specs ----

    def init(self, key: jax.Array) -> Params:
        lm_head = self.lm_head.init(fold(key, "lm_head"))
        if self.vocab_padded != self.cfg.vocab_size:
            keep = jnp.arange(self.vocab_padded) < self.cfg.vocab_size
            lm_head["weight"] = jnp.where(keep[None, :], lm_head["weight"],
                                          0.0)
        return {
            "embedding": self.embedding.init(fold(key, "embedding")),
            **{name: self._init_layers(key, name, count, names)
               for name, count, names in self._segments},
            "norm": self.final_norm.init(fold(key, "norm")),
            "lm_head": lm_head,
        }

    def specs(self) -> Params:
        return {
            "embedding": self.embedding.specs(),
            **{name: self._layer_specs(names, name)
               for name, _, names in self._segments},
            "norm": self.final_norm.specs(),
            "lm_head": self.lm_head.specs(),
        }

    @staticmethod
    def num_params(cfg: ModelConfig) -> int:
        return sum(param_counts(cfg).values())

    # ---- what differs inside the forward (per-shard, inside shard_map) ----

    def _positions(self, params: Params, x: jax.Array,
                   position_ids: jax.Array, dtype):
        """Nothing enters at the embedding; every layer gets the rotary
        slice's (cos, sin) at `position_ids` (the full-attention layers
        read them)."""
        return x.astype(dtype), rope_angles(
            position_ids, self.cfg.gdn_moe.rotary_dim, self.cfg.rope_theta)

    def _mix(self, lp: Params, y: jax.Array, layer_pos, dtype) -> jax.Array:
        if "gdn" in lp:
            return self._mods["gdn"].apply(lp["gdn"], y, dtype)
        attn = self._mods["attn"]
        q, k, v, gate = attn.qkv(lp["attn"], y, *layer_pos, dtype)
        o = causal_attention(q, k, v, impl=self.attn_impl)
        return attn.project(lp["attn"], o, gate, dtype)

    _head_logits = Transformer._head_logits

    def _ffn(self, lp: Params, y: jax.Array, tp: TPSublayers, dtype):
        return self._mods["moe"].apply(lp["moe"], y, dtype)

    def _fold_aux(self, auxs):
        # the layers' counters stay one row a layer
        return auxs

    def _extra_loss(self, params: Params, loss: jax.Array, x: jax.Array,
                    aux, trunk, input_ids, target_ids, position_ids,
                    mode: str, batch_axes):
        return loss, jax.tree.map(lambda a: lax.psum(a, batch_axes), aux)


def param_counts(cfg: ModelConfig) -> Dict[str, int]:
    """The family's parameters by part, as `init` makes them for `cfg` (the
    experts HELD, not the routed total): what `num_params` sums, and what
    the benchmark's own count is pinned against."""
    gm = cfg.gdn_moe
    d = cfg.attn_dim
    gdn = GatedDeltaNet(d, gm.linear_num_key_heads, gm.linear_num_value_heads,
                        gm.linear_key_head_dim, gm.linear_value_head_dim,
                        gm.linear_conv_kernel_dim).num_params()
    attn = GatedAttention(d, cfg.num_heads, cfg.kv_heads, gm.head_dim,
                          gm.rotary_dim).num_params()
    ffn = (d * cfg.num_experts                               # router
           + 3 * d * gm.shared_expert_intermediate_size + d  # shared + gate
           + cfg.experts_held * 3 * d * gm.moe_intermediate_size)
    full = cfg.num_layers // gm.full_attention_interval
    return {
        "embedding_and_head": 2 * cfg.vocab_size * d,
        "final_norm": d,
        "gdn_layers": (cfg.num_layers - full) * (gdn + ffn + 2 * d),
        "attn_layers": full * (attn + ffn + 2 * d),
    }
