"""The `ssm_moe` family: layers of ONE sublayer each, by a pattern (`M` a
Mamba-2 state-space mixer, `*` grouped-query attention with no positions,
`E` a sigmoid-routed expert FFN whose experts live in a latent narrower than
the model), a multi-token-prediction module that is a pattern of its own
(the Nemotron-H architecture as Nemotron 3 publishes it, `nemotron_h`), on
the same decoder stack as the other families.

`SsmMoETransformer` is a subclass of `models/stack.DecoderStack` and holds
only what differs:

* **a layer is one norm and one sublayer** (`one_sublayer`): `x +
  sublayer(RMSNorm(x))`, where every other family's layer is a mixer and
  then a feed-forward part. What a layer's parameters hold says which: a
  `mamba` layer asks `_mix_counted`, an attention layer holds `wq` / `wk` /
  `wv` / `wo` and goes through the stack's own (q, k, v) dispatch (so the
  flash kernel with its native grouping on the TPU), an expert layer holds
  `moe` and has no mixer before it;
* **the pattern** is `cfg.ssm_moe.hybrid_override_pattern`, one letter a
  layer, cut into periods that repeat by run length
  (`models/conv_moe.layer_blocks` with this family's three kinds:
  `params["moe_layers_<i>"]`, `params["mamba_layers_<i>"]`,
  `params["attn_layers_<i>"]` of the i-th block, stacked (periods, layers a
  period, ...)). The benchmark's cut `EMEMEMEMEM*` is (expert, Mamba) five
  times in one scan and the attention layer;
* **the Mamba-2 mixer**: `parallel/mamba.Mamba2Mixer` around the chunked
  recurrence of `ops/ssd.py`, BUILT at the heads and B / C groups the job
  holds (`mamba_num_heads`, `n_groups`: one tensor-parallel rank's share,
  the first head `mamba_head_offset`), as the attention is at its
  `num_heads` / `num_kv_heads`. Nothing stands in for the absent ranks and
  `tp_size > 1` is refused: the reduce over a real `tp` axis is not written
  (ROADMAP);
* **attention takes NO positions** (`_positions` hands the layers none):
  Nemotron-H's attention layers carry no position embedding, the mixers
  before them do;
* **the expert FFN**: `parallel/moe.SharedRoutedFFN(score="sigmoid",
  activation="relu2", gated=False, latent=moe_latent_size,
  shared_width=...)`: the router and the shared expert read the d-wide
  token, the routed experts `down (relu(up l))^2` the token's latent, the
  weights the chosen scores normalised and times `routed_scaling_factor`;
  the job holds `experts_held` of the experts the router scores; the
  selection bias is a leaf no rule moves (the configuration publishes no
  speed);
* **multi-token prediction**: `models/mla_moe.MultiTokenPrediction`, the
  third family's module, its layers by `mtp_hybrid_override_pattern` (`*E`:
  an attention and an expert layer under two keys, `_mtp_keys`);
* an untied head, the plain RMSNorm (eps `norm_eps`), no bias but the
  convolution's.

A Mamba layer counts its decay, a row a layer beside the expert layers'
rows: `ssm_decay_min` (`parallel/mamba.py`).

What is not made to work is refused with a message: where the model is
built (`refuses`), by ZeRO 2/3 and the bucketed reducer
(`hand_reduced_grads`), by `models/decode.py` and the serving engines
(`decodable`: a recurrent state and a convolution's last inputs are not in
`serving/kv_manager.py`).

Named scopes inside the step, for a device trace's `op_name`:
`mamba/in_proj|conv|ssd|gate_norm|out_proj` (parallel/mamba.py), `gqa_attn`
(the projections and `W_o`; the flash calls stay the kernels' own),
`moe_latent/down|up`, `moe_route`, `moe_experts`, `moe_shared`
(parallel/moe.py) and `mtp`.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict

import jax
from jax import lax

from ..config import ModelConfig
from ..ops.ssd import ssd_flops_per_token
from ..parallel.linear import ColumnParallelLinear, RowParallelLinear
from ..parallel.mamba import Mamba2Mixer
from ..parallel.moe import SharedRoutedFFN
from ..parallel.norm import RMSNorm
from .conv_moe import layer_blocks, pattern_of
from .mla_moe import MultiTokenPrediction
from .stack import DecoderStack, Params

KINDS = {"M": "mamba", "*": "attn", "E": "moe"}
MODULES = {"mamba": ("norm1", "mamba"),
           "attn": ("norm1", "wq", "wk", "wv", "wo"),
           "moe": ("norm1", "moe")}


def mixer_of(cfg: ModelConfig) -> Mamba2Mixer:
    sm = cfg.ssm_moe
    return Mamba2Mixer(
        cfg.attn_dim, sm.mamba_num_heads, sm.mamba_head_dim,
        sm.ssm_state_size, sm.n_groups, sm.conv_kernel, sm.chunk_size,
        sm.norm_eps, sm.mamba_head_offset, sm.time_step_min,
        sm.time_step_max, sm.time_step_floor)


def layer_counts(cfg: ModelConfig) -> Dict[str, int]:
    """Layers by kind, the main model's and the module's apart."""
    sm = cfg.ssm_moe
    mtp = sm.mtp_hybrid_override_pattern * sm.num_nextn_predict_layers
    return {**{kind: sm.hybrid_override_pattern.count(letter)
               for letter, kind in KINDS.items()},
            **{f"mtp_{kind}": mtp.count(letter)
               for letter, kind in KINDS.items()}}


@dataclass(frozen=True)
class SsmMoETransformer(MultiTokenPrediction, DecoderStack):
    """The ssm_moe family (module docstring)."""

    family = "ssm_moe"
    one_sublayer = True
    ffn_inputs = 0            # no dense MLP in any layer
    tied_head = False
    decodable = False
    hand_reduced_grads = False
    config_extra = "ssm_moe"
    attn_scope = "gqa_attn"
    ffn_norm_key = "norm1"    # the final norm is a layer's norm
    _router_aux_losses = False
    # the decay's row is a minimum over the tokens
    _counter_reduces = {"ssm_decay_min": lax.pmin}
    refuses = {
        "tp_size > 1": "the mixers are built at one tensor-parallel rank's "
                       "share of the heads (cfg.ssm_moe.mamba_num_heads, "
                       "n_groups; num_heads, num_kv_heads); the reduce over "
                       "a tp axis is not written",
        "pp_size > 1": "the pipeline splits one segment of identical "
                       "layers; this family has three kinds of layer, a "
                       "pattern and a multi-token-prediction module behind "
                       "it",
        "cp_size > 1": "the recurrence's state and the convolution's taps "
                       "run along the whole sequence; no hand-over of either "
                       "between sequence shards is written",
        "ep_size > 1": "a job holds one share of the experts, "
                       "cfg.ssm_moe.experts_held; the all-to-all between "
                       "shares is not written",
        "sequence_parallel=True": "the router, the convolution and the "
                                  "recurrence read whole sequences",
        "attn_t_real": "pad tokens would be routed and would move the state",
        "ZeRO stage 3": "",
    }

    def _check_facts(self):
        sm = self.cfg.ssm_moe
        if len(sm.hybrid_override_pattern) != self.cfg.num_layers:
            raise ValueError(
                f"hybrid_override_pattern names "
                f"{len(sm.hybrid_override_pattern)} layers, num_layers is "
                f"{self.cfg.num_layers}")
        if sm.num_nextn_predict_layers not in (0, 1):
            raise ValueError("multi-token prediction is written for depth "
                             f"0 or 1, got {sm.num_nextn_predict_layers}")
        mtp = sm.mtp_hybrid_override_pattern
        if len(set(mtp)) != len(mtp) or not set(mtp) <= set(KINDS):
            raise ValueError(
                f"mtp_hybrid_override_pattern {mtp!r}: the module's layers "
                f"are one of each kind at most, of {sorted(KINDS)}")
        mixer_of(self.cfg)      # heads that are not whole groups
        self._blocks            # a letter the family has no layer for

    # ---- the layer pattern ----

    @functools.cached_property
    def _blocks(self):
        return layer_blocks(tuple(self.cfg.ssm_moe.hybrid_override_pattern),
                            0, KINDS, self.family)

    @property
    def _pattern(self):
        return pattern_of(self._blocks)

    @property
    def _mtp_kinds(self):
        return tuple(KINDS[letter] for letter
                     in self.cfg.ssm_moe.mtp_hybrid_override_pattern)

    @property
    def _mtp_keys(self):
        return tuple(f"mtp_{kind}_layers" for kind in self._mtp_kinds)

    @property
    def _segments(self):
        """(parameter key, layers, module names) of every stacked key, the
        multi-token-prediction module's layers last."""
        main = tuple((key, (repeats or 1) * n, MODULES[kind])
                     for repeats, parts in self._blocks
                     for key, kind, _, n in parts)
        if not self.cfg.ssm_moe.num_nextn_predict_layers:
            return main
        return main + tuple((f"mtp_{kind}_layers", 1, MODULES[kind])
                            for kind in self._mtp_kinds)

    # ---- facts for the stack, the step and training/memory.py ----

    @property
    def head_dim(self) -> int:
        return self.cfg.ssm_moe.head_dim

    @property
    def stacked_layers(self) -> int:
        sm = self.cfg.ssm_moe
        return self.cfg.num_layers + (len(sm.mtp_hybrid_override_pattern)
                                      * sm.num_nextn_predict_layers)

    @property
    def layer_extra_elems_per_token(self) -> float:
        """What a Mamba layer's backward holds at its fullest beside the
        d-wide tensors the dense skeleton counts, in elements of the compute
        dtype a token (the skeleton's 3.4 f is the shared expert's, and an
        expert layer's latent rows and one chunk of its dispatch are less
        than this): the input projection `[z | xBC | dt]`, the
        convolution's float32 sums (two elements a channel) and `[x | B |
        C]`; of the chunked recurrence, a chunk row of float32 decays a
        head (`chunk` wide, two elements each), the mixed scores in the
        compute dtype, the float32 output and the gated copy; each with its
        cotangent. NOT yet set from the chip's reading: cell 13 on a v5e
        counts 13.27 GiB at rung `dots`, the rung `auto` picks there, for a
        step this makes 13.91 (my chip runs, PR 63). Since PR 69 the decays
        and the mixed scores stay in VMEM on a TPU (`ops/pallas/ssd.py`;
        between forward and backward a layer holds the chunks' entering
        states, 2 N a head-channel and chunk) and this count, the text's,
        stands above the step by them; not re-fitted (ROADMAP D17)."""
        mixer = self._mods["mamba"]
        proj = mixer.inner + mixer.conv_channels + mixer.heads
        scan = mixer.heads * mixer.chunk * 3 + 4 * mixer.inner
        return 2.0 * (proj + 3 * mixer.conv_channels + scan)

    # ---- sub-module definitions ----

    @functools.cached_property
    def _mods(self) -> Dict[str, Any]:
        cfg, sm = self.cfg, self.cfg.ssm_moe
        d = self.d
        qd = cfg.num_heads * sm.head_dim
        col = functools.partial(ColumnParallelLinear, add_bias=False,
                                gather_output=False)
        return {
            "norm1": RMSNorm(d, sm.norm_eps),
            "mamba": mixer_of(cfg),
            "wq": col(d, qd),
            "wk": col(d, self.kv_dim),
            "wv": col(d, self.kv_dim),
            "wo": RowParallelLinear(qd, d, add_bias=False,
                                    split_input=False),
            "moe": SharedRoutedFFN(
                d, sm.moe_intermediate_size, cfg.num_experts,
                top_k=cfg.moe_top_k, held=sm.experts_held,
                offset=sm.expert_offset, scaling=sm.routed_scaling_factor,
                tp_size=self.tp_size, score="sigmoid", activation="relu2",
                n_group=sm.n_group, topk_group=sm.topk_group, gated=False,
                latent=sm.moe_latent_size,
                shared_width=sm.moe_shared_expert_intermediate_size),
        }

    # ---- what differs inside the forward (per-shard, inside shard_map) ----

    def _positions(self, params: Params, x: jax.Array,
                   position_ids: jax.Array, dtype):
        """No layer takes positions: the attention layers carry none."""
        return x.astype(dtype), ()

    def _mix_counted(self, lp: Params, y: jax.Array, layer_pos, dtype):
        return self._mods["mamba"].apply(lp["mamba"], y, dtype)

    @staticmethod
    def param_counts(cfg: ModelConfig) -> Dict[str, int]:
        """The family's parameters by part (`DecoderStack.num_params`)."""
        sm = cfg.ssm_moe
        d, n = cfg.attn_dim, layer_counts(cfg)
        qd, kvd = cfg.num_heads * sm.head_dim, cfg.kv_heads * sm.head_dim
        mamba = mixer_of(cfg).num_params() + d          # + the layer's norm
        attn = 2 * d * qd + 2 * d * kvd + d
        expert = 2 * sm.moe_latent_size * sm.moe_intermediate_size
        moe = (d + d * cfg.num_experts + cfg.num_experts    # router, bias
               + 2 * d * sm.moe_latent_size + cfg.experts_held * expert
               + 2 * d * sm.moe_shared_expert_intermediate_size)
        return {
            "embedding_and_head": 2 * cfg.vocab_size * d,
            "final_norm": d,
            "mamba_layers": n["mamba"] * mamba,
            "attn_layers": n["attn"] * attn,
            "moe_layers": n["moe"] * moe,
            "mtp": (n["mtp_mamba"] * mamba + n["mtp_attn"] * attn
                    + n["mtp_moe"] * moe
                    + sm.num_nextn_predict_layers * (2 * d * d + 3 * d)),
        }

    @staticmethod
    def flops_per_step(cfg, batch, seqlen, num_params) -> float:
        """The held experts at a token's mean share of them (two matrices
        an expert, `moe_latent_size` wide); the embedding's lookup is no
        matmul but the head runs once more for the module; attention at the
        causal T^2 / 2 in the attention layers only; the chunked
        recurrence's own products (`ops/ssd.ssd_flops_per_token`), forward
        and twice that backward."""
        sm, n = cfg.ssm_moe, layer_counts(cfg)
        held = cfg.experts_held
        idle = ((n["moe"] + n["mtp_moe"])
                * (held - cfg.moe_top_k * held / cfg.num_experts)
                * 2 * sm.moe_latent_size * sm.moe_intermediate_size)
        params = (num_params - idle + (sm.num_nextn_predict_layers - 1)
                  * cfg.vocab_size * cfg.attn_dim)
        scan = sm.mamba_num_heads * ssd_flops_per_token(
            sm.mamba_head_dim, sm.ssm_state_size,
            sm.mamba_num_heads // sm.n_groups, sm.chunk_size)
        return (6 * params * batch * seqlen
                + 12 * (n["attn"] + n["mtp_attn"]) * batch * cfg.num_heads
                * seqlen * seqlen * sm.head_dim / 2
                + 3 * (n["mamba"] + n["mtp_mamba"]) * scan * batch * seqlen)
