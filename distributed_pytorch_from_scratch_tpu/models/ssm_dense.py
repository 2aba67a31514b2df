"""The `ssm_dense` family: a DENSE hybrid whose every layer is a mixer AND a
SwiGLU, the mixer by a pattern a Mamba-2 state-space mixer (nine in ten) or
grouped-query attention with no positions, under four published scalars and
a head tied to the embedding (the Granite 4.0-H architecture,
`granitemoehybrid` with no experts), on the same decoder stack as the other
families.

`SsmDenseTransformer` is a subclass of `models/stack.DecoderStack` and holds
only what differs:

* **a layer is two sublayers, both scaled before the add**
  (`residual_scale` = `cfg.ssm_dense.residual_multiplier`): `h = x + s
  mixer(N1(x))`, `x' = h + s mlp(N2(h))`, RMSNorm at `rms_norm_eps`, no
  bias but the convolution's. The second sublayer is the stack's dense
  SwiGLU (`_mlp`, the ladder's names `ffn_gate` / `ffn_up`) in EVERY layer,
  under the named scope `dense_ffn`;
* **the mixer by `cfg.ssm_dense.layer_types`**, cut into periods that repeat
  by run length (`models/conv_moe.layer_blocks` with this family's two
  kinds: `params["mamba_layers_<i>"]`, `params["attn_layers_<i>"]` of the
  i-th block, stacked (periods, layers a period, ...)): a `mamba` layer's
  parameters hold no `wo`, so the stack asks `_mix_counted` as the FIRST
  half of the layer (`parallel/mamba.Mamba2Mixer` around the chunked
  recurrence of `ops/ssd.py`, all its heads and its one B / C group whole);
  an `attention` layer holds `wq` / `wk` / `wv` / `wo` and goes through the
  stack's own (q, k, v) dispatch (so the flash kernel with its native
  grouping on the TPU). The published 40 layers are (mamba x 5, attention),
  (mamba x 9, attention) x 3, mamba x 4; the benchmark's cut is the first
  ten;
* **attention takes NO positions** (`position_embedding_type` "nope":
  `_positions` hands the layers none) and its softmax is over `q k^T x
  attention_multiplier` (`softmax_scale`; the published 1 / 64 where `1 /
  sqrt(head_dim)` is 1 / 8);
* **the embedding's rows times `embedding_multiplier`** (`embed_scale`, in
  float32 as they enter) and **the logits over `logits_scaling`**
  (`logit_scale`) on the head tied to the embedding (the table normal(0,
  `initializer_range`));
* its counts.

A Mamba layer counts its decay, a row a Mamba layer: `ssm_decay_min`
(`parallel/mamba.py`); the loss counts `resid_rms_last`, the RMS over the
width of the residual stream that enters the final norm, a mean over the
step's tokens (float32): what the two multipliers exist to hold steady.

What is not made to work is refused with a message: where the model is
built (`refuses`: a counted mixer split by heads over a real `tp` axis has
no reduce written; a pipeline over a pattern of two mixer kinds; the
recurrence's state and the convolution's taps over sequence shards; pad
rows), by ZeRO 2/3 and the bucketed reducer (`hand_reduced_grads`), by
`models/decode.py` and the serving engines (`decodable`: a recurrent state
and a convolution's last inputs are not in `serving/kv_manager.py`).

Named scopes inside the step, for a device trace's `op_name`:
`mamba/in_proj|conv|ssd|gate_norm|out_proj` (parallel/mamba.py), `gqa_attn`
(the projections and `W_o`; the flash calls stay the kernels' own),
`dense_ffn` (every layer's SwiGLU) and the stack's `head_loss`.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from ..config import IGNORE_INDEX, ModelConfig
from ..ops.ssd import ssd_flops_per_token
from ..parallel.embedding import VocabParallelEmbedding
from ..parallel.linear import ColumnParallelLinear, RowParallelLinear
from ..parallel.mamba import Mamba2Mixer
from ..parallel.norm import RMSNorm
from .conv_moe import layer_blocks, pattern_of
from .stack import DecoderStack, Params, TPSublayers

KINDS = {"mamba": "mamba", "attention": "attn"}
MIXER = {"mamba": ("mamba",), "attn": ("wq", "wk", "wv", "wo")}
DENSE = ("gate_proj", "up_proj", "down_proj")
# `layer_extra_elems_per_token` in model widths a token: set from the chip's
# reading of the benchmark's cell (PERF.md section 5)
LAYER_FIT_WIDTHS = -51.0


def module_names(kind: str):
    """The modules of a layer whose mixer is `kind` ("mamba" | "attn")."""
    return ("norm1", *MIXER[kind], "norm2", *DENSE)


def mixer_of(cfg: ModelConfig) -> Mamba2Mixer:
    sd = cfg.ssm_dense
    return Mamba2Mixer(
        cfg.attn_dim, sd.mamba_n_heads, sd.mamba_d_head, sd.mamba_d_state,
        sd.mamba_n_groups, sd.mamba_d_conv, sd.mamba_chunk_size,
        sd.rms_norm_eps, 0, sd.time_step_min, sd.time_step_max,
        sd.time_step_floor)


def layer_counts(cfg: ModelConfig) -> Dict[str, int]:
    """Layers by kind of mixer."""
    return {kind: cfg.ssm_dense.layer_types.count(name)
            for name, kind in KINDS.items()}


@dataclass(frozen=True)
class SsmDenseTransformer(DecoderStack):
    """The ssm_dense family (module docstring)."""

    family = "ssm_dense"
    ffn_inputs = 2            # gate and up both read the MLP's input
    tied_head = True
    decodable = False
    hand_reduced_grads = False
    config_extra = "ssm_dense"
    attn_scope = "gqa_attn"
    # no router anywhere: what the layers hand back are the mixers' counters
    _router_aux_losses = False
    # the decay's row is a minimum over the tokens
    _counter_reduces = {"ssm_decay_min": lax.pmin}
    refuses = {
        "tp_size > 1": "the Mamba mixer's heads and its one B / C group are "
                       "whole (cfg.ssm_dense.mamba_n_heads, mamba_n_groups); "
                       "no reduce of a counted mixer split by heads over a "
                       "tp axis is written",
        "pp_size > 1": "the pipeline splits one segment of identical "
                       "layers; this family's pattern has two kinds of "
                       "mixer",
        "cp_size > 1": "the recurrence's state and the convolution's taps "
                       "run along the whole sequence; no hand-over of either "
                       "between sequence shards is written",
        "sequence_parallel=True": "the convolution and the recurrence read "
                                  "whole sequences",
        "attn_t_real": "pad tokens would enter the convolution and move "
                       "the state",
        "ZeRO stage 3": "",
    }

    def _check_facts(self):
        cfg, sd = self.cfg, self.cfg.ssm_dense
        if len(sd.layer_types) != cfg.num_layers:
            raise ValueError(
                f"layer_types names {len(sd.layer_types)} layers, num_layers "
                f"is {cfg.num_layers}")
        if cfg.num_experts:
            raise ValueError("the ssm_dense family's layers are dense: "
                             "cfg.num_experts must be 0")
        if sd.position_embedding_type != "nope":
            raise ValueError(
                f"the ssm_dense family's attention takes no positions "
                f"(position_embedding_type 'nope'), got "
                f"{sd.position_embedding_type!r}")
        if not sd.mamba_conv_bias:
            raise ValueError("the Mamba mixer's convolution has a bias "
                             "(mamba_conv_bias true is the one form written)")
        if (sd.mamba_n_heads * sd.mamba_d_head
                != sd.mamba_expand * cfg.attn_dim):
            raise ValueError(
                f"mamba_n_heads {sd.mamba_n_heads} x mamba_d_head "
                f"{sd.mamba_d_head} is not mamba_expand {sd.mamba_expand} x "
                f"the model's width {cfg.attn_dim}")
        mixer_of(cfg)           # heads that are not whole groups
        self._blocks            # a layer type the family has no mixer for

    # ---- the four scalars (the stack applies each in one place) ----

    @property
    def embed_scale(self) -> float:
        return self.cfg.ssm_dense.embedding_multiplier

    @property
    def residual_scale(self) -> float:
        return self.cfg.ssm_dense.residual_multiplier

    @property
    def softmax_scale(self) -> "float | None":
        return self.cfg.ssm_dense.attention_multiplier

    @property
    def logit_scale(self) -> float:
        return 1.0 / self.cfg.ssm_dense.logits_scaling

    # ---- the layer pattern ----

    @functools.cached_property
    def _blocks(self):
        return layer_blocks(self.cfg.ssm_dense.layer_types, 0, KINDS,
                            self.family)

    @property
    def _pattern(self):
        return pattern_of(self._blocks)

    @property
    def _segments(self):
        """(parameter key, layers, module names) of every stacked key."""
        return tuple((key, (repeats or 1) * n, module_names(kind))
                     for repeats, parts in self._blocks
                     for key, kind, _, n in parts)

    # ---- facts for training/memory.py ----

    @property
    def layer_extra_elems_per_token(self) -> float:
        """SET FROM THE CHIP'S READING, and negative: what this family's
        step holds at its fullest is the state, the stack's bfloat16 cast,
        the kept stacks and the whole gradient tree with LESS beside them
        than the dense skeleton's 6 d + 3.4 f a token. By count a Mamba
        layer's backward holds more than any dense layer's (the input
        projection `[z | xBC | dt]`, the convolution's float32 sums, a
        chunk row of float32 decays a head, 256 wide at two elements each,
        the mixed scores, the float32 output and the gated copy, each with
        its cotangent: 174,208 elements a token at the published sizes,
        1.33 GiB at 4096 tokens), but that is live in the MIDDLE of the
        backward scan, when the later layers' gradients do not exist yet in
        the runtime's count. The benchmark's cell on a v5e counts 13.445
        GiB at rung `dots`, which `auto` picks, and 12.977 at the floor (my
        chip runs, PR 68; PERF.md section 5): the ten layers' `ffn_gate` /
        `ffn_up` stacks (1.25 GiB by size) cost the chip 0.47, because at
        the floor their recomputed copies were among the temporaries.
        `LAYER_FIT_WIDTHS` model widths a token come back off, which makes
        13.85 at `dots` (+3.0%) and 12.56 at the floor (-3.2%: the floor is
        what is left where nothing fits and is picked by no estimate). The
        untuned count made 15.77 and 14.48; a job of another shape reads
        the estimate as far off as that (11 - 17%). Since PR 69 the decays
        and the mixed scores stay in VMEM on a TPU (`ops/pallas/ssd.py`) and
        the chip counts less than the fit by what they held (PERF.md
        section 5, PR 69); not re-fitted (ROADMAP D17)."""
        return LAYER_FIT_WIDTHS * self.d

    # ---- sub-module definitions ----

    @functools.cached_property
    def embedding(self) -> VocabParallelEmbedding:
        return VocabParallelEmbedding(
            self.cfg.vocab_size, self.d, tp_size=self.tp_size,
            init_std=self.cfg.ssm_dense.initializer_range)

    @functools.cached_property
    def _mods(self) -> Dict[str, Any]:
        cfg = self.cfg
        d, eps = self.d, cfg.ssm_dense.rms_norm_eps
        col = functools.partial(ColumnParallelLinear, add_bias=False,
                                gather_output=False)
        row = functools.partial(RowParallelLinear, add_bias=False,
                                split_input=False)
        return {
            "norm1": RMSNorm(d, eps),
            "norm2": RMSNorm(d, eps),
            "mamba": mixer_of(cfg),
            "wq": col(d, d),
            "wk": col(d, cfg.kv_dim),
            "wv": col(d, cfg.kv_dim),
            "wo": row(d, d),
            "gate_proj": col(d, cfg.ffn_dim),
            "up_proj": col(d, cfg.ffn_dim),
            "down_proj": row(cfg.ffn_dim, d),
        }

    # ---- what differs inside the forward (per-shard, inside shard_map) ----

    def _positions(self, params: Params, x: jax.Array,
                   position_ids: jax.Array, dtype):
        """The embedding's rows times the multiplier, in float32; no layer
        takes positions."""
        return (x * self.embed_scale).astype(dtype), ()

    def _mix_counted(self, lp: Params, y: jax.Array, layer_pos, dtype):
        return self._mods["mamba"].apply(lp["mamba"], y, dtype)

    def _mlp(self, lp: Params, y: jax.Array, tp: TPSublayers,
             dtype) -> jax.Array:
        with jax.named_scope("dense_ffn"):      # every layer's SwiGLU
            return super()._mlp(lp, y, tp, dtype)

    def _extra_loss(self, params: Params, loss: jax.Array, x: jax.Array,
                    aux, trunk, input_ids, target_ids, position_ids,
                    mode: str, batch_axes):
        """No further loss term; one counter beside the layers':
        `resid_rms_last`, the RMS over the width of what enters the final
        norm, float32, a mean over the tokens that are not ignored."""
        with jax.named_scope("head_loss"):
            live = (target_ids != IGNORE_INDEX).astype(jnp.float32)
            rms = jnp.sqrt(jnp.mean(jnp.square(
                lax.stop_gradient(x).astype(jnp.float32)), axis=-1))
            sums = lax.psum((jnp.sum(rms * live), jnp.sum(live)), batch_axes)
        return loss, {**self._counters(aux, batch_axes),
                      "resid_rms_last": sums[0] / jnp.maximum(sums[1], 1.0)}

    # ---- counts ----

    @staticmethod
    def param_counts(cfg: ModelConfig) -> Dict[str, int]:
        """The family's parameters by part (`DecoderStack.num_params`); the
        tied table counts once."""
        d, n = cfg.attn_dim, layer_counts(cfg)
        mlp = 3 * d * cfg.ffn_dim + 2 * d       # + the layer's two norms
        return {
            "embedding": cfg.vocab_size * d, "final_norm": d,
            "mamba_layers": n["mamba"] * (mixer_of(cfg).num_params() + mlp),
            "attn_layers": n["attn"] * (2 * d * d + 2 * d * cfg.kv_dim + mlp),
        }

    @staticmethod
    def flops_per_step(cfg, batch, seqlen, num_params) -> float:
        """Every parameter but the norms and the recurrence's own few is a
        matmul's (the tied table's lookup is none, its one matrix is the
        head's; the taps are no matmul: their share is under a thousandth
        and stays in); attention at the full T^2 in the attention layers
        only, as every family counts it; the chunked recurrence's own
        products (`ops/ssd.ssd_flops_per_token`), forward and twice that
        backward."""
        sd, n = cfg.ssm_dense, layer_counts(cfg)
        scan = sd.mamba_n_heads * ssd_flops_per_token(
            sd.mamba_d_head, sd.mamba_d_state,
            sd.mamba_n_heads // sd.mamba_n_groups, sd.mamba_chunk_size)
        return (6 * num_params * batch * seqlen
                + 12 * n["attn"] * batch * cfg.num_heads * seqlen * seqlen
                * cfg.head_dim
                + 3 * n["mamba"] * scan * batch * seqlen)

