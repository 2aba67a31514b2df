"""The `conv_moe` family: gated short-convolution mixers, about three to one
with grouped-query attention layers whose q and k are normed per head,
leading layers with a dense SwiGLU and then a sigmoid-routed expert FFN with
a selection bias and no shared expert, a head tied to the embedding (the
LFM2 architecture, `lfm2_moe`), on the same decoder stack as the other
families.

`ConvMoETransformer` is a subclass of `models/stack.DecoderStack` and holds
only what differs:

* **a pattern that is a leading segment and then periods**, derived from
  `cfg.conv_moe.layer_types` and `num_dense_layers` by run length
  (`layer_blocks`): the leading dense layers are one segment
  (`params["dense_layers"]`), what follows is cut into periods that repeat
  (`params["attn_layers_<i>"]`, `params["conv_layers_<i>"]` of the i-th
  period block, stacked (periods, layers a period, ...)). The published 24
  layers are 2 dense convolution layers, (attention, conv x 3) x 4 and
  (attention, conv x 2) x 2; the benchmark's cut is 1 dense layer and one
  period of four: the same program (`DecoderStack._pattern`);
* **two kinds of mixer in one family**: a convolution layer's parameters
  hold `conv` (`parallel/shortconv.ShortConv`) and no `wo`, so the stack
  asks `_mix`; an attention layer holds `wq`/`wk`/`wv`/`wo` and goes through
  the stack's own (q, k, v) dispatch (`_qkv`, `causal_attention`, so the
  flash kernel with its native grouping on the TPU) with the `q_norm` /
  `k_norm` it also holds applied per head before the rotation, and RoPE
  (half-split pairs) over the whole head;
* **the expert FFN**: `parallel/moe.SharedRoutedFFN(score="sigmoid",
  n_shared=0)`: the router scores all `cfg.num_experts`, the job holds
  `cfg.conv_moe.experts_held` of them (one chip's share of an
  expert-parallel deployment; None = all); no token is dropped, no
  auxiliary loss; the selection bias is a leaf no gradient reaches;
* the plain RMSNorm (eps `norm_eps`) for both layer norms, the final norm
  and the q/k norms; the head tied to the embedding (initialised at 0.02,
  the published `initializer_range`); no bias anywhere.

What is not made to work is refused with a message: where the model is
built (`refuses`), by ZeRO 2/3 and the bucketed reducer
(`hand_reduced_grads`), by `models/decode.py` and the serving engines
(`decodable`: a convolution's last inputs are a state
`serving/kv_manager.py` does not hold).

Named scopes inside the step, for a device trace's `op_name`: `shortconv`
(parallel/shortconv.py), `gqa_attn` (the projections, q/k norms, RoPE and
`W_o`; the flash calls stay the kernels' own), `dense_ffn`, and `moe_route`,
`moe_experts` (parallel/moe.py).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax

from ..config import ModelConfig
from ..parallel.embedding import VocabParallelEmbedding
from ..parallel.linear import ColumnParallelLinear, RowParallelLinear
from ..parallel.moe import SharedRoutedFFN
from ..parallel.norm import RMSNorm
from ..parallel.shortconv import ShortConv
from .stack import DecoderStack, Params, TPSublayers, idle_expert_params

KINDS = {"conv": "conv", "full_attention": "attn"}
MIXER = {"conv": ("conv",),
         "attn": ("wq", "wk", "wv", "q_norm", "k_norm", "wo")}
DENSE = ("gate_proj", "up_proj", "down_proj")
INIT_STD = 0.02     # the published `initializer_range`, of the tied embedding


def module_names(kind: str, dense: bool) -> Tuple[str, ...]:
    """The modules of a layer whose mixer is `kind` ("conv" | "attn")."""
    return ("norm1", *MIXER[kind], "norm2", *(DENSE if dense else ("moe",)))


def layer_blocks(layer_types, num_dense_layers: int, kinds=None,
                 family: str = "conv_moe"):
    """`layer_types` and `num_dense_layers` -> the blocks of the layer
    pattern, by run length: ((repeats, ((parameter key, mixer kind, dense?,
    layers), ...)), ...), `repeats` None for a segment scanned once.
    `kinds` names the kinds of layer a family has (this family's `KINDS`;
    `models/swa_moe.py` cuts its own two the same way, `models/ssm_moe.py`
    its three).

    The leading dense layers are one segment (one kind of mixer). What
    follows is read as runs of one kind; a period starts at a run and ends
    before that run's kind comes again, and it repeats while the same runs
    follow: A C C C A C C C A C C A C C is ((A, C x 3) x 2, (A, C x 2) x
    2)."""
    named = KINDS if kinds is None else kinds
    kinds = []
    for name in layer_types:
        if name not in named:
            raise ValueError(f"layer_types holds {name!r}; the {family} "
                             f"family has {sorted(named)}")
        kinds.append(named[name])
    lead, rest = kinds[:num_dense_layers], kinds[num_dense_layers:]
    if len(set(lead)) > 1:
        raise ValueError("the leading dense layers are one segment of one "
                         f"kind of mixer, got {lead}")
    blocks = [(None, (("dense_layers", lead[0], True, len(lead)),))] if lead \
        else []
    runs = []                                   # [kind, layers]
    for kind in rest:
        if runs and runs[-1][0] == kind:
            runs[-1][1] += 1
        else:
            runs.append([kind, 1])
    at = 0
    while at < len(runs):
        # a period is one run, or two of unlike kinds
        period = runs[at:at + 2]
        n = len(period)
        repeats = 1
        while runs[at + repeats * n:at + (repeats + 1) * n] == period:
            repeats += 1
        i = sum(r is not None for r, _ in blocks)
        blocks.append((repeats, tuple((f"{kind}_layers_{i}", kind, False, c)
                                      for kind, c in period)))
        at += repeats * n
    return tuple(blocks)


def pattern_of(blocks):
    """`DecoderStack._pattern` of `layer_blocks`' blocks: a segment's key, a
    period's (key, layers a period) pairs."""
    return tuple(parts[0][0] if repeats is None
                 else tuple((key, n) for key, _, _, n in parts)
                 for repeats, parts in blocks)


def layers_in_order(params: Params, blocks):
    """The stacked layers of a parameter tree laid out by `blocks`
    (`layer_blocks`), one pytree a layer, in the order the layers run: what
    a reference that LOOPS its layers walks."""
    out = []
    for repeats, parts in blocks:
        for p in range(repeats or 1):
            for key, _, _, n in parts:
                for j in range(n):
                    at = (j,) if repeats is None else (p, j)
                    out.append(jax.tree.map(lambda a: a[at], params[key]))
    return out


@dataclass(frozen=True)
class ConvMoETransformer(DecoderStack):
    """The conv_moe family (module docstring)."""

    family = "conv_moe"
    ffn_inputs = 2            # the leading layers' SwiGLU: gate and up
    tied_head = True
    decodable = False
    hand_reduced_grads = False
    config_extra = "conv_moe"
    attn_scope = "gqa_attn"
    _router_aux_losses = False
    refuses = {
        "pp_size > 1": "the pipeline splits one segment of identical "
                       "layers; this family has a leading segment and then "
                       "periods of two kinds of layer",
        "cp_size > 1": "the convolution's taps run along the whole "
                       "sequence; no exchange of a shard's last inputs is "
                       "written",
        "ep_size > 1": "a job holds one share of the experts, "
                       "cfg.conv_moe.experts_held; the all-to-all between "
                       "shares is not written",
        "sequence_parallel=True": "the router and the convolution read "
                                  "whole sequences",
        "attn_t_real": "pad tokens would be routed and would enter the "
                       "convolution",
        "ZeRO stage 3": "",
    }

    def _check_facts(self):
        cm = self.cfg.conv_moe
        if len(cm.layer_types) != self.cfg.num_layers:
            raise ValueError(
                f"layer_types names {len(cm.layer_types)} layers, num_layers "
                f"is {self.cfg.num_layers}")
        if not 0 <= cm.num_dense_layers < self.cfg.num_layers:
            raise ValueError(
                f"num_dense_layers {cm.num_dense_layers} must leave an "
                f"expert layer among {self.cfg.num_layers} layers")
        self._blocks    # a pattern the family cannot cut is refused here

    # ---- the layer pattern ----

    @functools.cached_property
    def _blocks(self):
        cm = self.cfg.conv_moe
        return layer_blocks(cm.layer_types, cm.num_dense_layers)

    @property
    def _pattern(self):
        return pattern_of(self._blocks)

    @property
    def _segments(self):
        """(parameter key, layers, module names) of every stacked key."""
        return tuple((key, (repeats or 1) * n, module_names(kind, dense))
                     for repeats, parts in self._blocks
                     for key, kind, dense, n in parts)

    # ---- facts for training/memory.py ----

    @property
    def layer_extra_elems_per_token(self) -> float:
        """What an expert layer's backward holds at its fullest, beside the
        d-wide tensors the dense skeleton counts, in elements of the
        compute dtype a token: a convolution layer's `[B | C | u]` and its
        cotangent (6 d), the gated products `B u` and `C c` (2 d) and the
        taps' float32 sum with its cotangent (4 d in elements of two
        bytes); and one chunk of the expert dispatch
        (`SharedRoutedFFN.chunk_share` of a token's pairs: rows in and
        out, and the hidden activations `[gate | up]`, their product and
        both cotangents). A chunk is one mean share of the pairs
        (`parallel/moe.CHUNK_SHARES`: at a held share of 1/4 one row a
        token at top-4; until PR 71 ALL pairs there). An attention layer
        holds less.
        The last term takes 18.96 d a token back off and is SET FROM THE
        CHIP'S READING (the skeleton's 3.4 f a token is the one dense
        layer's MLP, which no expert layer holds beside its chunk): cell 7
        on a v5e counts 9.632 GiB at rung `true` and 10.339 at `dots`, the
        rung `auto` picks, for steps this makes 9.85 and 10.44 (my chip
        runs, PR 71: the term PR 62 set at a chunk of all the pairs, 10.899
        and 11.609 for 11.04 and 11.64, still reads inside the rule's
        -0.5% / +4.5% at a chunk of a quarter of them, so it stands)."""
        moe = self._mods["moe"]
        chunk_rows = moe.chunk_share * moe.top_k
        f = self.cfg.conv_moe.moe_intermediate_size / self.tp_size
        return 12.0 * self.d / self.tp_size + chunk_rows * (
            2 * self.d + 5 * f) - 18.96 * self.d / self.tp_size

    # ---- sub-module definitions ----

    @functools.cached_property
    def embedding(self) -> VocabParallelEmbedding:
        return VocabParallelEmbedding(self.cfg.vocab_size, self.d,
                                      tp_size=self.tp_size,
                                      init_std=INIT_STD)

    @functools.cached_property
    def _mods(self) -> Dict[str, Any]:
        cfg, cm = self.cfg, self.cfg.conv_moe
        d, eps = self.d, cm.norm_eps
        col = functools.partial(ColumnParallelLinear, add_bias=False,
                                gather_output=False)
        row = functools.partial(RowParallelLinear, add_bias=False,
                                split_input=False)
        return {
            "norm1": RMSNorm(d, eps),
            "norm2": RMSNorm(d, eps),
            "conv": ShortConv(d, cm.conv_L_cache, tp_size=self.tp_size),
            "wq": col(d, d),
            "wk": col(d, cfg.kv_dim),
            "wv": col(d, cfg.kv_dim),
            # one weight vector for all query heads, one for all key heads
            "q_norm": RMSNorm(cfg.head_dim, eps),
            "k_norm": RMSNorm(cfg.head_dim, eps),
            "wo": row(d, d),
            "gate_proj": col(d, cfg.ffn_dim),
            "up_proj": col(d, cfg.ffn_dim),
            "down_proj": row(cfg.ffn_dim, d),
            "moe": SharedRoutedFFN(
                d, cm.moe_intermediate_size, cfg.num_experts,
                top_k=cfg.moe_top_k, held=cm.experts_held,
                offset=cm.expert_offset, n_shared=0,
                scaling=cm.routed_scaling_factor, tp_size=self.tp_size,
                score="sigmoid"),
        }

    # ---- what differs inside the forward (per-shard, inside shard_map) ----

    def _mix(self, lp: Params, y: jax.Array, layer_pos, dtype) -> jax.Array:
        return self._mods["conv"].apply(lp["conv"], y, dtype)

    def _mlp(self, lp: Params, y: jax.Array, tp: TPSublayers,
             dtype) -> jax.Array:
        with jax.named_scope("dense_ffn"):   # the leading layers' SwiGLU
            return super()._mlp(lp, y, tp, dtype)

    @staticmethod
    def param_counts(cfg: ModelConfig) -> Dict[str, int]:
        """The family's parameters by part (`DecoderStack.num_params`); the
        tied embedding counts once."""
        cm = cfg.conv_moe
        d, h = cfg.attn_dim, cfg.head_dim
        mixer = {"conv": ShortConv(d, cm.conv_L_cache).num_params(),
                 "attn": 2 * d * d + 2 * d * cfg.kv_dim + 2 * h}
        dense = 3 * d * cfg.ffn_dim
        experts = (d * cfg.num_experts + cfg.num_experts      # router + bias
                   + cfg.experts_held * 3 * d * cm.moe_intermediate_size)
        out = {"embedding": cfg.vocab_size * d, "final_norm": d,
               "dense_layers": 0, "conv_layers": 0, "attn_layers": 0}
        for i, name in enumerate(cm.layer_types):
            is_dense = i < cm.num_dense_layers
            key = "dense_layers" if is_dense else KINDS[name] + "_layers"
            out[key] += mixer[KINDS[name]] + 2 * d + (dense if is_dense
                                                      else experts)
        return out

    @staticmethod
    def flops_per_step(cfg, batch, seqlen, num_params) -> float:
        """The held experts at a token's mean share of them, in the expert
        layers only; the tied embedding's one matrix is the head's matmul
        (its lookup is none); attention at the full T^2 in the attention
        layers only; the convolution's taps are no matmul."""
        cm = cfg.conv_moe
        n = num_params - idle_expert_params(
            cfg, cfg.num_layers - cm.num_dense_layers,
            cm.moe_intermediate_size)
        full = sum(kind == "full_attention" for kind in cm.layer_types)
        return (6 * n * batch * seqlen
                + 12 * full * batch * cfg.num_heads * seqlen * seqlen
                * cfg.head_dim)
