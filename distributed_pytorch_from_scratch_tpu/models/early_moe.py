"""The `early_moe` family: a grouped-query expert decoder whose ROUTER READS
THE LAYER'S INPUT, before attention, over ReLU-gated experts, with
sliding-window and full attention layers three to one (the SmallThinker
architecture, `smallthinker`), on the same decoder stack as the other
families.

`EarlyRouterMoETransformer` is a subclass of `models/stack.DecoderStack` and
holds only what differs:

* **a pattern that is periods and nothing else**, derived from
  `cfg.early_moe.sliding_window_layout` by run length
  (`models/conv_moe.layer_blocks` with this family's two kinds and no
  leading dense layer): `params["full_layers_<i>"]` and
  `params["window_layers_<i>"]`, stacked (periods, layers a period, ...).
  The published 52 layers are (full, window x 3) x 13; the benchmark's cut
  is one period: the same program (`DecoderStack._pattern`);
* **the kind of a layer is its `_pattern` key's** (`_kind`), as in
  `models/swa_moe.py`: a `window` layer attends under
  `ops/attention.sliding_window(W)` (`_attn_mask`: a row sees itself and
  the W - 1 rows before it; a window that covers the sequence is the
  triangle, with the causal call) with RoPE (half-split pairs, the whole
  head) on q and k; a `full` layer attends to its whole past and takes NO
  positions at all (`unrotated_kinds`). `rope_layout` must say the same
  layers as `sliding_window_layout` (the published layouts do);
* **attention**: `num_heads` query heads over `num_kv_heads` key-value
  heads of `early_moe.head_dim` (28 over 4 published: a group of 7, not a
  power of two), no q/k norm, no gate, no bias; the stack's own (q, k, v)
  dispatch, so the flash kernels with their native grouping on the TPU,
  planned from the declared mask;
* **two norms a layer**: `x' = x + attn(N1(x))`, `x'' = x' + ffn(N2(x'))`;
* **the expert FFN**: `parallel/moe.SharedRoutedFFN(score="softmax",
  n_shared=0, activation="relu")`, every layer an expert layer. **The
  router reads x, the layer's input, before anything else in the layer**
  (`router_reads_layer_input`: the stack carries x past the attention half
  to `_ffn`, the expert layer takes it as `router_x`): `logits = x W_r`,
  the top-k logits, the weights a softmax over the chosen (= the softmax
  over all routed experts normalised over the chosen). The experts read
  `N2(x')` and are `W_down(relu(W_gate m) * (W_up m))`. The routing
  depends on nothing the attention half computes; nothing here forces
  where the compiler places it. The job holds `cfg.early_moe.experts_held`
  of the experts (one chip's share of an expert-parallel deployment; None
  = all); no token is dropped, no auxiliary loss, no selection bias:
  nothing balances this router;
* an untied head, the embedding with no multiplier, no bias anywhere.

What is not made to work is refused with a message: where the model is
built (`refuses`), by ZeRO 2/3 and the bucketed reducer
(`hand_reduced_grads`), by `models/decode.py` and the serving engines
(`decodable`: a window layer's cache is a ring of W rows, which
`serving/kv_manager.py`'s pools do not hold beside a growing one).

Named scopes inside the step, for a device trace's `op_name`: `gqa_attn`
(the projections, RoPE and `W_o`; the flash calls stay the kernels' own,
and a window layer's carry `_window` in their names, split or resident),
and `moe_route`, `moe_experts` (parallel/moe.py), with the router's
product, the top-k, `sort_pairs` and `index` under `moe_route/early`.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict

from ..config import ModelConfig
from ..ops.attention import CAUSAL, live_entries, sliding_window
from ..parallel.linear import ColumnParallelLinear, RowParallelLinear
from ..parallel.moe import SharedRoutedFFN
from ..parallel.norm import RMSNorm
from .conv_moe import layer_blocks, pattern_of
from .stack import DecoderStack, idle_expert_params

# `sliding_window_layout`'s entries -> the kind of the layer
KINDS = {1: "window", 0: "full"}
MODULES = ("norm1", "wq", "wk", "wv", "wo", "norm2", "moe")


@dataclass(frozen=True)
class EarlyRouterMoETransformer(DecoderStack):
    """The early_moe family (module docstring)."""

    family = "early_moe"
    ffn_inputs = 0            # no dense MLP: every layer's FFN is routed
    tied_head = False
    decodable = False
    hand_reduced_grads = False
    config_extra = "early_moe"
    attn_scope = "gqa_attn"
    _router_aux_losses = False
    unrotated_kinds = ("full",)
    router_reads_layer_input = True
    refuses = {
        "pp_size > 1": "the pipeline splits one segment of identical "
                       "layers; this family has periods of two kinds of "
                       "layer",
        "cp_size > 1": "the ring and Ulysses paths mask by a causal order "
                       "of positions; a window's left edge is not in them",
        "ep_size > 1": "a job holds one share of the experts, "
                       "cfg.early_moe.experts_held; the all-to-all between "
                       "shares, which the early routing is for, is not "
                       "written",
        "sequence_parallel=True": "the router reads whole sequences of the "
                                  "layer's input",
        "attn_t_real": "pad tokens would be routed, and the declared mask "
                       "takes no real length",
        "ZeRO stage 3": "",
    }

    def _check_facts(self):
        em = self.cfg.early_moe
        if len(em.sliding_window_layout) != self.cfg.num_layers:
            raise ValueError(
                f"sliding_window_layout names "
                f"{len(em.sliding_window_layout)} layers, num_layers is "
                f"{self.cfg.num_layers}")
        if tuple(em.rope_layout) != tuple(em.sliding_window_layout):
            raise ValueError(
                "the early_moe family rotates q and k in its window layers "
                "and in no other: rope_layout must equal "
                f"sliding_window_layout, got {tuple(em.rope_layout)} and "
                f"{tuple(em.sliding_window_layout)}")
        if em.sliding_window_size < 1:
            raise ValueError(f"sliding_window_size {em.sliding_window_size}"
                             f": a row sees itself at least")
        self._blocks    # a pattern the family cannot cut is refused here

    # ---- the layer pattern ----

    @functools.cached_property
    def _blocks(self):
        return layer_blocks(self.cfg.early_moe.sliding_window_layout, 0,
                            KINDS, self.family)

    @property
    def _pattern(self):
        return pattern_of(self._blocks)

    @property
    def _segments(self):
        """(parameter key, layers, module names) of every stacked key."""
        return tuple((key, repeats * n, MODULES)
                     for repeats, parts in self._blocks
                     for key, _, _, n in parts)

    def _kind(self, key: str) -> str:
        return next(kind for _, parts in self._blocks
                    for at, kind, _, _ in parts if at == key)

    # ---- facts for the stack and training/memory.py ----

    @property
    def head_dim(self) -> int:
        return self.cfg.early_moe.head_dim

    @property
    def layer_extra_elems_per_token(self) -> float:
        """What a layer's backward holds at its fullest beside the d-wide
        tensors the dense skeleton counts, in elements of the compute dtype
        a token: q, its rotated copy, the heads' output and the two
        cotangents the flash backward reads and writes at heads x head_dim
        where the skeleton counts them at d, k and v with their rotated
        copies and cotangents; and one chunk of the expert dispatch
        (`SharedRoutedFFN.chunk_share` of a token's pairs: a quarter of
        them at a held share of a quarter, 1.5 rows a token at top-6): rows
        in and out with their cotangents, the outputs and the sums in
        float32 (twice an element), and the hidden activations `[gate |
        up]`, their product and both cotangents. The last term adds 9.5 d a
        token and is SET FROM THE CHIP'S READING (beside one chunk's
        buffers the walk's backward carries the input's cotangent and the
        sums whole, which the count above does not hold): cell 10 on a v5e
        counts 13.366 GiB at rung `true`, 14.002 at `flash` and 14.538 at
        `dots`, the rung `auto` picks, for steps this makes 13.53, 13.97
        and 14.54 (my chip runs, PR 71; until then the one chunk was ALL
        the pairs, the term took 16.47 d back off and the cell stood at
        `flash` with 14.459)."""
        moe = self._mods["moe"]
        chunk_rows = moe.chunk_share * moe.top_k
        f = self.cfg.early_moe.moe_ffn_hidden_size / self.tp_size
        attn = (5 * self.cfg.num_heads * self.head_dim + 6 * self.kv_dim
                - 2 * self.d) / self.tp_size
        return (attn + chunk_rows * (6 * self.d + 5 * f)
                + 9.5 * self.d / self.tp_size)

    # ---- sub-module definitions ----

    @functools.cached_property
    def _mods(self) -> Dict[str, Any]:
        cfg, em = self.cfg, self.cfg.early_moe
        d, eps = self.d, em.rms_norm_eps
        qd = cfg.num_heads * em.head_dim
        col = functools.partial(ColumnParallelLinear, add_bias=False,
                                gather_output=False)
        row = functools.partial(RowParallelLinear, add_bias=False,
                                split_input=False)
        return {
            "norm1": RMSNorm(d, eps),
            "wq": col(d, qd),
            "wk": col(d, self.kv_dim),
            "wv": col(d, self.kv_dim),
            "wo": row(qd, d),
            "norm2": RMSNorm(d, eps),
            "moe": SharedRoutedFFN(
                d, em.moe_ffn_hidden_size, cfg.num_experts,
                top_k=cfg.moe_top_k, held=em.experts_held,
                offset=em.expert_offset, n_shared=0, scaling=1.0,
                tp_size=self.tp_size, score="softmax", activation="relu"),
        }

    # ---- what differs inside the forward (per-shard, inside shard_map) ----

    def _attn_mask(self, t: int, kind=None):
        """A window layer's band; None (the causal call) for a full layer
        and for a window that covers the sequence."""
        window = self.cfg.early_moe.sliding_window_size
        if kind != "window" or window >= t:
            return None
        return sliding_window(window)

    @staticmethod
    def param_counts(cfg: ModelConfig) -> Dict[str, int]:
        """The family's parameters by part (`DecoderStack.num_params`)."""
        em = cfg.early_moe
        d, h = cfg.attn_dim, em.head_dim
        attn = 2 * d * cfg.num_heads * h + 2 * d * cfg.kv_heads * h
        experts = (d * cfg.num_experts                           # router
                   + cfg.experts_held * 3 * d * em.moe_ffn_hidden_size)
        out = {"embedding_and_head": 2 * cfg.vocab_size * d, "final_norm": d,
               "window_layers": 0, "full_layers": 0}
        for flag in em.sliding_window_layout:
            out[KINDS[flag] + "_layers"] += attn + 2 * d + experts
        return out

    @staticmethod
    def flops_per_step(cfg, batch, seqlen, num_params) -> float:
        """The held experts at a token's mean share of them, in every
        layer; the embedding's lookup is no matmul; attention at each
        kind's LIVE entries: the triangle in a full layer, the band of
        `sliding_window_size` rows in a window layer."""
        em = cfg.early_moe
        n = num_params - cfg.vocab_size * cfg.attn_dim - idle_expert_params(
            cfg, cfg.num_layers, em.moe_ffn_hidden_size)
        live = sum(live_entries(
            sliding_window(em.sliding_window_size)
            if KINDS[flag] == "window" else CAUSAL, seqlen)
            for flag in em.sliding_window_layout)
        return (6 * n * batch * seqlen
                + 12 * batch * cfg.num_heads * live * em.head_dim)
