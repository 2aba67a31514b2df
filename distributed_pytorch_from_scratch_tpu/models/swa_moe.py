"""The `swa_moe` family: a grouped-query expert decoder whose attention
layers are of TWO KINDS over one parameter tree, sliding-window layers about
three to one with full-attention layers (the Trinity architecture, `afmoe`),
on the same decoder stack as the other families.

`SlidingWindowMoETransformer` is a subclass of `models/stack.DecoderStack`
and holds only what differs:

* **a pattern that is a leading segment and then periods**, derived from
  `cfg.swa_moe.layer_types` and `num_dense_layers` by run length
  (`models/conv_moe.layer_blocks` with this family's two kinds): the
  leading dense layers are one segment (`params["dense_layers"]`), what
  follows is cut into periods that repeat (`params["window_layers_<i>"]`,
  `params["full_layers_<i>"]`, stacked (periods, layers a period, ...)).
  The published 32 layers are 2 dense window layers, (window, full) once
  and (window x 3, full) x 7; the benchmark's cut is 1 dense layer and one
  period of four: the same program (`DecoderStack._pattern`);
* **the kind of a layer is its `_pattern` key's** (`_kind`), which the stack
  hands the layer body, since both kinds hold the same parameters: a
  `window` layer attends under `ops/attention.sliding_window(W)`
  (`_attn_mask`: a row sees itself and the W - 1 rows before it; a window
  that covers the sequence is the triangle, with the causal call) with RoPE
  (half-split pairs, the whole head) on q and k; a `full` layer attends to
  its whole past and takes NO positions at all (`unrotated_kinds`);
* **attention**: `num_heads` query heads over `num_kv_heads` key-value
  heads of `swa_moe.head_dim` (heads x width need not be the model's
  width), q and k normed per head, the heads' outputs times the sigmoid of
  a gate projected from the layer's input (`wg`, a leaf of its own beside
  `wq`); the stack's own (q, k, v) dispatch, so the flash kernels with
  their native grouping on the TPU, planned from the declared mask;
* **four norms a layer**: `x + N2(attn(N1(x)))`, then `x + N4(ffn(N3(x)))`
  (`post_attn_norm_key`, `post_ffn_norm_key`); the plain RMSNorm, weight 1
  at init;
* **the embedding's rows times sqrt(width)** (`embed_scale`, `mup_enabled`);
* **the expert FFN**: `parallel/moe.SharedRoutedFFN(score="sigmoid")`: the
  router scores all `cfg.num_experts`, the weights are the chosen scores
  normalised and times `route_scale`, the job holds
  `cfg.swa_moe.experts_held` of the experts (one chip's share of an
  expert-parallel deployment; None = all) and the shared expert; no token
  is dropped, no auxiliary loss: **the selection bias is the balancing**, a
  leaf no gradient reaches, updated after every optimizer step from the
  step's own counts at `load_balance_coeff` (`router_bias_speed`;
  training/optim.router_bias_step);
* an untied head, no bias anywhere.

What is not made to work is refused with a message: where the model is
built (`refuses`), by ZeRO 2/3 and the bucketed reducer
(`hand_reduced_grads`), by `models/decode.py` and the serving engines
(`decodable`: a window layer's cache is a ring of W rows, which
`serving/kv_manager.py`'s pools do not hold beside a growing one).

Named scopes inside the step, for a device trace's `op_name`: `gqa_attn`
(the projections, q/k norms, RoPE, the gate and `W_o`; the flash calls stay
the kernels' own, and a window layer's carry `_window` in their names),
`dense_ffn`, and `moe_route`, `moe_experts`, `moe_shared` (parallel/moe.py).
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax

from ..config import ModelConfig
from ..ops.attention import CAUSAL, live_entries, sliding_window
from ..parallel.linear import ColumnParallelLinear, RowParallelLinear
from ..parallel.moe import SharedRoutedFFN
from ..parallel.norm import RMSNorm
from .conv_moe import layer_blocks, pattern_of
from .stack import DecoderStack, Params, TPSublayers, idle_expert_params

KINDS = {"sliding_attention": "window", "full_attention": "full"}
ATTENTION = ("wq", "wk", "wv", "wg", "q_norm", "k_norm", "wo")
DENSE = ("gate_proj", "up_proj", "down_proj")


def module_names(dense: bool) -> Tuple[str, ...]:
    """The modules of a layer, of either kind."""
    return ("norm1", *ATTENTION, "norm2", "norm3",
            *(DENSE if dense else ("moe",)), "norm4")


@dataclass(frozen=True)
class SlidingWindowMoETransformer(DecoderStack):
    """The swa_moe family (module docstring)."""

    family = "swa_moe"
    ffn_inputs = 2            # the leading layers' SwiGLU: gate and up
    tied_head = False
    decodable = False
    hand_reduced_grads = False
    config_extra = "swa_moe"
    attn_scope = "gqa_attn"
    _router_aux_losses = False
    ffn_norm_key = "norm3"
    post_attn_norm_key = "norm2"
    post_ffn_norm_key = "norm4"
    unrotated_kinds = ("full",)
    refuses = {
        "pp_size > 1": "the pipeline splits one segment of identical "
                       "layers; this family has a leading segment and then "
                       "periods of two kinds of layer",
        "cp_size > 1": "the ring and Ulysses paths mask by a causal order "
                       "of positions; a window's left edge is not in them",
        "ep_size > 1": "a job holds one share of the experts, "
                       "cfg.swa_moe.experts_held; the all-to-all between "
                       "shares is not written",
        "sequence_parallel=True": "the router reads whole sequences",
        "attn_t_real": "pad tokens would be routed, and the declared mask "
                       "takes no real length",
        "ZeRO stage 3": "",
    }

    def _check_facts(self):
        sw = self.cfg.swa_moe
        if len(sw.layer_types) != self.cfg.num_layers:
            raise ValueError(
                f"layer_types names {len(sw.layer_types)} layers, num_layers "
                f"is {self.cfg.num_layers}")
        if not 0 <= sw.num_dense_layers < self.cfg.num_layers:
            raise ValueError(
                f"num_dense_layers {sw.num_dense_layers} must leave an "
                f"expert layer among {self.cfg.num_layers} layers")
        if sw.sliding_window < 1:
            raise ValueError(f"sliding_window {sw.sliding_window}: a row "
                             f"sees itself at least")
        self._blocks    # a pattern the family cannot cut is refused here

    # ---- the layer pattern ----

    @functools.cached_property
    def _blocks(self):
        sw = self.cfg.swa_moe
        return layer_blocks(sw.layer_types, sw.num_dense_layers, KINDS,
                            self.family)

    @property
    def _pattern(self):
        return pattern_of(self._blocks)

    @property
    def _segments(self):
        """(parameter key, layers, module names) of every stacked key."""
        return tuple((key, (repeats or 1) * n, module_names(dense))
                     for repeats, parts in self._blocks
                     for key, _, dense, n in parts)

    def _kind(self, key: str) -> str:
        return next(kind for _, parts in self._blocks
                    for at, kind, _, _ in parts if at == key)

    # ---- facts for the stack, the step and training/memory.py ----

    @property
    def head_dim(self) -> int:
        return self.cfg.swa_moe.head_dim

    @property
    def embed_scale(self) -> "float | None":
        return math.sqrt(self.d) if self.cfg.swa_moe.mup_enabled else None

    @property
    def router_bias_speed(self) -> "float | None":
        return self.cfg.swa_moe.load_balance_coeff or None

    @property
    def layer_extra_elems_per_token(self) -> float:
        """What an expert layer's backward holds at its fullest beside the
        d-wide tensors the dense skeleton counts, in elements of the
        compute dtype a token: q, its rotated copy, the gate's logits, the
        heads' output, the gated copy and the two cotangents the flash
        backward reads and writes at heads x head_dim where the skeleton
        counts them at d, k and v with their rotated copies and cotangents,
        the two post-norms' inputs; and one chunk of the expert dispatch
        (`SharedRoutedFFN.chunk_share` of a token's pairs): rows in and
        out with their cotangents, the outputs and the scatter's operand in
        float32 (twice an element), and the hidden activations `[gate |
        up]`, their product and both cotangents, beside the shared
        expert's. At a held share of 1/8 the chunk is one mean share, an
        eighth of all pairs, 1 row a token (six shares, 6 rows a token,
        until PR 50, when the chunk was what sized the step). The last
        term takes 1.69 d a token back off and is SET FROM THE CHIP'S
        READING: cell 9 on a v5e counts 14.695 GiB at rung `true`, the
        rung `auto` picks there, for a step this makes 14.89 (ledger, PR
        61; my chip run, PR 62; without the term it made 14.99)."""
        moe = self._mods["moe"]
        chunk_rows = moe.chunk_share * moe.top_k
        f = self.cfg.swa_moe.moe_intermediate_size / self.tp_size
        attn = (7 * self.cfg.num_heads * self.head_dim + 6 * self.kv_dim
                ) / self.tp_size
        return (attn + (chunk_rows + moe.n_shared) * (6 * self.d + 5 * f)
                - 1.69 * self.d / self.tp_size)

    # ---- sub-module definitions ----

    @functools.cached_property
    def _mods(self) -> Dict[str, Any]:
        cfg, sw = self.cfg, self.cfg.swa_moe
        d, eps = self.d, sw.rms_norm_eps
        qd = cfg.num_heads * sw.head_dim
        col = functools.partial(ColumnParallelLinear, add_bias=False,
                                gather_output=False)
        row = functools.partial(RowParallelLinear, add_bias=False,
                                split_input=False)
        return {
            **{f"norm{i}": RMSNorm(d, eps) for i in (1, 2, 3, 4)},
            "wq": col(d, qd),
            "wk": col(d, self.kv_dim),
            "wv": col(d, self.kv_dim),
            "wg": col(d, qd),           # the output gate's logits
            # one weight vector for all query heads, one for all key heads
            "q_norm": RMSNorm(sw.head_dim, eps),
            "k_norm": RMSNorm(sw.head_dim, eps),
            "wo": row(qd, d),
            "gate_proj": col(d, cfg.ffn_dim),
            "up_proj": col(d, cfg.ffn_dim),
            "down_proj": row(cfg.ffn_dim, d),
            "moe": SharedRoutedFFN(
                d, sw.moe_intermediate_size, cfg.num_experts,
                top_k=cfg.moe_top_k, held=sw.experts_held,
                offset=sw.expert_offset, n_shared=sw.num_shared_experts,
                scaling=sw.route_scale, tp_size=self.tp_size,
                score="sigmoid"),
        }

    # ---- what differs inside the forward (per-shard, inside shard_map) ----

    def _attn_mask(self, t: int, kind=None):
        """A window layer's band; None (the causal call) for a full layer
        and for a window that covers the sequence."""
        window = self.cfg.swa_moe.sliding_window
        if kind != "window" or window >= t:
            return None
        return sliding_window(window)

    def _mlp(self, lp: Params, y: jax.Array, tp: TPSublayers,
             dtype) -> jax.Array:
        with jax.named_scope("dense_ffn"):   # the leading layers' SwiGLU
            return super()._mlp(lp, y, tp, dtype)

    @staticmethod
    def param_counts(cfg: ModelConfig) -> Dict[str, int]:
        """The family's parameters by part (`DecoderStack.num_params`)."""
        sw = cfg.swa_moe
        d, h = cfg.attn_dim, sw.head_dim
        attn = 3 * d * cfg.num_heads * h + 2 * d * cfg.kv_heads * h + 2 * h
        dense = 3 * d * cfg.ffn_dim
        experts = (d * cfg.num_experts + cfg.num_experts      # router + bias
                   + (cfg.experts_held + sw.num_shared_experts)
                   * 3 * d * sw.moe_intermediate_size)
        out = {"embedding_and_head": 2 * cfg.vocab_size * d, "final_norm": d,
               "dense_layers": 0, "window_layers": 0, "full_layers": 0}
        for i, name in enumerate(sw.layer_types):
            is_dense = i < sw.num_dense_layers
            key = "dense_layers" if is_dense else KINDS[name] + "_layers"
            out[key] += attn + 4 * d + (dense if is_dense else experts)
        return out

    @staticmethod
    def flops_per_step(cfg, batch, seqlen, num_params) -> float:
        """The held experts at a token's mean share of them, in the expert
        layers only (the shared expert whole); the embedding's lookup is no
        matmul; attention at each kind's LIVE entries: the triangle in a
        full layer, the band of `sliding_window` rows in a window layer."""
        sw = cfg.swa_moe
        n = num_params - cfg.vocab_size * cfg.attn_dim - idle_expert_params(
            cfg, cfg.num_layers - sw.num_dense_layers,
            sw.moe_intermediate_size)
        live = sum(live_entries(
            sliding_window(sw.sliding_window)
            if KINDS[name] == "window" else CAUSAL, seqlen)
            for name in sw.layer_types)
        return (6 * n * batch * seqlen
                + 12 * batch * cfg.num_heads * live * sw.head_dim)
