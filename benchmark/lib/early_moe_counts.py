"""Operations, bytes and parameters of the early_moe family from its shapes:
the yardstick's own counts for the metrics the `train_early_moe` runner
feeds (pinned by benchmark/tests/test_early_moe_counts.py).

Conventions, beside those of benchmark/lib/flops.py and
benchmark/lib/swa_moe_counts.py:

* **Parameters** (`param_counts`): what ONE job holds, the experts HELD and
  the vocabulary slice (an untied head); not the published model. No
  shared expert, no bias, two norms a layer, every layer an expert layer.
* **Live entries** (`live_entries`, swa_moe_counts'): a full layer's causal
  triangle `T (T + 1) / 2` a head and sequence, a window layer's band `W (2
  T - W + 1) / 2` (a row sees itself and the W - 1 rows before it).
* **Forward FLOPs a token** (`forward_flops_per_token`): 2 x the parameters
  a token's matmuls touch here (the four attention projections; the router;
  the routed experts at `rows_per_token`, the step's counter summed over
  the layers; the head once; the embedding's lookup is no matmul), the
  scores at each kind's LIVE entries (`4 H head_dim` an entry for QK^T and
  PV together).
* **Active FLOPs per trained token** (`train_flops_per_token`), the
  numerator of `train_step.active_mfu_pct`: 6 x the same parameters, plus
  attention: a FULL layer at the full T^2 (`12 H T head_dim` a token, the
  convention of every `mfu` in this benchmark: twice its triangle), a
  WINDOW layer at its live entries and no more (`12 H head_dim` an entry).
  Recompute is not counted, and ReLU's zeros are counted as any other
  value: the grouped products are dense over a row's hidden width.
* **A flash call** (`flash_call_cost`): swa_moe_counts' own, imported: it
  reads `n_head`, `n_kv_head` and `head_dim` of the sizes it is handed, and
  `benchmark/lib/swa_scopes.flash_roofline_pct` reads `window` beside them.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Tuple

from benchmark.lib.swa_moe_counts import (flash_call_cost,  # noqa: F401
                                          live_entries)


class EarlyMoESizes(NamedTuple):
    d_model: int
    n_head: int
    n_kv_head: int
    head_dim: int           # heads x head_dim need not be d_model
    window: int
    layout: Tuple[int, ...]  # 1: a window layer, 0: a full layer, as run
    d_expert: int
    n_routed: int           # experts the router scores (published)
    n_held: int             # of which this job holds
    top_k: int
    vocab: int              # the slice held

    @property
    def n_layer(self) -> int:
        return len(self.layout)

    @property
    def window_layers(self) -> int:
        return sum(self.layout)

    @property
    def full_layers(self) -> int:
        return self.n_layer - self.window_layers

    @property
    def expert_layers(self) -> int:
        return self.n_layer


def attention_params(s: EarlyMoESizes) -> int:
    d = s.d_model       # wq, wo; wk, wv: no bias, no q/k norm, no gate
    return 2 * d * s.n_head * s.head_dim + 2 * d * s.n_kv_head * s.head_dim


def expert_params(s: EarlyMoESizes) -> int:
    return 3 * s.d_model * s.d_expert


def ffn_params(s: EarlyMoESizes, held: "int | None" = None) -> int:
    """A layer's FFN: the router and the experts `held` (this job's by
    default)."""
    held = s.n_held if held is None else held
    return s.d_model * s.n_routed + held * expert_params(s)


def param_counts(s: EarlyMoESizes) -> Dict[str, int]:
    """Parameters this job holds, by part."""
    d = s.d_model
    layer = attention_params(s) + 2 * d + ffn_params(s)
    return {
        "attention": attention_params(s),
        "expert": expert_params(s),
        "ffn": ffn_params(s),
        "ffn_uncut": ffn_params(s, s.n_routed),
        "layer": layer,
        "layer_uncut": attention_params(s) + 2 * d
        + ffn_params(s, s.n_routed),
        "embedding_and_head": 2 * s.vocab * d,
        "total": s.n_layer * layer + 2 * s.vocab * d + d,
    }


def live_entries_per_token(s: EarlyMoESizes, seqlen: int) -> float:
    """Live entries a head and token, summed over the layers."""
    return (s.window_layers * live_entries(seqlen, s.window)
            + s.full_layers * live_entries(seqlen, None)) / seqlen


def active_matmul_params(s: EarlyMoESizes, rows_per_token: float) -> float:
    """Parameters one token's matmuls touch in this job. `rows_per_token` is
    summed over the layers."""
    d = s.d_model
    return (s.n_layer * (attention_params(s) + d * s.n_routed)
            + rows_per_token * expert_params(s)
            + s.vocab * d)


def forward_flops_per_token(s: EarlyMoESizes, seqlen: int,
                            rows_per_token: float) -> float:
    scores = 4.0 * s.n_head * s.head_dim * live_entries_per_token(s, seqlen)
    return 2.0 * active_matmul_params(s, rows_per_token) + scores


def train_flops_per_token(s: EarlyMoESizes, seqlen: int,
                          rows_per_token: float) -> float:
    attention = 12.0 * s.n_head * s.head_dim * (
        s.full_layers * seqlen
        + s.window_layers * live_entries(seqlen, s.window) / seqlen)
    return 6.0 * active_matmul_params(s, rows_per_token) + attention
