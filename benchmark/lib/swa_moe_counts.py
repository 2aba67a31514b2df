"""Operations, bytes and parameters of the swa_moe family from its shapes:
the yardstick's own counts for the metrics the `train_swa_moe` runner feeds
(pinned by benchmark/tests/test_swa_moe_counts.py).

Conventions, beside those of benchmark/lib/flops.py and
benchmark/lib/conv_moe_counts.py:

* **Parameters** (`param_counts`): what ONE job holds, the experts HELD
  beside the shared expert and the vocabulary slice (an untied head); not
  the published model. The selection bias (128 a layer) is counted: it is
  state the job holds, though no gradient reaches it.
* **Live entries** (`live_entries`): a full layer's causal triangle `T (T +
  1) / 2` a head and sequence, a window layer's band `W (2 T - W + 1) / 2`
  (a row sees itself and the W - 1 rows before it).
* **Forward FLOPs a token** (`forward_flops_per_token`): 2 x the parameters
  a token's matmuls touch here (the four attention projections, the gate's
  among them; the router; the shared expert; the routed experts at
  `rows_per_token`, the step's counter summed over the expert layers; the
  head once; the embedding's lookup is no matmul), the scores at each
  kind's LIVE entries (`4 H head_dim` an entry for QK^T and PV together).
* **Active FLOPs per trained token** (`train_flops_per_token`), the
  numerator of `train_step.active_mfu_pct`: 6 x the same parameters, plus
  attention: a FULL layer at the full T^2 (`12 H T head_dim` a token, the
  convention of every `mfu` in this benchmark: twice its triangle), a
  WINDOW layer at its live entries and no more (`12 H head_dim` an entry:
  the band has no square to be counted at, and a count above what the mask
  needs would read as utilisation). Recompute and the padding rows of a
  chunk computed whole are not counted.
* **A flash call** (`flash_call_cost`): `n_head` query heads over
  `n_kv_head` key-value heads, all `head_dim` wide, at a kind's live
  entries, 4 x head_dim FLOPs an entry forward and 10 backward; the
  operands' bytes with K and V once a key-value head.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Tuple

from benchmark.lib.flops import CallCost

WINDOW, FULL = "sliding_attention", "full_attention"


class SwaMoESizes(NamedTuple):
    d_model: int
    n_head: int
    n_kv_head: int
    head_dim: int           # heads x head_dim need not be d_model
    window: int
    layer_types: Tuple[str, ...]   # WINDOW | FULL, as run here
    n_dense: int            # leading layers with a dense SwiGLU
    d_dense: int
    d_expert: int
    n_shared: int
    n_routed: int           # experts the router scores (published)
    n_held: int             # of which this job holds
    top_k: int
    vocab: int              # the slice held

    @property
    def n_layer(self) -> int:
        return len(self.layer_types)

    @property
    def window_layers(self) -> int:
        return self.layer_types.count(WINDOW)

    @property
    def full_layers(self) -> int:
        return self.layer_types.count(FULL)

    @property
    def expert_layers(self) -> int:
        return self.n_layer - self.n_dense


def attention_matmul_params(s: SwaMoESizes) -> int:
    d = s.d_model       # wq, wg, wo; wk, wv
    return 3 * d * s.n_head * s.head_dim + 2 * d * s.n_kv_head * s.head_dim


def attention_params(s: SwaMoESizes) -> int:
    return attention_matmul_params(s) + 2 * s.head_dim     # q and k norms


def dense_mlp_params(s: SwaMoESizes) -> int:
    return 3 * s.d_model * s.d_dense


def expert_params(s: SwaMoESizes) -> int:
    return 3 * s.d_model * s.d_expert


def ffn_params(s: SwaMoESizes, held: "int | None" = None) -> int:
    """An expert layer's FFN: the router, its selection bias, the shared
    expert and the experts `held` (this job's by default)."""
    held = s.n_held if held is None else held
    return (s.d_model * s.n_routed + s.n_routed
            + (s.n_shared + held) * expert_params(s))


def param_counts(s: SwaMoESizes) -> Dict[str, int]:
    """Parameters this job holds, by part."""
    d = s.d_model
    norms = 4 * d
    layers = sum(attention_params(s) + norms
                 + (dense_mlp_params(s) if i < s.n_dense else ffn_params(s))
                 for i in range(s.n_layer))
    return {
        "attention": attention_params(s),
        "dense_mlp": dense_mlp_params(s),
        "expert": expert_params(s),
        "ffn": ffn_params(s),
        "ffn_uncut": ffn_params(s, s.n_routed),
        "dense_layer": attention_params(s) + norms + dense_mlp_params(s),
        "expert_layer": attention_params(s) + norms + ffn_params(s),
        "expert_layer_uncut": attention_params(s) + norms
        + ffn_params(s, s.n_routed),
        "embedding_and_head": 2 * s.vocab * d,
        "total": layers + 2 * s.vocab * d + d,
    }


def live_entries(seqlen: int, window: "int | None") -> int:
    """Score entries a head and sequence leave live: a full layer's (`window`
    None) triangle, a window layer's band."""
    w = seqlen if window is None else min(window, seqlen)
    return w * (2 * seqlen - w + 1) // 2


def live_entries_per_token(s: SwaMoESizes, seqlen: int) -> float:
    """Live entries a head and token, summed over the layers."""
    return (s.window_layers * live_entries(seqlen, s.window)
            + s.full_layers * live_entries(seqlen, None)) / seqlen


def active_matmul_params(s: SwaMoESizes, rows_per_token: float) -> float:
    """Parameters one token's matmuls touch in this job. `rows_per_token` is
    summed over the expert layers."""
    d = s.d_model
    return (s.n_layer * attention_matmul_params(s)
            + s.n_dense * dense_mlp_params(s)
            + s.expert_layers * (d * s.n_routed
                                 + s.n_shared * expert_params(s))
            + rows_per_token * expert_params(s)
            + s.vocab * d)


def forward_flops_per_token(s: SwaMoESizes, seqlen: int,
                            rows_per_token: float) -> float:
    scores = 4.0 * s.n_head * s.head_dim * live_entries_per_token(s, seqlen)
    return 2.0 * active_matmul_params(s, rows_per_token) + scores


def train_flops_per_token(s: SwaMoESizes, seqlen: int,
                          rows_per_token: float) -> float:
    attention = 12.0 * s.n_head * s.head_dim * (
        s.full_layers * seqlen
        + s.window_layers * live_entries(seqlen, s.window) / seqlen)
    return 6.0 * active_matmul_params(s, rows_per_token) + attention


def flash_call_cost(batch: int, seqlen: int, s: SwaMoESizes, itemsize: int,
                    backward: bool, window: "int | None") -> CallCost:
    """One flash call over `batch` sequences of `seqlen` rows at the live
    entries of a full layer (`window` None) or of a window layer."""
    entries = batch * s.n_head * live_entries(seqlen, window)
    q = batch * s.n_head * seqlen * s.head_dim * itemsize
    kv = batch * s.n_kv_head * seqlen * s.head_dim * itemsize
    vector = batch * s.n_head * seqlen * 4
    if backward:        # q, o, do, dq; k, v, dk, dv; lse, delta
        return CallCost(10.0 * s.head_dim * entries,
                        4 * q + 4 * kv + 2 * vector)
    return CallCost(4.0 * s.head_dim * entries, 2 * q + 2 * kv + vector)
