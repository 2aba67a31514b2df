"""Operations, bytes and parameters of the dsa_moe family from its shapes:
the yardstick's own counts for the metrics the `train_dsa_moe` runner feeds
(pinned by benchmark/tests/test_dsa_moe_counts.py).

Conventions, beside those of benchmark/lib/flops.py and
benchmark/lib/bd_moe_counts.py:

* **Parameters** (`param_counts`): what ONE job holds, the experts HELD and
  the vocabulary slice; not the published model. A layer's attention holds
  the indexer (`indexer_params`: three projections and the index key's
  LayerNorm).
* **The MATHEMATICS is counted, not what a walk computes.** A row t keeps
  `min(t + 1, topk)` keys (`kept_pairs`), and the attention over them is 4 x
  head_dim FLOPs a pair and head forward and 10 backward, whichever kernel
  makes them and however many masked pairs it computes on the way
  (`dsa.flash_computed_over_live` says how many). The indexer scores the
  whole TRIANGLE (`triangle_pairs`), 2 x heads x width FLOPs a pair, once
  forward for the selection; its loss makes the score once more and
  transposes it twice (6 x heads x width a pair of the triangle: every pair
  is differentiable only where kept, but a kernel cannot know which before
  it has made the score, and the mathematics needs the score of every pair
  to choose) and the heads' summed probabilities over the KEPT pairs (2 x
  head_dim a pair and head). A kernel that makes the score again (each of
  the five walks does) spends time, not work.
* **Active FLOPs per trained token** (`train_flops_per_token`), the
  numerator of `train_step.active_mfu_pct`: 6 x the parameters a token's
  matmuls touch here (attention, indexer and router every layer, the routed
  experts at `rows_per_token`, the head; the embedding's lookup is no
  matmul), plus the three terms above per token.
* **The kernels** (`dsa_flash_cost`, `dsa_select_cost`,
  `dsa_index_loss_cost`): the mathematics of one call and its operands'
  bytes once each.
"""

from __future__ import annotations

from typing import Dict, NamedTuple

from benchmark.lib.flops import CallCost


class DsaMoESizes(NamedTuple):
    d_model: int
    n_layer: int            # as run here; every layer an expert layer
    n_head: int
    n_kv_head: int
    head_dim: int           # heads x head_dim need not be d_model
    d_expert: int
    n_routed: int           # experts the router scores (published)
    n_held: int             # of which this job holds
    top_k: int              # experts a token takes
    vocab: int              # the slice held
    index_heads: int
    index_dim: int
    index_topk: int         # keys a row keeps

    @property
    def expert_layers(self) -> int:
        return self.n_layer


def kept_pairs(seqlen: int, topk: int) -> int:
    """(row, key) pairs a sequence keeps: `sum_t min(t + 1, topk)`."""
    k = min(topk, seqlen)
    return k * (k + 1) // 2 + (seqlen - k) * k


def triangle_pairs(seqlen: int) -> int:
    return seqlen * (seqlen + 1) // 2


def attention_matmul_params(s: DsaMoESizes) -> int:
    d = s.d_model
    return 2 * d * s.n_head * s.head_dim + 2 * d * s.n_kv_head * s.head_dim


def indexer_matmul_params(s: DsaMoESizes) -> int:
    return s.d_model * (s.index_heads * s.index_dim + s.index_dim
                        + s.index_heads)


def indexer_params(s: DsaMoESizes) -> int:
    return indexer_matmul_params(s) + 2 * s.index_dim       # the LayerNorm


def attention_params(s: DsaMoESizes) -> int:
    return (attention_matmul_params(s) + 2 * s.head_dim     # q and k norms
            + indexer_params(s))


def expert_params(s: DsaMoESizes) -> int:
    return 3 * s.d_model * s.d_expert


def ffn_params(s: DsaMoESizes, held: "int | None" = None) -> int:
    """A layer's FFN: the router and the experts `held` (this job's by
    default). No shared expert, no selection bias."""
    held = s.n_held if held is None else held
    return s.d_model * s.n_routed + held * expert_params(s)


def param_counts(s: DsaMoESizes) -> Dict[str, int]:
    """Parameters this job holds, by part."""
    d = s.d_model
    layer = attention_params(s) + 2 * d + ffn_params(s)
    return {
        "attention": attention_params(s) - indexer_params(s),
        "indexer": indexer_params(s),
        "router": d * s.n_routed,
        "expert": expert_params(s),
        "ffn": ffn_params(s),
        "layer": layer,
        "layer_uncut": attention_params(s) + 2 * d
        + ffn_params(s, s.n_routed),
        "embedding_and_head": 2 * s.vocab * d,
        "total": s.n_layer * layer + 2 * s.vocab * d + d,
    }


def active_matmul_params(s: DsaMoESizes, rows_per_token: float) -> float:
    """Parameters one token's matmuls touch in this job: every layer's
    attention, indexer and router, the routed experts at `rows_per_token`
    (summed over the layers), the head."""
    return (s.n_layer * (attention_matmul_params(s)
                         + indexer_matmul_params(s)
                         + s.d_model * s.n_routed)
            + rows_per_token * expert_params(s)
            + s.vocab * s.d_model)


def mechanism_flops_per_token(s: DsaMoESizes, seqlen: int) -> Dict[str, float]:
    """The mechanism's FLOPs a token and LAYER, forward and backward
    together, by part (module docstring)."""
    kept = kept_pairs(seqlen, s.index_topk) / seqlen
    triangle = triangle_pairs(seqlen) / seqlen
    index = 2.0 * s.index_heads * s.index_dim
    return {"attend": 12.0 * s.n_head * s.head_dim * kept,
            "index_select": index * triangle,
            "index_loss": 3.0 * index * triangle
            + 2.0 * s.n_head * s.head_dim * kept}


def train_flops_per_token(s: DsaMoESizes, seqlen: int,
                          rows_per_token: float) -> float:
    return (6.0 * active_matmul_params(s, rows_per_token)
            + s.n_layer * sum(mechanism_flops_per_token(s, seqlen).values()))


def _operands(batch: int, seqlen: int, s: DsaMoESizes, itemsize: int):
    """Bytes of one sequence batch's (q-like, k-like, index) operands."""
    q = batch * s.n_head * seqlen * s.head_dim * itemsize
    kv = batch * s.n_kv_head * seqlen * s.head_dim * itemsize
    index = batch * seqlen * ((s.index_heads + 1) * s.index_dim * itemsize
                              + 4 * s.index_heads + 8)   # qI, kI, w, tau, cut
    return q, kv, index


def dsa_flash_cost(batch: int, seqlen: int, s: DsaMoESizes, itemsize: int,
                   backward: bool) -> CallCost:
    """The attention over the kept pairs of `batch` sequences, one forward
    (4 x head_dim a pair and head) or one backward (10 x; two kernels share
    it): each operand and result once."""
    pairs = batch * s.n_head * kept_pairs(seqlen, s.index_topk)
    q, kv, index = _operands(batch, seqlen, s, itemsize)
    vector = batch * s.n_head * seqlen * 4
    if backward:        # q, do, dq; k, v, dk, dv; lse, delta; both kernels
        return CallCost(10.0 * s.head_dim * pairs,
                        3 * q + 4 * kv + 2 * vector + 2 * index)
    return CallCost(4.0 * s.head_dim * pairs, 2 * q + 2 * kv + vector + index)


def dsa_select_cost(batch: int, seqlen: int, s: DsaMoESizes,
                    itemsize: int) -> CallCost:
    """The score of every causal pair, once, and the choice (no FLOPs of
    its own are counted: compares are not matmuls)."""
    _, _, index = _operands(batch, seqlen, s, itemsize)
    return CallCost(2.0 * s.index_heads * s.index_dim * batch
                    * triangle_pairs(seqlen), index)


def dsa_index_loss_cost(batch: int, seqlen: int, s: DsaMoESizes,
                        itemsize: int) -> CallCost:
    """The indexer's loss and its gradients: the score and its two
    transposes over the triangle, the heads' probabilities over the kept
    pairs."""
    q, kv, index = _operands(batch, seqlen, s, itemsize)
    flops = batch * (6.0 * s.index_heads * s.index_dim
                     * triangle_pairs(seqlen)
                     + 2.0 * s.n_head * s.head_dim
                     * kept_pairs(seqlen, s.index_topk))
    return CallCost(flops, q + kv // 2 + 2 * index
                    + batch * s.n_head * seqlen * 4)
