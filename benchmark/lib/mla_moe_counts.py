"""Operations, bytes and parameters of the mla_moe family from its shapes:
the yardstick's own counts for the metrics the `train_scopes` runner feeds
(pinned by benchmark/tests/test_mla_moe_counts.py).

Conventions, beside those of benchmark/lib/flops.py:

* **Parameters** (`param_counts`): what ONE job holds, the experts HELD and
  the vocabulary slice, not the published model.
* **Active FLOPs per trained token** (`train_flops_per_token`), the
  numerator of `train_step.active_mfu_pct`: 6 x the parameters a token's
  matmuls touch here, plus attention at the full T^2 with q/k's width for
  QK^T and v's for PV. A token's matmuls: every attention projection, the
  dense layer's MLP, each expert layer's router and shared expert, the
  ROUTED EXPERTS AT THE ROWS THE STEP'S COUNTER SAYS WERE COMPUTED HERE
  (`rows_per_token`: pairs whose expert is held, summed over the expert
  layers, over the tokens), the multi-token-prediction module's projection,
  and the head TWICE (the main model's and the module's). The embedding's
  lookup is no matmul. Recompute is not counted.
* **Flash calls at two widths** (`flash_call_cost`): causal entries
  `T (T + 1) / 2` a head row; forward QK^T at `qk` and PV at `v` (2 FLOPs
  a multiply-add); backward the scores again, dQ, dK at `qk` and dV, dP at
  `v`. Bytes: q, k (and dq, dk) at `qk`, v, o (and do, dv) at `v`, each
  once, and the float32 row vectors. The program may pad v to q's width
  inside; the count stays the mathematics'.
* **Grouped expert products** (`expert_products_cost`): for `rows` (token,
  choice) pairs through one layer's held experts, forward and backward (3
  passes of 3 products: 18 d f FLOPs a row), and per pass the held
  experts' three matrices and the rows in and out once.
"""

from __future__ import annotations

from typing import Dict, NamedTuple

from benchmark.lib.flops import CallCost


class LatentMoESizes(NamedTuple):
    d_model: int
    n_head: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    d_ff: int              # the dense layers' SwiGLU width
    d_expert: int          # an expert's SwiGLU width
    n_routed: int          # experts the router scores (published)
    n_held: int            # of which this job holds
    n_shared: int
    top_k: int
    n_dense_layer: int
    n_expert_layer: int
    n_mtp: int
    vocab: int             # the slice held

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def attention_layers(self) -> int:
        return self.n_dense_layer + self.n_expert_layer + self.n_mtp

    @property
    def expert_layers(self) -> int:
        return self.n_expert_layer + self.n_mtp


def attention_matmul_params(s: LatentMoESizes) -> int:
    d, h = s.d_model, s.n_head
    return (d * s.q_lora_rank + s.q_lora_rank * h * s.qk_head_dim
            + d * (s.kv_lora_rank + s.qk_rope_head_dim)
            + s.kv_lora_rank * h * (s.qk_nope_head_dim + s.v_head_dim)
            + h * s.v_head_dim * d)


def expert_params(s: LatentMoESizes) -> int:
    return 3 * s.d_model * s.d_expert


def param_counts(s: LatentMoESizes) -> Dict[str, int]:
    """Parameters this job holds, by part."""
    d = s.d_model
    attention = (attention_matmul_params(s) + s.q_lora_rank + s.kv_lora_rank
                 + 2 * d)                       # latent norms, layer norms
    expert_layer = (attention + d * s.n_routed + s.n_routed
                    + (s.n_held + s.n_shared) * expert_params(s))
    return {
        "attention": attention,
        "expert_layer": expert_layer,
        "dense_layer": attention + 3 * d * s.d_ff,
        "mtp_module": expert_layer + 2 * d * d + 3 * d,
        "embedding_and_head": 2 * s.vocab * d,
        "total": (s.n_dense_layer * (attention + 3 * d * s.d_ff)
                  + s.n_expert_layer * expert_layer
                  + s.n_mtp * (expert_layer + 2 * d * d + 3 * d)
                  + 2 * s.vocab * d + d),
    }


def active_matmul_params(s: LatentMoESizes, rows_per_token: float) -> float:
    """Parameters one token's matmuls touch in this job. `rows_per_token`
    is summed over the expert layers (the module's included)."""
    d = s.d_model
    return (s.attention_layers * attention_matmul_params(s)
            + s.n_dense_layer * 3 * d * s.d_ff
            + s.expert_layers * (d * s.n_routed
                                 + s.n_shared * expert_params(s))
            + rows_per_token * expert_params(s)
            + s.n_mtp * 2 * d * d
            + (1 + s.n_mtp) * s.vocab * d)


def train_flops_per_token(s: LatentMoESizes, seqlen: int,
                          rows_per_token: float) -> float:
    attention = (6 * s.attention_layers * s.n_head
                 * (s.qk_head_dim + s.v_head_dim) * seqlen)
    return 6.0 * active_matmul_params(s, rows_per_token) + attention


def flash_call_cost(rows: int, seqlen: int, qk_dim: int, v_dim: int,
                    itemsize: int, backward: bool) -> CallCost:
    """One flash call over `rows` = batch * heads causal rows of `seqlen`,
    q/k `qk_dim` wide and v/o `v_dim` wide."""
    entries = rows * seqlen * (seqlen + 1) / 2
    qk = rows * seqlen * qk_dim * itemsize
    v = rows * seqlen * v_dim * itemsize
    vector = rows * seqlen * 4
    if backward:
        return CallCost((6.0 * qk_dim + 4.0 * v_dim) * entries,
                        4 * qk + 4 * v + 2 * vector)
    return CallCost(2.0 * (qk_dim + v_dim) * entries, 2 * qk + 2 * v + vector)


def expert_products_cost(rows: float, s: LatentMoESizes,
                         itemsize: int) -> CallCost:
    """One layer's grouped products over `rows` pairs, forward and backward."""
    passes = 3
    weights = s.n_held * expert_params(s) * itemsize
    return CallCost(passes * 6.0 * rows * s.d_model * s.d_expert,
                    passes * (weights + 2 * rows * s.d_model * itemsize))
