"""Operations and bytes computed from shapes: the yardstick's own counts.

Conventions (stated once, pinned by benchmark/tests/test_flops.py):

* **Model FLOPs per trained token** (`train_flops_per_token`), the numerator
  of `train_step.mfu_pct`: `6 * N` for the matmuls of forward and backward
  (2 FLOPs per multiply-add, backward twice the forward), plus attention at
  the **full** `T^2` score matrix, not halved for causality:
  `12 * n_layer * n_head * head_dim * T` per token (QK^T and PV, forward and
  backward). `N` counts every parameter of the published model once: a
  **tied head is counted once** (the token embedding doubles as the head, so
  its `V * d` matmul is in `6 * N` exactly once), learned positions and
  biases are included (under 0.4% of N). **Recompute is not counted**: a
  step that recomputes its forward under remat does more work than this
  number, and its MFU is lower for it. The published vocabulary is used, not
  the padding a tensor-parallel layout adds.
* **Flash attention calls** (`flash_call_cost`), the numerator of
  `kernels.flash_roofline`: what the *causal* algorithm needs for one call,
  `T * (T + 1) / 2` score entries per head row. Forward: QK^T and PV, 4
  FLOPs per entry and head-dim element. Backward: five matmuls (scores
  recomputed, dV, dP, dQ, dK), 10 FLOPs. Bytes: each operand and result
  read or written once (q, k, v, o and the log-sum-exp forward; q, k, v, o,
  do, lse, delta in and dq, dk, dv out backward). Counting the causal half
  keeps the roofline share under 100% for a kernel that skips masked tiles.
"""

from __future__ import annotations

from typing import NamedTuple


class DecoderSizes(NamedTuple):
    """What a family file distils from its configuration for the counts."""

    d_model: int
    n_layer: int
    n_head: int
    head_dim: int
    d_ff: int
    vocab: int
    n_positions: int
    mlp_matmuls: int       # 2 for GELU MLP, 3 for gated (SwiGLU)
    tied_head: bool
    learned_positions: bool
    biases: bool
    norm_params_per_layer: int   # 4*d for two LayerNorms, 2*d for RMSNorm


def param_count(s: DecoderSizes) -> int:
    d = s.d_model
    attn = 4 * d * d + (4 * d if s.biases else 0)
    mlp = s.mlp_matmuls * d * s.d_ff
    if s.biases:
        mlp += (s.mlp_matmuls - 1) * s.d_ff + d
    final_norm = s.norm_params_per_layer // 2
    n = s.vocab * d + s.n_layer * (attn + mlp + s.norm_params_per_layer)
    n += final_norm
    if s.learned_positions:
        n += s.n_positions * d
    if not s.tied_head:
        n += s.vocab * d
    return n


def train_flops_per_token(s: DecoderSizes, seqlen: int) -> float:
    attention = 12 * s.n_layer * s.n_head * s.head_dim * seqlen
    return 6.0 * param_count(s) + attention


class CallCost(NamedTuple):
    flops: float
    bytes: float


def flash_call_cost(rows: int, seqlen: int, head_dim: int, itemsize: int,
                    backward: bool) -> CallCost:
    """One flash call over `rows` = batch * heads (as held by one device)
    causal rows of `seqlen` x `head_dim`."""
    entries = rows * seqlen * (seqlen + 1) / 2
    tensor = rows * seqlen * head_dim * itemsize
    vector = rows * seqlen * 4            # lse / delta, float32
    if backward:
        return CallCost(10.0 * entries * head_dim, 8 * tensor + 2 * vector)
    return CallCost(4.0 * entries * head_dim, 4 * tensor + vector)


def roofline_seconds(cost: CallCost, flops_per_s: float,
                     bytes_per_s: float) -> "tuple[float, str]":
    """The least time the chip could take, and which bound binds."""
    t_compute = cost.flops / flops_per_s
    t_memory = cost.bytes / bytes_per_s
    return ((t_compute, "compute") if t_compute >= t_memory
            else (t_memory, "memory"))
