"""Operations, bytes and parameters of the loop_llama family from its shapes:
the yardstick's own counts for the metrics the `train_loop` runner feeds
(pinned by benchmark/tests/test_loop_llama_counts.py).

Conventions, beside those of benchmark/lib/flops.py:

* **Parameters** (`param_counts`): what the job holds. A layer is four
  attention matrices, three of the SwiGLU and FOUR norms, no bias; an
  untied head; the one final norm; the exit gate (d + 1).
* **Matmul parameters a pass** (`matmul_params_per_pass`): the layers'
  matrices and the head (an exit a pass). The embedding's lookup, the norms
  and the gate (a product over the width on the vector unit) are no
  matmuls.
* **Model FLOPs per trained token** (`train_flops_per_token`), the numerator
  of `train_step.mfu_pct` in this family's cells: a step passes the stack
  `passes` times over the same weights, so `6 x passes x` the matmul
  parameters a pass (NOT `6 N`: the parameter count says a quarter of the
  work at four passes), plus attention at the **full** `T^2` as every `mfu`
  of this benchmark counts it, `12 x n_head x head_dim x T` a token and
  layer APPLICATION, `passes x n_layer` of them. Recompute is not counted
  (the layers' under remat, the exits' logits in the backward).
  `causal_train_flops_per_token` is the same with attention at the causal
  triangle (half), for the reckoning that sizes a window.
* **A flash call** is benchmark/lib/flops.flash_call_cost's (every query
  head has its own key-value head): `kernels.flash_roofline` reads
  `n_head` and `head_dim` of these sizes.
"""

from __future__ import annotations

from typing import Dict, NamedTuple


class LoopLlamaSizes(NamedTuple):
    d_model: int
    n_layer: int            # layers held (the cut), each passed `passes` times
    n_head: int
    n_kv_head: int
    head_dim: int
    d_ff: int
    vocab: int
    passes: int             # `total_ut_steps`


def layer_matmul_params(s: LoopLlamaSizes) -> int:
    d = s.d_model
    return (2 * d * s.n_head * s.head_dim + 2 * d * s.n_kv_head * s.head_dim
            + 3 * d * s.d_ff)


def param_counts(s: LoopLlamaSizes) -> Dict[str, int]:
    d = s.d_model
    layer = layer_matmul_params(s) + 4 * d
    return {"layer": layer, "embedding_and_head": 2 * s.vocab * d,
            "final_norm": d, "exit_gate": d + 1,
            "total": s.n_layer * layer + 2 * s.vocab * d + d + d + 1}


def matmul_params_per_pass(s: LoopLlamaSizes) -> int:
    return s.n_layer * layer_matmul_params(s) + s.vocab * s.d_model


def train_flops_per_token(s: LoopLlamaSizes, seqlen: int) -> float:
    attention = 12.0 * s.n_layer * s.n_head * s.head_dim * seqlen
    return s.passes * (6.0 * matmul_params_per_pass(s) + attention)


def causal_train_flops_per_token(s: LoopLlamaSizes, seqlen: int) -> float:
    attention = 6.0 * s.n_layer * s.n_head * s.head_dim * (seqlen + 1)
    return s.passes * (6.0 * matmul_params_per_pass(s) + attention)
