"""Operations, bytes and parameters of the ssm_dense family from its shapes:
the yardstick's own counts for the metrics the `train_ssm_dense` runner
feeds (pinned by benchmark/tests/test_ssm_dense_counts.py).

Conventions, beside those of benchmark/lib/flops.py and ssm_moe_counts.py:

* **Parameters** (`param_counts`): what the job holds. EVERY layer is a
  mixer, a SwiGLU of three matrices `d x d_ff` and two norms; a Mamba layer's
  mixer is the input projection `[z | xBC | dt]`, the out projection, the
  taps with their bias, `A_log`, `D`, `dt_bias` a head and the gated norm's
  weight; an attention layer's is q, k, v, o with no bias. The TIED table
  counts once; the final norm.
* **Model FLOPs per trained token** (`train_flops_per_token`), the numerator
  of `train_step.mfu_pct` in this family's cells: 6 x the parameters a
  token's matmuls touch (the table ONCE: its matrix is the head's matmul,
  its lookup is none; the taps, the norms and the recurrence's few are no
  matmuls), attention at the FULL T^2 in the attention layers only (`12 x
  n_head x head_dim x T` a token and attention layer, as every `mfu` of this
  benchmark counts it), plus three times the chunked recurrence's forward
  products. Recompute is not counted.
* **The chunked recurrence** (`ssd_flops_per_token`, ONE layer's heads, at
  chunk C, `benchmark/lib/ssm_moe_counts.py`'s count at this family's
  sizes): a chunk's `C B^T` scores once a GROUP (2 C N: with ONE group,
  once for all 64 heads), the scores times `dt x` a head (2 C P), a head's
  own chunk state and the entering state's part (2 P N each). A count of
  the MATHEMATICS at that chunk, whatever implements it. `ssd_cost` is a
  layer's recurrence over a step, forward and backward, and the bytes of x
  and y, B and C, dt and the chunk states once each way, for
  `model.ssd_roofline`.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Tuple

# the Mamba-2 mixer's and the attention's counts read a sizes tuple's fields
# by name (`m_head`, `m_group`, `chunk`, `n_head`, ...): the ssm_moe family's
# functions are this family's at its own sizes
from benchmark.lib.ssm_moe_counts import (attn_params,
                                          mamba_matmul_params, mamba_params,
                                          ssd_cost, ssd_flops_per_token)


class SsmDenseSizes(NamedTuple):
    d_model: int
    m_head: int            # the SSM heads (all of them)
    m_head_dim: int        # P
    m_state: int           # N
    m_group: int           # the B / C groups
    conv: int              # the convolution's taps
    chunk: int
    n_head: int            # query heads
    n_kv_head: int
    head_dim: int
    d_ff: int              # the SwiGLU's width, every layer's
    layer_types: Tuple[str, ...]    # "mamba" | "attention", the layers held
    vocab: int             # the slice held

    @property
    def n_mamba_layer(self) -> int:
        return self.layer_types.count("mamba")

    @property
    def n_attn_layer(self) -> int:
        return self.layer_types.count("attention")

    @property
    def n_layer(self) -> int:
        return len(self.layer_types)

    @property
    def m_inner(self) -> int:
        return self.m_head * self.m_head_dim

    @property
    def m_conv_channels(self) -> int:
        return self.m_inner + 2 * self.m_group * self.m_state


def mlp_params(s: SsmDenseSizes) -> int:
    return 3 * s.d_model * s.d_ff


def param_counts(s: SsmDenseSizes) -> Dict[str, int]:
    """Parameters this job holds, by part (a layer with its two norms)."""
    d = s.d_model
    mamba = mamba_params(s) + mlp_params(s) + 2 * d
    attn = attn_params(s) + mlp_params(s) + 2 * d
    return {"mamba_mixer": mamba_params(s), "attn_mixer": attn_params(s),
            "mlp": mlp_params(s), "mamba_layer": mamba, "attn_layer": attn,
            "embedding": s.vocab * d,
            "total": (s.n_mamba_layer * mamba + s.n_attn_layer * attn
                      + s.vocab * d + d)}


def matmul_params(s: SsmDenseSizes) -> int:
    """Parameters one token's matmuls touch: the mixers' and the SwiGLUs'
    matrices and the tied table once (the head)."""
    return (s.n_mamba_layer * mamba_matmul_params(s)
            + s.n_attn_layer * attn_params(s) + s.n_layer * mlp_params(s)
            + s.vocab * s.d_model)


def train_flops_per_token(s: SsmDenseSizes, seqlen: int) -> float:
    attention = 12.0 * s.n_attn_layer * s.n_head * s.head_dim * seqlen
    return (6.0 * matmul_params(s) + attention
            + 3.0 * s.n_mamba_layer * ssd_flops_per_token(s))
