"""Operations, bytes and parameters of the mhc_mla_moe family from its
shapes: `benchmark/lib/mla_moe_counts.py`'s counts (this family's sublayers
are that family's) and, beside them, what the hyper-connection mixers add
(pinned by benchmark/tests/test_mhc_mla_moe_counts.py).

A mixer, per token (n streams of C; parallel/hyper.py has the equations):

* **parameters**: W, nC x (n^2 + 2n), three alpha and n^2 + 2n biases; the
  exit mixer behind the last layer (and behind the multi-token-prediction
  module) has W nC x n, one alpha, n biases. Two mixers a layer.
* **FLOPs** (`train_flops_per_token`), 2 a multiply-add, forward x 3 for
  forward and backward as every product of `mla_moe_counts`: the product
  with W (in the active parameters), the read `sum_i pre_i X[i]` (2 n C)
  and the write `H X + post y` (2 n (n + 1) C). The sigmoids, exp and the
  Sinkhorn rounds are 40 n^2 divisions a token beside 2 n C (n^2 + 2n)
  multiply-adds and are not counted. Recompute is not counted.
* **bytes** (`mixers_step_cost`): what the mixers MUST move through HBM
  whatever implements them, at the compute dtype: a mixer's forward reads
  X and y and writes u and X', (2n + 2) C elements a token; its backward
  reads X, dX' and du and writes dX and dy, (3n + 2) C (ISSUE 57's count:
  it leaves the backward's second read of y out, and a bound that is lower
  is still a bound); W is read once forward and once backward and its
  gradient written once (float32). The exit reads X and writes h forward,
  (n + 1) C, and reads X and dh and writes dX backward, (2n + 1) C. ONE
  forward and ONE backward a mixer a step: the recompute under remat is
  time, not work, so it lowers `model.mhc_roofline`; so does every float32
  copy of the streams an unfused implementation carries through HBM. A
  fused kernel reads higher against the same count and cannot read over
  100%.
"""

from __future__ import annotations

from typing import Dict, NamedTuple

from benchmark.lib import mla_moe_counts as base
from benchmark.lib.flops import CallCost


class HyperLatentMoESizes(NamedTuple):
    """`mla_moe_counts.LatentMoESizes`'s fields (its functions read these
    by name) and the streams'."""

    d_model: int
    n_head: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    d_ff: int
    d_expert: int
    n_routed: int
    n_held: int
    n_shared: int
    top_k: int
    n_dense_layer: int
    n_expert_layer: int
    n_mtp: int
    vocab: int
    hc_mult: int               # n, the residual streams
    sinkhorn_iters: int

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def attention_layers(self) -> int:
        return self.n_dense_layer + self.n_expert_layer + self.n_mtp

    @property
    def expert_layers(self) -> int:
        return self.n_expert_layer + self.n_mtp

    @property
    def mixers(self) -> int:
        """Two a layer, the module's layer too."""
        return 2 * self.attention_layers

    @property
    def exits(self) -> int:
        return 1 + self.n_mtp

    @property
    def maps_width(self) -> int:
        return self.hc_mult * self.hc_mult + 2 * self.hc_mult


def mixer_params(s: HyperLatentMoESizes) -> int:
    return s.hc_mult * s.d_model * s.maps_width + 3 + s.maps_width


def exit_params(s: HyperLatentMoESizes) -> int:
    return s.hc_mult * s.d_model * s.hc_mult + 1 + s.hc_mult


def param_counts(s: HyperLatentMoESizes) -> Dict[str, int]:
    """Parameters this job holds, by part: `mla_moe`'s and the mixers'."""
    parts = base.param_counts(s)
    mixers = s.mixers * mixer_params(s) + s.exits * exit_params(s)
    return {**parts, "stream_mixers": mixers,
            "total": parts["total"] + mixers}


def mixer_matmul_params(s: HyperLatentMoESizes) -> int:
    """The W's a token's maps are products with."""
    n, c = s.hc_mult, s.d_model
    return s.mixers * n * c * s.maps_width + s.exits * n * c * n


def stream_sum_flops_per_token(s: HyperLatentMoESizes) -> float:
    """Forward FLOPs a token of the mixers' weighted sums."""
    n, c = s.hc_mult, s.d_model
    return (s.mixers * (2 * n * c + 2 * n * (n + 1) * c)
            + s.exits * 2 * n * c)


def train_flops_per_token(s: HyperLatentMoESizes, seqlen: int,
                          rows_per_token: float) -> float:
    return (base.train_flops_per_token(s, seqlen, rows_per_token)
            + 6.0 * mixer_matmul_params(s)
            + 3.0 * stream_sum_flops_per_token(s))


def mixers_step_cost(s: HyperLatentMoESizes, tokens: int,
                     itemsize: int) -> CallCost:
    """All the mixers of one step over `tokens` tokens: the FLOPs above and
    the bytes that must move (module docstring)."""
    n, c = s.hc_mult, s.d_model
    stream_elems = (s.mixers * ((2 * n + 2) + (3 * n + 2))
                    + s.exits * ((n + 1) + (2 * n + 1))) * c
    weights = 3 * 4 * (s.mixers * n * c * s.maps_width
                       + s.exits * n * c * n)
    flops = tokens * (6.0 * mixer_matmul_params(s)
                      + 3.0 * stream_sum_flops_per_token(s))
    return CallCost(flops, tokens * stream_elems * itemsize + weights)
