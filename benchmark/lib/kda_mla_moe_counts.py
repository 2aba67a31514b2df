"""Operations, bytes and parameters of the kda_mla_moe family from its shapes:
the yardstick's own counts for the metrics the `train_kda` runner feeds
(pinned by benchmark/tests/test_kda_mla_moe_counts.py).

Conventions, beside those of benchmark/lib/flops.py, mla_moe_counts.py and
gdn_moe_counts.py:

* **Parameters** (`param_counts`): what ONE job holds, the experts HELD and
  the vocabulary slice, not the published model. The leading dense layers
  are `n_dense_layer` delta layers; of the expert layers `n_kda_expert_layer`
  mix by Kimi Delta Attention and `n_mla_expert_layer` by latent attention
  (one a group of `group` layers).
* **Forward FLOPs a token** (`forward_flops_per_token`): 2 x the parameters
  a token's matmuls touch here (the routed experts at `rows_per_token`, the
  step's counter summed over the layers), the latent layers' scores counted
  CAUSALLY (`H (T + 1) (qk + v)`), and the chunked rule's own products.
* **Active FLOPs per trained token** (`train_flops_per_token`), the
  numerator of `train_step.active_mfu_pct`: 6 x the same parameters, plus
  attention at the FULL T^2 in the latent layers (the convention of every
  `mfu` in this benchmark: `6 H T (qk + v)` a layer), plus three times the
  rule's forward products. Recompute is not counted.
* **The chunked rule with a decay a channel** (`rule_flops_per_token`, a
  head and token, at chunk C): the two in-chunk score products (K K^T and Q
  K^T about reference rows: 2 C d_k each), the unit triangular solve of
  [W | U] (C (d_k + d_v)), three products with the state (2 d_k d_v each)
  and the chunk's scores times its new values (2 C d_v): the scalar rule's
  count, a decay a channel changes no product's size. It is a count of the
  MATHEMATICS, whatever implements it: the program's sub-blocks multiply
  each column block against up to four reference rows, which is time and
  not work. `rule_cost` is a layer's rule over a step, forward and backward
  (three times the forward's FLOPs), and the bytes of q, k, v, o (compute
  dtype), **g (float32, d_k A TOKEN AND HEAD: 128 times the scalar rule's)**,
  beta (float32) and the chunk states (float32, d_k x d_v a head and chunk),
  each once each way.
"""

from __future__ import annotations

from typing import Dict, NamedTuple

from benchmark.lib.flops import CallCost

RULE_CHUNK = 64     # the program's (ops/delta_rule.CHUNK), stated in the
                    # configuration file's `assumed.rule_chunk`


class KdaMlaMoESizes(NamedTuple):
    d_model: int
    n_head: int            # both mixers' heads
    d_k: int               # a delta head's q / k / decay width
    d_v: int               # ... and its v / output width
    conv: int              # the convolutions' taps
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    d_ff: int              # the dense layers' SwiGLU width
    d_expert: int
    n_routed: int          # experts the router scores (published)
    n_held: int            # of which this job holds
    n_shared: int
    top_k: int
    n_group: int
    topk_group: int
    group: int             # layers a group: group - 1 delta, 1 latent
    n_dense_layer: int     # leading delta layers with a dense SwiGLU
    n_kda_expert_layer: int
    n_mla_expert_layer: int
    n_mtp: int
    vocab: int             # the slice held

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def kda_layers(self) -> int:
        return self.n_dense_layer + self.n_kda_expert_layer

    @property
    def mla_layers(self) -> int:
        return self.n_mla_expert_layer + self.n_mtp

    @property
    def expert_layers(self) -> int:
        return self.n_kda_expert_layer + self.n_mla_expert_layer + self.n_mtp

    @property
    def n_layer(self) -> int:
        return (self.n_dense_layer + self.n_kda_expert_layer
                + self.n_mla_expert_layer)


def kda_matmul_params(s: KdaMlaMoESizes) -> int:
    """q, k, the decay's (d_k), v, the output gate (d_v), beta, W_o."""
    d, h = s.d_model, s.n_head
    return d * h * (3 * s.d_k + 2 * s.d_v) + d * h + h * s.d_v * d


def kda_params(s: KdaMlaMoESizes) -> int:
    h = s.n_head
    return (kda_matmul_params(s) + h * (2 * s.d_k + s.d_v) * s.conv
            + h + h * s.d_k + s.d_v)       # A_log, dt_bias, the norm


def mla_matmul_params(s: KdaMlaMoESizes) -> int:
    """No q latent; a gate a head."""
    d, h = s.d_model, s.n_head
    return (d * h * s.qk_head_dim + d * h
            + d * (s.kv_lora_rank + s.qk_rope_head_dim)
            + s.kv_lora_rank * h * (s.qk_nope_head_dim + s.v_head_dim)
            + h * s.v_head_dim * d)


def mla_params(s: KdaMlaMoESizes) -> int:
    return mla_matmul_params(s) + s.kv_lora_rank


def expert_params(s: KdaMlaMoESizes) -> int:
    return 3 * s.d_model * s.d_expert


def ffn_params(s: KdaMlaMoESizes, held: "int | None" = None) -> int:
    """An expert layer's FFN: router, bias, shared, the experts `held`."""
    held = s.n_held if held is None else held
    return (s.d_model * s.n_routed + s.n_routed
            + (held + s.n_shared) * expert_params(s))


def param_counts(s: KdaMlaMoESizes) -> Dict[str, int]:
    """Parameters this job holds, by part."""
    d = s.d_model
    dense = kda_params(s) + 2 * d + 3 * d * s.d_ff
    kda_expert = kda_params(s) + 2 * d + ffn_params(s)
    mla_expert = mla_params(s) + 2 * d + ffn_params(s)
    module = mla_expert + 2 * d * d + 3 * d
    return {
        "kda_mixer": kda_params(s), "mla_mixer": mla_params(s),
        "ffn": ffn_params(s), "ffn_uncut": ffn_params(s, s.n_routed),
        "dense_layer": dense, "kda_expert_layer": kda_expert,
        "mla_expert_layer": mla_expert, "mtp_module": module,
        "embedding_and_head": 2 * s.vocab * d,
        "total": (s.n_dense_layer * dense
                  + s.n_kda_expert_layer * kda_expert
                  + s.n_mla_expert_layer * mla_expert + s.n_mtp * module
                  + 2 * s.vocab * d + d),
    }


def active_matmul_params(s: KdaMlaMoESizes, rows_per_token: float) -> float:
    """Parameters one token's matmuls touch in this job. `rows_per_token` is
    summed over the expert layers. The embedding's lookup and the depthwise
    convolutions are no matmuls."""
    d = s.d_model
    return (s.kda_layers * kda_matmul_params(s)
            + s.mla_layers * mla_matmul_params(s)
            + s.n_dense_layer * 3 * d * s.d_ff
            + s.expert_layers * (d * s.n_routed
                                 + s.n_shared * expert_params(s))
            + rows_per_token * expert_params(s)
            + s.n_mtp * 2 * d * d
            + (1 + s.n_mtp) * s.vocab * d)


def rule_flops_per_token(s: KdaMlaMoESizes, chunk: int = RULE_CHUNK) -> float:
    """The chunked rule's forward FLOPs a token, all heads of one layer."""
    return s.n_head * (4.0 * chunk * s.d_k + chunk * (s.d_k + s.d_v)
                       + 6.0 * s.d_k * s.d_v + 2.0 * chunk * s.d_v)


def forward_flops_per_token(s: KdaMlaMoESizes, seqlen: int,
                            rows_per_token: float) -> float:
    causal = s.mla_layers * s.n_head * (seqlen + 1.0) * (s.qk_head_dim
                                                         + s.v_head_dim)
    return (2.0 * active_matmul_params(s, rows_per_token) + causal
            + s.kda_layers * rule_flops_per_token(s))


def train_flops_per_token(s: KdaMlaMoESizes, seqlen: int,
                          rows_per_token: float) -> float:
    attention = (6.0 * s.mla_layers * s.n_head
                 * (s.qk_head_dim + s.v_head_dim) * seqlen)
    return (6.0 * active_matmul_params(s, rows_per_token) + attention
            + 3.0 * s.kda_layers * rule_flops_per_token(s))


def rule_cost(batch: int, seqlen: int, s: KdaMlaMoESizes, itemsize: int,
              chunk: int = RULE_CHUNK) -> CallCost:
    """One layer's rule over a step of `batch` sequences, forward and
    backward."""
    tokens = batch * seqlen
    rows = tokens * s.n_head
    qkvo = rows * (2 * s.d_k + 2 * s.d_v) * itemsize
    gates = rows * (s.d_k + 1) * 4          # g a channel, beta a head
    states = rows / chunk * s.d_k * s.d_v * 4
    return CallCost(3.0 * tokens * rule_flops_per_token(s, chunk),
                    2.0 * (qkvo + gates + states))
