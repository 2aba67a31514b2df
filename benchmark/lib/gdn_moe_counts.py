"""Operations, bytes and parameters of the gdn_moe family from its shapes:
the yardstick's own counts for the metrics the `train_hybrid` runner feeds
(pinned by benchmark/tests/test_gdn_moe_counts.py).

Conventions, beside those of benchmark/lib/flops.py and
benchmark/lib/mla_moe_counts.py:

* **Parameters** (`param_counts`): what ONE job holds, the experts HELD and
  the vocabulary slice, not the published model.
* **Forward FLOPs a token** (`forward_flops_per_token`): 2 x the parameters
  a token's matmuls touch here (the routed experts at `rows_per_token`, the
  step's counter summed over the layers), the full-attention layers' scores
  counted CAUSALLY (`2 H (T + 1) head_dim` for QK^T and PV together), and
  the chunked rule's own products (`rule_flops_per_token`). 469 MFLOP at
  the cell's shapes.
* **Active FLOPs per trained token** (`train_flops_per_token`), the
  numerator of `train_step.active_mfu_pct`: 6 x the same parameters, plus
  attention at the FULL T^2 in the full-attention layers (the convention of
  every `mfu` in this benchmark: `12 H T head_dim` a layer), plus three
  times the rule's forward products. Recompute is not counted.
* **The chunked rule** (`rule_flops_per_token`, a value head and token, at
  chunk C): K K^T and Q K^T inside the chunk (2 C d_k each), the unit
  triangular solve of [W | U] (C (d_k + d_v)), three products with the
  state (W S, Q S, K^T V': 2 d_k d_v each) and the chunk's scores times its
  new values (2 C d_v). `rule_cost` is a layer's rule over a step, forward
  and backward (three times the forward's FLOPs), and the bytes of q, k, v,
  o (compute dtype), g, beta (float32) and the chunk states (float32, d_k x
  d_v a value head and chunk), each once each way.
* **Flash calls at a group** (`gqa_flash_call_cost`): causal entries `T (T
  + 1) / 2` a QUERY head row at `head_dim` for QK^T and for PV; bytes: q, o
  (and do, dq) a query head, k, v (and dk, dv) ONCE A KEY-VALUE HEAD, and
  the float32 row vectors.
"""

from __future__ import annotations

from typing import Dict, NamedTuple

from benchmark.lib.flops import CallCost

RULE_CHUNK = 64     # the program's (ops/delta_rule.CHUNK), stated in the
                    # configuration file's `assumed.rule_chunk`


class GdnMoESizes(NamedTuple):
    d_model: int
    n_head: int            # full attention: query heads
    n_kv_head: int
    head_dim: int
    rotary_dim: int
    n_k_head: int          # Gated DeltaNet: key heads
    n_v_head: int
    d_k: int
    d_v: int
    conv: int
    interval: int          # a period: interval - 1 linear layers, 1 full
    n_layer: int
    d_expert: int
    d_shared: int
    n_routed: int          # experts the router scores (published)
    n_held: int            # of which this job holds
    top_k: int
    vocab: int             # the slice held

    @property
    def full_layers(self) -> int:
        return self.n_layer // self.interval

    @property
    def linear_layers(self) -> int:
        return self.n_layer - self.full_layers

    @property
    def expert_layers(self) -> int:
        return self.n_layer



def gdn_matmul_params(s: GdnMoESizes) -> int:
    d = s.d_model
    qkvz = 2 * s.n_k_head * s.d_k + 2 * s.n_v_head * s.d_v
    return d * qkvz + d * 2 * s.n_v_head + s.n_v_head * s.d_v * d


def gdn_params(s: GdnMoESizes) -> int:
    conv = (2 * s.n_k_head * s.d_k + s.n_v_head * s.d_v) * s.conv
    return gdn_matmul_params(s) + conv + 2 * s.n_v_head + s.d_v


def attention_matmul_params(s: GdnMoESizes) -> int:
    d, h = s.d_model, s.head_dim
    return d * s.n_head * 2 * h + 2 * d * s.n_kv_head * h + s.n_head * h * d


def attention_params(s: GdnMoESizes) -> int:
    return attention_matmul_params(s) + 2 * s.head_dim


def expert_params(s: GdnMoESizes) -> int:
    return 3 * s.d_model * s.d_expert


def ffn_params(s: GdnMoESizes, held: "int | None" = None) -> int:
    """A layer's FFN: router, the shared expert with its gate, the experts
    `held` (this job's by default)."""
    d = s.d_model
    held = s.n_held if held is None else held
    return (d * s.n_routed + 3 * d * s.d_shared + d
            + held * expert_params(s))


def param_counts(s: GdnMoESizes) -> Dict[str, int]:
    """Parameters this job holds, by part."""
    d = s.d_model
    linear = gdn_params(s) + ffn_params(s) + 2 * d
    full = attention_params(s) + ffn_params(s) + 2 * d
    return {
        "gdn_mixer": gdn_params(s),
        "attention_mixer": attention_params(s),
        "ffn": ffn_params(s),
        "ffn_uncut": ffn_params(s, s.n_routed),
        "linear_layer": linear,
        "full_layer": full,
        "embedding_and_head": 2 * s.vocab * d,
        "total": (s.linear_layers * linear + s.full_layers * full
                  + 2 * s.vocab * d + d),
    }


def active_matmul_params(s: GdnMoESizes, rows_per_token: float) -> float:
    """Parameters one token's matmuls touch in this job. `rows_per_token` is
    summed over the layers. The embedding's lookup and the depthwise
    convolution are no matmuls."""
    d = s.d_model
    return (s.linear_layers * gdn_matmul_params(s)
            + s.full_layers * attention_matmul_params(s)
            + s.expert_layers * (d * s.n_routed + 3 * d * s.d_shared + d)
            + rows_per_token * expert_params(s)
            + s.vocab * d)


def rule_flops_per_token(s: GdnMoESizes, chunk: int = RULE_CHUNK) -> float:
    """The chunked rule's forward FLOPs a token, all value heads of one
    layer."""
    return s.n_v_head * (4.0 * chunk * s.d_k + chunk * (s.d_k + s.d_v)
                         + 6.0 * s.d_k * s.d_v + 2.0 * chunk * s.d_v)


def forward_flops_per_token(s: GdnMoESizes, seqlen: int,
                            rows_per_token: float) -> float:
    causal = s.full_layers * 2.0 * s.n_head * (seqlen + 1) * s.head_dim
    return (2.0 * active_matmul_params(s, rows_per_token) + causal
            + s.linear_layers * rule_flops_per_token(s))


def train_flops_per_token(s: GdnMoESizes, seqlen: int,
                          rows_per_token: float) -> float:
    attention = 12.0 * s.full_layers * s.n_head * s.head_dim * seqlen
    return (6.0 * active_matmul_params(s, rows_per_token) + attention
            + 3.0 * s.linear_layers * rule_flops_per_token(s))


def rule_cost(batch: int, seqlen: int, s: GdnMoESizes, itemsize: int,
              chunk: int = RULE_CHUNK) -> CallCost:
    """One layer's rule over a step of `batch` sequences, forward and
    backward."""
    tokens = batch * seqlen
    rows = tokens * s.n_v_head
    qkvo = rows * (2 * s.d_k + 2 * s.d_v) * itemsize
    gates = rows * 2 * 4
    states = rows / chunk * s.d_k * s.d_v * 4
    return CallCost(3.0 * tokens * rule_flops_per_token(s, chunk),
                    2.0 * (qkvo + gates + states))


def gqa_flash_call_cost(batch: int, seqlen: int, s: GdnMoESizes,
                        itemsize: int, backward: bool) -> CallCost:
    """One flash call over `batch` sequences: `n_head` query heads over
    `n_kv_head` key-value heads, all `head_dim` wide."""
    entries = batch * s.n_head * seqlen * (seqlen + 1) / 2
    q = batch * s.n_head * seqlen * s.head_dim * itemsize
    kv = batch * s.n_kv_head * seqlen * s.head_dim * itemsize
    vector = batch * s.n_head * seqlen * 4
    if backward:        # q, o, do, dq; k, v, dk, dv; lse, delta
        return CallCost(10.0 * s.head_dim * entries,
                        4 * q + 4 * kv + 2 * vector)
    return CallCost(4.0 * s.head_dim * entries, 2 * q + 2 * kv + vector)
