"""Operations, bytes and parameters of the conv_moe family from its shapes:
the yardstick's own counts for the metrics the `train_conv_moe` runner feeds
(pinned by benchmark/tests/test_conv_moe_counts.py).

Conventions, beside those of benchmark/lib/flops.py,
benchmark/lib/mla_moe_counts.py and benchmark/lib/gdn_moe_counts.py:

* **Parameters** (`param_counts`): what ONE job holds, the experts HELD and
  the vocabulary slice, the tied embedding once; not the published model.
* **Forward FLOPs a token** (`forward_flops_per_token`): 2 x the parameters
  a token's matmuls touch here (the routed experts at `rows_per_token`, the
  step's counter summed over the expert layers; the tied head once; the
  embedding's lookup and the depthwise convolution are no matmuls), the
  attention layers' scores counted CAUSALLY (`2 H (T + 1) head_dim` for
  QK^T and PV together). 432 MFLOP at the cell's shapes and 1 row a token
  and layer; the program EXECUTES more: `executed_forward_flops_per_token`
  counts the expert products at the rows a whole chunk computes.
* **Active FLOPs per trained token** (`train_flops_per_token`), the
  numerator of `train_step.active_mfu_pct`: 6 x the same parameters, plus
  attention at the FULL T^2 in the attention layers (the convention of
  every `mfu` in this benchmark: `12 H T head_dim` a layer). Recompute and
  the padding rows of a chunk computed whole are not counted.
* **The short convolution** (`shortconv_cost`, one layer over a step,
  forward and backward): the two projections' FLOPs (`W_in` d x 3 d and
  `W_out` d x d: 6 x 4 d^2 a token) and the bytes of x, `[B | C | u]`, c
  and y once each way. The taps' sum is 2 x taps FLOPs a channel: not
  counted.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Tuple

from benchmark.lib.flops import CallCost


class ConvMoESizes(NamedTuple):
    d_model: int
    n_head: int             # attention layers: query heads
    n_kv_head: int
    head_dim: int
    conv: int               # the convolution's taps
    layer_types: Tuple[str, ...]   # "conv" | "full_attention", as run here
    n_dense: int            # leading layers with a dense SwiGLU
    d_dense: int
    d_expert: int
    n_routed: int           # experts the router scores (published)
    n_held: int             # of which this job holds
    top_k: int
    vocab: int              # the slice held

    @property
    def n_layer(self) -> int:
        return len(self.layer_types)

    @property
    def conv_layers(self) -> int:
        return self.layer_types.count("conv")

    @property
    def attn_layers(self) -> int:
        return self.layer_types.count("full_attention")

    @property
    def expert_layers(self) -> int:
        return self.n_layer - self.n_dense


def shortconv_matmul_params(s: ConvMoESizes) -> int:
    return 4 * s.d_model * s.d_model            # W_in (d, 3 d), W_out (d, d)


def shortconv_params(s: ConvMoESizes) -> int:
    return shortconv_matmul_params(s) + s.d_model * s.conv


def attention_matmul_params(s: ConvMoESizes) -> int:
    d = s.d_model
    return 2 * d * s.n_head * s.head_dim + 2 * d * s.n_kv_head * s.head_dim


def attention_params(s: ConvMoESizes) -> int:
    return attention_matmul_params(s) + 2 * s.head_dim     # q and k norms


def dense_mlp_params(s: ConvMoESizes) -> int:
    return 3 * s.d_model * s.d_dense


def expert_params(s: ConvMoESizes) -> int:
    return 3 * s.d_model * s.d_expert


def ffn_params(s: ConvMoESizes, held: "int | None" = None) -> int:
    """An expert layer's FFN: the router, its selection bias, the experts
    `held` (this job's by default). No shared expert."""
    held = s.n_held if held is None else held
    return (s.d_model * s.n_routed + s.n_routed
            + held * expert_params(s))


def param_counts(s: ConvMoESizes) -> Dict[str, int]:
    """Parameters this job holds, by part."""
    d = s.d_model
    mixer = {"conv": shortconv_params(s),
             "full_attention": attention_params(s)}
    layers = sum(mixer[kind] + 2 * d + (dense_mlp_params(s) if i < s.n_dense
                                        else ffn_params(s))
                 for i, kind in enumerate(s.layer_types))
    return {
        "shortconv_mixer": shortconv_params(s),
        "attention_mixer": attention_params(s),
        "dense_mlp": dense_mlp_params(s),
        "expert": expert_params(s),
        "ffn": ffn_params(s),
        "ffn_uncut": ffn_params(s, s.n_routed),
        "dense_conv_layer": mixer["conv"] + dense_mlp_params(s) + 2 * d,
        "conv_layer": mixer["conv"] + ffn_params(s) + 2 * d,
        "attention_layer": mixer["full_attention"] + ffn_params(s) + 2 * d,
        "embedding": s.vocab * d,
        "total": layers + s.vocab * d + d,
    }


def active_matmul_params(s: ConvMoESizes, rows_per_token: float) -> float:
    """Parameters one token's matmuls touch in this job. `rows_per_token` is
    summed over the expert layers."""
    d = s.d_model
    return (s.conv_layers * shortconv_matmul_params(s)
            + s.attn_layers * attention_matmul_params(s)
            + s.n_dense * dense_mlp_params(s)
            + s.expert_layers * d * s.n_routed
            + rows_per_token * expert_params(s)
            + s.vocab * d)


def forward_flops_per_token(s: ConvMoESizes, seqlen: int,
                            rows_per_token: float) -> float:
    causal = s.attn_layers * 2.0 * s.n_head * (seqlen + 1) * s.head_dim
    return 2.0 * active_matmul_params(s, rows_per_token) + causal


def executed_forward_flops_per_token(s: ConvMoESizes, seqlen: int,
                                     chunk_rows_per_token: float) -> float:
    """What the program's forward executes a token where an expert layer's
    live chunk of `chunk_rows_per_token` rows a token is computed whole
    (`parallel/moe.SharedRoutedFFN`: all `top_k` pairs at this share)."""
    return forward_flops_per_token(
        s, seqlen, s.expert_layers * chunk_rows_per_token)


def train_flops_per_token(s: ConvMoESizes, seqlen: int,
                          rows_per_token: float) -> float:
    attention = 12.0 * s.attn_layers * s.n_head * s.head_dim * seqlen
    return 6.0 * active_matmul_params(s, rows_per_token) + attention


def shortconv_cost(batch: int, seqlen: int, s: ConvMoESizes,
                   itemsize: int) -> CallCost:
    """One convolution layer's mixer over a step of `batch` sequences,
    forward and backward."""
    tokens = batch * seqlen
    return CallCost(6.0 * tokens * shortconv_matmul_params(s),
                    2.0 * tokens * 6 * s.d_model * itemsize)
