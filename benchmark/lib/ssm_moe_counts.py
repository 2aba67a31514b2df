"""Operations, bytes and parameters of the ssm_moe family from its shapes:
the yardstick's own counts for the metrics the `train_ssm_moe` runner feeds
(pinned by benchmark/tests/test_ssm_moe_counts.py).

Conventions, beside those of benchmark/lib/flops.py, mla_moe_counts.py and
gdn_moe_counts.py:

* **Parameters** (`param_counts`): what ONE job holds: the SSM heads and B /
  C groups, the query and key-value heads, the experts HELD and the
  vocabulary slice, not the published model. A layer is one norm and one
  sublayer: `n_mamba_layer` Mamba-2 mixers, `n_attn_layer` attentions,
  `n_moe_layer` expert FFNs (router, selection bias, both latent
  projections and the shared expert whole; the held experts of TWO matrices
  `d_latent x d_expert` each).
* **Forward FLOPs a token** (`forward_flops_per_token`): 2 x the parameters
  a token's matmuls touch here (the routed experts at `rows_per_token`, the
  step's counter summed over the layers), attention's scores counted
  CAUSALLY, and the chunked recurrence's own products.
* **Active FLOPs per trained token** (`train_flops_per_token`), the
  numerator of `train_step.active_mfu_pct`: 6 x the same parameters, plus
  attention at the FULL T^2 in the attention layers (the convention of
  every `mfu` in this benchmark: `12 H T head_dim` a layer), plus three
  times the recurrence's forward products. Recompute is not counted.
* **The chunked recurrence** (`ssd_flops_per_token`, ONE layer's heads, at
  chunk C): a chunk's `C B^T` scores once a GROUP (2 C N), the scores times
  `dt x` a head (2 C P), a head's own chunk state and the entering state's
  part (2 P N each). A count of the MATHEMATICS at that chunk, whatever
  implements it: the decays' float32 passes over C x C a head and chunk are
  time and not work. `ssd_cost` is a layer's recurrence over a step, forward
  and backward (three times the forward's FLOPs), and the bytes of x and y
  (compute dtype, P a head), B and C (compute dtype, N a group), dt (float32
  a head) and the chunk states (float32, P x N a head and chunk), each once
  each way.
* **The latent experts' products** (`latent_expert_products_cost`): 12 l f
  FLOPs a computed row (two products of l x f, forward and twice backward)
  and three passes over the held experts' TWO matrices and the l-wide rows:
  `model.moe_experts_roofline` reckons 18 d f and three matrices, so this
  family has a reader of its own.
"""

from __future__ import annotations

from typing import Dict, NamedTuple

from benchmark.lib.flops import CallCost


class SsmMoESizes(NamedTuple):
    d_model: int
    m_head: int            # the SSM heads HELD
    m_head_dim: int        # P
    m_state: int           # N
    m_group: int           # the B / C groups HELD
    conv: int              # the convolution's taps
    chunk: int
    n_head: int            # the query heads HELD
    n_kv_head: int         # the key-value heads HELD
    head_dim: int
    d_latent: int          # what a routed expert reads and writes
    d_expert: int
    d_shared: int          # the shared expert's hidden width
    n_routed: int          # experts the router scores (published)
    n_held: int            # of which this job holds
    top_k: int
    pattern: str           # a letter a layer: M, *, E
    mtp_pattern: str       # the module's layers ("" where it is left out)
    vocab: int             # the slice held

    @property
    def n_mamba_layer(self) -> int:
        return (self.pattern + self.mtp_pattern).count("M")

    @property
    def n_attn_layer(self) -> int:
        return (self.pattern + self.mtp_pattern).count("*")

    @property
    def n_moe_layer(self) -> int:
        return (self.pattern + self.mtp_pattern).count("E")

    @property
    def expert_layers(self) -> int:
        return self.n_moe_layer

    @property
    def n_layer(self) -> int:
        return len(self.pattern)

    @property
    def m_inner(self) -> int:
        return self.m_head * self.m_head_dim

    @property
    def m_conv_channels(self) -> int:
        return self.m_inner + 2 * self.m_group * self.m_state


def mamba_matmul_params(s: SsmMoESizes) -> int:
    """[z | xBC | dt] and the out projection."""
    return (s.d_model * (s.m_inner + s.m_conv_channels + s.m_head)
            + s.m_inner * s.d_model)


def mamba_params(s: SsmMoESizes) -> int:
    return (mamba_matmul_params(s) + s.m_conv_channels * (s.conv + 1)
            + 3 * s.m_head + s.m_inner)     # A_log, D, dt_bias; the norm


def attn_params(s: SsmMoESizes) -> int:
    return 2 * s.d_model * s.head_dim * (s.n_head + s.n_kv_head)


def expert_params(s: SsmMoESizes) -> int:
    return 2 * s.d_latent * s.d_expert


def ffn_params(s: SsmMoESizes, held: "int | None" = None) -> int:
    """An expert layer's FFN: router, bias, the latent projections, the
    shared expert, the experts `held`."""
    held = s.n_held if held is None else held
    return (s.d_model * s.n_routed + s.n_routed
            + 2 * s.d_model * s.d_latent + 2 * s.d_model * s.d_shared
            + held * expert_params(s))


def param_counts(s: SsmMoESizes) -> Dict[str, int]:
    """Parameters this job holds, by part (a layer with its one norm)."""
    d = s.d_model
    mamba, attn, moe = (mamba_params(s) + d, attn_params(s) + d,
                        ffn_params(s) + d)
    module = 2 * d * d + 3 * d if s.mtp_pattern else 0
    return {
        "mamba_layer": mamba, "attn_layer": attn, "moe_layer": moe,
        "moe_layer_uncut": ffn_params(s, s.n_routed) + d,
        "mtp_joints": module,
        "embedding_and_head": 2 * s.vocab * d,
        "total": (s.n_mamba_layer * mamba + s.n_attn_layer * attn
                  + s.n_moe_layer * moe + module + 2 * s.vocab * d + d),
    }


def active_matmul_params(s: SsmMoESizes, rows_per_token: float) -> float:
    """Parameters one token's matmuls touch in this job. `rows_per_token` is
    summed over the expert layers. The embedding's lookup and the depthwise
    convolution are no matmuls."""
    d = s.d_model
    return (s.n_mamba_layer * mamba_matmul_params(s)
            + s.n_attn_layer * attn_params(s)
            + s.n_moe_layer * (d * s.n_routed + 2 * d * s.d_latent
                               + 2 * d * s.d_shared)
            + rows_per_token * expert_params(s)
            + (2 * d * d if s.mtp_pattern else 0)
            + (2 if s.mtp_pattern else 1) * s.vocab * d)


def ssd_flops_per_token(s: SsmMoESizes) -> float:
    """The chunked recurrence's forward FLOPs a token, all heads of one
    layer."""
    return (s.m_group * 2.0 * s.chunk * s.m_state
            + s.m_head * (2.0 * s.chunk * s.m_head_dim
                          + 4.0 * s.m_head_dim * s.m_state))


def forward_flops_per_token(s: SsmMoESizes, seqlen: int,
                            rows_per_token: float) -> float:
    causal = s.n_attn_layer * s.n_head * (seqlen + 1.0) * 2 * s.head_dim
    return (2.0 * active_matmul_params(s, rows_per_token) + causal
            + s.n_mamba_layer * ssd_flops_per_token(s))


def train_flops_per_token(s: SsmMoESizes, seqlen: int,
                          rows_per_token: float) -> float:
    attention = 12.0 * s.n_attn_layer * s.n_head * s.head_dim * seqlen
    return (6.0 * active_matmul_params(s, rows_per_token) + attention
            + 3.0 * s.n_mamba_layer * ssd_flops_per_token(s))


def ssd_cost(batch: int, seqlen: int, s: SsmMoESizes,
             itemsize: int) -> CallCost:
    """One layer's recurrence over a step of `batch` sequences, forward and
    backward."""
    tokens = batch * seqlen
    xy = tokens * 2 * s.m_inner * itemsize
    bc = tokens * 2 * s.m_group * s.m_state * itemsize
    dt = tokens * s.m_head * 4
    states = tokens / s.chunk * s.m_inner * s.m_state * 4
    return CallCost(3.0 * tokens * ssd_flops_per_token(s),
                    2.0 * (xy + bc + dt + states))


def latent_expert_products_cost(rows: float, s: SsmMoESizes,
                                itemsize: int) -> CallCost:
    """One layer's grouped products over `rows` pairs, forward and
    backward."""
    passes = 3
    weights = s.n_held * expert_params(s) * itemsize
    return CallCost(passes * 4.0 * rows * s.d_latent * s.d_expert,
                    passes * (weights + 2 * rows * s.d_latent * itemsize))
