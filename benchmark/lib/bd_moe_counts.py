"""Operations, bytes and parameters of the bd_moe family from its shapes:
the yardstick's own counts for the metrics the `train_bd_moe` runner feeds
(pinned by benchmark/tests/test_bd_moe_counts.py).

Conventions, beside those of benchmark/lib/flops.py and
benchmark/lib/conv_moe_counts.py:

* **A token is a DATA token.** A sequence of L data tokens goes through the
  stack as 2L ROWS, `[noised ; clean]`; `tokens_per_s` counts b x L. Every
  layer's matmuls touch a token's two rows, the head the noised one.
* **Parameters** (`param_counts`): what ONE job holds, the experts HELD and
  the vocabulary slice; not the published model.
* **The mask's live entries** (`live_entries`): `L (L + B)` a head and
  sequence of the `4 L^2` square: the block diagonal `L B`, noised to
  earlier clean blocks `L (L - B) / 2`, clean to clean `L (L + B) / 2`.
* **Active FLOPs per trained data token** (`train_flops_per_token`), the
  numerator of `train_step.active_mfu_pct`: 6 x the parameters the token's
  two rows touch here (attention and the router twice a layer, the routed
  experts at `rows_per_token`, the step's counter summed over the layers
  per DATA token, the head once; the embedding's lookup is no matmul), plus
  attention at the mask's LIVE entries, `12 H (L + B) head_dim` a layer
  (where every other cell counts the full T^2 of a causal kernel, this mask
  is the mechanism, and what it leaves live is the work). Recompute, the
  padding rows of a chunk computed whole and the entries a tile's plan
  computes dead are not counted.
* **The flash calls** (`bd_flash_call_cost`), the numerator of
  `kernels.bd_flash_roofline`: what the MASK needs whatever implements it:
  4 x head_dim FLOPs a live entry forward, 10 backward; each operand and
  result once, K and V once a key-value head, over 2L rows.
"""

from __future__ import annotations

from typing import Dict, NamedTuple

from benchmark.lib.flops import CallCost


class BdMoESizes(NamedTuple):
    d_model: int
    n_layer: int            # as run here; every layer an expert layer
    n_head: int
    n_kv_head: int
    head_dim: int           # heads x head_dim need not be d_model
    d_expert: int
    n_routed: int           # experts the router scores (published)
    n_held: int             # of which this job holds
    top_k: int
    vocab: int              # the slice held
    block_length: int

    @property
    def expert_layers(self) -> int:
        return self.n_layer


def attention_matmul_params(s: BdMoESizes) -> int:
    d = s.d_model
    return 2 * d * s.n_head * s.head_dim + 2 * d * s.n_kv_head * s.head_dim


def attention_params(s: BdMoESizes) -> int:
    return attention_matmul_params(s) + 2 * s.head_dim     # q and k norms


def expert_params(s: BdMoESizes) -> int:
    return 3 * s.d_model * s.d_expert


def ffn_params(s: BdMoESizes, held: "int | None" = None) -> int:
    """A layer's FFN: the router and the experts `held` (this job's by
    default). No shared expert, no selection bias."""
    held = s.n_held if held is None else held
    return s.d_model * s.n_routed + held * expert_params(s)


def param_counts(s: BdMoESizes) -> Dict[str, int]:
    """Parameters this job holds, by part."""
    d = s.d_model
    layer = attention_params(s) + 2 * d + ffn_params(s)
    return {
        "attention": attention_params(s),
        "router": d * s.n_routed,
        "expert": expert_params(s),
        "ffn": ffn_params(s),
        "ffn_uncut": ffn_params(s, s.n_routed),
        "layer": layer,
        "layer_uncut": attention_params(s) + 2 * d
        + ffn_params(s, s.n_routed),
        "embedding_and_head": 2 * s.vocab * d,
        "total": s.n_layer * layer + 2 * s.vocab * d + d,
    }


def live_entries(seqlen: int, block_length: int) -> int:
    """Score entries the mask leaves live, a head and sequence of `seqlen`
    data tokens (2 x `seqlen` rows)."""
    return seqlen * (seqlen + block_length)


def active_matmul_params(s: BdMoESizes, rows_per_token: float) -> float:
    """Parameters one DATA token's matmuls touch in this job: its two rows
    through every layer's attention and router, the routed experts at
    `rows_per_token` (summed over the layers, both rows), the head once."""
    return (2 * s.n_layer * (attention_matmul_params(s)
                             + s.d_model * s.n_routed)
            + rows_per_token * expert_params(s)
            + s.vocab * s.d_model)


def forward_flops_per_token(s: BdMoESizes, seqlen: int,
                            rows_per_token: float) -> float:
    scores = s.n_layer * 4.0 * s.n_head * s.head_dim * (
        live_entries(seqlen, s.block_length) / seqlen)
    return 2.0 * active_matmul_params(s, rows_per_token) + scores


def train_flops_per_token(s: BdMoESizes, seqlen: int,
                          rows_per_token: float) -> float:
    attention = 12.0 * s.n_layer * s.n_head * s.head_dim * (
        live_entries(seqlen, s.block_length) / seqlen)
    return 6.0 * active_matmul_params(s, rows_per_token) + attention


def bd_flash_call_cost(batch: int, seqlen: int, s: BdMoESizes,
                       itemsize: int, backward: bool) -> CallCost:
    """One flash call over `batch` sequences of `seqlen` data tokens (2 x
    `seqlen` rows): `n_head` query heads over `n_kv_head` key-value heads,
    all `head_dim` wide, at the mask's live entries."""
    entries = batch * s.n_head * live_entries(seqlen, s.block_length)
    rows = 2 * seqlen
    q = batch * s.n_head * rows * s.head_dim * itemsize
    kv = batch * s.n_kv_head * rows * s.head_dim * itemsize
    vector = batch * s.n_head * rows * 4
    if backward:        # q, o, do, dq; k, v, dk, dv; lse, delta
        return CallCost(10.0 * s.head_dim * entries,
                        4 * q + 4 * kv + 2 * vector)
    return CallCost(4.0 * s.head_dim * entries, 2 * q + 2 * kv + vector)
