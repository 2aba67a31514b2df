"""The `dsa_moe` family: a grouped-query expert decoder whose every layer
CHOOSES ITS KEYS (the Keye-VL-2.0 language model, `KeyeVL2`; the mechanism is
DeepSeek-V3.2's sparse attention and its sparse training stage), on the same
decoder stack as the other families.

`SelectedAttentionMoETransformer` is a subclass of `models/stack.DecoderStack`
and holds only what differs:

* **the attention module is the layer's own** (`parallel/dsa.
  SelectedAttention` under the key `attn`: a layer's parameters hold no `wo`
  of the stack's, so the stack asks `_mix_counted`): `num_heads` query heads
  over `num_kv_heads` key-value heads of `dsa_moe.head_dim`, q and k normed
  per head before RoPE (half-split pairs over the whole head, from the
  position ids), and beside them the LIGHTNING INDEXER, which reads a
  `stop_gradient` of the layer's normed input: `indexer_num_heads` index
  heads of `indexer_head_dim` over ONE index key head score every earlier
  token, `I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])` in float32; a row
  keeps the `topk` keys of largest score (ties to the earlier key; all of
  them where it sees fewer) and the heads attend over that set alone. The
  set is DATA: `ops/index_select.selected_attention`, the one attention
  entry point that takes no `ops/attention.AttnMask`;
* **a layer's own loss** (`DecoderStack.layer_losses`): the KL of the
  heads' summed attention probabilities over a row's set, a constant, from
  the softmax of the row's index scores over the same set, a mean over the
  rows, summed over the layers and added to the CE with weight 1. By the two
  stop-gradients the CE's gradient at every leaf of `attn.indexer` is
  exactly zero and the KL's at every other leaf is exactly zero;
* **the expert FFN**: `parallel/moe.SharedRoutedFFN(score="softmax",
  n_shared=0)`: the router scores all `cfg.num_experts` and normalises over
  the chosen, the job holds `cfg.dsa_moe.experts_held` of them (one chip's
  share of an expert-parallel deployment; None = all); no token is dropped,
  no auxiliary loss, no shared expert;
* the plain RMSNorm (eps `rms_norm_eps`) everywhere, an untied head, no
  bias anywhere but the index key's LayerNorm.

What is not made to work is refused with a message: where the model is
built (`refuses`: the indexer under tensor parallelism among them), by ZeRO
2/3 and the bucketed reducer (`hand_reduced_grads`), by `models/decode.py`,
`generate.py` and the serving engines (`decodable`: a decoded token would
score the cache's index keys and read a selected cache, which
`serving/kv_manager.py`'s pools do not hold).

Named scopes inside the step, for a device trace's `op_name`: `gqa_attn`
(the main projections, q/k norms, RoPE and `W_o`), `dsa_index` (the
indexer's projections, LayerNorm and RoPE; on the XLA path the score too),
`dsa_select`, `dsa_attend`, `dsa_index_loss` (around the kernels
`dsa_select`, `dsa_flash_fwd` / `dsa_flash_bwd_dq` / `dsa_flash_bwd_dkv`,
`dsa_index_loss`), and `moe_route`, `moe_experts` (parallel/moe.py).
"""

from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Any, Dict

import jax
from jax.sharding import PartitionSpec as P

from ..config import ModelConfig
from ..ops.rope import rope_angles
from ..parallel.dsa import LightningIndexer, SelectedAttention
from ..parallel.moe import SharedRoutedFFN
from ..parallel.norm import RMSNorm
from .stack import DecoderStack, Params, idle_expert_params


def kept_pairs(seqlen: int, topk: int) -> int:
    """The (row, key) pairs a sequence of `seqlen` rows keeps: `sum_t min(t
    + 1, topk)`."""
    k = min(topk, seqlen)
    return k * (k + 1) // 2 + (seqlen - k) * k


def attention_of(cfg: ModelConfig) -> SelectedAttention:
    dm = cfg.dsa_moe
    return SelectedAttention(
        cfg.attn_dim, cfg.num_heads, cfg.kv_heads, dm.head_dim, dm.topk,
        LightningIndexer(cfg.attn_dim, dm.indexer_num_heads,
                         dm.indexer_head_dim, dm.rms_norm_eps),
        dm.rms_norm_eps)


@dataclass(frozen=True)
class SelectedAttentionMoETransformer(DecoderStack):
    """The dsa_moe family (module docstring)."""

    family = "dsa_moe"
    # does a layer write out what it chose beside its sums: `make_probe`'s
    # model (`_Probing`), and no step's (the sets are (t, t) a sequence and
    # layer)
    probe = False
    ffn_inputs = 0            # no dense MLP: every layer's FFN is routed
    tied_head = False
    decodable = False
    hand_reduced_grads = False
    config_extra = "dsa_moe"
    _router_aux_losses = False
    layer_losses = {"dsa_index_kl": "dsa_rows"}
    refuses = {
        "tp_size > 1": "the indexer's one key head, its head weights and "
                       "the head-summed target of its loss belong to every "
                       "rank; heads split over 'tp' would each need the "
                       "others' probabilities",
        "pp_size > 1": "a pipeline's microbatches would each carry their "
                       "layers' own loss through the schedule",
        "cp_size > 1": "a row's chosen keys lie anywhere in its past: the "
                       "ring and Ulysses paths hold a shard of it",
        "ep_size > 1": "a job holds one share of the experts, "
                       "cfg.dsa_moe.experts_held; the all-to-all between "
                       "shares is not written",
        "sequence_parallel=True": "the router and the indexer read whole "
                                  "sequences",
        "attn_t_real": "pad tokens would be routed and scored",
        "ZeRO stage 3": "",
    }

    # ---- facts for the stack and training/memory.py ----

    @property
    def head_dim(self) -> int:
        return self.cfg.dsa_moe.head_dim

    # the rows' sets travel from the selection to the backward's walks as one
    # bit a (row, key) pair, named with the lse (ops/index_select.py)
    flash_lse_bytes_per_pair = 1 / 8

    @property
    def layer_extra_elems_per_token(self) -> float:
        """What a layer's backward holds at its fullest beside the d-wide
        tensors the dense skeleton counts, in elements of the compute dtype
        a token: `models/bd_moe.py`'s attention and dispatch terms (q, its
        rotated copy, the heads' output and the two cotangents at heads x
        head_dim, k and v with theirs; one chunk of the expert dispatch),
        and the indexer's: qI with its rotated copy and the loss walk's
        gradient of it at `indexer_num_heads x indexer_head_dim`, the
        kernels' per-row float32 columns (each head's lse and delta, the
        head weights and their gradient, two elements each). The T x T
        score and the selection's temporaries are in NO term: the kernels
        make the score a tile in VMEM, and a row's set, one bit a pair,
        is kept with the heads' lse (`flash_lse_bytes_per_pair`:
        ops/pallas/dsa_attention.py). The last term is what the chip
        counts beyond those, SET FROM ITS READING (PERF.md section 5)."""
        dm = self.cfg.dsa_moe
        moe = self._mods["moe"]
        chunk_rows = moe.chunk_share * moe.top_k
        attn = (5 * self.cfg.num_heads * self.head_dim + 6 * self.kv_dim
                - 2 * self.d)
        index = (3 * dm.indexer_num_heads * dm.indexer_head_dim
                 + 4 * self.cfg.num_heads + 4 * dm.indexer_num_heads)
        return (attn + index
                + chunk_rows * (6 * self.d + 5 * dm.moe_intermediate_size)
                + LAYER_FIT_WIDTHS * self.d)

    # ---- sub-module definitions ----

    @functools.cached_property
    def _mods(self) -> Dict[str, Any]:
        cfg, dm = self.cfg, self.cfg.dsa_moe
        return {
            "norm1": RMSNorm(self.d, dm.rms_norm_eps),
            "attn": attention_of(cfg),
            "norm2": RMSNorm(self.d, dm.rms_norm_eps),
            "moe": SharedRoutedFFN(
                self.d, dm.moe_intermediate_size, cfg.num_experts,
                top_k=cfg.moe_top_k, held=dm.experts_held,
                offset=dm.expert_offset, n_shared=0, scaling=1.0,
                tp_size=self.tp_size, score="softmax"),
        }

    # ---- what differs inside the forward (per-shard, inside shard_map) ----

    def _positions(self, params: Params, x: jax.Array,
                   position_ids: jax.Array, dtype):
        """The heads' (cos, sin) and the indexer's, which rotates all of
        its narrower head at the same base."""
        dm = self.cfg.dsa_moe
        return x.astype(dtype), (
            *rope_angles(position_ids, dm.head_dim, self.cfg.rope_theta),
            *rope_angles(position_ids, dm.indexer_head_dim,
                         self.cfg.rope_theta))

    def _mix_counted(self, lp: Params, y: jax.Array, layer_pos, dtype):
        return self._mods["attn"].apply(lp["attn"], y, layer_pos, dtype,
                                        impl=self.attn_impl, probe=self.probe)

    def make_probe(self, mesh):
        """Jitted `(params, input_ids, position_ids)` -> what every layer
        chose on that batch, from the implementation the step runs:
        `dsa_score_rows` (layers, b, rows, t) float32, the index scores of
        a sequence's last rows (`ops/index_select.PROBE_ROWS`), and
        `dsa_live` (layers, b, t, t) int8, is key s in row t's set. A
        check's, and a test's: no step calls it."""
        probing = _Probing(**{**{f.name: getattr(self, f.name)
                                 for f in dataclasses.fields(self)},
                              "remat": False})

        def shard(params, input_ids, position_ids):
            _, aux, _ = probing._resolved(input_ids.shape[1])._trunk(
                params, input_ids, position_ids)
            return aux["dsa_score_rows"], aux["dsa_live"]

        batch = P(("dp", "ep"), "cp")
        rows = P(None, ("dp", "ep", "cp"))      # (cp is refused: one)
        return jax.jit(jax.shard_map(
            shard, mesh=mesh, in_specs=(self.specs(), batch, batch),
            out_specs=(rows, rows)))

    # ---- counts ----

    @staticmethod
    def param_counts(cfg: ModelConfig) -> Dict[str, int]:
        """The family's parameters by part (`DecoderStack.num_params`)."""
        dm, d = cfg.dsa_moe, cfg.attn_dim
        experts = (d * cfg.num_experts                           # router
                   + cfg.experts_held * 3 * d * dm.moe_intermediate_size)
        return {"embedding_and_head": 2 * cfg.vocab_size * d, "final_norm": d,
                "layers": cfg.num_layers * (attention_of(cfg).num_params()
                                            + 2 * d + experts)}

    @staticmethod
    def flops_per_step(cfg, batch, seqlen, num_params) -> float:
        """The MATHEMATICS, whatever implements it: every held parameter's
        matmul at a token's mean share of the experts (the embedding's
        lookup is none); the attention at the pairs a row KEEPS (`sum_t
        min(t + 1, topk)` a head, 4 x head_dim FLOPs a pair forward and
        twice that backward); the indexer's score at the whole TRIANGLE (2
        x heads x width a pair), forward once for the selection, and three
        times that for its loss (the score again and its two gradients);
        the loss's target at the kept pairs (2 x head_dim a head and pair).
        A walk that computes masked pairs, or makes the score again a
        kernel, does more: that is time, not work."""
        dm = cfg.dsa_moe
        n = num_params - cfg.vocab_size * cfg.attn_dim - idle_expert_params(
            cfg, cfg.num_layers, dm.moe_intermediate_size)
        kept = kept_pairs(seqlen, dm.topk)
        triangle = seqlen * (seqlen + 1) // 2
        index = 2 * dm.indexer_num_heads * dm.indexer_head_dim
        return (6 * n * batch * seqlen
                + cfg.num_layers * batch * (
                    (12 + 2) * cfg.num_heads * dm.head_dim * kept
                    + 4 * index * triangle))


class _Probing(SelectedAttentionMoETransformer):
    """The family with its layers' choice written out (`make_probe`)."""

    probe = True


# model widths a token the chip counts beyond the terms of
# `layer_extra_elems_per_token` (set from the benchmark's cell on a v5e,
# whose window read 13.861 GiB at `flash` and 14.917 at `dots`: PERF.md §5)
LAYER_FIT_WIDTHS = 3.0
