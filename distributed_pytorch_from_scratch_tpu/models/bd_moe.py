"""The `bd_moe` family: a grouped-query expert decoder trained by BLOCK
DIFFUSION (the SDAR architecture, `sdar_moe`), on the same decoder stack as
the other families.

`BlockDiffusionMoETransformer` is a subclass of `models/stack.DecoderStack`
and holds only what differs:

* **the training objective.** The host hands the step `(input_ids,
  target_ids, position_ids)` of shape (b, L) like every family; `input_ids`
  is the clean sequence `x0` and the shifted `target_ids` is NOT read (a
  position's logits predict the token AT that position). Inside the step
  the family draws the noise (`block_diffusion_noise`: one level `p` a
  sequence, a Bernoulli(p) mask a position, the mask token in place of a
  masked token), lays the rows `[xt ; x0]` out, 2L a sequence, with the
  positions `[0..L-1 ; 0..L-1]`, runs them through every layer under the
  declared attention mask `ops/attention.block_diffusion(block_length, L)`
  (`DecoderStack._attn_mask`), and takes the loss on the L noised rows
  only: `(1 / (b L)) sum_seq (1 / p_seq) sum_{i masked} CE(logits_i,
  x0_i)`, through the vocab-parallel CE. Tokens a second count DATA
  tokens, b x L, never the 2L rows;
* **the noise is one pure function of (seed, step, x0)**: the key is the
  model's `noise_seed` folded with the optimizer state's step count, which
  `training/train_step.py` hands `make_loss`'s function as a fifth argument
  (`draws_noise`), and with a checksum of `x0`; ONE `jax.random` draw a
  batch, outside the shard_map, so every mesh draws the same noise.
  `make_loss(given_noise=True)` takes
  `(xt, m, p)` as arrays instead (the tests and the benchmark's check hand
  the reference the step's own draw);
* **attention**: `num_heads` query heads over `num_kv_heads` key-value
  heads of `bd_moe.head_dim` (heads x width need not be the model's width:
  `DecoderStack.head_dim`), q and k normed per head before RoPE (half-split
  pairs over the whole head, computed from the position ids, which repeat);
  the stack's own (q, k, v) dispatch, so the flash kernel with its native
  grouping on the TPU;
* **the expert FFN**: `parallel/moe.SharedRoutedFFN(score="softmax",
  n_shared=0)`: the router scores all `cfg.num_experts` and normalises over
  the chosen, the job holds `cfg.bd_moe.experts_held` of them (one chip's
  share of an expert-parallel deployment; None = all); no token is
  dropped, no auxiliary loss, no shared expert;
* the plain RMSNorm (eps `rms_norm_eps`) everywhere, an untied head, no
  bias anywhere.

`forward_shard` / `make_forward` take rows ALREADY doubled (2L a sequence,
with their positions) and return logits for all of them: the tests of what
the mask means read it.

What is not made to work is refused with a message: where the model is
built (`refuses`), by ZeRO 2/3 and the bucketed reducer
(`hand_reduced_grads`), by `models/decode.py`, `generate.py` and the
serving engines (`decodable`: a block decoded by denoising steps is not a
token a step).

Named scopes inside the step, for a device trace's `op_name`: `bd_noise`
(the draw, the select, the rows, positions and loss weights), `gqa_attn`
(the projections, q/k norms, RoPE and `W_o`; the flash calls stay the
kernels' own), and `moe_route`, `moe_experts` (parallel/moe.py).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..config import ModelConfig
from ..ops.attention import block_diffusion
from ..parallel.linear import ColumnParallelLinear, RowParallelLinear
from ..parallel.moe import SharedRoutedFFN
from ..parallel.norm import RMSNorm
from .stack import DecoderStack, idle_expert_params


def block_diffusion_noise(seed, step, x0: jax.Array, mask_token_id: int,
                          eps: float):
    """(seed, step, x0 (b, L)) -> (xt (b, L), m (b, L) bool, p (b,)): per
    sequence `t ~ U(0, 1)` and `p = (1 - eps) t + eps`; per position `m ~
    Bernoulli(p)` independently; `xt = mask_token_id where m else x0`. A
    function of its arguments alone; the level and the L uniforms of a
    sequence come from ONE draw.

    The key is the seed folded with the step count AND with a checksum of
    the batch: a Python `seed` is a constant of the compiled step, so a job
    that must compile ONE step for all its seeds (the benchmark's cell:
    every `--seed` would otherwise miss the compile cache) keeps one
    `noise_seed` and still draws other noise for other data."""
    b, L = x0.shape
    odd = 2 * lax.iota(jnp.uint32, b * L).reshape(b, L) + 1
    checksum = jnp.sum(x0.astype(jnp.uint32) * odd, dtype=jnp.uint32)
    key = jax.random.fold_in(
        jax.random.fold_in(jax.random.key(seed), step), checksum)
    u = jax.random.uniform(key, (b, L + 1), jnp.float32)
    p = (1.0 - eps) * u[:, 0] + eps
    m = u[:, 1:] < p[:, None]
    return jnp.where(m, jnp.asarray(mask_token_id, x0.dtype), x0), m, p


@dataclass(frozen=True)
class BlockDiffusionMoETransformer(DecoderStack):
    """The bd_moe family (module docstring)."""

    family = "bd_moe"
    ffn_inputs = 0            # no dense MLP: every layer's FFN is routed
    tied_head = False
    decodable = False
    hand_reduced_grads = False
    config_extra = "bd_moe"
    attn_scope = "gqa_attn"
    _router_aux_losses = False
    draws_noise = True
    head_rows_share = 0.5       # the head reads the noised half
    refuses = {
        "pp_size > 1": "a pipeline's microbatches would each need their "
                       "noise and their doubled rows",
        "cp_size > 1": "the ring and Ulysses paths mask by a causal order "
                       "of positions; a sequence's two halves share theirs",
        "ep_size > 1": "a job holds one share of the experts, "
                       "cfg.bd_moe.experts_held; the all-to-all between "
                       "shares is not written",
        "sequence_parallel=True": "the router reads whole sequences and "
                                  "the loss reads half of the rows",
        "attn_t_real": "pad tokens would be routed, and the declared mask "
                       "takes no real length",
        "ZeRO stage 3": "",
    }

    def _check_facts(self):
        bd = self.cfg.bd_moe
        if not 0 <= bd.mask_token_id < self.cfg.vocab_size:
            raise ValueError(f"mask_token_id {bd.mask_token_id} is not in "
                             f"the vocabulary of {self.cfg.vocab_size}")

    # ---- facts for the stack and training/memory.py ----

    @property
    def head_dim(self) -> int:
        return self.cfg.bd_moe.head_dim

    @property
    def layer_extra_elems_per_token(self) -> float:
        """What a layer's backward holds at its fullest beside the d-wide
        tensors the dense skeleton counts, in elements of the compute dtype
        a ROW (the stack sees 2L rows a sequence): q, its rotated copy, the
        heads' output and the two cotangents the flash backward reads and
        writes at heads x head_dim where the skeleton counts them at d, k
        and v with their rotated copies and cotangents; and one chunk of
        the expert dispatch (`SharedRoutedFFN.chunk_share` of a row's
        pairs): rows in and out with their cotangents, the outputs and the
        scatter's operand in float32 (twice an element), and the hidden
        activations `[gate | up]`, their product and both cotangents. At a
        held share of 1/8 the chunk is one mean share, an eighth of all
        pairs, 1 row a row (six shares, 6 rows a row, until PR 50, when
        the chunk was what sized the step). The last term, 12.91 d a row,
        is what the chip counts beyond those and is SET FROM ITS READINGS
        (the flash backward's operands by head are most of it; not told
        apart): cell 8 on a v5e counts 13.461 GiB at rung `true` and 13.547
        at `flash`, the rung `auto` picks (the 0.76 GiB of kept outputs
        cost it 0.09: the peak is not where the stacks are longest), for
        steps this makes 13.40 and 14.16, so that both sit inside -1% /
        +5% (ledger, PR 61; my chip run, PR 62; without the term `true`
        made 12.59)."""
        moe = self._mods["moe"]
        chunk_rows = moe.chunk_share * moe.top_k
        f = self.cfg.bd_moe.moe_intermediate_size / self.tp_size
        attn = (5 * self.cfg.num_heads * self.head_dim + 6 * self.kv_dim
                - 2 * self.d) / self.tp_size
        return (attn + chunk_rows * (6 * self.d + 5 * f)
                + 12.91 * self.d / self.tp_size)

    # ---- sub-module definitions ----

    @functools.cached_property
    def _mods(self) -> Dict[str, Any]:
        cfg, bd = self.cfg, self.cfg.bd_moe
        d, eps = self.d, bd.rms_norm_eps
        qd = cfg.num_heads * bd.head_dim
        col = functools.partial(ColumnParallelLinear, add_bias=False,
                                gather_output=False)
        row = functools.partial(RowParallelLinear, add_bias=False,
                                split_input=False)
        return {
            "norm1": RMSNorm(d, eps),
            "wq": col(d, qd),
            "wk": col(d, self.kv_dim),
            "wv": col(d, self.kv_dim),
            # one weight vector for all query heads, one for all key heads
            "q_norm": RMSNorm(bd.head_dim, eps),
            "k_norm": RMSNorm(bd.head_dim, eps),
            "wo": row(qd, d),
            "norm2": RMSNorm(d, eps),
            "moe": SharedRoutedFFN(
                d, bd.moe_intermediate_size, cfg.num_experts,
                top_k=cfg.moe_top_k, held=bd.experts_held,
                offset=bd.expert_offset, n_shared=0, scaling=1.0,
                tp_size=self.tp_size, score="softmax"),
        }

    # ---- what differs inside the forward (per-shard, inside shard_map) ----

    def _attn_mask(self, t: int):
        """The rows of a sequence are its noised and its clean copy."""
        return block_diffusion(self.cfg.bd_moe.block_length, t // 2)

    def _noised_rows(self, input_ids: jax.Array, position_ids: jax.Array,
                     noise):
        """The batch as the stack sees it and as the loss reads it: (rows
        `[xt ; x0]` (b, 2L), targets `x0` (b, L), positions twice (b, 2L),
        loss weights `m / p` (b, L), this shard's counts)."""
        xt, m, p = noise
        with jax.named_scope("bd_noise"):
            rows = jnp.concatenate([xt, input_ids], axis=1)
            positions = jnp.concatenate([position_ids, position_ids], axis=1)
            masked = m.astype(jnp.float32)
            weight = masked / p[:, None]
            # sums over POSITIONS (a level counts once a position of its
            # sequence): `p_sum / positions` is the batch's mean level
            # (`weight_sum / positions` is the draw's own factor on the
            # loss, 1 in expectation: the loss over it is the weighted MEAN
            # CE of the masked positions, which a log can follow)
            counts = {"masked": jnp.sum(masked),
                      "p_sum": jnp.sum(jnp.ones_like(masked) * p[:, None]),
                      "weight_sum": jnp.sum(weight),
                      "positions": jnp.sum(jnp.ones_like(masked))}
        return rows, input_ids, positions, weight, counts

    def _draw_noise(self, step, input_ids: jax.Array):
        """The step's draw for the global batch, `(xt, m, p)`, from
        (`noise_seed`, `step`, `input_ids`): what `make_loss`'s function
        hands `loss_shard` as `noise`."""
        bd = self.cfg.bd_moe
        with jax.named_scope("bd_noise"):
            return block_diffusion_noise(self.noise_seed, step, input_ids,
                                         bd.mask_token_id, bd.noise_eps)

    @staticmethod
    def _noise_specs():
        """How the draw's arrays lie over the mesh: `xt` and `m` like the
        batch, `p` a sequence."""
        batch = P(("dp", "ep"), "cp")
        return batch, batch, P(("dp", "ep"))

    @staticmethod
    def param_counts(cfg: ModelConfig) -> Dict[str, int]:
        """The family's parameters by part (`DecoderStack.num_params`)."""
        bd = cfg.bd_moe
        d, h = cfg.attn_dim, bd.head_dim
        attn = 2 * d * cfg.num_heads * h + 2 * d * cfg.kv_heads * h + 2 * h
        experts = (d * cfg.num_experts                           # router
                   + cfg.experts_held * 3 * d * bd.moe_intermediate_size)
        return {"embedding_and_head": 2 * cfg.vocab_size * d, "final_norm": d,
                "layers": cfg.num_layers * (attn + 2 * d + experts)}

    @staticmethod
    def flops_per_step(cfg, batch, seqlen, num_params) -> float:
        """Per DATA token (`batch` x `seqlen` of them): a token's two rows,
        noised and clean, through every layer's attention, router and held
        experts (at a row's mean share of them); the head on the noised row
        only; the embedding's lookup is no matmul; attention at the entries
        the declared mask leaves live, `L + B` a head and data token."""
        bd = cfg.bd_moe
        layers = num_params - 2 * cfg.vocab_size * cfg.attn_dim - cfg.attn_dim
        layers -= idle_expert_params(cfg, cfg.num_layers,
                                     bd.moe_intermediate_size)
        return (6 * (2 * layers + cfg.vocab_size * cfg.attn_dim)
                * batch * seqlen
                + 12 * cfg.num_layers * batch * cfg.num_heads * seqlen
                * (seqlen + bd.block_length) * bd.head_dim)
