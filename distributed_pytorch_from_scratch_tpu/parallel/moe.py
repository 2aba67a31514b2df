"""Mixture-of-Experts FFN with expert parallelism (EP), TPU-native.

No reference counterpart: the reference's FFN is dense SwiGLU and it has no
router or expert sharding of any kind (SURVEY §2.4 "EP ❌",
`/root/reference/models/model.py:81-95`). This module is the framework
extension that turns the dense SwiGLU sublayer into a top-k routed MoE, with

* **Expert parallelism over the mesh axis 'ep'**: each ep shard owns
  `num_experts / ep` experts (leading expert dim of every expert weight is
  sharded with `P('ep', ...)`). Tokens are exchanged with ONE
  `lax.all_to_all` before and one after expert compute — the GShard/Switch
  dispatch pattern, riding ICI like every other collective here.

* **Tensor parallelism inside each expert over 'tp'**: gate/up are
  column-sharded, down is row-sharded — the same Megatron pattern as the
  dense FFN (`parallel/linear.py`), expressed as batched-over-experts
  einsums so the MXU sees one big (E_local, tokens, d) x (E_local, d, f)
  contraction instead of a Python loop over experts.

* **Static shapes throughout** (XLA requirement): routing uses the
  capacity-factor formulation — each expert accepts at most C tokens per ep
  shard; overflow tokens fall through the residual connection (standard
  Switch behaviour). With a generous `capacity_factor` nothing drops and
  the layer is exactly `sum_k gate_k * expert_k(x)`, which the equivalence
  tests exploit (routing is sharding-invariant in expectation AND in value
  when no token drops).

* **Dispatch/combine as static-shape scatter/gather**: each (token, k)
  routing resolves to a flat slot id `e * C + c`; dispatch is one
  scatter-add into the (E*C, d) expert buffer and combine is one gather
  back, weighted by the top-k gate values. Memory is O(S*k + E*C*d) —
  the earlier dense one-hot formulation built (S, E, C) masks, which is
  O(cf*k*S^2) and could not fit HBM at bench scale (ADVICE r2: ~4.1e9
  mask elements at b32 x t1000 x E8). Each expert slot receives at most
  one token (slot positions are a per-expert cumsum), so the scatter has
  no duplicate-index accumulation and stays bit-deterministic; dropped
  tokens route to one trash row that is sliced off. The transpose
  (backward) of scatter-add is a gather and vice versa — no sorts, no
  dynamic shapes.

Auxiliary losses follow Switch/ST-MoE: load-balance loss
`E * sum_e(frac_tokens_e * mean_prob_e)` and router z-loss
`mean(logsumexp(router_logits)^2)`. `apply` returns LOCAL sums; the model's
loss_shard psums them over the batch axes so the totals are independent of
how tokens are sharded (tests assert this).
"""

from __future__ import annotations

import contextlib
import functools
import math
from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..ops.collectives import copy_to, reduce_from
from ..ops.pallas import sum_held as sum_held_kernel
from ..runtime.prng import fold

Params = Dict[str, Any]


@dataclass(frozen=True)
class MoEFFN:
    """Top-k routed SwiGLU experts; drop-in for the dense FFN sublayer."""

    d: int                 # model dim
    f: int                 # per-expert hidden dim
    num_experts: int
    top_k: int = 2
    # Per-expert slots per ep shard: C = ceil(capacity_factor * S * k / E)
    # where S = local tokens. >= E/k guarantees zero drops for any routing;
    # 2.0 is a training-friendly default with rare drops.
    capacity_factor: float = 2.0
    # Renormalise the top-k gate weights to sum to 1 (Mixtral style). False
    # keeps raw softmax mass (Switch style).
    renormalize: bool = True
    ep_size: int = 1
    tp_size: int = 1
    ep_axis: str = "ep"
    tp_axis: str = "tp"

    def __post_init__(self):
        if self.num_experts % self.ep_size != 0:
            raise ValueError(f"num_experts {self.num_experts} not divisible "
                             f"by ep_size {self.ep_size}")
        if self.f % self.tp_size != 0:
            raise ValueError(f"expert ffn dim {self.f} not divisible by "
                             f"tp_size {self.tp_size}")
        if not (1 <= self.top_k <= self.num_experts):
            raise ValueError(f"top_k {self.top_k} out of range for "
                             f"{self.num_experts} experts")

    # ---- init / specs ----

    def init(self, key: jax.Array) -> Params:
        E, d, f = self.num_experts, self.d, self.f

        def expert_w(k, idim, odim):
            bound = 1.0 / math.sqrt(idim)
            return jax.random.uniform(k, (E, idim, odim), jnp.float32,
                                      -bound, bound)

        return {
            # router kept tiny + f32; zero-init (standard: uniform routing at
            # step 0, so early training matches the dense layer's scale)
            "router": jnp.zeros((d, E), jnp.float32),
            "gate": expert_w(fold(key, "gate"), d, f),
            "up": expert_w(fold(key, "up"), d, f),
            "down": expert_w(fold(key, "down"), f, d),
        }

    def specs(self) -> Params:
        ep, tp = self.ep_axis, self.tp_axis
        return {
            "router": P(None, None),
            "gate": P(ep, None, tp),
            "up": P(ep, None, tp),
            "down": P(ep, tp, None),
        }

    # ---- routing (static-shape, per ep shard) ----

    def _capacity(self, tokens: int) -> int:
        c = math.ceil(self.capacity_factor * tokens * self.top_k
                      / self.num_experts)
        return max(4, c)

    def _route(self, logits: jax.Array) -> Tuple[jax.Array, jax.Array, Params]:
        """(S, E) router logits -> flat slot ids (S, k) into the (E*C) expert
        buffer (E*C = trash for dropped tokens), combine weights (S, k), aux
        local sums."""
        S, E = logits.shape
        C = self._capacity(S)
        probs = jax.nn.softmax(logits, axis=-1)            # (S, E) f32
        topv, topi = lax.top_k(probs, self.top_k)          # (S, k)
        if self.renormalize:
            topv = topv / jnp.sum(topv, axis=-1, keepdims=True)

        # Position of each (slot, token) routing within its expert. Slot-major
        # priority (all slot-0 picks beat slot-1 picks), token order within a
        # slot — the Switch convention.
        onehot = jax.nn.one_hot(topi, E, dtype=jnp.int32)  # (S, k, E)
        flat = onehot.transpose(1, 0, 2).reshape(self.top_k * S, E)
        pos_flat = jnp.cumsum(flat, axis=0) - flat          # (k*S, E)
        pos = (pos_flat.reshape(self.top_k, S, E)
               .transpose(1, 0, 2))                         # (S, k, E)
        pos_tok = jnp.sum(pos * onehot, axis=-1)            # (S, k)
        keep = (pos_tok < C) & (topv > 0)                   # (S, k)

        # Flat slot id per (token, k): expert-major, trash slot E*C for drops.
        slots = jnp.where(keep, topi * C + pos_tok, E * C)  # (S, k)
        weights = jnp.where(keep, topv, 0.0)                # (S, k)

        aux = {
            # routed (pre-drop) assignment counts, the Switch f_e numerator
            "tokens_per_expert": jnp.sum(onehot, axis=(0, 1)).astype(jnp.float32),
            "prob_sum": jnp.sum(probs, axis=0),             # (E,)
            "z_sum": jnp.sum(jax.nn.logsumexp(logits, axis=-1) ** 2),
            "tokens": jnp.asarray(S, jnp.float32),
            "dropped": jnp.sum(1.0 - keep.astype(jnp.float32)),
        }
        return slots, weights, aux

    # ---- forward (per-shard, inside shard_map) ----

    def apply(self, params: Params, x: jax.Array,
              compute_dtype: jnp.dtype = jnp.float32
              ) -> Tuple[jax.Array, Params]:
        """x (b, t, d) -> (y (b, t, d), aux local sums).

        Must run inside shard_map over ('ep', 'tp'); x is the ep shard's
        local tokens, replicated over tp.
        """
        b, t, d = x.shape
        S = b * t
        xf = x.reshape(S, d)

        # Router in f32 for a stable softmax; stop-gradient-free (the router
        # trains through the combine weights).
        logits = xf.astype(jnp.float32) @ params["router"]
        slots, weights, aux = self._route(logits)
        E, C = self.num_experts, self._capacity(S)

        xd = xf.astype(compute_dtype)
        # Dispatch: scatter each kept (token, k) copy into its expert slot.
        # Every slot receives at most one token, plus the trash row E*C that
        # absorbs drops and is sliced off — deterministic, O(S*k*d) work.
        xk = jnp.broadcast_to(xd[:, None, :], (S, self.top_k, d))
        expert_in = (jnp.zeros((E * C + 1, d), compute_dtype)
                     .at[slots.reshape(-1)]
                     .add(xk.reshape(S * self.top_k, d), mode="drop")
                     [: E * C].reshape(E, C, d))

        if self.ep_size > 1:
            # (E, C, d) -> (E/ep, ep*C, d): each ep shard receives its own
            # experts' slots from every peer.
            expert_in = lax.all_to_all(expert_in, self.ep_axis,
                                       split_axis=0, concat_axis=1,
                                       tiled=True)

        # Batched Megatron FFN over the local experts: gate/up column-sharded
        # over tp (copy_to installs the psum of input grads), down
        # row-sharded (reduce_from sums the partial products).
        h_in = copy_to(expert_in, self.tp_axis)
        gate = jnp.einsum("ecd,edf->ecf", h_in,
                          params["gate"].astype(compute_dtype))
        up = jnp.einsum("ecd,edf->ecf", h_in,
                        params["up"].astype(compute_dtype))
        h = jax.nn.silu(gate) * up
        out = jnp.einsum("ecf,efd->ecd", h,
                         params["down"].astype(compute_dtype))
        out = reduce_from(out, self.tp_axis)

        if self.ep_size > 1:
            out = lax.all_to_all(out, self.ep_axis,
                                 split_axis=1, concat_axis=0, tiled=True)

        # Combine: gather each (token, k)'s expert output back (trash row ->
        # zeros) and sum weighted by the top-k gate values.
        out_flat = jnp.concatenate(
            [out.reshape(E * C, d), jnp.zeros((1, d), out.dtype)])
        picked = out_flat[slots.reshape(-1)].reshape(S, self.top_k, d)
        y = jnp.sum(picked * weights[..., None].astype(compute_dtype), axis=1)
        return y.reshape(b, t, d), aux


# ---- the sorted dispatch's row movers (SharedRoutedFFN) ----
#
# A chunk of the sorted pairs is M rows of them: sorted row r of the chunk
# holds token `tok[r]`. The chunk's first rows are the held pairs'; the
# rows past them are PADDING, which the movers own: `take_held` makes them
# zeros, `sum_held` selects them away before it multiplies, and so no
# cotangent of a padding row is read either (the grouped products'
# transposes write whatever they like there). Each mover is the other's
# transpose, and `_walk_chunks_bwd` writes that out, where autodiff would
# make the gather's a row scatter-add.
#
# What a 4 KB row costs on a v5e (bf16 x 2048; `scripts/
# tune_moe_dispatch.py` alone on the chip, PERF.md section 6, PRs 42, 50
# and 65): a gathered row 24 ns, an element-wise pass 12.5, and XLA:TPU's
# row scatter-add, which walks its updates one at a time whatever their
# indices, 75 - 82 ns a row at 49,152 rows and up, 110 at 16,384, 155 at
# 8,192. So no row scatter-add is left in the layer (`sum_held` below; PR
# 65), and both movers cost by the chunk's M rows, whatever the share of
# the experts a job holds (before PR 71 a job that held a sixth or more
# moved the rows of ONE chunk of all its pairs by k gathers a token
# through the sort's inverse permutation, and walked every pair for the
# third of them it held).


def take_held(x: jax.Array, tok: jax.Array, valid: jax.Array) -> jax.Array:
    """(S, d) tokens -> the chunk's (M, d) rows, which come back by
    `sum_held`: plain `x[tok]` with the padding rows SELECTED to zeros."""
    return jnp.where(valid, jnp.take(x, tok, axis=0), 0)


# `sum_held` adds a chunk's rows onto the sums a BLOCK of this many tokens
# at a time, from a WINDOW of this many of the chunk's token-sorted rows,
# which starts on a multiple of the kernel's 128 lanes (a slice the chip
# reads whole tiles of). A block of 256 tokens owns 256 rows of a full
# chunk of M = S rows in the mean, so a window of 512 less the alignment's
# 127 holds it with eight standard deviations to spare, and a block that
# owns more takes further windows (PERF.md section 6, PR 65).
SUM_BLOCK = 256
SUM_WINDOW = 512


def sum_held(y: jax.Array, r: jax.Array, tok: jax.Array, valid: jax.Array,
             interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """`take_held`'s transpose onto running sums: `y[s] + sum of the
    chunk's HELD rows r[m] with tok[m] == s`, summed in float32 and cast
    once, and the windows it took. No scatter: XLA:TPU's row scatter-add
    sorts its indices, gathers its updates into sorted order and then
    walks them a row at a time (110 - 155 ns a row where a gathered row
    costs 24). Here the rows go into token order the same way (ONE sort of
    the tokens, padding rows keyed past the last token, and ONE row
    gather), and then a block of `SUM_BLOCK` tokens owns a contiguous run
    of them, from `start[i]` (a compare and a sum: no scalar gather). The
    run is read a window of `SUM_WINDOW` rows at a time and summed onto
    the block's tokens by the matrix unit: the (block, window) one-hot of
    `the window's tokens == the block's` times the window, accumulated in
    float32 on top of y's block. One window holds a block's rows under any
    routing near balance; a block that owns more (every token on a few
    held experts: up to `SUM_BLOCK` k rows) takes as many windows as
    `start` says, so every pair that exists is summed.

    What follows the gather MULTIPLIES, so the padding rows, which hold
    whatever a grouped product's transpose left there, are SELECTED to
    zeros first: in token order they are the last rows, so the select is a
    compare of a row's place with the held rows' count where a window is
    read (as a pass of its own behind the gather it cost half of what the
    gather costs). A held row that is not finite spreads over its window's
    block of tokens (0 x inf), where the scatter kept it to its token.

    On a TPU the walk over the blocks is one Mosaic kernel
    (`ops/pallas/sum_held.py`; `interpret` asks for it under the
    interpreter) at whole blocks and windows; the `scan` below is its
    oracle and every other backend's text. Alone on a v5e at M = S =
    16,384 rows of 2048 in bf16: the row scatter-add 2.11 ms, the scan
    0.93 - 1.09, the kernel 0.50 (the sort 0.01, the gather 0.10, the
    walk 0.39) and 0.2 more where `y` is a jit input and not a loop's
    carry (PERF.md section 6, PR 65). The transpose is `take_held`
    (`_walk_chunks_bwd` writes it out); nothing here is differentiated."""
    (S, d), M = y.shape, r.shape[0]
    B, W = min(SUM_BLOCK, S), min(SUM_WINDOW, M)
    blocks = -(-S // B)
    with jax.named_scope("sum_held"):
        with jax.named_scope("index"):
            key = jnp.where(valid[:, 0], tok, blocks * B).astype(jnp.int32)
            tok_s, perm = lax.sort((key, lax.iota(jnp.int32, M)),
                                   num_keys=1, is_stable=True)
            edges = jnp.arange(blocks + 1, dtype=jnp.int32) * B
            start = jnp.sum(tok_s[:, None] < edges, axis=0, dtype=jnp.int32)
            lanes = sum_held_kernel.LANES
            first = start[:-1] // lanes * lanes
            windows = jnp.maximum(1, -(-(start[1:] - first) // W))
        # (a permutation is in bounds: "clip" spares the rows the select
        # that `take`'s default, a fill past the bounds, would pass them by)
        r_s, held = jnp.take(r, perm, axis=0, mode="clip"), start[-1]
        if interpret or (jax.default_backend() == "tpu"
                         and sum_held_kernel.fits(S, M, d, B, W)):
            # one set of mesh axes for the kernel's operands: the sums'
            vma = tuple(jax.typeof(y).vma)
            vary = lambda a: copy_to(a, vma) if vma else a
            return sum_held_kernel.sum_blocks(
                y, vary(r_s), vary(tok_s), vary(first), vary(windows),
                vary(held[None]), block=B, window=W,
                interpret=interpret), jnp.sum(windows)
        if blocks * B > S:
            y = jnp.pad(y, ((0, blocks * B - S), (0, 0)))

        def block(_, at_block):
            i, y_block, first, windows = at_block

            def window(j, acc):
                lo = first + j * W          # rows before it are summed
                at = jnp.minimum(lo, M - W)
                place = at + jnp.arange(W)
                rows = jnp.where((place < held)[:, None],
                                 lax.dynamic_slice_in_dim(r_s, at, W), 0)
                toks = lax.dynamic_slice_in_dim(tok_s, at, W)
                hot = ((toks == i * B + jnp.arange(B)[:, None])
                       & (place >= lo))
                return acc + jnp.dot(hot.astype(r.dtype), rows,
                                     precision=lax.Precision.HIGHEST,
                                     preferred_element_type=jnp.float32)

            acc = window(0, y_block.astype(jnp.float32))
            acc = lax.fori_loop(1, windows, window, acc)
            return None, acc.astype(y.dtype)

        _, out = lax.scan(block, None, (
            jnp.arange(blocks, dtype=jnp.int32), y.reshape(blocks, B, d),
            first, windows))
        return out.reshape(blocks * B, d)[:S], jnp.sum(windows)


# ---- the sorted dispatch's walk over its chunks (SharedRoutedFFN) ----
#
# Chunk c of the sorted pairs is rows [c M, c M + M): its tokens and
# weights, the rows of each held expert inside it (every group ends at its
# expert's own last row, so the groups cover the chunk's held rows and
# nothing more), and which rows are held at all.


def chunk_of(c, M: int, token: jax.Array, w_sorted: jax.Array,
             ends: jax.Array, rows_here: jax.Array):
    with jax.named_scope("moe_route"):
        lo = c * M
        sizes = jnp.diff(jnp.clip(ends - lo, 0, M),
                         prepend=0).astype(jnp.int32)
        tok = lax.dynamic_slice_in_dim(token, lo, M)
        wc = lax.dynamic_slice_in_dim(w_sorted, lo, M)
        valid = ((lo + jnp.arange(M)) < rows_here)[:, None]
        return lo, sizes, tok, wc, valid


def held_experts(rows: jax.Array, gate_up: jax.Array, down: jax.Array,
                 wc: jax.Array, sizes: jax.Array, valid: jax.Array,
                 act=jax.nn.silu) -> jax.Array:
    """A chunk's (M, d) rows through the held experts' two grouped products
    (`act(gate) * up` between them: the family's `activation`; `act(up)`
    where the experts are of two matrices, which is said by the first
    product's width: `gate_up` is then `up` alone, as wide as `down` is
    long) and times their weights. The grouped kernels write the rows of their
    groups and NOTHING ELSE: a row no group holds comes back as whatever
    the buffer held, from the forward products and from their transposes
    alike (a 5,000-fold gradient norm on the chip, PR 33; the CPU lowering
    zero-fills). The rows past the held pairs have NO group, so they are
    SELECTED away, never multiplied: going in and on the cotangent side by
    the movers, coming out by `valid` here (the weights' cotangent reads
    every row of `out`, so the select comes BEFORE the multiply: a
    weight's cotangent is the row itself). Between the two products they
    are garbage that nothing reads: a product and its transposes read
    their groups' rows."""
    f = down.shape[1]
    with jax.named_scope("moe_experts"):
        gu = lax.ragged_dot(rows, gate_up, sizes)
        hidden = (act(gu[:, :f]) * gu[:, f:] if gate_up.shape[-1] == 2 * f
                  else act(gu))
        out = lax.ragged_dot(hidden, down, sizes)
    with jax.named_scope("moe_route"):
        return jnp.where(valid, out, 0) * wc[:, None].astype(out.dtype)


def zeros_like_varying(a: jax.Array) -> jax.Array:
    """Zeros that vary over the mesh axes `a` varies over: a loop's carry."""
    vma = tuple(jax.typeof(a).vma)
    return copy_to(jnp.zeros_like(a), vma) if vma else jnp.zeros_like(a)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def walk_chunks(M: int, act, xd: jax.Array, gate_up: jax.Array,
                down: jax.Array, w_sorted: jax.Array, token: jax.Array,
                ends: jax.Array, rows_here: jax.Array):
    """The layer's walk over its chunks: rows in by `take_held`, through
    `held_experts`, back by `sum_held`, chunk after chunk UP TO
    THE LAST HELD ROW: a loop of `ceil(rows_here / M)` steps, so a chunk
    past the held rows costs nothing, forward or backward. Returns the
    (S, d) sums, the rows the groups covered, the rows walked (M a step)
    and the windows `sum_held` took over the live chunks' token blocks.
    The float operands vary over the same mesh axes (the caller casts
    them), so no collective runs inside a loop whose length differs
    between data shards.

    The transpose is written out, because autodiff's is what a fine chunk
    cannot afford: a `scan` over ALL the chunks whose every step, live or
    skipped by a `cond`, adds a chunk's cotangents of `gate_up`, `down`
    and the input (zeros, for a skipped one) to the running sums: 1.6 -
    2.2 ms a chunk and layer on a v5e at cells 5, 8 and 9's shapes,
    whatever the chunk holds (PERF.md section 6, PR 50). Here the sums
    are the loop's carry: the rows' cotangents are summed straight onto
    the input's (`sum_held` again: it is `take_held`'s transpose), a live
    chunk's weight cotangents are added once, and there is no other
    chunk."""
    n_live = -(-rows_here // M)

    def body(carry):
        c, y, computed, windows = carry
        _, sizes, tok, wc, valid = chunk_of(c, M, token, w_sorted, ends,
                                            rows_here)
        with jax.named_scope("moe_route"):
            rows = take_held(xd, tok, valid)
        out = held_experts(rows, gate_up, down, wc, sizes, valid, act)
        with jax.named_scope("moe_route"):
            y, took = sum_held(y, out.astype(y.dtype), tok, valid)
        return c + 1, y, computed + jnp.sum(sizes), windows + took

    zero = rows_here * 0
    c, y, computed, windows = lax.while_loop(
        lambda carry: carry[0] < n_live, body,
        (zero, zeros_like_varying(xd), zero, zero))
    return y, computed, c * M, windows


def _walk_chunks_fwd(M, act, xd, gate_up, down, w_sorted, token, ends,
                     rows_here):
    return (walk_chunks(M, act, xd, gate_up, down, w_sorted, token, ends,
                        rows_here),
            (xd, gate_up, down, w_sorted, token, ends, rows_here))


def _walk_chunks_bwd(M, act, res, g):
    xd, gate_up, down, w_sorted, token, ends, rows_here = res
    d_y = g[0]
    n_live = -(-rows_here // M)

    def body(carry):
        c, d_x, d_gate_up, d_down, d_w = carry
        lo, sizes, tok, wc, valid = chunk_of(c, M, token, w_sorted, ends,
                                             rows_here)
        with jax.named_scope("moe_route"):
            # the chunk's rows again (nothing of a chunk is kept), and
            # `sum_held`'s transpose: the sums' cotangent at its tokens
            rows = take_held(xd, tok, valid)
            d_out = jnp.take(d_y, tok, axis=0)
        _, pull = jax.vjp(
            lambda rows, gate_up, down, wc: held_experts(
                rows, gate_up, down, wc, sizes, valid, act).astype(d_y.dtype),
            rows, gate_up, down, wc)
        d_rows, d_gu, d_dn, d_wc = pull(d_out)
        with jax.named_scope("moe_route"):
            # `take_held`'s transpose, onto the running sum
            d_x, _ = sum_held(d_x, d_rows, tok, valid)
            d_w = lax.dynamic_update_slice_in_dim(d_w, d_wc, lo, 0)
        with jax.named_scope("moe_experts"):
            d_gate_up, d_down = d_gate_up + d_gu, d_down + d_dn
        return c + 1, d_x, d_gate_up, d_down, d_w

    _, d_x, d_gate_up, d_down, d_w = lax.while_loop(
        lambda carry: carry[0] < n_live, body,
        (rows_here * 0, *map(zeros_like_varying,
                             (xd, gate_up, down, w_sorted))))
    return d_x, d_gate_up, d_down, d_w, None, None, None


walk_chunks.defvjp(_walk_chunks_fwd, _walk_chunks_bwd)


# ---- the sorted dispatch's index work (SharedRoutedFFN) ----
#
# Beside the rows, a layer moves four bytes a (token, choice) pair: the
# chosen experts' scores, the weights into sorted order, the counts of the
# held and of all routed experts. Written as `take_along_axis`, `w[order]`
# and `bincount` each is an XLA scalar gather or scatter-add, which the
# chip walks an element at a time: 8 - 10 ns an element in the step, what a
# 4 KB row costs to move (PERF.md section 6, PR 43). So none is: a value
# picked by an index is a compare against an iota and a reduce over a
# one-hot that is never stored (one term is not zero: exact), a count is a
# column sum of such a one-hot, and a value that follows the sort rides it
# as an operand. Autodiff transposes the first into the same compare
# (a select of the cotangent, summed over the choices); the sort says its
# own transpose below.


def pick_scores(s: jax.Array, chosen: jax.Array) -> jax.Array:
    """(S, E) scores, (S, k) chosen -> `take_along_axis(s, chosen, -1)`."""
    with jax.named_scope("index"):
        hot = chosen[..., None] == jnp.arange(s.shape[-1], dtype=chosen.dtype)
        return jnp.sum(jnp.where(hot, s[:, None, :], 0), axis=-1)


def count_keys(key: jax.Array, length: int) -> jax.Array:
    """(N,) keys in [0, length) -> `bincount(key, length=length)`, int32."""
    hot = key[:, None] == jnp.arange(length, dtype=key.dtype)
    return jnp.sum(hot, axis=0, dtype=jnp.int32)


@jax.custom_vjp
def sort_pairs(key: jax.Array, w: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(N,) keys and weights -> `order = argsort(key, stable=True)` and
    `w[order]`: ONE sort with the pair's number and its weight as operands.
    The cotangent of `w` goes back by a sort on `order` (a permutation's
    inverse is the sort of it), where autodiff would gather through the
    sort and transpose that into a scalar scatter-add."""
    with jax.named_scope("moe_route"), jax.named_scope("index"):
        _, order, w_sorted = lax.sort(
            (key, lax.iota(jnp.int32, key.shape[0]), w), num_keys=1,
            is_stable=True)
        return order, w_sorted


def _sort_pairs_fwd(key, w):
    order, w_sorted = sort_pairs(key, w)
    return (order, w_sorted), order


def _sort_pairs_bwd(order, g):
    with jax.named_scope("moe_route"), jax.named_scope("index"):
        return None, lax.sort((order, g[1]), num_keys=1)[1]


sort_pairs.defvjp(_sort_pairs_fwd, _sort_pairs_bwd)


# The gate activations a family may state for `SharedRoutedFFN`'s experts
# (`activation`): SwiGLU's, ReGLU's (its zeros are computed like any other
# value: the grouped products are dense over a row's hidden width), and the
# squared ReLU of Nemotron-H's two-matrix experts (`gated` False).
ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu,
               "relu2": lambda x: jnp.square(jax.nn.relu(x))}

# A chunk of `SharedRoutedFFN`'s sorted pairs: the unit of the layer's WORK
# and of its MEMORY. Every mover and pass of a live chunk (the `x[tok]`
# gather, the selects, the weights' multiply, `act(gate) * up` between the two
# grouped products, `sum_held`'s sort, gather and sums, and the transposes
# of them all) walks the chunk's M rows whatever it holds, and the walk
# stops at the last held row (`walk_chunks`); the grouped products alone
# follow the held rows inside a chunk (the class docstring). So a layer pays for
# `M * ceil(rows_here / M)` rows (its `rows_walked` counter), and the
# chunk is `CHUNK_SHARES` times the job's mean share of the pairs. A finer
# grain walks less padding (about M / 2 a layer) and makes the step's
# staircase in a layer's held rows finer, at the price of more live chunks,
# each of which adds its cotangents of `gate_up` and `down` (whole, zeros
# for the experts it does not reach) to the running sums and calls the
# grouped kernel on fewer rows. One reading set it, not a law (TPU v5
# lite, 15 s windows at one data seed, `step_ms_p90` at 1 / 0.5 / 0.25 of
# a share against 707.8 / 625.9 / 705.1 at six shares: PERF.md section 6,
# PR 50): cell 9 559.1 / 554.9 / 586.5 (its balanced router holds 1.05
# shares a layer, so a second chunk of one share is nearly empty), cell 8
# 493.9 / 510.0 / 561.5, cell 5 632.5 / 655.6 / 667.9; `rows_walked /
# rows_here` 1.75 / 1.34 / 1.15, 1.45 / 1.23 / 1.10, 1.46 / 1.17 / 1.10.
# With autodiff's transpose (a `scan` over ALL the chunks under a `cond`)
# the same three grains read 628.7 / 680.1 / 822.2, 574.6 / 667.4 / 846.1
# and 775.4 / 909.6 / 1129.5: there every chunk, live or skipped, cost 1.6
# - 2.2 ms a layer, which is why the walk's transpose is written by hand.
CHUNK_SHARES = 1


@dataclass(frozen=True)
class SharedRoutedFFN:
    """A router over `num_experts` routed gated experts (`act(gate) * up`,
    then `down`: SwiGLU unless the family says otherwise), of which this job
    HOLDS `held` (experts [offset, offset + held)), plus shared experts
    every token takes: the DeepSeek-V3 FFN, as one chip of an
    expert-parallel deployment computes it between two all-to-alls.

    Facts a family states (fields, below the DeepSeek-V3 defaults):
    `score` "softmax" scores by a softmax over all routed experts and has
    no selection bias (no `bias` leaf); `shared_gate` multiplies the shared
    expert's output by `sigmoid(x w_sg)`, one scalar a token (the leaf
    `shared["gate_score"]`, d -> 1): Qwen3-Next's expert layer;
    `activation` is the experts' gate activation, held and shared alike
    (`ACTIVATIONS`: "silu", or "relu" for a ReGLU expert), which reaches
    `walk_chunks` and its hand-written transpose through `held_experts`;
    `n_group` > 1 limits the selection to groups
    (DeepSeek-V3's `noaux_tc` rule, which Ling-3.0 publishes too: the routed
    experts stand in `n_group` groups of equal size, one a node of the
    deployment; a group's score is the sum of its two largest biased
    scores, the `topk_group` best groups are kept and the `top_k` largest
    biased scores INSIDE them chosen, `select`, under the scope
    `moe_route/groups`; the layer then counts `groups_hit` (n_group,), the
    tokens of which a group got at least one choice), and `n_group` 1 is
    the selection over all of them, the program it has always been;
    `gated` False makes every expert, held and shared, TWO matrices, `down
    (act(up x))`, with no `gate` leaf (Nemotron-H's `relu2` experts);
    `latent` is the width the ROUTED experts read and write where that is
    not the model's: the tokens go down `latent["down"]` (d -> latent, no
    bias, no norm) before the dispatch and the experts' weighted sum comes
    back up `latent["up"]` (latent -> d) after it, under the scopes
    `moe_latent/down` and `moe_latent/up`, so the rows the movers carry and
    the grouped products' contraction are `latent` wide, while the router
    and the shared expert read the d-wide token (Nemotron 3's latent
    experts); `shared_width` is the shared expert's hidden width where it
    is not `n_shared` routed experts'. And
    one the CALLER states, a call at a time: `apply`'s
    optional `router_x` is what the router reads where that is not what
    the experts read (a family whose router reads the layer's input,
    before attention: the routing's index work, `route`, `sort_pairs` and
    `index`, then depends on nothing the attention half computes, runs
    under the inner scope `moe_route/early`, and the compiler places it
    where it likes; the router's gradient enters the residual stream
    through `router_x`).

    Routing (float32): `s = sigmoid(x W_r)` over all routed experts; the
    `top_k` largest of `s + bias` are chosen (`bias` is the selection bias
    of auxiliary-loss-free balancing: a leaf no gradient reaches, moved
    after every optimizer step by `training/optim.router_bias_step` from
    this layer's `routed` counter where the family's configuration
    publishes the rule's speed, `DecoderStack.router_bias_speed`, and left
    at zero where it does not); the weights are
    `s[chosen]`, normalised over ALL chosen experts, held or not, times
    `scaling`. The layer adds `w_e E_e(x)` for the chosen experts it holds
    and the shared expert; what an absent expert would have added is left
    out (with `held == num_experts` nothing is). No value here is looked up
    by an index: `s[chosen]` is a compare of `chosen` against an iota and a
    sum over the one-hot (`pick_scores`), the held and the routed experts'
    counts are column sums of such one-hots (`count_keys`), and the
    weights reach sorted order as an operand of the sort (`sort_pairs`):
    a scalar gather or scatter-add costs the chip what a 4 KB row costs
    (above `pick_scores`).

    Dispatch is sorted and grouped, with no capacity and NO DROP: the
    (token, choice) pairs are sorted by held expert (absent ones last) and
    the held experts' rows go through grouped matrix products
    (`lax.ragged_dot`: XLA:TPU makes it a grouped-matmul kernel whose grid
    follows the group sizes). The sorted pairs are walked in chunks
    (`chunk_rows`), and a chunk is the unit of the layer's WORK and of its
    MEMORY (above `CHUNK_SHARES`): the job's mean share of the pairs
    whatever share of the experts it holds (all the pairs where it holds
    them all), and the walk is a loop that STOPS at the last held row
    (`walk_chunks`, forward and its hand-written transpose), so the
    movers, the selects, the weights' multiply, `act(gate) * up` and their
    transposes walk `M * ceil(rows_here / M)` rows (`rows_walked`) and a
    chunk past the held rows costs nothing. Every pair that exists is
    computed whatever the routing (tests force all tokens onto a few
    experts).
    Rows go in by `take_held` (`x[tok]`, zeros SELECTED into the padding
    rows) and come back by `sum_held`: the chunk's rows put in token order
    (one sort of its tokens, one row gather), then summed onto the tokens a
    block of 256 at a time by one-hot products on the matrix unit, in
    float32, cast once (on a TPU one Mosaic kernel; `sum_held`'s docstring,
    and the layer's `sum_windows` / `sum_blocks` counters: the windows of
    sorted rows the blocks took, one each near balance, k where the one
    chunk is all the pairs of a job that holds every expert). Both cost by
    the chunk's M rows, and no XLA scatter is left in the layer: the row
    scatter-add `y.at[tok].add` this mover replaced cost 110 - 155 ns a
    row where a gathered row costs 24 (PR 65). **The products follow the
    rows**: each held expert's group ends at its own last row, the rows of
    a live chunk past its last held pair belong to NO group, and XLA:TPU's
    grouped kernel walks the groups it is given, so the products' time is
    the held rows' (PERF.md section 6, PR 47; before PR 50 a chunk was six
    mean shares, 98,304 rows for some 20,000 held at a share of an eighth,
    and everything but the products walked them all: PR 50; a job that
    held a sixth or more kept ONE chunk of all its pairs, moved by gathers
    through the sort's inverse, until PR 71). What that makes
    load-bearing:
    a row no group holds comes back from a product AND from its transposes
    as whatever the buffer held, so every such row is selected, never
    multiplied, on both sides of the products (`held_experts` and the
    movers around it). The step's
    time now follows the routing, seed by seed, where nothing balances the
    router (PR 33 read 1.2 - 2.3% between seeds).

    Tensor parallelism: every expert's gate/up are column-sharded and its
    down row-sharded over `tp_axis`, like the dense FFN; the router and the
    bias are replicated. There is no expert axis here: a job that holds
    several shares runs several of these (ROADMAP, expert parallelism).
    """

    d: int
    f: int                       # per-expert hidden width
    num_experts: int             # routed experts the router scores
    top_k: int
    held: "int | None" = None    # None: all
    offset: int = 0
    n_shared: int = 1
    scaling: float = 1.0
    tp_size: int = 1
    tp_axis: str = "tp"
    score: str = "sigmoid"       # or "softmax" (class docstring)
    shared_gate: bool = False
    activation: str = "silu"     # a key of ACTIVATIONS
    n_group: int = 1             # groups the selection is limited to
    topk_group: int = 1          # ... of which a token keeps this many
    gated: bool = True           # False: experts of two matrices, no gate
    latent: "int | None" = None  # the routed experts' width where not d
    shared_width: "int | None" = None   # where not n_shared * f

    def __post_init__(self):
        held = self.num_held
        if self.n_group > 1:
            if self.score != "sigmoid":
                raise ValueError("a group-limited selection is written for "
                                 "sigmoid scores")
            if (self.num_experts % self.n_group
                    or not 1 <= self.topk_group <= self.n_group):
                raise ValueError(
                    f"{self.num_experts} experts in n_group {self.n_group} "
                    f"groups of which topk_group {self.topk_group}: the "
                    f"groups must divide the experts and hold the kept")
            if (self.num_experts // self.n_group < 2 or self.topk_group
                    * (self.num_experts // self.n_group) < self.top_k):
                raise ValueError(
                    f"the {self.topk_group} kept groups of "
                    f"{self.num_experts // self.n_group} experts must hold "
                    f"two experts each and top_k {self.top_k} together")
        if self.score not in ("sigmoid", "softmax"):
            raise ValueError(f"score must be 'sigmoid' or 'softmax', got "
                             f"{self.score!r}")
        if self.activation not in ACTIVATIONS:
            raise ValueError(f"activation must be one of "
                             f"{sorted(ACTIVATIONS)}, got "
                             f"{self.activation!r}")
        if self.shared_gate and not self.n_shared:
            raise ValueError("shared_gate needs a shared expert")
        if not (0 <= self.offset and self.offset + held <= self.num_experts):
            raise ValueError(
                f"held experts [{self.offset}, {self.offset + held}) are not "
                f"among the {self.num_experts} routed experts")
        if not (1 <= self.top_k <= self.num_experts):
            raise ValueError(f"top_k {self.top_k} out of range for "
                             f"{self.num_experts} experts")
        if self.f % self.tp_size or self.shared_f % self.tp_size:
            raise ValueError(f"expert widths {self.f}, {self.shared_f} not "
                             f"divisible by tp_size {self.tp_size}")

    @property
    def num_held(self) -> int:
        return self.num_experts if self.held is None else self.held

    @property
    def shared_f(self) -> int:
        """The shared expert's hidden width."""
        return (self.n_shared * self.f if self.shared_width is None
                else self.shared_width)

    # ---- init / specs ----

    def init(self, key: jax.Array) -> Params:
        d, f, H = self.d, self.f, self.num_held
        l = self.latent or d        # what a routed expert reads and writes

        def w(k, shape, idim):
            bound = 1.0 / math.sqrt(idim)
            return jax.random.uniform(k, shape, jnp.float32, -bound, bound)

        p = {
            # a RANDOM router (the zero one of MoEFFN would send every
            # token to the first top_k experts: sigmoid ties at 0.5)
            "router": w(fold(key, "router"), (d, self.num_experts), d),
            # the selection bias: zeros, which only the rule of
            # training/optim.router_bias_step moves (never a gradient)
            "bias": jnp.zeros((self.num_experts,), jnp.float32),
            "gate": w(fold(key, "gate"), (H, l, f), l),
            "up": w(fold(key, "up"), (H, l, f), l),
            "down": w(fold(key, "down"), (H, f, l), f),
        }
        if self.score == "softmax":
            del p["bias"]
        if self.latent:
            p["latent"] = {"down": w(fold(key, "latent_down"), (d, l), d),
                           "up": w(fold(key, "latent_up"), (l, d), l)}
        if self.n_shared:
            fs = self.shared_f
            p["shared"] = {"gate": w(fold(key, "shared_gate"), (d, fs), d),
                           "up": w(fold(key, "shared_up"), (d, fs), d),
                           "down": w(fold(key, "shared_down"), (fs, d), fs)}
            if self.shared_gate:
                p["shared"]["gate_score"] = w(fold(key, "shared_gate_score"),
                                              (d, 1), d)
        if not self.gated:
            del p["gate"]
            if self.n_shared:
                del p["shared"]["gate"]
        return p

    def specs(self) -> Params:
        tp = self.tp_axis
        s = {"router": P(None, None), "bias": P(None),
             "gate": P(None, None, tp), "up": P(None, None, tp),
             "down": P(None, tp, None)}
        if self.n_shared:
            s["shared"] = {"gate": P(None, tp), "up": P(None, tp),
                           "down": P(tp, None)}
            if self.shared_gate:
                s["shared"]["gate_score"] = P(None, None)
        if self.score == "softmax":
            del s["bias"]
        if self.latent:
            s["latent"] = {"down": P(None, None), "up": P(None, None)}
        if not self.gated:
            del s["gate"]
            if self.n_shared:
                del s["shared"]["gate"]
        return s

    # ---- routing ----

    def route(self, params: Params, xf: jax.Array
              ) -> Tuple[jax.Array, jax.Array]:
        """(S, d) tokens -> chosen experts (S, k) int32 and their combine
        weights (S, k) float32. The router's product runs in float32 at
        precision "highest": a bf16 pass moves scores by 2^-9, which flips
        a top-k choice wherever two experts sit that close."""
        logits = jnp.dot(xf.astype(jnp.float32), params["router"],
                         precision=lax.Precision.HIGHEST)
        if self.score == "softmax":
            s = jax.nn.softmax(logits, axis=-1)
            _, chosen = lax.top_k(s, self.top_k)
        else:
            s = jax.nn.sigmoid(logits)
            chosen = self.select(s + lax.stop_gradient(params["bias"]))
        w = pick_scores(s, chosen)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * self.scaling
        return chosen, w

    def select(self, biased: jax.Array) -> jax.Array:
        """The `top_k` experts a token takes, (S, k) int32, from its biased
        scores (S, num_experts): the largest of them all, or, with
        `n_group` > 1, the largest inside the `topk_group` groups whose two
        largest scores sum highest (class docstring)."""
        if self.n_group > 1:
            with jax.named_scope("groups"):
                S = biased.shape[0]
                grouped = biased.reshape(S, self.n_group, -1)
                of_group = jnp.sum(lax.top_k(grouped, 2)[0], axis=-1)
                _, kept = lax.top_k(of_group, self.topk_group)
                keep = jnp.any(kept[..., None] == jnp.arange(self.n_group),
                               axis=1)                      # (S, n_group)
                biased = jnp.where(keep[..., None], grouped,
                                   -jnp.inf).reshape(S, -1)
        return lax.top_k(biased, self.top_k)[1]

    def groups_hit(self, chosen: jax.Array) -> jax.Array:
        """(n_group,) float32: the tokens of which a group got at least one
        choice (a token is sent to the nodes of the groups it hits)."""
        group = chosen // (self.num_experts // self.n_group)
        hit = jnp.any(group[..., None] == jnp.arange(self.n_group), axis=1)
        return jnp.sum(hit.astype(jnp.float32), axis=0)

    def index(self, chosen: jax.Array, w: jax.Array):
        """The sorted dispatch's index work over the (S, k) pairs, with no
        scalar gather or scatter (above `sort_pairs`): `order`, the pair of
        a sorted row, pairs sorted by held expert and absent experts last;
        `w_sorted`, the weights in that order; `ends` (held,), the sorted
        row each held expert's pairs end at; `routed` (num_experts,) int32,
        the pairs each routed expert was chosen for."""
        H = self.num_held
        local = chosen - self.offset
        here = (local >= 0) & (local < H)
        key = jnp.where(here, local, H).reshape(-1)             # (S*k,)
        order, w_sorted = sort_pairs(key, w.reshape(-1))
        with jax.named_scope("index"):
            ends = jnp.cumsum(count_keys(key, H + 1)[:H])
            routed = count_keys(chosen.reshape(-1), self.num_experts)
        return order, w_sorted, ends, routed

    @property
    def chunk_share(self) -> float:
        """The part of the (token, choice) pairs one chunk holds:
        `CHUNK_SHARES` times this job's mean share of them, all of them
        where it holds every expert (the family sizes the dispatch's
        buffers from it for `training/memory.py`)."""
        return min(1.0, CHUNK_SHARES * self.num_held / self.num_experts)

    def chunk_rows(self, pairs: int) -> int:
        """Rows a chunk of `pairs` sorted pairs holds: `chunk_share` of
        them, up to a multiple of 512 (the grouped kernel's row tile); all
        of them where that is more than there are. The last chunk may run
        past the pairs (`apply` pads)."""
        want = max(512, -(-int(self.chunk_share * pairs) // 512) * 512)
        return pairs if want >= pairs else want

    # ---- forward (per-shard, inside shard_map) ----

    def apply(self, params: Params, x: jax.Array,
              compute_dtype: jnp.dtype = jnp.float32,
              router_x: "jax.Array | None" = None
              ) -> Tuple[jax.Array, Params]:
        """x (b, t, d), replicated over tp -> (y (b, t, d), counters); the
        router reads `router_x` (b, t, d) where one is given, `x` otherwise
        (class docstring):
        `routed` (num_experts,) the pairs each routed expert was chosen
        for, `rows_here` the pairs whose expert is held, `rows_computed`
        the rows of the groups the grouped products were handed, over the
        chunks (the held rows: the counter says so of the program that
        ran), `rows_walked` the rows of the chunks whose body ran (what
        the movers and the passes paid for: `M * ceil(rows_here / M)`),
        `sum_blocks` the token blocks `sum_held` walked over the live
        chunks and `sum_windows` the windows of sorted rows they took (one
        a block unless a block owns more rows than a window holds), all
        float32 and local to this shard."""
        b, t, d = x.shape
        S, k = b * t, self.top_k
        xf = x.reshape(S, d)
        xd = copy_to(xf.astype(compute_dtype), self.tp_axis)
        xl = xd                 # what the routed experts read
        if self.latent:
            with jax.named_scope("moe_latent"), jax.named_scope("down"):
                xl = xd @ params["latent"]["down"].astype(compute_dtype)

        M = self.chunk_rows(S * k)
        act = ACTIVATIONS[self.activation]
        early = (contextlib.nullcontext() if router_x is None
                 else jax.named_scope("early"))
        with jax.named_scope("moe_route"), early:
            chosen, w = self.route(
                params, xf if router_x is None else router_x.reshape(S, d))
            order, w_sorted, ends, routed = self.index(chosen, w)
            rows_here = ends[-1]
            token = order // k
            counters = {"routed": routed.astype(jnp.float32),
                        "rows_here": rows_here.astype(jnp.float32)}
            if self.n_group > 1:
                with jax.named_scope("groups"):
                    counters["groups_hit"] = self.groups_hit(chosen)

        chunks = -(-S * k // M)
        if chunks * M > S * k:        # the last chunk runs past the pairs
            token = jnp.pad(token, (0, chunks * M - S * k))
            w_sorted = jnp.pad(w_sorted, (0, chunks * M - S * k))
        # gate and up as one grouped product: one pass over the rows
        gate_up = (jnp.concatenate([params["gate"], params["up"]], axis=-1)
                   if self.gated else params["up"]).astype(compute_dtype)
        down = params["down"].astype(compute_dtype)
        # one set of mesh axes for the walk's float operands: the rows'
        # (batch axes and tp)
        vma = tuple(jax.typeof(xl).vma)
        vary = lambda a: copy_to(a, vma) if vma else a
        y, computed, walked, windows = walk_chunks(
            M, act, xl, vary(gate_up), vary(down), vary(w_sorted), token,
            ends, rows_here)
        blocks = walked // M * -(-S // min(SUM_BLOCK, S))
        counters["rows_computed"] = computed.astype(jnp.float32)
        counters["rows_walked"] = walked.astype(jnp.float32)
        counters["sum_windows"] = windows.astype(jnp.float32)
        counters["sum_blocks"] = blocks.astype(jnp.float32)

        if self.latent:
            with jax.named_scope("moe_latent"), jax.named_scope("up"):
                y = y @ params["latent"]["up"].astype(compute_dtype)
        if self.n_shared:
            with jax.named_scope("moe_shared"):
                sp = params["shared"]
                if self.gated:
                    g = xd @ sp["gate"].astype(compute_dtype)
                    u = xd @ sp["up"].astype(compute_dtype)
                    hidden = act(g) * u
                else:
                    hidden = act(xd @ sp["up"].astype(compute_dtype))
                out = hidden @ sp["down"].astype(compute_dtype)
                if self.shared_gate:
                    # the gate's product reads whole tokens on every tp
                    # rank and scales this rank's partial sum
                    gate = jax.nn.sigmoid(
                        xd @ sp["gate_score"].astype(compute_dtype))
                    out = out * gate
                y = y + out
        y = reduce_from(y, self.tp_axis)
        return y.reshape(b, t, d), counters


def aux_zeros(num_experts: int) -> Params:
    """Zero aux sums with the same structure `MoEFFN.apply` returns — used
    as the scan unit for dense layers so MoE and dense bodies scan alike."""
    z = jnp.zeros((), jnp.float32)
    return {"tokens_per_expert": jnp.zeros((num_experts,), jnp.float32),
            "prob_sum": jnp.zeros((num_experts,), jnp.float32),
            "z_sum": z, "tokens": z, "dropped": z}


def aux_losses(aux: Params, num_experts: int, top_k: int
               ) -> Tuple[jax.Array, jax.Array]:
    """(load_balance_loss, z_loss) from GLOBALLY-summed aux stats.

    Switch load balance: E * sum_e(f_e * P_e) with f_e the fraction of
    routed assignments to expert e and P_e the mean router prob — minimised
    (== 1) by uniform routing. Callers psum the aux sums over the batch axes
    first so the value is sharding-invariant.
    """
    tokens = jnp.maximum(aux["tokens"], 1.0)
    f = aux["tokens_per_expert"] / (tokens * top_k)
    p = aux["prob_sum"] / tokens
    lb = num_experts * jnp.sum(f * p)
    z = aux["z_sum"] / tokens
    return lb, z
