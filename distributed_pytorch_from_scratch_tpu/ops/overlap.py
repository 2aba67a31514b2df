"""Communication-overlap kernels: ring-decomposed collective matmuls and
bucketed gradient reduction.

The Megatron collectives in `parallel/linear.py` are monolithic: a
sequence-parallel column-linear all-gathers the FULL activation before the
first MXU flop, and a row-linear blocks on a full psum_scatter after the
last one — on a real mesh the ICI time is pure serial overhead. This module
decomposes exactly those collectives so the wire hides under the matmul
("On Optimizing the Communication of Model Parallelism", arXiv:2211.05322):

* `ag_matmul(x, ws, axis)` — chunked all-gather-then-multiply. Each rank
  starts from its local sequence chunk; every ring step issues the next
  `ppermute` hop AND the partial dot of the chunk already in hand — two
  ops with no data dependency, which XLA's latency-hiding scheduler runs
  concurrently. `ws` is a TUPLE of weights sharing one ring (wq/wk/wv,
  gate/up), so the fused path moves the same bytes as the single shared
  all-gather it replaces.

* `matmul_rs(x, w, axis)` — partial-dot-then-reduce-scatter, the same ring
  in reverse: each step computes the partial product for the chunk whose
  accumulator is about to arrive, and the add rides behind the hop.

Both carry custom VJPs so the backward overlaps too: ag_matmul's dx is a
matmul_rs ring (the conjugate), its dw re-gathers x chunks around the same
ring; matmul_rs mirrors. Numerics: the ring accumulates partial sums in a
fixed rank order, which is a DIFFERENT float summation order than
psum_scatter's — equivalence against the monolithic path is allclose at
the repo's standard tolerances, not bitwise (tests/test_overlap.py).

Ring convention (see `ops.collectives.ring_permute`): shift=+1 sends rank
i -> i+1, so after s forward hops rank r holds the chunk ORIGINATED by
rank (r - s) mod n; the reduce ring forwards accumulators the same
direction, with rank r at step s contributing to the chunk destined for
rank (r + n-1-s) mod n = (r - (s+1)) mod n.

RING ORDER. The full-sequence side of both ops (ag_matmul's outputs,
matmul_rs's input) holds its n sequence chunks in the order the ring
visits them, not in rank order: chunk j is the one that originated at rank
(r - j) mod n, this rank's own first. Every slice and every placement is
then at a static offset. The first version kept rank order and wrote each
partial dot into its output with a `dynamic_update_slice` at an offset that
depends on the rank: on a v5e those were stand-alone copies, 0.29 ms each
for a (8, 512, 2560) chunk and 42 ms of a 312 ms step of GPT-2 large at
dp2 x tp2, more than the rings hid (PERF.md section 6, PR 28). A consumer
that does not care where a token sits (gelu, the gated product, the next
matmul_rs) takes ring order as it is; one that does (attention, the loss)
goes through `ring_order`, the permutation between the two, which is its
own inverse and its own transpose and fuses into what reads it.

* `bucketed_psum(tree, axes, bucket_mb, reduce_dtype)` — DP/ZeRO-1
  gradient reduction in size-bounded buckets instead of one end-of-step
  blob: leaves are raveled + concatenated into <= bucket_mb buckets and
  each bucket issues its own psum the moment its last cotangent exists in
  the dataflow, so XLA can interleave the reductions with the remaining
  backward compute. `reduce_dtype=jnp.bfloat16` is the EQuARX-style
  compressed variant (arXiv:2506.17615): the WIRE carries bf16, the
  optimizer's f32 master accumulate is untouched (grads are cast back to
  f32 after the reduce; no stochastic rounding). `reduce_dtype=jnp.int8`
  compresses further: `lax.psum` cannot express the per-hop requantization
  a block-scaled int8 all-reduce needs, so the bucket routes through
  `_quantized_allreduce` — a hand-rolled reduce-scatter + all-gather ring
  (the EQuARX schedule itself) whose every hop carries int8 codes plus one
  f32 scale per `quant.WIRE_GROUP` elements (<1% overhead), quarter the
  f32 wire bytes; the accumulate between hops stays f32 on-rank.

* `exchange_grads(tree, axis)` — the default step's own sum of a layer's
  weight cotangents over 'dp' (PR 32): the identity forward, and in the
  backward one typed all-gather a leaf plus a sum in rank order, which the
  TPU backend runs as start / done pairs under the backward's dots, where
  the psum it replaces was one synchronous all-reduce at the layer's end.

* `ring_all_gather(x, axis, dim)` / `bucketed_reduce_scatter(...)` /
  `quantized_reduce_scatter(...)` — the ZeRO-2/3 wires (training/zero.py).
  `ring_all_gather` is the per-layer ZeRO-3 param gather: n-1 explicit
  ppermute hops (overlappable like the matmul rings) whose TRANSPOSE is
  the conjugate ring reduce-scatter — the backward's grad reduction,
  derived by autodiff. `bucketed_reduce_scatter` is `bucketed_psum` with
  the all-reduce swapped for one `psum_scatter` per bucket at IDENTICAL
  bucket boundaries (half the wire bytes; each rank receives only its
  per-leaf shards); its int8 wire routes through
  `quantized_reduce_scatter`, which is `quantized_allreduce` stopped
  after its reduce-scatter half.

* `ag_matmul(..., quantized=True)` / `matmul_rs(..., quantized=True)` —
  the `tp_overlap='ring_q'` variants: the SAME ring schedules, but every
  ppermute payload is int8 codes + per-token-row scales. GATHER rings
  (ag forward, both bwd re-gather rings) quantize ONCE at the chunk's
  origin rank — error is one rounding regardless of ring size — while
  REDUCE rings (rs forward, ag's dx ring) requantize the partial
  accumulator each hop (error grows ~linearly in n; bounds pinned in
  tests/test_quant.py). The matmuls consume dequantized operands at the
  original dtype, so MXU accumulate precision is unchanged.
  quantized=False stays bit-identical to the pre-quantization paths.

All ops MUST run inside `shard_map` code partitioned over `axis`.
"""

from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
# jax 0.9.0 has the varying -> invariant gather under this name only;
# `lax.all_gather(..., to="reduced")` returns the reduced type, which is not
# a replicated weight's cotangent type (and `slice` has no rule for it)
from jax._src.lax.parallel import all_gather_invariant

from .collectives import ring_permute
from .quant import (WIRE_GROUP, dequantize_groups, dequantize_rows,
                    quantize_groups, quantize_rows)


def _axis_size(axis: str) -> int:
    return lax.axis_size(axis)  # static int: mesh shape is trace-time known


def _ring_hop_q(z: jax.Array, axis: str, dtype):
    """One quantized ring hop of a full-precision payload: quantize to
    int8 + per-row scales, ppermute BOTH (codes and scales travel
    together), dequantize on arrival. The reduce-ring building block —
    each call adds one rounding to the circulating accumulator."""
    q, sc = quantize_rows(z)
    q = ring_permute(q, axis, shift=1)
    sc = ring_permute(sc, axis, shift=1)
    return dequantize_rows(q, sc, dtype)


def _like_primal(ct: jax.Array, primal: jax.Array) -> jax.Array:
    """Type a custom-VJP cotangent like its primal. A weight replicated
    over the batch axes meets activations that vary over them, so its
    per-shard cotangent varies over those axes too; shard_map's typing
    wants it back as replicated as the weight, which is the psum autodiff
    itself inserts for a plain `x @ w` (the transpose of the implicit
    varying cast on w). No-op where the types already agree (over 'dp'
    they do wherever the layer handed its weights over through
    `exchange_grads`), and under check_vma=False, where nothing carries a
    type."""
    extra = tuple(sorted(jax.typeof(ct).vma - jax.typeof(primal).vma))
    return lax.psum(ct, extra) if extra else ct


def _check_2d(name: str, x: jax.Array) -> None:
    if x.ndim < 2:
        raise ValueError(f"{name} needs a (..., seq, feature) operand, got "
                         f"shape {x.shape}")


def _chunks(a: jax.Array, n: int) -> "list[jax.Array]":
    """The n equal sequence chunks of `a` (dim -2), at static offsets."""
    tl = a.shape[-2] // n
    return [lax.slice_in_dim(a, j * tl, (j + 1) * tl, axis=-2)
            for j in range(n)]


# -------------------------------------------------------------- ring_order --

@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def ring_order(x: jax.Array, axis: str = "tp") -> jax.Array:
    """Permute the n sequence chunks of `x` (dim -2) between rank order and
    ring order: chunk j of the result is chunk (r - j) mod n of `x` on rank
    r. The map is an involution, so the same call goes either way, and a
    permutation's transpose is its inverse, so the VJP is the same call on
    the cotangent (a plain transpose of the dynamic slices would be the
    `dynamic_update_slice` copies this layout exists to avoid). `x` is a
    per-rank tensor (it varies over `axis`)."""
    n = _axis_size(axis)
    if n == 1:
        return x
    if x.shape[-2] % n != 0:
        raise ValueError(
            f"ring_order: sequence length {x.shape[-2]} not divisible by "
            f"axis {axis!r} size {n}")
    idx = lax.axis_index(axis)
    tl = x.shape[-2] // n
    return jnp.concatenate(
        [lax.dynamic_slice_in_dim(x, jnp.mod(idx - j, n) * tl, tl, axis=-2)
         for j in range(n)], axis=-2)


ring_order.defvjp(lambda x, axis: (ring_order(x, axis), None),
                  lambda axis, _, dy: (ring_order(dy, axis),))


# ---------------------------------------------------------- ring_all_gather --

def ring_all_gather(x: jax.Array, axis: str, dim: int = 0) -> jax.Array:
    """Ring-decomposed all-gather of `x` along `dim` over `axis`: rank r's
    chunk lands at slot r, so the result equals
    `lax.all_gather(x, axis, axis=dim, tiled=True)` exactly (pure data
    movement, no float reassociation).

    Decomposed into n-1 explicit `ppermute` hops (ring convention of this
    module: shift=+1, rank r holds rank (r-s)'s chunk after s hops) so
    XLA's latency-hiding scheduler can slide each hop under whatever
    compute is adjacent in the dataflow — the ZeRO-3 per-layer parameter
    gather issues this inside the layer scan, where the previous layer's
    matmuls are still in flight.

    The TRANSPOSE is the conjugate ring reduce-scatter: ppermute transposes
    to the reverse ppermute, so differentiating through this gather hands
    each rank the dp-SUMMED cotangent of its own chunk. That emergent reduce-scatter IS ZeRO-2/3's
    gradient wire: half the all-reduce bytes, derived by autodiff instead
    of hand-written.
    """
    n = _axis_size(axis)
    if n == 1:
        return x
    idx = lax.axis_index(axis)
    tl = x.shape[dim]
    out = jnp.zeros((*x.shape[:dim], tl * n, *x.shape[dim + 1:]), x.dtype)
    chunk = x
    for s in range(n):
        if s < n - 1:
            nxt = ring_permute(chunk, axis, shift=1)
        slot = jnp.mod(idx - s, n)  # origin rank of the chunk in hand
        out = lax.dynamic_update_slice_in_dim(out, chunk, slot * tl,
                                              axis=dim)
        if s < n - 1:
            chunk = nxt
    return out


# --------------------------------------------------------------- ag_matmul --

def _ag_matmul_impl(x: jax.Array, ws: Tuple[jax.Array, ...],
                    axis: str, quantized: bool) -> Tuple[jax.Array, ...]:
    """Ring all-gather-matmul forward: x (..., t/n, d) seq-sharded over
    `axis`, each w (d, o_local) -> each y (..., t, o_local) in RING ORDER,
    equal to `ring_order(all_gather(x, axis, tiled over -2) @ w)` up to
    summation order.

    quantized=True: the chunk is quantized ONCE here at its origin and the
    int8 codes + per-row scales circulate instead of the full-precision
    payload; every rank (the origin included, for cross-rank consistency)
    dequantizes before its dots — the output equals the monolithic path
    applied to dq(q(x)), one rounding per element total."""
    n = _axis_size(axis)
    parts = [[] for _ in ws]
    if quantized:
        q, sc = quantize_rows(x)
        chunk = dequantize_rows(q, sc, x.dtype)
    else:
        chunk = x
    for s in range(n):
        # issue the hop FIRST: it has no dependency on this step's dots, so
        # the scheduler overlaps the wire with the MXU work
        if s < n - 1:
            if quantized:
                q = ring_permute(q, axis, shift=1)
                sc = ring_permute(sc, axis, shift=1)
            else:
                nxt = ring_permute(chunk, axis, shift=1)
        # the chunk in hand originated at rank (r - s): ring position s
        for j, w in enumerate(ws):
            parts[j].append(chunk @ w)
        if s < n - 1:
            chunk = (dequantize_rows(q, sc, x.dtype) if quantized else nxt)
    return tuple(jnp.concatenate(p, axis=-2) for p in parts)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def ag_matmul(x: jax.Array, ws: Tuple[jax.Array, ...],
              axis: str = "tp",
              quantized: bool = False) -> Tuple[jax.Array, ...]:
    """Fused all-gather-matmul over a ring.

    `x` is this rank's (..., t/n, d) sequence chunk; `ws` a tuple of local
    (d, o_j) weights sharing ONE ring (same bytes on the wire as a single
    all-gather, however many weights consume it). Returns a tuple of
    (..., t, o_j) full-sequence outputs in RING ORDER (module docstring;
    `ring_order` gives rank order). The custom VJP reduces the fan-out
    cotangents on one reverse ring (dx) while re-gathering x chunks for the
    weight grads on a second — both overlapped the same way as the forward.

    `quantized` (tp_overlap='ring_q') puts int8 codes + per-row scales on
    every hop: the x chunks (fwd and the bwd re-gather ring) quantize once
    at origin; the bwd dx reduce ring requantizes its accumulator per hop.
    False is bit-identical to the unquantized ring.
    """
    _check_2d("ag_matmul", x)
    if not isinstance(ws, (tuple, list)) or not ws:
        raise ValueError("ag_matmul takes a non-empty tuple of weights "
                         "(one ring shared by all of them)")
    for w in ws:
        if w.ndim != 2 or w.shape[0] != x.shape[-1]:
            raise ValueError(
                f"ag_matmul weight shape {w.shape} does not contract with "
                f"x feature dim {x.shape[-1]}")
    return _ag_matmul_impl(x, tuple(ws), axis, quantized)


def _ag_matmul_fwd(x, ws, axis, quantized):
    return _ag_matmul_impl(x, tuple(ws), axis, quantized), (x, tuple(ws))


def _ag_matmul_bwd(axis, quantized, res, dys):
    x, ws = res
    n = _axis_size(axis)
    bdims = tuple(range(x.ndim - 1))  # batch+seq dims to contract for dw
    dy_chunks = [_chunks(dy, n) for dy in dys]   # ring order, like the ys

    dx_acc = None
    dws = [jnp.zeros_like(w) for w in ws]
    if quantized:
        # the re-gather ring circulates dq(q(x)) — the same x~ the forward
        # consumed, quantized once at origin
        q, sc = quantize_rows(x)
        chunk = dequantize_rows(q, sc, x.dtype)
    else:
        chunk = x
    for s in range(n):
        if s < n - 1:
            if quantized:
                q = ring_permute(q, axis, shift=1)
                sc = ring_permute(sc, axis, shift=1)
            else:
                nxt = ring_permute(chunk, axis, shift=1)
        # dw ring: the chunk in hand originated at rank (r - s), ring
        # position s; it pairs with the cotangent rows of that position.
        # dx ring (the conjugate reduce-scatter): this step contributes the
        # partial destined for rank (r - (s+1)), ring position s+1, whose
        # accumulator arrives next
        part = None
        for j, w in enumerate(ws):
            dws[j] = dws[j] + jnp.tensordot(
                chunk, dy_chunks[j][s], axes=(bdims, bdims))
            p = dy_chunks[j][(s + 1) % n] @ w.T
            part = p if part is None else part + p
        if s == 0:
            dx_acc = part
        elif quantized:
            # reduce ring: the accumulator requantizes each hop (the only
            # ring_q payload whose error grows with n)
            dx_acc = _ring_hop_q(dx_acc, axis, part.dtype) + part
        else:
            dx_acc = ring_permute(dx_acc, axis, shift=1) + part
        if s < n - 1:
            chunk = (dequantize_rows(q, sc, x.dtype) if quantized else nxt)
    return dx_acc.astype(x.dtype), tuple(
        _like_primal(dw.astype(w.dtype), w) for dw, w in zip(dws, ws))


ag_matmul.defvjp(_ag_matmul_fwd, _ag_matmul_bwd)


# --------------------------------------------------------------- matmul_rs --

def _matmul_rs_impl(x: jax.Array, w: jax.Array, axis: str,
                    quantized: bool) -> jax.Array:
    """Ring matmul-reduce-scatter forward: x (..., t, f_local) in RING
    ORDER, w (f_local, o) -> (..., t/n, o), equal to
    `psum_scatter(ring_order(x) @ w, axis, scatter over -2)` up to
    summation order.

    quantized=True: the circulating accumulator requantizes before each
    hop (int8 codes + per-row scales on the wire); the local partial dot
    and the add stay at the original dtype — n-1 roundings end-to-end."""
    n = _axis_size(axis)
    x_chunks = _chunks(x, n)
    acc = None
    for s in range(n):
        # the partial for rank (r - (s+1)), ring position s+1; the last
        # step's is this rank's own
        part = x_chunks[(s + 1) % n] @ w
        # the hop and the next step's dot are independent: wire hides
        if s == 0:
            acc = part
        elif quantized:
            acc = _ring_hop_q(acc, axis, part.dtype) + part
        else:
            acc = ring_permute(acc, axis, shift=1) + part
    return acc


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def matmul_rs(x: jax.Array, w: jax.Array, axis: str = "tp",
              quantized: bool = False) -> jax.Array:
    """Fused matmul-reduce-scatter over a ring (the ag_matmul conjugate).

    `x` holds this rank's partial-product input over the FULL sequence in
    RING ORDER (module docstring: what `ag_matmul` returns; `ring_order`
    converts rank order), `w` the local (f, o) weight; the result is this
    rank's summed (t/n) sequence chunk. Refuses a sequence length the ring cannot chunk evenly
    — pick a t divisible by the axis size (same constraint as
    `sequence_parallel` itself).

    `quantized` (tp_overlap='ring_q'): the forward reduce ring requantizes
    its accumulator per hop; the backward cotangent-gather ring quantizes
    once at origin. False is bit-identical to the unquantized ring.
    """
    _check_2d("matmul_rs", x)
    n = _axis_size(axis)
    if x.shape[-2] % n != 0:
        raise ValueError(
            f"matmul_rs: sequence length {x.shape[-2]} not divisible by "
            f"axis {axis!r} size {n} — the ring needs even chunks")
    if w.ndim != 2 or w.shape[0] != x.shape[-1]:
        raise ValueError(
            f"matmul_rs weight shape {w.shape} does not contract with x "
            f"feature dim {x.shape[-1]}")
    return _matmul_rs_impl(x, w, axis, quantized)


def _matmul_rs_fwd(x, w, axis, quantized):
    return _matmul_rs_impl(x, w, axis, quantized), (x, w)


def _matmul_rs_bwd(axis, quantized, res, dy):
    x, w = res
    n = _axis_size(axis)
    bdims = tuple(range(x.ndim - 1))
    x_chunks = _chunks(x, n)

    dx_parts = []
    dw = jnp.zeros_like(w)
    # ring-gather the cotangent chunks; quantized mode codes dy ONCE at
    # origin (a gather ring, like the forward ag chunks)
    if quantized:
        q, sc = quantize_rows(dy)
        chunk = dequantize_rows(q, sc, dy.dtype)
    else:
        chunk = dy
    for s in range(n):
        if s < n - 1:
            if quantized:
                q = ring_permute(q, axis, shift=1)
                sc = ring_permute(sc, axis, shift=1)
            else:
                nxt = ring_permute(chunk, axis, shift=1)
        # the cotangent in hand is rank (r - s)'s: ring position s of x
        dx_parts.append((chunk @ w.T).astype(x.dtype))
        dw = dw + jnp.tensordot(x_chunks[s], chunk, axes=(bdims, bdims))
        if s < n - 1:
            chunk = (dequantize_rows(q, sc, dy.dtype) if quantized else nxt)
    return (jnp.concatenate(dx_parts, axis=-2),
            _like_primal(dw.astype(w.dtype), w))


matmul_rs.defvjp(_matmul_rs_fwd, _matmul_rs_bwd)


# ------------------------------------------------- the dp gradient exchange --

# leaves under this many elements (biases, norm gains) share one gather
SMALL_LEAF = 1 << 16


def exchange_sum(x: jax.Array, axis: str) -> jax.Array:
    """The sum of every rank's `x` over `axis`, typed replicated over it:
    one gather of the n copies and a local sum in RANK ORDER, so every rank
    adds the same values in the same order and the replicas hold the same
    bits (the optimizer runs on each of them). At n = 2 the gather is one
    exchange with the peer, moves the bytes an all-reduce moves, and
    `a + b` rounded once is what the all-reduce returns. At n > 2 it moves
    (n-1) copies where a reduce-scatter + all-gather ring would move
    2(n-1)/n: that ring was written and compiled (PERF.md section 6,
    PR 32) and the TPU compiler made synchronous all-gathers and an
    all-reduce of it, so there is one form, timed at n = 2 only."""
    g = all_gather_invariant(x, axis)          # (n, ...) in rank order
    return functools.reduce(jnp.add, [g[r] for r in range(g.shape[0])])


def _exchange_tree(cts, axis: str):
    """`exchange_sum` of every leaf, each gather depending on its own leaf
    only: it starts where that cotangent is made. The small leaves ride
    together, grouped by dtype and by the axes they still vary over."""
    leaves, treedef = jax.tree.flatten(cts)
    out = list(leaves)
    small: "dict[tuple, list[int]]" = {}
    for i, g in enumerate(leaves):
        if g.size < SMALL_LEAF:
            key = (jnp.dtype(g.dtype).name, jax.typeof(g).vma)
            small.setdefault(key, []).append(i)
        else:
            out[i] = exchange_sum(g, axis)
    for idxs in small.values():
        flat = exchange_sum(
            jnp.concatenate([leaves[i].ravel() for i in idxs]), axis)
        off = 0
        for i in idxs:
            n = leaves[i].size
            out[i] = flat[off:off + n].reshape(leaves[i].shape)
            off += n
    return jax.tree.unflatten(treedef, out)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def exchange_grads(tree, axis: str = "dp"):
    """Hand a layer's weights, replicated over `axis`, to code whose
    activations vary over it: the identity, typed varying. The backward
    owns what the transpose of that cast would have been, the sum of the
    cotangents over `axis`, and runs it as `exchange_sum` a leaf.

    Why not the psum: XLA combines a layer's psums into one all-reduce that
    waits for the last cotangent and is the last op of the backward body,
    synchronous: on GPT-2 large at dp2 x tp2 16.0 ms of a 257 ms step in
    which the chip only waits for one ICI link (19.7 MB of bf16 a layer a
    chip at 44 GB/s). A v5e runs an all-gather as a start / done pair and
    keeps them apart: `proj`, `fc` and the attention projection finish
    under the rest of the layer's backward, two of q/k/v under the third's
    dot (PERF.md section 6, PR 32: the step 12.9 ms shorter). Inside the
    callee the weights vary over `axis`, so `_like_primal` and the
    transposes find nothing left to sum over it; cp, ep and tp sums stay
    where they were. For a caller whose activations are typed varying
    over `axis`: one that feeds every replica the same rows has nothing to
    sum, and under check_vma=False nothing is typed (the hand-reduced
    builders of training/zero.py, which sum once, by hand)."""
    return jax.tree.map(lambda a: lax.pcast(a, (axis,), to="varying"), tree)


exchange_grads.defvjp(
    lambda tree, axis: (exchange_grads(tree, axis), None),
    lambda axis, _, cts: (_exchange_tree(cts, axis),))


# ------------------------------------------------------ bucketed reduction --

def _quantized_rs_blocks(blocks: jax.Array, axis: str,
                         group: int = WIRE_GROUP) -> jax.Array:
    """Reduce-scatter phase of the EQuARX int8 ring over pre-blocked rows.

    `blocks` is (n, P) f32 with P a multiple of `group` (scale groups never
    straddle callers' leaf boundaries); row j is this rank's contribution
    to the block OWNED by rank j. The partial sum for block j starts at
    rank j+1 and walks the +1 ring: each rank dequantizes the arriving
    int8 partial, adds its OWN f32 row (the master accumulate — every
    cross-rank addition happens in f32 on-rank), and requantizes for the
    next hop. After n-1 hops this rank holds ITS block's full f32 sum.

    Wire bytes: (n-1)/n x size x 1 byte + scales — exactly HALF the full
    `quantized_allreduce` ring (whose all-gather phase moves the same
    again). This half on its own is the ZeRO-2 int8 gradient wire: each
    dp rank needs only the grad shard it updates, so the gather half is
    simply never issued.
    """
    n = _axis_size(axis)
    idx = lax.axis_index(axis)
    chunk = blocks.shape[1]

    def block(j):
        return lax.dynamic_slice_in_dim(blocks, j, 1, axis=0)[0]

    # block j's partial starts at rank j+1, so this rank SEEDS block
    # idx-1; at step s the arriving partial is for block idx-1-s and picks
    # up this rank's contribution before the next hop
    send = block(jnp.mod(idx - 1, n))
    for s in range(1, n):
        q, sc = quantize_groups(send, group)
        q = ring_permute(q, axis, shift=1)
        sc = ring_permute(sc, axis, shift=1)
        arrived = dequantize_groups(q, sc, chunk, group)
        send = arrived + block(jnp.mod(idx - 1 - s, n))
    return send  # full f32 sum of block `idx`


def quantized_reduce_scatter(blocks: jax.Array, axis: str,
                             group: int = WIRE_GROUP) -> jax.Array:
    """Block-scaled int8 ring reduce-scatter over ONE mesh axis.

    `blocks` must be (n, P) with n = the axis size and P a multiple of
    `group`; returns this rank's (P,) f32 summed row. This is
    `quantized_allreduce` stopped after its reduce-scatter half — half
    the wire bytes, because the caller (ZeRO-2's bucketed grad reduce)
    only needs the shard it owns. Error: the circulating partial is
    requantized n-1 times -> worst-case (n-1) x (group amax)/254
    absolute, strictly tighter than the full ring's bound pinned in
    tests/test_quant.py."""
    n = _axis_size(axis)
    if blocks.ndim != 2 or blocks.shape[0] != n:
        raise ValueError(
            f"quantized_reduce_scatter needs (axis_size, P) blocks; got "
            f"shape {blocks.shape} on axis {axis!r} of size {n}")
    if blocks.shape[1] % group:
        raise ValueError(
            f"quantized_reduce_scatter needs P % group == 0 so no scale "
            f"group straddles a block boundary; got P={blocks.shape[1]}, "
            f"group={group}")
    if n == 1:
        return blocks[0]
    return _quantized_rs_blocks(blocks.astype(jnp.float32), axis, group)


def _quantized_allreduce_axis(x: jax.Array, axis: str,
                              group: int = WIRE_GROUP) -> jax.Array:
    """Block-scaled int8 ring all-reduce of a flat f32 vector over ONE
    mesh axis (the EQuARX schedule, arXiv:2506.17615).

    Reduce-scatter phase: the partial sum for block j starts at rank j+1
    and walks the +1 ring, each rank dequantizing the arriving int8
    partial, adding its OWN f32 contribution (the master accumulate —
    every addition happens in f32 on-rank), and requantizing for the next
    hop; after n-1 hops rank j holds block j's full sum in f32. All-gather
    phase: each rank quantizes its owned block ONCE and rings it around;
    every rank — the owner included — dequantizes the same codes, so the
    result is bit-identical across ranks (the optimizer step depends on
    replica-identical grads). Wire bytes: 2(n-1)/n x size x 1 byte + one
    f32 scale per `group` elements — quarter of the f32 psum ring.

    Error: block j's partial is requantized n-1 times plus once in the
    gather -> worst-case n x (group amax)/254 absolute; the bound pinned
    in tests/test_quant.py."""
    n = _axis_size(axis)
    if n == 1:
        return x
    idx = lax.axis_index(axis)
    size = x.shape[0]
    chunk = -(-size // n)
    chunk = -(-chunk // group) * group      # scale groups never straddle
    xp = jnp.pad(x.astype(jnp.float32), (0, n * chunk - size))
    blocks = xp.reshape(n, chunk)

    # -- reduce-scatter phase (shared with ZeRO-2's standalone RS wire)
    own = _quantized_rs_blocks(blocks, axis, group)

    # -- all-gather: one quantization at the owner, n-1 hops
    q, sc = quantize_groups(own, group)
    out = jnp.zeros_like(blocks)
    out = lax.dynamic_update_slice_in_dim(
        out, dequantize_groups(q, sc, chunk, group)[None], idx, axis=0)
    for s in range(1, n):
        q = ring_permute(q, axis, shift=1)
        sc = ring_permute(sc, axis, shift=1)
        origin = jnp.mod(idx - s, n)
        out = lax.dynamic_update_slice_in_dim(
            out, dequantize_groups(q, sc, chunk, group)[None], origin,
            axis=0)
    return out.reshape(-1)[:size]


def quantized_allreduce(x: jax.Array, axes,
                        group: int = WIRE_GROUP) -> jax.Array:
    """Sequential per-axis quantized all-reduces (sum over axis products
    factors); axes of size 1 are free. The int8 reduce_dtype backend of
    `bucketed_psum`."""
    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    for ax in axes:
        x = _quantized_allreduce_axis(x, ax, group)
    return x


def bucket_partition(sizes: Sequence[int], bucket_bytes: int,
                     itemsize: int = 4) -> "list[list[int]]":
    """Group leaf indices into consecutive buckets of <= bucket_bytes each
    (a single leaf larger than the bound gets its own bucket). Deterministic
    in tree order so every shard builds the identical schedule."""
    buckets, cur, cur_bytes = [], [], 0
    for i, size in enumerate(sizes):
        nbytes = size * itemsize
        if cur and cur_bytes + nbytes > bucket_bytes:
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += nbytes
    if cur:
        buckets.append(cur)
    return buckets


def bucketed_psum(tree, axes, bucket_mb: float = 25.0,
                  reduce_dtype=None):
    """psum a pytree over `axes` in size-bounded buckets.

    Value-equivalent to `jax.tree.map(lambda g: lax.psum(g, axes), tree)`
    but issues one flattened psum per <= bucket_mb bucket: each bucket's
    collective depends only on its own leaves, so it can launch as soon as
    the backward has produced them and overlap with the rest of the
    backward — instead of one whole-tree blob at the end of the step.

    `reduce_dtype` (e.g. jnp.bfloat16) compresses the WIRE only: buckets
    cast down before the psum and back to their original dtype after, so
    the optimizer's f32 master accumulate still sees f32 grads (EQuARX-
    style; adds one bf16 rounding per grad element plus the reduced-
    precision accumulation across the `axes` ranks). `jnp.int8` goes
    further: each bucket routes through `quantized_allreduce` — a
    hand-rolled reduce-scatter + all-gather ring whose hops carry int8
    codes + per-WIRE_GROUP f32 scales (quarter the f32 bytes) while every
    cross-rank addition happens in f32 on-rank (psum itself cannot
    express per-hop requantization). Error bound pinned alongside the
    bf16 one in tests/test_quant.py.
    """
    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    if not axes:
        return tree
    leaves, treedef = jax.tree.flatten(tree)
    if not leaves:
        return tree
    # buckets never mix dtypes (concatenate would silently promote); grads
    # are uniformly f32 here, but the grouping keeps the op total
    by_dtype: "dict[str, list[int]]" = {}
    for i, leaf in enumerate(leaves):
        by_dtype.setdefault(jnp.dtype(leaf.dtype).name, []).append(i)
    buckets = []
    for idxs in by_dtype.values():
        itemsize = leaves[idxs[0]].dtype.itemsize
        for group in bucket_partition([leaves[i].size for i in idxs],
                                      int(bucket_mb * 2**20), itemsize):
            buckets.append([idxs[g] for g in group])
    int8_wire = (reduce_dtype is not None
                 and jnp.dtype(reduce_dtype) == jnp.int8)

    def leaf_pad(z: jax.Array) -> int:
        # int8 buckets pad each leaf to a WIRE_GROUP multiple so no scale
        # group straddles two leaves: a tiny-magnitude leaf (norm gain)
        # concatenated after a large one would otherwise inherit the big
        # leaf's group scale and lose all its mantissa
        return (-z.size) % WIRE_GROUP if int8_wire else 0

    out = [None] * len(leaves)
    for idxs in buckets:
        flat = jnp.concatenate([
            jnp.pad(leaves[i].ravel(), (0, leaf_pad(leaves[i])))
            for i in idxs])
        if int8_wire:
            reduced = quantized_allreduce(flat, axes).astype(flat.dtype)
        elif reduce_dtype is not None:
            reduced = lax.psum(flat.astype(reduce_dtype), axes)
            reduced = reduced.astype(flat.dtype)
        else:
            reduced = lax.psum(flat, axes)
        off = 0
        for i in idxs:
            n = leaves[i].size
            out[i] = reduced[off:off + n].reshape(leaves[i].shape)
            off += n + leaf_pad(leaves[i])
    return jax.tree.unflatten(treedef, out)


def bucketed_reduce_scatter(leaves, dims, axis, other_axes=(),
                            bucket_mb: float = 25.0, reduce_dtype=None):
    """ZeRO-2's gradient wire: sum each leaf over `axis` (+`other_axes`)
    but return only THIS rank's `axis`-shard, sliced along `dims[i]`.

    Same bucket boundaries as `bucketed_psum` (partitioned on full leaf
    bytes, deterministic in list order) so swapping the all-reduce for the
    reduce-scatter changes the wire, not the schedule — half the bytes at
    identical buckets. Layout trick: each leaf moves its scatter dim to the
    front and reshapes to (n, size/n), so row r is rank r's shard
    flattened; buckets concatenate along the column axis and ONE
    `lax.psum_scatter` over the whole bucket hands every rank exactly its
    own per-leaf shards back. `reduce_dtype=jnp.bfloat16` casts the wire
    only (grads return to f32 for the optimizer's master accumulate);
    `jnp.int8` routes the bucket through `quantized_reduce_scatter` — the
    EQuARX ring stopped after its reduce-scatter half — with leaves padded
    to WIRE_GROUP multiples so no scale group straddles two leaves.

    `other_axes` (e.g. ('cp',) or the SP tp axis for tp-replicated leaves)
    are summed AFTER the scatter with a plain f32 psum of the 1/n shard —
    the payload is already scattered, so compressing the residual sum
    would spend extra roundings on 1/n of the bytes for ~nothing.

    Returns the list of local shards: leaf i's shape with `dims[i]`
    divided by the axis size (callers declare matching shard_map
    out_specs). Every `dims[i]` must be divisible by the axis size —
    callers pick dims with `training/zero`'s spec rule, which guarantees
    it.
    """
    n = _axis_size(axis)
    other_axes = tuple(other_axes)
    int8_wire = (reduce_dtype is not None
                 and jnp.dtype(reduce_dtype) == jnp.int8)
    dtypes = {jnp.dtype(g.dtype) for g in leaves}
    if len(dtypes) > 1:
        # concatenate would silently promote a mixed bucket; grads are
        # uniformly f32 here, so this is a misuse guard, not a code path
        raise ValueError(f"bucketed_reduce_scatter buckets never mix "
                         f"dtypes; got {sorted(map(str, dtypes))}")
    prep = []
    for g, d in zip(leaves, dims):
        if g.shape[d] % n:
            raise ValueError(
                f"bucketed_reduce_scatter: leaf dim {d} of shape {g.shape} "
                f"not divisible by axis {axis!r} size {n}")
        a = jnp.moveaxis(g, d, 0)
        shard_shape = (a.shape[0] // n,) + a.shape[1:]
        m = a.reshape(n, -1)
        pad = (-m.shape[1]) % WIRE_GROUP if int8_wire else 0
        if pad:
            m = jnp.pad(m, ((0, 0), (0, pad)))
        prep.append((m, shard_shape, d))
    # identical bucket boundaries to bucketed_psum: full leaf bytes
    buckets = bucket_partition([g.size for g in leaves],
                               int(bucket_mb * 2**20),
                               leaves[0].dtype.itemsize if leaves else 4)
    out = [None] * len(leaves)
    for idxs in buckets:
        flat = jnp.concatenate([prep[i][0] for i in idxs], axis=1)
        if n == 1:
            own = flat[0]
        elif int8_wire:
            own = quantized_reduce_scatter(flat, axis).astype(flat.dtype)
        elif reduce_dtype is not None:
            own = lax.psum_scatter(flat.astype(reduce_dtype), axis,
                                   scatter_dimension=0, tiled=True)
            own = own[0].astype(flat.dtype)
        else:
            own = lax.psum_scatter(flat, axis, scatter_dimension=0,
                                   tiled=True)[0]
        if other_axes:
            own = lax.psum(own, other_axes)
        off = 0
        for i in idxs:
            m, shard_shape, d = prep[i]
            per = leaves[i].size // n
            seg = own[off:off + per]
            out[i] = jnp.moveaxis(seg.reshape(shard_shape), 0, d)
            off += m.shape[1]
    return out
