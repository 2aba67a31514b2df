"""The chunked gated delta rule as two Pallas kernels: a chunk's operands
made in VMEM from the rule's inputs, then the walk over the chunks
(ops/delta_rule.py has the rule, the XLA text of the same, and what it
hands these).

Per head and chunk of `C` tokens the kernels read `q`, `k`, `v` in the
compute dtype, the chunk's running decay `G` (the inclusive sum of `g`) and
`beta` float32, both lane-dense as rows of one (8, C) tile, and `T = (I +
A)^-1` (C x C, float32), the one piece that stays XLA's (an exact float32
inverse by halves; nothing differentiates through it). Everything else of
a chunk lives in VMEM and never in HBM:

    operands    D     = exp(G_i - G_j) where i >= j, else 0
                rhs   = [beta k exp(G) | beta v]            float32
                [W|U] = T rhs                               float32 (`_dot32`)
                attn  = (q k^T) * D      q_in = q exp(G)
                k_out = k exp(G_end - G)
    walk        v_new = U - W S
                o     = q_in S + attn v_new
                S    <- e S + k_out^T v_new                 e = exp(G_end)

    backward    the operands again, then the walk's transpose
                dv_new = attn^T do + k_out dS        (dS: of the state LEFT)
                dattn  = do v_new^T    dq_in = do S^T    dk_out = v_new dS^T
                dU     = dv_new        dW    = -dv_new S^T
                dS    <- e dS + q_in^T do - W^T dv_new
                and the operands' transpose, by hand
                drhs   = T^T [dW | dU]
                dA     = -strict_lower(drhs [W | U]^T)
                dq = (dattn * D) k + dq_in exp(G)      dv = beta drhs_U
                dk = (dattn * D)^T q + (dA * D)^T (beta k) + dk_out
                     exp(G_end - G) + beta d(beta k)
                d(beta k) = drhs_W exp(G) + (dA * D) k
                dbeta = rows(drhs_U * v) + rows(d(beta k) * k)
                dG    = rows(M) - columns(M) + rows(drhs_W * rhs_W + dq_in
                        * q_in - dk_out * k_out), M = dattn * attn + dA * A,
                        and at the chunk's last row sum(dk_out * k_out) +
                        e sum(dS * S)

A decay's cotangent is always a sum of d(x) * x over what the decay
scaled, never a quotient of exponentials, and a decay itself the
exponential of a masked difference (ops/delta_rule.py's docstring has why).
The products' operands are the compute dtype (`q`'s); the state, `G`, `U`
and every sum float32. `T rhs` and its transposes (`T^T [dW | dU]`, `drhs [W
| U]^T`) are float32 products to float32's accuracy, as the XLA text's solve
at `Precision.HIGHEST`: `_dot32` multiplies bfloat16 pieces of either side,
the six pairs of pieces that HIGHEST multiplies, with the pieces of the left
side that meet one piece of the right stacked along the rows, because at 64
rows a pass costs what loading the matrix unit's stationary side costs. `T
rhs` itself is (T scaled by columns) [k | v], the same sums with the float32
factors on one side: in a bfloat16 model k and v are exact and three passes
reach float32's accuracy.

`G` and `beta` come as rows (lane-dense: a (C, 1) column in HBM is a
128-lane tile a row) and are turned in VMEM, by a select against the
identity and a reduction: exact, and no transpose of a one-row tile (which
this Mosaic hangs on). `dG` and `dbeta` go out the same way.

Either kernel is one `pallas_call` for the heads of as many sequences as
`sequences_a_call` allows (heads are all the same to the kernels: no loop
over sequences copies a sequence's inputs, outputs and residuals in and
out): the grid is (blocks of heads, blocks of chunks), chunks last and
sequential, and the state (in the backward its cotangent) stays in VMEM in
float32 from a head block's first chunk to its last; the backward walks the
chunks from the last to the first. A grid step runs `CHUNK_BLOCK` chunks of
`HEAD_BLOCK` heads. The forward, asked for residuals, writes out
the state each BLOCK of chunks entered with (its only residual besides `T`):
the backward walks a block's later states again from the operands it makes
anyway, and `v_new` likewise, which halves what the states cost in HBM and
in both kernels' DMA. A head's chunk is a chain of products each waiting for
the last one's result, 64 rows a product: the unit is done with a product
long before its result is back, and the scheduler does not reorder a body
this long by itself. So the chunk functions are generators that yield
between dependent products and `_in_turn` traces `HEADS_IN_TURN` heads'
chains side by side; a grid step's groups of that many heads are a
`fori_loop`, whose body is traced once (every head unrolled cost 3 - 5 s of
each set-up's tracing and lowering). `e` and `G_end` are one scalar a head and chunk each:
they come in through SMEM (two scalar prefetches). The backward's `dq`,
`dk`, `dv` take the buffers of `q`, `k`, `v`, which the rule reads last.

Names and operand counts are part of the benchmark's yardstick
(benchmark/lib/kernels.py reads any Mosaic call with 3 or 6 operands, or a
name starting `flash_`, as a flash call): `gdn_rule_fwd` has 7 operands
(e, G_end, q, k, v, [G; beta], T), `gdn_rule_bwd` 10 (those, the states,
do and the final state's cotangent).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _NN, _NT, _dot, _out_struct, _vmem_limit

FWD_NAME = "gdn_rule_fwd"
BWD_NAME = "gdn_rule_bwd"
# heads and chunks a grid step (tune_delta_rule.py's sweep, PERF.md PR 36)
HEAD_BLOCK = 8
CHUNK_BLOCK = 2
# heads of a grid step whose chains are traced side by side (`_in_turn`;
# tune_delta_rule.py --turns, PERF.md PR 38: the backward 4.18 / 2.74 / 2.44
# / 2.40 ms a sequence at 1 / 2 / 4 / 8 with the groups unrolled); the
# groups are a loop, so a longer body is traced once, not `HEAD_BLOCK` times
HEADS_IN_TURN = 4
# the most scalars of one table in SMEM (e or G_end, a head and chunk): 64
# KiB, four sequences of 32 heads and 128 chunks
TABLE_SCALARS = 2 ** 14
# rows of the tile that carries G (row 0) and beta (row 1), and their
# cotangents back: one float32 sublane tile
ROWS = 8

_TN = (((0,), (0,)), ((), ()))   # a^T @ b


def holds(d_k: int, d_v: int, chunk: int) -> bool:
    """The shapes the kernels take: the widths fill whole lanes and a
    chunk's rows whole sublane tiles of either dtype."""
    return d_k % 128 == 0 and d_v % 128 == 0 and chunk % 16 == 0


def sequences_a_call(b: int, h: int, n: int) -> int:
    """How many of b sequences of h heads and n chunks one call takes (the
    kernels know heads, not sequences): the largest divisor of b whose two
    scalar tables, a float32 a head and chunk each, stay within
    `TABLE_SCALARS`."""
    fit = max(1, TABLE_SCALARS // (h * n))
    return max(d for d in range(1, min(b, fit) + 1) if b % d == 0)


def _blocks(h: int, n: int) -> Tuple[int, int]:
    """(heads, chunks) a grid step. A head block may hang over the last
    head (heads are independent: what the overhang computes is never
    written); a chunk block divides the chunks, which are one chain."""
    return min(HEAD_BLOCK, h), max(c for c in range(1, CHUNK_BLOCK + 1)
                                   if n % c == 0)


def _pieces(x):
    """x as a sum of bfloat16 arrays: itself where it is one already, and
    three (a float32's 24 bits, eight a piece) otherwise."""
    if x.dtype == jnp.bfloat16:
        return [x]
    pieces, rest = [], x.astype(jnp.float32)
    for _ in range(3):
        pieces.append(rest.astype(jnp.bfloat16))
        rest = rest - pieces[-1].astype(jnp.float32)
    return pieces


def _dot32(a, b, dims):
    """a @ b or a @ b^T (`_NN`, `_NT`) to float32's accuracy on a matrix
    unit that multiplies bfloat16: `T rhs` and its transposes, which the
    XLA text's solve multiplies at `Precision.HIGHEST`. The same sum:
    either side in bfloat16 pieces and the pairs of pieces that reach
    float32's last bits (i + j <= 2: six passes of two float32 sides, three
    where one side is bfloat16 to begin with and so exact). The pieces of
    `a` that meet one piece of `b` go through the unit stacked along a's
    rows: `b` is the side the unit holds still, and loading it is what a
    pass of 64 rows costs."""
    A, B = _pieces(a), _pieces(b)
    rows = a.shape[0]
    total = None
    for j in reversed(range(len(B))):           # the small terms first
        stack = A[:min(len(A), 3 - j)]
        out = _dot(jnp.concatenate(stack, axis=0) if len(stack) > 1
                   else stack[0], B[j], dims)
        for i in reversed(range(len(stack))):
            piece = out[i * rows:(i + 1) * rows]
            total = piece if total is None else total + piece
    return total


def _rows(x):
    return jnp.sum(x, axis=1, keepdims=True)


# ------------------------------------------------------ a chunk's operands

class _Chunk(NamedTuple):
    """What one head's chunk holds besides the state, float32: the masks
    (identity, under the diagonal), G and beta as columns, the decays, and
    the walk's operands before their casts."""
    eye: jax.Array
    strict: jax.Array
    beta: jax.Array     # (C, 1)
    D: jax.Array        # (C, C)
    eg: jax.Array       # (C, 1) exp(G)
    ek: jax.Array       # (C, 1) exp(G_end - G)
    W: jax.Array
    U: jax.Array
    attn: jax.Array
    q_in: jax.Array
    k_out: jax.Array


def _operands(q, k, v, G, beta, g_end, T) -> _Chunk:
    """q, k (C, d_k), v (C, d_v) in the compute dtype; G, beta (1, C) and T
    (C, C) float32; g_end a scalar.

    [W | U] = T rhs with rhs = [beta exp(G) k | beta v] is multiplied as (T
    scaled by columns) [k | v]: the same sums, with the float32 factors all
    on one side, so that in a bfloat16 model the other side is exact and
    the product takes three passes for float32's accuracy, not six."""
    f32 = jnp.float32
    C = q.shape[0]
    i = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    j = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    eye, lower = i == j, i >= j
    column = lambda row: _rows(jnp.where(eye, row, 0.0))
    Gc, bc = column(G), column(beta)
    # exp of a masked difference: G_i - G_j <= 0 wherever i >= j
    D = jnp.where(lower, jnp.exp(jnp.where(lower, Gc - G, 0.0)), 0.0)
    eg, ek = jnp.exp(Gc), jnp.exp(g_end - Gc)
    return _Chunk(
        eye, i > j, bc, D, eg, ek,
        W=_dot32(T * (beta * jnp.exp(G)), k, _NN), U=_dot32(T * beta, v, _NN),
        attn=_dot(q, k, _NT) * D, q_in=q.astype(f32) * eg,
        k_out=k.astype(f32) * ek)


# ---------------------------------------------------------------- forward

def _fwd_chunk(S, q, k, v, G, beta, g_end, e, T):
    """One head's chunk step: the state it enters with and the chunk's
    inputs -> (o float32, the state it leaves). A generator, as
    `_bwd_chunk` is and for its reason: `_in_turn` traces several heads'
    chains side by side."""
    dtype = q.dtype
    x = _operands(q, k, v, G, beta, g_end, T)
    Sb = S.astype(dtype)
    o_state = _dot(x.q_in.astype(dtype), Sb, _NN)
    yield
    v_new = (x.U - _dot(x.W.astype(dtype), Sb, _NN)).astype(dtype)
    yield
    o = o_state + _dot(x.attn.astype(dtype), v_new, _NN)
    return o, e * S + _dot(x.k_out.astype(dtype), v_new, _TN)


def _in_turn(chains):
    """Run generators a stretch at a time, each in its turn, to their ends:
    what they returned, in their order."""
    returned = [None] * len(chains)
    live = dict(enumerate(chains))
    while live:
        for n, chain in list(live.items()):
            try:
                next(chain)
            except StopIteration as end:
                returned[n] = end.value
                del live[n]
    return returned


def _turns(hb: int) -> int:
    """Heads traced side by side: the most that divide a grid step's."""
    return max(t for t in range(1, min(HEADS_IN_TURN, hb) + 1) if hb % t == 0)


def _fwd_kernel(decay_ref, end_ref, q_ref, k_ref, v_ref, gb_ref, t_ref,
                o_ref, s_ref, *residual_refs, heads: int):
    """Blocks (hb, cb, C, .); `s_ref` (hb, d_k, d_v) is the final state's
    output block, whose index ignores the chunk axis: resident, it IS the
    carried state. The grid step's chunks and groups of heads are a
    `fori_loop`, its body the one group's chains: traced once, where
    unrolled bodies cost seconds of every set-up."""
    i, j = pl.program_id(0), pl.program_id(1)
    hb, cb = q_ref.shape[:2]
    turn = _turns(hb)

    @pl.when(j == 0)
    def _first_chunk():
        s_ref[...] = jnp.zeros_like(s_ref)

    def group(step, _):
        c, group = step // (hb // turn), step % (hb // turn)
        these = [group * turn + u for u in range(turn)]
        if residual_refs:
            @pl.when(c == 0)
            def _entering():
                for hh in these:
                    residual_refs[0][hh, 0] = s_ref[hh]
        at = lambda hh: (jnp.minimum(i * hb + hh, heads - 1), j * cb + c)
        for hh, (o, S) in zip(these, _in_turn([
                _fwd_chunk(
                    s_ref[hh], q_ref[hh, c], k_ref[hh, c], v_ref[hh, c],
                    gb_ref[hh, c, 0:1, :], gb_ref[hh, c, 1:2, :],
                    end_ref[at(hh)], decay_ref[at(hh)], t_ref[hh, c])
                for hh in these])):
            s_ref[hh] = S
            o_ref[hh, c] = o.astype(o_ref.dtype)

    lax.fori_loop(0, cb * (hb // turn), group, None)


def rule_forward(q: jax.Array, k: jax.Array, v: jax.Array, gb: jax.Array,
                 T: jax.Array, *, residuals: bool, interpret: bool = False):
    """q, k (h, n, C, d_k), v (h, n, C, d_v) in the compute dtype; gb (h, n,
    `ROWS`, C) float32, row 0 the chunk's running decay G and row 1 beta; T
    (h, n, C, C) float32. Returns (o (h, n, C, d_v) in v's dtype, the final
    state (h, d_k, d_v) float32) and, with `residuals`, the state every
    block of chunks (a grid step's, `_blocks`) entered with, (h, blocks,
    d_k, d_v) float32."""
    h, n, C, dk = q.shape
    dv = v.shape[-1]
    hb, cb = _blocks(h, n)
    G_end = gb[:, :, 0, -1]
    block = lambda *tail: pl.BlockSpec(
        (hb, cb) + tail, lambda i, j, *_: (i, j) + (0,) * len(tail))
    out_specs = [block(C, dv),
                 pl.BlockSpec((hb, dk, dv), lambda i, j, *_: (i, 0, 0))]
    out_shape = [_out_struct((h, n, C, dv), v.dtype, v),
                 _out_struct((h, dk, dv), jnp.float32, v)]
    if residuals:
        out_specs.append(pl.BlockSpec(
            (hb, 1, dk, dv), lambda i, j, *_: (i, j, 0, 0)))
        out_shape.append(_out_struct((h, n // cb, dk, dv), jnp.float32, v))
    item = q.dtype.itemsize
    step_bytes = hb * cb * (
        C * (2 * dk + 2 * dv) * item + (ROWS + C) * max(C, 128) * 4
        ) + (1 + residuals) * hb * dk * dv * 4
    return pl.pallas_call(
        functools.partial(_fwd_kernel, heads=h),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(pl.cdiv(h, hb), n // cb),
            in_specs=[block(C, dk), block(C, dk), block(C, dv),
                      block(ROWS, C), block(C, C)],
            out_specs=out_specs),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(2 * step_bytes)),
        cost_estimate=pl.CostEstimate(
            flops=2 * h * n * C * (C * (2 * dk + dv) + 3 * dk * dv + C * dv),
            bytes_accessed=h * n * step_bytes // (hb * cb),
            transcendentals=h * n * C * (C + 2)),
        interpret=interpret,
        name=FWD_NAME,
    )(jnp.exp(G_end), G_end, q, k, v, gb, T)


# --------------------------------------------------------------- backward

def _bwd_chunk(dS, S, x, q, k, v, do, G, e, T):
    """The transpose of `_fwd_chunk`: the cotangent of the state the chunk
    LEFT, the state it entered with, its operands `x` and its inputs ->
    (dq, dk, dv float32, dG and dbeta as (1, C) rows, the cotangent of the
    state the chunk entered with).

    A generator: it yields where the next products wait for the last ones'
    results (the chain is nine products long, and a product of 64 rows is
    over long before its result is back), so that `_in_turn` can trace
    several heads' chains side by side."""
    f32 = jnp.float32
    dtype = q.dtype
    cast = lambda z: z.astype(dtype)
    qf, kf, vf = q.astype(f32), k.astype(f32), v.astype(f32)
    kb_f = kf * x.beta
    Sb, dSb, kb = cast(S), cast(dS), cast(kb_f)
    W, attn, q_in, k_out = cast(x.W), cast(x.attn), cast(x.q_in), cast(x.k_out)
    A = jnp.where(x.strict, _dot(kb, k, _NT) * x.D, 0.0)
    dq_in = _dot(do, Sb, _NT)
    Tt = T.T
    yield
    v_new = cast(x.U - _dot(W, Sb, _NN))
    # the walk's transpose
    dv_new = _dot(attn, do, _TN) + _dot(k_out, dSb, _NN)
    yield
    dvb = cast(dv_new)
    dattn = _dot(do, v_new, _NT)
    dk_out = _dot(v_new, dSb, _NT)
    dW = -_dot(dvb, Sb, _NT)
    de = jnp.sum(_rows(dS * S), axis=0, keepdims=True)           # (1, 1)
    dS_in = e * dS + _dot(q_in, do, _TN) - _dot(W, dvb, _TN)
    # the operands' transpose
    drhs_U = _dot32(Tt, dv_new, _NN)
    yield
    drhs_W = _dot32(Tt, dW, _NN)
    d_qk = cast(dattn * x.D)
    dq = _dot(d_qk, k, _NN) + dq_in * x.eg
    dk_qk = _dot(d_qk, q, _TN)
    yield
    dA = -(_dot32(drhs_W, x.W, _NT) + _dot32(drhs_U, x.U, _NT))
    yield
    M = dattn * x.attn + dA * A
    d_kk = cast(jnp.where(x.strict, dA * x.D, 0.0))
    dkb = drhs_W * x.eg + _dot(d_kk, k, _NN)
    dk = dk_qk + _dot(d_kk, kb, _TN) + dk_out * x.ek + dkb * x.beta
    dv = drhs_U * x.beta
    dbeta = _rows(drhs_U * vf) + _rows(dkb * kf)                  # (C, 1)
    left = _rows(dk_out * x.k_out)          # d(G_end - G), a row at a time
    dG = _rows(drhs_W * (kb_f * x.eg) + dq_in * x.q_in) - left + _rows(M)
    row = lambda column: jnp.sum(jnp.where(x.eye, column, 0.0), axis=0,
                                 keepdims=True)
    last = lax.broadcasted_iota(jnp.int32, G.shape, 1) == G.shape[1] - 1
    dG = (row(dG) - jnp.sum(M, axis=0, keepdims=True)
          + jnp.where(last, jnp.sum(left, axis=0, keepdims=True) + e * de,
                      0.0))
    return dq, dk, dv, dG, row(dbeta), dS_in


def _bwd_block(dS, S, chunks):
    """One head's block of chunks in the backward: the cotangent of the
    state the block LEFT, the state it entered with and, chunk by chunk,
    (q, k, v, do, G, beta, g_end, e, T) -> (a (dq, dk, dv, dG, dbeta) a
    chunk, the cotangent of the state the block entered with). The forward
    keeps one state a block: the states the later chunks entered with are
    walked again from the operands the backward makes anyway. A generator,
    like the chunk's."""
    dtype = chunks[0][0].dtype
    operands, states = [], [S]
    for q, k, v, do, G, beta, g_end, e, T in chunks:
        operands.append(_operands(q, k, v, G, beta, g_end, T))
    for (*_, e, _), x in zip(chunks[:-1], operands):
        yield
        Sb = states[-1].astype(dtype)
        v_new = (x.U - _dot(x.W.astype(dtype), Sb, _NN)).astype(dtype)
        yield
        states.append(e * states[-1]
                      + _dot(x.k_out.astype(dtype), v_new, _TN))
    out = [None] * len(chunks)
    for c in reversed(range(len(chunks))):
        q, k, v, do, G, _, _, e, T = chunks[c]
        *out[c], dS = yield from _bwd_chunk(dS, states[c], operands[c], q, k,
                                            v, do, G, e, T)
    return out, dS


def _bwd_kernel(decay_ref, end_ref, q_ref, k_ref, v_ref, gb_ref, t_ref,
                s_ref, do_ref, ds_last_ref, dq_ref, dk_ref, dv_ref, dgb_ref,
                ds_ref, *, heads: int):
    """The grid's chunk axis runs backwards (the index maps turn it): step
    j holds chunk block `last - j`. `ds_ref` (hb, d_k, d_v) is scratch: the
    state's cotangent, from the final state's down to the first chunk. The
    grid step's groups of heads are a `fori_loop`, as the forward's."""
    i, j = pl.program_id(0), pl.program_id(1)
    hb, cb = q_ref.shape[:2]
    turn = _turns(hb)
    first = (pl.num_programs(1) - 1 - j) * cb

    @pl.when(j == 0)
    def _last_chunk():
        ds_ref[...] = ds_last_ref[...]

    def group(group, _):
        at = lambda hh, c: (jnp.minimum(i * hb + hh, heads - 1), first + c)
        chunk = lambda hh, c: (
            q_ref[hh, c], k_ref[hh, c], v_ref[hh, c], do_ref[hh, c],
            gb_ref[hh, c, 0:1, :], gb_ref[hh, c, 1:2, :], end_ref[at(hh, c)],
            decay_ref[at(hh, c)], t_ref[hh, c])
        these = [group * turn + u for u in range(turn)]
        for hh, (out, dS) in zip(these, _in_turn([
                _bwd_block(ds_ref[hh], s_ref[hh, 0],
                           [chunk(hh, c) for c in range(cb)])
                for hh in these])):
            ds_ref[hh] = dS
            for c, (dq, dk, dv, dG, dbeta) in enumerate(out):
                dq_ref[hh, c] = dq.astype(dq_ref.dtype)
                dk_ref[hh, c] = dk.astype(dk_ref.dtype)
                dv_ref[hh, c] = dv.astype(dv_ref.dtype)
                dgb_ref[hh, c, 0:1, :] = dG
                dgb_ref[hh, c, 1:2, :] = dbeta

    lax.fori_loop(0, hb // turn, group, None)


def rule_backward(q: jax.Array, k: jax.Array, v: jax.Array, gb: jax.Array,
                  T: jax.Array, S_in: jax.Array, do: jax.Array,
                  dS: jax.Array, *, interpret: bool = False):
    """`rule_forward`'s inputs and residual, do (h, n, C, d_v) in the
    compute dtype and the final state's cotangent dS (h, d_k, d_v) float32
    -> (dq, dk, dv in their inputs' dtype, dgb (h, n, `ROWS`, C) float32 of
    which row 0 is dG and row 1 dbeta; the other rows are not written)."""
    h, n, C, dk = q.shape
    dv = v.shape[-1]
    hb, cb = _blocks(h, n)
    last = n // cb - 1
    G_end = gb[:, :, 0, -1]
    block = lambda *tail: pl.BlockSpec(
        (hb, cb) + tail, lambda i, j, *_: (i, last - j) + (0,) * len(tail))
    state = pl.BlockSpec((hb, dk, dv), lambda i, j, *_: (i, 0, 0))
    item = q.dtype.itemsize
    step_bytes = hb * cb * (
        C * (4 * dk + 3 * dv) * item + (2 * ROWS + C) * max(C, 128) * 4
        ) + 3 * hb * dk * dv * 4
    return pl.pallas_call(
        functools.partial(_bwd_kernel, heads=h),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(pl.cdiv(h, hb), n // cb),
            in_specs=[block(C, dk), block(C, dk), block(C, dv),
                      block(ROWS, C), block(C, C),
                      pl.BlockSpec((hb, 1, dk, dv),
                                   lambda i, j, *_: (i, last - j, 0, 0)),
                      block(C, dv), state],
            out_specs=[block(C, dk), block(C, dk), block(C, dv),
                       block(ROWS, C)],
            scratch_shapes=[pltpu.VMEM((hb, dk, dv), jnp.float32)]),
        out_shape=[_out_struct(q.shape, q.dtype, v),
                   _out_struct(k.shape, k.dtype, v),
                   _out_struct(v.shape, v.dtype, v),
                   _out_struct(gb.shape, jnp.float32, v)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(2 * step_bytes)),
        cost_estimate=pl.CostEstimate(
            flops=2 * h * n * C * (C * (6 * dk + 4 * dv) + 6 * dk * dv
                                   + 2 * C * dv),
            bytes_accessed=h * n * step_bytes // (hb * cb),
            transcendentals=h * n * C * (C + 2)),
        # q, k, v are the rule's last readers' here: their cotangents take
        # their buffers (0.4 GB of a call's 1.8 at 2 x 8192 tokens)
        input_output_aliases={2: 0, 3: 1, 4: 2},
        interpret=interpret,
        name=BWD_NAME,
    )(jnp.exp(G_end), G_end, q, k, v, gb, T, S_in, do, dS)
