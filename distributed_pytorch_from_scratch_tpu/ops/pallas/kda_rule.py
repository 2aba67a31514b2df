"""The chunked delta rule with a decay a CHANNEL (Kimi Delta Attention) as
three Pallas kernels: a chunk's operands made in VMEM from the rule's
inputs, then the walk over the chunks with the state resident
(ops/delta_rule.py has the rule, `channel_delta_rule`'s XLA text of the
same, and what it hands these; ops/pallas/delta_rule.py is the sibling for
one decay a head, whose helpers these use).

Per head and chunk of `C` tokens the kernels read `q`, `k` (C, d_k), `v` (C,
d_v) in the compute dtype, one float32 tile `Gb` of `C + ROWS` rows, d_k
wide (rows 0 .. C - 1 the chunk's running decay `G`, the inclusive sum of
`g`; row C `beta`, lane-dense in its first C lanes), and `T = (I + A)^-1`
(C x C, float32). Everything else of a chunk lives in VMEM and never in HBM.
With `r` the first row of a sub-block of `sub` rows:

    factors     R_b   = exp(G_i - G_r)         the sub-block's rows, <= 1
                E_b   = exp(G_r - G_j)         every column up to the
                                               sub-block's end (the text's
                                               factorisation, and its
                                               reliance on g >= -5)
    pairs       A     = strict_lower((beta k R_b) (k E_b)^T)
                attn  = lower_incl((q R_b) (k E_b)^T)
                        (a sub-block's rows against the columns up to its
                        end: the ten 16 x 16 tiles on or under the diagonal;
                        what lies over the diagonal is SELECTED away)
    operands    rhs   = [beta k exp(G) | beta v]             float32
                [W|U] = T rhs                  float32 (`_dot32`: six passes
                                               for W, both sides float32;
                                               three for U, as (T scaled by
                                               columns) v)
                q_in  = q exp(G)    k_out = k exp(G_end - G)
    walk        v_new = U - W S
                o     = q_in S + attn v_new
                S    <- Diag(e) S + k_out^T v_new       e = exp(G_end), a row

`kda_rule_pairs` writes `A` (from `k` and `Gb`: two operands); XLA's
`_unit_lower_inverse` makes `T` from it (exact float32; nothing
differentiates through it). `kda_rule_fwd` (`q`, `k`, `v`, `Gb`, `T`: five)
makes the rest and walks; asked for residuals it writes out the state each
block of chunks entered with. `kda_rule_bwd` (those, the states, `do` and
the final state's cotangent: eight) makes a chunk's operands again, walks
the chunks in reverse from those states, and transposes the operands by
hand:

    the walk's transpose, as the scalar rule's (ops/pallas/delta_rule.py)
    drhs   = T^T [dW | dU]      dA = -strict_lower(drhs [W | U]^T)
    the pairs' transpose, a sub-block at a time, with P = [dA; dattn]
                dL   = P (k E_b)        d(k E_b) = P^T [beta k R_b; q R_b]
                dq_pairs = dL_q R_b     d(beta k)_pairs = dL_k R_b
                dk_pairs = sum_b d(k E_b) E_b
    dq = dq_pairs + dq_in exp(G)        dv = beta drhs_U
    d(beta k) = d(beta k)_pairs + drhs_W exp(G)
    dk = dk_pairs + dk_out exp(G_end - G) + beta d(beta k)
    dbeta = rows(drhs_U * v) + rows(d(beta k) * k)
    dG = q dq_pairs + beta k d(beta k)_pairs - k dk_pairs + drhs_W rhs_W
         + dq_in q_in - dk_out k_out, ELEMENTWISE (a decay a channel), and
         at the chunk's last row columns(dk_out k_out) + e rows(dS * S)

(the reference row's own cotangent is the difference of two equal sums and
is left out). A decay's cotangent is always a sum of d(x) * x over what the
decay scaled, never a quotient of exponentials. The products' operands are
the compute dtype; the state, `G`, `U` and every sum float32. `dG` leaves
as an array of its own (XLA's running sum back to `dg` reads it as it is),
`dbeta` lane-dense as row 0 of a (`ROWS`, C) tile. `G` itself stays XLA's
cumsum: the same sum on the matrix unit (a triangle of ones times g's three
bfloat16 pieces) took 2 ms off cell 12's step and read 2.4e-4 from the text
in float32 on the chip where XLA's reads 1.7e-5 (PERF.md, PR 60).

The state is kept TRANSPOSED, (d_v, d_k): its decay `e` is then a row that
broadcasts along the sublanes (no (1, d_k) -> (d_k, 1) turn, which this
Mosaic hangs on), `W S` and `q_in S` contract the minor dimensions and the
update is `v_new^T k_out`. The callers turn the final state, its cotangent
and nothing else.

The grid is the scalar kernels': (blocks of heads, blocks of chunks), chunks
last and sequential (the backward's index maps run them from the last), a
grid step's groups of `HEADS_IN_TURN` heads a `fori_loop` whose body is
traced once, their dependent chains side by side (`_in_turn`). The three
calls are `jax.jit(inline=True)`: a step's five layers call each three times
and a signature is traced once.

Names and operand counts are part of the benchmark's yardstick
(benchmark/lib/kernels.py reads a Mosaic call named `flash_*`, or with 3 or
6 operands, as a flash call): 2, 5 and 8 here.
"""

from __future__ import annotations

import functools
from typing import List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .delta_rule import ROWS, _TN, _dot32, _in_turn, _rows
from .flash_attention import _NN, _NT, _dot, _out_struct, _vmem_limit

PAIRS_NAME = "kda_rule_pairs"
FWD_NAME = "kda_rule_fwd"
BWD_NAME = "kda_rule_bwd"
# heads and chunks a grid step, and the heads of one whose chains are traced
# side by side, in the pairs and forward kernels and in the backward
# (scripts/tune_delta_rule.py --channel, PERF.md PR 60: at 32 heads x 4096
# tokens the blocks from 4 x 2 to 16 x 2 read within 3%; the forward 1.37 /
# 0.85 / 0.58 / 0.44 ms at 1 / 2 / 4 / 8 in turn, the backward 2.57 / 1.86 /
# 1.46 / 1.39, where 8 double what Mosaic takes to compile it, 2.4 -> 4.9 s)
HEAD_BLOCK = 8
CHUNK_BLOCK = 2
HEADS_IN_TURN = 8
BWD_HEADS_IN_TURN = 4


def holds(d_k: int, d_v: int, chunk: int, sub: int) -> bool:
    """The shapes the kernels take: widths that fill whole lanes, a chunk
    of one 64-row tile in sub-blocks of 16 rows (a bfloat16 sublane tile:
    the strips the pairs are made in)."""
    return d_k % 128 == 0 and d_v % 128 == 0 and chunk == 64 and sub == 16


def blocks(h: int, n: int) -> Tuple[int, int]:
    """(heads, chunks) a grid step. A head block may hang over the last
    head (what the overhang computes is never written); a chunk block
    divides the chunks, which are one chain."""
    return min(HEAD_BLOCK, h), max(c for c in range(1, CHUNK_BLOCK + 1)
                                   if n % c == 0)


def _grid_step(h: int, n: int, backward: bool = False
               ) -> Tuple[int, int, int]:
    """`blocks` and the heads of a grid step traced side by side (the most
    that divide it): the calls' static `step`, read from the module's
    constants at every call because a jitted signature is traced once."""
    hb, cb = blocks(h, n)
    most = BWD_HEADS_IN_TURN if backward else HEADS_IN_TURN
    return hb, cb, max(t for t in range(1, min(most, hb) + 1) if hb % t == 0)


# ------------------------------------------------------ a chunk's operands

def _iotas(C: int):
    return (lax.broadcasted_iota(jnp.int32, (C, C), 0),
            lax.broadcasted_iota(jnp.int32, (C, C), 1))


def _column(row):
    """A (1, C) row as a (C, 1) column, by a select against the identity
    and a reduction: exact, and no transpose of a one-row tile."""
    i, j = _iotas(row.shape[1])
    return _rows(jnp.where(i == j, row, 0.0))


def _row(column):
    i, j = _iotas(column.shape[0])
    return jnp.sum(jnp.where(i == j, column, 0.0), axis=0, keepdims=True)


def _masks(C: int):
    """(under the diagonal, on or under it)."""
    i, j = _iotas(C)
    return i > j, i >= j


def _factors(G, sub: int):
    """G (C, d_k) -> (R (C, d_k), [E_b (C, d_k) a sub-block]): each
    sub-block's rows, and every column up to its end, about its first row,
    exponentials of differences; E_b's rows past the sub-block's end are 0
    (there the exponent has no bound: never made)."""
    C, dk = G.shape
    R, E = [], []
    for r0 in range(0, C, sub):
        end = r0 + sub
        ref = G[r0:r0 + 1]
        R.append(jnp.exp(G[r0:end] - ref))
        E.append(jnp.concatenate([jnp.exp(ref - G[:end]),
                                  jnp.zeros((C - end, dk), G.dtype)], axis=0)
                 if end < C else jnp.exp(ref - G))
    return jnp.concatenate(R, axis=0), E


def _pairs(lefts, k_cols, sub: int, dtype):
    """The pair products a sub-block at a time: `lefts` float32 (C, d_k)
    arrays whose rows [r0, r0 + sub) are the sub-block's left factors
    already, `k_cols` a (C, d_k) float32 right factor a sub-block -> a (C,
    C) float32 array a left, unmasked."""
    strips = []
    for b, right in enumerate(k_cols):
        rows = slice(b * sub, (b + 1) * sub)
        left = jnp.concatenate([x[rows] for x in lefts], axis=0)
        strips.append(_dot(left.astype(dtype), right.astype(dtype), _NT))
    return [jnp.concatenate([s[a * sub:(a + 1) * sub] for s in strips],
                            axis=0) for a in range(len(lefts))]


class _Chunk(NamedTuple):
    """What one head's chunk holds besides the state, float32."""
    beta: jax.Array     # (C, 1)
    eg: jax.Array       # (C, d_k) exp(G)
    ek: jax.Array       # (C, d_k) exp(G_end - G)
    e: jax.Array        # (1, d_k) exp(G_end)
    R: jax.Array        # (C, d_k) the sub-blocks' row factors
    E: List[jax.Array]  # (C, d_k) a sub-block: its column factors
    kb: jax.Array       # (C, d_k) beta k
    W: jax.Array
    U: jax.Array
    attn: jax.Array
    q_in: jax.Array
    k_out: jax.Array


def _operands(q, k, v, G, beta, T, sub: int) -> _Chunk:
    """q, k (C, d_k), v (C, d_v) in the compute dtype; G (C, d_k), beta (1,
    C) and T (C, C) float32."""
    f32 = jnp.float32
    C = q.shape[0]
    qf, kf = q.astype(f32), k.astype(f32)
    bc = _column(beta)
    R, E = _factors(G, sub)
    kb = kf * bc
    eg = jnp.exp(G)
    g_end = G[C - 1:C]
    ek = jnp.exp(g_end - G)
    attn, = _pairs([qf * R], [kf * e for e in E], sub, q.dtype)
    return _Chunk(
        bc, eg, ek, jnp.exp(g_end), R, E, kb,
        W=_dot32(T, kb * eg, _NN), U=_dot32(T * beta, v, _NN),
        attn=jnp.where(_masks(C)[1], attn, 0.0), q_in=qf * eg, k_out=kf * ek)


def _chunk_inputs(refs, hh, c, C: int):
    """One head's chunk from the blocks of (q, k, v, Gb, T)."""
    q_ref, k_ref, v_ref, gb_ref, t_ref = refs
    return (q_ref[hh, c], k_ref[hh, c], v_ref[hh, c], gb_ref[hh, c, 0:C, :],
            gb_ref[hh, c, C:C + 1, 0:C], t_ref[hh, c])


# ------------------------------------------------------------------ pairs

def _pairs_chunk(k, G, beta, sub: int):
    """One head's chunk of `A`; a generator, so that several chunks'
    factors and products are traced side by side (`_in_turn`)."""
    kf = k.astype(jnp.float32)
    R, E = _factors(G, sub)
    yield
    left = kf * _column(beta) * R
    yield
    A, = _pairs([left], [kf * e for e in E], sub, k.dtype)
    return jnp.where(_masks(k.shape[0])[0], A, 0.0)


def _pairs_kernel(k_ref, gb_ref, a_ref, *, sub: int, turn: int):
    hb, cb, C, _ = k_ref.shape

    def group(step, _):
        c, group = step // (hb // turn), step % (hb // turn)
        these = [group * turn + u for u in range(turn)]
        for hh, A in zip(these, _in_turn([
                _pairs_chunk(k_ref[hh, c], gb_ref[hh, c, 0:C, :],
                             gb_ref[hh, c, C:C + 1, 0:C], sub)
                for hh in these])):
            a_ref[hh, c] = A

    lax.fori_loop(0, cb * (hb // turn), group, None)


def _block(hb, cb, index=lambda i, j: (i, j)):
    return lambda *tail: pl.BlockSpec(
        (hb, cb) + tail, lambda i, j: index(i, j) + (0,) * len(tail))


def rule_pairs(k: jax.Array, Gb: jax.Array, *, sub: int,
               interpret: bool = False) -> jax.Array:
    """k (h, n, C, d_k) in the compute dtype, Gb (h, n, C + `ROWS`, d_k)
    float32 (module docstring) -> A (h, n, C, C) float32, strictly lower."""
    return _pairs_call(k, Gb, sub=sub, interpret=interpret,
                       step=_grid_step(*k.shape[:2]))


@functools.partial(jax.jit, inline=True,
                   static_argnames=("sub", "interpret", "step"))
def _pairs_call(k, Gb, *, sub, interpret, step):
    h, n, C, dk = k.shape
    hb, cb, turn = step
    block = _block(hb, cb)
    return pl.pallas_call(
        functools.partial(_pairs_kernel, sub=sub, turn=turn),
        grid=(pl.cdiv(h, hb), n // cb),
        in_specs=[block(C, dk), block(C + ROWS, dk)],
        out_specs=block(C, C),
        out_shape=_out_struct((h, n, C, C), jnp.float32, k),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=2 * h * n * C * C * dk,
            bytes_accessed=h * n * (C * dk * k.dtype.itemsize
                                    + (C + ROWS) * dk * 4 + C * 128 * 4),
            transcendentals=h * n * 4 * C * dk),
        interpret=interpret,
        name=PAIRS_NAME,
    )(k, Gb)


# ---------------------------------------------------------------- forward

def _fwd_chunk(St, q, k, v, G, beta, T, sub: int):
    """One head's chunk step: the (transposed) state it enters with and the
    chunk's inputs -> (o float32, the state it leaves). A generator, as
    `_bwd_chunk` is and for its reason."""
    dtype = q.dtype
    x = _operands(q, k, v, G, beta, T, sub)
    Sb = St.astype(dtype)
    o_state = _dot(x.q_in.astype(dtype), Sb, _NT)
    yield
    v_new = (x.U - _dot(x.W.astype(dtype), Sb, _NT)).astype(dtype)
    yield
    o = o_state + _dot(x.attn.astype(dtype), v_new, _NN)
    return o, x.e * St + _dot(v_new, x.k_out.astype(dtype), _TN)


def _fwd_kernel(q_ref, k_ref, v_ref, gb_ref, t_ref, o_ref, s_ref,
                *residual_refs, sub: int, turn: int):
    """Blocks (hb, cb, C, .); `s_ref` (hb, d_v, d_k) is the final state's
    output block, whose index ignores the chunk axis: resident, it IS the
    carried state."""
    j = pl.program_id(1)
    hb, cb, C, _ = q_ref.shape
    refs = (q_ref, k_ref, v_ref, gb_ref, t_ref)

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
        for hh, (o, S) in zip(these, _in_turn([
                _fwd_chunk(s_ref[hh], *_chunk_inputs(refs, hh, c, C), sub)
                for hh in these])):
            s_ref[hh] = S
            o_ref[hh, c] = o.astype(o_ref.dtype)

    lax.fori_loop(0, cb * (hb // turn), group, None)


def rule_forward(q: jax.Array, k: jax.Array, v: jax.Array, Gb: jax.Array,
                 T: jax.Array, *, sub: int, residuals: bool,
                 interpret: bool = False):
    """q, k (h, n, C, d_k), v (h, n, C, d_v) in the compute dtype; Gb (h, n,
    C + `ROWS`, d_k) and T (h, n, C, C) float32. Returns (o (h, n, C, d_v)
    in v's dtype, the final state TRANSPOSED (h, d_v, d_k) float32) and,
    with `residuals`, the transposed state every block of chunks (a grid
    step's, `blocks`) entered with, (h, blocks, d_v, d_k) float32."""
    return _forward_call(q, k, v, Gb, T, sub=sub, residuals=residuals,
                         interpret=interpret, step=_grid_step(*q.shape[:2]))


@functools.partial(jax.jit, inline=True, static_argnames=(
    "sub", "residuals", "interpret", "step"))
def _forward_call(q, k, v, Gb, T, *, sub, residuals, interpret, step):
    h, n, C, dk = q.shape
    dv = v.shape[-1]
    hb, cb, turn = step
    block = _block(hb, cb)
    out_specs = [block(C, dv),
                 pl.BlockSpec((hb, dv, dk), lambda i, j: (i, 0, 0))]
    out_shape = [_out_struct((h, n, C, dv), v.dtype, v),
                 _out_struct((h, dv, dk), jnp.float32, v)]
    if residuals:
        out_specs.append(pl.BlockSpec((hb, 1, dv, dk),
                                      lambda i, j: (i, j, 0, 0)))
        out_shape.append(_out_struct((h, n // cb, dv, dk), jnp.float32, v))
    item = q.dtype.itemsize
    step_bytes = hb * cb * (
        C * (2 * dk + 2 * dv) * item + ((C + ROWS) * dk + C * 128) * 4
        ) + (1 + residuals) * hb * dk * dv * 4
    return pl.pallas_call(
        functools.partial(_fwd_kernel, sub=sub, turn=turn),
        grid=(pl.cdiv(h, hb), n // cb),
        in_specs=[block(C, dk), block(C, dk), block(C, dv),
                  block(C + ROWS, dk), block(C, C)],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(2 * step_bytes)),
        cost_estimate=pl.CostEstimate(
            flops=2 * h * n * C * (C * (2 * dk + dv) + 3 * dk * dv + C * dv),
            bytes_accessed=h * n * step_bytes // (hb * cb),
            transcendentals=h * n * 6 * C * dk),
        interpret=interpret,
        name=FWD_NAME,
    )(q, k, v, Gb, T)


# --------------------------------------------------------------- backward

def _bwd_chunk(dSt, St, x: _Chunk, q, k, v, do, T, sub: int):
    """The transpose of `_fwd_chunk`: the cotangent of the state the chunk
    LEFT, the state it entered with (both transposed), its operands `x`
    and its inputs -> (dq, dk, dv, dG (C, d_k) float32, dbeta a (1, C)
    row, the cotangent of the state the chunk entered with). A generator:
    it yields where the next products wait for the last ones' results."""
    f32 = jnp.float32
    dtype = q.dtype
    C = q.shape[0]
    cast = lambda z: z.astype(dtype)
    qf, kf, vf = q.astype(f32), k.astype(f32), v.astype(f32)
    strict, lower = _masks(C)
    Sb, dSb = cast(St), cast(dSt)
    W, attn, q_in, k_out = cast(x.W), cast(x.attn), cast(x.q_in), cast(x.k_out)
    dq_in = _dot(do, Sb, _NN)
    Tt = T.T
    yield
    v_new = cast(x.U - _dot(W, Sb, _NT))
    # the walk's transpose
    dv_new = _dot(attn, do, _TN) + _dot(k_out, dSb, _NT)
    yield
    dvb = cast(dv_new)
    dattn = jnp.where(lower, _dot(do, v_new, _NT), 0.0)
    dk_out = _dot(v_new, dSb, _NN)
    dW = -_dot(dvb, Sb, _NN)
    de = jnp.sum(dSt * St, axis=0, keepdims=True)               # (1, d_k)
    dS_in = x.e * dSt + _dot(do, q_in, _TN) - _dot(dvb, W, _TN)
    # the operands' transpose
    drhs_U = _dot32(Tt, dv_new, _NN)
    yield
    drhs_W = _dot32(Tt, dW, _NN)
    yield
    dA = jnp.where(strict, -(_dot32(drhs_W, x.W, _NT)
                             + _dot32(drhs_U, x.U, _NT)), 0.0)
    yield
    # the pairs' transpose, a sub-block's strip at a time
    lefts = (x.kb * x.R, qf * x.R)
    dL_k, dL_q, dk_pairs = [], [], None
    for b, E in enumerate(x.E):
        rows = slice(b * sub, (b + 1) * sub)
        P = cast(jnp.concatenate([dA[rows], dattn[rows]], axis=0))
        dL = _dot(P, cast(kf * E), _NN)                         # (2 sub, .)
        dL_k.append(dL[:sub])
        dL_q.append(dL[sub:])
        part = _dot(P, cast(jnp.concatenate(
            [left[rows] for left in lefts], axis=0)), _TN) * E
        dk_pairs = part if dk_pairs is None else dk_pairs + part
    dkb_pairs = jnp.concatenate(dL_k, axis=0) * x.R
    dq_pairs = jnp.concatenate(dL_q, axis=0) * x.R
    dkb = drhs_W * x.eg + dkb_pairs
    dq = dq_pairs + dq_in * x.eg
    dk = dk_pairs + dk_out * x.ek + dkb * x.beta
    dv = drhs_U * x.beta
    dbeta = _rows(drhs_U * vf) + _rows(dkb * kf)                  # (C, 1)
    left = dk_out * x.k_out
    dG = (qf * dq_pairs + x.kb * dkb_pairs - kf * dk_pairs
          + drhs_W * (x.kb * x.eg) + dq_in * x.q_in - left)
    last = lax.broadcasted_iota(jnp.int32, dG.shape, 0) == C - 1
    dG = dG + jnp.where(
        last, jnp.sum(left, axis=0, keepdims=True) + x.e * de, 0.0)
    return dq, dk, dv, dG, _row(dbeta), dS_in


def _bwd_block(dSt, St, chunks, sub: int):
    """One head's block of chunks in the backward: the cotangent of the
    state the block LEFT, the state it entered with and, chunk by chunk,
    (q, k, v, G, beta, T, do) -> (a (dq, dk, dv, dG, dbeta) a chunk, the
    cotangent of the state the block entered with). The forward keeps one
    state a block: the states the later chunks entered with are walked
    again from the operands the backward makes anyway."""
    dtype = chunks[0][0].dtype
    operands = [_operands(*chunk[:6], sub) for chunk in chunks]
    states = [St]
    for x in operands[:-1]:
        yield
        Sb = states[-1].astype(dtype)
        v_new = (x.U - _dot(x.W.astype(dtype), Sb, _NT)).astype(dtype)
        yield
        states.append(x.e * states[-1]
                      + _dot(v_new, x.k_out.astype(dtype), _TN))
    out = [None] * len(chunks)
    for c in reversed(range(len(chunks))):
        q, k, v, _, _, T, do = chunks[c]
        *out[c], dSt = yield from _bwd_chunk(dSt, states[c], operands[c], q,
                                             k, v, do, T, sub)
    return out, dSt


def _bwd_kernel(q_ref, k_ref, v_ref, gb_ref, t_ref, s_ref, do_ref,
                ds_last_ref, dq_ref, dk_ref, dv_ref, dg_ref, db_ref, ds_ref,
                *, sub: int, turn: int):
    """The grid's chunk axis runs backwards (the index maps turn it).
    `ds_ref` (hb, d_v, d_k) is scratch: the state's cotangent, from the
    final state's down to the first chunk."""
    j = pl.program_id(1)
    hb, cb, C, _ = q_ref.shape
    refs = (q_ref, k_ref, v_ref, gb_ref, t_ref)

    @pl.when(j == 0)
    def _last_chunk():
        ds_ref[...] = ds_last_ref[...]

    def group(group, _):
        these = [group * turn + u for u in range(turn)]
        for hh, (out, dS) in zip(these, _in_turn([
                _bwd_block(ds_ref[hh], s_ref[hh, 0],
                           [(*_chunk_inputs(refs, hh, c, C), do_ref[hh, c])
                            for c in range(cb)], sub)
                for hh in these])):
            ds_ref[hh] = dS
            for c, (dq, dk, dv, dG, dbeta) in enumerate(out):
                dq_ref[hh, c] = dq.astype(dq_ref.dtype)
                dk_ref[hh, c] = dk.astype(dk_ref.dtype)
                dv_ref[hh, c] = dv.astype(dv_ref.dtype)
                dg_ref[hh, c] = dG
                db_ref[hh, c, 0:1, :] = dbeta

    lax.fori_loop(0, hb // turn, group, None)


def rule_backward(q: jax.Array, k: jax.Array, v: jax.Array, Gb: jax.Array,
                  T: jax.Array, S_in: jax.Array, do: jax.Array,
                  dS: jax.Array, *, sub: int, interpret: bool = False):
    """`rule_forward`'s inputs and residual, do (h, n, C, d_v) in the
    compute dtype and the final state's cotangent TRANSPOSED, dS (h, d_v,
    d_k) float32 -> (dq, dk, dv in their inputs' dtype, dG (h, n, C, d_k)
    float32 on its own (XLA's running sum reads it as it is), dbeta as row
    0 of a (h, n, `ROWS`, C) float32 tile whose other rows are not
    written)."""
    return _backward_call(q, k, v, Gb, T, S_in, do, dS, sub=sub,
                          interpret=interpret,
                          step=_grid_step(*q.shape[:2], backward=True))


@functools.partial(jax.jit, inline=True,
                   static_argnames=("sub", "interpret", "step"))
def _backward_call(q, k, v, Gb, T, S_in, do, dS, *, sub, interpret, step):
    h, n, C, dk = q.shape
    dv = v.shape[-1]
    hb, cb, turn = step
    last = n // cb - 1
    block = _block(hb, cb, lambda i, j: (i, last - j))
    state = pl.BlockSpec((hb, dv, dk), lambda i, j: (i, 0, 0))
    item = q.dtype.itemsize
    step_bytes = hb * cb * (
        C * (4 * dk + 3 * dv) * item + (2 * (C + ROWS) * dk + C * 128) * 4
        ) + 3 * hb * dk * dv * 4
    return pl.pallas_call(
        functools.partial(_bwd_kernel, sub=sub, turn=turn),
        grid=(pl.cdiv(h, hb), n // cb),
        in_specs=[block(C, dk), block(C, dk), block(C, dv),
                  block(C + ROWS, dk), block(C, C),
                  pl.BlockSpec((hb, 1, dv, dk),
                               lambda i, j: (i, last - j, 0, 0)),
                  block(C, dv), state],
        out_specs=[block(C, dk), block(C, dk), block(C, dv), block(C, dk),
                   block(ROWS, C)],
        scratch_shapes=[pltpu.VMEM((hb, dv, dk), jnp.float32)],
        out_shape=[_out_struct(q.shape, q.dtype, v),
                   _out_struct(k.shape, k.dtype, v),
                   _out_struct(v.shape, v.dtype, v),
                   _out_struct(q.shape, jnp.float32, v),
                   _out_struct((h, n, ROWS, C), jnp.float32, v)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(2 * step_bytes)),
        cost_estimate=pl.CostEstimate(
            flops=2 * h * n * C * (C * (8 * dk + 4 * dv) + 6 * dk * dv
                                   + 2 * C * dv),
            bytes_accessed=h * n * step_bytes // (hb * cb),
            transcendentals=h * n * 6 * C * dk),
        # q, k, v are the rule's last readers' here: their cotangents take
        # their buffers
        input_output_aliases={0: 0, 1: 1, 2: 2},
        interpret=interpret,
        name=BWD_NAME,
    )(q, k, v, Gb, T, S_in, do, dS)
