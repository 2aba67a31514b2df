"""The two joints of a hyper-connection mixer (parallel/hyper.py) as Pallas
kernels: every pass over the streams `(n, T, C)` is ONE kernel over blocks
of tokens that reads the streams in the compute dtype, does its arithmetic
in float32 in VMEM and writes once. No float32 copy of the streams goes
through HBM and no reduction over C is a pass of its own.

Per token, X in R^{n x C}, W (nC, width), `width` = n^2 + 2n (n of an exit
mixer):

    read   m = (x W) rsqrt(mean(x^2) + norm_eps)           (width, T) float32
           pre = sigmoid(alpha_0 m[:n] + b[:n]) + eps
           u = sum_i pre_i X[i]                            (T, C)
    write  X'[i] = sum_j H[i, j] X[j] + post_i y           (n, T, C)

Four kernels, each with its transpose written by hand:

* `mhc_read_fwd`: X once. The product with W runs on the matrix unit a
  block of tokens at a time. X is exact in bfloat16, so it is W that needs
  its mantissa: W is cut into three bfloat16 pieces (`_split`: 8 + 8 + 8
  bits, exact) that stand SIDE BY SIDE in the 128 columns one pass of the
  unit computes anyway (3 x 24 = 72 of 128), and the three partial products
  are added in float32: what precision "highest" computes of a bfloat16 X,
  at the cost of one pass. Float32 streams take the float32 product at
  "highest". `mean(x^2)`, the scale, `pre` and u are made a group of
  `ROWS` tokens at a time from the block in VMEM.
* `mhc_write_fwd`: X, y, H and post -> X'; twenty multiply-adds a token and
  column, float32, rounded once.
* `mhc_write_bwd`: X, y, H, post, dX' -> dy, dH and dpost (and, for
  streams that came through no read joint, the H^T dX' part of dX; a
  layer's mixer leaves that part to `mhc_read_bwd`: "the joints,
  differentiable" below). The twenty reductions over C are ONE product a
  group of tokens on the matrix unit: the group's rows of dX' (n `ROWS`, C)
  times those of [X; y] ((n + 1) `ROWS`, C) transposed, of which the
  entries of equal token are the sums wanted (`_equal_token_sums`); the
  operands are the streams as they lie in VMEM, exact in bfloat16,
  accumulated in float32.
* `mhc_read_bwd`: X, du, dm and the write joint's dX' and H -> dX, dW and
  dz (of which dalpha_0 and db[:n] are sums). ONE pass: `dpre_i = sum_c
  du_c X[i, c]` is a token's own (the same product of a group's rows), so
  the small float32 backward of the sigmoid and the scale is made in the
  kernel, a group of tokens at a time, before the block's dX = pre_i du +
  (dr W^T) + g X[i] + sum_j H[j, i] dX'[j], rounded once. dr W^T takes W^T
  as two pieces and dr as two (three of the four cross products, stacked
  along the 128 rows one pass contracts anyway): 16 bits of a cotangent
  that is rounded to 8. dW += dr^T x accumulates in float32 across the
  grid in a resident output, dr in three exact pieces stacked along the
  rows.

Small float32 arrays travel TOKEN-MAJOR, `(T, 128)`: a token's numbers in
the lanes of its row, so that a column is a per-token scalar that
broadcasts along C. `m` itself leaves `mhc_read_fwd` as `(rows, T)`, tokens
on the lanes, as the maps' `jax.numpy` text (the sigmoids, the clamp, the
Sinkhorn rounds) takes it.

The four calls are `jax.jit(inline=True)`: a step calls each a dozen times
(two joints a segment, forward, recompute and backward) and a kernel's body
is a few hundred lines to trace; jitted, a signature is traced once and its
program put in place at every call site under the site's own scopes (2.5 s
of a step's tracing, PR 58).

Names and operand counts: `benchmark/lib/kernels.FLASH` reads any Mosaic
call of exactly 3 or 6 operands, or named `flash_*`, as a flash kernel. The
calls here have 4 (`mhc_read_fwd`, `mhc_write_fwd`), 5 (`mhc_write_bwd`)
and 7 or 9 (`mhc_read_bwd`) operands (alpha_0 and b[:n] a float32 tile
each).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import (_NN, _NT, _out_struct, _round_up,
                              _vmem_limit)

READ_FWD = "mhc_read_fwd"
READ_BWD = "mhc_read_bwd"
WRITE_FWD = "mhc_write_fwd"
WRITE_BWD = "mhc_write_bwd"

LANES = 128
# tokens of one group: what the kernels' element-wise loops and the
# equal-token products take at a time (two bfloat16 sublane tiles a stream)
ROWS = 32
# columns of C an element-wise expression takes at a time
LANE_CHUNK = 512
# tokens of a grid step, forward and backward kernels (scripts/
# tune_stream_mixer.py's sweep, PERF.md PR 58)
FWD_BLOCK = 256
BWD_BLOCK = 128
# what a grid step may keep in VMEM, blocks double-buffered and scratch
BLOCK_VMEM_BYTES = 72 * 2 ** 20


def _pieces(dtype) -> int:
    """Pieces W is cut into for one pass of the matrix unit: three beside
    bfloat16 streams, itself beside float32 streams."""
    return 3 if dtype == jnp.bfloat16 else 1


def holds(n: int, d: int, dtype) -> bool:
    """The shapes the kernels take: C fills whole lanes, a token's maps and
    their pieces fit the 128 columns of one pass, and a backward block of
    `ROWS` tokens fits the VMEM a call asks for."""
    width = n * n + 2 * n
    item = jnp.dtype(dtype).itemsize
    return (d % LANES == 0 and dtype in (jnp.bfloat16, jnp.float32)
            and 3 * _round_up(width, 8) <= LANES
            and _read_bwd_bytes(n, d, ROWS, item, 3) <= BLOCK_VMEM_BYTES)


def token_block(tokens: int, block: int, n: int, d: int, itemsize: int,
                step_bytes) -> int:
    """Tokens a grid step takes: `block`, halved while the step's VMEM
    (`step_bytes(n, d, block, itemsize)`) is over the budget; a call of
    fewer tokens takes them all, padded to whole lanes."""
    block = min(block, _round_up(tokens, LANES))
    while block > ROWS and step_bytes(n, d, block, itemsize) \
            > BLOCK_VMEM_BYTES:
        block //= 2
    return block


def padded_tokens(tokens: int, block: int = None) -> int:
    """The tokens of a mixer's calls, padded: to whole forward blocks (a
    block halved for VMEM, and the backward's, divide them), so that what
    one call keeps token-major for another has the other's rows."""
    return _round_up(tokens, min(block or FWD_BLOCK,
                                 _round_up(tokens, LANES)))


def forward_block(n: int, d: int, tokens: int, dtype) -> int:
    """Tokens a grid step of the read's forward takes at this shape."""
    return token_block(tokens, FWD_BLOCK, n, d, jnp.dtype(dtype).itemsize,
                       _read_fwd_bytes)


def _split(a: jax.Array, pieces: int):
    """float32 `a` as `pieces` float32 arrays of 8 significant bits each,
    exact in bfloat16, that sum to `a` (to 8 `pieces` bits; three are `a`).
    By masking, not by a pair of converts: XLA drops such a pair as excess
    precision, and the pieces after the first would be zero."""
    out, rest = [], a
    for _ in range(pieces):
        top = lax.bitcast_convert_type(
            lax.bitcast_convert_type(rest, jnp.int32) & jnp.int32(-65536),
            jnp.float32)
        out.append(top)
        rest = rest - top
    return out


def _dot(a, b, dims):
    """A product on the matrix unit, float32 out: bfloat16 operands in one
    pass (exact products, float32 sums), float32 ones at "highest"."""
    precision = lax.Precision.HIGHEST if a.dtype == jnp.float32 else None
    return lax.dot_general(a, b, dims, precision=precision,
                           preferred_element_type=jnp.float32)


def _lane_chunks(C: int):
    return [(c0, min(LANE_CHUNK, C - c0)) for c0 in range(0, C, LANE_CHUNK)]


def _fold(v):
    """(rows, k 128) -> (rows, 128): the k lane tiles added."""
    out = v[:, :LANES]
    for k in range(1, v.shape[1] // LANES):
        out = out + v[:, k * LANES:(k + 1) * LANES]
    return out


def _column(v, k: int):
    """Column k of a token-major tile: (rows, 1), a scalar a token."""
    return v[:, k:k + 1]


def _in_lane(cols, lanes):
    """(rows, 1) columns -> one token-major tile (rows, 128) with
    `cols[i]` in lane `lanes[i]` and zero elsewhere."""
    rows = cols[0].shape[0]
    lane = lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
    out = jnp.zeros((rows, LANES), jnp.float32)
    for col, k in zip(cols, lanes):
        out = jnp.where(lane == k, col, out)
    return out


def _equal_token_sums(left, right):
    """left: a list of (ROWS, C) row groups, right: another; the streams as
    they lie in VMEM. Returns sums[a][b] (ROWS, 1) = sum_c left[a][t, c]
    right[b][t, c]: ONE product of the stacked groups on the matrix unit,
    of which only the entries of equal token are kept."""
    G = _dot(jnp.concatenate(left, axis=0), jnp.concatenate(right, axis=0),
             _NT)                             # (len(left) R, len(right) R)
    R = ROWS
    row = lax.broadcasted_iota(jnp.int32, (R, G.shape[1]), 0)
    col = lax.broadcasted_iota(jnp.int32, (R, G.shape[1]), 1)
    out = []
    for a in range(len(left)):
        Ga = G[a * R:(a + 1) * R]
        out.append([jnp.sum(jnp.where(col - b * R == row, Ga, 0.0), axis=1,
                            keepdims=True) for b in range(len(right))])
    return out


def _groups(bt: int, body) -> None:
    """`body(rows)` for every group of `ROWS` tokens of a block of `bt`."""
    def one(g, carry):
        body(pl.ds(pl.multiple_of(g * ROWS, ROWS), ROWS))
        return carry
    lax.fori_loop(0, bt // ROWS, one, 0)


def _guarded_call(interpret: bool, like: jax.Array) -> bool:
    """Whether a call's kernel is `_guarded`: under the interpreter where
    the operands carry shard_map's varying axes."""
    return bool(interpret and getattr(jax.typeof(like), "vma", None))


def _guarded(kernel):
    """The kernel, its body under a condition that always holds where the
    call says `guarded`: the interpreter inside shard_map types a kernel's
    ops one by one against the varying axes of the blocks it hands them,
    and a constant beside a block's value fails it; under a condition the
    body is one closed program that is not typed again (the flash kernels'
    `pl.when`s do the same unasked). Compiled for the chip a kernel is
    never typed that way and is not guarded."""
    @functools.wraps(kernel)
    def call(*refs, guarded, **static):
        step = pl.program_id(0)     # (not readable under the condition)
        if guarded:
            pl.when(step >= 0)(lambda: kernel(step, *refs, **static))
        else:
            kernel(step, *refs, **static)
    return call


# ------------------------------------------------------------ read, forward

@_guarded
def _read_fwd_kernel(step, x_ref, w_ref, a_ref, b_ref, m_ref, u_ref, tok_ref,
                     *, n, width, wp, pieces, eps, norm_eps):
    bt, C = u_ref.shape
    part = _dot(x_ref[0], w_ref[0], _NN)                    # (bt, 128)
    for i in range(1, n):
        part = part + _dot(x_ref[i], w_ref[i], _NN)
    raw = part
    for k in range(1, pieces):      # the pieces' columns, added: [:, :wp]
        raw = raw + pltpu.roll(part, LANES - k * wp, 1)
    tok_ref[...] = raw
    lane = lax.broadcasted_iota(jnp.int32, (ROWS, LANES), 1)
    alpha = a_ref[0:1, :]
    chunks = _lane_chunks(C)

    def group(rows):
        ss = jnp.zeros((ROWS, LANES), jnp.float32)
        for i in range(n):
            for c0, cl in chunks:
                xv = x_ref[i, rows, pl.ds(c0, cl)].astype(jnp.float32)
                ss = ss + _fold(xv * xv)
        rs = lax.rsqrt(jnp.sum(ss, axis=1, keepdims=True) / (n * C)
                       + norm_eps)
        m = tok_ref[rows, :] * rs
        pre = jax.nn.sigmoid(alpha * m + b_ref[0:1, :]) + eps
        # the block's m token-major, the scale in the column past it
        tok_ref[rows, :] = jnp.where(lane < width, m,
                                     jnp.where(lane == width, rs, 0.0))
        cols = [_column(pre, i) for i in range(n)]
        for c0, cl in chunks:
            at = pl.ds(c0, cl)
            acc = cols[0] * x_ref[0, rows, at].astype(jnp.float32)
            for i in range(1, n):
                acc = acc + cols[i] * x_ref[i, rows, at].astype(
                    jnp.float32)
            u_ref[rows, at] = acc.astype(u_ref.dtype)

    _groups(bt, group)
    m_ref[...] = tok_ref[...].T[:m_ref.shape[0], :]


def _read_fwd_bytes(n, d, bt, item, pieces=3):
    return (2 * (n + 1) * bt * d * item + 2 * n * d * LANES
            * (2 if pieces == 3 else 4) + 4 * bt * LANES * 4)


def _w_side_by_side(w, n: int, C: int, width: int, pieces: int):
    """W (n C, width) float32 as the forward's right side (n, C, 128): its
    pieces side by side, each `wp` columns wide."""
    wp = _round_up(width, 8)
    w = jnp.pad(w.astype(jnp.float32).reshape(n, C, width),
                ((0, 0), (0, 0), (0, wp - width)))
    if pieces == 1:
        return jnp.pad(w, ((0, 0), (0, 0), (0, LANES - wp)))
    return jnp.pad(jnp.concatenate(_split(w, pieces), axis=-1),
                   ((0, 0), (0, 0), (0, LANES - pieces * wp))
                   ).astype(jnp.bfloat16)


def _pad_tokens(a, axis: int, to: int):
    pad = to - a.shape[axis]
    if not pad:
        return a
    return jnp.pad(a, [(0, pad if k == axis else 0) for k in range(a.ndim)])


def _token_major(a, tokens: int):
    """(k, T) float32, tokens on the lanes -> (tokens, 128), a token's k
    numbers in the lanes of its row."""
    return jnp.pad(a.astype(jnp.float32).T,
                   ((0, tokens - a.shape[1]), (0, LANES - a.shape[0])))


def _tile(v):
    """A scalar or a few numbers as the first row of a float32 tile (8,
    128): the scalar in every lane, the numbers in the first lanes."""
    v = v.astype(jnp.float32)
    row = jnp.broadcast_to(v, (LANES,)) if v.ndim == 0 else jnp.pad(
        v, (0, LANES - v.shape[0]))
    return jnp.pad(row[None], ((0, 7), (0, 0)))


@functools.partial(jax.jit, inline=True, static_argnames=(
    "width", "eps", "norm_eps", "block", "interpret"))
def read_forward(X, w, alpha0, b_pre, *, width: int, eps: float,
                 norm_eps: float, block: int = None,
                 interpret: bool = False):
    """X (n, T, C) in the compute dtype, W (n C, width), alpha_0 (), b[:n]
    -> m (width, T) float32, u (T, C) in X's dtype, and what the backward
    keeps: m token-major with the scale behind it, (T padded, 128)."""
    n, T, C = X.shape
    pieces = _pieces(X.dtype)
    wp = _round_up(width, 8)
    bt = token_block(T, block or FWD_BLOCK, n, C, X.dtype.itemsize,
                     _read_fwd_bytes)
    Tp = padded_tokens(T, block)
    rows = _round_up(width + 1, 8)
    tile = pl.BlockSpec((8, LANES), lambda t: (0, 0))
    m, u, tok = pl.pallas_call(
        functools.partial(_read_fwd_kernel, n=n, width=width, wp=wp,
                          pieces=pieces, eps=eps, norm_eps=norm_eps,
                          guarded=_guarded_call(interpret, X)),
        grid=(Tp // bt,),
        in_specs=[pl.BlockSpec((n, bt, C), lambda t: (0, t, 0)),
                  pl.BlockSpec((n, C, LANES), lambda t: (0, 0, 0)),
                  tile, tile],
        out_specs=[pl.BlockSpec((rows, bt), lambda t: (0, t)),
                   pl.BlockSpec((bt, C), lambda t: (t, 0)),
                   pl.BlockSpec((bt, LANES), lambda t: (t, 0))],
        out_shape=[_out_struct((rows, Tp), jnp.float32, X),
                   _out_struct((Tp, C), X.dtype, X),
                   _out_struct((Tp, LANES), jnp.float32, X)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_vmem_limit(_read_fwd_bytes(
                n, C, bt, X.dtype.itemsize, pieces))),
        cost_estimate=pl.CostEstimate(
            flops=2 * Tp * n * C * (LANES + 2),
            bytes_accessed=(n + 1) * Tp * C * X.dtype.itemsize,
            transcendentals=Tp * LANES),
        interpret=interpret,
        name=READ_FWD,
    )(_pad_tokens(X, 1, Tp), _w_side_by_side(w, n, C, width, pieces),
      _tile(alpha0), _tile(b_pre))
    return m[:width, :T], u[:T], tok


# ----------------------------------------------------------- read, backward

@_guarded
def _read_bwd_kernel(step, x_ref, du_ref, *refs, n, width, wp, rp, pieces, eps,
                     passes):
    if passes:      # the write joint's dX' and H, token-major
        do_ref, h_ref, refs = refs[0], refs[1], refs[2:]
    (wt_ref, tok_ref, dm_ref, a_ref, b_ref, dx_ref, dw_ref, dz_ref,
     d_ref, pg_ref, xw_ref) = refs
    bt, C = du_ref.shape
    alpha = a_ref[0:1, :]
    lane = lax.broadcasted_iota(jnp.int32, (ROWS, LANES), 1)
    chunks = _lane_chunks(C)

    @pl.when(step == 0)
    def _():
        dw_ref[...] = jnp.zeros(dw_ref.shape, jnp.float32)

    def small(rows):
        """A group's float32 backward: dpre from the streams, through the
        sigmoid into dm, and dm through the scale."""
        dpre = _equal_token_sums(
            [du_ref[rows, :]], [x_ref[i, rows, :] for i in range(n)])[0]
        tok = tok_ref[rows, :]
        m = jnp.where(lane < width, tok, 0.0)
        rs = _column(tok, width)
        s = jax.nn.sigmoid(alpha * m + b_ref[0:1, :])
        dz = jnp.where(lane < n, _in_lane(dpre, range(n)) * s * (1.0 - s),
                       0.0)
        dm = dm_ref[rows, :] + alpha * dz
        g = -(rs * rs) * jnp.sum(dm * m, axis=1, keepdims=True) / (n * C)
        dz_ref[rows, :] = dz
        d_ref[rows, :] = dm * rs                        # dr, token-major
        # pre_i in the lanes below n, g in lane n
        pg_ref[rows, :] = jnp.where(lane < n, s + eps,
                                    jnp.where(lane == n, g, 0.0))

    _groups(bt, small)

    dr = d_ref[...]                                         # (bt, 128)
    # dW += dr^T x: dr's exact pieces stacked along the rows
    drt = dr.T                                              # (128, bt)
    if pieces == 1:
        left = drt[:rp]
    else:
        left = jnp.concatenate([p[:rp] for p in _split(drt, pieces)],
                               axis=0).astype(jnp.bfloat16)
    # dr W^T: [hi | hi | lo] against [hi; lo; hi] of W^T
    if pieces == 1:
        across = dr
    else:
        hi, lo = _split(dr, 2)
        across = (hi + pltpu.roll(hi, wp, 1)
                  + pltpu.roll(lo, 2 * wp, 1)).astype(jnp.bfloat16)
    for i in range(n):
        dw_ref[i] += _dot(left, x_ref[i], _NN)
        xw_ref[i] = _dot(across, wt_ref[i], _NN)            # (bt, C)

    def streams(rows):
        pg = pg_ref[rows, :]
        g = _column(pg, n)
        pre = [_column(pg, i) for i in range(n)]
        if passes:
            h = h_ref[rows, :]
            res = [[_column(h, i * n + j) for j in range(n)]
                   for i in range(n)]
        for c0, cl in chunks:
            cols = pl.ds(c0, cl)
            du = du_ref[rows, cols].astype(jnp.float32)
            if passes:
                ds = [do_ref[i, rows, cols].astype(jnp.float32)
                      for i in range(n)]
            for j in range(n):
                dx = (pre[j] * du
                      + g * x_ref[j, rows, cols].astype(jnp.float32)
                      + xw_ref[j, rows, cols])
                if passes:      # + sum_i H[i, j] dX'[i], the write's part
                    for i in range(n):
                        dx = dx + res[i][j] * ds[i]
                dx_ref[j, rows, cols] = dx.astype(dx_ref.dtype)

    _groups(bt, streams)


def _read_bwd_bytes(n, d, bt, item, pieces=3, passes=True):
    rp = _round_up(n * n + 2 * n, 16)
    return (2 * ((2 + passes) * n + 1) * bt * d * item     # x, dx, dX', du
            + n * bt * d * 4                                # dr W^T
            + 2 * n * pieces * rp * d * 4                   # dW
            + 2 * n * d * LANES * (2 if pieces == 3 else 4)
            + 10 * bt * LANES * 4)


def _wt_stacked(w, n: int, C: int, width: int, pieces: int):
    """W as the backward's right side (n, 128, C): W^T, or its two pieces
    [hi; lo; hi], each `wp` rows."""
    wp = _round_up(width, 8)
    wt = jnp.pad(w.astype(jnp.float32).reshape(n, C, width).transpose(
        0, 2, 1), ((0, 0), (0, wp - width), (0, 0)))
    if pieces == 1:
        return jnp.pad(wt, ((0, 0), (0, LANES - wp), (0, 0)))
    hi, lo = _split(wt, 2)
    return jnp.pad(jnp.concatenate([hi, lo, hi], axis=1),
                   ((0, 0), (0, LANES - 3 * wp), (0, 0))
                   ).astype(jnp.bfloat16)


@functools.partial(jax.jit, inline=True, static_argnames=(
    "width", "eps", "block", "interpret"))
def read_backward(X, w, alpha0, b_pre, tok, dm, du, through=None, *,
                  width: int, eps: float, block: int = None,
                  interpret: bool = False):
    """The transpose of `read_forward`: X (n, T, C), what the forward kept
    (`tok`), the cotangents dm (width, T) float32 and du (T, C), and
    `through`: None, or the write joint's (dX' (n, T, C), H token-major (T
    padded, 128)), of which the kernel makes the write's part of dX, sum_i
    H[i, j] dX'[i], in the same pass: one rounding of dX and no array of
    the part. Returns dX (n, T, C), dW (n C, width), dalpha_0 (), db[:n]
    (n,), float32 the last three."""
    n, T, C = X.shape
    Tp = tok.shape[0]
    passes = through is not None
    assert not passes or through[1].shape == tok.shape, (through[1].shape,
                                                        tok.shape)
    pieces = _pieces(X.dtype)
    wp, rp = _round_up(width, 8), _round_up(width, 16)
    bt = token_block(T, block or BWD_BLOCK, n, C, X.dtype.itemsize,
                     functools.partial(_read_bwd_bytes, passes=passes))
    assert Tp % bt == 0, (Tp, bt)
    stream = pl.BlockSpec((n, bt, C), lambda t: (0, t, 0))
    small = pl.BlockSpec((bt, LANES), lambda t: (t, 0))
    tile = pl.BlockSpec((8, LANES), lambda t: (0, 0))
    dx, dw, dz = pl.pallas_call(
        functools.partial(_read_bwd_kernel, n=n, width=width, wp=wp, rp=rp,
                          pieces=pieces, eps=eps, passes=passes,
                          guarded=_guarded_call(interpret, X)),
        grid=(Tp // bt,),
        in_specs=[stream, pl.BlockSpec((bt, C), lambda t: (t, 0)),
                  *([stream, small] if passes else []),
                  pl.BlockSpec((n, LANES, C), lambda t: (0, 0, 0)),
                  small, small, tile, tile],
        out_specs=[stream,
                   pl.BlockSpec((n, pieces * rp, C), lambda t: (0, 0, 0)),
                   small],
        scratch_shapes=[pltpu.VMEM((bt, LANES), jnp.float32),
                        pltpu.VMEM((bt, LANES), jnp.float32),
                        pltpu.VMEM((n, bt, C), jnp.float32)],
        out_shape=[_out_struct((n, Tp, C), X.dtype, X),
                   _out_struct((n, pieces * rp, C), jnp.float32, X),
                   _out_struct((Tp, LANES), jnp.float32, X)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_limit(_read_bwd_bytes(
                n, C, bt, X.dtype.itemsize, pieces, passes))),
        cost_estimate=pl.CostEstimate(
            flops=2 * Tp * n * C * (2 * LANES + pieces * rp + 3
                                    + passes * n),
            bytes_accessed=((2 + passes) * n + 1) * Tp * C
            * X.dtype.itemsize,
            transcendentals=Tp * LANES),
        interpret=interpret,
        name=READ_BWD,
    )(_pad_tokens(X, 1, Tp), _pad_tokens(du, 0, Tp),
      *([_pad_tokens(through[0], 1, Tp), through[1]] if passes else []),
      _wt_stacked(w, n, C, width, pieces), tok, _token_major(dm, Tp),
      _tile(alpha0), _tile(b_pre))
    dw = sum(dw[:, k * rp:k * rp + width] for k in range(pieces))
    dz = dz[:T, :n]
    return (dx[:, :T], dw.transpose(0, 2, 1).reshape(n * C, width),
            jnp.sum(dz * tok[:T, :n]), jnp.sum(dz, axis=0))


# ----------------------------------------------------------- write, forward

def _coefficients(h_ref, p_ref, rows, n):
    h, p = h_ref[rows, :], p_ref[rows, :]
    return ([[_column(h, i * n + j) for j in range(n)] for i in range(n)],
            [_column(p, i) for i in range(n)])


@_guarded
def _write_fwd_kernel(step, x_ref, y_ref, h_ref, p_ref, o_ref, *, n):
    bt, C = y_ref.shape
    chunks = _lane_chunks(C)

    def group(rows):
        res, gain = _coefficients(h_ref, p_ref, rows, n)
        for c0, cl in chunks:
            cols = pl.ds(c0, cl)
            xs = [x_ref[j, rows, cols].astype(jnp.float32)
                  for j in range(n)]
            y = y_ref[rows, cols].astype(jnp.float32)
            for i in range(n):
                acc = res[i][0] * xs[0]
                for j in range(1, n):
                    acc = acc + res[i][j] * xs[j]
                o_ref[i, rows, cols] = (acc + gain[i] * y).astype(o_ref.dtype)

    _groups(bt, group)


def _write_bytes(n, d, bt, item, bwd=False):
    return (2 * ((2 + bwd) * n + 1 + bwd) * bt * d * item
            + (4 + 2 * bwd) * bt * LANES * 4)


def coefficients(res, post, block: int = None):
    """H (n, n, T) and post (n, T) float32 as the write kernels take them,
    token-major: ((T padded, 128), (T padded, 128)), H[i, j] in lane i n +
    j; padded as the mixer's calls are (`padded_tokens`)."""
    n, T = post.shape
    Tp = padded_tokens(T, block)
    return (_token_major(res.reshape(n * n, T), Tp), _token_major(post, Tp))


@functools.partial(jax.jit, inline=True,
                   static_argnames=("block", "interpret"))
def write_forward(X, y, hc, pc, *, block: int = None,
                  interpret: bool = False):
    """X (n, T, C), y (T, C), H and post token-major (`coefficients`) ->
    X' (n, T, C) in X's dtype."""
    n, T, C = X.shape
    Tp = hc.shape[0]
    bt = token_block(T, block or FWD_BLOCK, n, C, X.dtype.itemsize,
                     _write_bytes)
    assert Tp % bt == 0, (Tp, bt)
    stream = pl.BlockSpec((n, bt, C), lambda t: (0, t, 0))
    small = pl.BlockSpec((bt, LANES), lambda t: (t, 0))
    out = pl.pallas_call(
        functools.partial(_write_fwd_kernel, n=n,
                          guarded=_guarded_call(interpret, X)),
        grid=(Tp // bt,),
        in_specs=[stream, pl.BlockSpec((bt, C), lambda t: (t, 0)), small,
                  small],
        out_specs=stream,
        out_shape=_out_struct((n, Tp, C), X.dtype, X),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_vmem_limit(_write_bytes(
                n, C, bt, X.dtype.itemsize))),
        cost_estimate=pl.CostEstimate(
            flops=2 * Tp * n * (n + 1) * C,
            bytes_accessed=(2 * n + 1) * Tp * C * X.dtype.itemsize,
            transcendentals=0),
        interpret=interpret,
        name=WRITE_FWD,
    )(_pad_tokens(X, 1, Tp), _pad_tokens(y, 0, Tp), hc, pc)
    return out[:, :T]


# ---------------------------------------------------------- write, backward

@_guarded
def _write_bwd_kernel(step, x_ref, y_ref, h_ref, p_ref, do_ref, *outs, n,
                      part):
    dx_ref = outs[0] if part else None
    dy_ref, dc_ref = outs[-2:]
    bt, C = y_ref.shape
    chunks = _lane_chunks(C)

    def group(rows):
        res, gain = _coefficients(h_ref, p_ref, rows, n)
        for c0, cl in chunks:
            cols = pl.ds(c0, cl)
            ds = [do_ref[i, rows, cols].astype(jnp.float32)
                  for i in range(n)]
            dy = gain[0] * ds[0]
            for i in range(1, n):
                dy = dy + gain[i] * ds[i]
            dy_ref[rows, cols] = dy.astype(dy_ref.dtype)
            for j in range(n if part else 0):
                acc = res[0][j] * ds[0]
                for i in range(1, n):
                    acc = acc + res[i][j] * ds[i]
                dx_ref[j, rows, cols] = acc.astype(dx_ref.dtype)
        # dH[i, j] = sum_c dX'[i] X[j], dpost_i = sum_c dX'[i] y
        sums = _equal_token_sums(
            [do_ref[i, rows, :] for i in range(n)],
            [x_ref[j, rows, :] for j in range(n)]
            + [y_ref[rows, :]])
        dc_ref[rows, :] = _in_lane(
            [sums[i][j] for i in range(n) for j in range(n)]
            + [sums[i][n] for i in range(n)], range(n * n + n))

    _groups(bt, group)


@functools.partial(jax.jit, inline=True,
                   static_argnames=("part", "block", "interpret"))
def write_backward(X, y, hc, pc, dout, *, part: bool, block: int = None,
                   interpret: bool = False):
    """The transpose of `write_forward`: -> (dX's part sum_i H[i, j]
    dX'[i] (n, T, C) where `part`, else None: the read's backward makes it
    then), dy (T, C) in X's dtype, dH (n, n, T) and dpost (n, T)
    float32."""
    n, T, C = X.shape
    Tp = hc.shape[0]
    bt = token_block(T, block or BWD_BLOCK, n, C, X.dtype.itemsize,
                     functools.partial(_write_bytes, bwd=True))
    assert Tp % bt == 0, (Tp, bt)
    stream = pl.BlockSpec((n, bt, C), lambda t: (0, t, 0))
    row = pl.BlockSpec((bt, C), lambda t: (t, 0))
    small = pl.BlockSpec((bt, LANES), lambda t: (t, 0))
    *dx, dy, dc = pl.pallas_call(
        functools.partial(_write_bwd_kernel, n=n, part=part,
                          guarded=_guarded_call(interpret, X)),
        grid=(Tp // bt,),
        in_specs=[stream, row, small, small, stream],
        out_specs=[*([stream] if part else []), row, small],
        out_shape=[*([_out_struct((n, Tp, C), X.dtype, X)] if part else []),
                   _out_struct((Tp, C), X.dtype, X),
                   _out_struct((Tp, LANES), jnp.float32, X)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_vmem_limit(_write_bytes(
                n, C, bt, X.dtype.itemsize, bwd=True))),
        cost_estimate=pl.CostEstimate(
            flops=2 * Tp * n * (n + 1) * C * (1 + ROWS),
            bytes_accessed=((2 + part) * n + 2) * Tp * C
            * X.dtype.itemsize,
            transcendentals=0),
        interpret=interpret,
        name=WRITE_BWD,
    )(_pad_tokens(X, 1, Tp), _pad_tokens(y, 0, Tp), hc, pc,
      _pad_tokens(dout, 1, Tp))
    dc = dc[:T].T
    return (dx[0][:, :T] if part else None, dy[:T],
            dc[:n * n].reshape(n, n, T), dc[n * n:n * n + n])


# ------------------------------------------- the joints, differentiable
#
# A layer's mixer reads and then writes the SAME streams, and dX is the sum
# of the two joints' cotangents. Made apart, the write's part, sum_i H[i, j]
# dX'[i], is an (n, T, C) array written, read again and added: a pass more
# and a rounding more. So the write's part is DEFERRED to the read's
# backward, which has the block of X in VMEM anyway: `read_streams` hands
# out, beside (m, u), the streams again and a slot (an array of H's
# token-major shape, its value unread), `through`, and
# `write_streams_through` takes them; its backward answers dX' where the
# streams' cotangent is asked and H where the slot's is, and the read's
# backward makes the part from the two. The pair's transpose is exact; each
# alone is not, so the two are only ever used together
# (`parallel/hyper.StreamMixer.maps` / `.post`). Streams that did not come
# through a read joint (maps built by hand) take `write_streams`, whose
# backward makes the part itself.

class Joint(NamedTuple):
    """What is static of a joint's calls."""

    width: int
    eps: float
    norm_eps: float
    interpret: bool = False


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def read_streams(joint: Joint, passes: bool, X, w, alpha0, b_pre):
    """The read joint of a mixer: X (n, b, t, C), W (n C, width), alpha_0,
    b[:n] -> (m (width, T) float32, u (b, t, C)) and, with `passes`,
    `through`: (X, a slot) for `write_streams_through` (above)."""
    return _read_fwd(joint, passes, X, w, alpha0, b_pre)[0]


def _read_fwd(joint, passes, X, w, alpha0, b_pre):
    n, b, t, C = X.shape
    m, u, tok = read_forward(
        X.reshape(n, b * t, C), w, alpha0, b_pre, width=joint.width,
        eps=joint.eps, norm_eps=joint.norm_eps, interpret=joint.interpret)
    out = (m, u.reshape(b, t, C))
    if passes:      # (the slot: any array of H's token-major shape)
        out += ((X, tok),)
    return out, (X, w, alpha0, b_pre, tok)


def _read_bwd(joint, passes, saved, cotangents):
    X, w, alpha0, b_pre, tok = saved
    dm, du = cotangents[:2]
    n, b, t, C = X.shape
    through = None
    if passes:      # what the write's backward answered: (dX', H)
        dout, hc = cotangents[2]
        through = (dout.reshape(n, b * t, C), hc)
    dx, dw, dalpha, db = read_backward(
        X.reshape(n, b * t, C), w, alpha0, b_pre, tok, dm,
        du.reshape(b * t, C), through, width=joint.width,
        eps=joint.eps, interpret=joint.interpret)
    return (dx.reshape(X.shape), dw.astype(w.dtype),
            dalpha.astype(alpha0.dtype), db.astype(b_pre.dtype))


read_streams.defvjp(_read_fwd, _read_bwd)


def _write_fwd(interpret, X, y, res, post):
    n, b, t, C = X.shape
    hc, pc = coefficients(res, post)
    out = write_forward(X.reshape(n, b * t, C), y.reshape(b * t, C), hc, pc,
                        interpret=interpret)
    return out.reshape(X.shape), (X, y, hc, pc)


def _write_bwd(interpret, part, saved, dout):
    X, y, hc, pc = saved
    n, b, t, C = X.shape
    dx, dy, dres, dpost = write_backward(
        X.reshape(n, b * t, C), y.reshape(b * t, C), hc, pc,
        dout.reshape(n, b * t, C), part=part, interpret=interpret)
    return (dx.reshape(X.shape) if part else dout, dy.reshape(y.shape),
            dres, dpost)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def write_streams(interpret: bool, X, y, res, post):
    """The write joint: X (n, b, t, C), y (b, t, C), H (n, n, T), post (n,
    T) float32 -> X' (n, b, t, C)."""
    return _write_fwd(interpret, X, y, res, post)[0]


write_streams.defvjp(
    _write_fwd, lambda interpret, saved, dout: _write_bwd(interpret, True,
                                                          saved, dout))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def write_streams_through(interpret: bool, through, y, res, post):
    """The write joint of the mixer whose read joint handed out `through`
    (above): the same X'."""
    return _through_fwd(interpret, through, y, res, post)[0]


def _through_fwd(interpret, through, y, res, post):
    return _write_fwd(interpret, through[0], y, res, post)


def _through_bwd(interpret, saved, dout):
    dout, dy, dres, dpost = _write_bwd(interpret, False, saved, dout)
    # (dX', H) where (the streams', the slot's) cotangents are asked
    return (dout, saved[2]), dy, dres, dpost


write_streams_through.defvjp(_through_fwd, _through_bwd)
