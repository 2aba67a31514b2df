"""The Mamba-2 state-space recurrence in its chunked form as two Pallas
kernels: a chunk's decays and its masked `C B^T` made in VMEM, the heads'
states resident there while the chunks are walked (ops/ssd.py has the
recurrence, the XLA text of the same and what it hands these).

The kernels read and write the mixer's own layout: x, y and dy as `(b, t, H
P)`, B and C as `(b, t, G N)`. A grid step is one chunk of `Q` tokens of one
block of `hb` heads of ONE group (`head_block`: 8, or the group's heads if
fewer), `W = hb P` lanes wide; the grid is (sequences, chunks, head blocks),
every axis sequential, the head blocks innermost: a group's B and C are
fetched once a chunk, its scores `s = C B^T` (Q, Q) are made by the group's
first block and kept in scratch, and EVERY head's state stays in VMEM for
the whole call (`(H / hb, N, W)` float32, 2 MB at 64 heads), kept
TRANSPOSED, `(N, W)`: a head's decay is then a row over its lanes and the
three products that touch the state take all `hb` heads at once.

A head is `P` = 64 lanes, half a lane tile, so two heads share one: the
per-head products `M_h xd_h` run a PAIR at a time on `(Q, 128)` slices with
the other head's lanes selected to 0 (a 64-wide product costs the matrix
unit what a 128-wide one does), and nothing is sliced inside a tile.

XLA hands the kernels `dt` and the running sum `cum` BOTH ways, a column a
head (`cols` (b, t, 2 H padded to whole lane tiles): `[dt | cum]` as they
lie, every head's, one block a chunk) and `cum` a row a head (`rows` (b, H
/ hb, c, hb, Q)), 1 - 2 MB a layer each: a `(1, n) -> (n, 1)` turn hangs
this Mosaic, and `cum` stays XLA's `cumsum` (the same sum on the matrix
unit read 14 times less exact, PERF.md PR 60). A grid step takes its heads'
columns out of the block by two dynamic turns of the lanes (`_own_columns`;
a 0 / 1 product on the matrix unit was as exact and cost the forward a
quarter more: everything a step does waits for it) and spreads each over
its head's lanes in VMEM (`_wide`). Per step, float32 unless cast:

    xd  = x dt                                    (dtype)
    L_h = exp(where(i >= j, cum_i - cum_j, -inf)) the mask on the EXPONENT
    M_h = (s L_h)                                 (cast once to dtype)
    y   = M_h xd_h + exp(cum) (C S)               S the state ENTERED with
    S  <- exp(cum_last) S + B^T (xd exp(cum_last - cum))

`ssd_fwd` (x, B, C, cols, rows: five operands) writes y and, asked for
residuals, the state each chunk entered with, `(b, c, N, H P)` float32.
`ssd_bwd` (those, the states and dy: seven) walks the chunks in reverse
with the states' cotangent resident, makes each chunk's decays again and
transposes by hand:

    dcar = dy exp(cum)      dC += dcar S^T        dS_in = e dS + C^T dcar
    dxw  = B dS             dB += xw dS^T         (xw = xd exp(last - cum))
    dM_h = dy_h xd_h^T      ds += dM_h L_h        dxd1_h = M_h^T dy_h
    dC  += ds B             dB += ds^T C          (the group's last block)
    dxd  = dxd1 + dxw exp(last - cum)             dx = dxd dt
    ddt  = heads(dxd x)     the direct part; `cum`'s comes back through XLA
    dcum = heads(dy y - xd dxd1 - dxw xw)
    dlast = columns(dxw xw) + e columns(dS S)     a row over W lanes

A decay's cotangent is a sum of `d(z) z` over what the decay scaled, never a
quotient of exponentials: what row i of `M` receives is `dy_i . (M xd)_i`
and what column j gives is `xd_j . (M^T dy)_j`, so no `(Q, Q)` array is
reduced. `heads` sums each head's 64 lanes on the matrix unit (`_dot32`
against a 0 / 1 matrix: float32's accuracy). dB and dC are float32 blocks
that stay in VMEM over a group's head blocks and are written once a chunk,
and so is `[ddt | dcum]`, laid out as `cols` (the head sums land in their
heads' lanes); it and `dlast` (b, c, 1, H P) leave as they are and XLA sums
`dlast` over a head's lanes, adds it at each chunk's last row, runs the
reversed running sum and multiplies by `A` and `dt`.

Names and operand counts are part of the benchmark's yardstick
(benchmark/lib/kernels.py reads a Mosaic call named `flash_*`, or with 3 or
6 operands, as a flash call): 5 and 7 here.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .delta_rule import _TN, _dot32
from .flash_attention import _NN, _NT, _dot, _out_struct, _vmem_limit

FWD_NAME = "ssd_fwd"
BWD_NAME = "ssd_bwd"
HEAD_DIM = 64       # a head's lanes: two heads a lane tile
LANES = 128
# the most heads a grid step (scripts/tune_ssd.py, PERF.md PR 69)
HEAD_BLOCK = 8


def holds(head_dim: int, state: int, chunk: int, heads_a_group: int) -> bool:
    """The shapes the kernels take: heads of 64 lanes in pairs of one
    group, a state and a chunk of whole lane tiles."""
    return (head_dim == HEAD_DIM and state % LANES == 0
            and chunk % LANES == 0 and heads_a_group % 2 == 0)


def head_block(heads_a_group: int) -> int:
    """Heads a grid step: the most whole pairs, up to `HEAD_BLOCK`, that
    divide a group (a block reads ONE group's B and C)."""
    return max(h for h in range(2, min(HEAD_BLOCK, heads_a_group) + 1, 2)
               if heads_a_group % h == 0)


# ------------------------------------------------------------ a step's parts

def _wide(cols, first: int, hb: int):
    """Columns [first, first + hb) of `cols` (Q, .), a head each, every one
    over its head's 64 lanes: (Q, hb 64)."""
    low = lax.broadcasted_iota(jnp.int32, (cols.shape[0], LANES),
                               1) < HEAD_DIM
    return jnp.concatenate([
        jnp.where(low, cols[:, h:h + 1], cols[:, h + 1:h + 2])
        for h in range(first, first + hb, 2)], axis=1)


def columns_width(heads: int) -> int:
    """The lanes of `cols`: `[dt | cum]` of every head, in whole tiles."""
    return -(-2 * heads // LANES) * LANES


def _lane_of(i, hb: int, heads: int):
    """The lane of `cols` that holds the i-th of a grid step's 2 hb columns:
    its heads' dt, then their cum."""
    return pl.program_id(2) * hb + jnp.where(i < hb, i, heads + i - hb)


def _own_columns(cols, hb: int, heads: int):
    """`cols` (Q, width): dt in lanes [0, H), cum in [H, 2 H) -> (Q, 128)
    with the grid step's `hb` heads' dt in lanes [0, hb) and cum in [hb, 2
    hb): two turns of the lanes by what the grid step says (exact, and off
    the matrix unit: everything a step does waits for these)."""
    width = cols.shape[1]
    first = pl.program_id(2) * hb
    dt = pltpu.roll(cols, (width - first) % width, 1)[:, :LANES]
    cum = pltpu.roll(cols, (2 * width + hb - heads - first) % width,
                     1)[:, :LANES]
    lane = lax.broadcasted_iota(jnp.int32, dt.shape, 1)
    return jnp.where(lane < hb, dt, cum)


def _head_sums(zs, hb: int, heads: int, width: int):
    """`zs` two float32 (Q, hb 64) arrays -> (Q, width) laid out as `cols`:
    the first array's sums over each head's lanes in the lanes of the grid
    step's heads' dt, the second's in those of their cum, 0 elsewhere: one
    product against a 0 / 1 matrix, at float32's accuracy (`_dot32`)."""
    z = jnp.concatenate(zs, axis=1)
    i = lax.broadcasted_iota(jnp.int32, (z.shape[1], width), 0) // HEAD_DIM
    lane = lax.broadcasted_iota(jnp.int32, (z.shape[1], width), 1)
    put = jnp.where(lane == _lane_of(i, hb, heads), 1.0, 0.0)
    return _dot32(z, put.astype(jnp.bfloat16), _NN)


def _caster(dtype, interpret: bool):
    """The cast to the compute dtype. Under the interpreter the CPU's
    compiler takes a rounding back out where the rounded value is read in
    float32 again (excess precision), and the backward's sums cancel only
    between the SAME rounded values: a barrier keeps the rounding, as
    Mosaic does."""
    if interpret:
        return lambda a: lax.optimization_barrier(a.astype(dtype))
    return lambda a: a.astype(dtype)


class _Step:
    """What forward and backward both make of a grid step's blocks: x (Q,
    W) in the compute dtype, cols (Q, width) and rows (hb, Q) float32."""

    def __init__(self, x, cols, rows, hb: int, heads: int, cast):
        Q = x.shape[0]
        cols = _own_columns(cols, hb, heads)
        self.hb, self.cols, self.rows = hb, cols, rows
        self.dt = cast(_wide(cols, 0, hb))
        self.cum = _wide(cols, hb, hb)
        last = self.cum[Q - 1:Q]
        self.e_last = jnp.exp(last)                             # (1, W)
        self.to_end = cast(jnp.exp(last - self.cum))
        self.xd = cast(x * self.dt)
        i = lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
        self.seen = i >= lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
        low = lax.broadcasted_iota(jnp.int32, (Q, LANES), 1) < HEAD_DIM
        self.halves = (low, ~low)

    def decay(self, h: int):
        """Head h's decays (Q, Q): the mask on the exponent."""
        gap = (self.cols[:, self.hb + h:self.hb + h + 1]
               - self.rows[h:h + 1, :])
        return jnp.exp(jnp.where(self.seen, gap, -jnp.inf))

    def pairs(self):
        """(the pair's lanes, [(head, the lanes of the tile that are its)])
        a pair of heads."""
        for p in range(self.hb // 2):
            yield slice(p * LANES, (p + 1) * LANES), [
                (2 * p + u, mine) for u, mine in enumerate(self.halves)]


def _first_of_group(per_group: int):
    return pl.program_id(2) % per_group == 0


# ---------------------------------------------------------------- forward

def _fwd_kernel(x_ref, b_ref, c_ref, cols_ref, rows_ref, y_ref, *rest,
                hb: int, heads: int, per_group: int, interpret: bool):
    """Blocks x, y (Q, W), B, C (Q, N), cols (Q, width), rows (hb, Q) and,
    asked for, the entering state (N, W); scratch the states (H / hb, N, W)
    and the group's scores (Q, Q)."""
    *residual_refs, st_ref, s_ref = rest
    hi = pl.program_id(2)
    cast = _caster(x_ref.dtype, interpret)

    @pl.when(pl.program_id(1) == 0)
    def _first_chunk():
        st_ref[hi] = jnp.zeros(st_ref.shape[1:], jnp.float32)

    @pl.when(_first_of_group(per_group))
    def _scores():
        s_ref[...] = _dot(c_ref[...], b_ref[...], _NT)

    S = st_ref[hi]
    if residual_refs:
        residual_refs[0][...] = S
    k = _Step(x_ref[...], cols_ref[...], rows_ref[...], hb, heads, cast)
    carried = _dot(c_ref[...], cast(S), _NN) * jnp.exp(k.cum)
    s = s_ref[...]
    for lanes, pair in k.pairs():
        xp = k.xd[:, lanes]
        inside = carried[:, lanes]
        for h, mine in pair:
            M = cast(s * k.decay(h))
            inside = inside + _dot(M, jnp.where(mine, xp, 0), _NN)
        y_ref[:, lanes] = inside.astype(y_ref.dtype)
    st_ref[hi] = k.e_last * S + _dot(b_ref[...], cast(k.xd * k.to_end), _TN)


def _specs(Q: int, W: int, N: int, hb: int, width: int, per_group: int,
           chunk_of):
    """The block specs of (x-like, B-like, cols-like, rows, state a chunk),
    `chunk_of(j)` the chunk a grid step takes."""
    return (
        pl.BlockSpec((None, Q, W), lambda b, j, h: (b, chunk_of(j), h)),
        pl.BlockSpec((None, Q, N),
                     lambda b, j, h: (b, chunk_of(j), h // per_group)),
        pl.BlockSpec((None, Q, width), lambda b, j, h: (b, chunk_of(j), 0)),
        pl.BlockSpec((None, None, None, hb, Q),
                     lambda b, j, h: (b, h, chunk_of(j), 0, 0)),
        pl.BlockSpec((None, None, N, W),
                     lambda b, j, h: (b, chunk_of(j), 0, h)))


def forward(x: jax.Array, B: jax.Array, C: jax.Array, cols: jax.Array,
            rows: jax.Array, *, heads_a_group: int, residuals: bool,
            interpret: bool = False):
    """x (b, t, H 64) and B, C (b, t, G N) in the compute dtype, t whole
    chunks; cols (b, t, `columns_width(H)`) and rows (b, H / hb, c, hb, Q)
    float32 (module docstring), hb `head_block(heads_a_group)`. Returns [y
    (b, t, H 64) in x's dtype] and, with `residuals`, the transposed state
    every chunk entered with, (b, c, N, H 64) float32."""
    return _forward_call(x, B, C, cols, rows, per_group=heads_a_group
                         // rows.shape[3], residuals=residuals,
                         interpret=interpret)


@functools.partial(jax.jit, inline=True, static_argnames=(
    "per_group", "residuals", "interpret"))
def _forward_call(x, B, C, cols, rows, *, per_group, residuals, interpret):
    b, t, HP = x.shape
    nhb, c, hb, Q = rows.shape[1:]
    W = hb * HEAD_DIM
    N = B.shape[2] // (nhb // per_group)        # B holds the groups' states
    wide, group, col, row, state = _specs(Q, W, N, hb, cols.shape[2],
                                          per_group, lambda j: j)
    out_specs, out_shape = [wide], [_out_struct(x.shape, x.dtype, x)]
    if residuals:
        out_specs.append(state)
        out_shape.append(_out_struct((b, c, N, HP), jnp.float32, x))
    item = x.dtype.itemsize
    step_bytes = (Q * (2 * W + 2 * N) * item
                  + (Q * cols.shape[2] + hb * Q) * 4 + residuals * N * W * 4)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, hb=hb, heads=nhb * hb,
                          per_group=per_group, interpret=interpret),
        grid=(b, c, nhb),
        in_specs=[wide, group, group, col, row],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((nhb, N, W), jnp.float32),
                        pltpu.VMEM((Q, Q), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=_vmem_limit(
                2 * step_bytes + (nhb * N * W + Q * Q) * 4)),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * c * nhb * Q * (Q * N // per_group + Q * W
                                         + 2 * N * W),
            bytes_accessed=b * c * nhb * step_bytes,
            transcendentals=b * c * nhb * Q * (hb * Q + 2 * W)),
        interpret=interpret,
        name=FWD_NAME,
    )(x, B, C, cols, rows)


# --------------------------------------------------------------- backward

def _bwd_kernel(x_ref, b_ref, c_ref, cols_ref, rows_ref, sin_ref, dy_ref,
                dx_ref, db_ref, dc_ref, dcols_ref, dlast_ref,
                dst_ref, s_ref, ds_ref, *, hb: int, heads: int,
                per_group: int, interpret: bool):
    """`_fwd_kernel`'s blocks, the state the chunk entered with and dy (Q,
    W); out dx (Q, W), dB and dC (Q, N) float32 (resident over a group's
    head blocks), [ddt | dcum] (Q, width) laid out as cols (resident over
    a chunk's head blocks) and dlast (1, W); scratch the
    states' cotangents (H / hb, N, W), the group's scores and their
    cotangent (Q, Q). The grid's chunk axis runs backwards (the index maps
    turn it)."""
    hi = pl.program_id(2)
    f32 = jnp.float32
    cast = _caster(x_ref.dtype, interpret)
    Bm, Cm = b_ref[...], c_ref[...]

    @pl.when(pl.program_id(1) == 0)
    def _last_chunk():
        dst_ref[hi] = jnp.zeros(dst_ref.shape[1:], f32)

    @pl.when(_first_of_group(per_group))
    def _scores():
        s_ref[...] = _dot(Cm, Bm, _NT)
        ds_ref[...] = jnp.zeros_like(ds_ref)
        db_ref[...] = jnp.zeros_like(db_ref)
        dc_ref[...] = jnp.zeros_like(dc_ref)

    x, dy = x_ref[...], dy_ref[...]
    S, dS = sin_ref[...], dst_ref[hi]
    k = _Step(x, cols_ref[...], rows_ref[...], hb, heads, cast)
    Sb, dSb = cast(S), cast(dS)
    xw = cast(k.xd * k.to_end)
    decay_in = jnp.exp(k.cum)
    dyf = dy.astype(f32)
    # the three products around the state, all heads at once
    carried = _dot(Cm, Sb, _NN) * decay_in
    dcar = cast(dyf * decay_in)
    dc_ref[...] += _dot(dcar, Sb, _NT)
    dxw = _dot(Bm, dSb, _NN)
    db_ref[...] += _dot(xw, dSb, _NT)
    dst_ref[hi] = k.e_last * dS + _dot(Cm, dcar, _TN)
    # inside the chunk, a pair of heads at a time
    s, ds = s_ref[...], ds_ref[...]
    y, dxd1 = [], []
    for lanes, pair in k.pairs():
        xp, dyp = k.xd[:, lanes], dy[:, lanes]
        y_p, dxd_p = carried[:, lanes], None
        for h, mine in pair:
            L = k.decay(h)
            M = cast(s * L)
            dy_h = jnp.where(mine, dyp, 0)
            ds = ds + _dot(dy_h, xp, _NT) * L
            y_p = y_p + _dot(M, jnp.where(mine, xp, 0), _NN)
            back = _dot(M, dy_h, _TN)
            dxd_p = back if dxd_p is None else dxd_p + back
        y.append(y_p)
        dxd1.append(dxd_p)
    ds_ref[...] = ds
    y, dxd1 = jnp.concatenate(y, axis=1), jnp.concatenate(dxd1, axis=1)
    dxd = dxd1 + dxw * k.to_end.astype(f32)
    dx_ref[...] = (dxd * k.dt.astype(f32)).astype(dx_ref.dtype)
    ended = dxw * xw.astype(f32)
    sums = _head_sums([dxd * x.astype(f32),
                       dyf * y - k.xd.astype(f32) * dxd1 - ended], hb, heads,
                      dcols_ref.shape[1])

    @pl.when(hi == 0)
    def _first_block():
        dcols_ref[...] = sums

    @pl.when(hi != 0)
    def _later_block():
        dcols_ref[...] += sums

    dlast_ref[...] = (jnp.sum(ended, axis=0, keepdims=True)
                      + k.e_last * jnp.sum(dS * S, axis=0, keepdims=True))

    @pl.when(hi % per_group == per_group - 1)
    def _group_done():
        dsb = cast(ds_ref[...])
        dc_ref[...] += _dot(dsb, Bm, _NN)
        db_ref[...] += _dot(dsb, Cm, _TN)


def backward(x: jax.Array, B: jax.Array, C: jax.Array, cols: jax.Array,
             rows: jax.Array, S_in: jax.Array, dy: jax.Array, *,
             heads_a_group: int, interpret: bool = False):
    """`forward`'s inputs and residual and dy (b, t, H 64) in x's dtype ->
    (dx in x's dtype, dB and dC (b, t, G N) float32, `[ddt | dcum]` laid
    out as `cols` (ddt the part that does not go through `cum`), dlast (b, c,
    1, H 64): what a chunk's last `cum` gets besides its row of dcum, still
    to be summed over each head's lanes)."""
    return _backward_call(x, B, C, cols, rows, S_in, dy,
                          per_group=heads_a_group // rows.shape[3],
                          interpret=interpret)


@functools.partial(jax.jit, inline=True,
                   static_argnames=("per_group", "interpret"))
def _backward_call(x, B, C, cols, rows, S_in, dy, *, per_group, interpret):
    b, t, HP = x.shape
    nhb, c, hb, Q = rows.shape[1:]
    N = S_in.shape[2]
    W = hb * HEAD_DIM
    wide, group, col, row, state = _specs(Q, W, N, hb, cols.shape[2],
                                          per_group, lambda j: c - 1 - j)
    item = x.dtype.itemsize
    step_bytes = (Q * (3 * W + 2 * N) * item
                  + (2 * Q * cols.shape[2] + hb * Q) * 4
                  + (N * W + 2 * Q * N + W) * 4)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, hb=hb, heads=nhb * hb,
                          per_group=per_group, interpret=interpret),
        grid=(b, c, nhb),
        in_specs=[wide, group, group, col, row, state, wide],
        out_specs=[wide, group, group, col,
                   pl.BlockSpec((None, None, 1, W),
                                lambda b, j, h: (b, c - 1 - j, 0, h))],
        out_shape=[_out_struct(x.shape, x.dtype, x),
                   _out_struct(B.shape, jnp.float32, x),
                   _out_struct(C.shape, jnp.float32, x),
                   _out_struct(cols.shape, jnp.float32, x),
                   _out_struct((b, c, 1, HP), jnp.float32, x)],
        scratch_shapes=[pltpu.VMEM((nhb, N, W), jnp.float32),
                        pltpu.VMEM((Q, Q), jnp.float32),
                        pltpu.VMEM((Q, Q), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=_vmem_limit(
                2 * step_bytes + (nhb * N * W + 2 * Q * Q) * 4)),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * c * nhb * Q * (3 * Q * N // per_group + 3 * Q * W
                                         + 5 * N * W + 6 * W * LANES),
            bytes_accessed=b * c * nhb * step_bytes,
            transcendentals=b * c * nhb * Q * (hb * Q + 2 * W)),
        interpret=interpret,
        name=BWD_NAME,
    )(x, B, C, cols, rows, S_in, dy)
