"""The Mamba-1 selective scan as two Pallas kernels: the state walked token
by token in vector registers, forward and backward (ops/selective_scan.py
has the recurrence, the XLA text of the same and what it hands these).

A channel c holds a state `h[c, :]` of `N` numbers; its decay is a (channel,
state) table, `exp(dt_t[c] A[c, n])`, so a chunk has no `C B^T` and nothing
here is a matrix product: the work is `t x channels x N` multiply-adds and
exponentials on the VECTOR unit, in order over t. The kernels keep the state
TRANSPOSED, `(N, channels)`: the N states of a channel down the sublanes, the
channels along the lanes, so `dt_t` and `u_t` are rows over the lanes and
`B_t`, `C_t` columns down the sublanes. XLA hands the columns already spread
over one lane tile, `(b, t, N, 128)` float32 (134 MB a layer at 16k, made
inside the call's own forward and backward and not kept), so that a token's
column is one aligned load and no `(1, n) -> (n, 1)` turn is asked of Mosaic.

A grid step is `CHUNK` tokens of `channels_a_step(c)` channels (512: four
lane tiles, a state of eight vector registers carried through the token
loop); the grid is (sequences, chunks, channel blocks), every axis
sequential, the channel blocks innermost, and every block's state stays in
VMEM for the whole call (`(blocks, N, 512)` float32, 327 KB at 5120
channels). The token loop takes `GROUP` = 8 tokens a trip (one sublane tile
of `dt`, `u` and `y` rows, read and written whole).

`sscan_fwd` (u, dt, A^T, B's and C's columns: five operands) writes y (b, t,
c) float32 and, asked for residuals, the state each chunk ENTERED with,
`(b, chunks, N, c)` float32 (42 MB a layer at 16k). `sscan_bwd` (those, the
states and dy: seven) walks the chunks in reverse with the state's cotangent
resident: a step makes its chunk's states again into VMEM (`(CHUNK, N, 512)`
float32, 4 MB), then walks its tokens backward:

    g_t   = C_t dy_t + a_{t+1} g_{t+1}            the state's whole cotangent
    dC_t  = sum_c dy_t h_t      dB_t = sum_c g_t (dt_t u_t)
    s_t   = sum_n g_t B_t       du_t = s_t dt_t
    e_t   = g_t h_{t-1} a_t     ddt_t = s_t u_t + sum_n e_t A
    dA   += e_t dt_t

The two sums over channels leave as they lie, a token's `(N, 128)` partial
summed over the step's lane tiles and over the channel blocks in VMEM (`(b, t,
N, 128)` float32), and XLA sums the 128 lanes; `dA` leaves a chunk at a time
and XLA sums the chunks.

Names and operand counts are part of the benchmark's yardstick
(benchmark/lib/kernels.py reads a Mosaic call named `flash_*`, or with 3 or
6 operands, as a flash call): 5 and 7 here.
"""

from __future__ import annotations

import functools

import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _out_struct, _vmem_limit

FWD_NAME = "sscan_fwd"
BWD_NAME = "sscan_bwd"
LANES = 128
CHUNK = 128         # tokens a grid step
GROUP = 8           # tokens a trip of the token loop: a sublane tile of rows
CHANNELS = 512      # the most channels a grid step


def holds(channels: int, state: int) -> bool:
    """The shapes the kernels take: channels in whole lane tiles, a state in
    whole sublane tiles."""
    return channels % LANES == 0 and state % 8 == 0


def channels_a_step(channels: int) -> int:
    """The most whole lane tiles, up to `CHANNELS`, that divide the
    channels."""
    return max(w for w in range(LANES, CHANNELS + 1, LANES)
               if channels % w == 0)


def _tiles(row, n: int):
    """A (rows, n * 128) value as its n lane tiles."""
    return [row[:, l * LANES:(l + 1) * LANES] for l in range(n)]


def _rows(ref, r, n: int):
    """Rows [r, r + GROUP) of a (CHUNK, W) block, float32, a lane tile
    each."""
    return _tiles(ref[pl.ds(r, GROUP), :].astype(jnp.float32), n)


def _put(rows, i: int, row):
    """`rows` (GROUP, 128) with row `i` set to `row` (1, 128)."""
    at = lax.broadcasted_iota(jnp.int32, rows.shape, 0) == i
    return jnp.where(at, row, rows)


# ---------------------------------------------------------------- forward

def _fwd_kernel(u_ref, dt_ref, a_ref, bb_ref, cb_ref, y_ref, *rest,
                residuals: bool):
    """Blocks: u, dt (CHUNK, W); A^T (N, W); B's and C's columns (CHUNK, N,
    128); out y (CHUNK, W) float32 and, with `residuals`, the state the
    chunk entered with (N, W); scratch: every channel block's state."""
    h_ref = rest[-1]
    k, j = pl.program_id(1), pl.program_id(2)
    n = u_ref.shape[1] // LANES

    @pl.when(k == 0)
    def _():
        h_ref[j] = jnp.zeros(h_ref.shape[1:], jnp.float32)

    entered = h_ref[j]
    if residuals:
        rest[0][...] = entered
    A = _tiles(a_ref[...], n)

    def group(g, h):
        r = pl.multiple_of(g * GROUP, GROUP)
        u8, dt8 = _rows(u_ref, r, n), _rows(dt_ref, r, n)
        y8 = [jnp.zeros((GROUP, LANES), jnp.float32) for _ in range(n)]
        h = list(h)
        for i in range(GROUP):
            B, C = bb_ref[r + i], cb_ref[r + i]         # (N, 128)
            for l in range(n):
                dt = dt8[l][i:i + 1]
                h[l] = jnp.exp(dt * A[l]) * h[l] + B * (dt * u8[l][i:i + 1])
                y8[l] = _put(y8[l], i,
                             jnp.sum(C * h[l], axis=0, keepdims=True))
        y_ref[pl.ds(r, GROUP), :] = jnp.concatenate(y8, axis=1)
        return tuple(h)

    h = lax.fori_loop(0, u_ref.shape[0] // GROUP, group,
                      tuple(_tiles(entered, n)))
    h_ref[j] = jnp.concatenate(h, axis=1)


def forward(u, dt, At, Bb, Cb, *, residuals: bool, interpret: bool):
    """u, dt (b, T, c) with T a multiple of CHUNK, At (N, c), Bb and Cb (b,
    T, N, 128) -> y (b, T, c) float32 [, the states (b, T / CHUNK, N, c)]."""
    b, T, c = u.shape
    N = At.shape[0]
    W, nk = channels_a_step(c), T // CHUNK
    wide = pl.BlockSpec((None, CHUNK, W), lambda b, k, j: (b, k, j))
    cols = pl.BlockSpec((None, CHUNK, N, LANES), lambda b, k, j: (b, k, 0, 0))
    out_specs = [wide]
    out_shape = [_out_struct((b, T, c), jnp.float32, u)]
    if residuals:
        out_specs.append(pl.BlockSpec((None, None, N, W),
                                      lambda b, k, j: (b, k, 0, j)))
        out_shape.append(_out_struct((b, nk, N, c), jnp.float32, u))
    step_bytes = (CHUNK * W * (u.dtype.itemsize + 8)
                  + 2 * CHUNK * N * LANES * 4 + 2 * N * W * 4)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, residuals=residuals),
        grid=(b, nk, c // W),
        in_specs=[wide, wide,
                  pl.BlockSpec((N, W), lambda b, k, j: (0, j)), cols, cols],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((c // W, N, W), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=_vmem_limit(2 * step_bytes + N * c * 4)),
        cost_estimate=pl.CostEstimate(
            flops=7 * b * T * c * N, transcendentals=b * T * c * N,
            bytes_accessed=b * nk * (c // W) * step_bytes),
        interpret=interpret,
        name=FWD_NAME,
    )(u, dt, At, Bb, Cb)


# --------------------------------------------------------------- backward

def _bwd_kernel(u_ref, dt_ref, a_ref, bb_ref, cb_ref, hin_ref, dy_ref,
                du_ref, ddt_ref, da_ref, dbb_ref, dcb_ref, hs_ref, g_ref):
    """`_fwd_kernel`'s blocks, the state the chunk entered with (N, W) and
    dy (CHUNK, W); out du, ddt (CHUNK, W) float32, this chunk's dA^T (N, W),
    and dB's and dC's partial columns (CHUNK, N, 128), resident over the
    channel blocks; scratch: the chunk's states BEFORE each token (CHUNK, N,
    W) and every channel block's carried cotangent `a_{t+1} g_{t+1}`."""
    k, j = pl.program_id(1), pl.program_id(2)
    n = u_ref.shape[1] // LANES
    trips = u_ref.shape[0] // GROUP
    A = _tiles(a_ref[...], n)

    @pl.when(k == 0)            # the LAST chunk: nothing comes after it
    def _():
        g_ref[j] = jnp.zeros(g_ref.shape[1:], jnp.float32)

    def again(g, h):
        r = pl.multiple_of(g * GROUP, GROUP)
        u8, dt8 = _rows(u_ref, r, n), _rows(dt_ref, r, n)
        h = list(h)
        for i in range(GROUP):
            B = bb_ref[r + i]
            hs_ref[r + i] = jnp.concatenate(h, axis=1)
            for l in range(n):
                dt = dt8[l][i:i + 1]
                h[l] = jnp.exp(dt * A[l]) * h[l] + B * (dt * u8[l][i:i + 1])
        return tuple(h)

    lax.fori_loop(0, trips, again, tuple(_tiles(hin_ref[...], n)))

    @pl.when(j == 0)            # the blocks stay in VMEM over the channel
    def _():                    # blocks, which add into them
        dbb_ref[...] = jnp.zeros(dbb_ref.shape, jnp.float32)
        dcb_ref[...] = jnp.zeros(dcb_ref.shape, jnp.float32)

    def group(back, carry):
        r = pl.multiple_of((trips - 1 - back) * GROUP, GROUP)
        u8, dt8, dy8 = (_rows(ref, r, n) for ref in (u_ref, dt_ref, dy_ref))
        zeros = lambda: [jnp.zeros((GROUP, LANES), jnp.float32)
                         for _ in range(n)]
        du8, ddt8 = zeros(), zeros()
        gc, dA = list(carry[:n]), list(carry[n:])
        for i in reversed(range(GROUP)):
            B, C = bb_ref[r + i], cb_ref[r + i]
            before = _tiles(hs_ref[r + i], n)
            dB = dC = jnp.zeros_like(B)
            for l in range(n):
                dt, u, dy = (x[l][i:i + 1] for x in (dt8, u8, dy8))
                a = jnp.exp(dt * A[l])
                xu = dt * u
                g = gc[l] + C * dy
                dC = dC + dy * (a * before[l] + B * xu)
                dB = dB + g * xu
                s = jnp.sum(g * B, axis=0, keepdims=True)
                e = g * before[l] * a
                du8[l] = _put(du8[l], i, s * dt)
                ddt8[l] = _put(ddt8[l], i, s * u + jnp.sum(
                    e * A[l], axis=0, keepdims=True))
                dA[l] = dA[l] + e * dt
                gc[l] = a * g
            dbb_ref[r + i] += dB
            dcb_ref[r + i] += dC
        du_ref[pl.ds(r, GROUP), :] = jnp.concatenate(du8, axis=1)
        ddt_ref[pl.ds(r, GROUP), :] = jnp.concatenate(ddt8, axis=1)
        return tuple(gc + dA)

    zero = jnp.zeros((a_ref.shape[0], LANES), jnp.float32)
    out = lax.fori_loop(0, trips, group,
                        tuple(_tiles(g_ref[j], n)) + (zero,) * n)
    g_ref[j] = jnp.concatenate(out[:n], axis=1)
    da_ref[...] = jnp.concatenate(out[n:], axis=1)


def backward(u, dt, At, Bb, Cb, H_in, dy, *, interpret: bool):
    """`forward`'s operands, its states and dy (b, T, c) float32 -> du, ddt
    (b, T, c) float32, dA^T a chunk (b, T / CHUNK, N, c), dB's and dC's
    partial columns (b, T, N, 128)."""
    b, T, c = u.shape
    N = At.shape[0]
    W, nk = channels_a_step(c), T // CHUNK
    at = lambda k: nk - 1 - k                   # the chunks in reverse
    wide = pl.BlockSpec((None, CHUNK, W), lambda b, k, j: (b, at(k), j))
    cols = pl.BlockSpec((None, CHUNK, N, LANES),
                        lambda b, k, j: (b, at(k), 0, 0))
    state = pl.BlockSpec((None, None, N, W),
                         lambda b, k, j: (b, at(k), 0, j))
    f32 = lambda shape: _out_struct(shape, jnp.float32, u)
    step_bytes = (CHUNK * W * (u.dtype.itemsize + 16)
                  + 4 * CHUNK * N * LANES * 4 + 3 * N * W * 4)
    return pl.pallas_call(
        _bwd_kernel,
        grid=(b, nk, c // W),
        in_specs=[wide, wide, pl.BlockSpec((N, W), lambda b, k, j: (0, j)),
                  cols, cols, state, wide],
        out_specs=[wide, wide, state, cols, cols],
        out_shape=[f32((b, T, c)), f32((b, T, c)), f32((b, nk, N, c)),
                   f32(Bb.shape), f32(Cb.shape)],
        scratch_shapes=[pltpu.VMEM((CHUNK, N, W), jnp.float32),
                        pltpu.VMEM((c // W, N, W), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=_vmem_limit(
                2 * step_bytes + (CHUNK * N * W + N * c) * 4)),
        cost_estimate=pl.CostEstimate(
            flops=24 * b * T * c * N, transcendentals=2 * b * T * c * N,
            bytes_accessed=b * nk * (c // W) * step_bytes),
        interpret=interpret,
        name=BWD_NAME,
    )(u, dt, At, Bb, Cb, H_in, dy)
