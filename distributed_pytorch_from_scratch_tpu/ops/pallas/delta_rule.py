"""The chunked gated delta rule's walk over chunks as two Pallas kernels
(ops/delta_rule.py has the rule and what it feeds these with).

What reads the state `S` (d_k x d_v, float32, a head) is a chain of one step
a chunk. Per head and chunk, with the chunk-parallel operands `W`, `U`,
`attn`, `q_in`, `k_out` and the chunk's whole decay `e = exp(G_end)`:

    forward     v_new = U - W S
                o     = q_in S + attn v_new
                S    <- e S + k_out^T v_new

    backward    dv    = attn^T do + k_out dS          (dS: of the state LEFT)
                dattn = do v_new^T     dq_in = do S^T     dk_out = v_new dS^T
                dU    = dv             dW    = -dv S^T
                de    = sum(dS * S)
                dS   <- e dS + q_in^T do - W^T dv

Either is one `pallas_call` a sequence: the grid is (blocks of heads, blocks
of chunks), chunks last and sequential, and the state (in the backward its
cotangent) stays in VMEM in float32 from a head block's first chunk to its
last; the backward walks the chunks from the last to the first and reads
the state each chunk ENTERED with, which the forward writes out when it is
asked for residuals. A grid step runs `CHUNK_BLOCK` chunks of `HEAD_BLOCK`
heads, unrolled: the heads are independent chains, so the scheduler has a
second head's products to issue while the first waits on its own. The
products' operands are the compute dtype (`q_in`'s), `U`, the state, the
decay and every sum float32, as in the rule's XLA text.

`e` is one scalar a head and chunk: it comes in through SMEM (a scalar
prefetch), not as a (rows, 1) block that would hold one value in 128 lanes;
`de` goes out as an (8, d_v) tile of partial sums (the state's rows folded
by eight, which costs no cross-sublane reduction) that the caller sums.

Names and operand counts are part of the benchmark's yardstick
(benchmark/lib/kernels.py reads any Mosaic call with 3 or 6 operands, or a
name starting `flash_`, as a flash call): `gdn_rule_fwd` has 5 operands,
`gdn_rule_bwd` 9.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _NN, _NT, _dot, _out_struct

FWD_NAME = "gdn_rule_fwd"
BWD_NAME = "gdn_rule_bwd"
# heads and chunks a grid step (tune_delta_rule.py's sweep, PERF.md PR 36)
HEAD_BLOCK = 8
CHUNK_BLOCK = 2

_TN = (((0,), (0,)), ((), ()))   # a^T @ b


def holds(d_k: int, d_v: int, chunk: int) -> bool:
    """The shapes the kernels take: the widths fill whole lanes (the joined
    [W | U] is cut at d_k) and a chunk's rows whole sublane tiles of either
    dtype."""
    return d_k % 128 == 0 and d_v % 128 == 0 and chunk % 16 == 0


def _blocks(h: int, n: int) -> Tuple[int, int]:
    """(heads, chunks) a grid step. A head block may hang over the last
    head (heads are independent: what the overhang computes is never
    written); a chunk block divides the chunks, which are one chain."""
    return min(HEAD_BLOCK, h), max(c for c in range(1, CHUNK_BLOCK + 1)
                                   if n % c == 0)


def _vmem_limit(block_bytes: int) -> int:
    """Blocks double-buffered plus room for the body's own values."""
    return min(2 * block_bytes + 16 * 2 ** 20, 100 * 2 ** 20)


# ---------------------------------------------------------------- forward

def _fwd_chunk(S, W, U, attn, q_in, k_out, e):
    """One head's chunk step: the state it enters with and the chunk's
    operands -> (o, v_new in the products' dtype, the state it leaves)."""
    dtype = q_in.dtype
    Sb = S.astype(dtype)
    v_new = (U - _dot(W.astype(dtype), Sb, _NN)).astype(dtype)
    o = _dot(q_in, Sb, _NN) + _dot(attn, v_new, _NN)
    return o, v_new, e * S + _dot(k_out, v_new, _TN)


def _fwd_kernel(decay_ref, wu_ref, attn_ref, q_ref, k_ref, o_ref, s_ref,
                *residual_refs, heads: int):
    """Blocks (hb, cb, C, .); `s_ref` (hb, d_k, d_v) is the final state's
    output block, whose index ignores the chunk axis: resident, it IS the
    carried state."""
    i, j = pl.program_id(0), pl.program_id(1)
    hb, cb = q_ref.shape[:2]
    dk = q_ref.shape[-1]

    @pl.when(j == 0)
    def _first_chunk():
        s_ref[...] = jnp.zeros_like(s_ref)

    for c in range(cb):
        for hh in range(hb):
            S = s_ref[hh]
            e = decay_ref[jnp.minimum(i * hb + hh, heads - 1), j * cb + c]
            o, v_new, s_ref[hh] = _fwd_chunk(
                S, wu_ref[hh, c, :, :dk], wu_ref[hh, c, :, dk:],
                attn_ref[hh, c], q_ref[hh, c], k_ref[hh, c], e)
            o_ref[hh, c] = o.astype(o_ref.dtype)
            if residual_refs:
                residual_refs[0][hh, c] = S
                residual_refs[1][hh, c] = v_new


def walk_forward(WU: jax.Array, attn: jax.Array, q_in: jax.Array,
                 k_out: jax.Array, decay: jax.Array, *, out_dtype,
                 residuals: bool, interpret: bool = False):
    """WU (h, n, C, d_k + d_v) float32, the solve's [W | U]; attn (h, n, C,
    C), q_in, k_out (h, n, C, d_k) in the products' dtype; decay (h, n)
    float32. Returns (o (h, n, C, d_v) in `out_dtype`, the final state (h,
    d_k, d_v) float32) and, with `residuals`, the state every chunk entered
    with (h, n, d_k, d_v) float32 and v_new (h, n, C, d_v)."""
    h, n, C, dk = q_in.shape
    dv = WU.shape[-1] - dk
    hb, cb = _blocks(h, n)
    block = lambda *tail: pl.BlockSpec(
        (hb, cb) + tail, lambda i, j, _: (i, j) + (0,) * len(tail))
    out_specs = [block(C, dv),
                 pl.BlockSpec((hb, dk, dv), lambda i, j, _: (i, 0, 0))]
    out_shape = [_out_struct((h, n, C, dv), out_dtype, WU),
                 _out_struct((h, dk, dv), jnp.float32, WU)]
    if residuals:
        out_specs += [block(dk, dv), block(C, dv)]
        out_shape += [_out_struct((h, n, dk, dv), jnp.float32, WU),
                      _out_struct((h, n, C, dv), q_in.dtype, WU)]
    item = q_in.dtype.itemsize
    step_bytes = hb * cb * (
        C * (dk + dv) * 4 + (C * max(C, 128) + 2 * C * dk) * item
        + C * dv * jnp.dtype(out_dtype).itemsize
        + residuals * (dk * dv * 4 + C * dv * item)) + hb * dk * dv * 4
    return pl.pallas_call(
        functools.partial(_fwd_kernel, heads=h),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(pl.cdiv(h, hb), n // cb),
            in_specs=[block(C, dk + dv), block(C, C), block(C, dk),
                      block(C, dk)],
            out_specs=out_specs),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(step_bytes)),
        cost_estimate=pl.CostEstimate(
            flops=2 * h * n * C * (2 * dk * dv + C * dv + dk * dv),
            bytes_accessed=h * n * step_bytes // (hb * cb),
            transcendentals=0),
        interpret=interpret,
        name=FWD_NAME,
    )(decay, WU, attn, q_in, k_out)


# --------------------------------------------------------------- backward

def _bwd_chunk(dS, S, W, attn, q_in, k_out, v_new, do, e):
    """The transpose of `_fwd_chunk`: the cotangent of the state the chunk
    LEFT and what the forward held -> (dW, dU, dattn, dq_in, dk_out, the
    decay's cotangent as an (8, d_v) tile of partial sums, the cotangent of
    the state the chunk entered with)."""
    dtype = q_in.dtype
    Sb, dSb = S.astype(dtype), dS.astype(dtype)
    dv = _dot(attn, do, _TN) + _dot(k_out, dSb, _NN)
    dvb = dv.astype(dtype)
    de = jnp.sum((dS * S).reshape(-1, 8, S.shape[-1]), axis=0)
    dS_in = (e * dS + _dot(q_in, do, _TN)
             - _dot(W.astype(dtype), dvb, _TN))
    return (-_dot(dvb, Sb, _NT), dv, _dot(do, v_new, _NT),
            _dot(do, Sb, _NT), _dot(v_new, dSb, _NT), de, dS_in)


def _bwd_kernel(decay_ref, w_ref, attn_ref, q_ref, k_ref, s_ref, v_ref,
                do_ref, ds_last_ref, dwu_ref, dattn_ref, dq_ref, dk_ref,
                de_ref, ds_ref, *, heads: int):
    """The grid's chunk axis runs backwards (the index maps turn it): step
    j holds chunk block `last - j`. `ds_ref` (hb, d_k, d_v) is scratch: the
    state's cotangent, from the final state's down to the first chunk."""
    i, j = pl.program_id(0), pl.program_id(1)
    hb, cb = q_ref.shape[:2]
    dk = q_ref.shape[-1]
    first = (pl.num_programs(1) - 1 - j) * cb

    @pl.when(j == 0)
    def _last_chunk():
        ds_ref[...] = ds_last_ref[...]

    for c in reversed(range(cb)):
        for hh in range(hb):
            e = decay_ref[jnp.minimum(i * hb + hh, heads - 1), first + c]
            dW, dU, dattn, dq_in, dk_out, de, ds_ref[hh] = _bwd_chunk(
                ds_ref[hh], s_ref[hh, c], w_ref[hh, c], attn_ref[hh, c],
                q_ref[hh, c], k_ref[hh, c], v_ref[hh, c], do_ref[hh, c], e)
            dwu_ref[hh, c, :, :dk] = dW
            dwu_ref[hh, c, :, dk:] = dU
            dattn_ref[hh, c] = dattn.astype(dattn_ref.dtype)
            dq_ref[hh, c] = dq_in.astype(dq_ref.dtype)
            dk_ref[hh, c] = dk_out.astype(dk_ref.dtype)
            de_ref[hh, c] = de


def walk_backward(WU: jax.Array, attn: jax.Array, q_in: jax.Array,
                  k_out: jax.Array, decay: jax.Array, S_in: jax.Array,
                  v_new: jax.Array, do: jax.Array, dS: jax.Array, *,
                  interpret: bool = False):
    """`walk_forward`'s operands and residuals, do (h, n, C, d_v) in the
    products' dtype and the final state's cotangent dS (h, d_k, d_v)
    float32 -> the cotangents (dWU float32; dattn, dq_in, dk_out in their
    operands' dtype; ddecay (h, n) float32)."""
    h, n, C, dk = q_in.shape
    dv = WU.shape[-1] - dk
    hb, cb = _blocks(h, n)
    last = n // cb - 1
    block = lambda *tail: pl.BlockSpec(
        (hb, cb) + tail, lambda i, j, _: (i, last - j) + (0,) * len(tail))
    state = pl.BlockSpec((hb, dk, dv), lambda i, j, _: (i, 0, 0))
    item = q_in.dtype.itemsize
    step_bytes = hb * cb * (
        C * dk * 4 + (2 * C * max(C, 128) + 4 * C * dk + 2 * C * dv) * item
        + dk * dv * 4 + C * (dk + dv) * 4 + 8 * dv * 4) + 2 * hb * dk * dv * 4
    dWU, dattn, dq_in, dk_out, de = pl.pallas_call(
        functools.partial(_bwd_kernel, heads=h),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(pl.cdiv(h, hb), n // cb),
            # of [W | U] the backward reads W: the block is the left d_k
            # columns
            in_specs=[block(C, dk), block(C, C), block(C, dk), block(C, dk),
                      block(dk, dv), block(C, dv), block(C, dv), state],
            out_specs=[block(C, dk + dv), block(C, C), block(C, dk),
                       block(C, dk), block(8, dv)],
            scratch_shapes=[pltpu.VMEM((hb, dk, dv), jnp.float32)]),
        out_shape=[_out_struct(WU.shape, jnp.float32, WU),
                   _out_struct(attn.shape, attn.dtype, WU),
                   _out_struct(q_in.shape, q_in.dtype, WU),
                   _out_struct(k_out.shape, k_out.dtype, WU),
                   _out_struct((h, n, 8, dv), jnp.float32, WU)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(step_bytes)),
        cost_estimate=pl.CostEstimate(
            flops=2 * h * n * C * (5 * dk * dv + 2 * C * dv),
            bytes_accessed=h * n * step_bytes // (hb * cb),
            transcendentals=0),
        interpret=interpret,
        name=BWD_NAME,
    )(decay, WU, attn, q_in, k_out, S_in, v_new, do, dS)
    return dWU, dattn, dq_in, dk_out, jnp.sum(de, axis=(-2, -1))
