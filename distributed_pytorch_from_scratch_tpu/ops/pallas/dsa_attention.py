"""Attention over a live set that is DATA: the five Mosaic kernels behind
`ops/index_select.selected_attention` (the mathematics and the XLA oracle
are there).

A row t of a sequence keeps the `top_k` keys s <= t of largest index score
`I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])` (float32; ties to the
earlier key). The score is T x T a sequence and is never written to HBM:
every kernel RE-MAKES a tile of it from `qI` (b, J, t, c), `kI` (b, t, c)
and `w` (b, J, t, 1) by the one function `_index_tile`, so all five see the
same numbers, and the selection travels as two numbers a row: `tau`, the
row's `top_k`-th largest score, and `cut`, the last key index that is kept
among the keys whose score EQUALS tau. `(s <= t) and (I > tau or (I == tau
and s <= cut))` (`_live`) is then the row's set, exactly.

* `dsa_select`: a grid step holds one block of query rows' scores against
  every key up to the block's last row, as order-preserving int32 keys in
  VMEM (`_sortable`), and finds tau by building its bits from the top (32
  passes of compare-and-count over the scratch, no sort), then how many
  keys are tied at tau and, only where a row's ties straddle the budget,
  `cut` by bisection on the key index;
* `dsa_flash_fwd`: the flash walk of the whole triangle, ALL the query
  heads of a row block a grid step (the index tile and the set's mask are
  made once a tile and shared by the heads, a key-value group at a time),
  online softmax under the mask; beside o and each head's lse it returns
  the logsumexp of I over a row's set and the number of keys it kept;
* `dsa_flash_bwd_dq`, `dsa_flash_bwd_dkv`: the standard two backward walks
  under the same mask (the choice carries no gradient);
* `dsa_index_loss`: the indexer's own loss in one more walk: a tile's
  head-summed attention probabilities P (from q, k and the forward's lse),
  the KL of P from softmax_S(I), its gradient `softmax_S(I) - P` pushed back
  through the ReLU into qI, kI and w, and the entropy of softmax_S(I).

Nothing here plans tiles from a declaration: a tile above the diagonal is
skipped, every other tile is computed whole and masked
(`dsa.flash_computed_over_live` in the benchmark says what that costs).
The call names match none of `benchmark/lib/kernels.py`'s flash patterns,
and no call has 3 or 6 operands, on purpose: the static-mask flash metrics
do not count these.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _out_struct, _require_tpu

MASK = -1e30
INT_MIN = -(2 ** 31)
BLOCK_Q = 128
BLOCK_K = 512
# keys a pass of the selection's compare-and-count reads at once
COUNT_CHUNK = 2048
VMEM_LIMIT = 96 * 2 ** 20

_NT = (((1,), (1,)), ((), ()))   # a @ b^T
_NN = (((1,), (0,)), ((), ()))   # a @ b
_TN = (((0,), (0,)), ((), ()))   # a^T @ b


def _dot(a, b, dims):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _kernel(body, rank: int, like: jax.Array, interpret: bool, **static):
    """`body(ids, last, *refs, **static)` as the kernel of a grid of `rank`
    axes: `ids` the grid step's program ids, `last` the last index of the
    innermost axis. Under the interpreter where the operands carry
    shard_map's varying axes the body runs under a condition that always
    holds (`ops/pallas/stream_mixer._guarded`'s reason: the interpreter
    types a kernel's ops one by one there, and a loop's counter beside a
    block's value fails it; a condition's body is one closed program)."""
    guarded = bool(interpret and getattr(jax.typeof(like), "vma", None))

    def call(*refs):
        ids = tuple(pl.program_id(axis) for axis in range(rank))
        last = pl.num_programs(rank - 1) - 1
        run = lambda: body(ids, last, *refs, **static)
        if guarded:
            pl.when(ids[0] >= 0)(run)
        else:
            run()
    return call


def _index_tile(qi, ki, w, bq: int):
    """qi (J bq, c) and ki (bk, c) in the compute dtype, w (J bq, 1) float32
    -> (z (J, bq, bk) float32, the heads' products before the ReLU, I (bq,
    bk) float32 with -0.0 written as 0.0)."""
    z = _dot(qi, ki, _NT)
    z = z.reshape(qi.shape[0] // bq, bq, ki.shape[0])
    score = jnp.sum(jnp.maximum(z, 0.0) * w.reshape(-1, bq, 1), axis=0)
    return z, jnp.where(score == 0.0, 0.0, score)


def _live(score, tau, cut, rows, cols):
    return (cols <= rows) & ((score > tau)
                             | ((score == tau) & (cols <= cut)))


def _flip(bits):
    """A float32's bits <-> the int32 whose signed order is the floats':
    the bits below the sign turned where the sign is set (an involution)."""
    return bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))


def _sortable(x):
    return _flip(lax.bitcast_convert_type(x, jnp.int32))


# ---------------------------------------------------------------- selection

def _select_kernel(ids, _, k_ref, qi_ref, ki_ref, w_ref, tau_ref, cut_ref, ties_ref,
                   keys_ref, *, bq: int, fill: int, chunk: int, t: int):
    i = ids[1]
    top_k = k_ref[0, 0]
    rows = i * bq + lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    chunks = (i * bq + bq + chunk - 1) // chunk     # up to the last row
    J, c = qi_ref.shape[1], qi_ref.shape[3]
    qi = qi_ref[0].reshape(J * bq, c)
    w = w_ref[0].reshape(J * bq, 1)

    def write(n, _):
        at = pl.multiple_of(n * fill, fill)
        _, score = _index_tile(qi, ki_ref[0, pl.ds(at, fill), :], w, bq)
        cols = at + lax.broadcasted_iota(jnp.int32, (1, fill), 1)
        keys_ref[:, pl.ds(at, fill)] = jnp.where(
            cols <= rows, _sortable(score), jnp.int32(INT_MIN))
        return 0

    lax.fori_loop(0, chunks * (chunk // fill), write, 0)

    def count(pred):
        """Keys a row (bq, 1) for which `pred(keys, cols)` holds."""
        def more(n, acc):
            at = pl.multiple_of(n * chunk, chunk)
            cols = at + lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
            hit = pred(keys_ref[:, pl.ds(at, chunk)], cols)
            return acc + jnp.sum(hit.astype(jnp.int32), axis=1,
                                 keepdims=True)
        return lax.fori_loop(0, chunks, more, jnp.zeros((bq, 1), jnp.int32))

    # the top_k-th largest key, bit by bit from the top, in the order of
    # the keys read as unsigned (u = s ^ INT_MIN): the largest u that
    # top_k keys reach
    def bit(n, found):
        cand = found | (jnp.int32(1) << (31 - n))
        reach = count(lambda keys, _: keys >= (cand ^ jnp.int32(INT_MIN)))
        return jnp.where(reach >= top_k, cand, found)

    found = lax.fori_loop(0, 32, bit, jnp.zeros((bq, 1), jnp.int32))
    tau_key = found ^ jnp.int32(INT_MIN)
    reach = count(lambda keys, _: keys >= tau_key)
    above = count(lambda keys, _: keys > tau_key)
    need = top_k - above            # of the keys tied at tau, the first few
    short = rows + 1 <= top_k       # the row keeps all it sees
    tied = (reach > top_k) & ~short
    ties_ref[0] = tied.astype(jnp.float32)
    cut_ref[0] = jnp.full((bq, 1), t, jnp.int32)

    @pl.when(jnp.max(tied.astype(jnp.int32)) > 0)
    def _():
        # the smallest index at which `need` tied keys have been seen
        def halve(_, bounds):
            lo, hi = bounds
            mid = (lo + hi) // 2
            seen = count(lambda keys, cols: (keys == tau_key)
                         & (cols <= mid))
            enough = seen >= need
            return jnp.where(enough, lo, mid + 1), jnp.where(enough, mid, hi)
        lo, _ = lax.fori_loop(
            0, max(t - 1, 1).bit_length() + 1, halve,
            (jnp.zeros((bq, 1), jnp.int32),
             jnp.full((bq, 1), t - 1, jnp.int32)))
        cut_ref[0] = jnp.where(tied, lo, t)

    tau = lax.bitcast_convert_type(_flip(tau_key), jnp.float32)
    tau_ref[0] = jnp.where(short, -jnp.inf, tau)


def select_call(q_idx, k_idx, w, top_k: int, *, bq: int, bk: int,
                interpret: bool):
    """(tau (b, t, 1) float32, cut (b, t, 1) int32, tied (b, t, 1) float32:
    is the row's threshold shared by keys on both sides of the budget)."""
    b, J, t, c = q_idx.shape
    assert t % bq == 0 and t % bk == 0, (t, bq, bk)
    # the score is made a tile of the walks' own shape (the same products,
    # bit for bit); a counting pass reads a few tiles' keys at once
    tiles = max(n for n in range(1, max(COUNT_CHUNK // bk, 1) + 1)
                if (t // bk) % n == 0)
    fill, chunk = bk, bk * tiles
    kernel = _kernel(_select_kernel, 2, q_idx, interpret, bq=bq, fill=fill,
                     chunk=chunk, t=t)
    row = lambda bi, i: (bi, i, 0)
    return pl.pallas_call(
        kernel,
        grid=(b, t // bq),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, J, bq, c), lambda bi, i: (bi, 0, i, 0)),
            pl.BlockSpec((1, t, c), lambda bi, i: (bi, 0, 0)),
            pl.BlockSpec((1, J, bq, 1), lambda bi, i: (bi, 0, i, 0)),
        ],
        out_specs=[pl.BlockSpec((1, bq, 1), row)] * 3,
        out_shape=[_out_struct((b, t, 1), jnp.float32, q_idx),
                   _out_struct((b, t, 1), jnp.int32, q_idx),
                   _out_struct((b, t, 1), jnp.float32, q_idx)],
        scratch_shapes=[pltpu.VMEM((bq, t), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="dsa_select",
    )(jnp.full((1, 1), top_k, jnp.int32), q_idx, k_idx, w)


# ------------------------------------------------------------ the flash walks

def _tile_live(i, j, qi_ref, ki_ref, w_ref, tau_ref, cut_ref, bq: int,
               bk: int):
    """(z, I, live (bq, bk) bool) of the grid's tile (i, j)."""
    J, c = qi_ref.shape[1], qi_ref.shape[3]
    z, score = _index_tile(qi_ref[0].reshape(J * bq, c), ki_ref[0],
                           w_ref[0].reshape(J * bq, 1), bq)
    rows = i * bq + lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    cols = j * bk + lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    return z, score, _live(score, tau_ref[0], cut_ref[0], rows, cols)


def _crosses(i, j, bq: int, bk: int):
    """Does tile (i, j) hold a key at or before one of its rows."""
    return j * bk <= i * bq + bq - 1


def _fwd_kernel(ids, last, q_ref, k_ref, v_ref, qi_ref, ki_ref, w_ref, tau_ref, cut_ref,
                o_ref, lse_ref, lse_i_ref, kept_ref,
                m_ref, l_ref, acc_ref, mi_ref, li_ref, n_ref,
                *, scale: float, bq: int, bk: int, group: int):
    _, i, j = ids

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, MASK, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
        mi_ref[...] = jnp.full(mi_ref.shape, MASK, jnp.float32)
        li_ref[...] = jnp.zeros(li_ref.shape, jnp.float32)
        n_ref[...] = jnp.zeros(n_ref.shape, jnp.float32)

    @pl.when(_crosses(i, j, bq, bk))
    def _():
        _, score, live = _tile_live(i, j, qi_ref, ki_ref, w_ref, tau_ref,
                                    cut_ref, bq, bk)
        # the index scores' own logsumexp over the set, and the set's size
        mi = jnp.maximum(mi_ref[...], jnp.max(
            jnp.where(live, score, MASK), axis=1, keepdims=True))
        li_ref[...] = (li_ref[...] * jnp.exp(mi_ref[...] - mi) + jnp.sum(
            jnp.where(live, jnp.exp(score - mi), 0.0), axis=1, keepdims=True))
        mi_ref[...] = mi
        n_ref[...] += jnp.sum(live.astype(jnp.float32), axis=1,
                              keepdims=True)
        h = q_ref.shape[3]
        for g in range(k_ref.shape[1]):
            heads = slice(g * group, (g + 1) * group)
            q = q_ref[0, heads].reshape(group * bq, h)
            s = (_dot(q, k_ref[0, g], _NT) * scale).reshape(group, bq, bk)
            s = jnp.where(live[None], s, MASK)
            m_old = m_ref[heads]
            m = jnp.maximum(m_old, jnp.max(s, axis=2, keepdims=True))
            # (a row with nothing live in this tile: exp(MASK - MASK) = 1)
            p = jnp.where(live[None], jnp.exp(s - m), 0.0)
            fade = jnp.exp(m_old - m)
            l_ref[heads] = l_ref[heads] * fade + jnp.sum(p, axis=2,
                                                         keepdims=True)
            pv = _dot(p.reshape(group * bq, bk).astype(v_ref.dtype),
                      v_ref[0, g], _NN)
            acc_ref[heads] = acc_ref[heads] * fade + pv.reshape(group, bq, -1)
            m_ref[heads] = m

    @pl.when(j == last)
    def _():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)
        lse_ref[0] = m_ref[...] + jnp.log(l_ref[...])
        lse_i_ref[0] = mi_ref[...] + jnp.log(li_ref[...])
        kept_ref[0] = n_ref[...]


def _specs(H: int, Hkv: int, J: int, h: int, c: int, bq: int, bk: int,
           rows_outer: bool):
    """The block specs the walks share, for a grid (b, query block, key
    block) (`rows_outer`) or (b, key block, query block). A tile that
    crosses nothing names the block already there, so nothing is fetched
    for it."""
    if rows_outer:
        qb = lambda bi, i, j: i
        kb = lambda bi, i, j: jnp.minimum(j, (i * bq + bq - 1) // bk)
    else:
        qb = lambda bi, j, i: jnp.maximum(i, (j * bk) // bq)
        kb = lambda bi, j, i: j
    of_q = lambda width, heads: pl.BlockSpec(
        (1, heads, bq, width), lambda *g: (g[0], 0, qb(*g), 0))
    of_k = lambda width: pl.BlockSpec(
        (1, Hkv, bk, width), lambda *g: (g[0], 0, kb(*g), 0))
    return {
        "q": of_q(h, H), "k": of_k(h), "v": of_k(h),
        "qi": of_q(c, J), "w": of_q(1, J), "row_h": of_q(1, H),
        "ki": pl.BlockSpec((1, bk, c), lambda *g: (g[0], kb(*g), 0)),
        "row": pl.BlockSpec((1, bq, 1), lambda *g: (g[0], qb(*g), 0)),
    }


def _shapes(q, k, q_idx, bq: int, bk: int):
    b, H, t, h = q.shape
    Hkv, (J, c) = k.shape[1], (q_idx.shape[1], q_idx.shape[3])
    assert t % bq == 0 and t % bk == 0, (t, bq, bk)
    return b, H, Hkv, t, h, J, c


def fwd_call(q, k, v, q_idx, k_idx, w, tau, cut, *, bq: int, bk: int,
             interpret: bool):
    """(o (b, H, t, h), lse (b, H, t, 1), the index scores' logsumexp over
    a row's set (b, t, 1), the keys a row kept (b, t, 1)), the last three
    float32."""
    b, H, Hkv, t, h, J, c = _shapes(q, k, q_idx, bq, bk)
    sp = _specs(H, Hkv, J, h, c, bq, bk, rows_outer=True)
    kernel = _kernel(_fwd_kernel, 3, q, interpret, scale=1.0 / math.sqrt(h),
                     bq=bq, bk=bk, group=H // Hkv)
    f32 = jnp.float32
    return pl.pallas_call(
        kernel,
        grid=(b, t // bq, t // bk),
        in_specs=[sp["q"], sp["k"], sp["v"], sp["qi"], sp["ki"], sp["w"],
                  sp["row"], sp["row"]],
        out_specs=[sp["q"], sp["row_h"], sp["row"], sp["row"]],
        out_shape=[_out_struct(q.shape, q.dtype, q),
                   _out_struct((b, H, t, 1), f32, q),
                   _out_struct((b, t, 1), f32, q),
                   _out_struct((b, t, 1), f32, q)],
        scratch_shapes=[pltpu.VMEM((H, bq, 1), f32), pltpu.VMEM((H, bq, 1), f32),
                        pltpu.VMEM((H, bq, h), f32), pltpu.VMEM((bq, 1), f32),
                        pltpu.VMEM((bq, 1), f32), pltpu.VMEM((bq, 1), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="dsa_flash_fwd",
    )(q, k, v, q_idx, k_idx, w, tau, cut)


def _p_ds(g: int, group: int, live, q_ref, k_ref, v_ref, do_ref, lse_ref,
          delta_ref, scale: float, bq: int, bk: int):
    """A key-value group's (q, do, p, ds) of one tile, the heads stacked:
    q, do (group bq, h), p and ds (group bq, bk) float32."""
    heads = slice(g * group, (g + 1) * group)
    h = q_ref.shape[3]
    q = q_ref[0, heads].reshape(group * bq, h)
    do = do_ref[0, heads].reshape(group * bq, h)
    s = (_dot(q, k_ref[0, g], _NT) * scale).reshape(group, bq, bk)
    p = jnp.where(live[None], jnp.exp(s - lse_ref[0, heads]), 0.0)
    dp = _dot(do, v_ref[0, g], _NT).reshape(group, bq, bk)
    ds = p * (dp - delta_ref[0, heads]) * scale
    flat = lambda a: a.reshape(group * bq, bk)
    return q, do, flat(p), flat(ds)


def _dq_kernel(ids, last, q_ref, k_ref, v_ref, qi_ref, ki_ref, w_ref, tau_ref, cut_ref,
               do_ref, lse_ref, delta_ref, dq_ref, acc_ref,
               *, scale: float, bq: int, bk: int, group: int):
    _, i, j = ids

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(_crosses(i, j, bq, bk))
    def _():
        *_, live = _tile_live(i, j, qi_ref, ki_ref, w_ref, tau_ref, cut_ref,
                              bq, bk)
        for g in range(k_ref.shape[1]):
            *_, ds = _p_ds(g, group, live, q_ref, k_ref, v_ref, do_ref,
                           lse_ref, delta_ref, scale, bq, bk)
            heads = slice(g * group, (g + 1) * group)
            acc_ref[heads] += _dot(ds.astype(k_ref.dtype), k_ref[0, g],
                                   _NN).reshape(group, bq, -1)

    @pl.when(j == last)
    def _():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(ids, last, q_ref, k_ref, v_ref, qi_ref, ki_ref, w_ref, tau_ref, cut_ref,
                do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                *, scale: float, bq: int, bk: int, group: int):
    _, j, i = ids

    @pl.when(i == 0)
    def _():
        dk_acc[...] = jnp.zeros(dk_acc.shape, jnp.float32)
        dv_acc[...] = jnp.zeros(dv_acc.shape, jnp.float32)

    @pl.when(_crosses(i, j, bq, bk))
    def _():
        *_, live = _tile_live(i, j, qi_ref, ki_ref, w_ref, tau_ref, cut_ref,
                              bq, bk)
        for g in range(k_ref.shape[1]):
            q, do, p, ds = _p_ds(g, group, live, q_ref, k_ref, v_ref, do_ref,
                                 lse_ref, delta_ref, scale, bq, bk)
            dv_acc[g] += _dot(p.astype(do.dtype), do, _TN)
            dk_acc[g] += _dot(ds.astype(q.dtype), q, _TN)

    @pl.when(i == last)
    def _():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def bwd_calls(q, k, v, q_idx, k_idx, w, tau, cut, do, lse, delta, *,
              bq: int, bk: int, interpret: bool):
    """(dq, dk, dv) of the attention over the chosen keys; `delta` (b, H,
    t, 1) float32 is `sum(do * o)` a row."""
    b, H, Hkv, t, h, J, c = _shapes(q, k, q_idx, bq, bk)
    common = dict(scale=1.0 / math.sqrt(h), bq=bq, bk=bk, group=H // Hkv)
    f32 = jnp.float32
    args = (q, k, v, q_idx, k_idx, w, tau, cut, do, lse, delta)

    def ins(sp):
        return [sp["q"], sp["k"], sp["v"], sp["qi"], sp["ki"], sp["w"],
                sp["row"], sp["row"], sp["q"], sp["row_h"], sp["row_h"]]

    sp = _specs(H, Hkv, J, h, c, bq, bk, rows_outer=True)
    dq = pl.pallas_call(
        _kernel(_dq_kernel, 3, q, interpret, **common),
        grid=(b, t // bq, t // bk),
        in_specs=ins(sp), out_specs=sp["q"],
        out_shape=_out_struct(q.shape, q.dtype, q),
        scratch_shapes=[pltpu.VMEM((H, bq, h), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="dsa_flash_bwd_dq",
    )(*args)
    sp = _specs(H, Hkv, J, h, c, bq, bk, rows_outer=False)
    dk, dv = pl.pallas_call(
        _kernel(_dkv_kernel, 3, q, interpret, **common),
        grid=(b, t // bk, t // bq),
        in_specs=ins(sp), out_specs=[sp["k"], sp["v"]],
        out_shape=[_out_struct(k.shape, k.dtype, q),
                   _out_struct(v.shape, v.dtype, q)],
        scratch_shapes=[pltpu.VMEM((Hkv, bk, h), f32),
                        pltpu.VMEM((Hkv, bk, h), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="dsa_flash_bwd_dkv",
    )(*args)
    return dq, dk, dv


# ------------------------------------------------------- the indexer's loss

def _loss_kernel(ids, last, q_ref, k_ref, lse_ref, qi_ref, ki_ref, w_ref, tau_ref,
                 cut_ref, lse_i_ref, kl_ref, ent_ref, dqi_ref, dw_ref,
                 dki_ref, kl_acc, ent_acc, dqi_acc, dw_acc,
                 *, scale: float, bq: int, bk: int, group: int):
    _, i, j = ids
    H, h = q_ref.shape[1], q_ref.shape[3]
    J, c = qi_ref.shape[1], qi_ref.shape[3]

    @pl.when((i == 0) & (j == 0))
    def _():
        dki_ref[...] = jnp.zeros(dki_ref.shape, jnp.float32)

    @pl.when(j == 0)
    def _():
        kl_acc[...] = jnp.zeros(kl_acc.shape, jnp.float32)
        ent_acc[...] = jnp.zeros(ent_acc.shape, jnp.float32)
        dqi_acc[...] = jnp.zeros(dqi_acc.shape, jnp.float32)
        dw_acc[...] = jnp.zeros(dw_acc.shape, jnp.float32)

    @pl.when(_crosses(i, j, bq, bk))
    def _():
        z, score, live = _tile_live(i, j, qi_ref, ki_ref, w_ref, tau_ref,
                                    cut_ref, bq, bk)
        # the heads' probabilities, summed: the indexer's target
        target = jnp.zeros((bq, bk), jnp.float32)
        for g in range(k_ref.shape[1]):
            heads = slice(g * group, (g + 1) * group)
            q = q_ref[0, heads].reshape(group * bq, h)
            s = (_dot(q, k_ref[0, g], _NT) * scale).reshape(group, bq, bk)
            target += jnp.sum(jnp.exp(s - lse_ref[0, heads]), axis=0)
        target = jnp.where(live, target * (1.0 / H), 0.0)
        log_pi = jnp.where(live, score - lse_i_ref[0], 0.0)
        pi = jnp.where(live, jnp.exp(log_pi), 0.0)
        kl_acc[...] += jnp.sum(
            jnp.where(target > 0.0,
                      target * (jnp.log(jnp.maximum(target, 1e-37))
                                - log_pi), 0.0), axis=1, keepdims=True)
        ent_acc[...] -= jnp.sum(pi * log_pi, axis=1, keepdims=True)
        # d KL / d I, back through the weighted ReLU
        d_score = (pi - target)[None]
        w = w_ref[0]                                     # (J, bq, 1)
        dw_acc[...] += jnp.sum(d_score * jnp.maximum(z, 0.0), axis=2,
                               keepdims=True)
        dz = jnp.where(z > 0.0, d_score * w, 0.0).reshape(J * bq, bk)
        dz = dz.astype(qi_ref.dtype)
        dqi_acc[...] += _dot(dz, ki_ref[0], _NN).reshape(J, bq, c)
        at = pl.multiple_of(j * bk, bk)
        dki_ref[0, pl.ds(at, bk), :] += _dot(
            dz, qi_ref[0].reshape(J * bq, c), _TN)

    @pl.when(j == last)
    def _():
        kl_ref[0] = kl_acc[...]
        ent_ref[0] = ent_acc[...]
        dqi_ref[0] = dqi_acc[...].astype(dqi_ref.dtype)
        dw_ref[0] = dw_acc[...]


def loss_call(q, k, lse, q_idx, k_idx, w, tau, cut, lse_i, *, bq: int,
              bk: int, interpret: bool):
    """(a row's KL (b, t, 1), the entropy of its softmax_S(I) (b, t, 1),
    and the gradients of the SUM of the rows' KL: d qI (b, J, t, c) in qI's
    dtype, d w (b, J, t, 1) float32, d kI (b, t, c) float32)."""
    b, H, Hkv, t, h, J, c = _shapes(q, k, q_idx, bq, bk)
    sp = _specs(H, Hkv, J, h, c, bq, bk, rows_outer=True)
    f32 = jnp.float32
    kernel = _kernel(_loss_kernel, 3, q, interpret, scale=1.0 / math.sqrt(h),
                     bq=bq, bk=bk, group=H // Hkv)
    return pl.pallas_call(
        kernel,
        grid=(b, t // bq, t // bk),
        in_specs=[sp["q"], sp["k"], sp["row_h"], sp["qi"], sp["ki"], sp["w"],
                  sp["row"], sp["row"], sp["row"]],
        out_specs=[sp["row"], sp["row"], sp["qi"], sp["w"],
                   pl.BlockSpec((1, t, c), lambda bi, i, j: (bi, 0, 0))],
        out_shape=[_out_struct((b, t, 1), f32, q),
                   _out_struct((b, t, 1), f32, q),
                   _out_struct(q_idx.shape, q_idx.dtype, q),
                   _out_struct((b, J, t, 1), f32, q),
                   _out_struct((b, t, c), f32, q)],
        scratch_shapes=[pltpu.VMEM((bq, 1), f32), pltpu.VMEM((bq, 1), f32),
                        pltpu.VMEM((J, bq, c), f32),
                        pltpu.VMEM((J, bq, 1), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="dsa_index_loss",
    )(q, k, lse, q_idx, k_idx, w, tau, cut, lse_i)


# ------------------------------------------------------------------ a probe

def _probe_kernel(ids, _, qi_ref, ki_ref, w_ref, tau_ref, cut_ref, score_ref,
                  live_ref, *, bq: int, bk: int):
    _, score, live = _tile_live(ids[1], ids[2], qi_ref,
                                ki_ref, w_ref, tau_ref, cut_ref, bq, bk)
    score_ref[0] = score
    live_ref[0] = live.astype(jnp.int8)


def probe_call(q_idx, k_idx, w, tau, cut, *, bq: int, bk: int,
               interpret: bool):
    """What the walks see, written out (a check's probe, in no step): (I
    (b, t, t) float32, every pair; is the pair in its row's set (b, t, t)
    int8)."""
    b, J, t, c = q_idx.shape
    of_q = lambda width: pl.BlockSpec((1, J, bq, width),
                                      lambda bi, i, j: (bi, 0, i, 0))
    row = pl.BlockSpec((1, bq, 1), lambda bi, i, j: (bi, i, 0))
    tile = pl.BlockSpec((1, bq, bk), lambda bi, i, j: (bi, i, j))
    return pl.pallas_call(
        _kernel(_probe_kernel, 3, q_idx, interpret, bq=bq, bk=bk),
        grid=(b, t // bq, t // bk),
        in_specs=[of_q(c), pl.BlockSpec((1, bk, c),
                                        lambda bi, i, j: (bi, j, 0)),
                  of_q(1), row, row],
        out_specs=[tile, tile],
        out_shape=[_out_struct((b, t, t), jnp.float32, q_idx),
                   _out_struct((b, t, t), jnp.int8, q_idx)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="dsa_probe",
    )(q_idx, k_idx, w, tau, cut)


def require_tpu(interpret: bool) -> None:
    _require_tpu("the selected-attention kernels", interpret)
