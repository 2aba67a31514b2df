"""Attention over a live set that is DATA: the five Mosaic kernels behind
`ops/index_select.selected_attention` (the mathematics and the XLA oracle
are there).

A row t of a sequence keeps the `top_k` keys s <= t of largest index score
`I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])` (float32; ties to the
earlier key). The score is T x T a sequence and is never written to HBM:
the two kernels that need its NUMBERS make a tile of it from `qI` (b, J, t,
c), `kI` (b, t, c) and `w` (b, J, t, 1) by the one function `_index_tile`
(the selection, and the indexer's loss), and the SET travels from the
kernel that chooses it to the walks that attend under it as ONE BIT A
(row, key) PAIR: `bits` (b, planes, t, bk) int32, the pair (t, s) in word
`[s // (32 bk), t, s mod bk]` at bit `(s // bk) mod 32`, so that the key
tile j of a walk reads its mask out of one (bq, bk) block of words with one
AND (`_tile_bit`); `planes` is `bit_planes(t, bk)`, one at every length up
to 32 key tiles (t^2 / 8 bytes a sequence: 32 MiB at 16,384).

* `dsa_select`: a grid step holds one block of query rows' scores against
  every key up to the block's last row, as order-preserving int32 keys in
  VMEM (`_sortable`), and finds `tau`, the row's `top_k`-th largest score,
  by building its bits from the top (32 passes of compare-and-count over
  the scratch, no sort), then how many keys are tied at tau and, only where
  a row's ties straddle the budget, `cut`, the last key index kept among
  the keys that score tau, by bisection on the key index. `(s <= t) and (I
  > tau or (I == tau and s <= cut))` (`_live`) is then the row's set,
  exactly, and one more pass over the scratch WRITES it (`bits`, the same
  rule on the keys it compared) and takes what only the set's own scores
  give: the logsumexp of I over the set and the set's size;
* `dsa_flash_fwd`: the flash walk of the whole triangle, ALL the query
  heads of a row block a grid step (a tile's mask is read once, turned
  once and shared by the heads), online softmax under the mask with the
  score tile TURNED: a head's scores are `k q^T`, (bk, bq), a key a
  sublane and a query row a lane, so the row maximum and the row sum run
  down the tile as element operations (a row a sublane made them 8192
  cross-lane reductions a tile, and the exponents waited for them: 31.3 ms
  a layer at the cell's shape, 15.5 turned); m, l (scratch (H, bq)), fade
  and lse are lane-dense rows; `p v` is `v^T p` into an accumulator kept
  turned (scratch (H, h, bq)) and turned back once a row block (with the
  accumulator the usual way up and `fade` turned into a column a tile it
  read 18.2 ms); a head's scores are asked for `AHEAD` heads before its
  exponents, so the matrix unit has products to make meanwhile; o and
  each head's lse, (b, H, t);
* `dsa_flash_bwd_dq`, `dsa_flash_bwd_dkv`: the standard two backward walks
  under the same bits (the choice carries no gradient);
* `dsa_index_loss`: the indexer's own loss in one more walk, the second
  and last to make the index tile (it needs z and I themselves): a tile's
  head-summed attention probabilities P (from q, k and the forward's lse),
  the KL of P from softmax_S(I), its gradient `softmax_S(I) - P` pushed back
  through the ReLU into qI, kI and w, and the entropy of softmax_S(I); its
  mask is `_live` of its own tile under the selection's (tau, cut), the
  same bits (tests/test_dsa_moe.py holds them equal pair for pair).

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
# heads whose scores the forward walk asks for before a head's exponents
AHEAD = 3

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


# key tiles a plane of the set's bits holds: a bit of an int32 word each
WORD = 32


def bit_planes(t: int, bk: int) -> int:
    """Planes of (t, bk) int32 words that hold a sequence's set."""
    return -(-(t // bk) // WORD)


def _tile_bit(j):
    """The word whose one bit is key tile `j`'s, in its plane."""
    return jnp.int32(1) << (j % WORD)


def _flip(bits):
    """A float32's bits <-> the int32 whose signed order is the floats':
    the bits below the sign turned where the sign is set (an involution)."""
    return bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))


def _sortable(x):
    return _flip(lax.bitcast_convert_type(x, jnp.int32))


def _scores(keys):
    """`_sortable`'s inverse: the float32 scores of int32 keys."""
    return lax.bitcast_convert_type(_flip(keys), jnp.float32)


# ---------------------------------------------------------------- selection

def _select_kernel(ids, _, k_ref, qi_ref, ki_ref, w_ref, tau_ref, cut_ref, ties_ref,
                   bits_ref, lse_i_ref, kept_ref, keys_ref,
                   *, bq: int, bk: int, chunk: int, t: int):
    i = ids[1]
    top_k = k_ref[0, 0]
    rows = i * bq + lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    chunks = (i * bq + bq + chunk - 1) // chunk     # up to the last row
    J, c = qi_ref.shape[1], qi_ref.shape[3]
    qi = qi_ref[0].reshape(J * bq, c)
    w = w_ref[0].reshape(J * bq, 1)

    def write(n, top):
        at = pl.multiple_of(n * bk, bk)
        _, score = _index_tile(qi, ki_ref[0, pl.ds(at, bk), :], w, bq)
        cols = at + lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        keys = jnp.where(cols <= rows, _sortable(score), jnp.int32(INT_MIN))
        keys_ref[:, pl.ds(at, bk)] = keys
        return jnp.maximum(top, jnp.max(keys, axis=1, keepdims=True))

    # (the row's largest key beside the writing: it is in the row's set)
    top = lax.fori_loop(0, chunks * (chunk // bk), write,
                        jnp.full((bq, 1), INT_MIN, jnp.int32))

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

    tau_ref[0] = jnp.where(short, -jnp.inf, _scores(tau_key))

    # The set itself, a key tile of the walks at a time: a bit a pair into
    # the tile's place of the row block's words, and, over the set, the sum
    # of exp(I - the row's largest) and the count.
    # (a key after its row is INT_MIN in the scratch, under every score: a
    # short row's threshold is INT_MIN itself, with no key kept AT it)
    edge = jnp.where(short, jnp.int32(INT_MIN), tau_key)
    cut = jnp.where(short, -1, cut_ref[0])
    largest = _scores(top)
    bits_ref[...] = jnp.zeros(bits_ref.shape, jnp.int32)

    def pack(n, sums):
        total, size = sums
        at = pl.multiple_of(n * bk, bk)
        keys = keys_ref[:, pl.ds(at, bk)]
        cols = at + lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        live = (keys > edge) | ((keys == edge) & (cols <= cut))
        plane = n // WORD
        bits_ref[0, plane] = bits_ref[0, plane] | jnp.where(
            live, _tile_bit(n), jnp.int32(0))
        total = total + jnp.sum(
            jnp.where(live, jnp.exp(_scores(keys) - largest), 0.0), axis=1,
            keepdims=True)
        size = size + jnp.sum(live.astype(jnp.float32), axis=1,
                              keepdims=True)
        return total, size

    zero = jnp.zeros((bq, 1), jnp.float32)
    total, size = lax.fori_loop(0, (i * bq + bq - 1) // bk + 1, pack,
                                (zero, zero))
    lse_i_ref[0] = largest + jnp.log(total)
    kept_ref[0] = size


def select_call(q_idx, k_idx, w, top_k: int, *, bq: int, bk: int,
                interpret: bool):
    """(tau (b, t, 1) float32, cut (b, t, 1) int32, tied (b, t, 1) float32:
    is the row's threshold shared by keys on both sides of the budget; the
    rows' sets `bits` (b, planes, t, bk) int32 (module docstring); the
    index scores' logsumexp over a row's set (b, t, 1) and the keys the
    row kept (b, t, 1), float32)."""
    b, J, t, c = q_idx.shape
    assert t % bq == 0 and t % bk == 0, (t, bq, bk)
    # the score is made a tile of the loss walk's own shape (the same
    # products, bit for bit); a counting pass reads a few tiles' keys at once
    tiles = max(n for n in range(1, max(COUNT_CHUNK // bk, 1) + 1)
                if (t // bk) % n == 0)
    kernel = _kernel(_select_kernel, 2, q_idx, interpret, bq=bq, bk=bk,
                     chunk=bk * tiles, t=t)
    planes = bit_planes(t, bk)
    row = pl.BlockSpec((1, bq, 1), lambda bi, i: (bi, i, 0))
    column = lambda dtype: _out_struct((b, t, 1), dtype, q_idx)
    f32 = jnp.float32
    return pl.pallas_call(
        kernel,
        grid=(b, t // bq),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, J, bq, c), lambda bi, i: (bi, 0, i, 0)),
            pl.BlockSpec((1, t, c), lambda bi, i: (bi, 0, 0)),
            pl.BlockSpec((1, J, bq, 1), lambda bi, i: (bi, 0, i, 0)),
        ],
        out_specs=[row, row, row,
                   pl.BlockSpec((1, planes, bq, bk),
                                lambda bi, i: (bi, 0, i, 0)),
                   row, row],
        out_shape=[column(f32), column(jnp.int32), column(f32),
                   _out_struct((b, planes, t, bk), jnp.int32, q_idx),
                   column(f32), column(f32)],
        scratch_shapes=[pltpu.VMEM((bq, t), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="dsa_select",
    )(jnp.full((1, 1), top_k, jnp.int32), q_idx, k_idx, w)


# ------------------------------------------------------------ the flash walks

def _tile_words(bits_ref, j):
    """(bq, bk) int32, not 0 where the pair of key tile `j` is in its row's
    set: the row block's words of that tile's plane under the tile's bit."""
    return bits_ref[0, 0] & _tile_bit(j)


def _tile_set(bits_ref, j):
    """(bq, bk) bool: the pairs of key tile `j` that are in their rows'
    sets."""
    return _tile_words(bits_ref, j) != 0


def _crosses(i, j, bq: int, bk: int):
    """Does tile (i, j) hold a key at or before one of its rows."""
    return j * bk <= i * bq + bq - 1


def _fwd_kernel(ids, last, q_ref, k_ref, v_ref, bits_ref, o_ref, lse_ref,
                m_ref, l_ref, acc_ref,
                *, scale: float, bq: int, bk: int, group: int):
    """m_ref, l_ref (H, bq) and acc_ref (H, h, bq): a tile is TURNED, a key
    a sublane and a query row a lane (module docstring)."""
    _, i, j = ids
    H = q_ref.shape[1]

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, MASK, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(_crosses(i, j, bq, bk))
    def _():
        # the tile's set, turned once and shared by the heads
        live = _tile_words(bits_ref, j).T != 0              # (bk, bq)

        def scores(u):
            return jnp.where(live, _dot(k_ref[0, u // group], q_ref[0, u],
                                        _NT) * scale, MASK)

        # (a head's scores are asked for `AHEAD` heads before its exponents
        # are taken: Mosaic keeps the matrix unit's calls in this order, and
        # the unit then has products to make while the vector unit works)
        ahead = [scores(u) for u in range(min(AHEAD, H))]
        for u in range(H):
            s = ahead.pop(0)
            if u + AHEAD < H:
                ahead.append(scores(u + AHEAD))
            row = slice(u, u + 1)
            m_old = m_ref[row]
            m = jnp.maximum(m_old, jnp.max(s, axis=0, keepdims=True))
            # (a row with nothing live yet has m = MASK and exp(MASK - MASK)
            # = 1: it takes its exponents from 0, and they are exp(MASK) = 0)
            p = jnp.exp(s - jnp.where(m == MASK, 0.0, m))
            fade = jnp.exp(m_old - m)
            l_ref[row] = l_ref[row] * fade + jnp.sum(p, axis=0, keepdims=True)
            acc_ref[u] = acc_ref[u] * fade + _dot(
                v_ref[0, u // group], p.astype(v_ref.dtype), _TN)
            m_ref[row] = m

    @pl.when(j == last)
    def _():
        for u in range(H):
            o_ref[0, u] = (acc_ref[u] / l_ref[u:u + 1]).T.astype(o_ref.dtype)
        lse_ref[0] = m_ref[...] + jnp.log(l_ref[...])


def _specs(H: int, Hkv: int, h: int, bq: int, bk: int, rows_outer: bool,
           index=None):
    """The block specs the walks share, for a grid (b, query block, key
    block) (`rows_outer`) or (b, key block, query block); with `index` (J,
    c), the index tensors' too. A tile that crosses nothing names the
    blocks already there, so nothing is fetched for it; the set's words are
    a row block's of the key tile's plane, which a walk with the rows
    outside changes only from one plane to the next."""
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
    sp = {
        "q": of_q(h, H), "k": of_k(h), "v": of_k(h), "row_h": of_q(1, H),
        "bits": pl.BlockSpec((1, 1, bq, bk), lambda *g: (
            g[0], kb(*g) // WORD, qb(*g), 0)),
        "row": pl.BlockSpec((1, bq, 1), lambda *g: (g[0], qb(*g), 0)),
    }
    if index is not None:
        J, c = index
        sp.update(qi=of_q(c, J), w=of_q(1, J), ki=pl.BlockSpec(
            (1, bk, c), lambda *g: (g[0], kb(*g), 0)))
    return sp


def _shapes(q, k, bq: int, bk: int):
    b, H, t, h = q.shape
    assert t % bq == 0 and t % bk == 0, (t, bq, bk)
    return b, H, k.shape[1], t, h


def fwd_call(q, k, v, bits, *, bq: int, bk: int, interpret: bool):
    """(o (b, H, t, h), lse (b, H, t) float32) of the attention over the
    rows' sets `bits` (`select_call`'s)."""
    b, H, Hkv, t, h = _shapes(q, k, bq, bk)
    sp = _specs(H, Hkv, h, bq, bk, rows_outer=True)
    kernel = _kernel(_fwd_kernel, 3, q, interpret, scale=1.0 / math.sqrt(h),
                     bq=bq, bk=bk, group=H // Hkv)
    f32 = jnp.float32
    row = pltpu.VMEM((H, bq), f32)
    return pl.pallas_call(
        kernel,
        grid=(b, t // bq, t // bk),
        in_specs=[sp["q"], sp["k"], sp["v"], sp["bits"]],
        out_specs=[sp["q"], pl.BlockSpec((1, H, bq),
                                         lambda bi, i, j: (bi, 0, i))],
        out_shape=[_out_struct(q.shape, q.dtype, q),
                   _out_struct((b, H, t), f32, q)],
        scratch_shapes=[row, row, pltpu.VMEM((H, h, bq), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="dsa_flash_fwd",
    )(q, k, v, bits)


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


def _dq_kernel(ids, last, q_ref, k_ref, v_ref, bits_ref, do_ref, lse_ref,
               delta_ref, dq_ref, acc_ref,
               *, scale: float, bq: int, bk: int, group: int):
    _, i, j = ids

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(_crosses(i, j, bq, bk))
    def _():
        live = _tile_set(bits_ref, j)
        for g in range(k_ref.shape[1]):
            *_, ds = _p_ds(g, group, live, q_ref, k_ref, v_ref, do_ref,
                           lse_ref, delta_ref, scale, bq, bk)
            heads = slice(g * group, (g + 1) * group)
            acc_ref[heads] += _dot(ds.astype(k_ref.dtype), k_ref[0, g],
                                   _NN).reshape(group, bq, -1)

    @pl.when(j == last)
    def _():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(ids, last, q_ref, k_ref, v_ref, bits_ref, do_ref, lse_ref,
                delta_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                *, scale: float, bq: int, bk: int, group: int):
    _, j, i = ids

    @pl.when(i == 0)
    def _():
        dk_acc[...] = jnp.zeros(dk_acc.shape, jnp.float32)
        dv_acc[...] = jnp.zeros(dv_acc.shape, jnp.float32)

    @pl.when(_crosses(i, j, bq, bk))
    def _():
        live = _tile_set(bits_ref, j)
        for g in range(k_ref.shape[1]):
            q, do, p, ds = _p_ds(g, group, live, q_ref, k_ref, v_ref, do_ref,
                                 lse_ref, delta_ref, scale, bq, bk)
            dv_acc[g] += _dot(p.astype(do.dtype), do, _TN)
            dk_acc[g] += _dot(ds.astype(q.dtype), q, _TN)

    @pl.when(i == last)
    def _():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def bwd_calls(q, k, v, bits, do, lse, delta, *, bq: int, bk: int,
              interpret: bool):
    """(dq, dk, dv) of the attention over the rows' sets `bits`; `delta`
    (b, H, t, 1) float32 is `sum(do * o)` a row."""
    b, H, Hkv, t, h = _shapes(q, k, bq, bk)
    common = dict(scale=1.0 / math.sqrt(h), bq=bq, bk=bk, group=H // Hkv)
    f32 = jnp.float32
    args = (q, k, v, bits, do, lse, delta)

    def ins(sp):
        return [sp["q"], sp["k"], sp["v"], sp["bits"], sp["q"], sp["row_h"],
                sp["row_h"]]

    sp = _specs(H, Hkv, h, bq, bk, rows_outer=True)
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
    sp = _specs(H, Hkv, h, bq, bk, rows_outer=False)
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
        z, score = _index_tile(qi_ref[0].reshape(J * bq, c), ki_ref[0],
                               w_ref[0].reshape(J * bq, 1), bq)
        # (the selection's rule on the same numbers: the walks' bits, which
        # read 0.5 ms a layer slower here than these compares, PR 73)
        rows = i * bq + lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
        cols = j * bk + lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        live = _live(score, tau_ref[0], cut_ref[0], rows, cols)
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
    dtype, d w (b, J, t, 1) float32, d kI (b, t, c) float32), under the
    selection's (tau, cut) and with its `lse_i`."""
    b, H, Hkv, t, h = _shapes(q, k, bq, bk)
    J, c = q_idx.shape[1], q_idx.shape[3]
    sp = _specs(H, Hkv, h, bq, bk, rows_outer=True, index=(J, c))
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

def _probe_kernel(ids, _, qi_ref, ki_ref, w_ref, bits_ref, score_ref,
                  live_ref, *, bq: int):
    J, c = qi_ref.shape[1], qi_ref.shape[3]
    _, score_ref[0] = _index_tile(qi_ref[0].reshape(J * bq, c), ki_ref[0],
                                  w_ref[0].reshape(J * bq, 1), bq)
    live_ref[0] = _tile_set(bits_ref, ids[2]).astype(jnp.int8)


def probe_call(q_idx, k_idx, w, bits, *, bq: int, bk: int, interpret: bool):
    """What the kernels see, written out (a check's probe, in no step): (I
    (b, t, t) float32, every pair, as `_index_tile` makes it; is the pair in
    its row's set (b, t, t) int8, as the walks read it out of `bits`)."""
    b, J, t, c = q_idx.shape
    of_q = lambda width: pl.BlockSpec((1, J, bq, width),
                                      lambda bi, i, j: (bi, 0, i, 0))
    tile = pl.BlockSpec((1, bq, bk), lambda bi, i, j: (bi, i, j))
    return pl.pallas_call(
        _kernel(_probe_kernel, 3, q_idx, interpret, bq=bq),
        grid=(b, t // bq, t // bk),
        in_specs=[of_q(c), pl.BlockSpec((1, bk, c),
                                        lambda bi, i, j: (bi, j, 0)),
                  of_q(1),
                  pl.BlockSpec((1, 1, bq, bk),
                               lambda bi, i, j: (bi, j // WORD, i, 0))],
        out_specs=[tile, tile],
        out_shape=[_out_struct((b, t, t), jnp.float32, q_idx),
                   _out_struct((b, t, t), jnp.int8, q_idx)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="dsa_probe",
    )(q_idx, k_idx, w, bits)


def require_tpu(interpret: bool, bq: int = None) -> None:
    """The kernels need a TPU or the interpreter; the forward walk's query
    block `bq` rides the lanes, so Mosaic takes whole lane tiles of it."""
    _require_tpu("the selected-attention kernels", interpret)
    if not interpret and bq is not None and bq % 128:
        raise ValueError(
            f"the selected-attention forward walk lays a block's query rows "
            f"along the lanes: on a TPU the sequence must be a multiple of "
            f"128 rows (its query block came out as {bq}); the Pallas "
            f"interpreter and the XLA text take any length")
