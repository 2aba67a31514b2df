"""Time the row movers of `parallel/moe.SharedRoutedFFN`'s sorted dispatch
alone on the attached TPU chip, at the expert cells' shapes (S = 16,384
tokens of d = 2048 in bf16; k choices, E routed experts of which H are held,
so a chunk of M = `chunk_rows(S k)` sorted rows: one mean share of the
pairs in cells 5, 6, 8 and 9 since PR 50, all of them in cell 7):

    python scripts/tune_moe_dispatch.py [--cells 5,6,7,8,9] [--check]
        [--forms rows|index|all]

prints, a cell, device milliseconds from a profiler capture (the union of
the ops' intervals a call, and the form's longest ops by name), and
nanoseconds a row of the chunk (M rows, whatever the form reads, so the
columns compare):

  - the plain forms: `x[tok]` (the gather; its source of 67 MB fits the
    chip's VMEM and XLA prefetches it there when the call stands alone),
    the same gather from the chunk's own rows (268 - 403 MB: from HBM),
    `y.at[tok].add(r)` (the row scatter-add, which is also what autodiff
    makes of the gather), and one element-wise pass over the chunk;
  - `take_rows` and `sum_rows` of the program, forward and transposed
    (`jax.vjp`), at every cell's shape whatever the layer's shape rule
    picks there (the cell's line says which), and `sum_rows` written six
    more ways: one
    (S, k, d) gather and a `reduce`, the same with k the major axis, the
    k columns walked in Python and by `lax.fori_loop`, with a real zero
    row and the gather told its indices are in bounds, and with a row
    laid out as one (16, 128) tile; the gather and the scatter-add over
    such tiles too;
  - the inverse permutation `pos` three ways (a second sort, a prefix sum
    over a one-hot of the keys, a scalar scatter of an iota) and the sort
    of the keys that the dispatch already makes;
  - the INDEX work over the S k pairs (`--forms index` times it alone, a
    minute a cell; ns an ELEMENT there is the ms over S k): the plain
    forms, each an XLA scalar gather or scatter-add (`bincount` of the
    keys and of the chosen experts, `take_along_axis(s, chosen)`,
    `w[order]`, and the transposes of those two), beside the program's
    (`count_keys`, `pick_scores`, `sort_pairs`, the cotangents by
    `jax.vjp`), `SharedRoutedFFN.index` whole against
    the plain forms whole, and the weights' cotangent taken back through
    `pos` (a scalar gather) for the price of what was not taken;
  - with `--check`, ON THE CHIP, `take_rows` / `sum_rows` and their
    cotangents against the plain forms (float32 to 1e-6, bf16 to a
    rounding of the float32 sum), the padding rows holding NaN; and the
    index forms against the plain ones, EXACTLY (the cotangents too: a
    selection and a permutation round nothing).

Each cell runs in a child process with a timeout (the parent touches no
JAX: a chip belongs to one process). The table behind `parallel/moe.py`'s
choice is PERF.md's (section 6, PRs 42 and 43, and PR 50 at chunks of a
share or less; TPU v5 lite).
"""

import argparse
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

# cell: (top_k, routed experts, experts held) of BENCHMARK.json's cells
CELLS = {5: (8, 256, 16), 6: (10, 512, 32), 7: (4, 32, 8), 8: (8, 128, 16),
         9: (8, 128, 16)}


def routing(s, k, experts, held, seed):
    """A random router's sorted dispatch, as `SharedRoutedFFN.apply` makes
    it: keys, order, tokens, `rows_here`, and the first chunk's `idx`."""
    import jax
    import jax.numpy as jnp

    from distributed_pytorch_from_scratch_tpu.parallel.moe import (
        SharedRoutedFFN)

    moe = SharedRoutedFFN(8, 8, experts, top_k=k, held=held)
    m = moe.chunk_rows(s * k)
    scores = jax.random.uniform(jax.random.key(seed), (s, experts))
    _, chosen = jax.lax.top_k(scores, k)
    key = jnp.where(chosen < held, chosen, held).reshape(-1)
    order = jnp.argsort(key, stable=True)
    rows_here = jnp.sum(key < held)
    tok = (order // k)[:m]
    pos = jnp.argsort(order).astype(jnp.int32).reshape(s, k)
    idx = jnp.where((pos < rows_here) & (pos < m), pos, m)
    valid = (jnp.arange(m) < rows_here)[:, None]
    return dict(m=m, key=key, order=order, tok=tok, idx=idx, valid=valid,
                n=jnp.minimum(rows_here, m), rows_here=int(rows_here))


def pos_ways(k, held):
    """Three ways to the inverse of `order = argsort(key)`."""
    import jax
    import jax.numpy as jnp

    def by_sort(key, order):
        return jnp.argsort(order).astype(jnp.int32)

    def by_prefix(key, order):
        hot = jax.nn.one_hot(key, held + 1, dtype=jnp.int32)
        before = jnp.cumsum(hot, axis=0) - hot          # earlier, same key
        starts = jnp.cumsum(jnp.sum(hot, axis=0)) - jnp.sum(hot, axis=0)
        return jnp.sum((before + starts) * hot, axis=1)

    def by_scatter(key, order):
        n = order.shape[0]
        return (jnp.zeros((n,), jnp.int32).at[order]
                .set(jnp.arange(n, dtype=jnp.int32), unique_indices=True))

    return {"pos by a second sort": by_sort,
            "pos by a prefix sum": by_prefix,
            "pos by a scalar scatter": by_scatter}


def index_forms(s, k, experts, held, seed):
    """{name: (fn, operands)} of the index work at a cell's shape, plain
    and the program's, and the pairs `check_index` holds equal."""
    import jax
    import jax.numpy as jnp

    from distributed_pytorch_from_scratch_tpu.parallel.moe import (
        SharedRoutedFFN, count_keys, pick_scores, sort_pairs)

    moe = SharedRoutedFFN(8, 8, experts, top_k=k, held=held)
    keys = jax.random.split(jax.random.key(seed + 2), 3)
    scores = jax.nn.softmax(jax.random.normal(keys[0], (s, experts)))
    _, chosen = jax.lax.top_k(scores, k)
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    key = jnp.where(chosen < held, chosen, held).reshape(-1)
    order = jnp.argsort(key, stable=True)
    pos = jnp.argsort(order).astype(jnp.int32)
    g_w, g_flat = (jax.random.normal(kk, a.shape)
                   for kk, a in zip(keys[1:], (w, key)))

    def plain_index(chosen, w):
        order = jnp.argsort(key, stable=True)
        return (order, w.reshape(-1)[order],
                jnp.cumsum(jnp.bincount(key, length=held + 1)[:held]),
                jnp.bincount(chosen.reshape(-1), length=experts))

    def program_index(chosen, w):
        order, w_sorted, ends, _, routed = moe.index(chosen, w, False)
        return order, w_sorted, ends, routed

    pull = lambda fn: (lambda g, a, *rest: jax.vjp(
        lambda a: fn(a, *rest), a)[1](g)[0])
    take = lambda s, chosen: jnp.take_along_axis(s, chosen, axis=-1)
    permute = lambda w, order: w[order]
    by_sort = lambda w, key: sort_pairs(key, w)[1]
    pairs = {       # plain form, the program's, operands
        "counts of the keys": (
            lambda key: jnp.bincount(key, length=held + 1),
            lambda key: count_keys(key, held + 1), (key,)),
        "counts of the chosen": (
            lambda c: jnp.bincount(c.reshape(-1), length=experts),
            lambda c: count_keys(c.reshape(-1), experts), (chosen,)),
        "s[chosen]": (take, pick_scores, (scores, chosen)),
        "cotangent of s[chosen]": (pull(take), pull(pick_scores),
                                   (g_w, scores, chosen)),
        "w[order]": (lambda w, key, order: permute(w, order),
                     lambda w, key, order: by_sort(w, key),
                     (w.reshape(-1), key, order)),
        "cotangent of w[order]": (
            lambda g, w, key, order: pull(permute)(g, w, order),
            lambda g, w, key, order: pull(by_sort)(g, w, key),
            (g_flat, w.reshape(-1), key, order)),
        "the index work whole": (plain_index, program_index, (chosen, w)),
        "cotangent of w through the index work": (
            lambda g, chosen, w: jax.vjp(
                lambda w: plain_index(chosen, w)[1], w)[1](g)[0],
            lambda g, chosen, w: jax.vjp(
                lambda w: program_index(chosen, w)[1], w)[1](g)[0],
            (g_flat, chosen, w)),
    }
    timed = {}
    for name, (plain, program, operands) in pairs.items():
        timed[f"{name}, plain"] = (plain, operands)
        timed[f"{name}, the program's"] = (program, operands)
    timed["cotangent of w[order] as g[pos] (a scalar gather)"] = (
        lambda g, pos: g[pos], (g_flat, pos))
    return timed, pairs


def check_index(pairs):
    """The program's index forms against the plain ones on this backend:
    every integer, every selection and every cotangent EXACTLY."""
    import jax
    import numpy as np

    for name, (plain, program, operands) in pairs.items():
        want = jax.tree.leaves(jax.jit(plain)(*operands))
        got = jax.tree.leaves(jax.jit(program)(*operands))
        assert len(want) == len(got)
        for a, b in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=name)
        print(f"  check {name}: equal", flush=True)


def sum_rows_ways():
    import jax
    import jax.numpy as jnp

    def columns(r, tok, idx):
        acc = jnp.zeros((idx.shape[0], r.shape[1]), jnp.float32)
        for j in range(idx.shape[1]):
            acc = acc + jnp.take(r, idx[:, j], axis=0, mode="fill",
                                 fill_value=0)
        return acc.astype(r.dtype)

    def fori(r, tok, idx):
        def body(j, acc):
            col = jax.lax.dynamic_index_in_dim(idx, j, 1, keepdims=False)
            return acc + jnp.take(r, col, axis=0, mode="fill", fill_value=0)
        acc = jnp.zeros((idx.shape[0], r.shape[1]), jnp.float32)
        return jax.lax.fori_loop(0, idx.shape[1], body, acc).astype(r.dtype)

    def in_bounds(r, tok, idx):
        # the zero row made real, so no index is out of bounds
        ext = jnp.concatenate([r, jnp.zeros((1, r.shape[1]), r.dtype)])
        picked = ext.at[idx].get(mode="promise_in_bounds")
        return jnp.sum(picked, axis=1, dtype=jnp.float32).astype(r.dtype)

    def whole(r, tok, idx):
        picked = jnp.take(r, idx, axis=0, mode="fill", fill_value=0)
        return jnp.sum(picked, axis=1, dtype=jnp.float32).astype(r.dtype)

    def k_major(r, tok, idx):
        # the sum over the MAJOR axis: k slabs of (S, d) added
        k, s = idx.shape[1], idx.shape[0]
        picked = jnp.take(r, idx.T.reshape(-1), axis=0, mode="fill",
                          fill_value=0).reshape(k, s, -1)
        return jnp.sum(picked, axis=0, dtype=jnp.float32).astype(r.dtype)

    def tiles(r, tok, idx):
        # a row as ONE (16, 128) tile, so a gathered row is contiguous
        k, s = idx.shape[1], idx.shape[0]
        picked = jnp.take(r.reshape(-1, 16, r.shape[1] // 16),
                          idx.T.reshape(-1), axis=0, mode="fill",
                          fill_value=0).reshape(k, s, 16, -1)
        return (jnp.sum(picked, axis=0, dtype=jnp.float32).astype(r.dtype)
                .reshape(s, -1))

    return {"sum_rows, one (S, k, d) gather and a reduce": whole,
            "sum_rows, columns in Python": columns,
            "sum_rows, columns by fori_loop": fori,
            "sum_rows, a real zero row": in_bounds,
            "sum_rows, k the major axis": k_major,
            "sum_rows, rows as (16, 128) tiles": tiles}


def child(args):
    sys.path.insert(0, ROOT)
    sys.path.insert(0, HERE)
    import jax
    import jax.numpy as jnp
    from tune_delta_rule import capture_ms

    from distributed_pytorch_from_scratch_tpu.parallel import moe
    from distributed_pytorch_from_scratch_tpu.runtime.compile_cache import (
        enable_compile_cache)

    enable_compile_cache()
    k, experts, held = CELLS[args.cell]
    s, d = args.s, args.d
    dtype = jnp.dtype(args.dtype)
    rt = routing(s, k, experts, held, args.seed)
    m, tok, idx, valid, n = (rt[z] for z in ("m", "tok", "idx", "valid", "n"))
    keys = jax.random.split(jax.random.key(args.seed + 1), 3)
    x = jax.random.normal(keys[0], (s, d)).astype(dtype)
    r = jnp.where(valid, jax.random.normal(keys[1], (m, d)), 0).astype(dtype)
    y = jnp.zeros((s, d), dtype)
    dev = jax.devices()[0]
    # what `SharedRoutedFFN.apply` picks at this shape
    by_rule = ("gathers" if s * k * moe.ROW_GATHER_NS
               <= m * moe.ROW_SCATTER_NS else "the scatter-add")
    head = dict(cell=args.cell, S=s, k=k, N=s * k, M=m, d=d,
                dtype=str(dtype), rows_here=rt["rows_here"], rule=by_rule,
                platform=dev.platform, device_kind=dev.device_kind)
    print(json.dumps(head), flush=True)

    plain_take = lambda x, tok: jnp.take(x, tok, axis=0)
    plain_add = lambda r, tok: jnp.zeros((s, d), r.dtype).at[tok].add(r)

    def vjp_of(fn, arg=0):
        """The transposed mover alone: the cotangent `g` pulled back to
        operand `arg` (a gather's or a sum's forward keeps nothing, so
        the compiler drops it)."""
        def pulled(g, *operands):
            at = lambda a: fn(*operands[:arg], a, *operands[arg + 1:])
            return jax.vjp(at, operands[arg])[1](g)[0]
        return pulled

    index, index_pairs = index_forms(s, k, experts, held, args.seed)
    timed = {
        "x[tok] (plain gather)": (plain_take, (x, tok)),
        "r[3 tok % M] (a gather from the chunk's rows)": (
            plain_take, (r, tok * 3 % m)),
        "y.at[tok].add(r) (plain scatter-add)": (plain_add, (r, tok)),
        "vjp of the plain gather": (vjp_of(plain_take), (r, x, tok)),
        "a pass over the chunk's rows (r * 2)": (lambda r: r * 2, (r,)),
        "take_rows": (moe.take_rows, (x, tok, idx, n)),
        "sum_rows": (moe.sum_rows, (y, r, tok, idx, n)),
        "vjp of take_rows": (vjp_of(moe.take_rows), (r, x, tok, idx, n)),
        "vjp of sum_rows": (vjp_of(moe.sum_rows, 1), (x, y, r, tok, idx, n)),
    }
    for name, fn in sum_rows_ways().items():
        timed[name] = (fn, (r, tok, idx))
    as_tiles = lambda a: a.reshape(a.shape[0], 16, -1)
    timed.update({
        "x[tok], rows as (16, 128) tiles": (
            lambda x, tok: jnp.take(as_tiles(x), tok, axis=0).reshape(m, d),
            (x, tok)),
        "y.at[tok].add(r), rows as (16, 128) tiles": (
            lambda r, tok: jnp.zeros((s, 16, d // 16), r.dtype).at[tok].add(
                as_tiles(r)).reshape(s, d), (r, tok)),
    })
    for name, fn in pos_ways(k, held).items():
        timed[name] = (fn, (rt["key"], rt["order"]))
    timed["argsort of the keys (stable)"] = (
        lambda key: jnp.argsort(key, stable=True), (rt["key"],))

    rows = {}

    if args.forms == "index":
        timed = {}
    if args.check and args.forms != "index":
        check(x, y, r, tok, idx, n)
    if args.check and args.forms != "rows":
        check_index(index_pairs)
    if args.forms != "rows":
        timed.update(index)
    for name, (fn, operands) in timed.items():
        ops = capture_ms(jax.jit(fn), *operands, iters=args.iters)
        ms = ops.pop("busy")
        if dev.platform != "tpu":       # a CPU run rehearses; it times nothing
            print(f"  {name:48s} not measured", flush=True)
            continue
        rows[name] = ms
        # an index form walks the S k pairs, a mover the chunk's M rows
        per, unit = ((s * k, "an element") if name in index
                     else (m, "a row of M"))
        longest = sorted(ops.items(), key=lambda kv: -kv[1])[:args.top]
        print(f"  {name:48s} {ms:8.3f} ms  {ms * 1e6 / per:7.1f} ns {unit}"
              f"   " + ", ".join(f"{k} {v:.3f}" for k, v in longest),
              flush=True)
    print(json.dumps({**head, "ms": rows}), flush=True)


def check(x, y, r, tok, idx, n):
    """The movers and their cotangents against the plain
    gather, select and row scatter-add, on this backend: float32 exactly
    (to 1e-6 of the largest entry), the compute dtype to one rounding of
    the float32 result. The padding rows hold NaN, going in and on the
    cotangent side, as a grouped product may leave them."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_pytorch_from_scratch_tpu.parallel.moe import (
        sum_rows, take_rows)

    held = (jnp.arange(tok.shape[0]) < n)[:, None]
    mine = lambda x, y, r: (take_rows(x, tok, idx, n),
                            sum_rows(y, r, tok, idx, n))
    plain = lambda x, y, r: (jnp.where(held, jnp.take(x, tok, axis=0), 0),
                             y.at[tok].add(jnp.where(held, r, 0)))
    f32 = lambda a: a.astype(jnp.float32)
    x32, y32 = f32(x), f32(x)[::-1] * 0.5
    r32 = jnp.where(held, f32(r), jnp.nan)
    gr, gy = jnp.where(held, f32(r)[::-1] + 1.0, jnp.nan), x32[::-1] - 1.0
    want, pull = jax.vjp(plain, x32, y32, r32)
    want = (*want, *pull((gr, gy)))
    for dtype, tol in ((jnp.float32, 1e-6), (x.dtype, 2.0 ** -7)):
        cast = lambda *a: tuple(z.astype(dtype) for z in a)
        got, pull = jax.vjp(mine, *cast(x32, y32, r32))
        got = (*got, *pull(cast(gr, gy)))
        for name, a, b in zip(
                ("take_rows", "sum_rows", "d x", "d y", "d r"), got, want):
            err = float(jnp.max(jnp.abs(f32(a) - b)))
            scale = float(jnp.max(jnp.abs(b)))
            print(f"  check {jnp.dtype(dtype).name:9s} {name:10s} max error "
                  f"{err:.3e} of {scale:.3e}", flush=True)
            np.testing.assert_array_less(err, tol * scale + 1e-30)


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default="7,8,5,6",
                    help="BENCHMARK.json's expert cells, by number")
    ap.add_argument("--s", type=int, default=16384, help="tokens a step")
    ap.add_argument("--d", type=int, default=2048)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--top", type=int, default=4,
                    help="a form's longest ops to name beside its time")
    ap.add_argument("--check", action="store_true",
                    help="hold the movers to the plain forms on this backend")
    ap.add_argument("--forms", default="all",
                    choices=("rows", "index", "all"),
                    help="the row movers, the index work, or both")
    ap.add_argument("--timeout", type=int, default=600,
                    help="seconds a cell's child may take")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "tune_moe_dispatch.jsonl"))
    ap.add_argument("--cell", type=int, default=None,
                    help="(the child's) the one cell to time in this process")
    return ap.parse_args(argv)


def main():
    args = parse_args()
    if args.cell is not None:
        return child(args)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    failed = []
    with open(args.out, "a") as out:
        for cell in (int(c) for c in args.cells.split(",")):
            print(f"cell {cell}", flush=True)
            cmd = [sys.executable, os.path.abspath(__file__), "--cell",
                   str(cell), *sys.argv[1:]]
            try:
                done = subprocess.run(cmd, timeout=args.timeout, text=True,
                                      stdout=subprocess.PIPE)
            except subprocess.TimeoutExpired as e:
                print(f"  cell {cell}: no result in {args.timeout} s\n"
                      f"{e.stdout or ''}", flush=True)
                failed.append(cell)
                continue
            print(done.stdout, end="", flush=True)
            if done.returncode:
                failed.append(cell)
            else:
                out.write(done.stdout.strip().splitlines()[-1] + "\n")
    if failed:
        sys.exit(f"cells {failed} gave no table")


if __name__ == "__main__":
    main()
