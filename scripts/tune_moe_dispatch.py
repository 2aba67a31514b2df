"""Time the row movers of `parallel/moe.SharedRoutedFFN`'s sorted dispatch
alone on the attached TPU chip, at the expert cells' shapes (S = 16,384
tokens of d = 2048 in bf16 unless `SHAPES` says otherwise; k choices, E
routed experts of which H are held, so a chunk of M = `chunk_rows(S k)`
sorted rows: one mean share of the pairs at every held share since PR 71,
all of them where every expert is held):

    python scripts/tune_moe_dispatch.py [--cells 10,7,8,5,6] [--check]
        [--forms rows|index|all] [--only held] [--share 0.5]

prints, a cell, device milliseconds from a profiler capture (the union of
the ops' intervals a call, and the form's longest ops by name), and
nanoseconds a row of the chunk (M rows, whatever the form reads, so the
columns compare):

  - the plain forms: `x[tok]` (the gather; its source of 67 MB fits the
    chip's VMEM and XLA prefetches it there when the call stands alone),
    the same gather from the chunk's own rows (268 - 403 MB: from HBM),
    `y.at[tok].add(r)` (the row scatter-add, which is also what autodiff
    makes of the gather), one element-wise pass over the chunk, and the
    gather and the scatter-add with a row laid out as one (16, 128) tile;
  - the movers of a chunk, `take_held` and `sum_held` (token-sorted rows
    summed by block one-hot products, PR 65), the latter beside the row
    scatter-add it replaced, at other blocks and windows, and its sort and
    its row gather alone (`--only held` times these and nothing else;
    cells 11, 12 and 13 are the short chunks of 2,048, 512 and 1,536 rows;
    `--share` holds that part of a cell's routed experts in place of the
    cell's own: 0.5 a half, 1 every expert, whose one chunk is all S k
    pairs and whose blocks of tokens own k windows each);
  - the sort of the keys that the dispatch makes;
  - the INDEX work over the S k pairs (`--forms index` times it alone, a
    minute a cell; ns an ELEMENT there is the ms over S k): the plain
    forms, each an XLA scalar gather or scatter-add (`bincount` of the
    keys and of the chosen experts, `take_along_axis(s, chosen)`,
    `w[order]`, and the transposes of those two), beside the program's
    (`count_keys`, `pick_scores`, `sort_pairs`, the cotangents by
    `jax.vjp`), and `SharedRoutedFFN.index` whole against
    the plain forms whole;
  - with `--check`, ON THE CHIP, `take_held` / `sum_held` against the
    plain gather, select and row scatter-add (float32 to 1e-6, bf16 to a
    rounding of the float32 sum), the padding rows holding NaN; and the
    index forms against the plain ones, EXACTLY (the cotangents too: a
    selection and a permutation round nothing).

Each cell runs in a child process with a timeout (the parent touches no
JAX: a chip belongs to one process). The tables are PERF.md's (section 6,
PRs 42, 43, 50, 65 and 71; TPU v5 lite). Until PR 71 a job that held a
sixth of the experts or more moved the rows of ONE chunk of all its pairs
by gathers through the sort's inverse (`take_rows` / `sum_rows`); the forms
that timed them, and `sum_rows` written six more ways, went with them.
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
         9: (8, 128, 16), 10: (6, 64, 16), 11: (4, 64, 8), 12: (8, 512, 8),
         13: (22, 512, 8)}
# cell: (tokens a step, row width) where they are not --s and --d: cell
# 10's rows of 2560, and the short chunks of 2,048, 512 and 1,536 rows
# (cell 13's rows are its latent's)
SHAPES = {10: (16384, 2560), 11: (4096, 3584), 12: (4096, 2560),
          13: (4096, 1024)}


def routing(s, k, experts, held, seed):
    """A random router's sorted dispatch, as `SharedRoutedFFN.apply` makes
    it: keys, order, the first chunk's tokens and held rows, `rows_here`."""
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
    valid = (jnp.arange(m) < rows_here)[:, None]
    return dict(m=m, key=key, order=order, tok=tok, valid=valid,
                rows_here=int(rows_here))


def add_held(y, r, tok, valid):
    """The row scatter-add `parallel/moe.py` moved a chunk's rows back by
    until PR 65: a padding row is aimed past `y`'s last row,
    where the scatter drops it."""
    import jax.numpy as jnp

    at = jnp.where(valid[:, 0], tok, y.shape[0])
    return y.at[at].add(r, mode="drop")


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
    g_w, g_flat = (jax.random.normal(kk, a.shape)
                   for kk, a in zip(keys[1:], (w, key)))

    def plain_index(chosen, w):
        order = jnp.argsort(key, stable=True)
        return (order, w.reshape(-1)[order],
                jnp.cumsum(jnp.bincount(key, length=held + 1)[:held]),
                jnp.bincount(chosen.reshape(-1), length=experts))

    program_index = moe.index

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
    if args.share is not None:
        held = max(1, round(args.share * experts))
    s, d = SHAPES.get(args.cell, (args.s, args.d))
    dtype = jnp.dtype(args.dtype)
    rt = routing(s, k, experts, held, args.seed)
    m, tok, valid = (rt[z] for z in ("m", "tok", "valid"))
    keys = jax.random.split(jax.random.key(args.seed + 1), 3)
    x = jax.random.normal(keys[0], (s, d)).astype(dtype)
    r = jnp.where(valid, jax.random.normal(keys[1], (m, d)), 0).astype(dtype)
    dev = jax.devices()[0]
    head = dict(cell=args.cell, S=s, k=k, N=s * k, M=m, d=d, held=held,
                experts=experts, dtype=str(dtype), rows_here=rt["rows_here"],
                live_chunks=-(-rt["rows_here"] // m),
                platform=dev.platform, device_kind=dev.device_kind)
    print(json.dumps(head), flush=True)

    plain_take = lambda x, tok: jnp.take(x, tok, axis=0)
    plain_add = lambda r, tok: jnp.zeros((s, d), r.dtype).at[tok].add(r)

    def sum_held_at(block, window, kernel=True):
        """`sum_held` traced with another block of tokens and window of
        rows than the module's (it reads both where it is traced), or as
        XLA's `scan` where the TPU would take the kernel."""
        def at(y, r, tok, valid):
            kept = moe.SUM_BLOCK, moe.SUM_WINDOW, moe.sum_held_kernel.fits
            moe.SUM_BLOCK, moe.SUM_WINDOW = block, window
            if not kernel:
                moe.sum_held_kernel.fits = lambda *shape: False
            try:
                return moe.sum_held(y, r, tok, valid)[0]
            finally:
                (moe.SUM_BLOCK, moe.SUM_WINDOW,
                 moe.sum_held_kernel.fits) = kept
        return at

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
        # a chunk's rows in, and back onto the sums: the row scatter-add
        # the layer had until PR 65 (a padding row aimed past the last
        # token and dropped), `sum_held` whole, at other blocks and
        # windows, and its sort and its row gather alone
        "take_held": (moe.take_held, (x, tok, valid)),
        "add_held (y.at[tok].add(r, mode=drop), until PR 65)": (
            add_held, (x, r, tok, valid)),
        "sum_held": (lambda *a: moe.sum_held(*a)[0], (x, r, tok, valid)),
        "sum_held, XLA's scan over the blocks (the other backends' text)": (
            sum_held_at(moe.SUM_BLOCK, moe.SUM_WINDOW, kernel=False),
            (x, r, tok, valid)),
        "sum_held, the sort of the chunk's tokens alone": (
            lambda tok: jax.lax.sort(
                (tok, jax.lax.iota(jnp.int32, m)), num_keys=1,
                is_stable=True), (tok,)),
        "sum_held, r[perm] alone": (
            lambda r, perm: jnp.take(r, perm, axis=0, mode="clip"),
            (r, jnp.argsort(tok))),
    }
    for block, window in ((128, 512), (256, 1024), (512, 512), (512, 1024)):
        timed[f"sum_held, blocks of {block} and windows of {window}"] = (
            sum_held_at(block, window), (x, r, tok, valid))
    as_tiles = lambda a: a.reshape(a.shape[0], 16, -1)
    timed.update({
        "x[tok], rows as (16, 128) tiles": (
            lambda x, tok: jnp.take(as_tiles(x), tok, axis=0).reshape(m, d),
            (x, tok)),
        "y.at[tok].add(r), rows as (16, 128) tiles": (
            lambda r, tok: jnp.zeros((s, 16, d // 16), r.dtype).at[tok].add(
                as_tiles(r)).reshape(s, d), (r, tok)),
    })
    timed["argsort of the keys (stable)"] = (
        lambda key: jnp.argsort(key, stable=True), (rt["key"],))

    rows = {}

    if args.forms == "index":
        timed = {}
    if args.only:
        timed = {name: v for name, v in timed.items() if args.only in name}
    if args.check and args.forms != "index":
        check(x, r, tok, valid)
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


def check(x, r, tok, held):
    """The movers against the plain gather, select and row scatter-add, on
    this backend: float32 exactly (to 1e-6 of the largest entry), the
    compute dtype to one rounding of the float32 result. The padding rows
    hold NaN, as a grouped product may leave them."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_pytorch_from_scratch_tpu.parallel.moe import (
        sum_held, take_held)

    mine = lambda x, y, r: (take_held(x, tok, held),
                            sum_held(y, r, tok, held)[0])
    plain = lambda x, y, r: (jnp.where(held, jnp.take(x, tok, axis=0), 0),
                             y.at[tok].add(jnp.where(held, r, 0)))
    f32 = lambda a: a.astype(jnp.float32)
    x32, y32 = f32(x), f32(x)[::-1] * 0.5
    r32 = jnp.where(held, f32(r), jnp.nan)
    want = jax.jit(plain)(x32, y32, r32)
    for dtype, tol in ((jnp.float32, 1e-6), (x.dtype, 2.0 ** -7)):
        got = jax.jit(mine)(*(z.astype(dtype) for z in (x32, y32, r32)))
        for name, a, b in zip(("take_held", "sum_held"), got, want):
            err = float(jnp.max(jnp.abs(f32(a) - b)))
            scale = float(jnp.max(jnp.abs(b)))
            print(f"  check {jnp.dtype(dtype).name:9s} {name:10s} max error "
                  f"{err:.3e} of {scale:.3e}", flush=True)
            np.testing.assert_array_less(err, tol * scale + 1e-30)


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default="10,7,8,5,6",
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
    ap.add_argument("--share", type=float, default=None,
                    help="the part of a cell's routed experts held, where "
                         "not the cell's own (0.5: a half; 1: all of them)")
    ap.add_argument("--only", default="",
                    help="time the forms whose name holds this and no other")
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
