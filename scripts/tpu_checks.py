"""Compiled-on-hardware validation of the Pallas kernels against the XLA
oracles: flash attention fwd+bwd (the train default, the fused, the
resident and the split block paths, GQA routing; the forward's row walk at
16 MiB of resident K and V against its gridded walk), the positional block
kernel (ring attention's building block) o + lse + bwd, and the
paged-attention kernel (decode and a prefill chunk, native and int8 pools,
`return_lse`) against `models.decode._gather_page_view`; and the sorted
expert layer (`parallel/moe.SharedRoutedFFN`) at an expert cell's shape
against one expert at a time, the device's free memory filled with NaN
first: XLA:TPU's grouped products write their groups' rows only, and the
layer's selects are what keeps the rest out (a second case takes the
movers' selects out and MUST differ); the state-space recurrence's two
kernels and the selected-attention family's five against their texts at
their cells' widths. Also asks the
timer question every later measurement rests on: does `block_until_ready`
wait for the device?

Usage: python scripts/tpu_checks.py [--out kernel_checks.json]
Prints PASS/FAIL lines with per-kernel compile+run timings; exits nonzero
on any mismatch or when no TPU is attached. The JSON artifact records the
device, {name, err, atol, ok, secs} per check and the timer comparison.
`--allow_cpu` runs the same checks under the Pallas interpreter at tiny
shapes — a preflight of this script, not evidence about a chip.
"""

import argparse
import json
import math
import os
import sys
import time

# Runnable from anywhere: `python scripts/tpu_checks.py` puts scripts/ (not
# the repo root) on sys.path, so the package import below needs the root.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from distributed_pytorch_from_scratch_tpu.models.decode import (  # noqa: E402
    _gather_page_view)
from distributed_pytorch_from_scratch_tpu.ops.attention import (  # noqa: E402
    CAUSAL, causal_attention_xla, repeat_kv, sliding_window)
from distributed_pytorch_from_scratch_tpu.ops.pallas import (  # noqa: E402
    flash_attention as fa_mod)
from distributed_pytorch_from_scratch_tpu.ops.pallas.flash_attention import (  # noqa: E402
    block_attention, flash_attention)
from distributed_pytorch_from_scratch_tpu.ops.pallas.paged_attention import (  # noqa: E402
    paged_attention)
from distributed_pytorch_from_scratch_tpu.ops.ring_attention import (  # noqa: E402
    _block_attn_xla)
from distributed_pytorch_from_scratch_tpu.ops import index_select  # noqa: E402
from distributed_pytorch_from_scratch_tpu.ops import ssd as ssd_mod  # noqa: E402
from distributed_pytorch_from_scratch_tpu.ops.pallas import (  # noqa: E402
    dsa_attention)
from distributed_pytorch_from_scratch_tpu.parallel import moe as moe_mod  # noqa: E402
from distributed_pytorch_from_scratch_tpu.runtime.compile_cache import (  # noqa: E402
    compile_cache_stats, enable_compile_cache)

RESULTS = []


def record(name, err, atol, secs, must_differ=False):
    # a NaN error fails; `must_differ` is a case built to fail, which passes
    # only if it does
    passed = bool(err <= atol) != must_differ
    RESULTS.append({"name": name, "err": err, "atol": atol, "ok": passed,
                    "secs": round(secs, 2)})
    print(f"{'PASS' if passed else 'FAIL'} {name}: max err {err:.2e} "
          f"(atol {atol:.2e}) in {secs:.1f}s", flush=True)


def max_err(got, want):
    return float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                 - want.astype(jnp.float32))))


def check(name, fn_got, want, atol):
    """Time compile+first-run of fn_got, compare against want."""
    t0 = time.time()
    got = jax.block_until_ready(fn_got())
    record(name, max_err(got, want), atol, time.time() - t0)


def check_grads(name, loss_got, loss_ref, args):
    """d/d(q, k, v) of two scalar losses: one compile+run for all three."""
    g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(*args)
    t0 = time.time()
    g_got = jax.block_until_ready(
        jax.jit(jax.grad(loss_got, argnums=(0, 1, 2)))(*args))
    secs = time.time() - t0
    for n_, ref_g, got_g in zip("qkv", g_ref, g_got):
        atol = 3e-1 * max(1.0, float(jnp.max(jnp.abs(ref_g))))
        record(f"{name} d{n_}", max_err(got_g, ref_g), atol, secs)


def flash_walk_checks(interp: bool, dtype, tol):
    """The two walks of the forward and of the backward at the shapes where
    the row walk asks Mosaic for more than its default scoped VMEM (a head's
    K and V resident are 16 MiB) and the resident backward keeps its
    whole-row blocks once (68 and 60 MiB, PR 56): the sixteen-thousand-row
    cell's, causal and under its window, and the hybrid cell's 256-wide
    head. The gridded walks (every budget at 0: the gridded forward, the
    split backward) are the reference: o and lse of `_fwd_call`, and the
    three gradients through `flash_attention`, whose backward reads the
    forward's o and lse."""
    cases = [("16k 128/128 group 7 causal", 16384, 128, 7, 0, 1024),
             ("16k 128/128 group 7 window 4096", 16384, 128, 7, 4096, 1024),
             ("8k 256/256 group 8 causal", 8192, 256, 8, 0, 1024)]
    if interp:
        cases = [("group 7 window", 1024, 16, 7, 600, 128)]
    key = jax.random.key(52)
    names = ("KV_ROW_VMEM_BYTES", "BWD_ROW_VMEM_BYTES",
             "BWD_ROW_ONCE_VMEM_BYTES")
    budgets = [getattr(fa_mod, name) for name in names]
    budget = budgets[0]
    for tag, t, d, group, window, blk in cases:
        mask = sliding_window(window) if window else CAUSAL
        q = jax.random.normal(jax.random.fold_in(key, 1), (1, 2 * group, t, d),
                              dtype)
        k = jax.random.normal(jax.random.fold_in(key, 2), (1, 2, t, d), dtype)
        v = jax.random.normal(jax.random.fold_in(key, 3), (1, 2, t, d), dtype)
        flat = lambda x: x.reshape(-1, t, d)
        fwd = lambda q, k, v: fa_mod._fwd_call(
            flat(q), flat(k), flat(v), t_real=t, block_q=blk, block_k=blk,
            hq=2 * group, hkv=2, interpret=interp, mask=mask)
        flash = lambda q, k, v: flash_attention(
            q, k, v, block_q=blk, block_k=blk, bwd_block_q=blk,
            bwd_block_k=blk, interpret=interp, mask=mask)
        grads = lambda: jax.jit(jax.grad(
            lambda *a: jnp.sum(flash(*a).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2)))(q, k, v)
        assert fa_mod._fwd_resident_bytes(t, d, d, q.dtype.itemsize) \
            <= budget, "the row walk is not taken at this shape"
        try:
            for name in names:
                setattr(fa_mod, name, 0)
            o_ref, lse_ref = jax.jit(fwd)(q, k, v)
            g_ref = grads()
        finally:
            for name, was in zip(names, budgets):
                setattr(fa_mod, name, was)
        row = jax.jit(fwd)
        check(f"flash fwd row against grid [{tag}] o",
              lambda: row(q, k, v)[0], o_ref, tol)
        check(f"flash fwd row against grid [{tag}] lse",
              lambda: row(q, k, v)[1], lse_ref, tol)
        t0 = time.time()
        g_got = jax.block_until_ready(grads())
        for n_, ref_g, got_g in zip("qkv", g_ref, g_got):
            record(f"flash row against grid [{tag}] d{n_}",
                   max_err(got_g, ref_g),
                   tol * max(1.0, float(jnp.max(jnp.abs(ref_g)))),
                   time.time() - t0)


def paged_oracle(q, k_pool, v_pool, tbl, start, ps):
    """The gather path's math: the dense page view the serving decode
    materialises (`_gather_page_view`) + masked f32 softmax. Returns
    (o, lse)."""
    b, h, cw, hd = q.shape
    kview = _gather_page_view(k_pool, tbl, jnp.float32)
    vview = _gather_page_view(v_pool, tbl, jnp.float32)
    kvh = kview.shape[1]
    qg = q.reshape(b, kvh, h // kvh, cw, hd).astype(jnp.float32)
    s = jnp.einsum("bkgqd,bktd->bkgqt", qg, kview,
                   precision="highest") / math.sqrt(hd)
    qpos = start[:, None] + jnp.arange(cw)[None, :]            # (b, cw)
    vis = (jnp.arange(kview.shape[2])[None, None, :]
           <= qpos[:, :, None])                                # (b, cw, t)
    s = jnp.where(vis[:, None, None], s, -1e30)
    o = jnp.einsum("bkgqt,bktd->bkgqd", jax.nn.softmax(s, axis=-1), vview,
                   precision="highest")
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    return o.reshape(b, h, cw, hd), lse.reshape(b, h, cw)


def paged_pool(rng, pages, kvh, ps, hd, int8, dtype):
    if int8:
        def one():
            return (jnp.asarray(rng.integers(-127, 128,
                                             (pages + 1, kvh, ps, hd)),
                                jnp.int8),
                    jnp.asarray(rng.uniform(0.01, 0.05,
                                            (pages + 1, kvh, ps)),
                                jnp.float32))
        return one(), one()
    return (jnp.asarray(rng.normal(size=(pages + 1, kvh, ps, hd)), dtype),
            jnp.asarray(rng.normal(size=(pages + 1, kvh, ps, hd)), dtype))


def one_expert_at_a_time(moe, params, x, dtype):
    """`SharedRoutedFFN.apply` with no shared expert as a sum over the held
    experts, each a dense FFN over EVERY token times the token's weight
    for it (zero where it was not chosen): no sort, no group, no chunk."""
    xf = x.reshape(-1, x.shape[-1])
    chosen, w = moe.route(params, xf)
    xd, y = xf.astype(dtype), 0.0
    for e in range(moe.num_held):
        w_e = jnp.sum(jnp.where(chosen == moe.offset + e, w, 0), axis=-1)
        h = (jax.nn.silu(xd @ params["gate"][e].astype(dtype))
             * (xd @ params["up"][e].astype(dtype)))
        y = y + (w_e[:, None]
                 * (h @ params["down"][e].astype(dtype)).astype(jnp.float32))
    return y.reshape(x.shape)


def fill_free_memory_with_nan():
    """What the device has free, written with NaN and freed again: a buffer
    that a program allocates there and does not write then reads NaN."""
    stats = jax.devices()[0].memory_stats() or {}
    free = stats.get("bytes_limit", 0) - stats.get("bytes_in_use", 0)
    jax.block_until_ready([jnp.full((2 ** 28,), jnp.nan, jnp.float32)
                           for _ in range(int(free * 0.9) // 2 ** 30)])


def grouped_rows_check(interp: bool, dtype, tol: float):
    """A grouped product and its two transposes read their groups' rows and
    no other: NaN in every row past the groups, of the left side and of
    the cotangent, reaches neither the groups' rows nor the weights'
    gradient (what `SharedRoutedFFN` leaves between its two products): at
    the chunk of an eighth held (cells 8 and 9's 16,384 rows), of a
    quarter (cell 10's 24,576 rows of 2560) and at one chunk of all the
    pairs of a job that holds every expert."""
    shapes = [(512, 32, 16, 4), (768, 32, 16, 4)] if interp else [
        (16384, 2048, 1536, 16), (24576, 2560, 1536, 16),
        (32768, 2048, 1536, 8)]
    for M, d, f, H in shapes:
        sizes = jnp.full((H,), M // (5 * H), jnp.int32)
        inside = (jnp.arange(M) < jnp.sum(sizes))[:, None]
        keys = jax.random.split(jax.random.key(21), 3)
        lhs = jax.random.normal(keys[0], (M, d), dtype)
        rhs = jax.random.normal(keys[1], (H, d, f), dtype) / math.sqrt(d)
        g = jax.random.normal(keys[2], (M, f), dtype)

        @jax.jit
        def run(lhs, g):
            out, pull = jax.vjp(
                lambda l, r: jax.lax.ragged_dot(l, r, sizes), lhs, rhs)
            d_lhs, d_rhs = pull(g)
            return (jnp.where(inside, out, 0), jnp.where(inside, d_lhs, 0),
                    d_rhs)

        want = run(jnp.where(inside, lhs, 0), jnp.where(inside, g, 0))
        fill_free_memory_with_nan()
        t0 = time.time()
        got = jax.block_until_ready(run(jnp.where(inside, lhs, jnp.nan),
                                        jnp.where(inside, g, jnp.nan)))
        for name, a, b in zip(("rows", "d_lhs", "d_rhs"), want, got):
            record(f"grouped product [{M} rows of {d}], NaN past its "
                   f"groups: {name}", max_err(b, a),
                   tol * float(jnp.max(jnp.abs(a))), time.time() - t0)


def sum_held_check(interp: bool, dtype, tol: float):
    """`parallel/moe.sum_held` (on a TPU the Mosaic kernel `moe_sum_held`
    behind XLA's sort and row gather; here under the interpreter) at cells
    8 and 9's chunk, 16,384 rows of 2048 onto 16,384 tokens, at cell 10's
    (a quarter held: 24,576 rows of 2560) and at the one chunk of all the
    pairs of a job that holds every expert (top-4: four rows a token, so
    a block of tokens owns two windows and more), against the
    row scatter-add it replaced run in float32, with NaN in every row past
    the held ones (what a grouped product's transpose may leave there:
    the mover multiplies what it reads, so its select must come first):
    a random routing, and every row on a twenty-third of the tokens, whose
    blocks own twenty times what a window holds."""
    shapes = [(512, 1024, 128), (512, 2048, 128)] if interp else [
        (16384, 16384, 2048), (16384, 24576, 2560), (8192, 32768, 2048)]
    for S, M, d in shapes:
        keys = jax.random.split(jax.random.key(31), 3)
        y = jax.random.normal(keys[0], (S, d), dtype)
        valid = (jnp.arange(M) < M - M // 5)[:, None]
        r = jnp.where(valid, jax.random.normal(keys[1], (M, d), dtype),
                      jnp.nan)
        for name, spread in (("random tokens", S), ("a few tokens", S // 23)):
            tok = jax.random.randint(keys[2], (M,), 0, spread)
            at = jnp.where(valid[:, 0], tok, S)
            want = y.astype(jnp.float32).at[at].add(
                jnp.where(valid, r, 0).astype(jnp.float32), mode="drop")
            fill_free_memory_with_nan()
            t0 = time.time()
            got, took = jax.block_until_ready(jax.jit(
                lambda y, r, tok: moe_mod.sum_held(
                    y, r, tok, valid, interpret=interp))(y, r, tok))
            blocks = S // moe_mod.SUM_BLOCK
            record(f"sum_held [{M} rows of {d} onto {S} tokens], NaN past "
                   f"the held rows, {name} ({int(took)} windows over "
                   f"{blocks} blocks)",
                   max_err(got, want), tol * float(jnp.max(jnp.abs(want))),
                   time.time() - t0)


def expert_layer_checks(interp: bool, dtype, tol: float):
    """The layer against `one_expert_at_a_time`, the output and the
    gradient of every leaf and of the input, at three held shares: cells 8
    and 9's shape (16,384 tokens of 2048, top-8 of 128 experts, 16 held:
    eight chunks of 16,384 sorted rows, of which the first one or two are
    live and the last live one ends in rows no group holds), cell 10's (a
    quarter held: 16,384 tokens of 2560, top-6 of 64, 16 held, four chunks
    of 24,576 rows) and a job that holds EVERY expert (top-2 of 8: one
    chunk of all 16,384 pairs, two rows a token). Then, at the first, with
    the movers' selects taken out, which lets the rows no group holds into
    the input's gradient: that one must DIFFER, or this check would not
    see what the selects keep out."""
    from jax.sharding import PartitionSpec as P
    from distributed_pytorch_from_scratch_tpu.config import MeshConfig
    from distributed_pytorch_from_scratch_tpu.runtime.mesh import make_mesh

    shapes = [(2, 256, 32, 16, 16, 2, 2), (2, 256, 32, 16, 8, 2, 2),
              (2, 256, 32, 16, 4, 4, 2)] if interp else [
        (2, 8192, 2048, 768, 128, 16, 8), (1, 16384, 2560, 768, 64, 16, 6),
        (1, 8192, 2048, 768, 8, 8, 2)]
    mesh = make_mesh(MeshConfig(dp=1, tp=1), devices=jax.devices()[:1])
    for nth, (b, t, d, f, E, H, k) in enumerate(shapes):
        moe = moe_mod.SharedRoutedFFN(d, f, E, top_k=k, held=H, n_shared=0)
        params = moe.init(jax.random.key(11))
        x = jax.random.normal(jax.random.key(12), (b, t, d), jnp.float32)
        held = int(jnp.sum(moe.route(params, x.reshape(-1, d))[0] < H))
        shape = (f"{H} of {E} held: {held} held rows in chunks of "
                 f"{moe.chunk_rows(b * t * k)}")

        def layer(params, x):
            return jax.shard_map(
                lambda p, x: moe.apply(p, x, dtype)[0], mesh=mesh,
                in_specs=(moe.specs(), P()), out_specs=P())(params, x)

        def value_and_grads(fn, params, x):
            sq = lambda p, x: jnp.sum(fn(p, x).astype(jnp.float32) ** 2)
            d_params, d_x = jax.grad(sq, (0, 1))(params, x)
            return {"y": fn(params, x), "d_x": d_x,
                    **{f"d_{name}": g for name, g in d_params.items()}}

        def run(fn):
            fill_free_memory_with_nan()
            t0 = time.time()
            # traced anew a run: the last one runs `layer` over other movers
            got = jax.block_until_ready(jax.jit(
                lambda p, x: value_and_grads(fn, p, x))(params, x))
            return got, time.time() - t0

        want, _ = run(lambda p, x: one_expert_at_a_time(moe, p, x, dtype))
        got, secs = run(layer)
        for name, a in want.items():
            record(f"expert layer [{shape}] {name}", max_err(got[name], a),
                   tol * float(jnp.max(jnp.abs(a))), secs)
        if interp or nth:    # the CPU lowering zero-fills: nothing to see
            continue
        movers = ("take_held", "sum_held")
        selected = [getattr(moe_mod, name) for name in movers]
        moe_mod.take_held = lambda x, tok, valid: jnp.take(x, tok, axis=0)
        moe_mod.sum_held = lambda y, r, tok, valid: (y.at[tok].add(r),
                                                     jnp.int32(0))
        try:
            got, secs = run(layer)
        finally:
            for name, mover in zip(movers, selected):
                setattr(moe_mod, name, mover)
        record("expert layer, the movers' selects out: d_x MUST differ",
               max_err(got["d_x"], want["d_x"]),
               tol * float(jnp.max(jnp.abs(want["d_x"]))), secs,
               must_differ=True)


# (heads, groups, chunk) of the two benchmark cells that run the state-space
# recurrence (PERF.md's numbering), at heads of 64 and a state of 128
CELL_SHAPES = {"15": (64, 1, 256), "13": (32, 2, 128)}


def ssd_inputs(b, t, H, G, dtype, seed=3):
    """x, dt, A, B, C about as the Mamba-2 mixer makes them at init: dt
    log-uniform in [1e-3, 1e-1], A = -(1 .. H) (a chunk of 256 sums to
    about -1,600 in the last head)."""
    k = jax.random.split(jax.random.key(seed), 4)
    normal = lambda key, *shape: jax.random.normal(
        key, shape, jnp.float32).astype(dtype)
    dt = jnp.exp(jax.random.uniform(k[1], (b, t, H), jnp.float32,
                                    np.log(1e-3), np.log(1e-1)))
    return (normal(k[0], b, t, H, 64), dt,
            -(1.0 + jnp.arange(H, dtype=jnp.float32)),
            normal(k[2], b, t, G, 128), normal(k[3], b, t, G, 128))


def ssd_errors(args, chunk: int, interpret: bool) -> dict:
    """The recurrence's kernels and its text, each against the text in
    float32 at `Precision.HIGHEST` on the same numbers: {what: {"kernels",
    "text"}}, the value and the five gradients relative to the
    reference's largest entry, `decay_min` as a difference."""
    w = jax.random.normal(jax.random.key(9), args[0].shape, jnp.float32)

    def run(fn, args):
        loss = lambda *a: jnp.sum(fn(*a)[0].astype(jnp.float32) * w)
        (y, low), grads = jax.jit(lambda *a: (
            fn(*a), jax.grad(loss, range(5))(*a)))(*args)
        return [y, *grads], low

    text = lambda *a: ssd_mod._ssd_text(*a, chunk, jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, want_low = run(text, [a.astype(jnp.float32) for a in args])
    # (on the chip `ssd` takes the kernels by itself; off it, asked for)
    got, got_low = run(lambda *a: ssd_mod.ssd(*a, chunk,
                                              interpret=interpret), args)
    plain, _ = run(text, args)
    rel = lambda a, b: max_err(a, b) / max(float(jnp.max(jnp.abs(b))), 1e-30)
    out = {name: {"kernels": rel(g, r), "text": rel(p, r)}
           for name, g, p, r in zip(("y", "dx", "ddt", "dA", "dB", "dC"),
                                    got, plain, want)}
    out["decay_min"] = {"kernels": abs(float(got_low) - float(want_low)),
                        "text": 0.0, "value": float(want_low)}
    return out


def ssd_checks(interp: bool, dtype, tol: float):
    """The state-space recurrence's kernels (ops/pallas/ssd.py) against its
    XLA text at both cells' shapes (under the interpreter: their heads,
    groups and chunks at 300 tokens, a length no chunk divides): the value,
    every input's gradient and `decay_min`. A kernel may stand as far from
    the float32 reference as twice the text in the same dtype does."""
    for cell, (H, G, chunk) in CELL_SHAPES.items():
        if interp:
            H //= 4
        args = ssd_inputs(1, 300 if interp else 4096, H, G, dtype)
        t0 = time.time()
        errors = ssd_errors(args, chunk, interp)
        secs = time.time() - t0
        for name, e in errors.items():
            record(f"ssd kernels, cell {cell}'s shape: {name}", e["kernels"],
                   max(2 * e["text"], 1e-3 if name == "decay_min" else tol),
                   secs)


def dsa_walk_checks(interp: bool, dtype, tol: float):
    """The selected-attention family's five kernels
    (ops/pallas/dsa_attention.py) against `selected_attention_xla` at the
    published widths (32 query heads over 4 key-value heads of 128, 16
    index heads of 64, top-k 2048, 4096 rows, blocks of 128 x 512; under
    the interpreter a twentieth of it): o, the forward walk's lse (against
    the text's logsumexp over each row's set), dq, dk, dv and the indexer's
    three gradients, each relative to the text's largest entry. The two
    sides choose their sets from scores summed in another order, so a pair
    at a row's threshold may differ: a 2048th of a row's weight."""
    b, H, Hkv, h, J, c, t, top_k = ((1, 4, 2, 16, 2, 8, 256, 24) if interp
                                    else (1, 32, 4, 128, 16, 64, 4096, 2048))
    ks = jax.random.split(jax.random.key(17), 7)
    normal = lambda key, *shape: jax.random.normal(
        key, shape, jnp.float32).astype(dtype)
    args = (normal(ks[0], b, H, t, h), normal(ks[1], b, Hkv, t, h),
            normal(ks[2], b, Hkv, t, h), normal(ks[3], b, J, t, c),
            normal(ks[4], b, t, c),
            jax.random.normal(ks[5], (b, t, J), jnp.float32) * 0.3)
    lanes = jax.random.normal(ks[6], (b, H, t, h), jnp.float32)

    def run(impl):
        def value(*a):
            o, sums = index_select.selected_attention(*a, top_k, impl=impl)
            return (jnp.sum(o.astype(jnp.float32) * lanes)
                    + sums["dsa_index_kl"]), o
        (_, o), grads = jax.jit(jax.value_and_grad(
            value, argnums=tuple(range(6)), has_aux=True))(*args)
        return [o, *grads]

    def lse_text(q, k, v, q_idx, k_idx, w):
        score = index_select.index_scores(q_idx, k_idx, w)
        keep = index_select.live(score,
                                 *index_select.select(score, top_k)[:2])
        logits = jnp.einsum("bhqd,bhkd->bhqk", q, repeat_kv(q, k, v)[0],
                            preferred_element_type=jnp.float32)
        return jax.nn.logsumexp(jnp.where(keep[:, None],
                                          logits / math.sqrt(h), -jnp.inf),
                                axis=-1)

    def lse_kernels(q, k, v, q_idx, k_idx, w):
        bq, bk = index_select.flash_blocks(t)
        blocks = dict(bq=bq, bk=bk, interpret=interp)
        bits = dsa_attention.select_call(
            q_idx, k_idx, index_select._rows_last(w), top_k, **blocks)[3]
        return dsa_attention.fwd_call(q, k, v, bits, **blocks)[1]

    want = run("xla") + [jax.jit(lse_text)(*args)]
    t0 = time.time()
    got = run("flash_interpret" if interp else "flash")
    got.append(jax.jit(lse_kernels)(*args))
    jax.block_until_ready(got)
    secs = time.time() - t0
    for name, g, r in zip(("o", "dq", "dk", "dv", "d q_idx", "d k_idx",
                           "d w", "lse"), got, want):
        record(f"dsa walk, {H} / {Hkv} heads of {h}, {t} rows: {name}",
               max_err(g, r) / max(float(jnp.max(jnp.abs(r))), 1e-30), tol,
               secs)


def timer_check(interpret: bool) -> dict:
    """Is `block_until_ready` honest here? Time the same chain of donated
    jitted steps twice: once ending in `block_until_ready`, once ending in
    a device->host copy of the result (which cannot complete early). An
    honest `block_until_ready` takes as long as the copy does; one that
    returns at enqueue time takes a small fraction of it."""
    n = 256 if interpret else 4096
    reps, steps = 8, 20
    w = jax.random.normal(jax.random.key(1), (n, n), jnp.bfloat16) / n ** .5

    def body(x):
        for _ in range(reps):
            x = (x @ w).astype(jnp.bfloat16)
        return x

    step = jax.jit(body, donate_argnums=0)
    fresh = lambda: jax.random.normal(jax.random.key(2), (n, n),
                                      jnp.bfloat16)
    for end in (jax.block_until_ready, lambda x: float(x[0, 0])):  # warm
        end(step(fresh()))
    out = {}
    for key, end in (("block_until_ready_s", jax.block_until_ready),
                     ("d2h_sync_s", lambda x: float(x[0, 0]))):
        x = jax.block_until_ready(fresh())
        t0 = time.perf_counter()
        for _ in range(steps):
            x = step(x)
        end(x)
        out[key] = time.perf_counter() - t0
    out["ratio"] = out["block_until_ready_s"] / out["d2h_sync_s"]
    out["matmul_tflops"] = (2 * n ** 3 * reps * steps
                            / out["block_until_ready_s"] / 1e12)
    # honest: the two agree (the copy adds one small transfer); dishonest
    # shows up as a ratio near 0
    out["ok"] = bool(out["ratio"] > 0.8)
    print(f"{'PASS' if out['ok'] else 'FAIL'} timer: {steps} chained donated "
          f"steps take {out['block_until_ready_s'] * 1e3:.1f} ms ending in "
          f"block_until_ready, {out['d2h_sync_s'] * 1e3:.1f} ms ending in a "
          f"D2H copy (ratio {out['ratio']:.2f}; {n}^3 bf16 matmul chain at "
          f"{out['matmul_tflops']:.1f} TFLOP/s"
          + (" — interpreter run, not a device number" if interpret else "")
          + ")", flush=True)
    return out


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--out", default=None,
                   help="write a JSON artifact with per-check results")
    p.add_argument("--allow_cpu", action="store_true",
                   help="no TPU needed: run every check under the Pallas "
                        "interpreter (asked for explicitly) at tiny shapes "
                        "— a preflight of this script, not on-chip evidence")
    return p.parse_args(argv)


def main():
    args = parse_args()
    cache_dir = enable_compile_cache()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count()}
    print(f"tpu_checks on {device['count']} x {device['platform']} "
          f"[{device['kind']}], compile cache {cache_dir}", flush=True)
    interp = args.allow_cpu and dev.platform != "tpu"
    if dev.platform != "tpu" and not args.allow_cpu:
        raise SystemExit(
            f"tpu_checks: no TPU attached (jax.devices()[0].platform is "
            f"{dev.platform!r}); the kernels are compiled by Mosaic. "
            f"--allow_cpu preflights the script under the interpreter")
    dtype = jnp.float32 if interp else jnp.bfloat16
    tol = 2e-3 if interp else 3e-2

    key = jax.random.key(0)
    loss = lambda fn: lambda *a: jnp.sum(fn(*a).astype(jnp.float32) ** 2)

    # --- flash attention: the train default (t=1000 MHA, table blocks),
    # then GQA-routed through the backward's three walks: one tile a head
    # (t <= block), two square blocks with the head resident, and the split
    # kernels (a backward key block of another size than the query block)
    flash_cases = [("default", 1000, None, None, 8, 8),
                   ("gqa fused", 512, 1024, 1024, 8, 2),
                   ("gqa resident", 1000, 512, 512, 8, 2),
                   ("gqa split", 1000, 512, 1024, 8, 2)]
    if interp:  # GQA over several blocks, both walks, exercises it all
        flash_cases = [("gqa resident", 200, 128, 128, 2, 1),
                       ("gqa split", 200, 128, 256, 2, 1)]
    for tag, t, blk, bwd_blk_k, hq, hkv in flash_cases:
        b, d = (1, 16) if interp else (2, 64)
        q = jax.random.normal(jax.random.fold_in(key, 1), (b, hq, t, d), dtype)
        k = jax.random.normal(jax.random.fold_in(key, 2), (b, hkv, t, d), dtype)
        v = jax.random.normal(jax.random.fold_in(key, 3), (b, hkv, t, d), dtype)
        flash = lambda q, k, v: flash_attention(
            q, k, v, block_q=blk, block_k=blk, bwd_block_q=blk,
            bwd_block_k=bwd_blk_k, interpret=interp)
        check(f"flash fwd [{tag}]", lambda: jax.jit(flash)(q, k, v),
              causal_attention_xla(q, k, v), tol)
        check_grads(f"flash [{tag}]", loss(flash),
                    loss(causal_attention_xla), (q, k, v))

    # --- the row walks where they ask for scoped VMEM of their own (the
    # forward's K and V, the backward's head kept once), against the
    # gridded forward and the split backward
    flash_walk_checks(interp, dtype, tol)

    # --- positional block kernel (ring attention building block)
    b, hq, hkv, tq, tk, d = (1, 2, 1, 100, 100, 16) if interp else \
        (2, 4, 2, 500, 500, 64)
    q = jax.random.normal(jax.random.fold_in(key, 5), (b, hq, tq, d), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 6), (b, hkv, tk, d), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 7), (b, hkv, tk, d), dtype)
    qp = jax.random.randint(jax.random.fold_in(key, 8), (b, tq), 100, 900)
    kp = jax.random.randint(jax.random.fold_in(key, 9), (b, tk), 100, 900)
    xla_blk = lambda q, k, v: _block_attn_xla(q, k, v, qp, kp,
                                              1.0 / np.sqrt(d))
    o_ref, lse_ref = jax.jit(xla_blk)(q, k, v)
    # ONE jitted wrapper reused by the 'o' and 'lse' checks: the lse
    # check's secs is then the cached-exec cost, not a second compile
    blk = jax.jit(lambda q, k, v: block_attention(q, k, v, qp, kp,
                                                  interpret=interp))
    check("block kernel o", lambda: blk(q, k, v)[0], o_ref, tol)
    alive = lse_ref > -1e29
    check("block kernel lse",
          lambda: jnp.where(alive, blk(q, k, v)[1], 0.0),
          jnp.where(alive, lse_ref, 0.0), tol)
    sq_o = lambda fn: lambda *a: jnp.sum(fn(*a)[0].astype(jnp.float32) ** 2)
    check_grads("block kernel",
                sq_o(lambda q, k, v: block_attention(q, k, v, qp, kp,
                                                     interpret=interp)),
                sq_o(xla_blk), (q, k, v))

    # --- paged attention over the page table vs the gathered dense view:
    # the serve default page size and the two the CPU tests use; decode
    # (cw=1: one query row at MHA) and a prefill chunk; native and int8
    rng = np.random.default_rng(0)
    kvh, hd, mp, slots, pages = (2, 16, 4, 4, 10) if interp else \
        (8, 64, 4, 4, 24)
    paged_cases = [(ps, int8, cw) for ps in (64, 16, 8)
                   for int8 in (False, True) for cw in (1, 128)
                   if cw == 1 or ps == 64]
    if interp:
        paged_cases = [(8, False, 8), (8, True, 1)]
    for ps, int8, cw in paged_cases:
        kpool, vpool = paged_pool(rng, pages, kvh, ps, hd, int8, dtype)
        tbl = jnp.asarray(rng.integers(0, pages, (slots, mp)), jnp.int32)
        hi = mp * ps - cw
        start = jnp.asarray([min(ps - 1, hi), min(2 * ps, hi), hi, 0],
                            jnp.int32)
        q = jnp.asarray(rng.normal(size=(slots, kvh, cw, hd)), dtype)
        o_ref, lse_ref = paged_oracle(q, kpool, vpool, tbl, start, ps)
        fn = jax.jit(lambda q, kpool, vpool: paged_attention(
            q, kpool, vpool, tbl, start, page_size=ps, return_lse=True,
            interpret=interp))
        tag = (f"{'decode' if cw == 1 else f'chunk cw={cw}'} ps={ps} "
               f"{'int8' if int8 else 'native'}")
        # outputs scale with |v| (int8 pages dequantize to ~+-6): the MXU
        # rounds the softmax weights to bf16, an error relative to that
        check(f"paged {tag} o", lambda: fn(q, kpool, vpool)[0], o_ref,
              tol * max(1.0, float(jnp.max(jnp.abs(o_ref)))))
        check(f"paged {tag} lse", lambda: fn(q, kpool, vpool)[1], lse_ref,
              tol)

    # --- the sorted expert layer: its grouped products end at the last
    # held row, and what they leave in the rows past it stays out
    grouped_rows_check(interp, dtype, tol)
    sum_held_check(interp, dtype, tol)
    expert_layer_checks(interp, dtype, tol)

    # --- the state-space recurrence's walk against its text
    ssd_checks(interp, dtype, tol)

    # --- the selected-attention walks against the dense text
    dsa_walk_checks(interp, dtype, tol)

    timer = timer_check(interp)

    ok = all(r["ok"] for r in RESULTS) and timer["ok"]
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            # top-level key is "all_ok", NOT "ok": each per-check record
            # also has an "ok" field — a partially-failing run must not
            # look complete to a grep
            json.dump({"device": device, "interpreted": interp,
                       "all_ok": ok, "checks": RESULTS, "timer": timer,
                       "compile_cache": compile_cache_stats()}, f, indent=1)
        print(f"wrote {args.out}")
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
