"""Sweep flash-attention kernel block sizes on the attached TPU chip.

VERDICT r3 weak #2: the 1024x1024 defaults in ops/pallas/flash_attention.py
were swept on v5e against the *pre-GQA* kernel; the GQA-routed forward, the
fused GQA backward, and the positional (ring) kernels have since replaced it.
This harness times the CURRENT kernels at the shapes that matter:

  - reference shape  b32 h8 t1000 hd64          (the 45m bench/train config)
  - GQA shape        b32 h8 hkv2 t1000 hd64     (the gqa presets)
  - long context     b2  h8 t8192 hd64          (the t=8k bench line)

For each shape: forward-only and forward+backward wall time per (block_q,
block_k) x (bwd_block_q, bwd_block_k) grid, plus the XLA dense attention as
the floor. Prints a table and the best combo per shape; nothing is written
(a winner is adopted by editing `flash_attention.DEFAULT_BLOCK` or
`flash_blocks`, with the reading beside it). Run on hardware:

    python scripts/tune_flash_blocks.py [--quick]

`--subtile` sweeps the causal SUB-TILE edge (ops/pallas/flash_attention.py's
FWD_SUBTILE / FWD_SUBTILE_WIDE / BWD_SUBTILE) and the grid's square block:
the forward and the backward Mosaic calls timed apart, bf16, at the shape
of `--t --d --dv --blocks` (default t=1024 hd64, one tile a head) and the
b*h of `--bh` (192 = gpt2-medium b12 on one chip, 80 = gpt2-large dp2 x tp2
a chip, 128 = the latent-attention cell's 4 x 32). The readings behind the
constants are in the notes beside them, one command a table:

    python scripts/tune_flash_blocks.py --subtile --bh 192,80
    python scripts/tune_flash_blocks.py --subtile --bh 128 --t 4096 --d 192 \
        --dv 128 --blocks 512,1024,2048 \
        --edges 128x256,128x512,256x256,256x512,512x256

`--backward` takes the backward of several blocks a head apart, one call
alone at `--bh --t --d --dv --group --window` (b*h counts query heads; 0:
causal): the resident walk (`_bwd_row_kernel`, one `flash_bwd` call) whole
as `_bwd_call` takes it at that shape (it prints the bytes with two buffers
a block and as taken, and which blocks are kept once), the same walk with
the OTHER buffer count (the budgets opened or shut by hand: how a size is
read before a rule admits it), its DMA alone, each of its five products
knocked out (wrong numbers, right time), and the two split kernels it
replaces in the same process, with the largest difference between the two
walks' dq, dk and dv. The tables beside BWD_SUBTILE, and the four readings
beside `BWD_ROW_ONCE_VMEM_BYTES` (PR 56):

    python scripts/tune_flash_blocks.py --backward --bh 128 --t 4096 \
        --d 192 --dv 128
    python scripts/tune_flash_blocks.py --backward --bh 64 --t 8192 --d 64 \
        --group 4
    python scripts/tune_flash_blocks.py --backward --bh 28 --t 16384 \
        --d 128 --group 7 [--window 4096]
    python scripts/tune_flash_blocks.py --backward --bh 32 --t 8192 --d 256 \
        --group 8
    python scripts/tune_flash_blocks.py --backward --bh 32 --t 8192 --d 128 \
        --group 8

`--forward` times the forward of several blocks a head, one call alone at
`--bh --t --d --dv --group --window` (0: causal): the row walk (a head's K
and V resident; the budget is opened to the row's size where the shipped
`KV_ROW_VMEM_BYTES` is under it, which is how a size is read BEFORE it is
admitted) beside the gridded walk in the same process, with the largest
difference between the two walks' o and lse. The readings beside
`KV_ROW_VMEM_BYTES`:

    python scripts/tune_flash_blocks.py --forward --bh 28 --t 16384 \
        --d 128 --group 7 --window 4096
    python scripts/tune_flash_blocks.py --forward --bh 32 --t 8192 --d 256 \
        --group 8

`--paged` sweeps the PAGED-attention kernel instead (ISSUE 14):
pages_per_block per (page_size, kv_dtype) serving decode shape
(ops/pallas/paged_attention.py's autotuner table; --write_cache persists
to the paged JSON cache so every later `--paged_attn pallas` dispatch on
this backend runs the tuned blocks).
"""

import argparse
import itertools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from distributed_pytorch_from_scratch_tpu.ops.attention import causal_attention_xla
from distributed_pytorch_from_scratch_tpu.ops.pallas.flash_attention import (
    flash_attention)
from distributed_pytorch_from_scratch_tpu.runtime.compile_cache import (
    enable_compile_cache)


def time_fn(fn, *args, iters=20, warmup=3):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3  # ms


def device_ms(fn, *args, match, iters=10):
    """Device time (ms) a call of `fn` spends in the ops whose HLO text
    holds `match` (a split backward is two kernels a call), from a profiler
    capture of `iters` calls. The host clock around a call of under a
    millisecond also reads the dispatch; the capture reads the kernel, as
    the benchmark's `kernels.flash_ms` does."""
    import glob
    import tempfile

    from jax.profiler import ProfileData

    from distributed_pytorch_from_scratch_tpu.training.metrics import (
        ProfilerTrace)

    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as tmp:
        capture = ProfilerTrace(tmp, start_step=0, num_steps=iters)
        capture.maybe_start(0)
        for _ in range(iters):
            out = fn(*args)
        capture.maybe_stop(iters, sync=out)
        data = ProfileData.from_file(glob.glob(os.path.join(
            capture.log_dir, "plugins", "profile", "*", "*.xplane.pb"))[0])
    durs = [ev.duration_ns for plane in data.planes
            if plane.name == "/device:TPU:0"
            for line in plane.lines if line.name == "XLA Ops"
            for ev in line.events if match in ev.name]
    if not durs:
        raise RuntimeError(f"no op named like {match!r} in the capture")
    return sum(durs) / iters / 1e6


def sweep_shape(name, b, h, hkv, t, d, blocks, iters):
    key = jax.random.PRNGKey(0)
    kq, kk, kv_ = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, h, t, d), jnp.bfloat16)
    k = jax.random.normal(kk, (b, hkv, t, d), jnp.bfloat16)
    v = jax.random.normal(kv_, (b, hkv, t, d), jnp.bfloat16)

    def loss(fn):
        def f(q, k, v):
            return jnp.sum(fn(q, k, v).astype(jnp.float32))
        return f

    print(f"\n=== {name}: b{b} h{h} hkv{hkv} t{t} hd{d} bf16 ===", flush=True)
    # XLA dense floor (what the fallback path uses)
    if h == hkv and t <= 4096:
        xla_fwd = jax.jit(causal_attention_xla)
        xla_bwd = jax.jit(jax.grad(loss(causal_attention_xla), argnums=(0, 1, 2)))
        try:
            print(f"  xla dense          fwd {time_fn(xla_fwd, q, k, v, iters=iters):8.3f} ms"
                  f"   fwd+bwd {time_fn(xla_bwd, q, k, v, iters=iters):8.3f} ms",
                  flush=True)
        except Exception as e:  # noqa: BLE001 - OOM at long t is expected
            print(f"  xla dense          failed: {type(e).__name__}", flush=True)

    results = []
    for bq, bk in blocks:
        if bq > t * 2 or bk > t * 2:
            continue
        fn = jax.jit(lambda q, k, v, bq=bq, bk=bk: flash_attention(
            q, k, v, block_q=bq, block_k=bk))
        try:
            ms = time_fn(fn, q, k, v, iters=iters)
        except Exception as e:  # noqa: BLE001
            print(f"  fwd  bq{bq:5d} bk{bk:5d}  FAILED {type(e).__name__}: {e}",
                  flush=True)
            continue
        results.append((ms, bq, bk))
        print(f"  fwd  bq{bq:5d} bk{bk:5d}  {ms:8.3f} ms", flush=True)
    results.sort()
    best_fwd = results[0] if results else None

    bwd_results = []
    fbq, fbk = (best_fwd[1], best_fwd[2]) if best_fwd else (1024, 1024)
    for bbq, bbk in blocks:
        if bbq > t * 2 or bbk > t * 2:
            continue
        fn = jax.jit(jax.grad(loss(
            lambda q, k, v, bbq=bbq, bbk=bbk: flash_attention(
                q, k, v, block_q=fbq, block_k=fbk,
                bwd_block_q=bbq, bwd_block_k=bbk)), argnums=(0, 1, 2)))
        try:
            ms = time_fn(fn, q, k, v, iters=iters)
        except Exception as e:  # noqa: BLE001
            print(f"  bwd  bq{bbq:5d} bk{bbk:5d}  FAILED {type(e).__name__}: {e}",
                  flush=True)
            continue
        bwd_results.append((ms, bbq, bbk))
        print(f"  f+b  bq{bbq:5d} bk{bbk:5d}  {ms:8.3f} ms  (fwd blocks "
              f"{fbq}x{fbk})", flush=True)
    bwd_results.sort()
    if best_fwd:
        print(f"  BEST fwd: {best_fwd[1]}x{best_fwd[2]} @ {best_fwd[0]:.3f} ms")
    if bwd_results:
        w = bwd_results[0]
        print(f"  BEST f+b: bwd {w[1]}x{w[2]} @ {w[0]:.3f} ms")


def sweep_subtiles(bhs, edges, t=1024, d=64, dv=None, blocks=None,
                   iters=50):
    """Forward and backward call times (ms) per grid block, sub-tile edge
    and b*h, at q/k width `d` and v width `dv`. The edge is set on the
    module (it is no argument of the kernels: the code picks it), the plan
    cache cleared and the calls jitted afresh. `edges` are (sub_q, sub_k)
    pairs; (t, t) is one masked sub-tile: the kernels before sub-tiles.
    `blocks` are the grid's square blocks (default: one tile a head). A
    combination Mosaic refuses is printed and skipped."""
    import distributed_pytorch_from_scratch_tpu.ops.pallas.flash_attention \
        as fa
    dv = dv or d
    rows = []

    def reading(tag, fn, args, match):
        """(device ms, host-clock ms) of one call, None where Mosaic refuses
        the combination (e.g. over its scoped VMEM)."""
        try:
            return (device_ms(fn, *args, match=match),
                    time_fn(fn, *args, iters=iters))
        except Exception as e:  # noqa: BLE001
            print(f"{tag}  {match} FAILED {type(e).__name__}: "
                  f"{str(e)[-200:]!r}", flush=True)

    cell = lambda r, i: f"{r[i]:7.3f}" if r else "   -   "
    for bh in bhs:
        key = jax.random.PRNGKey(bh)
        kq, kk, kv_, kd = jax.random.split(key, 4)
        q, k = (jax.random.normal(x, (bh, t, d), jnp.bfloat16)
                for x in (kq, kk))
        v, do = (jax.random.normal(x, (bh, t, dv), jnp.bfloat16)
                 for x in (kv_, kd))
        # the backward's time does not depend on what o and lse hold
        o, lse = jnp.zeros_like(v), jnp.zeros((bh, t, 1), jnp.float32)
        for block, edge in itertools.product(blocks or [t], edges):
            kw = dict(t_real=t, block_q=block, block_k=block, hq=1, hkv=1,
                      interpret=False)
            fa.FWD_SUBTILE = fa.FWD_SUBTILE_WIDE = fa.BWD_SUBTILE = edge
            fa.causal_subtile_plan.cache_clear()
            fwd = jax.jit(lambda q, k, v: fa._fwd_call(q, k, v, **kw))
            bwd = jax.jit(lambda q, k, v, o, lse, do: fa._bwd_call(
                q, k, v, o, lse, do, **kw))
            tag = (f"  bh{bh:4d} t{t} d{d}/{dv} block {block:4d} "
                   f"sub {edge[0]:4d}x{edge[1]:<4d}")
            f = reading(tag, fwd, (q, k, v), "flash_fwd")
            b = reading(tag, bwd, (q, k, v, o, lse, do), "flash_bwd")
            rows.append((bh, block, edge, f and f[0], b and b[0]))
            print(f"{tag}  fwd {cell(f, 0)} ms   bwd {cell(b, 0)} ms   "
                  f"(host clock {cell(f, 1)} / {cell(b, 1)})", flush=True)
    return rows


# the order in which a rectangle of `_bwd_row_kernel` calls `_dot`
BACKWARD_PRODUCTS = ("s", "dp", "dq", "dk", "dv")


def sweep_backward(bh, t, d, dv=None, group=1, block=1024, iters=10,
                   window=0):
    """The backward at several blocks a head, a call alone (module
    docstring). The knock-outs are made here and not in the kernel: `_dot`
    is replaced by one that counts a rectangle's five calls and returns
    zeros for one of them, and the kernel's body by one that only writes
    its outputs for the DMA's time. Returns {reading: ms}."""
    import distributed_pytorch_from_scratch_tpu.ops.pallas.flash_attention \
        as fa
    from distributed_pytorch_from_scratch_tpu.ops.attention import (
        CAUSAL, sliding_window)
    dv = dv or d
    mask = sliding_window(window) if window else CAUSAL
    key = jax.random.PRNGKey(bh)
    kq, kk, kv_, kd = jax.random.split(key, 4)
    q = jax.random.normal(kq, (bh, t, d), jnp.bfloat16)
    k = jax.random.normal(kk, (bh // group, t, d), jnp.bfloat16)
    v = jax.random.normal(kv_, (bh // group, t, dv), jnp.bfloat16)
    do = jax.random.normal(kd, (bh, t, dv), jnp.bfloat16)
    kw = dict(t_real=t, block_q=block, block_k=block, hq=group, hkv=1,
              interpret=False, mask=mask)
    # a real forward's o and lse: the two walks' numbers are compared
    o, lse = jax.jit(lambda q, k, v: fa._fwd_call(q, k, v, **kw))(q, k, v)
    args = (q, k, v, o, lse, do)
    real_dot, real_kernel = fa._dot, fa._bwd_row_kernel
    budgets = (fa.BWD_ROW_VMEM_BYTES, fa.BWD_ROW_ONCE_VMEM_BYTES)
    twice, once = (fa._bwd_resident_bytes(t, d, dv, 2, group, buffers=n)
                   for n in (2, 1))
    # the rule of `_bwd_call`: the buffers a block it takes here (0: over
    # both budgets, and the second is opened to the head for the row walk),
    # the budgets that give that walk and those that give the other count
    own = 2 if twice <= budgets[0] else 1 if once <= budgets[1] else 0
    home = budgets if own else (0, once)
    other = (0, max(once, budgets[1])) if own == 2 else (twice, 0)
    print(f"backward alone: bh{bh} t{t} d{d}/{dv} group {group} "
          f"{'window ' + str(window) if window else 'causal'} block "
          f"{block} sub {fa.BWD_SUBTILE}; the head resident is "
          f"{twice / 2 ** 20:.1f} MiB with two buffers a block and "
          f"{(twice if own == 2 else once) / 2 ** 20:.1f} as taken ("
          + {2: "2 buffers: none kept once",
             1: "1 buffer: all nine blocks kept once",
             0: "over both budgets, the second opened to it: all nine "
                "blocks kept once"}[own]
          + f") of budgets of {budgets[0] / 2 ** 20:.0f} twice and "
          f"{budgets[1] / 2 ** 20:.0f} once", flush=True)

    def shut(first, second):
        fa.BWD_ROW_VMEM_BYTES, fa.BWD_ROW_ONCE_VMEM_BYTES = first, second

    def without(product):
        calls = itertools.count()

        def dot(a, b, dims):
            if BACKWARD_PRODUCTS[next(calls) % len(BACKWARD_PRODUCTS)] \
                    != product:
                return real_dot(a, b, dims)
            cols = b.shape[0] if dims == fa._NT else b.shape[1]
            return jnp.zeros((a.shape[0], cols), jnp.float32)
        return dot

    def dma_alone(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                  dk_ref, dv_ref, *scratch, **static):
        for ref in (dq_ref, dk_ref, dv_ref):
            ref[...] = jnp.zeros(ref.shape, ref.dtype)

    out, grads = {}, {}

    def reading(tag, match="flash_bwd"):
        fn = jax.jit(lambda *a: fa._bwd_call(*a, **kw))
        t0 = time.perf_counter()
        try:
            compiled = fn.lower(*args).compile()
        except Exception as e:  # noqa: BLE001
            print(f"  {tag:28s} FAILED {type(e).__name__}: "
                  f"{str(e)[-300:]!r}", flush=True)
            return
        compile_s = time.perf_counter() - t0
        out[tag] = device_ms(compiled, *args, match=match, iters=iters)
        grads[tag] = compiled(*args)
        print(f"  {tag:28s} {out[tag]:8.3f} ms   (trace, lower and compile "
              f"{compile_s:5.1f} s)", flush=True)

    try:
        shut(*home)
        reading("row walk, whole")
        shut(*other)    # the other buffer count, the budgets set by hand
        reading(f"row walk, {9 if own == 2 else 0} blocks kept once")
        shut(*home)
        fa._bwd_row_kernel = dma_alone
        reading("row walk, DMA alone")
        fa._bwd_row_kernel = real_kernel
        for product in BACKWARD_PRODUCTS:
            fa._dot = without(product)
            reading(f"row walk, no {product}")
        fa._dot = real_dot
        shut(0, 0)
        reading("split kernels, dq + dkv")
        reading("split kernels, dq", match="flash_bwd_dq")
        reading("split kernels, dkv", match="flash_bwd_dkv")
    finally:
        fa._dot, fa._bwd_row_kernel = real_dot, real_kernel
        shut(*budgets)
    if {"row walk, whole", "split kernels, dq + dkv"} <= set(grads):
        for name, a, b in zip(("dq", "dk", "dv"), grads["row walk, whole"],
                              grads["split kernels, dq + dkv"]):
            a, b = a.astype(jnp.float32), b.astype(jnp.float32)
            print(f"  {name}: row walk against split kernels, largest "
                  f"difference {float(jnp.abs(a - b).max()):.3e} of "
                  f"{float(jnp.abs(b).max()):.3e}", flush=True)
    return out


def sweep_forward(bh, t, d, dv=None, group=1, window=0, block=1024,
                  iters=10):
    """The forward at several blocks a head, a call alone (module
    docstring): ms a call of the row walk and of the gridded walk, and how
    far their o and lse lie apart. Returns {walk: ms}."""
    import distributed_pytorch_from_scratch_tpu.ops.pallas.flash_attention \
        as fa
    from distributed_pytorch_from_scratch_tpu.ops.attention import (
        CAUSAL, sliding_window)
    dv = dv or d
    mask = sliding_window(window) if window else CAUSAL
    key = jax.random.PRNGKey(bh)
    kq, kk, kv_ = jax.random.split(key, 3)
    q = jax.random.normal(kq, (bh, t, d), jnp.bfloat16)
    k = jax.random.normal(kk, (bh // group, t, d), jnp.bfloat16)
    v = jax.random.normal(kv_, (bh // group, t, dv), jnp.bfloat16)
    kw = dict(t_real=t, block_q=block, block_k=block, hq=group, hkv=1,
              interpret=False, mask=mask)
    budget = fa.KV_ROW_VMEM_BYTES
    resident = fa._fwd_resident_bytes(t, d, dv, 2)
    print(f"forward alone: bh{bh} t{t} d{d}/{dv} group {group} "
          f"{'window ' + str(window) if window else 'causal'} block {block}; "
          f"K and V resident are {resident / 2 ** 20:.1f} MiB of a budget "
          f"of {budget / 2 ** 20:.0f}", flush=True)
    out, got = {}, {}
    try:
        for walk, opened in (("row", max(budget, resident)), ("grid", 0)):
            fa.KV_ROW_VMEM_BYTES = opened
            fn = jax.jit(lambda *a: fa._fwd_call(*a, **kw))
            t0 = time.perf_counter()
            try:
                compiled = fn.lower(q, k, v).compile()
            except Exception as e:  # noqa: BLE001
                print(f"  {walk:5s} FAILED {type(e).__name__}: "
                      f"{str(e)[-300:]!r}", flush=True)
                continue
            compile_s = time.perf_counter() - t0
            out[walk] = device_ms(compiled, q, k, v, match="flash_fwd",
                                  iters=iters)
            got[walk] = compiled(q, k, v)
            print(f"  {walk:5s} walk {out[walk]:8.3f} ms   (trace, lower and "
                  f"compile {compile_s:5.1f} s)", flush=True)
    finally:
        fa.KV_ROW_VMEM_BYTES = budget
    if len(got) == 2:
        for name, a, b in zip(("o", "lse"), got["row"], got["grid"]):
            a, b = a.astype(jnp.float32), b.astype(jnp.float32)
            print(f"  {name}: row walk against gridded, largest difference "
                  f"{float(jnp.abs(a - b).max()):.3e} of "
                  f"{float(jnp.abs(b).max()):.3e}", flush=True)
    return out


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--subtile", action="store_true",
                    help="sweep the causal sub-tile edge inside one grid "
                         "tile (forward and backward calls timed apart)")
    ap.add_argument("--backward", action="store_true",
                    help="the backward of several blocks a head alone at "
                         "--bh --t --d --dv --group: the resident walk "
                         "whole, its DMA, each product knocked out, and "
                         "the two split kernels")
    ap.add_argument("--forward", action="store_true",
                    help="the forward of several blocks a head alone at "
                         "--bh --t --d --dv --group --window: the row walk "
                         "beside the gridded walk")
    ap.add_argument("--window", type=int, default=0,
                    help="--forward, --backward: a sliding window (0: "
                         "causal)")
    ap.add_argument("--group", type=int, default=1,
                    help="--backward, --forward: query heads a kv head")
    ap.add_argument("--bh", default="192,80",
                    help="--subtile: comma-separated batch*heads a chip")
    ap.add_argument("--edges", default="128,256,512,1024",
                    help="--subtile: comma-separated sub-tile shapes, "
                         "an edge (256) or sub_q x sub_k (128x256)")
    ap.add_argument("--t", type=int, default=1024,
                    help="--subtile: sequence length")
    ap.add_argument("--d", type=int, default=64,
                    help="--subtile: width of q and k")
    ap.add_argument("--dv", type=int, default=None,
                    help="--subtile: width of v (default: --d)")
    ap.add_argument("--blocks", default=None,
                    help="--subtile: comma-separated square grid blocks "
                         "(default: --t, one tile a head)")
    ap.add_argument("--quick", action="store_true",
                    help="fewer block combos / iters")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--write_cache", action="store_true",
                    help="--paged: record each shape's winner in the paged "
                         "kernels' table (below). A flash sweep writes "
                         "nothing: its winner is adopted by editing "
                         "flash_attention.DEFAULT_BLOCK or flash_blocks, "
                         "with the reading beside it")
    ap.add_argument("--paged", action="store_true",
                    help="sweep the PAGED-attention kernel instead "
                         "(ops/pallas/paged_attention.py): pages_per_block "
                         "per (page_size, head_dim, kv_dtype) decode "
                         "shape; --write_cache persists to "
                         "PAGED_BLOCKS_CACHE or "
                         "the tracked ops/pallas/paged_blocks.json")
    return ap.parse_args(argv)


def sweep_paged(args):
    """Time the paged decode dispatch per pages_per_block candidate at the
    serving shapes that matter: page sizes {8, 16, 32, 64} x kv_dtype
    {native, int8} at the 45m head shape (kvh8 hd64), GQA (kvh2 group4)
    at the flagship page size. One table row per shape; the winner lands
    in the autotuner table (and the JSON cache with --write_cache)."""
    from distributed_pytorch_from_scratch_tpu.ops.pallas.paged_attention import (  # noqa: E501
        autotune_paged_block_config)

    sweep = (1, 2, 4) if args.quick else (1, 2, 4, 8)
    # NOTE the table key is (page_size, head_dim, kv_dtype, backend) —
    # kv_heads/group are timing context, not key parts — so the GQA
    # shape shares (16, 64, native)'s entry and must sweep FIRST: the
    # flagship kvh8 shape sweeps last so ITS winner is the one that
    # persists (the flash sweep's convention, see main()'s shape list)
    shapes = [(16, 64, None, 2, 4)]               # GQA: kvh2, group 4
    shapes += [(ps, 64, kv, 8, 1) for ps in (8, 16, 32, 64)
               for kv in (None, "int8")]
    for ps, hd, kv, kvh, grp in shapes:
        best = autotune_paged_block_config(
            ps, hd, kv_dtype=kv, kv_heads=kvh, group=grp, sweep=sweep,
            iters=args.iters, write_cache=args.write_cache)
        print(f"  paged ps{ps:3d} hd{hd} kv={kv or 'native'} kvh{kvh} "
              f"g{grp}: best pages_per_block={best.pages_per_block}",
              flush=True)


def main():
    args = parse_args()

    enable_compile_cache()
    if jax.devices()[0].platform != "tpu":
        raise SystemExit(f"tune_flash_blocks sweeps compiled kernels and "
                         f"needs a TPU; devices: {jax.devices()}")
    print("device:", jax.devices()[0].device_kind)

    if args.paged:
        return sweep_paged(args)
    if args.backward:
        for bh in args.bh.split(","):
            for block in (args.blocks or "1024").split(","):
                sweep_backward(int(bh), args.t, args.d, args.dv, args.group,
                               int(block), iters=min(args.iters, 10),
                               window=args.window)
        return
    if args.forward:
        for bh in args.bh.split(","):
            sweep_forward(int(bh), args.t, args.d, args.dv, args.group,
                          args.window, int(args.blocks or 1024),
                          iters=min(args.iters, 10))
        return
    if args.subtile:
        return sweep_subtiles([int(x) for x in args.bh.split(",")],
                              [tuple(int(e) for e in (x.split("x") * 2)[:2])
                               for x in args.edges.split(",")],
                              t=args.t, d=args.d, dv=args.dv,
                              blocks=[int(x) for x in args.blocks.split(",")]
                              if args.blocks else None,
                              iters=max(args.iters, 50))

    sizes = [256, 512, 1024] if args.quick else [128, 256, 512, 1024, 2048]
    blocks = list(itertools.product(sizes, sizes))

    shapes = [("gqa 4x", 32, 8, 2, 1000, 64, args.iters),
              ("long context 8k", 2, 8, 8, 8192, 64,
               max(5, args.iters // 4)),
              ("reference 45m", 32, 8, 8, 1000, 64, args.iters)]
    for name, b, h, hkv, t, d, iters in shapes:
        sweep_shape(name, b, h, hkv, t, d, blocks, iters)


if __name__ == "__main__":
    main()
