"""Time the hyper-connection mixers' Pallas kernels alone on the attached
TPU chip, at one shape (default: the mhc cell's, 4 streams x 4096 tokens x
3584, bf16).

    python scripts/tune_stream_mixer.py [--check] [--blocks 128,256]
        [--rows 32] [--chunks 512]

prints, in device milliseconds from a profiler capture (the host clock
around a call this short also reads the dispatch):

  - each of the four kernels (ops/pallas/stream_mixer.py) by its name, for
    every `--blocks` (tokens a grid step), `--rows` (tokens a group) and
    `--chunks` (columns an element-wise expression takes), beside the least
    bytes the call must move and what share of the chip's 819 GB/s that is;
  - one whole joint, forward and forward + backward (maps, the read, a
    stand-in sublayer, the write), as the kernels' path and as the
    `jax.numpy` text, busy time and the longest ops;
  - with `--check`, the kernels' path against the text ON THE CHIP: the
    value and every gradient (X, W, alpha, b), layer mixer and exit mixer:
    Mosaic's numbers, which the interpreter's tests cannot see.

The variants are built HERE, by setting the module's constants before a
trace; the program has no switch for them. The readings behind the
constants are PERF.md's (section 6, PR 58; TPU v5 lite).
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from distributed_pytorch_from_scratch_tpu.ops.pallas import (
    stream_mixer as kernels)
from distributed_pytorch_from_scratch_tpu.parallel.hyper import StreamMixer
from distributed_pytorch_from_scratch_tpu.runtime.compile_cache import (
    enable_compile_cache)
from tune_delta_rule import capture_ms

HBM_GB_S = 819.0


def inputs(n, t, d, dtype, width, seed=0):
    key = jax.random.key(seed)
    k = lambda i: jax.random.fold_in(key, i)
    X = jax.random.normal(k(0), (n, t, d), jnp.float32).astype(dtype)
    row = lambda i: jax.random.normal(k(i), (t, d), jnp.float32).astype(dtype)
    w = jax.random.uniform(k(1), (n * d, width), jnp.float32, -1, 1) \
        / np.sqrt(n * d)
    res = jax.nn.softmax(jax.random.normal(k(2), (n, n, t)), axis=1)
    post = 2 * jax.nn.sigmoid(jax.random.normal(k(3), (n, t)))
    return dict(X=X, y=row(4), du=row(5), w=w, alpha0=jnp.float32(1.0),
                b_pre=jax.random.normal(k(6), (n,)), res=res, post=post,
                dout=jax.random.normal(k(7), (n, t, d), jnp.float32).astype(
                    dtype),
                dm=jax.random.normal(k(8), (width, t)) * 1e-2)


def time_kernels(args, dtype):
    n, t, d = args.n, args.t, args.d
    width = n * n + 2 * n
    a = inputs(n, t, d, dtype, width)
    item = jnp.dtype(dtype).itemsize
    least = {  # the streams a call must move, bytes
        kernels.READ_FWD: (n + 1) * t * d * item,
        kernels.WRITE_FWD: (2 * n + 1) * t * d * item,
        kernels.WRITE_BWD: (2 * n + 2) * t * d * item,
        kernels.READ_BWD: (3 * n + 1) * t * d * item}
    kw = dict(width=width, eps=1e-6)
    for rows in args.rows:
        for chunk in args.chunks:
            for block in args.blocks:
                kernels.ROWS, kernels.LANE_CHUNK = rows, chunk
                rf = jax.jit(lambda: kernels.read_forward(
                    a["X"], a["w"], a["alpha0"], a["b_pre"], norm_eps=1e-6,
                    block=block, **kw))
                tok = rf()[2]
                hc, pc = kernels.coefficients(a["res"], a["post"], block)
                calls = {
                    kernels.READ_FWD: rf,
                    kernels.WRITE_FWD: jax.jit(lambda: kernels.write_forward(
                        a["X"], a["y"], hc, pc, block=block)),
                    kernels.WRITE_BWD: jax.jit(lambda: kernels.write_backward(
                        a["X"], a["y"], hc, pc, a["dout"], part=False,
                        block=block)),
                    kernels.READ_BWD: jax.jit(lambda: kernels.read_backward(
                        a["X"], a["w"], a["alpha0"], a["b_pre"], tok,
                        a["dm"], a["du"], (a["dout"], hc), block=block,
                        **kw))}
                for name, fn in calls.items():
                    if name not in args.kernels:
                        continue
                    try:
                        ms = capture_ms(fn)
                    except Exception as e:  # a block Mosaic refuses
                        print(json.dumps({"kernel": name, "block": block,
                                          "rows": rows, "chunk": chunk,
                                          "error": str(e)[:400]}), flush=True)
                        continue
                    mine = sum(v for k, v in ms.items() if k.startswith(name))
                    print(json.dumps({
                        "kernel": name, "block": block, "rows": rows,
                        "chunk": chunk, "device_ms": round(mine, 4),
                        "busy_ms": round(ms["busy"], 4),
                        "least_mb": round(least[name] / 1e6, 1),
                        "hbm_share_pct": round(
                            100 * least[name] / 1e6 / mine / HBM_GB_S, 1)}),
                        flush=True)


def joint(mixer, dtype):
    """One joint with a stand-in sublayer: a function of (params, X)."""
    def loss(p, X, ct):
        if mixer.exit_only:
            out = mixer.exit(p, X)
        else:
            maps = mixer.maps(p, X)
            u = mixer.pre(maps, X)
            y = jnp.tanh(u.astype(jnp.float32) * 0.7).astype(dtype)
            out = mixer.post(maps, X, y)
        return jnp.sum(out.astype(jnp.float32) * ct)
    return loss


def time_joint(args, dtype):
    n, t, d = args.n, args.t, args.d
    X = jax.random.normal(jax.random.key(1), (n, 1, t, d),
                          jnp.float32).astype(dtype)
    ct = jax.random.normal(jax.random.key(2), (n, 1, t, d), jnp.float32)
    for path in ("kernels", "text"):
        mixer = StreamMixer(d, n)
        if path == "text":
            undo, kernels.holds = kernels.holds, lambda *_: False
        p = mixer.init(jax.random.key(0))
        fwd = jax.jit(joint(mixer, dtype))
        both = jax.jit(jax.value_and_grad(joint(mixer, dtype), (0, 1)))
        for tag, fn in (("fwd", fwd), ("fwd+bwd", both)):
            ms = capture_ms(fn, p, X, ct)
            top = sorted(((v, k) for k, v in ms.items() if k != "busy"),
                         reverse=True)[:8]
            print(json.dumps({"joint": path, "pass": tag,
                              "busy_ms": round(ms["busy"], 4),
                              "top": [[k, round(v, 4)] for v, k in top]}),
                  flush=True)
        if path == "text":
            kernels.holds = undo


def check(args, dtype):
    n, d, t = args.n, args.d, args.check_t
    for exit_only in (False, True):
        mixer = StreamMixer(d, n, exit_only=exit_only)
        p = mixer.init(jax.random.key(0))
        X = jax.random.normal(jax.random.key(1), (n, 1, t, d),
                              jnp.float32).astype(dtype)
        ct = jax.random.normal(jax.random.key(2), (n, 1, t, d), jnp.float32)
        if exit_only:
            ct = ct[0]
        got = jax.jit(jax.value_and_grad(joint(mixer, dtype), (0, 1)))(
            p, X, ct)
        undo, kernels.holds = kernels.holds, lambda *_: False
        want = jax.jit(jax.value_and_grad(joint(mixer, dtype), (0, 1)))(
            p, X, ct)
        kernels.holds = undo
        rel = lambda a, b: float(
            np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32)
                   ).max() / max(np.abs(np.asarray(b, np.float32)).max(),
                                 1e-30))
        print(json.dumps({
            "check": "exit" if exit_only else "layer", "dtype": str(dtype),
            "tokens": t, "loss": [float(got[0]), float(want[0])],
            "rel_err": {"X": rel(got[1][1], want[1][1]),
                        **{k: rel(got[1][0][k], want[1][0][k])
                           for k in sorted(want[1][0])}}}), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n", type=int, default=4)
    ap.add_argument("--t", type=int, default=4096)
    ap.add_argument("--d", type=int, default=3584)
    ap.add_argument("--dtype", default="bfloat16")
    ints = lambda s: [int(x) for x in s.split(",")]
    ap.add_argument("--blocks", type=ints, default=[128, 256])
    ap.add_argument("--rows", type=ints, default=[kernels.ROWS])
    ap.add_argument("--chunks", type=ints, default=[kernels.LANE_CHUNK])
    ap.add_argument("--kernels", type=lambda s: s.split(","),
                    default=[kernels.READ_FWD, kernels.WRITE_FWD,
                             kernels.WRITE_BWD, kernels.READ_BWD])
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--check_t", type=int, default=1000)
    ap.add_argument("--no_joint", action="store_true")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if jax.default_backend() != "tpu":
        sys.exit("tune_stream_mixer.py times the chip: no TPU attached")
    dtype = jnp.dtype(args.dtype)
    rows, chunk = kernels.ROWS, kernels.LANE_CHUNK
    if args.check:
        check(args, dtype)
        check(args, jnp.dtype("float32"))
    time_kernels(args, dtype)
    kernels.ROWS, kernels.LANE_CHUNK = rows, chunk
    if not args.no_joint:
        time_joint(args, dtype)
    return 0


if __name__ == "__main__":
    sys.exit(main())
