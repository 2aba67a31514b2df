"""Time the chunked gated delta rule alone on the attached TPU chip, at one
shape (default: the hybrid cell's, 32 heads x 8192 tokens, 128 / 128, bf16,
chunk 64, ONE sequence: the rule runs a sequence at a time).

    python scripts/tune_delta_rule.py --h 32 --t 8192 --dk 128 --dv 128

prints, in device milliseconds from a profiler capture (the host clock
around a call this short also reads the dispatch):

  - the walk over the chunks as the Pallas kernels (ops/pallas/
    delta_rule.py), forward, forward with residuals and backward, by the
    kernels' names, for each `--blocks` (heads x chunks a grid step);
  - the whole rule for one sequence, forward and forward + backward, as the
    kernels' path and as the `lax.scan` text, with the scan's `while` ops
    and any XLA custom call apart;
  - with `--knockouts`, the forward and backward kernels with one part of
    the chunk step taken out (wrong numbers, right time): what the part
    costs is the difference. The variants are built HERE, by replacing the
    module's chunk-step functions; the program has no switch for them;
  - with `--solve`, (I + A)^-1 [W | U] alone: XLA's `triangular_solve`
    (what the rule had before PR 36) against `solve_unit_lower` at several
    base blocks and with its block products on the matrix unit.

The readings behind the module's constants (PERF.md section 6, PR 36; one
sequence, TPU v5 lite): the walk at 4x1 / 8x1 / 8x2 / 8x4 / 16x2 / 16x4
heads x chunks a grid step 1.036 / 0.889 / 0.778 / 0.760 / 0.762 / 0.760
ms forward, 1.999 / 1.766 / 1.687 / 1.674 / 1.667 / 1.706 backward; every
knock-out within 0.1 ms of the whole (DMA alone 0.671 / 1.688): the DMA
binds. The solve 4.31 ms as XLA's, 1.82 at base 8 (1.83 at 16, 1.84 at
4), 4.64 with its block products on the matrix unit.
"""

import argparse
import glob
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from distributed_pytorch_from_scratch_tpu.ops import delta_rule as rule
from distributed_pytorch_from_scratch_tpu.ops.pallas import (
    delta_rule as kernels)
from distributed_pytorch_from_scratch_tpu.runtime.compile_cache import (
    enable_compile_cache)


def capture_ms(fn, *args, iters=5):
    """{op name: device ms a call} over the leaf ops of `iters` calls of
    `fn`, plus "busy": the union of every op's interval (a `while` holds
    its body's ops, so a sum would count them twice)."""
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
    events = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
              for plane in data.planes if plane.name == "/device:TPU:0"
              for line in plane.lines if line.name == "XLA Ops"
              for ev in line.events]
    out, busy, end = {}, 0, 0
    for a, b, name in sorted(events):
        name = name.split(" = ")[0].lstrip("%")
        out[name] = out.get(name, 0.0) + (b - a) / iters / 1e6
        busy += max(b - max(a, end), 0)
        end = max(end, b)
    out["busy"] = busy / iters / 1e6
    return out


def named(ms, *parts):
    return sum(v for k, v in ms.items() if any(p in k for p in parts))


def walk_operands(h, t, dk, dv, chunk, dtype, seed=0):
    """Random operands of the walk at the shapes `_walk_operands` makes."""
    n = t // chunk
    keys = iter(jax.random.split(jax.random.key(seed), 9))
    normal = lambda shape, dt, scale=1.0: (
        scale * jax.random.normal(next(keys), shape, jnp.float32)).astype(dt)
    WU = normal((h, n, chunk, dk + dv), jnp.float32, 0.1)
    attn = normal((h, n, chunk, chunk), dtype, 0.1)
    q_in = normal((h, n, chunk, dk), dtype, 0.1)
    k_out = normal((h, n, chunk, dk), dtype, 0.1)
    decay = jnp.exp(-jnp.abs(normal((h, n), jnp.float32)))
    S_in = normal((h, n, dk, dv), jnp.float32)
    v_new = normal((h, n, chunk, dv), dtype)
    do = normal((h, n, chunk, dv), dtype)
    dS = normal((h, dk, dv), jnp.float32)
    return (WU, attn, q_in, k_out, decay), (S_in, v_new, do, dS)


def time_walks(args, dtype, tag=""):
    """The three kernel calls at the module's blocks as they stand."""
    fwd_in, bwd_in = walk_operands(args.h, args.t, args.dk, args.dv,
                                   args.chunk, dtype)
    row = {}
    for name, residuals in (("fwd", False), ("fwd_res", True)):
        fn = jax.jit(lambda *a, r=residuals: kernels.walk_forward(
            *a, out_dtype=dtype, residuals=r))
        row[name] = named(capture_ms(fn, *fwd_in), kernels.FWD_NAME)
    fn = jax.jit(lambda *a: kernels.walk_backward(*a))
    row["bwd"] = named(capture_ms(fn, *fwd_in, *bwd_in), kernels.BWD_NAME)
    print(f"  walk {tag:28s} fwd {row['fwd']:7.3f}  fwd+residuals "
          f"{row['fwd_res']:7.3f}  bwd {row['bwd']:7.3f} ms", flush=True)
    return row


def time_rule(args, dtype):
    """One sequence's whole rule, both paths, forward and with backward."""
    keys = jax.random.split(jax.random.key(1), 5)
    h, t, dk, dv = args.h, args.t, args.dk, args.dv
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = (unit(jax.random.normal(keys[0], (1, h, t, dk))) / dk ** 0.5)
    k = unit(jax.random.normal(keys[1], (1, h, t, dk)))
    v = jax.random.normal(keys[2], (1, h, t, dv))
    g = -jax.nn.softplus(jax.random.normal(keys[3], (1, h, t)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (1, h, t)))
    q, k, v = (z.astype(dtype) for z in (q, k, v))
    paths = {
        "kernels": lambda *a: rule.gated_delta_rule(*a, chunk=args.chunk),
        "scan": lambda *a: jax.lax.map(
            lambda r: jax.checkpoint(lambda *s: rule._one_sequence(
                *s, chunk=args.chunk))(*r), a),
    }
    for name, path in paths.items():
        loss = lambda *a, path=path: jnp.sum(
            path(*a)[0].astype(jnp.float32) ** 2)
        for what, fn in (("fwd", jax.jit(path)),
                         ("fwd+bwd", jax.jit(jax.grad(
                             loss, argnums=(0, 1, 2, 3, 4))))):
            ms = capture_ms(fn, q, k, v, g, beta, iters=3)
            top = sorted(((v_, k_) for k_, v_ in ms.items() if k_ != "busy"
                          and not k_.startswith("while")), reverse=True)[:6]
            print(f"  rule {name:8s} {what:8s} busy {ms['busy']:8.3f} ms  "
                  f"walk kernels {named(ms, 'gdn_rule_'):7.3f}  while "
                  f"{named(ms, 'while'):8.3f}  solve "
                  f"{named(ms, 'custom-call'):7.3f}  top: "
                  + ", ".join(f"{k_} {v_:.2f}" for v_, k_ in top),
                  flush=True)


def time_solves(args):
    """(I + A)^-1 [W | U] for one sequence's chunks: XLA's triangular solve
    (what the rule had) against `solve_unit_lower`, whose diagonal base
    block and whose block products are swept here."""
    h, n, C = args.h, args.t // args.chunk, args.chunk
    A = 0.1 * jnp.tril(jax.random.normal(jax.random.key(2), (h, n, C, C)), -1)
    rhs = jax.random.normal(jax.random.key(3), (h, n, C, args.dk + args.dv))
    xla = lambda A, rhs: jax.lax.linalg.triangular_solve(
        A + jnp.eye(C, dtype=A.dtype), rhs, left_side=True, lower=True,
        unit_diagonal=True)
    on_mxu = lambda X, Y: jnp.einsum("gijn,gjkn->gikn", X, Y,
                                     precision=jax.lax.Precision.HIGHEST)
    base, product = rule.SOLVE_BASE, rule._batch_minor_product
    want = None
    for name, solve, b, prod in (
            ("xla triangular_solve", xla, base, product),
            ("halves base 8", rule.solve_unit_lower, 8, product),
            ("halves base 16", rule.solve_unit_lower, 16, product),
            ("halves base 4", rule.solve_unit_lower, 4, product),
            ("halves base 8, MXU products", rule.solve_unit_lower, 8, on_mxu),
            ("halves base 16, MXU products", rule.solve_unit_lower, 16,
             on_mxu)):
        rule.SOLVE_BASE, rule._batch_minor_product = b, prod
        try:
            fwd = jax.jit(lambda A, rhs, solve=solve: solve(A, rhs))
            bwd = jax.jit(jax.grad(lambda A, rhs, solve=solve: jnp.sum(
                solve(A, rhs) ** 2), argnums=(0, 1)))
            got = fwd(A, rhs)
            want = got if want is None else want
            err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
            f, b_ = capture_ms(fwd, A, rhs), capture_ms(bwd, A, rhs)
            top = sorted(((v, k) for k, v in f.items() if k != "busy"),
                         reverse=True)[:4]
            print(f"  solve {name:30s} fwd busy {f['busy']:7.3f}  fwd+bwd "
                  f"busy {b_['busy']:7.3f} ms  against xla {err:.1e}  top: "
                  + ", ".join(f"{k} {v:.2f}" for v, k in top), flush=True)
        except Exception as e:  # noqa: BLE001
            print(f"  solve {name} FAILED {type(e).__name__}: "
                  f"{str(e)[-300:]!r}", flush=True)
        finally:
            rule.SOLVE_BASE, rule._batch_minor_product = base, product


def knockouts(args, dtype):
    """The kernels with one part of the chunk step taken out."""
    fwd, bwd = kernels._fwd_chunk, kernels._bwd_chunk
    dot, NN, TN, NT = kernels._dot, kernels._NN, kernels._TN, kernels._NT

    def no_state_write(S, W, U, attn, q_in, k_out, e):
        o, v_new, _ = fwd(S, W, U, attn, q_in, k_out, e)
        return o, v_new, S

    def no_attn(S, W, U, attn, q_in, k_out, e):
        Sb = S.astype(q_in.dtype)
        v_new = (U - dot(W.astype(q_in.dtype), Sb, NN)).astype(q_in.dtype)
        return dot(q_in, Sb, NN), v_new, e * S + dot(k_out, v_new, TN)

    def no_products(S, W, U, attn, q_in, k_out, e):     # DMA alone
        return U + q_in[:, :1] + k_out[:, :1] + W[:, :1] + attn[:, :1], \
            U.astype(q_in.dtype), S

    def bwd_no_state(dS, S, W, attn, q_in, k_out, v_new, do, e):
        *outs, _ = bwd(dS, S, W, attn, q_in, k_out, v_new, do, e)
        return (*outs, dS)

    def bwd_no_products(dS, S, W, attn, q_in, k_out, v_new, do, e):
        f32 = jnp.float32
        x = (do + v_new).astype(f32) + W + (q_in + k_out).astype(f32)
        return (x, x, attn.astype(f32), x, x,
                (dS * S)[:8], dS)

    time_walks(args, dtype, "whole")
    for name, f, b in (("no state update", no_state_write, bwd_no_state),
                       ("no attn @ v_new (fwd)", no_attn, bwd),
                       ("no products: DMA alone", no_products,
                        bwd_no_products)):
        kernels._fwd_chunk, kernels._bwd_chunk = f, b
        try:
            time_walks(args, dtype, name)
        finally:
            kernels._fwd_chunk, kernels._bwd_chunk = fwd, bwd


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--h", type=int, default=32)
    ap.add_argument("--t", type=int, default=8192)
    ap.add_argument("--dk", type=int, default=128)
    ap.add_argument("--dv", type=int, default=128)
    ap.add_argument("--chunk", type=int, default=rule.CHUNK)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--blocks", default=None,
                    help="comma-separated heads x chunks a grid step to "
                         "sweep (8x2,8x4,16x1); default: the module's")
    ap.add_argument("--knockouts", action="store_true")
    ap.add_argument("--solve", action="store_true",
                    help="time the solve alone, its variants swept")
    ap.add_argument("--no_walks", action="store_true")
    ap.add_argument("--no_rule", action="store_true",
                    help="the walks alone, not the whole rule")
    return ap.parse_args(argv)


def main():
    args = parse_args()
    enable_compile_cache()
    if jax.devices()[0].platform != "tpu":
        raise SystemExit(f"tune_delta_rule times compiled kernels and needs "
                         f"a TPU; devices: {jax.devices()}")
    dtype = jnp.dtype(args.dtype)
    print(f"device: {jax.devices()[0].device_kind}; h{args.h} t{args.t} "
          f"{args.dk}/{args.dv} chunk {args.chunk} {dtype.name}", flush=True)
    if args.solve:
        time_solves(args)
    was = kernels.HEAD_BLOCK, kernels.CHUNK_BLOCK
    for blocks in ([] if args.no_walks else args.blocks.split(",")
                   if args.blocks else [None]):
        if blocks:
            kernels.HEAD_BLOCK, kernels.CHUNK_BLOCK = map(
                int, blocks.split("x"))
        try:
            time_walks(args, dtype, f"blocks {kernels.HEAD_BLOCK}x"
                                    f"{kernels.CHUNK_BLOCK}")
        except Exception as e:  # noqa: BLE001 - Mosaic refuses a block
            print(f"  blocks {blocks} FAILED {type(e).__name__}: "
                  f"{str(e)[-300:]!r}", flush=True)
    kernels.HEAD_BLOCK, kernels.CHUNK_BLOCK = was
    if args.knockouts:
        knockouts(args, dtype)
    if not args.no_rule:
        time_rule(args, dtype)


if __name__ == "__main__":
    main()
