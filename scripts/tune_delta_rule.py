"""Time the chunked gated delta rule alone on the attached TPU chip, at one
shape (default: the hybrid cell's, 32 heads x 8192 tokens, 128 / 128, bf16,
chunk 64, ONE sequence: the rule runs a sequence at a time).

    python scripts/tune_delta_rule.py --h 32 --t 8192 --dk 128 --dv 128

prints, in device milliseconds from a profiler capture (the host clock
around a call this short also reads the dispatch):

  - the rule's Pallas kernels (ops/pallas/delta_rule.py: a chunk's
    operands made in VMEM, then the walk), forward, forward with residuals
    and backward, by the kernels' names, for each `--blocks` (heads x
    chunks a grid step);
  - the whole rule for one sequence, forward and forward + backward, as the
    kernels' path and as the `lax.scan` text, with the scan's `while` ops
    and any XLA custom call apart;
  - with `--knockouts`, the kernels with one part of the chunk step taken
    out (wrong numbers, right time): what the part costs is the
    difference. The variants are built HERE, by replacing the module's
    functions; the program has no switch for them;
  - with `--check`, the kernels' path against the `lax.scan` text on the
    chip (value, final state, all five gradients, at `--check_t` tokens):
    Mosaic's numbers, which the interpreter's tests cannot see;
  - with `--solve`, (I + A)^-1 [W | U] alone: XLA's `triangular_solve`
    (what the rule had before PR 36) against `solve_unit_lower` at several
    base blocks and with its block products on the matrix unit;
  - with `--channel`, all of the above but the knock-outs and the solve
    for the rule with a decay a CHANNEL (ops/pallas/kda_rule.py:
    `kda_rule_pairs`, `kda_rule_fwd`, `kda_rule_bwd`) at Kimi Delta
    Attention's cell (default 32 heads x 4096 tokens, 128 / 128, bf16):
    `--blocks` and `--turns` sweep that module's constants, the whole rule
    is `channel_delta_rule` as kernels and as its XLA text, and `--check`
    compares them on the chip.

The readings behind the modules' constants are PERF.md's (section 6, PRs 36
and 38, one sequence; PR 60 for `--channel`; TPU v5 lite).
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
    delta_rule as kernels, kda_rule as channel_kernels)
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


def rule_inputs(h, t, dk, dv, dtype, seed=1, channel=False):
    """One sequence's q, k, v, g, beta as the layer makes them: unit keys,
    queries scaled, (1, h, t, .); with `channel` a decay a channel under
    the bounded gate, -5 < g < 0."""
    keys = jax.random.split(jax.random.key(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = (unit(jax.random.normal(keys[0], (1, h, t, dk))) / dk ** 0.5)
    k = unit(jax.random.normal(keys[1], (1, h, t, dk)))
    v = jax.random.normal(keys[2], (1, h, t, dv))
    g = (-5.0 * jax.nn.sigmoid(jax.random.normal(keys[3], (1, h, t, dk)) - 2.0)
         if channel else
         -jax.nn.softplus(jax.random.normal(keys[3], (1, h, t))))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (1, h, t)))
    return (*(z.astype(dtype) for z in (q, k, v)), g, beta)


def kernel_operands(args, dtype):
    """The kernels' operands for one sequence, made as the rule makes them
    (`_kernel_inputs`, `_inverses`: T is a real inverse), and random
    residuals."""
    h, t, dk, dv = args.h, args.t, args.dk, args.dv
    def make(*a):
        inputs = rule._kernel_inputs(*(z[0] for z in a), chunk=args.chunk)
        return (*inputs, rule._inverses(1, inputs[1], inputs[3]))
    inputs = jax.jit(make)(*rule_inputs(h, t, dk, dv, dtype))
    n = inputs[0].shape[1]
    keys = jax.random.split(jax.random.key(0), 3)
    S_in = jax.random.normal(
        keys[0], (h, n // kernels._blocks(h, n)[1], dk, dv), jnp.float32)
    do = jax.random.normal(keys[1], (h, n, args.chunk, dv)).astype(dtype)
    dS = jax.random.normal(keys[2], (h, dk, dv), jnp.float32)
    return inputs, (S_in, do, dS)


def time_kernels(args, dtype, tag="", operands=None):
    """The three kernel calls at the module's blocks as they stand."""
    fwd_in, bwd_in = operands or kernel_operands(args, dtype)
    row = {}
    for name, residuals in (("fwd", False), ("fwd_res", True)):
        fn = jax.jit(lambda *a, r=residuals: kernels.rule_forward(
            *a, residuals=r))
        row[name] = named(capture_ms(fn, *fwd_in), kernels.FWD_NAME)
    fn = jax.jit(lambda *a: kernels.rule_backward(*a))
    row["bwd"] = named(capture_ms(fn, *fwd_in, *bwd_in), kernels.BWD_NAME)
    print(f"  kernels {tag:30s} fwd {row['fwd']:7.3f}  fwd+residuals "
          f"{row['fwd_res']:7.3f}  bwd {row['bwd']:7.3f} ms", flush=True)
    return row


def channel_operands(args, dtype):
    """The channel kernels' operands for one sequence, made as the rule
    makes them (T a real inverse), and random residuals."""
    h, dk, dv = args.h, args.dk, args.dv
    def make(*a):
        inputs = rule._channel_kernel_inputs(*(z[0] for z in a),
                                             chunk=args.chunk)
        return (*inputs, rule._channel_inverses(
            1, inputs[1], inputs[3], sub=rule.SUB, interpret=False))
    inputs = jax.jit(make)(*rule_inputs(h, args.t, dk, dv, dtype,
                                        channel=True))
    n = inputs[0].shape[1]
    keys = jax.random.split(jax.random.key(0), 3)
    S_in = jax.random.normal(
        keys[0], (h, n // channel_kernels.blocks(h, n)[1], dv, dk),
        jnp.float32)
    do = jax.random.normal(keys[1], (h, n, args.chunk, dv)).astype(dtype)
    dS = jax.random.normal(keys[2], (h, dv, dk), jnp.float32)
    return inputs, (S_in, do, dS)


def time_channel_kernels(args, dtype, tag=""):
    """The channel rule's four kernel calls at the module's blocks as they
    stand, by name."""
    K = channel_kernels
    fwd_in, bwd_in = channel_operands(args, dtype)
    sub = rule.SUB
    row = {"pairs": named(capture_ms(jax.jit(lambda k, gb: K.rule_pairs(
        k, gb, sub=sub)), fwd_in[1], fwd_in[3]), K.PAIRS_NAME)}
    for name, residuals in (("fwd", False), ("fwd_res", True)):
        fn = jax.jit(lambda *a, r=residuals: K.rule_forward(
            *a, sub=sub, residuals=r))
        row[name] = named(capture_ms(fn, *fwd_in), K.FWD_NAME)
    fn = jax.jit(lambda *a: K.rule_backward(*a, sub=sub))
    row["bwd"] = named(capture_ms(fn, *fwd_in, *bwd_in), K.BWD_NAME)
    print(f"  channel kernels {tag:24s} pairs {row['pairs']:7.3f}  fwd "
          f"{row['fwd']:7.3f}  fwd+residuals {row['fwd_res']:7.3f}  bwd "
          f"{row['bwd']:7.3f} ms", flush=True)
    return row


def rule_paths(args):
    """The whole rule as the program runs it on the chip and as the XLA
    text with its `lax.scan`, which it runs everywhere else."""
    if args.channel:
        return {
            "kernels": lambda *a: rule.channel_delta_rule(
                *a, chunk=args.chunk),
            "scan": lambda *a: jax.lax.map(
                lambda r: jax.checkpoint(lambda *s: rule._one_sequence_channel(
                    *s, chunk=args.chunk, sub=rule.SUB))(*r), a)}
    return {
        "kernels": lambda *a: rule.gated_delta_rule(*a, chunk=args.chunk),
        "scan": lambda *a: jax.lax.map(
            lambda r: jax.checkpoint(lambda *s: rule._one_sequence(
                *s, chunk=args.chunk))(*r), a)}


def check(args, dtype):
    """The kernels' path against the `lax.scan` text, on the chip."""
    a = rule_inputs(args.h, args.check_t, args.dk, args.dv, dtype, seed=2,
                    channel=args.channel)
    paths = rule_paths(args)
    f32 = jnp.float32

    def scalar(path):
        def loss(*a):
            o, S = path(*a)
            o = o.astype(f32)
            return jnp.sum(o * jnp.cos(o)) + jnp.sum(S * S)
        return loss
    got, want = ((jax.jit(path)(*a), jax.jit(jax.grad(
        scalar(path), argnums=(0, 1, 2, 3, 4)))(*a))
        for path in (paths["kernels"], paths["scan"]))
    rel = lambda x, y: float(
        jnp.linalg.norm((x.astype(f32) - y.astype(f32)).ravel())
        / jnp.maximum(jnp.linalg.norm(y.astype(f32).ravel()), 1e-30))
    names = ("o", "S", "dq", "dk", "dv", "dg", "dbeta")
    errs = {n: rel(x, y) for n, x, y in zip(
        names, (*got[0], *got[1]), (*want[0], *want[1]))}
    finite = all(bool(jnp.all(jnp.isfinite(x.astype(f32))))
                 for x in (*got[0], *got[1]))
    print(f"  check {dtype.name} t{args.check_t}: relative L2 against the "
          f"scan text: " + ", ".join(f"{n} {e:.2e}" for n, e in errs.items())
          + f"; finite {finite}", flush=True)


def time_rule(args, dtype):
    """One sequence's whole rule, both paths, forward and with backward."""
    q, k, v, g, beta = rule_inputs(args.h, args.t, args.dk, args.dv, dtype,
                                   channel=args.channel)
    paths = rule_paths(args)
    prefix = "kda_rule_" if args.channel else "gdn_rule_"
    for name in args.paths.split(","):
        path = paths[name]
        loss = lambda *a, path=path: jnp.sum(
            path(*a)[0].astype(jnp.float32) ** 2)
        for what, fn in (("fwd", jax.jit(path)),
                         ("fwd+bwd", jax.jit(jax.grad(
                             loss, argnums=(0, 1, 2, 3, 4))))):
            ms = capture_ms(fn, q, k, v, g, beta, iters=3)
            top = sorted(((v_, k_) for k_, v_ in ms.items() if k_ != "busy"
                          and not k_.startswith("while")), reverse=True)[:args.top]
            print(f"  rule {name:8s} {what:8s} busy {ms['busy']:8.3f} ms  "
                  f"kernels {named(ms, prefix):7.3f}  while "
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
    fwd, bwd, dot32 = kernels._fwd_chunk, kernels._bwd_block, kernels._dot32
    exp = jnp.exp
    f32 = jnp.float32

    def fwd_dma(S, q, k, v, G, beta, g_end, e, T):       # DMA alone
        yield       # a chain of one stretch, as `_in_turn` takes them
        return v.astype(f32) + q[:, :1] + k[:, :1] + T[:, :1], S

    def bwd_dma(dS, S, chunks):
        out = []
        for q, k, v, do, G, beta, g_end, e, T in chunks:
            x = q.astype(f32) + k + v[:, :1] + do[:, :1] + T[:, :1]
            out.append((x, x, v.astype(f32) + do, G + S[:1, :1], beta))
        yield       # a chain of one stretch, as `_in_turn` takes them
        return out, dS

    one_pass = lambda a, b, dims: kernels._dot(
        a.astype(dtype), b.astype(dtype), dims)

    operands = kernel_operands(args, dtype)
    time_kernels(args, dtype, "whole", operands)
    kernels._fwd_chunk, kernels._bwd_block = fwd_dma, bwd_dma
    try:
        time_kernels(args, dtype, "DMA alone", operands)
    finally:
        kernels._fwd_chunk, kernels._bwd_block = fwd, bwd
    # T rhs and its two transposes as one bf16 pass, and as nothing
    for name, dot in (("HIGHEST products in one pass", one_pass),
                      ("no T rhs (no HIGHEST product)",
                       lambda a, b, dims: a[:, :a.shape[0]]
                       if dims == kernels._NT else b)):
        kernels._dot32 = dot
        try:
            time_kernels(args, dtype, name, operands)
        except Exception as e:  # noqa: BLE001 - a knock-out Mosaic refuses
            print(f"  kernels {name} FAILED {type(e).__name__}: "
                  f"{str(e)[-300:]!r}", flush=True)
        finally:
            kernels._dot32 = dot32
    # the decays: no exponential (the masks and products stay)
    jnp.exp = lambda x: x
    try:
        time_kernels(args, dtype, "no decay (exp taken out)", operands)
    finally:
        jnp.exp = exp


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--channel", action="store_true",
                    help="the rule with a decay a channel and its kernels "
                         "(ops/pallas/kda_rule.py), at --t 4096 by default")
    ap.add_argument("--h", type=int, default=32)
    ap.add_argument("--t", type=int, default=None,
                    help="tokens (default 8192; 4096 with --channel)")
    ap.add_argument("--dk", type=int, default=128)
    ap.add_argument("--dv", type=int, default=128)
    ap.add_argument("--chunk", type=int, default=rule.CHUNK)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--blocks", default=None,
                    help="comma-separated heads x chunks a grid step to "
                         "sweep (8x2,8x4,16x1); default: the module's")
    ap.add_argument("--turns", default=None,
                    help="comma-separated heads whose backward chains are "
                         "traced side by side (1,2,4,8); default: the "
                         "module's")
    ap.add_argument("--knockouts", action="store_true")
    ap.add_argument("--check", action="store_true",
                    help="the kernels' path against the scan text's numbers")
    ap.add_argument("--check_t", type=int, default=1000)
    ap.add_argument("--solve", action="store_true",
                    help="time the solve alone, its variants swept")
    ap.add_argument("--no_kernels", action="store_true")
    ap.add_argument("--paths", default="kernels,scan",
                    help="which whole rules to time")
    ap.add_argument("--top", type=int, default=6,
                    help="how many of the whole rule's longest ops to name")
    ap.add_argument("--no_rule", action="store_true",
                    help="the kernels alone, not the whole rule")
    args = ap.parse_args(argv)
    if args.t is None:
        args.t = 4096 if args.channel else 8192
    if args.channel and (args.knockouts or args.solve):
        ap.error("--knockouts and --solve are the scalar rule's")
    return args


def main():
    args = parse_args()
    enable_compile_cache()
    if jax.devices()[0].platform != "tpu":
        raise SystemExit(f"tune_delta_rule times compiled kernels and needs "
                         f"a TPU; devices: {jax.devices()}")
    dtype = jnp.dtype(args.dtype)
    print(f"device: {jax.devices()[0].device_kind}; h{args.h} t{args.t} "
          f"{args.dk}/{args.dv} chunk {args.chunk} {dtype.name}", flush=True)
    if args.check:
        for check_dtype in (dtype, jnp.dtype("float32")):
            check(args, check_dtype)
    if args.solve:
        time_solves(args)
    K = channel_kernels if args.channel else kernels
    time_them = time_channel_kernels if args.channel else time_kernels
    was = K.HEAD_BLOCK, K.CHUNK_BLOCK
    for blocks in ([] if args.no_kernels else args.blocks.split(",")
                   if args.blocks else [None]):
        if blocks:
            K.HEAD_BLOCK, K.CHUNK_BLOCK = map(int, blocks.split("x"))
        try:
            time_them(args, dtype, f"blocks {K.HEAD_BLOCK}x{K.CHUNK_BLOCK}")
        except Exception as e:  # noqa: BLE001 - Mosaic refuses a block
            print(f"  blocks {blocks} FAILED {type(e).__name__}: "
                  f"{str(e)[-300:]!r}", flush=True)
    K.HEAD_BLOCK, K.CHUNK_BLOCK = was
    in_turn = (("HEADS_IN_TURN", "BWD_HEADS_IN_TURN") if args.channel
               else ("HEADS_IN_TURN",))
    was = [getattr(K, name) for name in in_turn]
    for turns in (args.turns.split(",") if args.turns else []):
        for name in in_turn:
            setattr(K, name, int(turns))
        try:
            time_them(args, dtype, f"{turns} heads in turn")
        except Exception as e:  # noqa: BLE001
            print(f"  turns {turns} FAILED {type(e).__name__}: "
                  f"{str(e)[-300:]!r}", flush=True)
    for name, value in zip(in_turn, was):
        setattr(K, name, value)
    if args.knockouts:
        knockouts(args, dtype)
    if not args.no_rule:
        time_rule(args, dtype)


if __name__ == "__main__":
    main()
