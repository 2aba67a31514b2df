"""The `train_ckpt` runner: the `train` runner's job with the host side of a
step going through the program, and a periodic asynchronous checkpoint.

No cell of `BENCHMARK.json` names this runner yet. Its cell,
`gpt2-medium.train-ckpt` (`workloads/gpt2-medium.train-ckpt.json`), ran on
the chip (PERF.md sections 5 and 6, PR 25) but cannot be admitted:
`step_ms_p90`, which every cell has to report, lies on an edge there and
spreads by more than half its bound. What a `benchmark` PR has to change is
in PERF.md section 7; until then `benchmark/tests/test_program_trace.py`
drives the runner.

Set-up, the check against the float32 reference, the timing
(`timing.run_window`: dispatch i, then wait i-1) and the end-to-end
metrics are `runners/train.py`'s, whose helpers are imported, not copied.
What differs is what a real run of `train.py` puts around the step, which
the `train` runner leaves out:

* batches come from the seeded stream through the program's
  `data/prefetch.Prefetcher` (depth 2: a worker thread draws the next
  batch while the device runs this one) and are fed by
  `runtime/mesh.batch_feeder`;
* the running loss is summed on the device, as `train()` does, and every
  `save_every` steps the runner calls
  `training/checkpoint.AsyncCheckpointer.save` right behind the dispatch,
  as `train()` does: sync the loss sum (this drains the device), join the
  write in flight, copy parameters and both Adam moments on the device,
  and hand the copy to a writer thread that pulls it to the host and writes
  one `.npz`; newest 1 kept. The directory is a fresh one under the system
  temp dir (`tempfile.gettempdir()`; which medium that is goes on the
  `checkpoint` log line), removed at the end;
* one `obs/trace.SpanTracer` for the process is handed to all three, so
  each emits its own spans (`prog.data_wait`, `prog.h2d`, `prog.ckpt.*` on
  the profiler's clock). Its `trace.jsonl` is on only in the `--trace 1`
  run, for the whole window; `--trace 0` pays only the annotations.

Warm-up makes one whole save (it compiles the snapshot copy), so nothing
compiles inside the window. With `--trace 1`, `trace_steps` more steps run
under the profiler right after the window, with one save at the third.

`correct` is the `train` runner's (the step's own loss and gradient norm on
the check batch against the float32 reference, every loss finite, the loss
falling) and also: the save in flight when the window closes is joined and
`validate_checkpoint` passes on the newest file; one more save of the final
state, made and joined outside the window, loads back (`load_checkpoint`)
equal bit for bit to the parameters and Adam moments on the device.
`attempted` counts the window's steps and saves, `failed` its non-finite
losses and the saves that raised or did not validate.

`measured` (what the per-layer readers get) is the `train` runner's plus
`phases` (chip 0's busy nanoseconds by phase, `benchmark/lib/
program_trace.py`), `window_spans` (the tracer's events of the window),
`capture_saves` and `ckpt_gap_ns` (the saves under the profiler and the
idle time under their caller-side spans).
"""

from __future__ import annotations

import json
import math
import os
import shutil
import tempfile
import time
from types import SimpleNamespace

import numpy as np

from benchmark.lib import flops, peaks, program_trace, timing, trace
from benchmark.lib.files import load_module
from benchmark.lib.job import Job, Outcome
from benchmark.runners.train import (
    CHECK_SEQUENCES, WARMUP_STEPS, _compare, _mean, _memory, _no_times,
    _peak_bytes, _reference, log)

WINDOW_OPEN, WINDOW_CLOSE = "bench.window_open", "bench.window_close"
# under the profiler the one save comes right behind this dispatch
TRACE_SAVE_AT = 3


def _quiet(fields: dict) -> dict:
    """For --rehearse: `_no_times`, and no group of times either."""
    return {k: (None if k.endswith(("_s", "_ms")) else v)
            for k, v in _no_times(fields).items()}


def _by_name(events) -> dict:
    """{span name: [count, mean milliseconds]} of the tracer's events."""
    out = {}
    for ev in events:
        n, total = out.get(ev["name"], (0, 0.0))
        out[ev["name"]] = (n + 1, total + ev["dur"] / 1e3)
    return {name: [n, total / n] for name, (n, total) in sorted(out.items())}


def _medium(path: str) -> str:
    """The mount that holds `path`, as /proc/mounts has it."""
    best = ("", "unknown")
    try:
        with open("/proc/mounts") as f:
            for line in f:
                device, mount, fstype = line.split()[:3]
                if (os.path.realpath(path).startswith(mount)
                        and len(mount) > len(best[0])):
                    best = (mount, f"{fstype} on {mount} ({device})")
    except OSError:
        pass
    return best[1]


def run(job: Job) -> Outcome:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from distributed_pytorch_from_scratch_tpu.config import (
        MeshConfig, OptimizerConfig)
    from distributed_pytorch_from_scratch_tpu.data.prefetch import Prefetcher
    from distributed_pytorch_from_scratch_tpu.obs.trace import SpanTracer
    from distributed_pytorch_from_scratch_tpu.runtime.compile_cache import (
        compile_cache_stats, enable_compile_cache)
    from distributed_pytorch_from_scratch_tpu.runtime.mesh import (
        batch_feeder, make_mesh)
    # a program without it cannot run this cell: fail before the chip
    from distributed_pytorch_from_scratch_tpu.training.checkpoint import (
        AsyncCheckpointer, list_checkpoints, validate_checkpoint)
    from distributed_pytorch_from_scratch_tpu.training.optim import (
        AdamState, init_adam_state)
    from distributed_pytorch_from_scratch_tpu.training.train_step import (
        build_train_step)

    w = job.workload
    cache_dir = enable_compile_cache()
    devices = jax.devices()
    chips = int(w["chips"])
    platform, kind = devices[0].platform, devices[0].device_kind
    if not job.rehearse and platform != "tpu":
        raise SystemExit(f"benchmark: backend is {platform!r}, not a TPU; "
                         f"nothing is measured off the chip")
    if len(devices) < chips:
        raise SystemExit(f"benchmark: {job.name} needs {chips} chip(s), "
                         f"JAX sees {len(devices)}")
    peak = None if job.rehearse else peaks.peak_for(kind)
    marks = [("reach_chip", time.time())]

    def mark(phase, *ready):
        jax.block_until_ready(ready)
        marks.append((phase, time.time()))

    mesh_sizes = dict(w["mesh"])
    if math.prod(mesh_sizes.values()) != chips:
        raise SystemExit(f"benchmark: mesh {mesh_sizes} is not {chips} chips")
    mesh = make_mesh(MeshConfig(**mesh_sizes), devices=devices[:chips])
    family = job.family.build(job.config, mesh_sizes, w["dtype"])
    model = family.model
    batch, seqlen = int(w["batch"]), int(w["seqlen"])
    save_every = int(w["checkpoint"]["save_every"])

    # the workload file's `checkpoint.root`, or the system temp dir
    scratch = tempfile.mkdtemp(prefix="bench-train-ckpt-",
                               dir=w["checkpoint"].get("root"))
    ckpt_dir = os.path.join(scratch, "ckpt")
    tracer = SpanTracer(os.path.join(scratch, "timeline"), enabled=job.trace,
                        process_name="benchmark")

    param_sh = model.shardings(mesh)
    params = jax.jit(model.init, out_shardings=param_sh)(
        jax.random.key(job.seed))
    feed = batch_feeder(mesh, tracer=tracer)
    mark("weights", params)

    batches = load_module("data", w["data"]["kind"]).TokenBatches
    ids, tgt, check_pos = batches(w["data"], family.sizes.vocab,
                                  CHECK_SEQUENCES, seqlen, job.seed + 1).next()
    want = _reference(family, mesh, params, ids, tgt, check_pos)
    mark("reference")

    scalar = NamedSharding(mesh, P())
    opt_state = jax.jit(init_adam_state, out_shardings=AdamState(
        step=scalar, mu=param_sh, nu=param_sh))(params)
    step_fn = build_train_step(model, mesh, OptimizerConfig(),
                               with_grad_norm=True)
    mark("adam_state", opt_state)

    stream = batches(w["data"], family.sizes.vocab, batch, seqlen, job.seed)
    pos = feed(stream.next()[2])

    def forever():
        while True:
            yield stream.next()

    prefetcher = Prefetcher(forever(), depth=2, tracer=tracer)  # train()'s
    annotate = jax.profiler.TraceAnnotation

    if batch % CHECK_SEQUENCES:
        raise SystemExit(f"benchmark: batch {batch} is not a multiple of "
                         f"the check's {CHECK_SEQUENCES} sequences")
    check_batch = [feed(np.tile(x, (batch // CHECK_SEQUENCES, 1)))
                   for x in (ids, tgt)]

    t0 = time.time()
    step = step_fn.lower(params, opt_state, *check_batch, pos).compile()
    step_temp_bytes = step.memory_analysis().temp_size_in_bytes

    ckpt = AsyncCheckpointer(
        ckpt_dir, model, mesh_sizes.get("tp", 1),
        reserve_last_n=int(w["checkpoint"]["keep"]), mesh_axes=mesh,
        tracer=tracer)
    # n counts dispatched steps; loss_sum is on the device, as in train()
    n, next_save, loss_sum = 0, None, jnp.zeros((), jnp.float32)
    saves, failed_saves = [], []

    def guarded(what, at_step, call):
        """A save that raises (its writer's error surfaces at the join) is
        counted, not fatal: the run's last line says how many failed."""
        try:
            return call()
        except Exception as e:     # noqa: BLE001 (any failure is a failed save)
            failed_saves.append(at_step)
            log(event="save_failed", step=at_step, what=what, error=repr(e))
            return None

    def dispatch():
        nonlocal params, opt_state, n, next_save, loss_sum
        ids, tgt, _ = prefetcher.pull(step=n)
        ids, tgt = feed(ids, tgt, step=n)
        with annotate("bench.dispatch"):
            params, opt_state, out = step(params, opt_state, ids, tgt, pos)
        loss_sum = loss_sum + out[0]
        n += 1
        if n == next_save:
            saves.append(n)
            guarded("save", n, lambda: ckpt.save(n, loss_sum, params,
                                                 opt_state))
            next_save = n + save_every
        return out      # (loss, gradient norm)

    def wait(out):
        with annotate("bench.wait"):
            out[0].block_until_ready()

    # the step's first call is the check: one optimizer step on the check
    # batch, whose loss and gradient norm the program itself returns
    params, opt_state, first = step(params, opt_state, *check_batch, pos)
    wait(first)
    compile_s = time.time() - t0
    check = _compare([float(x) for x in first], want, w["dtype"])
    log(event="check", **check)
    mark("step_compile_or_load_and_check")
    for _ in range(WARMUP_STEPS):
        wait(dispatch())
    # one whole save: compiles the snapshot copy, and its write is set-up
    guarded("save", n, lambda: ckpt.save(n, loss_sum, params, opt_state))
    guarded("join", n, ckpt.join)
    saves.clear()
    mark("warm_up")
    cache_setup = dict(compile_cache_stats())
    memory_setup = _memory(devices[:chips])

    wall_offset = time.time() - time.perf_counter()
    next_save = n + save_every
    tracer.instant(WINDOW_OPEN)
    window = timing.run_window(dispatch, wait, job.seconds)
    tracer.instant(WINDOW_CLOSE)
    setup_s = window.stamps[0] + wall_offset - job.t_process_start
    cache_window = dict(compile_cache_stats())
    losses = [float(loss) for loss, _ in window.results]
    window_saves = list(saves)
    # join the save in flight; the newest file must be whole
    t_join = time.perf_counter()
    guarded("join", window_saves[-1] if window_saves else n, ckpt.join)
    join_after_window_s = time.perf_counter() - t_join
    newest = list_checkpoints(ckpt_dir)
    newest_ok = bool(newest) and newest[-1][0] == (
        window_saves[-1] if window_saves else WARMUP_STEPS)
    if newest_ok:
        newest_ok = guarded("validate", newest[-1][0],
                            lambda: validate_checkpoint(
                                ckpt_dir, newest[-1][0])) is not None
    if not newest_ok and window_saves:
        failed_saves.append(window_saves[-1])

    captured, step_hlo = None, None
    if job.trace:
        next_save = n + TRACE_SAVE_AT
        with tempfile.TemporaryDirectory() as tmp:
            # The capture is the benchmark's own, as in runners/train.py.
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(  # graftcheck: disable=profiler-discipline
                tmp, profiler_options=opts)
            try:
                timing.run_window(dispatch, wait, float("inf"),
                                  max_steps=int(w["trace_steps"]))
                # the traced save's write belongs to the capture
                guarded("join", n, ckpt.join)
            finally:
                jax.profiler.stop_trace()  # graftcheck: disable=profiler-discipline
            captured = trace.load_xplane(trace.find_xplane(tmp))
        step_hlo = step.as_text()
        if job.dump_dir:
            os.makedirs(job.dump_dir, exist_ok=True)
            with open(os.path.join(job.dump_dir, job.name + ".trace.json"),
                      "w") as f:
                json.dump(trace.to_plain(captured), f)
            with open(os.path.join(job.dump_dir, job.name + ".step.hlo.txt"),
                      "w") as f:
                f.write(step_hlo)
            with open(os.path.join(job.dump_dir, job.name
                                   + ".intervals.json"), "w") as f:
                json.dump(window.step_intervals_ms, f)
    next_save = None
    prefetcher.close()
    memory = _memory(devices[:chips])
    peak_bytes = memory and _peak_bytes(memory)

    # one more save, of the final state, read back bit for bit
    t_back = time.perf_counter()
    guarded("save", n, lambda: ckpt.save(n, loss_sum, params, opt_state))
    read_back = guarded("read_back", n, lambda: _reads_back(
        ckpt, ckpt_dir, n, model, params, opt_state))
    read_back_s = time.perf_counter() - t_back
    tracer.close()
    events = program_trace.jsonl_events(
        os.path.join(scratch, "timeline", "trace.jsonl"))
    window_spans = program_trace.between(events, WINDOW_OPEN, WINDOW_CLOSE)
    counters = dict(saves=ckpt.saves, files=ckpt.files,
                    bytes_moved=ckpt.bytes_moved,
                    bytes_written=ckpt.bytes_written,
                    bytes_fed=feed.bytes_fed, prefetch_pulls=prefetcher.pulls)
    medium = _medium(scratch)
    shutil.rmtree(scratch, ignore_errors=True)

    intervals = window.step_intervals_ms
    slowest = intervals.index(max(intervals))
    tokens_per_step = batch * seqlen
    tokens_per_s = window.steps * tokens_per_step / window.seconds
    finite = [math.isfinite(x) for x in losses]
    first10, last10 = _mean(losses[:10]), _mean(losses[-10:])
    falling = len(losses) >= 20 and last10 < first10
    correct = bool(check["ok"] and all(finite) and falling
                   and newest_ok and read_back and not failed_saves)
    step_ms_p90 = timing.quantile(intervals, 0.9)
    end_to_end = {
        "tokens_per_s_per_chip": tokens_per_s / chips,
        "step_ms_p90": step_ms_p90,
        "setup_s": setup_s,
    }
    # the intervals a save (or a held-up host) stretched, as the host saw them
    slow = [x for x in intervals if x > 1.5 * timing.quantile(intervals, 0.5)]
    lines = [
        dict(event="window", steps=window.steps, seconds=window.seconds,
             step_ms_median=timing.quantile(intervals, 0.5),
             step_ms_p90=step_ms_p90,
             step_ms_max=max(intervals), interval_samples=len(intervals),
             around_slowest_ms=intervals[max(slowest - 2, 0):slowest + 4],
             slow_steps=len(slow), slow_steps_ms=slow[:12],
             quantiles_ms={q: timing.quantile(intervals, q) for q in (
                 0.05, 0.25, 0.75, 0.9, 0.95, 0.99)},
             loss_first10=first10, loss_last10=last10,
             losses_finite=all(finite), loss_fell=falling),
        dict(event="checkpoint", saves_in_window=window_saves,
             failed_saves=failed_saves, newest_ok=newest_ok,
             read_back=bool(read_back), medium=medium,
             join_after_window_s=join_after_window_s,
             read_back_s=read_back_s, host_wait_s=prefetcher.wait_time,
             # the window's spans by name (--trace 1): count, mean ms
             window_spans_ms=_by_name(window_spans), **counters),
        dict(event="setup", setup_s=setup_s,
             phases_s={phase: t - t_before for (phase, t), t_before in zip(
                 marks, [job.t_process_start] + [t for _, t in marks])},
             compile_cache={"dir": cache_dir, **cache_setup},
             compile_cache_after_window=cache_window,
             step_temp_bytes=step_temp_bytes, memory_after_setup=memory_setup,
             memory_after_window=memory, memory_peak_bytes=peak_bytes)]

    devs = trace.device_traces(captured) if captured else []
    device = {"platform": platform, "kind": kind,
              "count": jax.device_count(), "memory_peak_bytes": peak_bytes,
              "peak_bytes_in_use": memory and memory["peak_bytes_in_use"],
              "peak_bytes_reserved": memory and memory["peak_bytes_reserved"]}
    breakdown, phases, ckpt_gap_ns = None, None, None
    if job.trace and devs:
        dev = devs[0]
        device["busy_s"] = sum(d.busy_ns() for d in devs) / len(devs) / 1e9
        device["window_s"] = sum(d.window_ns for d in devs) / len(devs) / 1e9
        names = program_trace.op_names(step_hlo)
        runs = program_trace.step_runs(captured, dev)
        phases = program_trace.phase_ns(dev, runs, names)
        spans = program_trace.loop_spans(captured)
        ckpt_gap_ns = program_trace.covered_gap_ns(dev, (
            s for s in spans if s.name.startswith(
                program_trace.PROGRAM_PREFIX + "ckpt.")))
        gaps = program_trace.named_gaps(dev, spans)
        lines.append(dict(
            event="phases", steps=dev.steps, busy_ns=dev.busy_ns(),
            phase_sum_ns=sum(phases.values()), phases_ns=phases,
            idle_ns_by_span=gaps, ckpt_gap_ns=ckpt_gap_ns,
            top_unattributed=program_trace.top_unattributed(
                dev, runs, names)))
        breakdown = {"device_ops": trace.top_ops(dev),
                     "idle_gaps": trace.top_gaps(dev, spans),
                     "phases_s": {k: v / 1e9 for k, v in phases.items()},
                     "idle_s_by_span": {k: v / 1e9 for k, v in gaps.items()}}
    if job.rehearse:
        device.update(busy_s=None, window_s=None)
    for fields in lines:
        log(**(_quiet(fields) if job.rehearse else fields))

    measured = SimpleNamespace(
        workload=w, sizes=family.sizes, mesh=mesh_sizes, chips=chips,
        window=window, intervals_ms=intervals,
        tokens_per_s=tokens_per_s, setup_s=setup_s, compile_s=compile_s,
        cache_setup=cache_setup, cache_window=cache_window,
        flops_per_token=flops.train_flops_per_token(family.sizes, seqlen),
        peak=peak, peak_bytes=peak_bytes, devices=devs,
        phases=phases, window_spans=window_spans,
        capture_saves=len(saves) - len(window_saves),
        ckpt_gap_ns=ckpt_gap_ns)
    return Outcome(correct=correct,
                   attempted=window.steps + len(window_saves),
                   failed=finite.count(False) + len(set(failed_saves)),
                   end_to_end=end_to_end, measured=measured, device=device,
                   breakdown=breakdown)


def _reads_back(ckpt, ckpt_dir, step, model, params, opt_state) -> bool:
    """Join the final save and load it: parameters and both Adam moments
    equal, bit for bit, to what the device holds (one leaf at a time on the
    host side of the comparison)."""
    import jax
    from distributed_pytorch_from_scratch_tpu.training.checkpoint import (
        load_checkpoint)

    if ckpt.join() is None:
        return False
    loaded, loaded_opt, at = load_checkpoint(
        ckpt_dir, step, model.to_canonical(params), model.canonical_specs(),
        with_opt=True)

    def same(on_device, on_disk) -> bool:
        a, b = jax.tree.leaves(on_device), jax.tree.leaves(on_disk)
        return len(a) == len(b) and all(
            x.dtype == y.dtype and np.array_equal(np.asarray(x), y)
            for x, y in zip(a, b))

    return bool(at == step and loaded_opt is not None
                and same(model.to_canonical(params), loaded)
                and same(model.to_canonical(opt_state.mu), loaded_opt.mu)
                and same(model.to_canonical(opt_state.nu), loaded_opt.nu))
