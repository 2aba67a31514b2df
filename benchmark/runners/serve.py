"""The `serve` runner: one process serves the cell's open-loop traffic for
`--seconds` through the program's paged engine.

Set-up (everything before the first request is due; `setup_s` is process
start to that moment): reach the chip, the program's model from the
configuration file, the weights on the device in one jitted call from
`--seed`, the float32 reference's logits for the check sequences (before the
page pool takes the memory), `serving/engine.PagedEngine` built with the
keywords `serving/serve.py` passes for `--paged` (those the workload file's
`engine` group gives; every other one is left at the engine's default by not
passing it), the **check**, and a warm-up of every program the traffic can
reach: the eight prefill-chunk widths 1..128 and the decode step.

The check, on the timed engine's weights and through its page pool: for
each check sequence, the first `prefill` tokens go through the engine's own
chunked prefill (the timed prefill programs); then `decode` single-token
steps, teacher-forced, through a decode program that is **compiled apart
from the timed one**: the timed step samples inside the program and returns
only the token, so `engine_logits` builds the engine's step once more under
its `_debug_host_sampler` switch, which returns the logits it would sample
from. Same function, same pool and page table, another compile: what the
compiler does differently between the two is not seen. Those logits are
held to the family's plain reference (float32, matmul precision "highest",
full causal attention over the whole sequence, no cache). The prefill
program returns no logits; it is checked through what the decode steps read
from the pages it wrote. `engine_logits` is the one place that reaches into
the engine's private state, and says what it touches.

The window: requests are submitted when due (`benchmark/data/<kind>.plan`),
the engine is stepped whenever it has work, the loop sleeps only when idle.
This is `serving/loadgen.run_loadgen`'s loop, with the generator's lateness
recorded, a drain of at most `drain_s` after the last request is due, and
refused or unfinished requests counted as failed (`benchmark/lib/serving`).
With `--trace 1` the program's tracer writes its timeline, and the last
`trace_slice_s` seconds in which requests are due run under the profiler
(stopped when the last one is due, so that stopping it delays no arrival).

End-to-end metrics this runner offers: `ttft_p95_ms`, `tpot_p95_ms` (over
every request sent), `setup_s`. The runner is **staged**: no cell of
`BENCHMARK.json` names it yet (PERF.md section 7, PR 27); its per-layer
readers are `benchmark/lib/serving.READERS`.
"""

from __future__ import annotations

import json
import os
import shutil
import tempfile
import time
from types import SimpleNamespace
from typing import NamedTuple

import numpy as np

from benchmark.lib import serving, trace
from benchmark.lib.files import load_module
from benchmark.lib.job import Job, Outcome
from benchmark.runners.train import _memory, _peak_bytes, log

# The check's limit, by compute dtype, on the mean over the check's 4 x 64
# positions of |engine logits - reference logits|_2 / |reference logits|_2
# along the vocabulary. Read on the chip at the published widths (my chip
# runs, PR 27, second round; PERF.md section 2; `tools/check_seeds.py`):
# sound runs of the program 0.01066 to 0.01116 over 14 seeds (bfloat16's own
# rounding through 24 layers); the control, the program with its int8 pages
# switched on, 0.01239 to 0.01260 over 5 seeds; with its int8 decode weights
# as well 0.0221 to 0.0227. The control's smallest is 1.11 x the sound runs'
# largest, not the 3 x a limit would like: one scale a head vector keeps 7
# bits where bfloat16 keeps 8, so an int8 cache adds about half of
# bfloat16's own error in quadrature. What carries the limit is that the
# mean over 256 positions moves by 1.3% (one standard deviation) from seed
# to seed: the limit stands 5.5% over the sound runs' largest and 5% under
# the control's smallest. (The largest single position, compared in round
# one, reads 0.0115-0.0122 against 0.0134-0.0140.)
LIMITS = {"bfloat16": 0.01178}

# prompt lengths whose chunk widths are 1, 2, 4, ..., 128: every prefill
# program `PagedEngine._dispatch_chunk` can ask for at prefill_chunk 128
WARM_PROMPTS = (1, 2, 3, 5, 9, 17, 33, 65)


def _quiet(fields: dict) -> dict:
    """For --rehearse: no time taken off the chip goes on a log line."""
    def timed(key):
        return key.endswith(("_s", "_ms", "seconds")) or "_ms_" in key
    return {k: (None if timed(k) else v) for k, v in fields.items()}


def build_engine(model, mesh, params, spec: dict, vocab: int, n_positions: int,
                 tracer, **more):
    """`PagedEngine` as `serving/serve.py` builds it for `--paged`, with the
    workload file's `engine` group as keywords. `eos_id` is the first id
    past the vocabulary: greedy sampling never yields it, so every request
    produces exactly the tokens planned."""
    from distributed_pytorch_from_scratch_tpu.serving.engine import PagedEngine
    return PagedEngine(model, mesh, params, buf_len=n_positions, eos_id=vocab,
                       tracer=tracer, **spec, **more)


def reference_logits(job: Job, family, params, ids: np.ndarray,
                     keep: int) -> np.ndarray:
    """Float32 logits of the family's plain reference at the last `keep`
    positions of each sequence, on the host."""
    import jax
    import jax.numpy as jnp

    s = family.sizes
    eps = job.config.get("layer_norm_epsilon", 1e-5)
    pos = np.tile(np.arange(ids.shape[1], dtype=np.int32), (len(ids), 1))

    def last(p, i, q):
        p = jax.tree.map(lambda x: x.astype(jnp.float32), p)
        return job.family.reference_logits(
            p, i, q, n_head=s.n_head, vocab=s.vocab, eps=eps)[:, -keep:]

    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(last)(params, ids, pos))


def engine_logits(engine, ids: np.ndarray, prefill: int) -> np.ndarray:
    """Logits of the engine's decode program at positions prefill.. of each
    row of `ids`, teacher-forced, after the engine's own chunked prefill of
    the first `prefill` tokens; (sequences, decode, vocabulary) float32.

    What it touches of the engine beyond `submit`: `_admit` and
    `_pump_prefill` (prefill with no decode step between), `_build_step`
    under `_debug_host_sampler` (the decode program, returning logits),
    the slot arrays `_tokens`, `_pos`, `_seeds`, `_tbl`, `_slot_req`,
    `_ensure_writable` (the page a step writes) and `_release_slot`."""
    import jax.numpy as jnp
    from distributed_pytorch_from_scratch_tpu.serving.engine import Request

    decode = ids.shape[1] - prefill
    vocab = engine.eos_id
    for k, row in enumerate(ids):
        engine.submit(Request(rid=-1 - k, prompt=row[:prefill].tolist(),
                              max_new=decode + 1))
    done = []
    engine._admit(done)
    while engine._prefilling:
        engine._pump_prefill(done)
    slot_of = {req.rid: slot for slot, req in engine._slot_req.items()}
    slots = [slot_of[-1 - k] for k in range(len(ids))]
    engine._debug_host_sampler = True
    try:
        step = engine._build_step()
    finally:
        engine._debug_host_sampler = False
    rows = jnp.asarray(slots)
    out = np.zeros((len(ids), decode, vocab), np.float32)
    for j in range(decode):
        for k, slot in enumerate(slots):
            engine._ensure_writable(slot, prefill + j, prefill + j + 1)
            engine._tokens[slot] = ids[k, prefill + j]
        ks, vs, logits = step(
            engine._params_in, engine.pool.ks, engine.pool.vs,
            jnp.asarray(engine._tokens), jnp.asarray(engine._pos),
            jnp.asarray(engine._seeds), jnp.asarray(engine._tbl))
        engine.pool.adopt(ks, vs)
        out[:, j] = np.asarray(logits[rows])[:, :vocab]
        for slot in slots:
            engine._pos[slot] += 1
    for slot in slots:
        del engine._slot_req[slot]
        engine._release_slot(slot)
    return out


def compare(got: np.ndarray, want: np.ndarray, limit: float) -> dict:
    """Both (sequences, positions, vocabulary). The number compared is the
    mean over the positions of the relative L2 distance of one position's
    logits; the largest is printed beside it."""
    dist = np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)
    mean = float(dist.mean())
    return {"ok": bool(np.isfinite(got).all() and mean <= limit),
            "rel_l2_mean": mean, "rel_l2_limit": limit,
            "rel_l2_max": float(dist.max()),
            "rel_l2_median": float(np.median(dist)),
            "abs_max": float(np.abs(got - want).max()),
            "reference_abs_max": float(np.abs(want).max()),
            "positions": int(dist.size)}


def check_ids(w: dict, vocab: int, seed: int) -> np.ndarray:
    """The check's sequences: uniform ids from `seed + 1`."""
    chk = w["check"]
    return np.random.default_rng(seed + 1).integers(
        int(w["data"].get("reserved_ids", 0)), vocab, dtype=np.int32,
        size=(int(chk["sequences"]), int(chk["prefill"]) + int(chk["decode"])))


def warm_up(engine) -> None:
    """Every program the window can reach, through the public API."""
    from distributed_pytorch_from_scratch_tpu.serving.engine import Request
    for i, n in enumerate(WARM_PROMPTS):
        engine.submit(Request(rid=-1000 - i, prompt=[3] * n, max_new=2))
        engine.run_to_completion()


class Drove(NamedTuple):
    t_open: float           # on `clock`
    t_end: float            # seconds after t_open when the loop ended
    sent: int               # requests submitted (or refused at submit)
    submitted: dict         # rid -> seconds after t_open when submit ran
    refused: dict           # rid -> repr of what submit raised


def drive(engine, requests, due, seconds: float, drain_s: float,
          clock=time.monotonic, sleep=time.sleep, tick=None) -> Drove:
    """The open loop: submit each request when it is due, step the engine
    whenever it has work, sleep only when it has none; end when every
    request is sent and done, or `drain_s` after `seconds`. A request is
    timed from when it was **due**: `submit_t` is the planned moment, so
    the time the loop spent inside a step counts as the request's waiting.
    `clock` is the engine's clock. `tick(now)` runs once a turn."""
    from jax.profiler import TraceAnnotation as annotate
    from distributed_pytorch_from_scratch_tpu.serving.scheduler import (
        QueueFull)

    submitted, refused = {}, {}
    t_open = clock()
    i = 0
    while True:
        now = clock() - t_open
        if i >= len(requests) and not engine.has_work():
            break
        if now >= seconds + drain_s:
            break
        if tick is not None:
            tick(now)
        with annotate("bench.submit"):
            while i < len(requests) and due[i] <= now:
                req = requests[i]
                req.submit_t = t_open + due[i]
                submitted[req.rid] = clock() - t_open
                try:
                    engine.submit(req)
                except (QueueFull, ValueError) as e:
                    refused[req.rid] = repr(e)
                i += 1
        if engine.has_work():
            engine.step()
        elif i < len(requests):
            with annotate("bench.idle"):
                sleep(max(0.0, min(0.05, due[i] - (clock() - t_open))))
    return Drove(t_open, clock() - t_open, i, submitted, refused)


class _Capture:
    """The profiler over `slice_s` seconds that end at `stop_s` after the
    window opened. `tick` stops it at the first turn of the loop past that
    (stopping writes the capture and holds the loop for that long, in the
    traced run only); `finish` reads it, after the loop. The capture is the
    benchmark's own and the only one in this process, as in
    runners/train.py."""

    def __init__(self, directory: str, stop_s: float, slice_s: float):
        self.dir, self.start, self.stop = directory, stop_s - slice_s, stop_s
        self.wanted, self.on, self.done = slice_s > 0, False, False

    def tick(self, now: float) -> None:
        import jax
        if not self.wanted or self.done:
            return
        if not self.on and now >= self.start:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(  # graftcheck: disable=profiler-discipline
                self.dir, profiler_options=opts)
            self.on = True
        elif self.on and now >= self.stop:
            self._stop()

    def _stop(self) -> None:
        import jax
        jax.profiler.stop_trace()  # graftcheck: disable=profiler-discipline
        self.on, self.done = False, True

    def finish(self):
        """The capture as planes, or None if none was taken."""
        if self.on:
            self._stop()
        return (trace.load_xplane(trace.find_xplane(self.dir))
                if self.done else None)


def run(job: Job) -> Outcome:
    import jax
    from distributed_pytorch_from_scratch_tpu.config import MeshConfig
    from distributed_pytorch_from_scratch_tpu.obs.trace import SpanTracer
    from distributed_pytorch_from_scratch_tpu.runtime.compile_cache import (
        compile_cache_stats, enable_compile_cache)
    from distributed_pytorch_from_scratch_tpu.runtime.mesh import make_mesh
    from distributed_pytorch_from_scratch_tpu.serving.engine import Request

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
    marks = [("reach_chip", time.time())]

    def mark(phase, *ready):
        jax.block_until_ready(ready)
        marks.append((phase, time.time()))

    mesh_sizes = dict(w["mesh"])
    mesh = make_mesh(MeshConfig(**mesh_sizes), devices=devices[:chips])
    family = job.family.build(job.config, mesh_sizes, w["dtype"])
    model, sizes = family.model, family.sizes
    params = jax.jit(model.init, out_shardings=model.shardings(mesh))(
        jax.random.key(job.seed))
    mark("weights", params)

    planned = load_module("data", w["data"]["kind"]).plan(
        w["data"], sizes.vocab, sizes.n_positions, job.seconds, job.seed)
    ids = check_ids(w, sizes.vocab, job.seed)
    want = reference_logits(job, family, params, ids,
                            int(w["check"]["decode"]))
    mark("reference")

    scratch = tempfile.mkdtemp(prefix="bench-serve-")
    tracer = SpanTracer(os.path.join(scratch, "timeline"), enabled=job.trace,
                        process_name="benchmark")
    engine = build_engine(model, mesh, params, w["engine"], sizes.vocab,
                          sizes.n_positions, tracer)
    mark("engine", engine.pool.ks, engine.pool.vs)
    memory_engine = _memory(devices[:chips])

    t0 = time.time()
    check = compare(engine_logits(engine, ids, int(w["check"]["prefill"])),
                    want, LIMITS[w["dtype"]])
    log(event="check", **check)
    warm_up(engine)
    compile_s = time.time() - t0
    mark("check_and_warm_up")
    if engine.pool.pages_in_use or engine.has_work():
        raise SystemExit("benchmark: the engine did not drain after set-up")
    cache_setup = dict(compile_cache_stats())
    stats_setup = engine.stats()

    # ---- the window ----
    requests = [Request(rid=p.rid, prompt=p.prompt, max_new=p.output_len)
                for p in planned]
    # the slice of the window that is captured ends with the arrivals
    arrivals_end = job.seconds * float(
        w["data"]["arrivals"].get("window_share", 1.0))
    capture = _Capture(os.path.join(scratch, "capture"), arrivals_end,
                       float(w["trace_slice_s"]) if job.trace else 0.0)
    tracer.instant(serving.WINDOW_OPEN)
    setup_s = time.time() - job.t_process_start
    drove = drive(engine, requests, [p.due_s for p in planned], job.seconds,
                  float(w["drain_s"]), tick=capture.tick)
    captured = capture.finish()
    t_open, t_end, i = drove.t_open, drove.t_end, drove.sent
    submitted, refused = drove.submitted, drove.refused
    for rid, error in refused.items():
        log(event="refused", rid=rid, error=error)
    tracer.instant(serving.WINDOW_CLOSE)
    cache_window = dict(compile_cache_stats())
    stats = engine.stats()
    timeline = tracer.close()

    def since_open(t):
        return None if t is None else t - t_open

    served = [serving.Served(
        rid=r.rid, due=p.due_s, submitted=submitted[r.rid],
        admitted=since_open(r.admit_t), first=since_open(r.first_token_t),
        finished=since_open(r.finish_t), planned=p.output_len,
        produced=len(r.tokens), refused=r.rid in refused)
        for r, p in zip(requests[:i], planned)]
    limits = w["limits"]
    summary = serving.summarize(served, limits, arrivals_end, t_end)
    drained = engine.pool.pages_in_use == 0 and not engine.has_work()
    correct = bool(check["ok"] and summary["failed"] == 0 and drained
                   and i == len(requests))

    events = []
    if timeline:
        with open(os.path.join(scratch, "timeline", "trace.jsonl")) as f:
            events = serving.window_events(
                [json.loads(line) for line in f if line.strip()])
    if job.dump_dir and captured:
        os.makedirs(job.dump_dir, exist_ok=True)
        with open(os.path.join(job.dump_dir, job.name + ".trace.json"),
                  "w") as f:
            json.dump(trace.to_plain(captured), f)
    shutil.rmtree(scratch, ignore_errors=True)
    memory = _memory(devices[:chips])
    peak_bytes = memory and _peak_bytes(memory)

    end_to_end = {"ttft_p95_ms": summary["ttft_p95_ms"],
                  "tpot_p95_ms": summary["tpot_p95_ms"],
                  "setup_s": setup_s}
    preemptions = stats["preemptions"] - stats_setup["preemptions"]
    decode_steps = stats["decode_steps"] - stats_setup["decode_steps"]
    lines = [
        dict(event="window", seconds=job.seconds, drained_after_s=t_end,
             rate_rps=w["data"]["arrivals"]["rate_rps"], limits=limits,
             **summary, decode_steps=decode_steps, preemptions=preemptions,
             pages_in_use_after=engine.pool.pages_in_use,
             every_request_sent=i == len(requests)),
        dict(event="setup", setup_s=setup_s,
             phases_s={phase: t - t_before for (phase, t), t_before in zip(
                 marks, [job.t_process_start] + [t for _, t in marks])},
             compile_s=compile_s,
             compile_cache={"dir": cache_dir, **cache_setup},
             compile_cache_after_window=cache_window,
             num_pages=engine.pool.num_pages,
             memory_after_engine=memory_engine,
             memory_after_window=memory, memory_peak_bytes=peak_bytes)]
    for fields in lines:
        if job.rehearse:
            fields = _quiet(fields)
            fields.pop("phases_s", None)
        log(**fields)

    devs = trace.device_traces(captured) if captured else []
    device = {"platform": platform, "kind": kind,
              "count": jax.device_count(), "memory_peak_bytes": peak_bytes,
              "peak_bytes_in_use": memory and memory["peak_bytes_in_use"],
              "peak_bytes_reserved": memory and memory["peak_bytes_reserved"]}
    breakdown = None
    if job.trace and devs:
        device["busy_s"] = sum(d.busy_ns() for d in devs) / len(devs) / 1e9
        device["window_s"] = sum(d.window_ns for d in devs) / len(devs) / 1e9
        spans = sorted(trace.host_spans(captured, "prog.")
                       + trace.host_spans(captured, "bench."),
                       key=lambda e: e.start_ns)
        breakdown = {"device_ops": trace.top_ops(devs[0]),
                     "idle_gaps": trace.top_gaps(devs[0], spans)}
    if job.rehearse:
        device.update(busy_s=None, window_s=None)

    measured = SimpleNamespace(
        summary=summary, events=events, num_pages=engine.pool.num_pages,
        preemptions=preemptions, compile_s=compile_s,
        cache_setup=cache_setup, cache_window=cache_window, devices=devs)
    return Outcome(correct=correct, attempted=summary["sent"],
                   failed=summary["failed"], end_to_end=end_to_end,
                   measured=measured, device=device, breakdown=breakdown)
