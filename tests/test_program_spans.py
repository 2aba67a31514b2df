"""The program's own spans and names, where the work happens.

* a `SpanTracer.span` is also a `jax.profiler.TraceAnnotation`
  `prog.<name>`: under a capture it lies on the host plane, on its thread's
  line, with its arguments, whether or not trace.jsonl is written;
* `Prefetcher`, `batch_feeder` and `AsyncCheckpointer` emit their spans
  with a tracer and nothing without one, and `AsyncCheckpointer` writes
  the file a plain `save_checkpoint` writes;
* `LoopSpans` (what `train()` hands them) accounts goodput on the loop's
  thread only;
* the lowered train step carries the scopes a device trace is split by, and
  the flash calls their names.
"""

import glob
import json
import os
import re
import threading
import time
from contextlib import contextmanager

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_from_scratch_tpu.config import (
    MeshConfig, ModelConfig, OptimizerConfig)
from distributed_pytorch_from_scratch_tpu.data.prefetch import Prefetcher
from distributed_pytorch_from_scratch_tpu.models.gpt2 import GPT2Transformer
from distributed_pytorch_from_scratch_tpu.models.transformer import Transformer
from distributed_pytorch_from_scratch_tpu.obs import SpanTracer, TrainObserver
from distributed_pytorch_from_scratch_tpu.runtime.mesh import (
    batch_feeder, make_mesh)
from distributed_pytorch_from_scratch_tpu.training.checkpoint import (
    AsyncCheckpointer, load_checkpoint, save_checkpoint)
from distributed_pytorch_from_scratch_tpu.training.metrics import ProfilerTrace
from distributed_pytorch_from_scratch_tpu.training.optim import init_adam_state
from distributed_pytorch_from_scratch_tpu.training.train_step import (
    build_train_step)

CFG = ModelConfig(attn_dim=32, ffn_dim=64, num_heads=4, num_layers=2,
                  vocab_size=64, maxlen=16)


class Recorder:
    """Stands in for a SpanTracer: keeps (name, cat, args, thread), the
    args with what the block added to the dict the span yields."""

    def __init__(self):
        self.spans = []

    @contextmanager
    def span(self, name, cat=None, **args):
        found = {}
        yield found
        self.spans.append((name, cat, {**args, **found},
                           threading.get_ident()))

    def named(self, name):
        return [s for s in self.spans if s[0] == name]


# ------------------------------------------------- A. the profiler's clock

def _host_lines(log_dir):
    """{line index: [(event name, {stat: value})]} of the capture's host
    plane, `prog.` events only."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                       recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            found = [(e.name, dict(e.stats)) for e in line.events
                     if e.name.startswith("prog.")]
            if found:
                out[i] = found
    return out


@pytest.mark.parametrize("jsonl", [False, True])
def test_span_is_a_profiler_annotation_on_its_thread(tmp_path, jsonl):
    tracer = SpanTracer(str(tmp_path / "timeline") if jsonl else None)
    capture = ProfilerTrace(str(tmp_path / "capture"), start_step=0,
                            num_steps=1)
    capture.maybe_start(0)

    def writer():
        with tracer.span("ckpt.write", cat="checkpoint", step=25) as found:
            found["bytes"] = 4096       # known only when the span ends

    with tracer.span("data_wait", cat="data_wait", step=7):
        pass
    with tracer.span("h2d", cat="h2d", step=7):
        pass
    other = threading.Thread(target=writer)
    other.start()
    other.join()
    capture.maybe_stop(1, sync=jnp.zeros(()))

    lines = _host_lines(str(tmp_path / "capture"))
    by_name = {name: (line, stats) for line, found in lines.items()
               for name, stats in found}
    assert set(by_name) == {"prog.data_wait", "prog.h2d", "prog.ckpt.write"}
    assert int(by_name["prog.data_wait"][1]["step"]) == 7
    assert int(by_name["prog.ckpt.write"][1]["step"]) == 25
    # the annotation was entered before the block ran: its entry arguments
    assert "bytes" not in by_name["prog.ckpt.write"][1]
    # the loop's two spans share a line; the writer thread has its own
    assert by_name["prog.data_wait"][0] == by_name["prog.h2d"][0]
    assert by_name["prog.ckpt.write"][0] != by_name["prog.h2d"][0]

    # the timeline keeps its documented names, and is written only when on
    assert (tracer.close() is not None) == jsonl
    if jsonl:
        evs = [json.loads(l) for l in open(tmp_path / "timeline"
                                           / "trace.jsonl")]
        # (`jnp.zeros` above is built while the timeline is on: where an
        # earlier test of this process has `runtime/compile_cache` listening,
        # its `compile.*` spans are here too)
        evs = [e for e in evs if e.get("cat") != "compile"]
        assert [e["name"] for e in evs] == ["data_wait", "h2d", "ckpt.write"]
        # the event is written when the span ends: entry and late arguments
        assert evs[2]["args"] == {"step": 25, "bytes": 4096}
        assert evs[0]["args"] == {"step": 7} and evs[2]["tid"] != evs[0]["tid"]
    else:
        assert not os.path.exists(tmp_path / "timeline")


class CountingFile:
    def __init__(self):
        self.writes = self.flushes = 0

    def write(self, text):
        self.writes += 1

    def flush(self):
        self.flushes += 1

    def close(self):
        pass


class CountingLock:
    def __init__(self):
        self.acquired = 0
        self._lock = threading.Lock()

    def __enter__(self):
        self.acquired += 1
        return self._lock.__enter__()

    def __exit__(self, *exc):
        return self._lock.__exit__(*exc)


def test_tracer_hot_path_is_one_buffered_write_per_span(tmp_path):
    """What a span costs the calling thread, counted through an injected
    file and lock: one write under one lock, a flush every 64 events; an
    instant (the sentinel's and watchdog's crash-safety) flushes at once."""
    tracer = SpanTracer(str(tmp_path))
    tracer._jsonl, tracer._lock = CountingFile(), CountingLock()
    for i in range(128):
        with tracer.span("step", cat="step", step=i):
            pass
    out, lock = tracer._jsonl, tracer._lock
    assert (out.writes, lock.acquired, out.flushes) == (128, 128, 2)
    with tracer.span("step", cat="step", step=128):
        pass
    tracer.instant("nonfinite_loss", step=128)
    assert (out.writes, lock.acquired, out.flushes) == (130, 130, 3)
    assert not hasattr(tracer, "complete")


# ------------------------------------------- B. spans where the work happens

@pytest.mark.parametrize("traced", [True, False])
def test_prefetcher_times_its_own_waits(traced):
    rec = Recorder() if traced else None
    pf = Prefetcher(iter(range(5)), depth=2, transform=lambda x: x * 2,
                    tracer=rec)
    got = [pf.pull(step=0)] + [x for x in pf]
    assert got == [0, 2, 4, 6, 8]
    assert pf.pulls == 6 and pf.wait_time >= 0.0   # 5 items + the sentinel
    if not traced:
        return
    waits, made = rec.named("data_wait"), rec.named("prefetch_window")
    assert len(waits) == 6 and waits[0][2] == {"step": 0}
    assert {w[1] for w in waits} == {"data_wait"}
    assert len(made) == 5 and {m[1] for m in made} == {"data_prep"}
    # consumer's thread and the worker's
    assert {w[3] for w in waits} == {threading.get_ident()}
    assert {m[3] for m in made}.isdisjoint({threading.get_ident()})


@pytest.mark.parametrize("traced", [True, False])
def test_batch_feeder_times_its_own_puts(traced):
    mesh = make_mesh(MeshConfig(dp=1, tp=1), devices=jax.devices()[:1])
    rec = Recorder() if traced else None
    feed = batch_feeder(mesh, tracer=rec)
    x = np.arange(12, dtype=np.int32).reshape(3, 4)
    one = feed(x)
    ids, tgt, pos = feed(x, x + 1, x + 2, step=9)
    assert isinstance(one, jax.Array) and isinstance(pos, jax.Array)
    np.testing.assert_array_equal(np.asarray(tgt), x + 1)
    assert feed.bytes_fed == 4 * x.nbytes
    if traced:
        assert [(s[0], s[1], s[2]) for s in rec.spans] == [
            ("h2d", "h2d", {}), ("h2d", "h2d", {"step": 9})]


def _tiny_state():
    model = Transformer(CFG, tp_size=1)
    params = model.init(jax.random.key(0))
    opt = init_adam_state(params)
    opt = opt._replace(step=jnp.asarray(50, jnp.int32),
                       mu=jax.tree.map(lambda p: p + 1.0, opt.mu))
    return model, params, opt


@pytest.mark.parametrize("traced", [True, False])
def test_async_checkpointer_writes_the_file_save_checkpoint_writes(
        tmp_path, traced):
    model, params, opt = _tiny_state()
    rec = Recorder() if traced else None
    saved = []
    ckpt = AsyncCheckpointer(
        str(tmp_path / "async"), model, 1, start_step=40, reserve_last_n=1,
        tracer=rec, on_saved=lambda step, paths: saved.append((step, paths)))
    ckpt.save(50, jnp.asarray(25.0), params, opt)       # avg = 25 / 10
    ckpt.save(60, jnp.asarray(30.0), params, opt)       # joins 50's write
    assert [s[0] for s in saved] == [50] and ckpt.last_saved == 60
    (path,) = ckpt.join()
    assert ckpt.join() is None and [s[0] for s in saved] == [50, 60]
    assert os.path.basename(path) == "tprank-0_iter-60_loss-1.5000.npz"
    assert os.listdir(tmp_path / "async") == [os.path.basename(path)]

    (plain,) = save_checkpoint(str(tmp_path / "plain"), 60, 1.5, params,
                               model.canonical_specs(), 1, opt_state=opt)
    a, b = np.load(path), np.load(plain)
    assert sorted(a.files) == sorted(b.files)
    for key in a.files:
        np.testing.assert_array_equal(a[key], b[key])
    loaded, opt_loaded, step = load_checkpoint(
        str(tmp_path / "async"), 60, params, model.canonical_specs(),
        with_opt=True)
    jax.tree.map(np.testing.assert_array_equal, loaded,
                 jax.tree.map(np.asarray, params))
    jax.tree.map(np.testing.assert_array_equal, opt_loaded.mu,
                 jax.tree.map(np.asarray, opt.mu))

    state_bytes = 3 * sum(x.nbytes for x in jax.tree.leaves(params))
    assert (ckpt.saves, ckpt.files, ckpt.bytes_moved) == (2, 2,
                                                          2 * state_bytes)
    assert ckpt.bytes_written >= 2 * state_bytes
    if not traced:
        return
    me = threading.get_ident()
    order = [(s[0], s[2]["step"]) for s in rec.spans if s[3] == me]
    assert order == [("ckpt.loss_sync", 50), ("ckpt.snapshot", 50),
                     ("ckpt.loss_sync", 60), ("ckpt.join_prev", 50),
                     ("ckpt.snapshot", 60), ("ckpt.join_prev", 60)]
    written = [(s[0], s[2]["step"]) for s in rec.spans if s[3] != me]
    assert written == [("ckpt.d2h", 50), ("ckpt.write", 50),
                       ("ckpt.d2h", 60), ("ckpt.write", 60)]
    # only the writer's two spans learn anything as they run
    assert {s[0] for s in rec.spans if set(s[2]) != {"step"}} == {
        "ckpt.d2h", "ckpt.write"}
    assert {s[1] for s in rec.spans} == {"checkpoint"}


def _events(log_dir, *names):
    """The complete events of a closed tracer's trace.jsonl called one of
    `names`, in the order they were written (the order they ended)."""
    with open(os.path.join(log_dir, "trace.jsonl")) as f:
        evs = [json.loads(line) for line in f]
    return [e for e in evs if e.get("ph") == "X" and e["name"] in names]


def test_a_saves_writer_spans_carry_what_the_counters_grow_by(tmp_path):
    """`ckpt.d2h` and `ckpt.write` in trace.jsonl: `bytes` (and `files`)
    equal, save by save, to what `AsyncCheckpointer.bytes_moved`,
    `bytes_written` and `files` grew by at that save's join."""
    model, params, opt = _tiny_state()
    tracer = SpanTracer(str(tmp_path / "timeline"))
    ckpt = AsyncCheckpointer(str(tmp_path / "ckpt"), model, 1, tracer=tracer)
    grew = {}
    for step in (10, 20):
        before = (ckpt.bytes_moved, ckpt.bytes_written, ckpt.files)
        ckpt.save(step, jnp.asarray(1.0), params, opt)
        ckpt.join()
        grew[step] = tuple(now - was for now, was in zip(
            (ckpt.bytes_moved, ckpt.bytes_written, ckpt.files), before))
    tracer.close()
    d2h = _events(str(tmp_path / "timeline"), "ckpt.d2h")
    write = _events(str(tmp_path / "timeline"), "ckpt.write")
    assert [e["args"]["step"] for e in d2h] == [10, 20]
    for moved, written in zip(d2h, write):
        step = moved["args"]["step"]
        assert written["args"]["step"] == step
        assert set(moved["args"]) == {"step", "bytes"}
        assert set(written["args"]) == {"step", "bytes", "files"}
        assert (moved["args"]["bytes"], written["args"]["bytes"],
                written["args"]["files"]) == grew[step]
    assert grew[10][0] == 3 * sum(
        x.nbytes for x in jax.tree.leaves(params)) and grew[10][2] == 1
    # the caller-side spans learn nothing as they run: `step` alone
    for e in _events(str(tmp_path / "timeline"), "ckpt.loss_sync",
                     "ckpt.join_prev", "ckpt.snapshot"):
        assert set(e["args"]) == {"step"}


def test_a_plain_save_on_the_loops_thread_carries_its_bytes_too(tmp_path):
    """`save_checkpoint(async_write=False)` under what `train()` hands out:
    on the loop's thread a span goes through `TrainObserver.span`, which
    yields what the tracer's span yields."""
    model, params, opt = _tiny_state()
    observer = TrainObserver(str(tmp_path / "obs"), sentinel=False)
    (path,) = save_checkpoint(str(tmp_path / "ckpt"), 5, 1.0, params,
                              model.canonical_specs(), 1, opt_state=opt,
                              tracer=observer.loop_spans)
    observer.close(print_summary=False)
    (moved,) = _events(str(tmp_path / "obs"), "ckpt.d2h")
    (written,) = _events(str(tmp_path / "obs"), "ckpt.write")
    assert moved["args"] == {"step": 5, "bytes": 3 * sum(
        x.nbytes for x in jax.tree.leaves(params))}
    assert written["args"] == {"step": 5, "bytes": os.path.getsize(path),
                               "files": 1}


class SteppedClock:
    """A clock that moves only when told to, from any thread, and counts
    how often it was read (a span reads it when it starts and when it
    ends)."""

    def __init__(self):
        self._t, self.reads, self._lock = 0.0, 0, threading.Lock()

    def __call__(self):
        with self._lock:
            self.reads += 1
            return self._t

    def advance(self, seconds):
        with self._lock:
            self._t += seconds


def test_prefetch_window_covers_the_draw_and_the_transform_not_the_put(
        tmp_path):
    """On a clock that moves 1 s in every draw, 2 s in every transform and
    100 s while the worker waits for room in the queue, every
    `prefetch_window` lasts 3 s."""
    clock = SteppedClock()
    tracer = SpanTracer(str(tmp_path), clock=clock)
    made_second = threading.Event()

    def draws():
        for i in range(4):
            clock.advance(1.0)
            yield i

    def transform(i):
        clock.advance(2.0)
        if i == 1:
            made_second.set()
        return i

    pf = Prefetcher(draws(), depth=1, transform=transform, tracer=tracer)
    # item 0 fills the queue; the worker makes item 1 and waits to put it
    assert made_second.wait(timeout=30)
    deadline = time.monotonic() + 30
    while clock.reads < 1 + 2 * 2:      # the tracer's epoch, two whole spans
        assert time.monotonic() < deadline
        time.sleep(0.01)
    clock.advance(100.0)
    assert list(pf) == [0, 1, 2, 3]
    tracer.close()
    made = _events(str(tmp_path), "prefetch_window")
    # four items (a fifth span, the draw that found the source empty, made
    # nothing and moved no clock)
    assert [e["dur"] for e in made if e["dur"]] == [3e6] * 4
    assert {e["cat"] for e in made} == {"data_prep"}


@pytest.mark.parametrize("module", ["prefetch", "checkpoint"])
def test_without_a_tracer_a_span_is_nothing_at_all(module, tmp_path):
    """`span_of(None, ...)` is the `nullcontext()` it was: no object with a
    clock, no event, and `as found` is None, which is how the writer knows
    there is nothing to add its bytes to."""
    from contextlib import nullcontext
    from distributed_pytorch_from_scratch_tpu.obs.trace import span_of
    cm = span_of(None, "ckpt.d2h", cat="checkpoint", step=1)
    assert isinstance(cm, nullcontext)
    with cm as found:
        assert found is None
    if module == "prefetch":
        assert list(Prefetcher(iter(range(3)), depth=1)) == [0, 1, 2]
    else:
        model, params, opt = _tiny_state()
        ckpt = AsyncCheckpointer(str(tmp_path), model, 1)
        ckpt.save(10, jnp.asarray(1.0), params, opt)
        assert len(ckpt.join()) == 1 and ckpt.bytes_moved > 0
    assert not glob.glob(str(tmp_path / "**" / "trace.jsonl"),
                         recursive=True)


def test_checkpointer_gather_hook_decides_who_writes(tmp_path):
    """The multi-host seam: `gather` hands host arrays to the writing
    process and None to the others, which then start no write."""
    model, params, opt = _tiny_state()
    to_host = lambda p, o: (jax.tree.map(np.asarray, p),
                            o._replace(mu=jax.tree.map(np.asarray, o.mu),
                                       nu=jax.tree.map(np.asarray, o.nu)))
    rec = Recorder()
    main = AsyncCheckpointer(str(tmp_path / "main"), model, 1, tracer=rec,
                             gather=to_host)
    main.save(10, jnp.asarray(10.0), params, opt)
    assert len(main.join()) == 1 and rec.named("ckpt.gather")
    other = AsyncCheckpointer(str(tmp_path / "other"), model, 1,
                              gather=lambda p, o: None)
    other.save(10, jnp.asarray(10.0), params, opt)
    assert other.join() is None and other.last_saved == 10
    assert not os.path.exists(tmp_path / "other")


def test_loop_spans_account_goodput_on_the_loops_thread_only(tmp_path):
    observer = TrainObserver(str(tmp_path), sentinel=False)
    spans = observer.loop_spans
    with spans.span("ckpt.snapshot", cat="checkpoint", step=3):
        pass
    with spans.span("data_wait", cat="data_wait", step=4):
        pass

    def writer():
        with spans.span("ckpt.write", cat="checkpoint", step=3):
            pass

    other = threading.Thread(target=writer)
    other.start()
    other.join()
    buckets = observer.goodput.summary()["buckets_s"]
    flight = [r for r in observer.flight.snapshot() if r["kind"] == "span"]
    observer.close(print_summary=False)
    evs = json.load(open(tmp_path / "trace.json"))["traceEvents"]
    assert [(e["name"], e["cat"], e["args"]["step"]) for e in evs
            if e["ph"] == "X"] == [
        ("ckpt.snapshot", "checkpoint", 3), ("data_wait", "data_wait", 4),
        ("ckpt.write", "checkpoint", 3)]
    assert buckets["checkpoint"] > 0 and buckets["data_wait"] > 0
    # the writer's wall time runs beside the loop's: timeline only
    assert [(r["bucket"], r["name"]) for r in flight] == [
        ("checkpoint", "ckpt.snapshot"), ("data_wait", "data_wait")]


# ------------------------------------------------- C. names on the device

def test_compiled_step_carries_the_scopes_and_the_flash_names():
    mesh = make_mesh(MeshConfig(dp=1, tp=1), devices=jax.devices()[:1])
    model = GPT2Transformer(CFG, tp_size=1, attn_impl="flash_interpret",
                            remat=True)
    params = model.init(jax.random.key(0))
    opt = init_adam_state(params)
    ids = jnp.zeros((2, 16), jnp.int32)
    step = build_train_step(model, mesh, OptimizerConfig(),
                            with_grad_norm=True)
    hlo = step.lower(params, opt, ids, ids, ids).compile().as_text()
    names = set(re.findall(r'op_name="([^"]+)"', hlo))

    def some(*parts):
        return any(all(p in n for p in parts) for n in names)

    assert some("jit(step)/optimizer/")
    assert some("jit(step)/grad_norm/")
    # forward, recomputed forward and backward of the layer body are told
    # apart by what JAX itself adds to the name stack
    assert some("loss_and_grad/jvp(", "/while/body/")
    assert some("loss_and_grad/transpose(jvp(", "rematted_computation")
    assert some("loss_and_grad/transpose(jvp(", "/while/body/")
    # the head's forward and backward
    assert some("loss_and_grad/jvp(", "/head_loss/")
    assert some("loss_and_grad/transpose(jvp(", "/head_loss/")
    # the kernels' names ride the name stack into the instruction
    assert some("loss_and_grad/jvp(", "/flash_fwd/")
    assert some("rematted_computation/flash_fwd/")
    assert some("transpose(jvp(", "/flash_bwd")
    # nothing of the step is outside the three scopes
    assert all(n.startswith(("jit(step)/loss_and_grad", "jit(step)/optimizer",
                             "jit(step)/grad_norm"))
               for n in names if n.startswith("jit(step)/"))
