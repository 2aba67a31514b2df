"""ProfilerTrace window semantics (training/metrics.py).

The train loop's step counter can jump by steps_per_dispatch, so the
window logic must be boundary-tolerant: one trace per run, started at the
first boundary past start_step, stopped at-or-after stop_step, never
restarted. jax.profiler is monkeypatched — these are pure state-machine
tests, no real tracing."""

import jax
import jax.numpy as jnp
import pytest

from distributed_pytorch_from_scratch_tpu.training.metrics import (
    ProfilerTrace)


@pytest.fixture
def profiler_calls(monkeypatch):
    calls = []
    monkeypatch.setattr(
        jax.profiler, "start_trace",
        lambda d, profiler_options=None: calls.append(
            ("start", d, profiler_options)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop",)))
    return calls


def test_window_exact_steps(tmp_path, profiler_calls):
    p = ProfilerTrace(str(tmp_path), start_step=3, num_steps=2)
    for step in range(10):
        p.maybe_start(step)
        p.maybe_stop(step + 1, sync=jnp.zeros(()))
    starts = [c for c in profiler_calls if c[0] == "start"]
    stops = [c for c in profiler_calls if c[0] == "stop"]
    assert len(starts) == 1 and len(stops) == 1
    # started at the first boundary >= start_step, stopped at stop_step
    assert profiler_calls.index(starts[0]) < profiler_calls.index(stops[0])


def test_window_cleared_in_one_dispatch_jump(tmp_path, profiler_calls):
    """steps_per_dispatch=8 can hop the whole [3, 5) window in one jump:
    the trace must still start exactly once (at step 8) and stop at the
    next boundary, covering at least num_steps."""
    p = ProfilerTrace(str(tmp_path), start_step=3, num_steps=2)
    for step in range(0, 64, 8):
        p.maybe_start(step)
        p.maybe_stop(step + 8, sync=jnp.zeros(()))
    starts = [c for c in profiler_calls if c[0] == "start"]
    stops = [c for c in profiler_calls if c[0] == "stop"]
    assert len(starts) == 1 and len(stops) == 1


def test_done_prevents_restart(tmp_path, profiler_calls):
    p = ProfilerTrace(str(tmp_path), start_step=0, num_steps=1)
    p.maybe_start(0)
    p.maybe_stop(1)
    assert p._done and not p._active
    for step in range(2, 20):
        p.maybe_start(step)  # must not re-arm
    assert len([c for c in profiler_calls if c[0] == "start"]) == 1


def test_close_mid_window_stops_cleanly(tmp_path, profiler_calls):
    p = ProfilerTrace(str(tmp_path), start_step=0, num_steps=100)
    p.maybe_start(0)
    assert p._active
    p.close(sync=jnp.zeros(()))
    assert not p._active
    assert [c[:2] for c in profiler_calls] == [("start", p.log_dir),
                                               ("stop",)]
    p.close()  # idempotent: no second stop
    assert profiler_calls.count(("stop",)) == 1


def test_close_without_start_is_noop(tmp_path, profiler_calls):
    p = ProfilerTrace(str(tmp_path), start_step=5, num_steps=2)
    p.maybe_stop(1)
    p.close()
    assert profiler_calls == []


def test_capture_traces_no_python_frames_and_spans_its_start_and_stop(
        tmp_path, profiler_calls):
    """JAX's default traces Python frames (`python_tracer_level` 1), which
    slows the steps the capture reads: the program's capture turns it off,
    as the benchmark's do. Starting and stopping hold the loop's thread and
    are spans of the tracer the loop hands in."""
    from distributed_pytorch_from_scratch_tpu.obs.trace import SpanTracer

    assert jax.profiler.ProfileOptions().python_tracer_level != 0
    tracer = SpanTracer(str(tmp_path / "timeline"))
    p = ProfilerTrace(str(tmp_path), start_step=1, num_steps=2, tracer=tracer)
    for step in range(5):
        p.maybe_start(step)
        p.maybe_stop(step + 1, sync=jnp.zeros(()))
    (start,) = [c for c in profiler_calls if c[0] == "start"]
    assert start[2].python_tracer_level == 0
    tracer.close()
    import json
    events = json.load(open(tmp_path / "timeline" / "trace.json"))
    spans = [(e["name"], e["cat"], e["args"]["step"])
             for e in events["traceEvents"]
             # (a worker whose compile listener is on writes `compile.*` too)
             if e.get("ph") == "X" and e["name"].startswith("profile.")]
    assert spans == [("profile.start", "profile", 1),
                     ("profile.stop", "profile", 3)]
