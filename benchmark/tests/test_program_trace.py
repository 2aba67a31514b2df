"""benchmark/lib/program_trace.py against hand-worked values: the join of
device ops with the compiled step's `op_name`s, the phase of every op of a
hand-built capture written out one by one, the naming of idle gaps by the
loop's spans, the tracer's timeline cut to the window, and the readers of
the `train_ckpt` runner's per-layer metrics on a hand-built `measured`.
The runner is not named by a cell of BENCHMARK.json yet (PERF.md section 7,
PR 25), so its rehearsal builds the `Job` as `benchmark/run.py` does."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from benchmark.lib import program_trace as pt
from benchmark.lib import trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

STEP = "jit(step)/"
LOSS = STEP + "loss_and_grad/"
# a compiled module's text, cut to what `op_names` reads
HLO = f"""
HloModule jit_step, is_scheduled=true

%fused_computation.1 (p.0: f32[8]) -> f32[8] {{
  %p.0 = f32[8]{{0}} parameter(0)
  %mul.1 = f32[8]{{0}} multiply(%p.0, %p.0), metadata={{op_name="{LOSS}jvp(jit(loss_shard))/while/body/closed_call/mul" stack_frame_id=3}}
  ROOT %add.9 = f32[8]{{0}} add(%mul.1, %p.0), metadata={{op_name="{LOSS}jvp(jit(loss_shard))/while/body/closed_call/add"}}
}}

%fused_computation.2 (p.1: f32[8]) -> f32[8] {{
  %p.1 = f32[8]{{0}} parameter(0)
  %neg.1 = f32[8]{{0}} negate(%p.1), metadata={{op_name="{STEP}optimizer/neg"}}
  ROOT %copy.7 = f32[8]{{0}} copy(%neg.1)
}}

ENTRY %main.1 (a: f32[8]) -> f32[8] {{
  %a = f32[8]{{0}} parameter(0)
  %fusion.1 = f32[8]{{0}} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{LOSS}jvp(jit(loss_shard))/while/body/closed_call/add"}}
  %flash_fwd.2 = f32[8]{{0}} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={{op_name="{LOSS}jvp(jit(loss_shard))/while/body/closed_call/flash_fwd/pallas_call"}}
  %fusion.3 = f32[8]{{0}} fusion(%flash_fwd.2), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{LOSS}jvp(jit(loss_shard))/head_loss/dot_general"}}
  %fusion.4 = f32[8]{{0}} fusion(%fusion.3), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{LOSS}transpose(jvp(jit(loss_shard)))/head_loss/dot_general"}}
  %fusion.5 = f32[8]{{0}} fusion(%fusion.4), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{LOSS}transpose(jvp(jit(loss_shard)))/while/body/closed_call/checkpoint/rematted_computation/dot_general"}}
  %flash_bwd.6 = f32[8]{{0}} custom-call(%fusion.5), custom_call_target="tpu_custom_call", metadata={{op_name="{LOSS}transpose(jvp(jit(loss_shard)))/while/body/closed_call/checkpoint/flash_bwd/pallas_call"}}
  %fusion.7 = f32[8]{{0}} fusion(%flash_bwd.6), kind=kLoop, calls=%fused_computation.2
  %reduce.8 = f32[]{{:T(128)}} reduce(%fusion.7), metadata={{op_name="{STEP}grad_norm/reduce_sum"}}
  %copy.9 = f32[8]{{0}} copy(%fusion.7)
  ROOT %convert.10 = f32[8]{{0}} convert(%copy.9)
}}
"""

# chip 0: two runs of the step and, between them, another program (the
# snapshot copy), whose op is named like one of the step's. Every op's
# phase is written beside it; durations in ns.
OPS = [  # name, start, dur, phase
    ("fusion.1", 1000, 100, "fwd"),
    ("flash_fwd.2", 1100, 50, "fwd"),
    ("fusion.3", 1150, 30, "head_loss"),
    ("fusion.4", 1180, 40, "head_loss"),
    ("fusion.5", 1220, 90, "recompute"),
    ("flash_bwd.6", 1310, 80, "bwd"),
    ("fusion.7", 1390, 60, "optimizer"),     # no metadata: its called root
                                             # has none, its first named op
    ("reduce.8", 1450, 10, "optimizer"),
    ("copy.9", 1460, 20, "unattributed"),    # the compiler's own
    ("convert.10", 1480, 20, "unattributed"),
    ("fusion.1", 1600, 70, "other_programs"),   # the copy program's
    ("fusion.1", 2000, 100, "fwd"),
    ("flash_bwd.6", 2100, 200, "bwd"),
    ("copy.9", 2300, 25, "unattributed"),
]
MODULES = [("jit_step(1)", 1000, 500), ("jit__lambda(2)", 1590, 90),
           ("jit_step(1)", 2000, 400)]
HOST = [  # the loop's thread, then the writer's
    ("prog.data_wait", 900, 20), ("prog.h2d", 920, 30),
    ("bench.dispatch", 950, 40), ("prog.ckpt.loss_sync", 1400, 110),
    ("prog.ckpt.snapshot", 1520, 300), ("bench.wait", 1500, 900),
    ("prog.ckpt.d2h", 1700, 5000),
]


def planes():
    ev = lambda rows: [trace.Event(n, s, d) for n, s, d in rows]
    return [
        trace.Plane("/device:TPU:0", [
            trace.Line(trace.MODULES_LINE, ev(MODULES)),
            trace.Line(trace.OPS_LINE, ev([o[:3] for o in OPS])),
            trace.Line(trace.ASYNC_LINE, [])]),
        trace.Plane(trace.HOST_PLANE, [
            trace.Line("python", ev(HOST[:6])),
            trace.Line("python", ev(HOST[6:]))]),
    ]


def test_op_names_joins_by_instruction_and_a_fusion_is_its_roots():
    names = pt.op_names(HLO)
    assert names["fusion.1"].endswith("closed_call/add")
    assert names["flash_fwd.2"].endswith("flash_fwd/pallas_call")
    assert names["mul.1"].endswith("closed_call/mul")
    # no metadata of its own, the called root has none: the first named op
    assert names["fusion.7"] == STEP + "optimizer/neg"
    # the compiler's own instructions have no name stack
    assert "copy.9" not in names and "convert.10" not in names
    assert "a" not in names and "p.0" not in names


@pytest.mark.parametrize("op_name,phase", [
    (LOSS + "jvp(jit(loss_shard))/while/body/closed_call/dot_general", "fwd"),
    (LOSS + "jvp(jit(loss_shard))/jit(_take)/gather", "fwd"),
    (LOSS + "jvp(jit(loss_shard))/head_loss/dot_general", "head_loss"),
    (LOSS + "transpose(jvp(jit(loss_shard)))/head_loss/mul", "head_loss"),
    (LOSS + "transpose(jvp(jit(loss_shard)))/while/body/closed_call/"
     "checkpoint/rematted_computation/flash_fwd/pallas_call", "recompute"),
    (LOSS + "transpose(jvp(jit(loss_shard)))/while/body/closed_call/"
     "checkpoint/flash_bwd/pallas_call", "bwd"),
    (LOSS + "transpose(jvp(jit(loss_shard)))/jit(_take)/scatter-add", "bwd"),
    (STEP + "optimizer/jit(clip)/mul", "optimizer"),
    (STEP + "grad_norm/reduce_sum", "optimizer"),
    ("jit(step)/convert_element_type", "unattributed"),
    ("", "unattributed"), (None, "unattributed"),
])
def test_phase_rules(op_name, phase):
    assert pt.phase_of(op_name) == phase
    assert phase in pt.PHASES


def test_every_op_falls_in_one_phase_and_they_sum_to_busy():
    (dev,) = trace.device_traces(planes())
    assert dev.window == (1000, 2400) and dev.steps == 2
    runs = pt.step_runs(planes(), dev)
    assert runs == [(1000, 1500), (2000, 2400)]
    got = pt.phase_ns(dev, runs, pt.op_names(HLO))
    want = dict.fromkeys(pt.PHASES, 0)
    for _, _, dur, phase in OPS:
        want[phase] += dur
    assert got == want
    assert got == {"fwd": 250, "recompute": 90, "bwd": 280, "head_loss": 70,
                   "optimizer": 70, "other_programs": 70, "unattributed": 65}
    assert sum(got.values()) == dev.busy_ns() == 895
    assert pt.top_unattributed(dev, runs, pt.op_names(HLO)) == [
        ("copy.9", 45e-9), ("convert.10", 20e-9)]


def test_idle_gaps_are_named_by_the_loops_spans():
    (dev,) = trace.device_traces(planes())
    assert dev.gaps() == [(1500, 1600), (1670, 2000), (2325, 2400)]
    spans = pt.loop_spans(planes())
    # the writer's seconds-long span would cover every gap: left out
    assert "prog.ckpt.d2h" not in [s.name for s in spans]
    assert [s.dur_ns for s in spans] == sorted(s.dur_ns for s in spans)
    named = pt.named_gaps(dev, spans, at_least_ns=80)
    # 1500-1600: loss_sync covers 10, snapshot 80, wait 100 -> bench.wait;
    # 1670-2000: snapshot 150, wait 330 -> bench.wait; 75 ns is short
    assert named == {"bench.wait": 430, "short_gaps": 75}
    assert trace.top_gaps(dev, spans)[0] == ("bench.wait", 330e-9)
    ckpt = [s for s in spans if s.name.startswith("prog.ckpt.")]
    # under loss_sync or snapshot: 1500-1510 and 1520-1600 and 1670-1820
    assert pt.covered_gap_ns(dev, ckpt) == 10 + 80 + 150
    assert pt.named_gaps(dev, [], at_least_ns=80) == {
        "unattributed": 430, "short_gaps": 75}


def timeline(tmp_path):
    rows = [
        {"name": "h2d", "ph": "X", "ts": 1.0, "dur": 400.0, "tid": 1},
        {"name": "bench.window_open", "ph": "i", "ts": 2.0, "tid": 1},
        {"name": "data_wait", "ph": "X", "ts": 3.0, "dur": 100.0, "tid": 1,
         "args": {"step": 5}},
        {"name": "h2d", "ph": "X", "ts": 4.0, "dur": 300.0, "tid": 1,
         "args": {"step": 5}},
        {"name": "data_wait", "ph": "X", "ts": 5.0, "dur": 300.0, "tid": 1,
         "args": {"step": 6}},
        {"name": "h2d", "ph": "X", "ts": 6.0, "dur": 500.0, "tid": 1,
         "args": {"step": 6}},
        {"name": "ckpt.loss_sync", "ph": "X", "ts": 7.0, "dur": 250000.0,
         "tid": 1, "args": {"step": 7}},
        {"name": "ckpt.snapshot", "ph": "X", "ts": 8.0, "dur": 4000.0,
         "tid": 1, "args": {"step": 7}},
        {"name": "ckpt.d2h", "ph": "X", "ts": 9.0, "dur": 3.0e6, "tid": 2,
         "args": {"step": 7}},
        {"name": "ckpt.write", "ph": "X", "ts": 10.0, "dur": 2.5e6, "tid": 2,
         "args": {"step": 7}},
        {"name": "ckpt.loss_sync", "ph": "X", "ts": 11.0, "dur": 260000.0,
         "tid": 1, "args": {"step": 32}},
        {"name": "ckpt.join_prev", "ph": "X", "ts": 12.0, "dur": 30000.0,
         "tid": 1, "args": {"step": 7}},
        {"name": "ckpt.snapshot", "ph": "X", "ts": 13.0, "dur": 6000.0,
         "tid": 1, "args": {"step": 32}},
        {"name": "bench.window_close", "ph": "i", "ts": 14.0, "tid": 1},
        {"name": "ckpt.d2h", "ph": "X", "ts": 15.0, "dur": 9.0e6, "tid": 2,
         "args": {"step": 32}},
    ]
    path = tmp_path / "trace.jsonl"
    path.write_text("\n".join(json.dumps(r) for r in rows) + '\n{"torn')
    return str(path)


def test_the_timeline_is_cut_to_the_window(tmp_path):
    events = pt.jsonl_events(timeline(tmp_path))
    assert len(events) == 15                 # the torn line is left out
    inside = pt.between(events, "bench.window_open", "bench.window_close")
    assert len(inside) == 11 and inside[0]["name"] == "data_wait"
    assert pt.span_ms(inside, ("data_wait",)) == pytest.approx(0.4)
    assert pt.jsonl_events(str(tmp_path / "none.jsonl")) == []


def test_readers_on_a_hand_built_measured(tmp_path):
    (dev,) = trace.device_traces(planes())
    runs = pt.step_runs(planes(), dev)
    spans = pt.loop_spans(planes())
    inside = pt.between(pt.jsonl_events(timeline(tmp_path)),
                        "bench.window_open", "bench.window_close")
    m = SimpleNamespace(
        devices=[dev], phases=pt.phase_ns(dev, runs, pt.op_names(HLO)),
        window_spans=inside, capture_saves=1,
        ckpt_gap_ns=pt.covered_gap_ns(dev, [
            s for s in spans if s.name.startswith("prog.ckpt.")]))
    read = lambda name: pt.READERS[name](m)
    per_step = {"model.fwd_ms": 250, "model.recompute_ms": 90,
                "model.bwd_ms": 280, "model.head_loss_ms": 70,
                "train_step.optimizer_ms": 70,
                "train_step.unattributed_ms": 65}
    for name, ns in per_step.items():
        assert read(name) == pytest.approx(ns / 2 / 1e6), name
    assert read("checkpoint.device_ms") == pytest.approx((70 + 240) / 1e6)
    # with the other programs' 70 ns the phases are the busy time of a step
    assert (sum(per_step.values()) + 70) / 2 == dev.busy_ns() / dev.steps
    assert read("input.data_wait_ms") == pytest.approx(0.2)
    assert read("input.h2d_ms") == pytest.approx(0.4)
    # two saves: (250 + 4) and (260 + 30 + 6) ms
    assert read("checkpoint.stall_ms") == pytest.approx(275.0)
    assert read("checkpoint.write_s") == pytest.approx(5.5)

    # a program with no such spans, a run with no capture: nothing, no raise
    bare = SimpleNamespace(devices=[], window_spans=[])
    for metric in [*per_step, "checkpoint.device_ms", "input.data_wait_ms",
                   "input.h2d_ms", "checkpoint.stall_ms",
                   "checkpoint.write_s"]:
        assert pt.READERS[metric](bare) is None
    assert len(pt.READERS) == 11


# what benchmark/run.py --workload gpt2-medium.train-ckpt --seed 2147483659
# --seconds 2 --trace 1 --rehearse does up to the runner's Outcome
REHEARSE = """
import json, sys, time
t0 = time.time()
sys.path.insert(0, {root!r})
from benchmark.lib.files import load_json, load_module
from benchmark.lib.job import Job
name = "gpt2-medium.train-ckpt"
workload = load_json("workloads", name + ".json")
config = load_json("configs", workload["config"] + ".json")
tiny = workload["rehearse"]
config = {{**config, **tiny["config"]}}
workload = {{**workload, **{{k: v for k, v in tiny.items() if k != "config"}}}}
job = Job(t0, name, workload, config,
          load_module("families", config["family"]), 2147483659, 2.0, True,
          True, None)
out = load_module("runners", workload["runner"]).run(job)
print(json.dumps(dict(correct=out.correct, attempted=out.attempted,
                      failed=out.failed, end_to_end=sorted(out.end_to_end))))
"""


def test_rehearsal_saves_validates_and_reads_its_checkpoint_back():
    """The new cell end to end on the CPU: saves in the window, the newest
    whole, the final state read back bit for bit, the timeline on."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1",
               JAX_COMPILATION_CACHE_DIR=os.path.join(
                   ROOT, ".jax_cache", "rehearse"))
    done = subprocess.run(
        [sys.executable, "-c", REHEARSE.format(root=ROOT)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(l) for l in done.stdout.strip().splitlines()]
    (ckpt,) = [l for l in lines if l.get("event") == "checkpoint"]
    assert ckpt["newest_ok"] is True and ckpt["read_back"] is True
    assert ckpt["failed_saves"] == [] and len(ckpt["saves_in_window"]) >= 4
    steps = ckpt["saves_in_window"]
    assert {b - a for a, b in zip(steps, steps[1:])} == {5}
    # warm-up's, the window's, the traced one and the final one, all joined
    assert ckpt["saves"] == ckpt["files"] == len(steps) + 3
    assert ckpt["bytes_moved"] > 0 and ckpt["bytes_written"] > 0
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["end_to_end"] == ["setup_s", "step_ms_p90",
                                  "tokens_per_s_per_chip"]
    (window,) = [l for l in lines if l.get("event") == "window"]
    assert last["attempted"] == window["steps"] + len(steps)
