"""The `train_program` runner and `benchmark/lib/train_spans.py`: the cell's
rehearsal prints the contract's line with every metric the cell lists, the
numbers `correct` rests on and the window's stamps, all from the program's
own records; a reference perturbed by hand comes out `correct: false`; and
the readers, on a hand-written `trace.jsonl` of two intervals and one gap,
give the numbers worked out here."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from benchmark.lib import train_spans as ts
from benchmark.lib import trace
from benchmark.lib.program_trace import jsonl_events

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "gpt2-medium.train-program-b12-t1024"
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
DEVICE_SOURCES = {"host_clock", "device_trace", "program_span"}
NEW = ["setup.backend_s", "setup.logs_s", "setup.data_s", "setup.model_s",
       "setup.init_s", "setup.opt_state_s", "setup.build_step_s",
       "setup.unspanned_s", "loop.dispatch_ms", "loop.device_sync_ms",
       "loop.log_ms", "loop.log_programs", "loop.unspanned_pct",
       "loop.recompiles"]


def _env():
    return dict(os.environ, JAX_PLATFORMS="cpu",
                XLA_FLAGS="--xla_force_host_platform_device_count=1",
                JAX_COMPILATION_CACHE_DIR=os.path.join(
                    ROOT, ".jax_cache", "rehearse"))


@pytest.fixture(scope="module")
def traced():
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "3000000019", "--seconds", "1",
         "--trace", "1", "--rehearse"],
        cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    return [json.loads(line) for line in done.stdout.strip().splitlines()
            if line.startswith("{")]


def test_the_rehearsal_prints_every_new_metric(traced):
    line = traced[-1]
    listed = {m["name"]: m for m in MANIFEST["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    assert set(NEW) <= set(listed) and set(line["metrics"]) == set(listed)
    for name, got in line["metrics"].items():
        assert got["unit"] == listed[name]["unit"]
        if listed[name]["source"] in DEVICE_SOURCES:
            assert got["value"] is None, name
    # what a CPU run can say: the counts
    assert line["metrics"]["loop.log_programs"]["value"] == 0
    assert line["metrics"]["loop.recompiles"]["value"] == 0
    assert line["correct"] is True and line["failed"] == 0
    assert list(line)[-1] == "compared" and set(line["compared"]) == {
        "loss", "grad_norm", "losses_not_finite", "loss_last_interval",
        "params_built"}
    built, wanted = line["compared"]["params_built"]
    assert built == wanted > 0


def test_the_windows_stamps_are_the_programs(traced):
    window = next(d for d in traced if d.get("event") == "window")
    setup = next(d for d in traced if d.get("event") == "setup")
    # the traced run: the program's capture covers steps 3 to 26, so the
    # window opens at the sync of step 30, and closes at a sync
    assert window["open_step"] == 30 and window["close_step"] % 10 == 0
    assert window["steps"] == window["close_step"] - 30
    assert line_attempted(traced) == window["steps"]
    # one stamp a log interval from step 10 on, as `train()` wrote them,
    # and the run ended at the poll behind the closing one
    assert len(window["stamps"]) == window["close_step"] // 10
    assert window["stamps"] == sorted(window["stamps"])
    assert window["run_steps"] == window["close_step"]
    assert window["recompiles"]["count"] == 0
    # what the README's command would pass, and nothing of the runner's own
    argv = setup["argv"]
    assert argv[:2] == ["--family", "gpt2"] and "--max_steps" not in argv
    assert argv[argv.index("--log_interval") + 1] == "10"
    assert argv[-2:] == ["--profile_steps", "24"]
    assert setup["init_seed"] == setup["data_seed"] == 3000000019


def line_attempted(lines):
    return lines[-1]["attempted"]


PERTURBED = """
import sys
sys.path.insert(0, {root!r})
from benchmark.lib.cells import load_cell
from benchmark.lib.files import load_module
from benchmark.lib.job import Job
import time
runner = load_module("runners", "train_program")
real = runner._reference
def off_by_two_thousandths(*args):
    loss, norm = real(*args)
    return [loss * 1.002, norm]
runner._reference = off_by_two_thousandths
workload, config = load_cell({cell!r}, rehearse=True)
job = Job(time.time(), {cell!r}, workload, config,
          load_module("families", config["family"]), 11, 0.5, False, True,
          None)
outcome = runner.run(job)
print("RESULT", int(outcome.correct), outcome.compared["loss"][0],
      outcome.compared["loss"][1])
"""


def test_a_perturbed_reference_is_not_correct():
    done = subprocess.run(
        [sys.executable, "-c", PERTURBED.format(root=ROOT, cell=CELL)],
        cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    result = next(line for line in done.stdout.splitlines()
                  if line.startswith("RESULT")).split()
    assert result[1] == "0"
    assert float(result[2]) > float(result[3]) == 5e-4


def test_a_program_without_stop_is_refused_before_the_chip(tmp_path):
    """The parent commit's `train()` takes no `stop`: the runner says so
    and exits, with the backend untouched."""
    script = PERTURBED.format(root=ROOT, cell=CELL).replace(
        "real = runner._reference", """
from distributed_pytorch_from_scratch_tpu import train as program
old = program.train
program.train = lambda args: old(args)
real = runner._reference""")
    done = subprocess.run([sys.executable, "-c", script], cwd=ROOT,
                          env=_env(), capture_output=True, text=True,
                          timeout=600)
    assert done.returncode != 0 and "takes no `stop`" in done.stderr
    assert "RESULT" not in done.stdout


# ---- the readers on a hand-written timeline ----

LOOP, WORKER = 7, 9


def X(name, ts, dur, tid=LOOP, **args):
    ev = {"name": name, "ph": "X", "ts": ts, "dur": dur, "pid": 0,
          "tid": tid}
    if args:
        ev["args"] = args
    return ev


# microseconds from train()'s first line; log interval 2, the window from
# the sync of step 2 (ends 9,007,100) to the sync of step 6 (10,000,000)
EVENTS = [
    X("setup.backend", 0, 1_000_000), X("setup.logs", 1_000_000, 500_000),
    # 100,000 in no span
    X("setup.data", 1_600_000, 400_000), X("setup.model", 2_000_000, 100_000),
    X("setup.init", 2_100_000, 900_000),
    X("setup.opt_state", 3_000_000, 200_000),
    X("setup.build_step", 3_200_000, 300_000),
    X("data_wait", 3_500_000, 1_000, step=0), X("h2d", 3_501_000, 2_000),
    X("compile", 3_503_000, 5_000_000, step=0),
    X("compile.backend", 3_600_000, 4_000_000),     # inside `compile`
    X("step", 8_503_000, 3_000, step=0),
    # 100 in no span
    X("data_wait", 8_506_100, 100, step=1), X("h2d", 8_506_200, 300),
    X("step", 8_506_500, 500, step=1),
    # 100 in no span, then the sync that opens the window
    X("device_sync", 8_507_100, 500_000, step=2),
    X("log", 9_007_100, 2_000, step=2, programs=3),
    # the one gap written out: 100 in no span
    X("data_wait", 9_009_200, 100, step=2), X("h2d", 9_009_300, 300),
    X("step", 9_009_600, 400, step=2),
    # 200
    X("data_wait", 9_010_200, 100, step=3), X("h2d", 9_010_300, 300),
    X("step", 9_010_600, 600, step=3),
    # 100
    X("device_sync", 9_011_300, 488_700, step=4),
    X("log", 9_500_000, 1_000, step=4, programs=0),
    X("data_wait", 9_501_000, 100, step=4), X("h2d", 9_501_100, 300),
    X("step", 9_501_400, 400, step=4),
    # 200
    X("data_wait", 9_502_000, 100, step=5), X("h2d", 9_502_100, 300),
    X("step", 9_502_400, 600, step=5),
    X("device_sync", 9_503_000, 497_000, step=6),
    X("log", 10_000_000, 1_000, step=6, programs=1),    # past the window
    # the prefetch worker, beside the loop: none of the loop's numbers
    X("prefetch_window", 9_000_000, 900_000, tid=WORKER),
    {"name": "recompile", "ph": "i", "s": "p", "ts": 9_400_000, "pid": 0,
     "tid": LOOP},
]


@pytest.fixture
def measured(tmp_path):
    path = tmp_path / "trace.jsonl"
    path.write_text("".join(json.dumps(ev) + "\n" for ev in EVENTS)
                    + '{"name": "torn')
    timeline = [ev for ev in jsonl_events(str(path)) if ev["ph"] == "X"]
    assert len(timeline) == len(EVENTS) - 1
    return SimpleNamespace(timeline=timeline, window_steps=(2, 6),
                           setup_s=12.0, recompiles=[{"step": 5}])


def test_the_window_and_the_loops_thread(measured):
    assert ts.loop_thread(measured.timeline) == LOOP
    assert ts.window_us(measured) == (9_007_100, 10_000_000)
    assert [ev["args"]["step"] for ev in ts.in_window(measured, "log")] \
        == [2, 4]
    assert [ev["args"]["step"]
            for ev in ts.in_window(measured, "device_sync")] == [4, 6]
    assert len(ts.in_window(measured, "step")) == 4
    assert ts.in_window(measured, "prefetch_window") == []


WORKED = {
    "setup.backend_s": 1.0, "setup.logs_s": 0.5, "setup.data_s": 0.4,
    "setup.model_s": 0.1, "setup.init_s": 0.9, "setup.opt_state_s": 0.2,
    "setup.build_step_s": 0.3,
    # before the window the loop's thread is under a span for 3.4 s of
    # set-up + 1,000 + 2,000 + 5,000,000 (`compile.backend` inside it adds
    # nothing) + 3,000 + 100 + 300 + 500 + 500,000 us = 8.9069 s of 12
    "setup.unspanned_s": 12.0 - 8.9069,
    "loop.dispatch_ms": (0.4 + 0.6 + 0.4 + 0.6) / 4,
    "loop.device_sync_ms": (488.7 + 497.0) / 2,
    "loop.log_ms": (2.0 + 1.0) / 2,
    # the window's logs are those of steps 2 and 4; after the first: step 4's
    "loop.log_programs": 0.0,
    # 100 + 200 + 100 + 200 us of 992,900 in no span
    "loop.unspanned_pct": 100.0 * 600 / 992_900,
    "loop.recompiles": 1,
}


@pytest.mark.parametrize("name", NEW)
def test_a_reader_gives_the_worked_number(measured, name):
    assert set(WORKED) == set(NEW) == set(ts.READERS)
    assert ts.READERS[name](measured) == pytest.approx(WORKED[name],
                                                       rel=1e-9, abs=1e-9)
    # and the file the harness finds by the metric's name reads the same
    from benchmark.lib.files import load_module
    assert load_module("layer_metrics", name).read(measured) \
        == ts.READERS[name](measured)


@pytest.mark.parametrize("name", NEW)
def test_a_reader_gives_nothing_without_a_timeline(name):
    """`--trace 0`, or a program from before the spans: None, no raise."""
    for timeline in (None, [], [X("step", 0, 10, step=0)]):
        m = SimpleNamespace(timeline=timeline, window_steps=(2, 6),
                            setup_s=12.0, recompiles=[])
        got = ts.READERS[name](m)
        assert got is None or (name == "loop.recompiles" and got == 0)


def test_gaps_are_named_by_the_loops_own_spans():
    E = trace.Event
    host = trace.Plane(trace.HOST_PLANE, [
        trace.Line("python", [E("prog.step", 100, 50), E("prog.log", 200, 300),
                              E("prog.device_sync", 150, 40),
                              E("bench.other", 0, 1000)]),
        trace.Line("python", [E("prog.prefetch_window", 0, 1000)])])
    spans = ts.loop_thread_spans([host])
    assert [s.name for s in spans] == ["prog.device_sync", "prog.step",
                                      "prog.log"]
    dev = trace.DeviceTrace(0, (0, 1000), 2, [E("fusion.1", 0, 210),
                                              E("fusion.2", 480, 520)], [])
    assert trace.top_gaps(dev, spans) == [("prog.log", 270 / 1e9)]
