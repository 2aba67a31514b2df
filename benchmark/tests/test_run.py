"""run.py end to end on the CPU backend: `--rehearse` prints the contract's
last line with no device number in it, and without `--rehearse` a backend
that is not a TPU is a non-zero exit and no result."""

import functools
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.lib.cells import load_cell
from benchmark.lib.files import load_module
from benchmark.lib.job import Job, data_seed, init_seed

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CELLS = [(w["name"], w["chips"]) for w in MANIFEST["workloads"]]
DEVICE_SOURCES = {"host_clock", "device_trace", "program_span"}


def run(args, cwd=ROOT, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               JAX_COMPILATION_CACHE_DIR=os.path.join(
                   cwd, ".jax_cache", "rehearse"))
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmark", "run.py"), *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=600)


@functools.lru_cache(maxsize=None)
def rehearsal(cell, chips, seed, trace=0):
    """One rehearsal a (cell, seed, trace) and test process."""
    return run(["--workload", cell, "--seed", str(seed), "--seconds", "2",
                "--trace", str(trace), "--rehearse"], devices=chips)


def log_line(done, event):
    return next(d for d in map(json.loads, done.stdout.splitlines())
                if d.get("event") == event)


def reported(group, cell):
    return [m for m in MANIFEST[group]
            if cell in m.get("workloads", [cell])]


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell,chips", CELLS)
def test_rehearse_prints_the_contracts_line(cell, chips, trace):
    done = rehearsal(cell, chips, 3000000019, trace)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    # each number compared beside its limit: the line's last key, and the
    # last lines of standard error
    assert list(line)[-1] == "compared" and {"loss", "grad_norm"} <= set(
        line["compared"])
    assert all(len(pair) == 2 for pair in line["compared"].values())
    assert done.stderr.strip().splitlines()[-len(line["compared"]):] == [
        f"compared {k} {v} limit {limit}"
        for k, (v, limit) in line["compared"].items()]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 20
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == chips
    assert line["device"]["memory_peak_bytes"] is None
    wanted = reported("per_layer" if trace else "end_to_end", cell)
    assert set(line["metrics"]) == {m["name"] for m in wanted}
    for m in wanted:
        got = line["metrics"][m["name"]]
        assert got["unit"] == m["unit"]
        if m["source"] in DEVICE_SOURCES:
            assert got["value"] is None, m["name"]
    if trace:
        # (it moves `step_ms_p90`, so a cell that does not report that
        # metric does not list it: test_manifest.py)
        if "entry.compiles_in_window" in line["metrics"]:
            assert line["metrics"]["entry.compiles_in_window"]["value"] == 0
        assert line["device"]["busy_s"] is None
    # no time taken on the CPU stands on a log line either
    for text in done.stdout.strip().splitlines()[:-1]:
        for key, value in json.loads(text).items():
            if key.endswith(("_s", "_ms", "seconds")) or "_ms_" in key:
                assert value is None, (key, value)


def test_off_the_chip_nothing_is_measured():
    done = run(["--workload", CELLS[0][0], "--seed", "1", "--seconds", "1",
                "--trace", "0"])
    assert done.returncode != 0
    assert "not a TPU" in done.stderr
    assert not any(l.startswith("{") for l in done.stdout.splitlines())


def test_unknown_workload_is_refused():
    done = run(["--workload", "no-such-cell", "--seed", "1", "--seconds",
                "1", "--trace", "0", "--rehearse"])
    assert done.returncode != 0 and done.stdout == ""


def test_without_the_program_there_is_no_result(tmp_path):
    """A directory that holds only BENCHMARK.json and benchmark/."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    done = run(["--workload", CELLS[0][0], "--seed", "1", "--seconds", "1",
                "--trace", "0", "--rehearse"], cwd=str(tmp_path))
    assert done.returncode != 0 and done.stdout == ""


def job_of(cell, seed):
    workload, config = load_cell(cell, rehearse=True)
    return Job(0.0, cell, workload, config, None, seed, 2.0, False, True, None)


@pytest.mark.parametrize("cell,chips", CELLS)
def test_the_weights_seed_is_the_files_where_it_has_one(cell, chips):
    """`init_seed` in the workload file is the weights' seed whatever
    `--seed` is; a file without one follows `--seed`. The stream follows
    `--seed` unless `data.seed` pins it."""
    a, b = job_of(cell, 3000000019), job_of(cell, 7)
    pinned = a.workload.get("init_seed")
    if pinned is None:
        assert (init_seed(a), init_seed(b)) == (3000000019, 7)
    else:
        assert init_seed(a) == init_seed(b) == pinned
    replay = a.workload["data"].get("seed")
    first = []
    for job in (a, b):
        assert data_seed(job) == (job.seed if replay is None else replay)
        batches = load_module("data", job.workload["data"]["kind"])
        first.append(batches.TokenBatches(job.workload["data"], 503, 2, 64,
                                          data_seed(job)).next()[0])
    assert (first[0] == first[1]).all() == (replay is not None)


@pytest.mark.parametrize("cell,chips", CELLS)
def test_two_seeds_are_one_job_where_the_file_holds_the_weights(cell, chips):
    """Two `--seed`s through the runner itself: an expert cell initialises
    from the same key under both and sees other batches (its first losses
    differ) unless its file pins them too; a cell with no `init_seed`
    initialises from the seed."""
    runs = [rehearsal(cell, chips, seed) for seed in (3000000019, 7)]
    for done in runs:
        assert done.returncode == 0, done.stderr[-2000:]
    setups = [log_line(done, "setup") for done in runs]
    workload = job_of(cell, 0).workload
    pinned = workload.get("init_seed")
    if pinned is None:
        assert [s["init_seed"] for s in setups] == [3000000019, 7]
    else:
        assert [s["init_seed"] for s in setups] == [pinned, pinned]
    first = [log_line(done, "window")["loss_first10"] for done in runs]
    replay = workload["data"].get("seed")
    if replay is None:
        assert [s["data_seed"] for s in setups] == [3000000019, 7]
        assert first[0] != first[1]
    else:       # a replay: `--seed` draws nothing, the runs are one run
        assert [s["data_seed"] for s in setups] == [replay, replay]
        assert first[0] == first[1]


def test_unpinned_takes_weights_and_batches_from_the_seed():
    """The tools' flag: the replay cell with nothing pinned."""
    cell = next(c for c, _ in CELLS
                if "seed" in load_cell(c)[0]["data"])
    done = run(["--workload", cell, "--seed", "7", "--seconds", "2",
                "--trace", "0", "--rehearse", "--unpinned"])
    assert done.returncode == 0, done.stderr[-2000:]
    setup = log_line(done, "setup")
    assert (setup["init_seed"], setup["data_seed"]) == (7, 7)
