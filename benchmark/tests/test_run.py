"""run.py end to end on the CPU backend: `--rehearse` prints the contract's
last line with no device number in it, and without `--rehearse` a backend
that is not a TPU is a non-zero exit and no result."""

import json
import os
import shutil
import subprocess
import sys

import pytest

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


def reported(group, cell):
    return [m for m in MANIFEST[group]
            if cell in m.get("workloads", [cell])]


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell,chips", CELLS)
def test_rehearse_prints_the_contracts_line(cell, chips, trace):
    done = run(["--workload", cell, "--seed", "3000000019", "--seconds", "2",
                "--trace", str(trace), "--rehearse"], devices=chips)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
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
