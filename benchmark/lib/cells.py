"""A cell's two files as `benchmark/run.py` reads them, for the tools that
run a cell's runner outside `run.py` (benchmark/tools/). The configuration
is the one the workload file names, so a cell that `BENCHMARK.json` does not
list yet (a staged one: PERF.md section 7) loads too."""

from __future__ import annotations

from benchmark.lib.files import load_json


def load_cell(name: str, rehearse: bool = False):
    """(workload, config) of `workloads/<name>.json`; with `rehearse`, at
    the tiny shape the workload file gives."""
    workload = load_json("workloads", name + ".json")
    config = load_json("configs", workload["config"] + ".json")
    if rehearse:
        tiny = workload["rehearse"]
        config = {**config, **tiny.get("config", {})}
        workload = {**workload, **{k: v for k, v in tiny.items()
                                   if k != "config"}}
    return workload, config
