"""The benchmark's command:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell, configuration, family, runner or
per-layer metric is a file found by the name in `BENCHMARK.json`:

    benchmark/workloads/<cell>.json        what defines the job; names its runner
    benchmark/configs/<config>.json        the sizes as run; names its family
    benchmark/families/<family>.py         build(config, mesh sizes, dtype)
    benchmark/runners/<runner>.py          run(job) -> Outcome
    benchmark/data/<kind>.py               the batches a workload's `data` asks for
    benchmark/layer_metrics/<metric>.py    read(measured) -> number or None

The last line of standard output is one JSON object: `correct`, `attempted`,
`failed`, `metrics`, `device`, with `--trace 1` `breakdown`, and last
`compared`: each number `correct` rests on beside its limit (the last lines
of standard error say the same, one number a line). With `--trace 0` the
metrics are the cell's end-to-end metrics, with `--trace 1` its per-layer
metrics. Earlier lines are a log for people.

`--rehearse` (not part of the driver's command) runs the same control flow at
the tiny shape the workload file gives under `rehearse`, on any backend, and
prints every metric that is a time or a share of the device as `null`.
Without it a backend that is not a TPU is a non-zero exit and no result.
`--unpinned` (the tools' under `benchmark/tools/`, not the driver's) takes
weights and batches from `--seed` whatever the workload file pins: the
check's limits were read over many weights, and a control is read so again.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.time()

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib.cells import load_cell  # noqa: E402
from benchmark.lib.files import load_module  # noqa: E402
from benchmark.lib.job import Job  # noqa: E402 (after the path)

# sources whose numbers mean something only on the chip
DEVICE_SOURCES = ("host_clock", "device_trace", "program_span")


def reported_in(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--unpinned", action="store_true",
                    help="not the driver's: take weights and batches from "
                         "--seed whatever the workload file pins, as the "
                         "check's limits were read (benchmark/tools/)")
    ap.add_argument("--dump", default=None, metavar="DIR",
                    help="with --trace 1, also write the capture as plain "
                         "JSON there (how the test fixture was recorded)")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if args.workload not in cells:
        raise SystemExit(f"benchmark: {args.workload!r} is not a workload of "
                         f"BENCHMARK.json (has: {sorted(cells)})")
    workload, config = load_cell(args.workload, args.rehearse)
    if args.unpinned:
        workload = {k: v for k, v in workload.items() if k != "init_seed"}
        workload["data"] = {k: v for k, v in workload["data"].items()
                            if k != "seed"}
    job = Job(T_PROCESS_START, args.workload, workload, config,
              load_module("families", config["family"]), args.seed,
              args.seconds, bool(args.trace), args.rehearse, args.dump)
    outcome = load_module("runners", workload["runner"]).run(job)

    if args.trace:
        wanted = [m for m in manifest["per_layer"]
                  if reported_in(m, args.workload)]
        values = {m["name"]: load_module("layer_metrics", m["name"])
                  .read(outcome.measured) for m in wanted}
    else:
        wanted = [m for m in manifest["end_to_end"]
                  if reported_in(m, args.workload)]
        values = {m["name"]: outcome.end_to_end.get(m["name"])
                  for m in wanted}
    metrics = {}
    for m in wanted:
        value = values[m["name"]]
        if args.rehearse and m["source"] in DEVICE_SOURCES:
            value = None
        if value is not None or args.rehearse:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    line = {"correct": bool(outcome.correct), "attempted": outcome.attempted,
            "failed": outcome.failed, "metrics": metrics,
            "device": outcome.device}
    if outcome.breakdown is not None:
        line["breakdown"] = outcome.breakdown
    if outcome.compared is not None:
        # each number `correct` rests on beside its limit: the line's last
        # key, and the last lines of standard error
        line["compared"] = outcome.compared
        for name, (value, limit) in outcome.compared.items():
            print(f"compared {name} {value} limit {limit}", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
