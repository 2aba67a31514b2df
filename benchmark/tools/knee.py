"""The knee of a serving cell, found once when the cell is defined: a ladder
of offered rates, one run of the cell's runner a rung, each in a process of
its own (one process holds the chip; this parent never touches JAX).

    python3 benchmark/tools/knee.py --workload <cell> --rates 2,2.5,3.125 \
        --seconds 40 --seed 1 [--out chiprun_out/knee.json]

A rung is the cell as `benchmark/run.py` would run it, with
`data.arrivals.rate_rps` replaced. The table has, for each rung, the tails,
the share of requests sent that met both limits, the backlog at the middle
and at the close of the window, the 95th percentile of the wait for
admission and the preemptions. A rung **holds** (ISSUE 27's rule) if no
request failed, at least `MET_SHARE_PCT` of the requests sent met both of
the workload file's `limits`, and the backlog when the arrivals end is no
larger than at their middle; the knee is the highest rung that holds, or
none. The result goes into the workload file by hand, with the table, the
date and the JAX version.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

T_START = time.time()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

MET_SHARE_PCT = 90.0

KEEP = ("rate_rps", "sent", "failed", "attained_pct", "backlog_mid",
        "backlog_close", "ttft_p50_ms", "ttft_p95_ms", "tpot_p50_ms",
        "tpot_p95_ms", "queue_wait_p95_ms", "lateness_p95_ms",
        "out_tokens_per_s", "decode_steps", "preemptions", "drained_after_s")


def rung(args) -> int:
    """Child: one run at `--rate`, the runner's log lines on stdout."""
    from benchmark.lib.cells import load_cell
    from benchmark.lib.files import load_module
    from benchmark.lib.job import Job

    workload, config = load_cell(args.workload)
    workload["data"]["arrivals"]["rate_rps"] = args.rate
    job = Job(T_START, args.workload, workload, config,
              load_module("families", config["family"]), args.seed,
              args.seconds, False, False, None)
    outcome = load_module("runners", workload["runner"]).run(job)
    print(json.dumps({"event": "rung", "correct": bool(outcome.correct),
                      "end_to_end": outcome.end_to_end}), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--rates", default=None, help="comma-separated rungs")
    ap.add_argument("--rate", type=float, default=None, help="(child)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    if args.rate is not None:
        return rung(args)

    table = []
    for rate in (float(r) for r in args.rates.split(",")):
        done = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--workload",
             args.workload, "--seed", str(args.seed), "--seconds",
             str(args.seconds), "--rate", str(rate)],
            cwd=ROOT, capture_output=True, text=True)
        lines = [json.loads(l) for l in done.stdout.splitlines()
                 if l.startswith("{")]
        window = next((l for l in lines if l.get("event") == "window"), None)
        last = next((l for l in lines if l.get("event") == "rung"), {})
        if done.returncode or window is None:
            row = {"rate_rps": rate, "error": done.stderr[-800:]}
        else:
            row = {k: window.get(k) for k in KEEP}
            row["correct"] = last.get("correct")
            row["holds"] = bool(
                row["failed"] == 0 and row["attained_pct"] >= MET_SHARE_PCT
                and row["backlog_close"] <= row["backlog_mid"])
        print(json.dumps(row), flush=True)
        table.append(row)
    holding = [r["rate_rps"] for r in table if r.get("holds")]
    result = {"workload": args.workload, "seed": args.seed,
              "seconds": args.seconds, "met_share_pct": MET_SHARE_PCT,
              "knee_rps": max(holding) if holding else None, "ladder": table}
    print(json.dumps(result), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
