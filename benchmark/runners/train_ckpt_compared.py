"""The `train_ckpt_compared` runner: `runners/train_ckpt.py`'s run, whole and
unchanged, with what every admitted cell's output has carried since PR 49
and `train_ckpt` (PR 25, staged until PR 53) never got:

* the result's `compared`: each number `correct` rests on beside its limit.
  The check's two readings and the window's two (`runners/train.compared`),
  then the three this runner's `correct` adds: the saves that raised or did
  not validate, whether the newest file is whole, whether the final state
  read back bit for bit (each as a count of faults against 0);
* `init_seed` and `data_seed` on the `setup` log line (this cell pins
  neither, so both are `--seed`).

`train_ckpt` prints the numbers and keeps none, so they are taken where it
prints them: its module's `log` is this module's for the length of the run.
That is a wrapper around a file a `tracing` PR may not edit; the next
`benchmark` PR moves these few lines into `train_ckpt.py` and deletes this
file (PERF.md section 7, PR 53).
"""

from __future__ import annotations

from benchmark.lib.job import Job, Outcome, data_seed, init_seed
from benchmark.runners import train, train_ckpt


def run(job: Job) -> Outcome:
    seen = {}

    def log(**fields) -> None:
        if fields.get("event") == "setup":
            fields.update(init_seed=init_seed(job), data_seed=data_seed(job))
        seen[fields.get("event")] = fields
        train.log(**fields)

    train_ckpt.log = log
    try:
        outcome = train_ckpt.run(job)
    finally:
        train_ckpt.log = train.log
    window, ckpt = seen["window"], seen["checkpoint"]
    saves_failed = len(set(ckpt["failed_saves"]))
    return outcome._replace(compared={
        **train.compared(seen["check"], window["loss_first10"],
                         window["loss_last10"],
                         outcome.failed - saves_failed),
        "saves_failed": [saves_failed, 0],
        "newest_not_whole": [int(not ckpt["newest_ok"]), 0],
        "final_state_not_read_back": [int(not ckpt["read_back"]), 0]})
