"""What run.py hands a runner, and what the runner hands back."""

from __future__ import annotations

from typing import NamedTuple


class Job(NamedTuple):
    """What a runner gets."""

    t_process_start: float
    name: str
    workload: dict
    config: dict
    family: object       # the module benchmark/families/<family>.py
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    dump_dir: "str | None"


class Outcome(NamedTuple):
    """What a runner gives back."""

    correct: bool
    attempted: int
    failed: int
    end_to_end: dict     # metric name -> number, the runner's own
    measured: object     # handed to every per-layer reader
    device: dict
    breakdown: "dict | None"
    # name -> [number compared, its limit]: all that `correct` rests on
    compared: "dict | None" = None


def init_seed(job: Job) -> int:
    """The seed of the model's weights. A cell whose workload file carries
    `init_seed` has its weights from the file, so that every run of the cell
    is a run of one job whatever `--seed` draws (an expert cell's step
    follows its router's choices: PERF.md, section 4); any other cell has
    them from `--seed`."""
    return int(job.workload.get("init_seed", job.seed))


def data_seed(job: Job) -> int:
    """The seed of the cell's batches, the window's stream (this seed) and
    the check batch (this seed + 1): `--seed`, unless the workload file pins
    it under `data.seed`. Such a cell is a replay of one job and `--seed`
    draws nothing in it: the check is the step's first call, a real update
    of the state the window goes on training, so another check batch alone
    is another trajectory of the routers (PERF.md, section 2)."""
    return int(job.workload["data"].get("seed", job.seed))
