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
