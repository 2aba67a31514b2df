"""Device milliseconds per step in the R exits of a stack passed R times a
step (scope `head_loss`, the gate's `exit_gate` with it: the final norm after
every pass, the head, the CE, the gate, the weighting; forward, the logits
made again in the backward, and backward). Chip 0
(benchmark/lib/loop_scopes.py). None where the program has no such scopes."""

from benchmark.lib.loop_scopes import parts_ms_per_step


def read(m):
    return parts_ms_per_step(m, ("head_loss", "exit_gate"))
