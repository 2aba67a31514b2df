"""Device milliseconds per step in the exit gate (scope `exit_gate`, inside
`head_loss`: the gate's product over the width on the R states, `p`, the
entropy; both ways). Chip 0 (benchmark/lib/loop_scopes.py). None where the
program has no such scope."""

from benchmark.lib.loop_scopes import parts_ms_per_step


def read(m):
    return parts_ms_per_step(m, ("exit_gate",))
