"""Device milliseconds per step in the gated memory units (scope `gmu`: `d
x 5120`, the gate on the memory ONE lower layer left, `5120 x d`; one layer
in the cell). Forward, recompute and backward together; chip 0
(benchmark/lib/sambay_scopes.py)."""

from benchmark.lib.sambay_scopes import scope_ms_per_step


def read(m):
    return scope_ms_per_step(m, "gmu")
