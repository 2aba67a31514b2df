"""Device milliseconds per step in the expert layers' routing (scope `moe_route`): router, top-k, the sort of the (token, choice) pairs, the gather of the held experts' rows and the weighted scatter-add back. Forward,
recompute and backward together; chip 0 (benchmark/lib/scopes.py)."""

from benchmark.lib.scopes import scope_ms_per_step


def read(m):
    return scope_ms_per_step(m, "moe_route")
