"""Device milliseconds per step in the multi-token-prediction module's own ops (scope `mtp`: its two norms, the 4096 -> 2048 projection, its final norm, head and CE); its layer's attention and experts are in `model.mla_ms` and the `moe` metrics (innermost scope wins). Forward,
recompute and backward together; chip 0 (benchmark/lib/scopes.py)."""

from benchmark.lib.scopes import scope_ms_per_step


def read(m):
    return scope_ms_per_step(m, "mtp")
