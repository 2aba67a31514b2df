"""Device milliseconds per step in the grouped products of the held experts (scope `moe_experts` and XLA's `ragged-dot-*` kernels), all five expert layers. Forward,
recompute and backward together, the padding rows of a chunk computed whole included (`parallel/moe.CHUNK_SHARES`); chip 0 (benchmark/lib/scopes.py)."""

from benchmark.lib.scopes import scope_ms_per_step


def read(m):
    return scope_ms_per_step(m, "moe_experts")
