"""Device milliseconds per step in the differential attention layers OUTSIDE
their flash calls (scope `diff_attn`: q, k, v with their biases, lambda,
the heads' difference, their RMSNorm times `1 - lambda_init`, `W_o`; one
window layer and the full layer in the cell). Forward, recompute and
backward together; chip 0 (benchmark/lib/sambay_scopes.py)."""

from benchmark.lib.sambay_scopes import scope_ms_per_step


def read(m):
    return scope_ms_per_step(m, "diff_attn")
