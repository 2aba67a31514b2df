"""Device milliseconds per step in the cross-attention layers OUTSIDE their
flash calls (scope `cross_attn`: the queries alone, the layout of ANOTHER
layer's keys and values for the call, lambda, the difference, the norm,
`W_o`; one layer in the cell). Forward, recompute and backward together;
chip 0 (benchmark/lib/sambay_scopes.py)."""

from benchmark.lib.sambay_scopes import scope_ms_per_step


def read(m):
    return scope_ms_per_step(m, "cross_attn")
