"""Device milliseconds per step in the gated short-convolution mixers (scope
`shortconv`: `W_in`, the two gates, the three-tap causal depthwise
convolution, `W_out`; four layers in the cell). Forward, recompute and
backward together; chip 0 (benchmark/lib/conv_scopes.py)."""

from benchmark.lib.conv_scopes import own_scope_ms_per_step


def read(m):
    return own_scope_ms_per_step(m, "shortconv")
