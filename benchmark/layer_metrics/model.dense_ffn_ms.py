"""Device milliseconds per step in the leading dense layers' SwiGLU (scope
`dense_ffn`: gate, up and down at the published `intermediate_size`; one
layer in the cell). Forward, recompute and backward together; chip 0
(benchmark/lib/conv_scopes.py)."""

from benchmark.lib.conv_scopes import own_scope_ms_per_step


def read(m):
    return own_scope_ms_per_step(m, "dense_ffn")
