"""Device milliseconds per step inside the passes of the layer pattern (scope
`loop_pass`, the layers' `dense_ffn` with it) outside the flash kernels: R x
L layer applications, forward, recompute and backward. Chip 0
(benchmark/lib/loop_scopes.py). None where the program has no such scope."""

from benchmark.lib.loop_scopes import parts_ms_per_step


def read(m):
    return parts_ms_per_step(m, ("loop_pass", "dense_ffn"))
