"""Device milliseconds per step in the selective scan alone (scope
`mamba1/sscan`: the recurrence over `t x 5120 x 16` (channel, state) pairs,
its kernels `sscan_fwd` / `sscan_bwd` or its XLA text, the columns of B and
C the kernels are handed, `D u`). Forward, recompute and backward together;
chip 0 (benchmark/lib/sambay_scopes.py). None where the runner's split has
no such scope."""

from benchmark.lib.sambay_scopes import sscan_ms_per_step


def read(m):
    return sscan_ms_per_step(m)
