"""Device milliseconds per step in the Mamba-1 mixers whole (scope `mamba1`:
the input projection `[u | z]`, the 4-tap convolution with its bias and
SiLU, the projection to `[dt_r | B | C]`, dt's projection and softplus, the
selective scan, the gate and the out projection; two Mamba layers in the
cell). Forward, recompute and backward together; chip 0
(benchmark/lib/sambay_scopes.py). None where the runner's split has no such
scope (another family's runner, a program without the family)."""

from benchmark.lib.sambay_scopes import scope_ms_per_step


def read(m):
    return scope_ms_per_step(m, "mamba1")
