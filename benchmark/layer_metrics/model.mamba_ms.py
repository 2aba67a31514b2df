"""Device milliseconds per step in the Mamba-2 mixers whole (scope `mamba`:
the input projection `[z | xBC | dt]`, the 4-tap convolution with its bias
and SiLU, the chunked recurrence, the gate and the grouped norm, the out
projection; five Mamba layers in the cell). Forward, recompute and backward
together; chip 0 (benchmark/lib/ssm_scopes.py). None where the runner's
split has no such scope (another family's runner, a program without the
family)."""

from benchmark.lib.conv_scopes import own_scope_ms_per_step


def read(m):
    return own_scope_ms_per_step(m, "mamba")
