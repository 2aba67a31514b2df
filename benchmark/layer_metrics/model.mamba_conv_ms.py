"""Device milliseconds per step in the Mamba-2 mixers' causal depthwise
convolution (scope `mamba/conv`: four taps over the 4352 `[x | B | C]`
channels summed in float32, the bias and the SiLU; nine Mamba layers in the
cell): the vector unit and the HBM, no matmul. Forward, recompute and
backward together; chip 0 (benchmark/lib/ssm_dense_scopes.py over
benchmark/lib/ssm_scopes.mamba_parts_ns). None where the runner's split has
no such scope (another family's runner, a program without the family, an
untraced run)."""

from benchmark.lib.ssm_dense_scopes import mamba_part_ms_per_step


def read(m):
    return mamba_part_ms_per_step(m, "conv")
