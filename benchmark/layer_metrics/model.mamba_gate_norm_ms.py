"""Device milliseconds per step in the Mamba-2 mixers' gate and grouped norm
(scope `mamba/gate_norm`: `w * RMSNorm(y * silu(z))` over the one group's
4096 channels in float32; nine Mamba layers in the cell): the vector unit
and the HBM, no matmul. Forward, recompute and backward together; chip 0
(benchmark/lib/ssm_dense_scopes.py over
benchmark/lib/ssm_scopes.mamba_parts_ns). None where the runner's split has
no such scope (another family's runner, a program without the family, an
untraced run)."""

from benchmark.lib.ssm_dense_scopes import mamba_part_ms_per_step


def read(m):
    return mamba_part_ms_per_step(m, "gate_norm")
