"""Device milliseconds per step in the Mamba-2 mixers' two dense projections
(scopes `mamba/in_proj`, `[z | xBC | dt] = u W_in`, 2048 -> 8512, and
`mamba/out_proj`, 4096 -> 2048; nine Mamba layers in the cell): the matrix
unit's part of the mixer. Forward, recompute and backward together; chip 0
(benchmark/lib/ssm_dense_scopes.py over
benchmark/lib/ssm_scopes.mamba_parts_ns). None where the runner's split has
no such scope (another family's runner, a program without the family, an
untraced run)."""

from benchmark.lib.ssm_dense_scopes import mamba_part_ms_per_step


def read(m):
    return mamba_part_ms_per_step(m, "in_proj", "out_proj")
