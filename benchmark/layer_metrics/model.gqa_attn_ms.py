"""Device milliseconds per step in the grouped-query attention layers
outside the flash kernels (scope `gqa_attn`: the three projections, the q/k
norms per head, RoPE over the whole head, `W_o`; one layer in the cell).
Forward, recompute and backward together; chip 0
(benchmark/lib/conv_scopes.py). The kernels' time is `kernels.flash_ms`."""

from benchmark.lib.conv_scopes import own_scope_ms_per_step


def read(m):
    return own_scope_ms_per_step(m, "gqa_attn")
