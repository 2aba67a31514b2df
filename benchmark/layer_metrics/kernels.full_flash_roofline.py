"""Share of its roofline the FULL-attention layers' flash calls reach in a
model that also has window layers: as `kernels.window_flash_roofline`, over
the calls whose name carries no `_window`, at the causal triangle's `T (T +
1) / 2` live entries a head and sequence
(benchmark/lib/swa_moe_counts.flash_call_cost). Chip 0."""

from benchmark.lib.swa_scopes import flash_roofline_pct


def read(m):
    return flash_roofline_pct(m, window=False)
