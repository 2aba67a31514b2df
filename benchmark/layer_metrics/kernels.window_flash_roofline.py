"""Share of its roofline the sliding-window layers' flash calls reach: the
least time the chip could take for the traced calls from what the BAND
needs whatever implements it (per call `b x heads x W (2 T - W + 1) / 2`
live score entries, 4 x head_dim FLOPs an entry forward and 10 backward,
over the bf16 peak; the operands' bytes, K and V once a key-value head,
over the HBM peak; benchmark/lib/swa_moe_counts.flash_call_cost) over the
time they took. Entries a tile's plan computes dead are time and not work
(`window.flash_computed_over_live`). Chip 0."""

from benchmark.lib.swa_scopes import flash_roofline_pct


def read(m):
    return flash_roofline_pct(m, window=True)
