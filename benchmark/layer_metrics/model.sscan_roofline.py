"""Share of its roofline the selective scan reaches: the least time the chip
could take for the step's scans, whatever implements them (per Mamba layer
the LARGER of its HBM floor, the bytes of u, dt, y, B and C once each way
over the HBM peak, and its vector-unit floor, `3 x 7 x t x c x N` float32
operations over `flops_per_s / 32`: benchmark/lib/sambay_counts.py) over
`model.sscan_ms`. Recompute under remat is time and not work, so it lowers
the share. None where there is nothing to read."""

from benchmark.lib.sambay_scopes import sscan_roofline_pct


def read(m):
    return sscan_roofline_pct(m)
