"""Share of their roofline the differential layers' flash calls reach, the
window layer's and the full and cross layers' together: 40 maps of keys 64
wide and values 128 wide over 20 key heads, at each kind's LIVE entries
(benchmark/lib/sambay_counts.diff_flash_call_cost), over the time the calls
took. Chip 0. None where the runner's sizes are another family's."""

from benchmark.lib.sambay_scopes import diff_flash_roofline_pct


def read(m):
    return diff_flash_roofline_pct(m)
