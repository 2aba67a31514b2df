"""Share of its roofline the flash kernel reaches: the least time the chip
could take for the traced calls (per call the larger of causal FLOPs over
the bf16 peak and bytes over the HBM peak, benchmark/lib/flops.py) over the
time they took. At t=1024 and head_dim 64 the compute bound binds (about
256 FLOPs a byte against the chip's 240). Chip 0."""

from benchmark.lib.flops import roofline_seconds
from benchmark.lib.kernels import FLASH_BACKWARD, FLASH_FORWARD, flash_cost


def read(m):
    if not m.devices or m.peak is None:
        return None
    dev = m.devices[0]
    least = took = 0.0
    for pattern, backward in ((FLASH_FORWARD, False), (FLASH_BACKWARD, True)):
        calls = dev.select(pattern)
        seconds, _ = roofline_seconds(flash_cost(m, backward),
                                      m.peak.flops_per_s,
                                      m.peak.hbm_bytes_per_s)
        least += seconds * len(calls)
        took += dev.time_ns(calls) / 1e9
    return 100.0 * least / took if took else None
