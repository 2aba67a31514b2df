"""Device milliseconds per step in the flash attention forward and backward
calls (forward runs twice a layer under full remat). Chip 0."""

from benchmark.lib.kernels import FLASH


def read(m):
    if not m.devices:
        return None
    dev = m.devices[0]
    calls = dev.select(FLASH)
    return dev.time_ns(calls) / dev.steps / 1e6 if calls else None
