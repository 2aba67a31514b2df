"""Device milliseconds per step in the flash attention calls of the
sliding-window layers alone, forward (twice a layer under full remat) and
backward: the calls whose kernel name carries `_window`
(benchmark/lib/swa_scopes.py). Under a causal mask the same layers would
walk the whole triangle; the band is 0.44 of it at 8192 rows and a window
of 2048. Chip 0. Nothing where no call is named so."""

from benchmark.lib.swa_scopes import flash_calls


def read(m):
    if not m.devices:
        return None
    dev = m.devices[0]
    calls = flash_calls(dev, False, True) + flash_calls(dev, True, True)
    return dev.time_ns(calls) / dev.steps / 1e6 if calls else None
