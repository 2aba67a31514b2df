"""Mean `log` span over the window: the interval's host work, the device idle
under it."""

from benchmark.lib.train_spans import READERS

read = READERS["loop.log_ms"]
