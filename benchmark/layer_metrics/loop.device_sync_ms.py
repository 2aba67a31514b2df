"""Mean `device_sync` span over the window: how long the host waited for the
device at a log interval. Near 0 means the host sets the pace."""

from benchmark.lib.train_spans import READERS

read = READERS["loop.device_sync_ms"]
