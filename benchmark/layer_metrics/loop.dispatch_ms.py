"""Mean `step` span over the window: what one dispatch holds the loop's thread."""

from benchmark.lib.train_spans import READERS

read = READERS["loop.dispatch_ms"]
