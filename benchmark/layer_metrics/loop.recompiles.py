"""`recompile` events of `metrics.jsonl` inside the window: the step function
built again after its steady program. Should be 0."""

from benchmark.lib.train_spans import READERS

read = READERS["loop.recompiles"]
