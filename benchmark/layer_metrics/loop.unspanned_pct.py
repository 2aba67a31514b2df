"""Share of the window under no span of the loop's thread: the token count
over the batch, the eager loss sum, the heartbeat, the caller's `stop`."""

from benchmark.lib.train_spans import READERS

read = READERS["loop.unspanned_pct"]
