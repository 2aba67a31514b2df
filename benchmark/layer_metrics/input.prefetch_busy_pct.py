from benchmark.lib.loop_spans import READERS

read = READERS["input.prefetch_busy_pct"]
