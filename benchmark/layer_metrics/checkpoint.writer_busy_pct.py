from benchmark.lib.loop_spans import READERS

read = READERS["checkpoint.writer_busy_pct"]
