from benchmark.lib.loop_spans import READERS

read = READERS["checkpoint.d2h_gb_s"]
