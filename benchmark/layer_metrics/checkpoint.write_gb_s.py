from benchmark.lib.loop_spans import READERS

read = READERS["checkpoint.write_gb_s"]
