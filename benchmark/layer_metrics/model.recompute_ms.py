from benchmark.lib.program_trace import READERS

read = READERS["model.recompute_ms"]
