from benchmark.lib.program_trace import READERS

read = READERS["model.bwd_ms"]
