from benchmark.lib.program_trace import READERS

read = READERS["model.fwd_ms"]
