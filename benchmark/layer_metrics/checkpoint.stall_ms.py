from benchmark.lib.program_trace import READERS

read = READERS["checkpoint.stall_ms"]
