from benchmark.lib.program_trace import READERS

read = READERS["input.data_wait_ms"]
