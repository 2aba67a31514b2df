from benchmark.lib.program_trace import READERS

read = READERS["input.h2d_ms"]
