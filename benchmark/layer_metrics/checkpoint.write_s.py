from benchmark.lib.program_trace import READERS

read = READERS["checkpoint.write_s"]
