from benchmark.lib.program_trace import READERS

read = READERS["model.head_loss_ms"]
