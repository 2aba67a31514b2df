from benchmark.lib.program_trace import READERS

read = READERS["train_step.optimizer_ms"]
