"""`build_train_step` and what surrounds it up to the loop: the feeder, the
profiler, the checkpointer."""

from benchmark.lib.train_spans import READERS

read = READERS["setup.build_step_s"]
