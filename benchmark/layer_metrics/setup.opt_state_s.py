"""The Adam state made and placed. Host seconds, as `setup.init_s`."""

from benchmark.lib.train_spans import READERS

read = READERS["setup.opt_state_s"]
