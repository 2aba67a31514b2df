"""`get_dataloader`: the token JSON read and the packed loader's framing."""

from benchmark.lib.train_spans import READERS

read = READERS["setup.data_s"]
