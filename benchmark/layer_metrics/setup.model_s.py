"""The configuration, the layout's resolution, `select_remat`, the family's
build."""

from benchmark.lib.train_spans import READERS

read = READERS["setup.model_s"]
