"""The weights: `model.init` and their placement (on a resume the `restore`
spans nest inside). Host seconds: `train()` does not wait for the arrays."""

from benchmark.lib.train_spans import READERS

read = READERS["setup.init_s"]
