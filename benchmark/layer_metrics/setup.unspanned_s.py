"""`setup_s` less every span of the loop's thread before the window opened
(the `setup.*` spans, `compile`, the first intervals' `data_wait`, `h2d`,
`step`, `device_sync`, `log`, the capture's `profile.*`): the process before
`train()` (imports, the token file) and what of `train()` is in no span."""

from benchmark.lib.train_spans import READERS

read = READERS["setup.unspanned_s"]
