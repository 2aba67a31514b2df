"""Programs built inside a steady interval's `log` span (its `programs`
argument: `compile_cache_stats()["programs"]` after less before), mean over
the window's intervals after its first. Should be 0."""

from benchmark.lib.train_spans import READERS

read = READERS["loop.log_programs"]
