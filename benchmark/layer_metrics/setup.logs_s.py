"""`make_mesh` returning to the observer existing: the metrics writer (its
tensorboardX import, which imports torch), the exporter, the observer.
Booked after the fact."""

from benchmark.lib.train_spans import READERS

read = READERS["setup.logs_s"]
