"""`train()`'s first line to `make_mesh` returning: the rendezvous and the
first touch of the devices (what the other runners' `phases_s` calls
`reach_chip`). Booked after the fact: the observer needs the backend."""

from benchmark.lib.train_spans import READERS

read = READERS["setup.backend_s"]
