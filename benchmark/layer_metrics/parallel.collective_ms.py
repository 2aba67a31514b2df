"""Device milliseconds per step in collective ops (all-reduce, all-gather,
reduce-scatter, collective-permute, all-to-all): the union of their
intervals, synchronous ops and asynchronous start-to-done spans alike.
Chip 0."""


def read(m):
    if not m.devices:
        return None
    dev = m.devices[0]
    return dev.collective_ns() / dev.steps / 1e6
