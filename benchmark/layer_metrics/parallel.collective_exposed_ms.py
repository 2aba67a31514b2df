"""The part of `parallel.collective_ms` during which no other op runs on
that chip: what overlap would have to hide. Chip 0."""


def read(m):
    if not m.devices:
        return None
    dev = m.devices[0]
    return dev.exposed_collective_ns() / dev.steps / 1e6
