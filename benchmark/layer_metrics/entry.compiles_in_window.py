"""Programs built or loaded inside the measured window (cache hits and
misses both: either way jit met a shape it had not warmed up). Should be 0."""


def read(m):
    after, before = m.cache_window, m.cache_setup
    return (after["hits"] + after["misses"]
            - before["hits"] - before["misses"])
