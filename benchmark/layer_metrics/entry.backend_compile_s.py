"""Seconds in XLA's and Mosaic's compile by the end of set-up: persistent-cache
misses and programs without a cache key (the program's
`compile_cache_stats()`). With `entry.cache_misses` 0 it is what the
uncacheable programs cost. `None` from a program without the counter."""


def read(m):
    return m.cache_setup.get("backend_compile_s")
