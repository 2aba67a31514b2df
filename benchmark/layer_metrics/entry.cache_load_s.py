"""Seconds reading executables back from the persistent cache by the end of
set-up: the backend-compile spans that turned out to be hits, key and
deserialisation included (the program's `compile_cache_stats()`). `None`
from a program that does not keep the counter."""


def read(m):
    return m.cache_setup.get("cache_load_s")
