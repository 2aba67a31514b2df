"""Programs this process had to compile (persistent-cache misses, the
program's `compile_cache_stats()`) by the end of set-up. 0 on a warm cache."""


def read(m):
    return m.cache_setup["misses"]
