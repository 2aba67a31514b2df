"""Seconds of lowering jaxprs to MLIR modules by the end of set-up, every
program the process built (the program's `compile_cache_stats()`). No cache
removes it. `None` from a program that does not keep the counter."""


def read(m):
    return m.cache_setup.get("lower_s")
