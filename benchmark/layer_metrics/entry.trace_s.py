"""Seconds of Python tracing to jaxprs by the end of set-up, outermost
functions only, every program the process built (the program's
`compile_cache_stats()`, which listens to JAX's own trace events). No cache
removes it. `None` from a program that does not keep the counter."""


def read(m):
    return m.cache_setup.get("trace_s")
