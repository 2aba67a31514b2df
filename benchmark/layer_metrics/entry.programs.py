"""Functions that reached the backend, compiled or loaded, by the end of
set-up (the program's `compile_cache_stats()`): the eager ops of the
weights' init and the check among them, each a dispatch through trace, lower
and cache. `None` from a program that does not keep the counter."""


def read(m):
    return m.cache_setup.get("programs")
