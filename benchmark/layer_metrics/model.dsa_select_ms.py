"""Device milliseconds per step choosing the keys (the kernel `dsa_select`:
the index score of every causal pair, a block of query rows against every
earlier key in VMEM, and a row's top-2048 threshold by 32 passes of
compare-and-count; beside it the few XLA ops of the scope `dsa_select`).
Forward and recompute together (the choice has no backward); chip 0
(benchmark/lib/dsa_scopes.py). Nothing where the program has no such
kernel."""

from benchmark.lib.dsa_scopes import own_scope_ms_per_step


def read(m):
    return own_scope_ms_per_step(m, "dsa_select")
