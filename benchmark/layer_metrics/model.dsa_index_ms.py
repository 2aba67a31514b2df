"""Device milliseconds per step in the lightning indexer outside the kernels
(scope `dsa_index`: its three projections from the layer's normed input, the
index key's LayerNorm, RoPE over all of the index head and the head weights'
scale; six layers in the cell). The score itself is made inside the
kernels, a tile at a time (`model.dsa_select_ms`, `model.dsa_index_roofline`).
Forward, recompute and backward together; chip 0
(benchmark/lib/dsa_scopes.py). Nothing where the program has no such scope."""

from benchmark.lib.dsa_scopes import own_scope_ms_per_step


def read(m):
    return own_scope_ms_per_step(m, "dsa_index")
