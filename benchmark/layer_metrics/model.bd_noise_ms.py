"""Device milliseconds per step under the scope `bd_noise`: the block
diffusion step's draw (one `jax.random` call a batch: a level a sequence,
a uniform a position), the select of the mask token, the concatenation of
the rows `[noised ; clean]` and of their positions, the loss weights `m /
p` and the counts. Chip 0 (benchmark/lib/bd_scopes.py)."""

from benchmark.lib.bd_scopes import own_scope_ms_per_step


def read(m):
    return own_scope_ms_per_step(m, "bd_noise")
