"""Device milliseconds per step in the indexer's own loss (the kernel
`dsa_index_loss`: the heads' summed attention probabilities a tile, the KL
of the index scores' softmax from them, and its gradient back through the
ReLU into qI, kI and the head weights, in one walk of the triangle; beside
it the XLA ops of the scope `dsa_index_loss`). Forward and recompute
together (the walk makes its gradients with its value); chip 0
(benchmark/lib/dsa_scopes.py). Nothing where the program has no such
kernel."""

from benchmark.lib.dsa_scopes import own_scope_ms_per_step


def read(m):
    return own_scope_ms_per_step(m, "dsa_index_loss")
