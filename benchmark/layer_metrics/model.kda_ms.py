"""Device milliseconds per step in the Kimi Delta Attention mixers outside
the rule (scope `kda`: the projections of q, k, v, the decay and the output
gate, the three convolutions, the bounded gate's float32 passes, beta, the
gated per-head norm and `W_o`; five delta layers in the cell). Forward,
recompute and backward together; chip 0 (benchmark/lib/kda_scopes.py). None
where the runner's split has no such scope (another family's runner, a
program without the family)."""

from benchmark.lib.conv_scopes import own_scope_ms_per_step


def read(m):
    return own_scope_ms_per_step(m, "kda")
