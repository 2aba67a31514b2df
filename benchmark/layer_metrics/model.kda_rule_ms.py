"""Device milliseconds per step in the chunked delta rule with a decay a
channel alone (scope `kda_rule`: the sub-blocks' decay factors and the two
in-chunk score products, the triangular solves, the scan over chunks that
carries the state; five delta layers). Forward, recompute and backward
together; chip 0 (benchmark/lib/kda_scopes.py). XLA text: no kernel holds
this rule yet. None where the runner's split has no such scope."""

from benchmark.lib.conv_scopes import own_scope_ms_per_step


def read(m):
    return own_scope_ms_per_step(m, "kda_rule")
