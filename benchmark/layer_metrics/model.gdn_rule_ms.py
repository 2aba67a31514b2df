"""Device milliseconds per step in the chunked gated delta rule alone (scope
`gdn_rule`: the chunks' products and triangular solves, the scan over chunks
that carries the state; three linear layers). Forward, recompute and
backward together; chip 0 (benchmark/lib/hybrid_scopes.py). Since PR 36 the
scope holds the rule's two Pallas kernels and the chunk-parallel text XLA
keeps around them."""

from benchmark.lib.scopes import scope_ms_per_step


def read(m):
    if "gdn_rule" not in (getattr(m, "scopes", None) or {}):
        return None
    return scope_ms_per_step(m, "gdn_rule")
