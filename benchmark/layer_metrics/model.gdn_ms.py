"""Device milliseconds per step in the Gated DeltaNet mixers outside the rule (scope `gdn`: the two projections, the causal convolution, the gates, the gated output norm, `W_out`; three linear layers). Forward, both recomputes
and backward together; chip 0 (benchmark/lib/hybrid_scopes.py)."""

from benchmark.lib.scopes import scope_ms_per_step


def read(m):
    if "gdn" not in (getattr(m, "scopes", None) or {}):
        return None
    return scope_ms_per_step(m, "gdn")
