"""Device milliseconds per step in the gated full attention outside its kernel (scope `gated_attn`: the projections, the q/k norms per head, partial RoPE, the sigmoid output gate, `W_o`; one layer); the flash calls are
`kernels.flash_ms`'s, not in here. Forward, recompute and backward
together; chip 0 (benchmark/lib/hybrid_scopes.py)."""

from benchmark.lib.scopes import scope_ms_per_step


def read(m):
    if "gated_attn" not in (getattr(m, "scopes", None) or {}):
        return None
    return scope_ms_per_step(m, "gated_attn")
