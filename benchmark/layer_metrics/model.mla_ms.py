"""Device milliseconds per step in the latent attention's projections, latent norms, RoPE and output projection (scope `mla`, all six attention layers, the multi-token-prediction module's included); the flash calls are `kernels.flash_ms`'s, not in here. Forward,
recompute and backward together; chip 0 (benchmark/lib/scopes.py)."""

from benchmark.lib.scopes import scope_ms_per_step


def read(m):
    return scope_ms_per_step(m, "mla")
