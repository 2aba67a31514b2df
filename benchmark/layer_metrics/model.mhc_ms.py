"""Device milliseconds per step in the hyper-connection mixers (scope `mhc`:
the maps' product with W, the sigmoids and the Sinkhorn rounds, the read
`sum_i pre_i X[i]` and the write `H X + post y` of all ten mixers, and the
exit); forward, recompute and backward together; chip 0
(benchmark/lib/mhc_scopes.py; the breakdown has it by part). None where the
runner's `measured` carries no such scope (a program without the family)."""


def read(m):
    parts = getattr(m, "scopes", None)
    if not parts or "mhc" not in parts or not m.devices:
        return None
    return parts["mhc"] / m.devices[0].steps / 1e6
