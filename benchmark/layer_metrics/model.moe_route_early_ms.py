"""Device milliseconds per step in the part of the expert layers' routing
that runs from the LAYER'S INPUT, before attention (the inner scope
`moe_route/early` and the step's sorts: the router's product, the top-k,
the weights, the sort of the (token, choice) pairs and the index work, with
their transposes; benchmark/lib/early_scopes.py): what an architecture whose
router reads the layer's input lets a deployment start before the layer's
attention ends, and an exchange hide its counts under. A subset of
`model.moe_route_ms`; forward, recompute and backward together; chip 0.
Nothing where the runner hands no such reading (a program whose routing has
no such scope)."""


def read(m):
    ns = getattr(m, "route_early_ns", None)
    if ns is None or not m.devices:
        return None
    return ns / m.devices[0].steps / 1e6
