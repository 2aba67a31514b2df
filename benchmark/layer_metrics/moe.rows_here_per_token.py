"""(token, choice) pairs whose expert is held here, per token and expert
layer: the rows the grouped products NEED (the step's `rows_here` counter
over the window). top_k x held / routed under uniform routing (0.5 at a
share of a sixteenth and top-8); what the deployment's other chips compute
is the rest of top_k. Since PR 47 the products compute exactly these rows:
each held expert's group ends at its own last row and the rest of a live
chunk has no group, so the experts' time follows this counter. The chunk
(`parallel/moe.CHUNK_SHARES`: six times the mean share) is still what the
movers, the route's passes and the `silu * up` pass between the products
walk, and a second chunk going live still adds its own."""


def read(m):
    return getattr(m, "rows_here_per_token", None)
