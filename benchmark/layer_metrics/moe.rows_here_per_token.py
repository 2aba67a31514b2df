"""(token, choice) pairs whose expert is held here, per token and expert
layer: the rows the grouped products NEED (the step's `rows_here` counter
over the window). top_k x held / routed = 0.5 under uniform routing at this
cell's share; what the deployment's other chips compute is the rest of
top_k. The products compute more: a live chunk of the sorted pairs is
computed whole (six times the mean share, 3 rows a token and layer:
`parallel/moe.CHUNK_SHARES`), so today this counter moves the step only
where a second chunk goes live; it is what a kernel that follows the rows
will be sized by."""


def read(m):
    return getattr(m, "rows_here_per_token", None)
