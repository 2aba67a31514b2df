"""Share of its roofline the held experts' grouped products reach: the least
time the chip could take for the rows the step's counter says were computed
(per expert layer the larger of 18 d f FLOPs a row over the bf16 peak and
three passes over the held experts' weights and the rows over the HBM peak;
benchmark/lib/mla_moe_counts.expert_products_cost) over
`model.moe_experts_ms`. Recompute under remat is time and not work, so it
lowers the share; so do the `silu * up` pass between the two products, which
runs over the chunk's rows and not the held ones (time, not work: since PR
47 the products themselves stop at the last held row), and groups of a few
hundred rows, too small to fill the MXU (a held expert sees 512 rows a step
in cell 5 against 8192 in the deployment: the cell's cut)."""

from benchmark.lib.flops import roofline_seconds
from benchmark.lib.mla_moe_counts import expert_products_cost
from benchmark.lib.scopes import scope_ms_per_step


def read(m):
    took_ms = scope_ms_per_step(m, "moe_experts")
    rows = getattr(m, "rows_here_per_layer", None)
    if not took_ms or rows is None or m.peak is None:
        return None
    import jax.numpy as jnp
    itemsize = jnp.dtype(m.workload["dtype"]).itemsize
    least = sum(roofline_seconds(expert_products_cost(r, m.sizes, itemsize),
                                 m.peak.flops_per_s,
                                 m.peak.hbm_bytes_per_s)[0] for r in rows)
    return 100.0 * least / (took_ms / 1e3)
