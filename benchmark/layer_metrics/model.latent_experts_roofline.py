"""Share of its roofline the held LATENT experts' grouped products reach:
the least time the chip could take for the rows the step's counter says
were computed (per expert layer the larger of 12 l f FLOPs a row, l the
latent's width, over the bf16 peak and three passes over the held experts'
TWO matrices of l x f and the l-wide rows over the HBM peak;
benchmark/lib/ssm_moe_counts.latent_expert_products_cost) over
`model.moe_experts_ms`. `model.moe_experts_roofline` reckons 18 d f and
three matrices an expert, so a cell of this family is not in its list.
Recompute under remat is time and not work, so it lowers the share; so do
the `relu^2` pass between the two products, which runs over the chunk's rows
and not the held ones, and groups of a couple of hundred rows, too small to
fill the MXU (a held expert sees 176 rows a step in the cell against 2816
in the deployment: the cell's cut). None where the runner hands no such
cost (another family's runner, a program without the family)."""

from benchmark.lib.flops import roofline_seconds
from benchmark.lib.scopes import scope_ms_per_step


def read(m):
    costs = getattr(m, "latent_expert_costs", None)
    if costs is None or m.peak is None:
        return None
    took_ms = scope_ms_per_step(m, "moe_experts")
    if not took_ms:
        return None
    least = sum(roofline_seconds(cost, m.peak.flops_per_s,
                                 m.peak.hbm_bytes_per_s)[0] for cost in costs)
    return 100.0 * least / (took_ms / 1e3)
