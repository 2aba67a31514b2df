"""Share of its roofline the index score reaches where it is made for the
choice: the least time the chip could take for the kernel `dsa_select`'s
traced calls (the score of every causal pair once, 2 x 16 heads x 64 FLOPs a
pair over the bf16 peak; the operands' bytes over the HBM peak;
benchmark/lib/dsa_moe_counts.dsa_select_cost) over the time they took. The
threshold's 32 passes of compare-and-count are no matmul's FLOPs and count
as time. Chip 0. Nothing where the capture holds no such kernel."""

from benchmark.lib import dsa_scopes
from benchmark.lib.dsa_moe_counts import dsa_select_cost


def read(m):
    if not hasattr(m.sizes, "index_topk"):
        return None
    shape = dsa_scopes.call_shape(m)
    return dsa_scopes.kernel_roofline_pct(m, [
        (dsa_scopes.DSA_SELECT, dsa_select_cost(*shape[:2], m.sizes, shape[2]),
         1)])
