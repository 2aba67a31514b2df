"""Share of its roofline the indexer's loss walk reaches: the least time the
chip could take for the kernel `dsa_index_loss`'s traced calls (the score of
every causal pair and its two transposes, 6 x 16 heads x 64 FLOPs a pair,
and the heads' probabilities over the kept pairs, 2 x head_dim a pair and
head, over the bf16 peak; the operands' bytes over the HBM peak;
benchmark/lib/dsa_moe_counts.dsa_index_loss_cost) over the time they took.
The walk computes the heads' scores over the whole triangle to sum the
chosen pairs' probabilities: time, not work. Chip 0. Nothing where the
capture holds no such kernel."""

from benchmark.lib import dsa_scopes
from benchmark.lib.dsa_moe_counts import dsa_index_loss_cost


def read(m):
    if not hasattr(m.sizes, "index_topk"):
        return None
    shape = dsa_scopes.call_shape(m)
    return dsa_scopes.kernel_roofline_pct(m, [
        (dsa_scopes.DSA_INDEX_LOSS,
         dsa_index_loss_cost(*shape[:2], m.sizes, shape[2]), 1)])
