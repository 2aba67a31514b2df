"""Share of its roofline the attention over the CHOSEN keys reaches: the
least time the chip could take for the traced calls, from what the
mathematics needs whatever implements it (per call `b x heads x sum_t min(t
+ 1, 2048)` kept pairs, 4 x head_dim FLOPs a pair forward and 10 backward,
over the bf16 peak; the operands' bytes once, over the HBM peak;
benchmark/lib/dsa_moe_counts.dsa_flash_cost) over the time the kernels
`dsa_flash_fwd`, `dsa_flash_bwd_dq` and `dsa_flash_bwd_dkv` took. The walk
computes every pair of the triangle and masks what a row did not choose
(`dsa.flash_computed_over_live`), and makes the index score of every tile
again to know the mask: both are time and not work, and so is the
recomputed forward's second call, which is counted as a call. The split
backward is two kernels for one backward's work. Chip 0. Nothing where the
capture holds no such kernel."""

from benchmark.lib import dsa_scopes
from benchmark.lib.dsa_moe_counts import dsa_flash_cost


def read(m):
    if not hasattr(m.sizes, "index_topk"):
        return None
    shape = dsa_scopes.call_shape(m)
    return dsa_scopes.kernel_roofline_pct(m, [
        (dsa_scopes.DSA_FLASH_FORWARD,
         dsa_flash_cost(*shape[:2], m.sizes, shape[2], False), 1),
        (dsa_scopes.DSA_FLASH_BACKWARD,
         dsa_flash_cost(*shape[:2], m.sizes, shape[2], True), 2)])
