"""Share of its roofline the flash kernel reaches under grouped queries (width
256 and 16 heads over 2 in cell 6, 64 and 32 over 8 in cell 7: read from
`sizes`): the least time the chip could take for the traced calls (per call
the larger of causal FLOPs, QK^T and PV at the head's width for every query
head, over the bf16 peak and the operands' bytes, K and V once a key-value
head, over the HBM peak; benchmark/lib/gdn_moe_counts.gqa_flash_call_cost)
over the time they took. The backward of a multi-block grid is one call
where the head stays resident (since PR 40: cells 5 and 7) and two (dq; dk
and dv) where it does not (cell 6), which together do the backward's work; a
cell that mixed the two would be misread. Chip 0."""

from benchmark.lib.flops import roofline_seconds
from benchmark.lib.kernels import FLASH_BACKWARD, FLASH_FORWARD


def read(m):
    s = m.sizes
    if not m.devices or m.peak is None or not hasattr(s, "n_kv_head"):
        return None
    import jax.numpy as jnp
    from benchmark.lib.gdn_moe_counts import gqa_flash_call_cost
    dev, w = m.devices[0], m.workload
    batch = int(w["batch"]) // m.mesh.get("dp", 1)
    itemsize = jnp.dtype(w["dtype"]).itemsize
    least = took = 0.0
    for pattern, backward in ((FLASH_FORWARD, False), (FLASH_BACKWARD, True)):
        calls = dev.select(pattern)
        seconds, _ = roofline_seconds(
            gqa_flash_call_cost(batch, int(w["seqlen"]), s, itemsize,
                                backward),
            m.peak.flops_per_s, m.peak.hbm_bytes_per_s)
        # a split backward is two kernels for one backward's work
        names = {c.name.split(".")[0] for c in calls}
        per_backward = len(names) if backward and len(names) > 1 else 1
        least += seconds * len(calls) / per_backward
        took += dev.time_ns(calls) / 1e9
    return 100.0 * least / took if took else None
