"""Share of its roofline the flash kernels reach under the block-diffusion
mask: the least time the chip could take for the traced calls, from what the
MASK needs whatever implements it (per call `b x heads x L (L + B)` live
score entries, 4 x head_dim FLOPs an entry forward and 10 backward, over the
bf16 peak; the operands' bytes over 2L rows, K and V once a key-value head,
over the HBM peak; benchmark/lib/bd_moe_counts.bd_flash_call_cost) over the
time they took. Entries a tile's plan computes dead are time and not work
(`bd.flash_computed_over_live`), and so is the recomputed forward's second
call, which is counted as a call. A split backward (dq; dk and dv) is two
kernels for one backward's work. Chip 0."""

from benchmark.lib.flops import roofline_seconds
from benchmark.lib.kernels import FLASH_BACKWARD, FLASH_FORWARD


def read(m):
    s = m.sizes
    if not m.devices or m.peak is None or not hasattr(s, "block_length"):
        return None
    import jax.numpy as jnp
    from benchmark.lib.bd_moe_counts import bd_flash_call_cost
    dev, w = m.devices[0], m.workload
    batch = int(w["batch"]) // m.mesh.get("dp", 1)
    itemsize = jnp.dtype(w["dtype"]).itemsize
    least = took = 0.0
    for pattern, backward in ((FLASH_FORWARD, False), (FLASH_BACKWARD, True)):
        calls = dev.select(pattern)
        seconds, _ = roofline_seconds(
            bd_flash_call_cost(batch, int(w["seqlen"]), s, itemsize,
                               backward),
            m.peak.flops_per_s, m.peak.hbm_bytes_per_s)
        # a split backward is two kernels for one backward's work
        names = {c.name.split(".")[0] for c in calls}
        per_backward = len(names) if backward and len(names) > 1 else 1
        least += seconds * len(calls) / per_backward
        took += dev.time_ns(calls) / 1e9
    return 100.0 * least / took if took else None
