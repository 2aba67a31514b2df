"""Share of its roofline the flash kernel reaches at latent attention's two
widths: the least time the chip could take for the traced calls (per call
the larger of causal FLOPs, QK^T at q/k's 192 and PV at v's 128, over the
bf16 peak and the operands' bytes over the HBM peak;
benchmark/lib/mla_moe_counts.flash_call_cost) over the time they took. The
backward of a multi-block grid is one call where the head stays resident
(since PR 40: cells 5 and 7) and two (dq; dk and dv) where it does not (cell
6), which together do the backward's work; a cell that mixed the two would
be misread. Chip 0."""

from benchmark.lib.flops import roofline_seconds
from benchmark.lib.kernels import FLASH_BACKWARD, FLASH_FORWARD
from benchmark.lib.mla_moe_counts import flash_call_cost


def read(m):
    if not m.devices or m.peak is None:
        return None
    import jax.numpy as jnp
    dev, w, s = m.devices[0], m.workload, m.sizes
    rows = (int(w["batch"]) // m.mesh.get("dp", 1)) \
        * (s.n_head // m.mesh.get("tp", 1))
    itemsize = jnp.dtype(w["dtype"]).itemsize
    least = took = 0.0
    for pattern, backward in ((FLASH_FORWARD, False), (FLASH_BACKWARD, True)):
        calls = dev.select(pattern)
        seconds, _ = roofline_seconds(
            flash_call_cost(rows, int(w["seqlen"]), s.qk_head_dim,
                            s.v_head_dim, itemsize, backward),
            m.peak.flops_per_s, m.peak.hbm_bytes_per_s)
        # a split backward is two kernels for one backward's work
        names = {c.name.split(".")[0] for c in calls}
        per_backward = len(names) if backward and len(names) > 1 else 1
        least += seconds * len(calls) / per_backward
        took += dev.time_ns(calls) / 1e9
    return 100.0 * least / took if took else None
