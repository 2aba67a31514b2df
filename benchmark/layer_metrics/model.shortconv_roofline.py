"""Share of its roofline the gated short-convolution mixers reach: the least
time the chip could take for the step's convolution layers (per layer the
larger of the two projections' FLOPs, forward and backward, over the bf16
peak and the bytes of x, `[B | C | u]`, c and y once each way over the HBM
peak; benchmark/lib/conv_moe_counts.shortconv_cost) over
`model.shortconv_ms`. Recompute under remat is time and not work, so it
lowers the share; so do the element-wise passes between the two products
(the gates and the taps' float32 sum), which the count holds at one pass
over their operands."""

from benchmark.lib.conv_scopes import own_scope_ms_per_step
from benchmark.lib.flops import roofline_seconds


def read(m):
    took_ms = own_scope_ms_per_step(m, "shortconv")
    if not took_ms or m.peak is None:
        return None
    import jax.numpy as jnp
    from benchmark.lib.conv_moe_counts import shortconv_cost
    w, s = m.workload, m.sizes
    batch = int(w["batch"]) // m.mesh.get("dp", 1)
    least, _ = roofline_seconds(
        shortconv_cost(batch, int(w["seqlen"]), s,
                       jnp.dtype(w["dtype"]).itemsize),
        m.peak.flops_per_s, m.peak.hbm_bytes_per_s)
    return 100.0 * s.conv_layers * least / (took_ms / 1e3)
