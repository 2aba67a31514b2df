"""Share of its roofline the chunked gated delta rule reaches: the least
time the chip could take for the step's rules (per linear layer the larger
of the rule's FLOPs at the program's chunk, forward and backward, over the
bf16 peak and the bytes of q, k, v, o, g, beta and the chunk states once
each way over the HBM peak; benchmark/lib/gdn_moe_counts.rule_cost) over
`model.gdn_rule_ms`. Recompute under remat is time and not work, so it
lowers the share; so do products of 64 x 128 x 128 a head, far from the
MXU's shapes, and a scan of 128 dependent steps a sequence."""

from benchmark.lib.flops import roofline_seconds
from benchmark.lib.scopes import scope_ms_per_step


def read(m):
    if "gdn_rule" not in (getattr(m, "scopes", None) or {}):
        return None
    took_ms = scope_ms_per_step(m, "gdn_rule")
    if not took_ms or m.peak is None:
        return None
    import jax.numpy as jnp
    from benchmark.lib.gdn_moe_counts import rule_cost
    w, s = m.workload, m.sizes
    batch = int(w["batch"]) // m.mesh.get("dp", 1)
    least, _ = roofline_seconds(
        rule_cost(batch, int(w["seqlen"]), s, jnp.dtype(w["dtype"]).itemsize),
        m.peak.flops_per_s, m.peak.hbm_bytes_per_s)
    return 100.0 * s.linear_layers * least / (took_ms / 1e3)
