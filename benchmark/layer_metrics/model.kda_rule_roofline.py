"""Share of its roofline the chunked delta rule with a decay a channel
reaches: the least time the chip could take for the step's rules, whatever
implements them (per delta layer the larger of the rule's FLOPs at chunk 64,
forward and backward, over the bf16 peak and the bytes of q, k, v, o, the
float32 decay a channel, beta and the chunk states once each way over the HBM
peak; benchmark/lib/kda_mla_moe_counts.rule_cost, which the runner hands
over as `measured.kda_rule_cost`) over `model.kda_rule_ms`. Recompute under
remat is time and not work, so it lowers the share; so do the sub-blocks'
products against four reference rows, the float32 passes that make the decay
factors, products of 64 x 128 x 128 a head and a scan of 64 dependent steps
a sequence. None where there is nothing to read."""

from benchmark.lib.conv_scopes import own_scope_ms_per_step
from benchmark.lib.flops import roofline_seconds


def read(m):
    took_ms = own_scope_ms_per_step(m, "kda_rule")
    cost = getattr(m, "kda_rule_cost", None)
    if not took_ms or cost is None or m.peak is None:
        return None
    least, _ = roofline_seconds(cost, m.peak.flops_per_s,
                                m.peak.hbm_bytes_per_s)
    return 100.0 * m.sizes.kda_layers * least / (took_ms / 1e3)
