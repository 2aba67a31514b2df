"""Share of its roofline the hyper-connection mixers reach: the least time
the chip could take for what the mixers of one step MUST do, whatever
implements them (the larger of their FLOPs over the bf16 peak and the bytes
they must move over the HBM peak: the streams in and out of every mixer
once forward and once backward at the compute dtype, W and its gradient;
benchmark/lib/mhc_mla_moe_counts.mixers_step_cost: the bytes bind), over
`model.mhc_ms`. Recompute under remat is time and not work, so it lowers
the share; so does every float32 copy of the streams that XLA's unfused
passes carry through HBM: the share reads LOW today, and a fused mixer
kernel reads higher against the same count."""

from benchmark.lib.files import load_module
from benchmark.lib.flops import roofline_seconds


def read(m):
    took_ms = load_module("layer_metrics", "model.mhc_ms").read(m)
    cost = getattr(m, "mhc_cost", None)
    if not took_ms or cost is None or m.peak is None:
        return None
    least, _ = roofline_seconds(cost, m.peak.flops_per_s,
                                m.peak.hbm_bytes_per_s)
    return 100.0 * least / (took_ms / 1e3)
