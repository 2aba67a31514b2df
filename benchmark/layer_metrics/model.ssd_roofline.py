"""Share of its roofline the chunked state-space recurrence reaches: the
least time the chip could take for the step's recurrences, whatever
implements them (per Mamba layer the larger of the recurrence's FLOPs at
chunk 128, forward and backward, over the bf16 peak and the bytes of x, B,
C, dt, y and the chunk states once each way over the HBM peak;
benchmark/lib/ssm_moe_counts.ssd_cost, which the runner hands over as
`measured.ssd_cost`) over `model.ssd_ms`. Recompute under remat is time and
not work, so it lowers the share; so do the float32 passes that make the
decays (128 x 128 a head and chunk, exponentials and a select) and products
of 128 x 128 x 64 a head. None where there is nothing to read."""

from benchmark.lib.flops import roofline_seconds
from benchmark.lib.ssm_scopes import ssd_ms_per_step


def read(m):
    took_ms = ssd_ms_per_step(m)
    cost = getattr(m, "ssd_cost", None)
    if not took_ms or cost is None or m.peak is None:
        return None
    least, _ = roofline_seconds(cost, m.peak.flops_per_s,
                                m.peak.hbm_bytes_per_s)
    return 100.0 * m.sizes.n_mamba_layer * least / (took_ms / 1e3)
