"""Active-parameter FLOP utilisation of the window: the benchmark's own
FLOPs per token for a model with routed experts (6 x the parameters a
token's matmuls touch here, the routed experts at the rows the step's
counter says were computed, plus attention at the full T^2 with 192 for
QK^T and 128 for PV; recompute not counted;
benchmark/lib/mla_moe_counts.train_flops_per_token) x tokens per second /
(chips x the published bf16 peak)."""


def read(m):
    flops = getattr(m, "active_flops_per_token", None)
    if m.peak is None or flops is None:
        return None
    return 100.0 * flops * m.tokens_per_s / (m.chips * m.peak.flops_per_s)
