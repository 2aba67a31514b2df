"""Model FLOP utilisation of the window: the benchmark's own FLOPs per token
(benchmark/lib/flops.py: 6N plus attention at the full T^2, recompute not
counted) x tokens per second / (chips x the published bf16 peak)."""


def read(m):
    if m.peak is None:
        return None
    return (100.0 * m.flops_per_token * m.tokens_per_s
            / (m.chips * m.peak.flops_per_s))
