"""Median of the per-step completion intervals whose 90th percentile is the
end-to-end `step_ms_p90`: the steadier statistic, beside it."""

from benchmark.lib.timing import quantile


def read(m):
    return quantile(m.intervals_ms, 0.5)
