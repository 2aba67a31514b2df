"""Device milliseconds per step in the chunked state-space recurrence alone
(scope `mamba/ssd`: softplus of dt, the float32 decay sums and their
exponentials over 128 x 128 a head and chunk, the chunks' masked `C B^T`
scores, the three products and the scan that hands the states on; five Mamba
layers). Forward, recompute and backward together; chip 0
(benchmark/lib/ssm_scopes.py). XLA text: no kernel holds the recurrence yet,
and a Pallas walk is read on this same scope. None where the runner's split
has no such scope."""

from benchmark.lib.ssm_scopes import ssd_ms_per_step


def read(m):
    return ssd_ms_per_step(m)
