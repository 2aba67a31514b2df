"""Peak device memory of the process on the fullest chip, in GiB, as the
device's runtime counted it (`memory_stats()` after the window):
`peak_bytes_in_use`, the buffers (weights, Adam state, batches), plus
`peak_bytes_reserved`, what the runtime set aside for the loaded programs'
temporaries. `peak_bytes_in_use` alone leaves a running step's temporaries
out (PERF.md, section 3). The same number as the last line's
`memory_peak_bytes`; the log line `setup` has both parts, and what they were
when set-up ended."""


def read(m):
    return None if m.peak_bytes is None else m.peak_bytes / 2**30
