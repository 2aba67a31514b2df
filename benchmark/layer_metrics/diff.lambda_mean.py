"""The window's mean `diff_lambda`: the differential attention layers'
`lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init`, a mean over the
attention layers and the window's steps (the step's counter,
parallel/diff_attention.py). 0.2 - 0.8 by depth on fresh weights; a lambda
that drifts to 0 is plain attention, one past 1 subtracts more than it
adds. None where the step counts no such thing."""


def read(m):
    return getattr(m, "diff_lambda_mean", None)
