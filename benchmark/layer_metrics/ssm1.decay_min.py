"""The window's worst `sscan_decay_min`: over every step, Mamba-1 layer,
channel and state the most negative `dt A` of ONE step (the step's
counter; parallel/mamba1.py, ops/selective_scan.py): the decay is its
exponential, so near -87 a step forgets its state to the last bit. None
where the step counts no such thing."""


def read(m):
    return getattr(m, "sscan_decay_min", None)
