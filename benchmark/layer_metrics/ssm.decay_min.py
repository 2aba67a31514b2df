"""The window's worst `ssm_decay_min`: over every step, Mamba layer, head
and chunk the most negative `dt A` summed over a chunk of 128 tokens (the
step's counter; parallel/mamba.Mamba2Mixer, ops/ssd.py). A chunk's decays
are exponentials of differences of these sums: near -87 the float32 `exp`
of the whole chunk underflows (what has decayed that far adds nothing, so
it is no fault, but a reading there says the chunk is longer than a head's
memory). None where the step counts no such thing (a program without the
family)."""


def read(m):
    return getattr(m, "ssm_decay_min", None)
