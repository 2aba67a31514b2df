"""The window's worst `hc_sinkhorn_err`: over every step, layer, mixer and
token the largest |row sum - 1| or |column sum - 1| of H_res after the last
Sinkhorn round (the step's counter; parallel/hyper.StreamMixer.counters).
The columns are normalised last, so this is the rows' error: what 20 rounds
leave of a matrix that starts as exp of a clamped random map. None where
the step counts no such thing (a program without the family)."""


def read(m):
    return getattr(m, "sinkhorn_err", None)
