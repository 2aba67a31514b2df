"""The window's worst `kda_g_min`: over every step, delta layer, head,
token and channel the most negative decay `g` the bounded gate made (the
step's counter; parallel/kda.KimiDeltaAttention). The gate holds `g` over
`kda_lower_bound` (-5), which the chunked rule's sub-blocks rely on: a
reading under it says the bound is broken. None where the step counts no
such thing (a program without the family)."""


def read(m):
    return getattr(m, "kda_g_min", None)
