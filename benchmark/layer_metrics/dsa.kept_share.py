"""(row, key) pairs the step's rows kept over the causal pairs, from the
step's own counters over the window (`dsa_kept` / `dsa_causal`, a row a
layer: the kernels count a row's live pairs as they walk): `sum_t min(t + 1,
2048)` over the triangle, 0.2344 at 16384 rows, exactly, if every row kept
its 2048 and no more (a tie rule that failed, or a score re-made otherwise
by two kernels, reads off it). Nothing where the step counts no such
thing."""


def read(m):
    return getattr(m, "dsa_kept_share", None)
