"""(row, key) pairs the attention kernels' walks compute over the pairs the
rows KEEP (`sum_t min(t + 1, 2048)` a head and sequence): every tile that
holds a key at or before one of its rows is computed whole and masked, so
the walk computes the triangle and its diagonal tiles' upper halves, 4.3
times the kept pairs at 16384 rows; 1.0 would be a walk that touches only
what a row chose. Static, from the program's own block sizes at the cell's
shape (the runner's `measured.dsa_walk`). Nothing where the runner hands no
such count."""


def read(m):
    walk = getattr(m, "dsa_walk", None)
    return walk["computed"] / walk["kept"] if walk else None
