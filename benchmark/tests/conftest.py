"""One OpenBLAS thread a process for the rehearsals these tests spawn: the
CPU's `triangular_solve` (the gdn_moe family's chunked delta rule) is a
LAPACK call whose worker threads spin, and several pytest workers side by
side made a 64 x 64 solve take 217 ms where it takes 0.16 alone (PR 35):
the rehearsed cell then counts two steps in its window, not twenty. The
child processes inherit the setting."""

import os

os.environ.setdefault("OPENBLAS_NUM_THREADS", "1")
