"""Load imbalance of the experts held: per expert layer the most (token,
choice) pairs any held expert got over the held experts' mean, averaged
over the layers and the window's steps (the step's `routed` counter). 1.0
is perfect balance; the dropless dispatch computes whatever it is, at the
same cost while the held rows stay inside one chunk."""


def read(m):
    return getattr(m, "load_max_over_mean", None)
