"""Device milliseconds per step in ops that are neither a Pallas custom call
nor a collective: the matmuls, fusions and copies XLA makes of the model,
the loss and the optimizer. Chip 0 of the traced steps."""

from benchmark.lib.kernels import CUSTOM_CALL
from benchmark.lib.trace import is_collective


def read(m):
    if not m.devices:
        return None
    dev = m.devices[0]
    kernels = set(dev.select(CUSTOM_CALL))
    rest = [e for e in dev.ops
            if e not in kernels and not is_collective(e)]
    return dev.time_ns(rest) / dev.steps / 1e6
