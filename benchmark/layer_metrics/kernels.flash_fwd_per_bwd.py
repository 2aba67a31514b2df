"""Flash forward calls over flash backwards in the traced steps: 2.0 where a
layer's recompute runs the forward kernel again in the backward (the remat
floor), 1.0 where the kernel's output and its logsumexp are kept across the
recompute (from the `flash` rung of the program's remat ladder, which
`remat="auto"` picks where the chip has room). Counted by the calls' names
and operand counts (benchmark/lib/kernels.py); of a split backward (a
`flash_bwd_dq` and a `flash_bwd_dkv` call for one backward) the dq call is
counted. Chip 0."""

from benchmark.lib.kernels import FLASH_BACKWARD, FLASH_FORWARD


def read(m):
    if not m.devices:
        return None
    dev = m.devices[0]
    forward = dev.select(FLASH_FORWARD)
    backward = [c for c in dev.select(FLASH_BACKWARD)
                if not c.name.startswith("flash_bwd_dkv")]
    return len(forward) / len(backward) if forward and backward else None
