"""Share of the traced window in which no op ran on the device, averaged
over the chips used: 1 - union of op intervals / window."""


def read(m):
    if not m.devices:
        return None
    shares = [1.0 - d.busy_ns() / d.window_ns for d in m.devices]
    return 100.0 * sum(shares) / len(shares)
