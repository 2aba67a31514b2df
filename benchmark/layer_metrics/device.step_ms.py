"""The step as the device saw it: the traced window (first start to last end
of the step program on chip 0) over the steps in it. Beside the host
clock's `train_step.step_ms_median`, it says whether the host's timer and
the device agree."""


def read(m):
    if not m.devices:
        return None
    dev = m.devices[0]
    return dev.window_ns / dev.steps / 1e6
