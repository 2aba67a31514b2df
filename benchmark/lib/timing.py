"""Timing a stream of device steps from the host without stalling the device.

`run_window` dispatches step i, then waits for step i-1 and stamps the clock:
the device always has the next step queued, and the distance between two
stamps is a step's completion interval. The window opens at a stamp and
closes at the first stamp past `seconds`; only whole steps inside count.

A host clock reading is off by some half a millisecond: an interval between
two stamps means something where a step lasts 250 ms or more, which every
cell's does today (PERF.md, section 2).
"""

from __future__ import annotations

import time
from typing import Callable, List, NamedTuple


class Window(NamedTuple):
    stamps: List[float]     # stamps[0] opens the window; one more per step
    results: list           # what `dispatch` returned for each counted step

    @property
    def steps(self) -> int:
        return len(self.stamps) - 1

    @property
    def seconds(self) -> float:
        return self.stamps[-1] - self.stamps[0]

    @property
    def step_intervals_ms(self) -> List[float]:
        """Milliseconds from each step's completion to the next one's."""
        return [(b - a) * 1e3 for a, b in zip(self.stamps, self.stamps[1:])]


def run_window(dispatch: Callable[[], object], wait: Callable[[object], None],
               seconds: float, max_steps: "int | None" = None,
               clock: Callable[[], float] = time.perf_counter) -> Window:
    """`dispatch()` enqueues one step and returns a handle; `wait(handle)`
    returns when that step is done. Two steps are in flight when the window
    opens; the last step dispatched is waited for and not counted."""
    behind = dispatch()
    ahead = dispatch()
    wait(behind)
    stamps, results = [clock()], []
    while True:
        behind, ahead = ahead, dispatch()
        wait(behind)
        stamps.append(clock())
        results.append(behind)
        if stamps[-1] - stamps[0] >= seconds:
            break
        if max_steps is not None and len(results) >= max_steps:
            break
    wait(ahead)
    return Window(stamps, results)


def quantile(values: List[float], q: float) -> float:
    """Linear interpolation between order statistics (numpy's default)."""
    if not values:
        raise ValueError("quantile of no values")
    xs = sorted(values)
    pos = q * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)
