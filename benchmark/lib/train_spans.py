"""What `train()` itself says about one run of it: its set-up phase by phase,
and what its loop's thread did over the window, from the records the program
writes (`logs/trace.jsonl`, `logs/metrics.jsonl`). Arithmetic on what the
`train_program` runner hands as `measured`:

    `timeline`       every complete event of `trace.jsonl` (`name`, `ts` and
                     `dur` in microseconds from `train()`'s first line, `tid`,
                     `args`); None where the run wrote no timeline
    `window_steps`   (the step whose sync opened the window, the step whose
                     sync closed it)
    `setup_s`        process start to the opening stamp
    `recompiles`     the `recompile` events of `metrics.jsonl` in the window

The spans (the program's: `train.py`, `obs/observer.py`):

    `setup.backend`, `setup.logs`, `setup.data`, `setup.model`, `setup.init`,
    `setup.opt_state`, `setup.build_step`   once a run, one after another,
                     from `train()`'s first line to its loop
    `compile`        the step program's build, at the first dispatch
    `data_wait`, `h2d`, `step`   once a step: the pull from the prefetcher,
                     the feed, the dispatch
    `device_sync`    once a log interval: the host waits for the interval's
                     last step (its end is the interval's stamp)
    `log`            right behind it: the interval's host work, the device
                     idle under it; `programs` = programs built inside it
    `profile.start`, `profile.stop`   the program's own capture (`--trace 1`)

The loop's thread is the one that holds the `step` spans. The window is from
the end of `device_sync` at its opening step to the end of `device_sync` at
its closing step, on the timeline's clock; an event belongs to it if it ENDED
inside it, as in `benchmark/lib/program_trace.between`.

A reader gives None where the run wrote no timeline, and where the program
has no such span (one from before the spans existed).
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

from benchmark.lib.program_trace import PROGRAM_PREFIX
from benchmark.lib.trace import HOST_PLANE, length, union

SETUP_SPANS = ("setup.backend", "setup.logs", "setup.data", "setup.model",
               "setup.init", "setup.opt_state", "setup.build_step")


def _end(ev: dict) -> float:
    return ev["ts"] + ev["dur"]


def loop_thread(events: Sequence[dict]) -> Optional[int]:
    """The `tid` of the thread that dispatches the steps."""
    return next((ev["tid"] for ev in events if ev["name"] == "step"), None)


def loop_events(events: Sequence[dict]) -> List[dict]:
    tid = loop_thread(events)
    return [ev for ev in events if ev["tid"] == tid]


def sync_end_us(events: Sequence[dict], step: int) -> Optional[float]:
    """When the `device_sync` of `step` returned, on the timeline's clock."""
    return next((_end(ev) for ev in events if ev["name"] == "device_sync"
                 and ev.get("args", {}).get("step") == step), None)


def window_us(m) -> Optional[Tuple[float, float]]:
    events = getattr(m, "timeline", None)
    if not events:
        return None
    opened, closed = (sync_end_us(events, s) for s in m.window_steps)
    return None if opened is None or closed is None else (opened, closed)


def in_window(m, name: str) -> List[dict]:
    """The loop's thread's spans called `name` that ended in the window."""
    window = window_us(m)
    if window is None:
        return []
    lo, hi = window
    return [ev for ev in loop_events(m.timeline)
            if ev["name"] == name and lo < _end(ev) <= hi]


def covered_us(events: Sequence[dict], lo: float, hi: float) -> float:
    """Microseconds of [lo, hi) under any of `events` (nested or not)."""
    return length(union((max(ev["ts"], lo), min(_end(ev), hi))
                        for ev in events))


def _setup_span_s(name: str):
    def read(m):
        spans = [ev for ev in getattr(m, "timeline", None) or ()
                 if ev["name"] == name]
        return spans[0]["dur"] / 1e6 if len(spans) == 1 else None
    return read


def _setup_unspanned_s(m):
    """`setup_s` less what the loop's thread spent under a span of its own
    before the window opened: the process before `train()` (imports, the
    token file), and what of `train()` is in no span."""
    window = window_us(m)
    if window is None:
        return None
    covered = covered_us(loop_events(m.timeline), float("-inf"), window[0])
    return m.setup_s - covered / 1e6


def _mean_ms(name: str):
    def read(m):
        spans = in_window(m, name)
        return (sum(ev["dur"] for ev in spans) / len(spans) / 1e3
                if spans else None)
    return read


def _log_programs(m):
    """Programs built inside a steady interval's `log`: the mean of the
    spans' `programs` over the window's intervals after its first."""
    built = [ev.get("args", {}).get("programs")
             for ev in in_window(m, "log")[1:]]
    if not built or None in built:
        return None
    return sum(built) / len(built)


def _unspanned_pct(m):
    window = window_us(m)
    if window is None:
        return None
    lo, hi = window
    return 100.0 * (1.0 - covered_us(loop_events(m.timeline), lo, hi)
                    / (hi - lo))


def _recompiles(m):
    if getattr(m, "timeline", None) is None:
        return None
    return len(m.recompiles)


# ---- the capture: which span of the loop's thread the host was in ----

def loop_thread_spans(planes) -> list:
    """The program's spans on the capture's host plane that lie on the line
    (thread) of the `prog.step` spans, shortest first, so that of two spans
    that cover a gap whole `trace.top_gaps` names the inner one. The
    prefetch worker's line is left out: it works beside the loop and would
    lend its name to gaps it did not open."""
    spans = []
    for plane in planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            mine = [e for e in line.events
                    if e.name.startswith(PROGRAM_PREFIX)]
            if any(e.name == PROGRAM_PREFIX + "step" for e in mine):
                spans.extend(mine)
    return sorted(spans, key=lambda e: e.dur_ns)


READERS = {
    # s, once a run; all move setup_s
    **{f"{name}_s": _setup_span_s(name) for name in SETUP_SPANS},
    # s: the guard that the tree covers the set-up
    "setup.unspanned_s": _setup_unspanned_s,
    # ms/step: the dispatch
    "loop.dispatch_ms": _mean_ms("step"),
    # ms/interval: how long the host waited for the device; near 0 means
    # the host sets the pace
    "loop.device_sync_ms": _mean_ms("device_sync"),
    # ms/interval: host work with the device idle
    "loop.log_ms": _mean_ms("log"),
    "loop.log_programs": _log_programs,
    # %: window seconds under no span of the loop's thread
    "loop.unspanned_pct": _unspanned_pct,
    "loop.recompiles": _recompiles,
}
