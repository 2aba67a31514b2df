"""From the profiler's capture to numbers: the reduction every PR shares.

`jax.profiler` writes `<dir>/plugins/profile/<time>/<host>.xplane.pb`.
`load_xplane` reads it with nothing but JAX (`jax.profiler.ProfileData`) into
plain tuples, `to_plain` / `from_plain` carry the same thing as JSON (the
recorded fixture under benchmark/tests/fixtures/ is one), and everything below
is arithmetic on those tuples, checked against hand-worked values in
benchmark/tests/test_trace.py.

What a TPU capture looks like (v5e, jax 0.9.0; looked at by hand, PR 24):
one plane per chip, `/device:TPU:<n>`. Its line `XLA Modules` holds one event
per executed program (`jit_step(<fingerprint>)`). Its line `XLA Ops` holds
one event per executed HLO op, **nested**: a `while` spans the ops of its
body. The event's name is the whole HLO instruction as text
(`%closed_call.8 = (bf16[192,1024,64]{...}, ...) custom-call(...),
custom_call_target="tpu_custom_call", ...`); `parse_hlo` cuts it down to the
instruction's name (`closed_call.8`) and a `meta` of its opcode, and for a
custom call its target and number of operands (`custom-call
tpu_custom_call operands=3`). Its line `Async XLA Ops` holds one span per
asynchronous op from its `-start` to its `-done` (copies, and collectives
when XLA makes them asynchronous). Host threads are lines of `/host:CPU`; the
benchmark's own `TraceAnnotation` spans (`bench.data`, `bench.dispatch`,
`bench.wait`) appear there on the same clock.

Definitions:

* the **traced window** of a device is from the start of the first event of
  the step program on `XLA Modules` to the end of its last one; steps are
  counted there; ops outside it (the profiler's own start-up) are left out;
* the **ops** of a device are the leaves of `XLA Ops` (an event that
  contains a later one is a container and is left out, so no time is
  counted twice; events of no duration are left out before that); **busy** is the union of their intervals inside the
  window; **idle** is window minus busy; a **gap** is a maximal idle interval;
* an op is a **collective** if its opcode says so (`COLLECTIVE_PREFIXES`),
  on either line; collective time is the union of those intervals, and its
  **exposed** part is what is left of that union after taking away every
  interval in which an op that is not a collective runs on that device;
* a **kernel** (Pallas custom call) is picked out by a regular expression a
  per-layer metric brings, matched against the op's name and its `meta`.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
COLLECTIVE_PREFIXES = ("all-reduce", "all-gather", "reduce-scatter",
                       "collective-permute", "all-to-all",
                       "collective-broadcast")
_HLO = re.compile(r"^%(\S+) = .*?[\]})] ([a-z][\w\-]*)\(")
_CUSTOM_CALL = re.compile(
    r" custom-call\((.*?)\), custom_call_target=\"([^\"]+)\"")

Interval = Tuple[int, int]          # [start_ns, end_ns)


class Event(NamedTuple):
    name: str
    start_ns: int
    dur_ns: int
    meta: str = ""

    @property
    def end_ns(self) -> int:
        return self.start_ns + self.dur_ns


class Line(NamedTuple):
    name: str
    events: List[Event]


class Plane(NamedTuple):
    name: str
    lines: List[Line]


# ---- reading ----

def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def parse_hlo(text: str) -> Tuple[str, str]:
    """An HLO instruction as text -> (instruction name, meta). Text that is
    not an instruction comes back as it is, with no meta."""
    m = _HLO.match(text)
    if not m:
        return text, ""
    name, opcode = m.groups()
    call = _CUSTOM_CALL.search(text) if opcode == "custom-call" else None
    if call:
        return name, (f"custom-call {call.group(2)} "
                      f"operands={call.group(1).count('%')}")
    return name, opcode


def planes_of(profile_data) -> List[Plane]:
    """`jax.profiler.ProfileData` -> plain tuples, events sorted by start
    (a container before what it contains)."""
    planes = []
    for plane in profile_data.planes:
        lines = []
        for line in plane.lines:
            hlo = (DEVICE_PLANE.match(plane.name)
                   and line.name in (OPS_LINE, ASYNC_LINE))
            events = []
            for ev in line.events:
                name, meta = parse_hlo(ev.name) if hlo else (ev.name, "")
                events.append(Event(name, int(ev.start_ns),
                                    int(ev.duration_ns), meta))
            events.sort(key=lambda e: (e.start_ns, -e.dur_ns))
            lines.append(Line(line.name, events))
        planes.append(Plane(plane.name, lines))
    return planes


def load_xplane(path: str) -> List[Plane]:
    from jax.profiler import ProfileData
    return planes_of(ProfileData.from_file(path))


def to_plain(planes: Sequence[Plane]) -> list:
    return [{"plane": p.name, "lines": [
        {"line": l.name, "events": [list(e) for e in l.events]}
        for l in p.lines]} for p in planes]


def from_plain(obj: list) -> List[Plane]:
    return [Plane(p["plane"], [
        Line(l["line"], [Event(e[0], int(e[1]), int(e[2]),
                               e[3] if len(e) > 3 else "")
                         for e in l["events"]])
        for l in p["lines"]]) for p in obj]


# ---- interval arithmetic ----

def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def length(intervals: Iterable[Interval]) -> int:
    return sum(b - a for a, b in intervals)


def subtract(keep: List[Interval], remove: List[Interval]) -> List[Interval]:
    """`keep` minus `remove`; both are unions (sorted, disjoint)."""
    out, j = [], 0
    for a, b in keep:
        while j < len(remove) and remove[j][1] <= a:
            j += 1
        k, cur = j, a
        while k < len(remove) and remove[k][0] < b:
            if remove[k][0] > cur:
                out.append((cur, remove[k][0]))
            cur = max(cur, remove[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def clip(events: Iterable[Event], lo: int, hi: int) -> List[Interval]:
    return [(max(e.start_ns, lo), min(e.end_ns, hi)) for e in events
            if e.end_ns > lo and e.start_ns < hi]


# ---- the reduction ----

def is_collective(op: Event) -> bool:
    return op.meta.startswith(COLLECTIVE_PREFIXES)


def leaves(events: Sequence[Event]) -> List[Event]:
    """Events (sorted by start, longer first) that contain no later event.
    An event of no duration (a bitcast the chip never runs: the capture
    stamps it at the start of the op that follows) takes no time and is
    left out first, or the op it is stamped on would pass for its
    container and be lost."""
    events = [e for e in events if e.dur_ns > 0]
    out = []
    for i, e in enumerate(events):
        nxt = events[i + 1] if i + 1 < len(events) else None
        if nxt is None or nxt.start_ns >= e.end_ns:
            out.append(e)
    return out


class DeviceTrace(NamedTuple):
    """One chip inside its traced window: `ops` are the leaves of `XLA Ops`,
    `async_ops` the spans of `Async XLA Ops`."""

    index: int
    window: Interval
    steps: int
    ops: List[Event]
    async_ops: List[Event]

    @property
    def window_ns(self) -> int:
        return self.window[1] - self.window[0]

    def intervals(self, ops: Optional[Iterable[Event]] = None):
        return clip(self.ops if ops is None else ops, *self.window)

    def busy_ns(self) -> int:
        return length(union(self.intervals()))

    def gaps(self) -> List[Interval]:
        return subtract([self.window], union(self.intervals()))

    def select(self, pattern: "re.Pattern[str]") -> List[Event]:
        return [e for e in self.ops
                if pattern.search(e.name) or pattern.search(e.meta)]

    def time_ns(self, ops: Iterable[Event]) -> int:
        """Summed duration of `ops`, clipped to the window."""
        return length(self.intervals(ops))

    def collectives(self) -> List[Event]:
        return [e for e in self.ops + self.async_ops if is_collective(e)]

    def collective_ns(self) -> int:
        return length(union(self.intervals(self.collectives())))

    def exposed_collective_ns(self) -> int:
        coll = union(self.intervals(self.collectives()))
        rest = union(self.intervals(
            e for e in self.ops if not is_collective(e)))
        return length(subtract(coll, rest))


def _line(plane: Plane, name: str) -> List[Event]:
    for line in plane.lines:
        if line.name == name:
            return line.events
    return []


def step_module(planes: Sequence[Plane]) -> Optional[str]:
    """The program that took most device time on `XLA Modules`: the step."""
    totals: Dict[str, int] = {}
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            for e in _line(plane, MODULES_LINE):
                totals[e.name] = totals.get(e.name, 0) + e.dur_ns
    return max(totals, key=totals.get) if totals else None


def device_traces(planes: Sequence[Plane]) -> List[DeviceTrace]:
    """One `DeviceTrace` per chip that ran the step program; [] if none did
    (a capture from a backend with no device planes)."""
    module = step_module(planes)
    out = []
    for plane in planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m or module is None:
            continue
        runs = [e for e in _line(plane, MODULES_LINE) if e.name == module]
        if not runs:
            continue
        lo, hi = runs[0].start_ns, max(e.end_ns for e in runs)
        inside = lambda line: [e for e in _line(plane, line)
                               if e.end_ns > lo and e.start_ns < hi]
        out.append(DeviceTrace(int(m.group(1)), (lo, hi), len(runs),
                               leaves(inside(OPS_LINE)), inside(ASYNC_LINE)))
    return sorted(out, key=lambda d: d.index)


def host_spans(planes: Sequence[Plane], prefix: str) -> List[Event]:
    """The benchmark's own annotations on the host threads."""
    return sorted((e for p in planes if p.name == HOST_PLANE
                   for line in p.lines for e in line.events
                   if e.name.startswith(prefix)),
                  key=lambda e: e.start_ns)


def top_ops(dev: DeviceTrace, n: int = 10) -> List[Tuple[str, float]]:
    """Device ops by total seconds, grouped by HLO instruction name; a
    custom call's target and operand count are part of the name."""
    totals: Dict[str, int] = {}
    lo, hi = dev.window
    for e in dev.ops:
        label = f"{e.name} [{e.meta}]" if " " in e.meta else e.name
        inside = min(e.end_ns, hi) - max(e.start_ns, lo)
        totals[label] = totals.get(label, 0) + max(inside, 0)
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [(name, ns / 1e9) for name, ns in ranked]


def top_gaps(dev: DeviceTrace, spans: Sequence[Event],
             n: int = 10) -> List[Tuple[str, float]]:
    """The longest idle gaps, each named after the host span that covers
    most of it (`unattributed` where none does)."""
    out = []
    for a, b in sorted(dev.gaps(), key=lambda g: g[0] - g[1])[:n]:
        best, cover = "unattributed", 0
        for s in spans:
            c = min(b, s.end_ns) - max(a, s.start_ns)
            if c > cover:
                best, cover = s.name, c
        out.append((best, (b - a) / 1e9))
    return out
