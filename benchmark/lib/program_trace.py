"""What the program itself says about a traced run: its host spans, and the
JAX name stack (`op_name`) of every device op, reduced to a phase.

Spans. `obs/trace.SpanTracer.span` enters a
`jax.profiler.TraceAnnotation("prog.<name>", **args)`, so under a capture a
program span is an event of `/host:CPU` on the device's clock:
`trace.host_spans(planes, "prog.")` finds it, as it finds the runner's own
`bench.` spans (its arguments, `step=` and the like, are event stats there;
a thread is a line, and on the chip machine every Python thread's line is
called `python`). The same spans, for the whole window and with thread and
arguments, are in the tracer's `trace.jsonl` (`jsonl_events`), which the
profiler's few steps cannot give.

`op_name`. Looked for by hand in a v5e capture (jax 0.9.0, libtpu 0.0.34,
PR 25): it is **not there**. An event of `XLA Ops` is named by its HLO
instruction as text without the `metadata={...}` group, and its only stats
are `device_offset_ps`, `device_duration_ps` and `Time Scale Multiplier`;
`/host:metadata` is empty through `ProfileData`. So the name stack comes
from the other side: `compiled.as_text()` of the step, which the runner
holds, carries `metadata={op_name="jit(step)/loss_and_grad/..."}` on nearly
every instruction, a fusion's being its root's. `op_names` reads that text
into {instruction name: op_name}, and the join is by instruction name,
which `trace.parse_hlo` already cuts out of the event (`fusion.374`,
`flash_fwd.13`: a `pl.pallas_call(name=...)` reaches the instruction). An
instruction the compiler made itself (a copy, a convert of a weight, a slice
between memory spaces) has no metadata and no phase: `unattributed`, the
guard of the split.

Phases, by the first rule that matches an op's `op_name`:

    `head_loss`                  -> head_loss   (final norm, head, CE; both ways)
    `optimizer` or `grad_norm`   -> optimizer   (clip, Adam, schedule, the norm)
    `rematted_computation`       -> recompute   (the forward run again in the backward)
    `transpose(`                 -> bwd
    `loss_and_grad`              -> fwd
    an op outside every run of the step program (the snapshot copy, the
    loop's loss accumulation)    -> other_programs
    anything else                -> unattributed

The scopes are the program's (`training/train_step.py`, `models/gpt2.py`);
`jvp(`, `transpose(` and `rematted_computation` JAX adds itself. Every leaf
op of a `DeviceTrace` falls in exactly one phase, so the phases sum to the
device's busy time (ops of one TensorCore do not overlap).

Readers. `READERS` holds, by metric name, the `read(measured)` of the eleven
per-layer metrics the `train_ckpt` runner's `measured` feeds. They are not in
`BENCHMARK.json`, nor files of `layer_metrics/`, until a cell runs that
runner (PERF.md section 7, PR 25); `layer_metrics/<name>.py` is then
`read = READERS["<name>"]`.
"""

from __future__ import annotations

import bisect
import json
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from benchmark.lib import trace

PROGRAM_PREFIX = "prog."
PHASES = ("fwd", "recompute", "bwd", "head_loss", "optimizer",
          "other_programs", "unattributed")
# the spans of a save that hold the loop's thread (training/checkpoint.py)
CKPT_CALLER_SPANS = ("ckpt.loss_sync", "ckpt.join_prev", "ckpt.gather",
                     "ckpt.snapshot")
# and those of the writer thread, which run beside the loop
CKPT_WRITER_SPANS = ("ckpt.d2h", "ckpt.write")

_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) \(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(ROOT )?%(\S+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([^\s,)]+)")


# ---- op_name ----

def op_names(hlo_text: str) -> Dict[str, str]:
    """A compiled module's text -> {instruction name: op_name}. An
    instruction without metadata that calls a computation (a fusion) gets
    that computation's root's, or failing that its first named one's;
    one with neither is left out."""
    names: Dict[str, str] = {}
    calls: Dict[str, str] = {}
    roots: Dict[str, Optional[str]] = {}
    firsts: Dict[str, str] = {}
    computation = None
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            computation = head.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        found = _OP_NAME.search(line)
        if found:
            names[m.group(2)] = found.group(1)
            firsts.setdefault(computation, found.group(1))
        else:
            called = _CALLS.search(line)
            if called:
                calls[m.group(2)] = called.group(1)
        if m.group(1):
            roots[computation] = found.group(1) if found else None
    for name, called in calls.items():
        inherited = roots.get(called) or firsts.get(called)
        if inherited:
            names[name] = inherited
    return names


def phase_of(op_name: Optional[str]) -> str:
    """The phase of an op of the step program, from its `op_name`."""
    if not op_name:
        return "unattributed"
    if "head_loss" in op_name:
        return "head_loss"
    if "optimizer" in op_name or "grad_norm" in op_name:
        return "optimizer"
    if "rematted_computation" in op_name:
        return "recompute"
    if "transpose(" in op_name:
        return "bwd"
    if "loss_and_grad" in op_name:
        return "fwd"
    return "unattributed"


def step_runs(planes: Sequence[trace.Plane],
              dev: trace.DeviceTrace) -> List[trace.Interval]:
    """The executions of the step program on `dev`'s chip, as intervals."""
    module = trace.step_module(planes)
    for plane in planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) == dev.index:
            return trace.union(
                (e.start_ns, e.end_ns) for line in plane.lines
                if line.name == trace.MODULES_LINE
                for e in line.events if e.name == module)
    return []


def op_phases(dev: trace.DeviceTrace, runs: Sequence[trace.Interval],
              names: Dict[str, str]) -> List[str]:
    """The phase of each of `dev.ops`, in order. An op belongs to the step
    program if it starts inside one of `runs` (a union: sorted, disjoint)."""
    starts = [a for a, _ in runs]
    out = []
    for op in dev.ops:
        i = bisect.bisect_right(starts, op.start_ns) - 1
        out.append(phase_of(names.get(op.name))
                   if i >= 0 and op.start_ns < runs[i][1]
                   else "other_programs")
    return out


def phase_ns(dev: trace.DeviceTrace, runs: Sequence[trace.Interval],
             names: Dict[str, str]) -> Dict[str, int]:
    """Nanoseconds of `dev`'s leaf ops in each phase, clipped to its window."""
    out = dict.fromkeys(PHASES, 0)
    lo, hi = dev.window
    for op, phase in zip(dev.ops, op_phases(dev, runs, names)):
        out[phase] += max(min(op.end_ns, hi) - max(op.start_ns, lo), 0)
    return out


def top_unattributed(dev: trace.DeviceTrace, runs: Sequence[trace.Interval],
                     names: Dict[str, str],
                     n: int = 8) -> List[Tuple[str, float]]:
    """The ops of the step program that fell in no phase, by seconds: what
    a later scope or rule would have to name."""
    totals: Dict[str, int] = {}
    for op, phase in zip(dev.ops, op_phases(dev, runs, names)):
        if phase == "unattributed":
            totals[op.name] = totals.get(op.name, 0) + op.dur_ns
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [(name, ns / 1e9) for name, ns in ranked]


# ---- spans ----

def program_spans(planes: Sequence[trace.Plane]) -> List[trace.Event]:
    """The program's spans on the capture's host plane."""
    return trace.host_spans(planes, PROGRAM_PREFIX)


def loop_spans(planes: Sequence[trace.Plane]) -> List[trace.Event]:
    """What the loop's thread was doing, for naming an idle gap: the
    program's spans and the runner's `bench.` spans together, without the
    checkpoint writer's (a write lasts seconds and would cover every gap of
    the steps beside it, whatever their cause), shortest first, so that of
    two spans that cover a gap whole `trace.top_gaps` names the inner one."""
    writer = tuple(PROGRAM_PREFIX + s for s in CKPT_WRITER_SPANS)
    spans = [e for e in program_spans(planes) + trace.host_spans(
        planes, "bench.") if e.name not in writer]
    return sorted(spans, key=lambda e: e.dur_ns)


def named_gaps(dev: trace.DeviceTrace, spans: Sequence[trace.Event],
               at_least_ns: int = 100_000) -> Dict[str, int]:
    """Idle nanoseconds of `dev` by the span that covers most of each gap
    (`unattributed` where none does), over the gaps of `at_least_ns` and
    longer; shorter gaps are summed under `short_gaps`."""
    out: Dict[str, int] = {}
    for a, b in dev.gaps():
        name = "short_gaps"
        if b - a >= at_least_ns:
            name, cover = "unattributed", 0
            for s in spans:
                c = min(b, s.end_ns) - max(a, s.start_ns)
                if c > cover:
                    name, cover = s.name, c
        out[name] = out.get(name, 0) + (b - a)
    return out


def covered_gap_ns(dev: trace.DeviceTrace,
                   spans: Iterable[trace.Event]) -> int:
    """Idle nanoseconds of `dev` that lie under one of `spans`."""
    under = trace.union((s.start_ns, s.end_ns) for s in spans)
    gaps = dev.gaps()
    return trace.length(gaps) - trace.length(trace.subtract(gaps, under))


def jsonl_events(path: str) -> List[dict]:
    """The tracer's `trace.jsonl` as it stands (a torn last line is left
    out): complete events and instants, in the order they were written."""
    events = []
    try:
        with open(path) as f:
            for line in f:
                try:
                    ev = json.loads(line)
                except ValueError:
                    continue
                if ev.get("ph") in ("X", "i"):
                    events.append(ev)
    except FileNotFoundError:
        pass
    return events


def between(events: Sequence[dict], opened: str, closed: str) -> List[dict]:
    """The complete events written after the instant `opened` and before
    the instant `closed` (spans are written when they end)."""
    out, inside = [], False
    for ev in events:
        if ev["ph"] == "i":
            if ev["name"] == opened:
                inside = True
            elif ev["name"] == closed:
                inside = False
        elif inside:
            out.append(ev)
    return out


def span_ms(events: Iterable[dict], names: Sequence[str]) -> float:
    """Summed milliseconds of the complete events called one of `names`."""
    return sum(ev["dur"] for ev in events if ev["name"] in names) / 1e3


# ---- the per-layer readers of the train_ckpt runner's `measured` ----

def _phase_ms_per_step(phase: str):
    """Chip 0's device milliseconds per traced step in ops of `phase`."""
    def read(m):
        phases = getattr(m, "phases", None)
        if not phases or not m.devices:
            return None
        return phases[phase] / m.devices[0].steps / 1e6
    return read


def _span_mean(names: Sequence[str], counted: str, unit_ms: float = 1.0):
    """Summed time of the window's spans called one of `names` (the tracer's
    trace.jsonl), per span called `counted`, in units of `unit_ms`."""
    def read(m):
        spans = getattr(m, "window_spans", None) or ()
        n = sum(ev["name"] == counted for ev in spans)
        return span_ms(spans, names) / n / unit_ms if n else None
    return read


def _ckpt_device_ms(m):
    """Device milliseconds a save costs under the profiler: ops of other
    programs inside the traced steps (the snapshot copy, the loop's loss
    accumulation) plus the idle time under a caller-side `prog.ckpt.*`
    span, per traced save."""
    phases = getattr(m, "phases", None)
    if not phases or not m.capture_saves:
        return None
    return (phases["other_programs"] + m.ckpt_gap_ns) / m.capture_saves / 1e6


READERS = {
    # ms/step, device_trace, all move tokens_per_s_per_chip
    "model.fwd_ms": _phase_ms_per_step("fwd"),             # flash forward in it
    "model.recompute_ms": _phase_ms_per_step("recompute"),  # remat's cost
    "model.bwd_ms": _phase_ms_per_step("bwd"),
    "model.head_loss_ms": _phase_ms_per_step("head_loss"),  # both ways
    "train_step.optimizer_ms": _phase_ms_per_step("optimizer"),
    # the guard of the five above: ops with no `op_name`
    "train_step.unattributed_ms": _phase_ms_per_step("unattributed"),
    # ms/step, program_span: per dispatch over the window
    "input.data_wait_ms": _span_mean(("data_wait",), "data_wait"),
    "input.h2d_ms": _span_mean(("h2d",), "h2d"),
    # ms/save: what holds the loop's thread, per save of the window
    "checkpoint.stall_ms": _span_mean(CKPT_CALLER_SPANS, "ckpt.snapshot"),
    # s/save: the writer thread; past save_every x step time the next save
    # waits in ckpt.join_prev
    "checkpoint.write_s": _span_mean(CKPT_WRITER_SPANS, "ckpt.write", 1e3),
    "checkpoint.device_ms": _ckpt_device_ms,                # ms/save
}
