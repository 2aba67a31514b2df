"""A traced step split by the program's named scopes: which scope each device
op of the step belongs to, from its `op_name` (the JAX name stack the
compiled step's text carries; benchmark/lib/program_trace.op_names) and its
instruction name.

Rule: an op belongs to the scope named LAST in its `op_name`, the innermost
(an op of the multi-token-prediction module's attention is `mla`, the
module's own projection, head and CE are `mtp`). Two kinds of op are told by
their instruction instead: the flash kernels (`flash`: their time is
`kernels.flash_ms`, never a scope's) and the grouped expert products, which
XLA:TPU makes Mosaic kernels named `ragged-dot-*` whose metadata it
replaces (`moe_experts`); so it does a `sort`'s (`op_name="sort"`), and the
step's only sorts are the router's top-k and the argsort of the (token,
choice) pairs (`moe_route`). An op of the step with no scope is `rest`
(the dense layer's MLP, layer norms, residual adds, the embedding; JAX's
`transpose(`/`rematted_computation` are not scopes), one with no `op_name`
at all `unattributed`, one outside every run of the step `other_programs`.
Every leaf op falls in exactly one, so the parts sum to the device's busy
time.
"""

from __future__ import annotations

import bisect
import re
from typing import Dict, Optional, Sequence

from benchmark.lib import trace
from benchmark.lib.kernels import FLASH

SCOPES = ("mla", "moe_route", "moe_experts", "moe_shared", "mtp",
          "head_loss", "optimizer", "grad_norm")
PARTS = SCOPES + ("flash", "rest", "unattributed", "other_programs")
_SCOPE = re.compile(r"(?:^|/)(" + "|".join(SCOPES) + r")(?=/|$)")
RAGGED_DOT = re.compile(r"^ragged-dot")
SORT = re.compile(r"^sort[.\d]*$")


def scope_of(op: trace.Event, op_name: Optional[str]) -> str:
    if FLASH.search(op.name) or FLASH.search(op.meta):
        return "flash"
    if RAGGED_DOT.match(op.name):
        return "moe_experts"
    if SORT.match(op.name):
        return "moe_route"
    if not op_name:
        return "unattributed"
    found = _SCOPE.findall(op_name)
    return found[-1] if found else "rest"


def scope_ns(dev: trace.DeviceTrace, runs: Sequence[trace.Interval],
             names: Dict[str, str]) -> Dict[str, int]:
    """Nanoseconds of `dev`'s leaf ops in each part, clipped to its window.
    `runs` are the step program's executions (a union: sorted, disjoint)."""
    out = dict.fromkeys(PARTS, 0)
    starts = [a for a, _ in runs]
    lo, hi = dev.window
    for op in dev.ops:
        i = bisect.bisect_right(starts, op.start_ns) - 1
        inside = i >= 0 and op.start_ns < runs[i][1]
        part = scope_of(op, names.get(op.name)) if inside else "other_programs"
        out[part] += max(min(op.end_ns, hi) - max(op.start_ns, lo), 0)
    return out


def scope_ms_per_step(m, part: str):
    """Chip 0's device milliseconds per traced step in ops of `part`; None
    where the runner's `measured` carries no scope split (a runner that
    hands its readers no `op_name`, or an untraced run)."""
    parts = getattr(m, "scopes", None)
    if not parts or not m.devices:
        return None
    return parts[part] / m.devices[0].steps / 1e6
