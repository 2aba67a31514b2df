"""A traced step of the early_moe family split by the program's named
scopes: `benchmark/lib/scopes.py`'s rule with this family's scope list (the
lists of `lib/scopes.py`, `lib/hybrid_scopes.py`, `lib/conv_scopes.py`,
`lib/bd_scopes.py` and `lib/swa_scopes.py` are closed and belong to their
runners; the sixth list: ROADMAP D14), and which of the routing's ops run
from the LAYER'S INPUT.

Rule: an op belongs to the scope named LAST in its `op_name`, the
innermost. Told by their instruction instead, as there: the flash kernels
(`flash`: `kernels.flash_ms`, never `gqa_attn`'s; which of them are a
window layer's is `lib/swa_scopes.flash_calls`' to say, by the `_window` in
the kernel's name, split or resident), the grouped expert products
(`ragged-dot-*`: `moe_experts`) and the step's sorts (the router's top-k,
the argsort of the (token, choice) pairs: `moe_route`). An op of the step
with no scope is `rest` (layer norms, residual adds, the embedding), one
with no `op_name` at all `unattributed`, one outside every run of the step
`other_programs`. Every leaf op falls in exactly one, so the parts sum to
the device's busy time.

**The early part of `moe_route`** (`early_route_ns`; a subset of
`moe_route`, not a part beside it): the ops under the inner scope
`moe_route/early` (`parallel/moe.SharedRoutedFFN.apply` with a `router_x`:
the router's product from the layer's input, the top-k, the weights,
`sort_pairs`, `index`, and their transposes) and the step's sorts, whose
metadata XLA:TPU replaces by `op_name="sort"`: in this family every sort of
the step is the routing's (the top-k, the pairs' sort forward and in the
recomputed forward, the weights' cotangent back through it). It is what the
architecture lets a deployment start before a layer's attention ends;
forward, recompute and backward together, as every scope's time is. A
program whose routing has no such scope (any other family's) names none,
and the part is then the sorts alone; `early_route_ns` says None there.
"""

from __future__ import annotations

import bisect
import re
from typing import Dict, Optional, Sequence

from benchmark.lib import trace
from benchmark.lib.kernels import FLASH
from benchmark.lib.scopes import RAGGED_DOT, SORT

SCOPES = ("gqa_attn", "moe_route", "moe_experts", "head_loss", "optimizer",
          "grad_norm")
PARTS = SCOPES + ("flash", "rest", "unattributed", "other_programs")
_SCOPE = re.compile(r"(?:^|/)(" + "|".join(SCOPES) + r")(?=/|$)")
EARLY = re.compile(r"(?:^|/)moe_route/early(?=/|$)")


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


def _ops_of_the_step(dev: trace.DeviceTrace, runs: Sequence[trace.Interval]):
    """(op, is it inside a run of the step, its nanoseconds clipped to the
    device's window) of every leaf op of `dev`."""
    starts = [a for a, _ in runs]
    lo, hi = dev.window
    for op in dev.ops:
        i = bisect.bisect_right(starts, op.start_ns) - 1
        yield (op, i >= 0 and op.start_ns < runs[i][1],
               max(min(op.end_ns, hi) - max(op.start_ns, lo), 0))


def scope_ns(dev: trace.DeviceTrace, runs: Sequence[trace.Interval],
             names: Dict[str, str]) -> Dict[str, int]:
    """Nanoseconds of `dev`'s leaf ops in each part, clipped to its window.
    `runs` are the step program's executions (a union: sorted, disjoint)."""
    out = dict.fromkeys(PARTS, 0)
    for op, inside, ns in _ops_of_the_step(dev, runs):
        out[scope_of(op, names.get(op.name)) if inside
            else "other_programs"] += ns
    return out


def early_route_ns(dev: trace.DeviceTrace, runs: Sequence[trace.Interval],
                   names: Dict[str, str]) -> Optional[int]:
    """Nanoseconds of the step's ops that route from the layer's input
    (module docstring); None where no op of the step names the scope."""
    total, named = 0, False
    for op, inside, ns in _ops_of_the_step(dev, runs):
        if not inside or scope_of(op, names.get(op.name)) != "moe_route":
            continue
        scoped = bool(EARLY.search(names.get(op.name) or ""))
        named = named or scoped
        if scoped or SORT.match(op.name):
            total += ns
    return total if named else None
