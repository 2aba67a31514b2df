"""A traced step of the bd_moe family split by the program's named scopes:
`benchmark/lib/scopes.py`'s rule with this family's scope list (that
module's tuple, `lib/hybrid_scopes.py`'s and `lib/conv_scopes.py`'s are
closed and belong to their runners; the fourth list, and ROADMAP D14's
fifth copy of the recipe).

Rule: an op belongs to the scope named LAST in its `op_name`, the
innermost. Told by their instruction instead, as there: the flash kernels
(`flash`: `kernels.flash_ms`, never `gqa_attn`'s), the grouped expert
products (`ragged-dot-*`: `moe_experts`) and the step's sorts (the router's
top-k, the argsort of the (row, choice) pairs: `moe_route`). An op of the
step with no scope is `rest` (layer norms, residual adds, the embedding),
one with no `op_name` at all `unattributed`, one outside every run of the
step `other_programs`. Every leaf op falls in exactly one, so the parts sum
to the device's busy time.
"""

from __future__ import annotations

import bisect
import re
from typing import Dict, Optional, Sequence

from benchmark.lib import conv_scopes, trace
from benchmark.lib.kernels import FLASH
from benchmark.lib.scopes import RAGGED_DOT, SORT

SCOPES = ("bd_noise", "gqa_attn", "moe_route", "moe_experts", "head_loss",
          "optimizer", "grad_norm")
PARTS = SCOPES + ("flash", "rest", "unattributed", "other_programs")
_SCOPE = re.compile(r"(?:^|/)(" + "|".join(SCOPES) + r")(?=/|$)")


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
    """`conv_scopes.scope_ns` with this family's parts."""
    out = dict.fromkeys(PARTS, 0)
    starts = [a for a, _ in runs]
    lo, hi = dev.window
    for op in dev.ops:
        i = bisect.bisect_right(starts, op.start_ns) - 1
        inside = i >= 0 and op.start_ns < runs[i][1]
        part = scope_of(op, names.get(op.name)) if inside else "other_programs"
        out[part] += max(min(op.end_ns, hi) - max(op.start_ns, lo), 0)
    return out


# a part only this family's split has: None where `measured` has no such part
own_scope_ms_per_step = conv_scopes.own_scope_ms_per_step
