"""A traced step of the kda_mla_moe family split by the program's named
scopes: `benchmark/lib/scopes.py`'s rule with this family's scope list (that
module's tuple is closed and belongs to the `train_scopes` runner).

Rule: an op belongs to the scope named LAST in its `op_name`, the innermost
of this list (the chunked rule inside the delta mixer is `kda_rule`, the
projections, convolutions, gate, beta, output norm and `W_o` around it
`kda`; the latent layer's projections, gate and `wo` are `mla`, the
multi-token-prediction module's own projection, head and CE `mtp`). Told by
their instruction instead, as there: the flash kernels (`flash`:
`kernels.flash_ms`, never `mla`'s), the grouped expert products
(`ragged-dot-*`: `moe_experts`) and the step's sorts (`moe_route`). An op
of the step with no scope is `rest` (layer norms, residual adds, the
embedding), one with no `op_name` at all `unattributed`, one outside every
run of the step `other_programs`. Every leaf op falls in exactly one, so the
parts sum to the device's busy time.

`kda_parts_ns` splits the two delta scopes further by the inner scopes the
program names (`kda/gate`; `kda_rule/operands`, `kda_rule/walk`), for the
breakdown: where inside the rule the time goes.
"""

from __future__ import annotations

import bisect
import re
from typing import Dict, Optional, Sequence

from benchmark.lib import trace
from benchmark.lib.kernels import FLASH
from benchmark.lib.scopes import RAGGED_DOT, SORT

SCOPES = ("kda", "kda_rule", "mla", "dense_ffn", "moe_route", "moe_experts",
          "moe_shared", "mtp", "head_loss", "optimizer", "grad_norm")
PARTS = SCOPES + ("flash", "rest", "unattributed", "other_programs")
_SCOPE = re.compile(r"(?:^|/)(" + "|".join(SCOPES) + r")(?=/|$)")
# the inner scopes of the two delta scopes, innermost last
KDA_PARTS = ("kda/gate", "kda/other", "kda_rule/operands", "kda_rule/walk",
             "kda_rule/other")
_INNER = re.compile(r"(?:^|/)(gate|operands|walk)(?=/|$)")


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


def kda_part_of(op: trace.Event, op_name: Optional[str]) -> Optional[str]:
    """Which of `KDA_PARTS` an op of a delta scope belongs to; None for an
    op of another scope."""
    scope = scope_of(op, op_name)
    if scope not in ("kda", "kda_rule"):
        return None
    inner = _INNER.findall(op_name.rsplit(scope, 1)[1])
    allowed = ("gate",) if scope == "kda" else ("operands", "walk")
    inner = [i for i in inner if i in allowed]
    return f"{scope}/{inner[-1] if inner else 'other'}"


def _ns_by(dev: trace.DeviceTrace, runs: Sequence[trace.Interval],
           names: Dict[str, str], parts, part_of, outside) -> Dict[str, int]:
    out = dict.fromkeys(parts, 0)
    starts = [a for a, _ in runs]
    lo, hi = dev.window
    for op in dev.ops:
        i = bisect.bisect_right(starts, op.start_ns) - 1
        inside = i >= 0 and op.start_ns < runs[i][1]
        part = part_of(op, names.get(op.name)) if inside else outside
        if part is not None:
            out[part] += max(min(op.end_ns, hi) - max(op.start_ns, lo), 0)
    return out


def scope_ns(dev: trace.DeviceTrace, runs: Sequence[trace.Interval],
             names: Dict[str, str]) -> Dict[str, int]:
    """Nanoseconds of `dev`'s leaf ops in each part, clipped to its window.
    `runs` are the step program's executions (a union: sorted, disjoint)."""
    return _ns_by(dev, runs, names, PARTS, scope_of, "other_programs")


def kda_parts_ns(dev: trace.DeviceTrace, runs: Sequence[trace.Interval],
                 names: Dict[str, str]) -> Dict[str, int]:
    """The two delta scopes' nanoseconds by inner scope (`KDA_PARTS`)."""
    return _ns_by(dev, runs, names, KDA_PARTS, kda_part_of, None)
