"""A traced step of the loop_llama family split by the program's named scopes:
`benchmark/lib/scopes.py`'s rule with this family's scope list (that
module's tuple is closed and belongs to the `train_scopes` runner).

Rule: an op belongs to the scope named LAST in its `op_name`, the innermost
of this list: `loop_pass` is one pass of the layer pattern (under the scan
of passes one scope covers all R), `dense_ffn` the layers' SwiGLU inside it;
`head_loss` the R exits (the final norm after every pass, the head, the CE,
the weighting; forward, the logits made again in the backward, and
backward), `exit_gate` the gate, `p` and the entropy inside it. Told by
their instruction instead, as there: the flash kernels (`flash`:
`kernels.flash_ms`, never `loop_pass`'s). An op of the step with no scope is
`rest` (the embedding, the casts of the weights hoisted out of the loops),
one with no `op_name` at all `unattributed`, one outside every run of the
step `other_programs`. Every leaf op falls in exactly one, so the parts sum
to the device's busy time.
"""

from __future__ import annotations

import bisect
import re
from typing import Dict, Optional, Sequence

from benchmark.lib import trace
from benchmark.lib.kernels import FLASH
from benchmark.lib.scopes import scope_ms_per_step

SCOPES = ("loop_pass", "dense_ffn", "head_loss", "exit_gate", "optimizer",
          "grad_norm")
PARTS = SCOPES + ("flash", "rest", "unattributed", "other_programs")
_SCOPE = re.compile(r"(?:^|/)(" + "|".join(SCOPES) + r")(?=/|$)")


def scope_of(op: trace.Event, op_name: Optional[str]) -> str:
    if FLASH.search(op.name) or FLASH.search(op.meta):
        return "flash"
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


def parts_ms_per_step(m, parts: Sequence[str]):
    """Chip 0's device milliseconds per traced step in ops of `parts`
    together; None where the runner's `measured` has no such parts (another
    family's runner, a program without the scopes, an untraced run)."""
    split = getattr(m, "scopes", None) or {}
    if any(part not in split for part in parts):
        return None
    return sum(scope_ms_per_step(m, part) for part in parts)
