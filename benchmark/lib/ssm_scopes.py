"""A traced step of the ssm_moe family split by the program's named scopes:
`benchmark/lib/scopes.py`'s rule with this family's scope list (that
module's tuple is closed and belongs to the `train_scopes` runner).

Rule: an op belongs to the scope named LAST in its `op_name`, the innermost
of this list (everything the Mamba-2 mixer does is `mamba`; the attention
layer's projections and `wo` are `gqa_attn`; the two latent projections
around the dispatch `moe_latent`; the multi-token-prediction module's own
projection, head and CE `mtp`). Told by their instruction instead, as
there: the flash kernels (`flash`: `kernels.flash_ms`, never `gqa_attn`'s),
the grouped expert products (`ragged-dot-*`: `moe_experts`) and the step's
sorts (`moe_route`). An op of the step with no scope is `rest` (layer norms,
residual adds, the embedding), one with no `op_name` at all `unattributed`,
one outside every run of the step `other_programs`. Every leaf op falls in
exactly one, so the parts sum to the device's busy time.

`mamba_parts_ns` splits the mixer's scope by the inner scopes the program
names (`mamba/in_proj`, `conv`, `ssd`, `gate_norm`, `out_proj`), for
`model.ssd_ms` and the breakdown: where inside the mixer the time goes.
"""

from __future__ import annotations

import bisect
import re
from typing import Dict, Optional, Sequence

from benchmark.lib import trace
from benchmark.lib.kernels import FLASH
from benchmark.lib.scopes import RAGGED_DOT, SORT

SCOPES = ("mamba", "gqa_attn", "moe_latent", "moe_route", "moe_experts",
          "moe_shared", "mtp", "head_loss", "optimizer", "grad_norm")
PARTS = SCOPES + ("flash", "rest", "unattributed", "other_programs")
_SCOPE = re.compile(r"(?:^|/)(" + "|".join(SCOPES) + r")(?=/|$)")
INNER = ("in_proj", "conv", "ssd", "gate_norm", "out_proj")
MAMBA_PARTS = tuple(f"mamba/{name}" for name in INNER + ("other",))
_INNER = re.compile(r"(?:^|/)(" + "|".join(INNER) + r")(?=/|$)")


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


def mamba_part_of(op: trace.Event, op_name: Optional[str]) -> Optional[str]:
    """Which of `MAMBA_PARTS` an op of the mixer's scope belongs to; None
    for an op of another scope."""
    if scope_of(op, op_name) != "mamba":
        return None
    inner = _INNER.findall(op_name.rsplit("mamba", 1)[1])
    return f"mamba/{inner[-1] if inner else 'other'}"


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


def mamba_parts_ns(dev: trace.DeviceTrace, runs: Sequence[trace.Interval],
                   names: Dict[str, str]) -> Dict[str, int]:
    """The mixer's nanoseconds by inner scope (`MAMBA_PARTS`)."""
    return _ns_by(dev, runs, names, MAMBA_PARTS, mamba_part_of, None)


def ssd_ms_per_step(m):
    """Chip 0's device milliseconds per traced step in `mamba/ssd`; None
    where the runner's `measured` carries no such split (another family's
    runner, a program without the family, an untraced run)."""
    parts = getattr(m, "mamba_parts", None)
    if not parts or not m.devices:
        return None
    return parts["mamba/ssd"] / m.devices[0].steps / 1e6
