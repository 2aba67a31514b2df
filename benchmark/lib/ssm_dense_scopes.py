"""A traced step of the ssm_dense family split by the program's named
scopes: `benchmark/lib/scopes.py`'s rule with this family's scope list (that
module's tuple is closed and belongs to the `train_scopes` runner).

Rule: an op belongs to the scope named LAST in its `op_name`, the innermost
of this list (everything the Mamba-2 mixer does is `mamba`; the attention
layer's projections and `wo` are `gqa_attn`; the SwiGLU of EVERY layer is
`dense_ffn`). Told by their instruction instead, as there: the flash kernels
(`flash`: `kernels.flash_ms`, never `gqa_attn`'s). An op of the step with no
scope is `rest` (the layers' norms, the scaled residual adds, the
embedding's lookup and its multiplier), one with no `op_name` at all
`unattributed`, one outside every run of the step `other_programs`. Every
leaf op falls in exactly one, so the parts sum to the device's busy time.

The mixer's time by inner scope (`mamba/in_proj`, `conv`, `ssd`,
`gate_norm`, `out_proj`) is `benchmark/lib/ssm_scopes.mamba_parts_ns`, the
same program scopes as the ssm_moe family's; `mamba_part_ms_per_step` reads
one or several of them for this family's readers.
"""

from __future__ import annotations

import re
from typing import Dict, Optional, Sequence

from benchmark.lib import trace
from benchmark.lib.kernels import FLASH
from benchmark.lib.ssm_scopes import _ns_by

SCOPES = ("mamba", "gqa_attn", "dense_ffn", "head_loss", "optimizer",
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
    return _ns_by(dev, runs, names, PARTS, scope_of, "other_programs")


def mamba_part_ms_per_step(m, *inner: str):
    """Chip 0's device milliseconds per traced step in the mixer's inner
    scopes `inner` (names of `ssm_scopes.INNER`) together; None where the
    runner's `measured` carries no such split (another family's runner, a
    program without the family, an untraced run)."""
    parts = getattr(m, "mamba_parts", None)
    if not parts or not getattr(m, "devices", None):
        return None
    return (sum(parts[f"mamba/{name}"] for name in inner)
            / m.devices[0].steps / 1e6)
