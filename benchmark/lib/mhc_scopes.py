"""A traced step of the mhc_mla_moe family split by the program's named
scopes: `benchmark/lib/scopes.py`'s rule with this family's scope list (the
lists of the six earlier scope files are closed and belong to their
runners; the seventh list: ROADMAP D14). The list is `lib/scopes.py`'s
with `mhc` added, so the readers written for that one (`model.mla_ms`,
`model.moe_*`) read this runner's `measured.scopes` by the same names.

Rule: an op belongs to the scope named LAST in its `op_name`, the
innermost: a mixer of the multi-token-prediction module's layer is `mhc`,
not `mtp`; the exit mixer lies outside `head_loss`. Told by their
instruction instead, as there: the flash kernels (`flash`), the grouped
expert products (`ragged-dot-*`: `moe_experts`) and the step's sorts
(`moe_route`). An op of the step with no scope is `rest` (the dense layer's
MLP, layer norms, the embedding: NOT the residual joints, which are the
mixers' here), one with no `op_name` at all `unattributed`, one outside
every run of the step `other_programs`. Every leaf op falls in exactly
one, so the parts sum to the device's busy time.

**The parts of `mhc`** (`mhc_parts_ns`; subsets of `mhc`, not parts beside
it): the ops under `mhc/maps` (the product with W, the scale, the
sigmoids, the clamp), `mhc/sinkhorn` (exp and the rounds), `mhc/pre` (the
read), `mhc/post` (the write) and `mhc/exit`; forward, recompute and
backward together, as every scope's time is. An op whose fusion spans two
of them is named by its root's.
"""

from __future__ import annotations

import re
from typing import Dict, Optional, Sequence

from benchmark.lib import trace
from benchmark.lib.early_scopes import _ops_of_the_step
from benchmark.lib.kernels import FLASH
from benchmark.lib.scopes import RAGGED_DOT, SORT

SCOPES = ("mhc", "mla", "moe_route", "moe_experts", "moe_shared", "mtp",
          "head_loss", "optimizer", "grad_norm")
PARTS = SCOPES + ("flash", "rest", "unattributed", "other_programs")
MHC_PARTS = ("maps", "sinkhorn", "pre", "post", "exit")
_SCOPE = re.compile(r"(?:^|/)(" + "|".join(SCOPES) + r")(?=/|$)")
_MHC_PART = re.compile(r"(?:^|/)mhc/(" + "|".join(MHC_PARTS) + r")(?=/|$)")


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
    for op, inside, ns in _ops_of_the_step(dev, runs):
        out[scope_of(op, names.get(op.name)) if inside
            else "other_programs"] += ns
    return out


def mhc_parts_ns(dev: trace.DeviceTrace, runs: Sequence[trace.Interval],
                 names: Dict[str, str]) -> Dict[str, int]:
    """Nanoseconds of the step's `mhc` ops by the mixer's part (module
    docstring); an `mhc` op that names none of them is `other`."""
    out = dict.fromkeys(MHC_PARTS + ("other",), 0)
    for op, inside, ns in _ops_of_the_step(dev, runs):
        op_name = names.get(op.name)
        if not inside or scope_of(op, op_name) != "mhc":
            continue
        found = _MHC_PART.findall(op_name)
        out[found[-1] if found else "other"] += ns
    return out
