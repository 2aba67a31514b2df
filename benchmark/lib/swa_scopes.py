"""A traced step of the swa_moe family split by the program's named scopes:
`benchmark/lib/scopes.py`'s rule with this family's scope list (the lists of
`lib/scopes.py`, `lib/hybrid_scopes.py`, `lib/conv_scopes.py` and
`lib/bd_scopes.py` are closed and belong to their runners; the fifth list:
ROADMAP D14), and which flash calls are a window layer's.

Rule: an op belongs to the scope named LAST in its `op_name`, the
innermost (the selection bias's rule is `router_bias`, inside `optimizer`).
Told by their instruction instead, as there: the flash kernels (`flash`:
`kernels.flash_ms`, never `gqa_attn`'s), the grouped expert products
(`ragged-dot-*`: `moe_experts`) and the step's sorts (the router's top-k,
the argsort of the (token, choice) pairs: `moe_route`). An op of the step
with no scope is `rest` (layer norms, residual adds, the embedding), one
with no `op_name` at all `unattributed`, one outside every run of the step
`other_programs`. Every leaf op falls in exactly one, so the parts sum to
the device's busy time.

A window layer's flash calls carry `_window` in the kernel's name
(`ops/pallas/flash_attention._call_name`: `flash_fwd_window`,
`flash_bwd_window`, and `flash_bwd_dq_window` / `flash_bwd_dkv_window` where
the backward is split); a full layer's are the names every other cell's
have. A program without the window path names none so, and `window_calls`
finds nothing.
"""

from __future__ import annotations

import bisect
import re
from typing import Dict, List, Optional, Sequence

from benchmark.lib import trace
from benchmark.lib.kernels import FLASH, FLASH_BACKWARD, FLASH_FORWARD
from benchmark.lib.scopes import RAGGED_DOT, SORT

SCOPES = ("gqa_attn", "dense_ffn", "moe_route", "moe_experts", "moe_shared",
          "head_loss", "router_bias", "optimizer", "grad_norm")
PARTS = SCOPES + ("flash", "rest", "unattributed", "other_programs")
_SCOPE = re.compile(r"(?:^|/)(" + "|".join(SCOPES) + r")(?=/|$)")
WINDOW_CALL = re.compile(r"^flash_(?:fwd|bwd)(?:_dq|_dkv)?_window")


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


def flash_calls(dev: trace.DeviceTrace, backward: bool, window: bool
                ) -> List[trace.Event]:
    """Chip `dev`'s flash calls, forward or backward, of the window layers
    or of the full ones."""
    calls = dev.select(FLASH_BACKWARD if backward else FLASH_FORWARD)
    return [c for c in calls if bool(WINDOW_CALL.search(c.name)) == window]


def flash_roofline_pct(m, window: bool):
    """Share of its roofline the window layers' flash calls reach, or the
    full layers': the least time the chip could take for the traced calls
    at the kind's LIVE entries (benchmark/lib/swa_moe_counts.flash_call_cost)
    over the time they took. The recomputed forward's second call is counted
    as a call; a split backward is two kernels for one backward's work.
    None where the runner's sizes are another family's, or no such call was
    traced."""
    s = m.sizes
    if not m.devices or m.peak is None or not hasattr(s, "window"):
        return None
    import jax.numpy as jnp
    from benchmark.lib.flops import roofline_seconds
    from benchmark.lib.swa_moe_counts import flash_call_cost
    dev, w = m.devices[0], m.workload
    batch = int(w["batch"]) // m.mesh.get("dp", 1)
    itemsize = jnp.dtype(w["dtype"]).itemsize
    least = took = 0.0
    for backward in (False, True):
        calls = flash_calls(dev, backward, window)
        seconds, _ = roofline_seconds(
            flash_call_cost(batch, int(w["seqlen"]), s, itemsize, backward,
                            s.window if window else None),
            m.peak.flops_per_s, m.peak.hbm_bytes_per_s)
        names = {c.name.split(".")[0] for c in calls}
        per_backward = len(names) if backward and len(names) > 1 else 1
        least += seconds * len(calls) / per_backward
        took += dev.time_ns(calls) / 1e9
    return 100.0 * least / took if took else None
