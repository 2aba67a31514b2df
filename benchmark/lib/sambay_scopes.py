"""A traced step of the sambay family split by the program's named scopes:
`benchmark/lib/scopes.py`'s rule with this family's scope list (that module's
tuple is closed and belongs to the `train_scopes` runner), and the readers of
this family's per-layer metrics.

Rule: an op belongs to the scope named LAST in its `op_name`, the innermost
of this list (everything a Mamba-1 mixer does is `mamba1`; a `swa` or `full`
layer's projections, lambda, norm and `W_o` are `diff_attn`, a `cross`
layer's `cross_attn`; a gated memory unit `gmu`; the SwiGLU of EVERY layer
`dense_ffn`). Told by their instruction instead, as there: the flash kernels
(`flash`: `kernels.flash_ms`, never an attention scope's). An op of the step
with no scope is `rest` (the layers' LayerNorms, the residual adds, the
embedding's lookup, the sums of the shared values' cotangents), one with no
`op_name` at all `unattributed`, one outside every run of the step
`other_programs`. Every leaf op falls in exactly one, so the parts sum to
the device's busy time.

`mamba1_parts_ns` splits the mixer's scope by the inner scopes the program
names (`mamba1/in_proj`, `conv`, `x_proj`, `dt_proj`, `sscan`, `gate`,
`out_proj`): `mamba1/sscan` is `model.sscan_ms`'s (the scan's kernels, or
its XLA text, with the softplus before it and `D u` behind it).

A window layer's flash calls carry `_window` in the kernel's name, as
`benchmark/lib/swa_scopes.py` reads them.
"""

from __future__ import annotations

import re
from typing import Dict, Optional, Sequence

from benchmark.lib import trace
from benchmark.lib.kernels import FLASH
from benchmark.lib.ssm_scopes import _ns_by
from benchmark.lib.swa_scopes import flash_calls

SCOPES = ("mamba1", "diff_attn", "cross_attn", "gmu", "dense_ffn",
          "head_loss", "optimizer", "grad_norm")
PARTS = SCOPES + ("flash", "rest", "unattributed", "other_programs")
_SCOPE = re.compile(r"(?:^|/)(" + "|".join(SCOPES) + r")(?=/|$)")
INNER = ("in_proj", "conv", "x_proj", "dt_proj", "sscan", "gate", "out_proj")
MAMBA1_PARTS = tuple(f"mamba1/{name}" for name in INNER + ("other",))
_INNER = re.compile(r"(?:^|/)(" + "|".join(INNER) + r")(?=/|$)")


def scope_of(op: trace.Event, op_name: Optional[str]) -> str:
    if FLASH.search(op.name) or FLASH.search(op.meta):
        return "flash"
    if not op_name:
        return "unattributed"
    found = _SCOPE.findall(op_name)
    return found[-1] if found else "rest"


def mamba1_part_of(op: trace.Event, op_name: Optional[str]) -> Optional[str]:
    """Which of `MAMBA1_PARTS` an op of the mixer's scope belongs to; None
    for an op of another scope."""
    if scope_of(op, op_name) != "mamba1":
        return None
    inner = _INNER.findall(op_name.rsplit("mamba1", 1)[1])
    return f"mamba1/{inner[-1] if inner else 'other'}"


def scope_ns(dev: trace.DeviceTrace, runs: Sequence[trace.Interval],
             names: Dict[str, str]) -> Dict[str, int]:
    """Nanoseconds of `dev`'s leaf ops in each part, clipped to its window.
    `runs` are the step program's executions (a union: sorted, disjoint)."""
    return _ns_by(dev, runs, names, PARTS, scope_of, "other_programs")


def mamba1_parts_ns(dev: trace.DeviceTrace, runs: Sequence[trace.Interval],
                    names: Dict[str, str]) -> Dict[str, int]:
    """The mixer's nanoseconds by inner scope (`MAMBA1_PARTS`)."""
    return _ns_by(dev, runs, names, MAMBA1_PARTS, mamba1_part_of, None)


# ---- what the per-layer metrics read (None where there is nothing) ----

def scope_ms_per_step(m, part: str):
    """Chip 0's device milliseconds per traced step in `part` of this
    family's split; None where the runner's `measured` carries no such part
    (another family's runner, a program without the family, an untraced
    run)."""
    parts = getattr(m, "scopes", None)
    if not parts or part not in parts or not getattr(m, "devices", None):
        return None
    return parts[part] / m.devices[0].steps / 1e6


def sscan_ms_per_step(m):
    """Chip 0's device milliseconds per traced step in `mamba1/sscan`."""
    parts = getattr(m, "mamba1_parts", None)
    if not parts or not getattr(m, "devices", None):
        return None
    return parts["mamba1/sscan"] / m.devices[0].steps / 1e6


def sscan_roofline_pct(m):
    """Share of its roofline the selective scan reaches: the least time the
    chip could take for the step's scans (per Mamba layer the larger of its
    HBM floor and its vector-unit floor, `sambay_counts.sscan_floor_seconds`
    of `measured.sscan_cost`) over `model.sscan_ms`. Recompute under remat
    is time and not work, so it lowers the share."""
    from benchmark.lib.sambay_counts import sscan_floor_seconds
    took_ms = sscan_ms_per_step(m)
    cost = getattr(m, "sscan_cost", None)
    if not took_ms or cost is None or getattr(m, "peak", None) is None:
        return None
    return (100.0 * m.sizes.n_mamba_layer * sscan_floor_seconds(cost, m.peak)
            / (took_ms / 1e3))


def diff_flash_roofline_pct(m):
    """Share of their roofline the differential layers' flash calls reach,
    window and full calls together: the least time the chip could take for
    the traced calls at each kind's LIVE entries, keys 64 and values 128
    wide (`sambay_counts.diff_flash_call_cost`), over the time they took.
    The recomputed forward's second call is counted as a call; a split
    backward is two kernels for one backward's work."""
    s = getattr(m, "sizes", None)
    if (not getattr(m, "devices", None) or getattr(m, "peak", None) is None
            or not hasattr(s, "swa_window")):
        return None
    import jax.numpy as jnp
    from benchmark.lib.flops import roofline_seconds
    from benchmark.lib.sambay_counts import diff_flash_call_cost
    dev, w = m.devices[0], m.workload
    batch = int(w["batch"]) // m.mesh.get("dp", 1)
    itemsize = jnp.dtype(w["dtype"]).itemsize
    least = took = 0.0
    for window in (True, False):
        for backward in (False, True):
            calls = flash_calls(dev, backward, window)
            seconds, _ = roofline_seconds(
                diff_flash_call_cost(batch, int(w["seqlen"]), s, itemsize,
                                     backward,
                                     s.swa_window if window else None),
                m.peak.flops_per_s, m.peak.hbm_bytes_per_s)
            names = {c.name.split(".")[0] for c in calls}
            per_backward = len(names) if backward and len(names) > 1 else 1
            least += seconds * len(calls) / per_backward
            took += dev.time_ns(calls) / 1e9
    return 100.0 * least / took if took else None
