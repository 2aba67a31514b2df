"""A traced step of the dsa_moe family split by the program's named scopes
and its kernels: `benchmark/lib/scopes.py`'s rule with this family's scope
list (ROADMAP D14's further copy of the recipe).

Rule: an op belongs to the scope named LAST in its `op_name`, the
innermost. Told by their instruction instead: this family's own Mosaic
kernels, by the names the program gives them (`dsa_select`: the score of
every causal pair and the top-k threshold, part `dsa_select`; `dsa_flash_fwd`
/ `dsa_flash_bwd_dq` / `dsa_flash_bwd_dkv`: the attention over the chosen
keys, part `dsa_flash`; `dsa_index_loss`: part `dsa_index_loss`), the static
flash kernels if the step ran any (`flash`: it runs none), the grouped
expert products (`ragged-dot-*`: `moe_experts`) and the step's sorts (the
router's top-k, the argsort of the (row, choice) pairs: `moe_route`). The
XLA ops under the scope `dsa_attend` (the backward's `delta`) stay
`dsa_attend`. An op of the step with no scope is `rest` (layer norms,
residual adds, the embedding), one with no `op_name` at all `unattributed`,
one outside every run of the step `other_programs`. Every leaf op falls in
exactly one, so the parts sum to the device's busy time.
"""

from __future__ import annotations

import bisect
import re
from typing import Dict, Optional, Sequence

from benchmark.lib import conv_scopes, trace
from benchmark.lib.flops import roofline_seconds
from benchmark.lib.kernels import FLASH
from benchmark.lib.scopes import RAGGED_DOT, SORT

SCOPES = ("gqa_attn", "dsa_index", "dsa_select", "dsa_attend",
          "dsa_index_loss", "moe_route", "moe_experts", "head_loss",
          "optimizer", "grad_norm")
PARTS = SCOPES + ("dsa_flash", "flash", "rest", "unattributed",
                  "other_programs")
_SCOPE = re.compile(r"(?:^|/)(" + "|".join(SCOPES) + r")(?=/|$)")
# the program's kernels, by the names its calls carry
DSA_SELECT = re.compile(r"^dsa_select")
DSA_FLASH_FORWARD = re.compile(r"^dsa_flash_fwd")
DSA_FLASH_BACKWARD = re.compile(r"^dsa_flash_bwd")
DSA_INDEX_LOSS = re.compile(r"^dsa_index_loss")
KERNEL_PARTS = ((DSA_SELECT, "dsa_select"),
                (DSA_FLASH_FORWARD, "dsa_flash"),
                (DSA_FLASH_BACKWARD, "dsa_flash"),
                (DSA_INDEX_LOSS, "dsa_index_loss"))


def scope_of(op: trace.Event, op_name: Optional[str]) -> str:
    for pattern, part in KERNEL_PARTS:
        if pattern.search(op.name):
            return part
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


def kernel_roofline_pct(m, calls_and_costs):
    """The least time the chip could take for the traced calls over the
    time they took, in percent: `calls_and_costs` is [(pattern, cost of
    ONE unit of work, kernels that share a unit)]; a unit's calls are
    counted from the capture (a recomputed forward is a call: time and
    work both). None where nothing matched or `measured` lacks a peak."""
    if not m.devices or m.peak is None:
        return None
    dev = m.devices[0]
    least = took = 0.0
    for pattern, cost, shared in calls_and_costs:
        calls = dev.select(pattern)
        seconds, _ = roofline_seconds(cost, m.peak.flops_per_s,
                                      m.peak.hbm_bytes_per_s)
        least += seconds * len(calls) / shared
        took += dev.time_ns(calls) / 1e9
    return 100.0 * least / took if took else None


def call_shape(m):
    """(sequences a device, sequence length, bytes an element) of the
    cell's kernels' calls."""
    import jax.numpy as jnp
    w = m.workload
    return (int(w["batch"]) // m.mesh.get("dp", 1), int(w["seqlen"]),
            jnp.dtype(w["dtype"]).itemsize)
