"""Compiled-program introspection: what did XLA actually build?

Run once per program right after compile (zero steady-state cost):
  * `compiled.cost_analysis()`  -> FLOPs + bytes accessed, the ground truth
    to cross-check the hand-rolled `model_flops_per_step` MFU estimate
    against (a 2x disagreement means the MFU number is fiction);
  * `compiled.memory_analysis()` -> peak HBM (arguments + outputs + temps),
    the number that says how close to OOM the config runs;
  * the optimized HLO text -> per-collective comm byte counts (all-reduce /
    all-gather / reduce-scatter / all-to-all / collective-permute), the
    visibility that motivates comm-optimization work (arXiv:2211.05322) and
    quantized-collective accounting (arXiv:2506.17615): you cannot shrink
    traffic you cannot see.

Every probe is best-effort — backends without an analysis return None for
that field rather than failing the run.
"""

from __future__ import annotations

import re
from typing import Dict, Optional

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e5m2": 1, "f8e4m3fn": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

# `%x = f32[8,128]{1,0} all-reduce(...)` / tuple-shaped async starts.
# `-start` variants fold into the base op; `-done` carries no new bytes.
_COLL_RE = re.compile(
    r"=\s+(?P<shape>[^=\n]*?)\s*"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)(?P<start>-start)?\(")

_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([\d,]*)\]")


def _member_bytes(shape: str) -> "list[int]":
    """Bytes of each `dtype[dims]` member in an HLO shape string (unknown
    dtypes count 0)."""
    out = []
    for dtype, dims in _SHAPE_RE.findall(shape):
        size = _DTYPE_BYTES.get(dtype)
        if size is None:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out.append(n * size)
    return out


def _shape_bytes(shape: str) -> int:
    """Total bytes of an HLO shape string (tuples sum their members)."""
    return sum(_member_bytes(shape))


# who talks to whom: `replica_groups={{0,1},{2,3}}` / `[2,2]<=[4]` on the
# reducing/gathering ops, `source_target_pairs={{0,1},{1,0}}` on permutes
_GROUPS_RE = re.compile(
    r"(?:replica_groups|source_target_pairs)=(\S+?)(?:,\s|\s|$)")


def parse_collectives(hlo_text: str) -> Dict[str, dict]:
    """{op_kind: {"count": N, "bytes": output bytes summed, "groups":
    distinct device groupings}} from optimized HLO. Two mesh axes show up
    as two groupings (dp2 x tp2: tp ops over {{0,1},{2,3}}, dp ops over
    {{0,2},{1,3}}). Output-shape bytes are the standard per-hop accounting unit (a
    ring all-reduce moves ~2x this on the wire; the relative picture across
    collectives is what matters). Async `-start` forms carry a
    (operand..., result, context...) tuple shape — only the LARGEST member
    (the result) is counted, so the same logical op reports the same bytes
    whether XLA lowered it sync or async."""
    out: Dict[str, dict] = {}
    for m in _COLL_RE.finditer(hlo_text):
        op = m.group("op")
        rec = out.setdefault(op, {"count": 0, "bytes": 0, "groups": []})
        rec["count"] += 1
        eol = hlo_text.find("\n", m.end())
        g = _GROUPS_RE.search(hlo_text, m.end(),
                              eol if eol >= 0 else len(hlo_text))
        if g and g.group(1) not in rec["groups"]:
            rec["groups"].append(g.group(1))
        members = _member_bytes(m.group("shape"))
        rec["bytes"] += (max(members, default=0) if m.group("start")
                         else sum(members))
    return out


def _cost_dict(compiled) -> Optional[dict]:
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    return dict(ca) if ca else None


def analyze_compiled(compiled) -> dict:
    """Best-effort {flops, bytes_accessed, peak_hbm_bytes, collectives,
    comm_bytes} for one compiled executable."""
    out = {"flops": None, "bytes_accessed": None, "peak_hbm_bytes": None,
           "alias_bytes": None, "collectives": {}, "comm_bytes": 0}
    cost = _cost_dict(compiled)
    if cost:
        flops = cost.get("flops")
        out["flops"] = float(flops) if flops is not None else None
        ba = cost.get("bytes accessed")
        out["bytes_accessed"] = float(ba) if ba is not None else None
    try:
        ma = compiled.memory_analysis()
        out["peak_hbm_bytes"] = int(
            getattr(ma, "argument_size_in_bytes", 0)
            + getattr(ma, "output_size_in_bytes", 0)
            + getattr(ma, "temp_size_in_bytes", 0)
            + getattr(ma, "alias_size_in_bytes", 0))
        # donation hygiene: the bytes the donated params/opt state actually
        # aliased in-place. A train-step program reporting ~0 here means a
        # refactor broke the donation (e.g. a dtype change) and the
        # optimizer state silently doubled its footprint.
        out["alias_bytes"] = int(getattr(ma, "alias_size_in_bytes", 0))
    except Exception:
        pass
    try:
        colls = parse_collectives(compiled.as_text())
        out["collectives"] = colls
        out["comm_bytes"] = sum(c["bytes"] for c in colls.values())
    except Exception:
        pass
    return out


def format_analysis(a: dict, model_flops: Optional[float] = None,
                    steps_in_program: int = 1) -> str:
    """One human line; when `model_flops` (the hand-rolled per-step
    estimate) is given, append the cross-check ratio."""
    gib = 1024 ** 3
    parts = []
    if a.get("flops") is not None:
        parts.append(f"{a['flops'] / 1e9:.2f} GFLOPs/program")
        if model_flops:
            ratio = a["flops"] / max(model_flops * steps_in_program, 1e-9)
            parts.append(f"{ratio:.2f}x the model_flops_per_step estimate")
    if a.get("bytes_accessed") is not None:
        parts.append(f"{a['bytes_accessed'] / gib:.2f} GiB accessed")
    if a.get("peak_hbm_bytes"):
        parts.append(f"peak HBM {a['peak_hbm_bytes'] / gib:.2f} GiB")
    if a.get("alias_bytes") is not None:
        parts.append(f"donated/aliased {a['alias_bytes'] / gib:.2f} GiB "
                     f"in-place")
    if a.get("collectives"):
        comm = ", ".join(
            f"{op} x{c['count']} ({c['bytes'] / 2 ** 20:.1f} MiB)"
            for op, c in sorted(a["collectives"].items()))
        parts.append(f"comm: {comm}")
    return "compiled step: " + ("; ".join(parts) if parts
                                else "no analysis available on this backend")
