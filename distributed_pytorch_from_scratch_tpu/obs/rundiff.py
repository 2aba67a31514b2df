"""Cross-run diff engine + trajectory changepoint triage (ISSUE 17,
obs v6).

`runindex` says what each run IS; this module says what CHANGED between
two of them and — the part a bare config diff can't do — which measured
phase paid for it:

* `config_delta` joins the two RunCards' provenance stamps. A legacy
  side (no fingerprint) is reported loudly as unavailable, never as a
  silent None == None match.
* `phase_deltas` compares per-phase measured ms (the PR 14
  measured/analytic reconciles, falling back to duty-cycle capture
  phases), against a per-phase **noise floor** derived from the variance
  across each card's duty-cycle captures — a delta inside the floor is
  noise, not a finding.
* `suspects` ranks "this knob changed and this phase paid for it":
  every changed knob is joined to its affine phases (KNOB_PHASES);
  significant phase deltas no changed knob claims are reported as
  code/environment suspects (the git_rev delta owns them); changed
  knobs with no measured consequence rank last.
* `collective_diff` / `ledger_diff` / `hbm_delta` cover the graftcheck
  contract inventory, the PR 16 decision ledger, and the HBM watermark.
* the trajectory layer (`changepoint`, `trajectory_report`) generalizes
  the pairwise gate to the full outage-aware trajectory with a stdlib
  CUSUM-style step test that NAMES the run that moved each metric.

Stdlib-only, importable standalone next to runindex/schema.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

try:  # package import (obs consumers) vs obs-dir-on-sys.path (scripts)
    from . import runindex
    from .schema import EVENT_SCHEMA_VERSION
except ImportError:  # pragma: no cover - exercised via scripts
    import runindex
    from schema import EVENT_SCHEMA_VERSION

# knob -> the measured phases it plausibly moves. The join is advisory
# (a suspect, not a verdict): pages_per_block changes the page-copy
# granularity, bucket sizing changes the DP collective schedule, etc.
# Phases use the profparse MEASURED_PHASES taxonomy.
KNOB_PHASES: Dict[str, Tuple[str, ...]] = {
    "pages_per_block": ("copy", "compute"),
    "page_size": ("copy",),
    "paged_attn": ("copy", "compute"),
    "kv_dtype": ("copy", "convert"),
    "decode_weight_dtype": ("convert", "compute"),
    "prefill_chunk": ("host_gap", "compute"),
    "speculate_k": ("compute", "host_gap"),
    "steps_per_dispatch": ("host_gap",),
    "slots": ("host_gap",),
    "max_queue": ("host_gap",),
    "batch": ("compute",),
    "seqlen": ("compute",),
    "remat": ("compute",),
    "dp_reduce_bucket_mb": ("all-reduce", "reduce-scatter",
                            "collective-permute"),
    "dp_reduce_dtype": ("all-reduce", "reduce-scatter",
                        "collective-permute"),
    "zero": ("all-gather", "reduce-scatter"),
    "zero_stage": ("all-gather", "reduce-scatter"),
    "tp_overlap": ("collective-permute", "all-reduce", "all-gather"),
    "sequence_parallel": ("all-gather", "reduce-scatter", "all-reduce"),
}

# a phase delta below this many ms can never be significant, whatever
# the capture variance claims (two captures that happen to agree to a
# microsecond must not produce a zero floor)
MIN_FLOOR_MS = 0.05
# with fewer than 2 captures there is no variance estimate: fall back
# to this fraction of the baseline phase ms
DEFAULT_REL_FLOOR = 0.10


# -------------------------------------------------------------- config delta --

def config_delta(card_a: dict, card_b: dict) -> Dict[str, Any]:
    """Joined config view of two cards. When either side is legacy the
    delta is explicitly unavailable with a note naming the run — the
    diff must never pretend two unknown configs are identical."""
    fp_a = card_a.get("config_fingerprint")
    fp_b = card_b.get("config_fingerprint")
    out: Dict[str, Any] = {"fingerprint_a": fp_a, "fingerprint_b": fp_b,
                           "available": True, "changed": {},
                           "only_a": [], "only_b": [], "notes": []}
    legacy = [c["run"] for c in (card_a, card_b)
              if c.get("config_fingerprint") is None]
    if legacy:
        out["available"] = False
        out["notes"].append(
            f"config delta unavailable: {runindex.LEGACY_NOTE} on "
            f"{', '.join(legacy)}")
        return out
    if fp_a == fp_b:
        out["notes"].append("fingerprints match — same knobs")
        return out
    cfg_a = card_a.get("config") or {}
    cfg_b = card_b.get("config") or {}
    if not cfg_a or not cfg_b:
        out["notes"].append("fingerprints differ but a full config is "
                            "missing — knob-level delta unavailable")
        return out
    for k in sorted(set(cfg_a) | set(cfg_b)):
        if k not in cfg_a:
            out["only_b"].append(k)
        elif k not in cfg_b:
            out["only_a"].append(k)
        elif cfg_a[k] != cfg_b[k]:
            out["changed"][k] = [cfg_a[k], cfg_b[k]]
    return out


# -------------------------------------------------------------- phase deltas --

def _per_step_phases(entry: dict) -> Optional[Dict[str, float]]:
    phases = entry.get("phases")
    if not isinstance(phases, dict) or not phases:
        return None
    steps = entry.get("steps")
    div = float(steps) if isinstance(steps, (int, float)) and steps else 1.0
    return {p: v / div for p, v in phases.items()
            if isinstance(v, (int, float))}


def card_phases(card: dict) -> Optional[Dict[str, float]]:
    """Per-step phase ms for a card: the record's measured/analytic
    reconcile wins (already per-step); else the mean across duty-cycle
    capture events."""
    mva = card.get("measured_vs_analytic")
    if isinstance(mva, dict) and isinstance(mva.get("phases"), dict):
        return {p: v for p, v in mva["phases"].items()
                if isinstance(v, (int, float))}
    samples = [s for s in (_per_step_phases(e)
                           for e in card.get("profile_phases") or [])
               if s]
    if not samples:
        return None
    acc: Dict[str, List[float]] = {}
    for s in samples:
        for p, v in s.items():
            acc.setdefault(p, []).append(v)
    return {p: sum(vs) / len(vs) for p, vs in acc.items()}


def noise_floor(card: dict) -> Dict[str, float]:
    """Per-phase noise floor (ms) = population std across the card's
    duty-cycle captures. Needs >= 2 captures; phases with fewer samples
    get no entry (callers fall back to DEFAULT_REL_FLOOR)."""
    acc: Dict[str, List[float]] = {}
    for entry in card.get("profile_phases") or []:
        s = _per_step_phases(entry)
        if s:
            for p, v in s.items():
                acc.setdefault(p, []).append(v)
    floors = {}
    for p, vs in acc.items():
        if len(vs) >= 2:
            mean = sum(vs) / len(vs)
            floors[p] = max(
                math.sqrt(sum((v - mean) ** 2 for v in vs) / len(vs)),
                MIN_FLOOR_MS)
    return floors


def phase_deltas(card_a: dict, card_b: dict) -> List[Dict[str, Any]]:
    """Per-phase measured deltas b - a with per-phase noise floors.
    Each row: {phase, a_ms, b_ms, delta_ms, delta_pct, floor_ms,
    significant}. Phases only one side measured are listed with
    significant=None — visible, never silently dropped."""
    pa, pb = card_phases(card_a) or {}, card_phases(card_b) or {}
    floors_a, floors_b = noise_floor(card_a), noise_floor(card_b)
    rows = []
    for phase in sorted(set(pa) | set(pb)):
        a, b = pa.get(phase), pb.get(phase)
        if a is None or b is None:
            rows.append({"phase": phase, "a_ms": a, "b_ms": b,
                         "delta_ms": None, "delta_pct": None,
                         "floor_ms": None, "significant": None})
            continue
        floor = max(floors_a.get(phase, 0.0), floors_b.get(phase, 0.0))
        if floor == 0.0:
            floor = max(abs(a) * DEFAULT_REL_FLOOR, MIN_FLOOR_MS)
        delta = b - a
        rows.append({
            "phase": phase,
            "a_ms": round(a, 4), "b_ms": round(b, 4),
            "delta_ms": round(delta, 4),
            "delta_pct": round(100.0 * delta / a, 2) if a else None,
            "floor_ms": round(floor, 4),
            "significant": abs(delta) > floor,
        })
    return rows


# --------------------------------------- collectives / ledger / hbm deltas --

def collective_diff(card_a: dict, card_b: dict) -> Dict[str, Any]:
    """Graftcheck contract inventory diff: which expected_collectives /
    trace contracts flipped, appeared, or vanished between the runs."""
    ca = (card_a.get("collectives") or {}).get("contracts") or {}
    cb = (card_b.get("collectives") or {}).get("contracts") or {}
    if not ca and not cb:
        return {"available": False, "newly_failing": [],
                "newly_passing": [], "added": [], "removed": []}
    return {
        "available": True,
        "newly_failing": sorted(n for n in ca.keys() & cb.keys()
                                if ca[n] and not cb[n]),
        "newly_passing": sorted(n for n in ca.keys() & cb.keys()
                                if not ca[n] and cb[n]),
        "added": sorted(cb.keys() - ca.keys()),
        "removed": sorted(ca.keys() - cb.keys()),
    }


def ledger_diff(card_a: dict, card_b: dict) -> Dict[str, Any]:
    """Decision-ledger delta (PR 16): per-knob decision/applied counts
    on each side — a run whose controller suddenly started actuating a
    knob is itself a forensic lead."""
    ka = (card_a.get("ledger") or {}).get("knobs") or {}
    kb = (card_b.get("ledger") or {}).get("knobs") or {}
    rows = []
    for knob in sorted(set(ka) | set(kb)):
        a, b = ka.get(knob) or {}, kb.get(knob) or {}
        rows.append({"knob": knob,
                     "a": {"count": a.get("count", 0),
                           "applied": a.get("applied", 0),
                           "last": a.get("last")},
                     "b": {"count": b.get("count", 0),
                           "applied": b.get("applied", 0),
                           "last": b.get("last")}})
    return {"decisions_a": (card_a.get("ledger") or {}).get("decisions", 0),
            "decisions_b": (card_b.get("ledger") or {}).get("decisions", 0),
            "knobs": rows}


def hbm_delta(card_a: dict, card_b: dict) -> Optional[Dict[str, Any]]:
    ha, hb = card_a.get("hbm"), card_b.get("hbm")
    if not isinstance(ha, dict) and not isinstance(hb, dict):
        return None
    pa = (ha or {}).get("peak_bytes")
    pb = (hb or {}).get("peak_bytes")
    out = {"a_peak_bytes": pa, "b_peak_bytes": pb, "delta_bytes": None}
    if isinstance(pa, (int, float)) and isinstance(pb, (int, float)):
        out["delta_bytes"] = pb - pa
    return out


# ------------------------------------------------------------------ suspects --

def suspects(cfg_delta: dict, phases: List[Dict[str, Any]],
             card_a: dict, card_b: dict) -> List[Dict[str, Any]]:
    """Ranked "this knob changed and this phase paid for it" list.

    Ranking: knob-claimed significant deltas by |delta| / floor desc,
    then significant deltas no changed knob claims (attributed to the
    code/env delta), then changed knobs with no measured consequence."""
    sig = {r["phase"]: r for r in phases if r.get("significant")}
    changed = cfg_delta.get("changed") or {}
    claimed_phases = set()
    claimed, unclaimed, silent = [], [], []
    for knob, (old, new) in sorted(changed.items()):
        hit = False
        for phase in KNOB_PHASES.get(knob, ()):
            row = sig.get(phase)
            if row is None:
                continue
            hit = True
            claimed_phases.add(phase)
            claimed.append({
                "knob": knob, "old": old, "new": new, "phase": phase,
                "delta_ms": row["delta_ms"],
                "delta_pct": row["delta_pct"],
                "floor_ms": row["floor_ms"],
                "score": round(abs(row["delta_ms"]) /
                               max(row["floor_ms"], MIN_FLOOR_MS), 2),
                "verdict": f"{knob} changed {old!r} -> {new!r} and "
                           f"{phase} paid {row['delta_ms']:+.3f} ms/step",
            })
        if not hit:
            silent.append({
                "knob": knob, "old": old, "new": new, "phase": None,
                "delta_ms": None, "delta_pct": None, "floor_ms": None,
                "score": 0.0,
                "verdict": f"{knob} changed {old!r} -> {new!r} with no "
                           f"measured phase consequence above the noise "
                           f"floor",
            })
    for phase, row in sorted(sig.items()):
        if phase in claimed_phases:
            continue
        rev_a = card_a.get("git_rev") or "?"
        rev_b = card_b.get("git_rev") or "?"
        unclaimed.append({
            "knob": None, "old": None, "new": None, "phase": phase,
            "delta_ms": row["delta_ms"], "delta_pct": row["delta_pct"],
            "floor_ms": row["floor_ms"],
            "score": round(abs(row["delta_ms"]) /
                           max(row["floor_ms"], MIN_FLOOR_MS), 2),
            "verdict": f"{phase} moved {row['delta_ms']:+.3f} ms/step "
                       f"with no changed knob claiming it — code or "
                       f"environment delta (git {rev_a} -> {rev_b})",
        })
    claimed.sort(key=lambda s: -s["score"])
    unclaimed.sort(key=lambda s: -s["score"])
    return claimed + unclaimed + silent


# ------------------------------------------------------------------ diff doc --

def diff_runs(card_a: dict, card_b: dict) -> Dict[str, Any]:
    """The pairwise forensic report: one versioned run_diff document
    joining the config delta to its measured consequences."""
    cfg = config_delta(card_a, card_b)
    phases = phase_deltas(card_a, card_b)
    doc: Dict[str, Any] = {
        "tag": "run_diff",
        "schema_version": EVENT_SCHEMA_VERSION,
        "run_a": card_a.get("run"),
        "run_b": card_b.get("run"),
        "git_rev_a": card_a.get("git_rev"),
        "git_rev_b": card_b.get("git_rev"),
        "outage_a": card_a.get("outage_reason"),
        "outage_b": card_b.get("outage_reason"),
        "config_delta": cfg,
        "metric_deltas": [],
        "phase_deltas": phases,
        "collectives": collective_diff(card_a, card_b),
        "ledger": ledger_diff(card_a, card_b),
        "hbm": hbm_delta(card_a, card_b),
        "suspects": suspects(cfg, phases, card_a, card_b),
        "notes": list(cfg.get("notes") or []),
    }
    ma, mb = card_a.get("metrics") or {}, card_b.get("metrics") or {}
    for f in runindex.HEADLINE_FIELDS:
        if f in ("metric", "unit"):
            continue
        a, b = ma.get(f), mb.get(f)
        if not isinstance(a, (int, float)) or not isinstance(b,
                                                             (int, float)):
            continue
        doc["metric_deltas"].append({
            "field": f, "a": a, "b": b, "delta": round(b - a, 6),
            "delta_pct": round(100.0 * (b - a) / a, 2) if a else None,
        })
    for c in (card_a, card_b):
        if c.get("outage"):
            doc["notes"].append(
                f"{c['run']} is an OUTAGE ({c['outage_reason']}) — its "
                f"side of the diff is whatever the record carried, not a "
                f"trustworthy measurement")
    return doc


def format_diff(doc: dict) -> List[str]:
    """Human rendering of a run_diff doc (obs_diff / --explain stderr)."""
    lines = [f"run diff: {doc['run_a']} -> {doc['run_b']} "
             f"(git {doc.get('git_rev_a') or '?'} -> "
             f"{doc.get('git_rev_b') or '?'})"]
    cfg = doc.get("config_delta") or {}
    if not cfg.get("available"):
        lines.append("  config: (delta unavailable)")
    elif cfg.get("changed"):
        for k, (old, new) in sorted(cfg["changed"].items()):
            lines.append(f"  config: {k}: {old!r} -> {new!r}")
        for side, keys in (("a", cfg.get("only_a")),
                           ("b", cfg.get("only_b"))):
            if keys:
                lines.append(f"  config: only on {side}: "
                             f"{', '.join(keys)}")
    else:
        lines.append("  config: no knob changed")
    for row in doc.get("metric_deltas") or []:
        pct = (f" ({row['delta_pct']:+.1f}%)"
               if row.get("delta_pct") is not None else "")
        lines.append(f"  metric {row['field']}: {row['a']} -> "
                     f"{row['b']}{pct}")
    for row in doc.get("phase_deltas") or []:
        if row.get("significant") is None:
            lines.append(f"  phase {row['phase']}: only one side "
                         f"measured it (a={row['a_ms']}, b={row['b_ms']})")
        elif row["significant"]:
            lines.append(f"  phase {row['phase']}: {row['a_ms']} -> "
                         f"{row['b_ms']} ms/step "
                         f"({row['delta_ms']:+.3f}, floor "
                         f"{row['floor_ms']:.3f})")
    col = doc.get("collectives") or {}
    for key in ("newly_failing", "newly_passing", "added", "removed"):
        if col.get(key):
            lines.append(f"  collectives {key.replace('_', ' ')}: "
                         f"{', '.join(col[key])}")
    hbm = doc.get("hbm")
    if hbm and hbm.get("delta_bytes") is not None:
        lines.append(f"  hbm peak: {hbm['a_peak_bytes']:,} -> "
                     f"{hbm['b_peak_bytes']:,} "
                     f"({hbm['delta_bytes']:+,} bytes)")
    sus = doc.get("suspects") or []
    if sus:
        lines.append("  suspects (ranked):")
        for i, s in enumerate(sus, 1):
            lines.append(f"    {i}. {s['verdict']}")
    else:
        lines.append("  suspects: none — no knob change joined to a "
                     "significant phase delta")
    for note in doc.get("notes") or []:
        lines.append(f"  note: {note}")
    return lines


# ---------------------------------------------------------------- trajectory --

def changepoint(values: Sequence[float], min_seg: int = 2,
                threshold: float = 4.0) -> Optional[Dict[str, Any]]:
    """Single-changepoint step test (stdlib CUSUM flavor): for every
    split k the statistic is |mean_after - mean_before| over the pooled
    std error, with a scale floor so a perfectly flat series can't
    manufacture an infinite score. Returns the best split when it clears
    `threshold`, else None (no detectable step)."""
    vals = [float(v) for v in values]
    n = len(vals)
    if n < 2 * min_seg:
        return None
    best = None
    for k in range(min_seg, n - min_seg + 1):
        a, b = vals[:k], vals[k:]
        ma = sum(a) / len(a)
        mb = sum(b) / len(b)
        pooled = (sum((x - ma) ** 2 for x in a)
                  + sum((x - mb) ** 2 for x in b)) / max(n - 2, 1)
        scale = max(math.sqrt(pooled),
                    0.01 * (abs(ma) + abs(mb)) / 2.0, 1e-9)
        se = scale * math.sqrt(1.0 / len(a) + 1.0 / len(b))
        score = abs(mb - ma) / se
        if best is None or score > best["score"]:
            best = {"index": k, "score": round(score, 2),
                    "before_mean": round(ma, 4),
                    "after_mean": round(mb, 4)}
    if best is None or best["score"] < threshold:
        return None
    best["direction"] = ("up" if best["after_mean"] > best["before_mean"]
                         else "down")
    return best


def trajectory_report(cards: Sequence[dict], threshold: float = 4.0
                      ) -> List[Dict[str, Any]]:
    """Outage-aware trajectory over a card sequence (committed round
    order): outage cards are LISTED but never points — a run that found
    no backend must not read as a throughput collapse. One report
    per metric unit, with the changepoint (if any) naming the run whose
    arrival moved the metric."""
    groups: Dict[str, Dict[str, Any]] = {}
    for card in cards:
        m = card.get("metrics") or {}
        if card.get("outage"):
            unit = m.get("unit") or "(unknown)"
            g = groups.setdefault(unit, {"unit": unit, "metric": None,
                                         "series": [], "outages": []})
            g["outages"].append({"run": card.get("run"),
                                 "reason": card.get("outage_reason")})
            continue
        if not isinstance(m.get("value"), (int, float)):
            continue
        unit = m.get("unit") or "(unknown)"
        g = groups.setdefault(unit, {"unit": unit, "metric": None,
                                     "series": [], "outages": []})
        g["metric"] = g["metric"] or m.get("metric")
        g["series"].append({"run": card.get("run"),
                            "value": m["value"]})
    reports = []
    for unit in sorted(groups):
        g = groups[unit]
        cp = changepoint([pt["value"] for pt in g["series"]],
                         threshold=threshold)
        if cp is not None:
            cp = dict(cp, run=g["series"][cp["index"]]["run"])
        g["changepoint"] = cp
        reports.append(g)
    return reports


def format_trajectory(reports: Sequence[dict]) -> List[str]:
    lines = []
    for g in reports:
        lines.append(f"trajectory [{g['unit']}] "
                     f"{g.get('metric') or ''}".rstrip())
        for pt in g["series"]:
            lines.append(f"  {pt['run']}: {pt['value']:,}")
        for o in g["outages"]:
            lines.append(f"  {o['run']}: outage ({o['reason']}) — "
                         f"excluded from the series")
        cp = g.get("changepoint")
        if cp:
            lines.append(f"  CHANGEPOINT at {cp['run']}: mean "
                         f"{cp['before_mean']:,} -> {cp['after_mean']:,} "
                         f"({cp['direction']}, score {cp['score']})")
        elif len(g["series"]) >= 4:
            lines.append("  no detectable step")
        else:
            lines.append(f"  too few healthy points "
                         f"({len(g['series'])}) for a step test")
    return lines
