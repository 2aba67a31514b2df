"""Bench-regression gate: compare a fresh bench record against named
baseline records with per-metric tolerance bands (ISSUE 10).

The repo has a growing perf trajectory (tokens/s, MFU proxy, serving
TTFT/TPOT p95, comm-exposed ms) but until now no automated way to notice
when a PR regresses it — the ROADMAP's "land their numbers before
trusting any speedup claim" caveat in executable form. This gate:

* loads the FRESH record (a `bench.py` stdout JSON line, a
  `runs/rN/bench_*.json` artifact, or a driver-style `{"parsed": ...}`
  wrapper — all three shapes are recognised),
* picks the most recent COMPARABLE baseline among `--baseline` (same
  `unit`, exact `metric`-string match preferred, error records skipped —
  an outage is not a baseline); with none named it passes, saying so,
* checks each metric against its tolerance band in its GOOD direction
  (throughput must not drop, latency/exposed-comm must not grow), and
* exits 0 on pass, **1 on regression**, and 0-with-skip when the fresh
  record is a `backend_unavailable` outage — an environment fact, not a
  regression.

Wired into the staged `runs/` scripts (runs/r13/run_obs.sh) and
preflighted by tests/test_staged_session.py like every other staged
command. One machine-readable JSON line on stdout; human detail on
stderr.

Usage:
    python scripts/check_bench_regression.py --fresh runs/r13/bench_x.json
    python scripts/check_bench_regression.py --fresh new.json \
        --baseline old.json --tol_pct 15
"""

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _forensics():
    """The stdlib obs forensics modules (ISSUE 17), loaded standalone —
    the obs dir on sys.path, never the jax-heavy package import. This is
    how `pick_baseline` shares ONE outage classifier with the run index
    instead of re-implementing it."""
    obs_dir = os.path.join(REPO, "distributed_pytorch_from_scratch_tpu",
                           "obs")
    if obs_dir not in sys.path:
        sys.path.insert(0, obs_dir)
    import rundiff
    import runindex
    return runindex, rundiff

# metric field -> direction ("up" = bigger is better). `value` resolves
# per-unit below. Tolerances are fractions of the baseline.
# "ms" is the reshard record (bench --reshard): its headline value IS a
# wall latency, so `value` gates downward like reshard_ms.
LOWER_BETTER_UNITS = ("ms/step", "ms/step (analytic)", "ms")
THROUGHPUT_FIELDS = ("value", "vs_baseline", "paged_vs_slot",
                     "accepted_tokens_per_dispatch",
                     # serving fleet (ISSUE 19): the fleet headline, the
                     # scalar floor of the per-class SLO table, and the
                     # disagg A/B are all bigger-is-better
                     "fleet_tokens_per_sec", "fleet_slo_attainment_min",
                     "disagg_vs_colocated")
# prefill_ms_per_token (ISSUE 18) is the long-context cp serving number:
# the ring schedule exists to hold it flat-or-better while per-chip KV
# bytes shrink 1/cp, so a record where it GREW vs the trajectory means
# the ring (or its chunking) regressed, whatever tokens/s measured
LATENCY_FIELDS = ("ttft_ms_p95", "tpot_ms_p95", "prefill_ms_per_token",
                  # fleet (ISSUE 19): a grown page-stream tail or router
                  # hop is a regression whatever tokens/s measured
                  "transfer_ms_p95", "dispatch_ms_p95",
                  # reshard (ISSUE 20): elastic-restart downtime is this
                  # wall — a grown reshard is lost serving time
                  "reshard_ms")
# analytic decode-dispatch HBM traffic (ISSUE 14): strictly directional —
# a serving record whose per-step bytes GREW vs the trajectory regressed
# the decode roofline (e.g. the pallas arm silently fell back to gather,
# or the gather view grew — at cp>1 these are PER-CHIP bytes, ~1/cp of
# the cp=1 pool), whatever tokens/s happened to measure
BYTES_FIELDS = ("decode_hbm_bytes_per_step",
                # reshard (ISSUE 20): the minimal-transfer planner's whole
                # point — a record that MOVED more bytes for the same
                # src->dst pair means the plan degraded (e.g. a leaf fell
                # off the copy fast-path), whatever the wall clock did
                "reshard_bytes_moved")
# MEASURED attribution (ISSUE 15): when both records carry a
# measured_vs_analytic reconcile (bench --profile_every / the breakdown
# --capture_profile), the measured per-step device ms and the measured
# collective ms are strictly directional too — up = fail, whatever the
# analytic model claims. Per-phase measured ms are compared dynamically
# below (the phase set depends on what the capture saw).
MEASURED_FIELDS = ("measured_vs_analytic.measured_step_ms",
                   "measured_vs_analytic.comm_ms")


def load_record(path):
    """One bench record from any of the trajectory's on-disk shapes:
    a BENCH_rNN.json wrapper ({"parsed": {...}}), a bare bench JSON
    object, or a text/jsonl artifact whose LAST parseable JSON-object
    line is the record (bench.py prints diagnostics before the line)."""
    text = open(path).read()
    try:
        doc = json.loads(text)
        if isinstance(doc, dict):
            if "parsed" in doc and isinstance(doc["parsed"], dict):
                return doc["parsed"]
            if "metric" in doc or "error" in doc:
                return doc
    except ValueError:
        pass
    rec = None
    for line in text.splitlines():
        line = line.strip()
        if not line or not line.startswith("{"):
            continue
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and ("metric" in obj or "error" in obj):
            rec = obj
    if rec is None:
        raise SystemExit(f"no bench record found in {path} (expected a "
                         f"JSON object with 'metric' or 'error')")
    return rec


def pick_baseline(fresh, paths):
    """Most recent comparable committed record: same `unit`, exact
    `metric` string preferred (later rounds win either way); outage
    records are skipped. Returns (record, path) or (None, None).

    What counts as an outage is decided by `obs/runindex.outage_reason`
    — the SAME classifier the run-archive index uses (ISSUE 17): an
    error record, an rc != 0 wrapper, or a metric-less record can never
    become a baseline, and exactly one piece of code says so."""
    runindex, _ = _forensics()
    best = exact = None
    for p in paths:
        cls = runindex.classify_path(p)
        if cls["outage"] is not None:
            continue  # an outage is not a baseline
        rec = cls["record"]
        if rec.get("unit") != fresh.get("unit"):
            continue
        best = (rec, p)
        if rec.get("metric") == fresh.get("metric"):
            exact = (rec, p)
    return exact or best or (None, None)


def _get(rec, dotted):
    cur = rec
    for part in dotted.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return cur


def metric_checks(fresh, base, tol_pct, tol_latency_pct):
    """Per-metric comparisons for the pair's unit. Each check:
    {field, fresh, base, direction, tol_pct, ok}. A field absent on
    either side is skipped (older trajectory records predate some
    fields) — skipping is visible in the output, never silent."""
    unit = fresh.get("unit", "")
    fields = []
    if unit in LOWER_BETTER_UNITS:
        fields.append(("value", "down", tol_latency_pct))
        fields.append(("attribution.comm.exposed_ms", "down",
                       tol_latency_pct))
        fields.append(("comm.exposed_ms", "down", tol_latency_pct))
        # the reshard record rides this branch (unit "ms"): its
        # dedicated latency/bytes fields still gate directionally
        # (absent fields skip visibly, as everywhere)
        for f in LATENCY_FIELDS:
            fields.append((f, "down", tol_latency_pct))
        for f in BYTES_FIELDS:
            fields.append((f, "down", tol_latency_pct))
    else:
        for f in THROUGHPUT_FIELDS:
            fields.append((f, "up", tol_pct))
        for f in LATENCY_FIELDS:
            fields.append((f, "down", tol_latency_pct))
        for f in BYTES_FIELDS:
            fields.append((f, "down", tol_latency_pct))
    # measured attribution (both units): aggregate measured ms, plus one
    # dynamic check per phase BOTH captures measured — a phase only one
    # side saw is skipped visibly like any absent field
    for f in MEASURED_FIELDS:
        fields.append((f, "down", tol_latency_pct))
    fp = _get(fresh, "measured_vs_analytic.phases")
    bp = _get(base, "measured_vs_analytic.phases")
    if isinstance(fp, dict) and isinstance(bp, dict):
        for phase in sorted(set(fp) & set(bp)):
            fields.append((f"measured_vs_analytic.phases.{phase}",
                           "down", tol_latency_pct))
    checks, skipped = [], []
    for field, direction, tol in fields:
        fv, bv = _get(fresh, field), _get(base, field)
        if not isinstance(fv, (int, float)) or not isinstance(bv,
                                                              (int, float)):
            if fv is not None or bv is not None:
                skipped.append(field)
            continue
        if bv == 0:
            skipped.append(field)
            continue
        if direction == "up":
            ok = fv >= bv * (1.0 - tol / 100.0)
        else:
            ok = fv <= bv * (1.0 + tol / 100.0)
        checks.append({"field": field, "fresh": fv, "base": bv,
                       "direction": direction, "tol_pct": tol, "ok": ok})
    return checks, skipped


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--fresh", required=True,
                   help="the new bench record (bench.py stdout JSON line, "
                        "runs/rN/bench_*.json artifact, or BENCH_rNN.json)")
    p.add_argument("--baseline", nargs="*", default=None,
                   help="baseline record file(s), oldest first; with none "
                        "the gate has nothing to compare with and passes "
                        "with status no_baseline")
    p.add_argument("--controller", action="store_true",
                   help="the obs v5 CONTINUOUS gate: instead of comparing "
                        "against the committed trajectory, gate one "
                        "record's post-decision window against its "
                        "pre-decision window (serve.py --control act "
                        "lands them under rec['controller']['windows']). "
                        "Post must not be worse: tokens/s within "
                        "--tol_pct below pre, p95 latencies within "
                        "--tol_latency_pct above pre. A record with no "
                        "controller, no decisions, or no APPLIED decision "
                        "skips visibly (exit 0)")
    p.add_argument("--tol_pct", type=float, default=10.0,
                   help="throughput tolerance band (%% below baseline "
                        "that still passes)")
    p.add_argument("--tol_latency_pct", type=float, default=25.0,
                   help="latency / exposed-comm tolerance band (%% above "
                        "baseline that still passes)")
    p.add_argument("--explain", action="store_true",
                   help="on regression, attach the obs v6 forensic "
                        "report (config-delta -> phase-delta suspects "
                        "plus the trajectory changepoint for this "
                        "metric's unit) under out['forensics'] and "
                        "render it on stderr — a red gate ships its "
                        "own triage, not a bare exit 1")
    args = p.parse_args(argv)
    if args.controller and args.baseline is not None:
        p.error("--controller gates one record's pre/post windows; "
                "--baseline has no meaning there")
    if args.controller and args.explain:
        p.error("--explain diffs the fresh record against a baseline "
                "record; the controller gate's windows live inside ONE "
                "record — there is no pair to diff")
    return args


def run_controller(args) -> int:
    """Post- vs pre-decision windows of ONE --control act record: the
    controller must not have made the run worse. Skips (visibly, exit 0)
    when there is nothing to gate — gating absence as failure would
    punish runs whose traffic never needed a decision."""
    fresh = load_record(args.fresh)
    out = {"gate": "controller_window", "fresh": args.fresh}

    def skip(reason):
        out.update(status="skip", reason=reason)
        print(json.dumps(out))
        print(f"gate: SKIP — {reason}", file=sys.stderr)
        return 0

    ctl = fresh.get("controller")
    if not isinstance(ctl, dict):
        return skip("record carries no controller summary (--control off "
                    "or a pre-v5 record)")
    if not ctl.get("decisions"):
        return skip("controller made no decisions (traffic never "
                    "triggered a rule)")
    w = ctl.get("windows")
    if not isinstance(w, dict):
        return skip("no decision was APPLIED (advise mode, or act with "
                    "no safe point reached) — no post window exists")
    pre, post = w.get("pre") or {}, w.get("post") or {}
    if not pre.get("completed") or not post.get("completed"):
        return skip("a window has zero completed requests — too little "
                    "traffic on one side of the first actuation")
    fields = [("tokens_per_sec", "up", args.tol_pct),
              ("ttft_ms_p95", "down", args.tol_latency_pct),
              ("tpot_ms_p95", "down", args.tol_latency_pct)]
    checks, skipped = [], []
    for field, direction, tol in fields:
        pv, qv = pre.get(field), post.get(field)
        if not isinstance(pv, (int, float)) \
                or not isinstance(qv, (int, float)) or pv == 0:
            skipped.append(field)
            continue
        if direction == "up":
            ok = qv >= pv * (1.0 - tol / 100.0)
        else:
            ok = qv <= pv * (1.0 + tol / 100.0)
        checks.append({"field": field, "pre": pv, "post": qv,
                       "direction": direction, "tol_pct": tol, "ok": ok})
    regressions = [c for c in checks if not c["ok"]]
    out.update(status="regression" if regressions else "ok",
               decisions=ctl.get("decisions"),
               applied=ctl.get("applied"), checks=checks,
               skipped_fields=skipped)
    print(json.dumps(out))
    for c in checks:
        arrow = {"up": ">=", "down": "<="}[c["direction"]]
        verdict = "ok" if c["ok"] else "REGRESSION"
        print(f"gate: {c['field']}: post {c['post']} {arrow} pre "
              f"{c['pre']} (tol {c['tol_pct']:g}%) — {verdict}",
              file=sys.stderr)
    if skipped:
        print(f"gate: skipped (absent/zero in a window): "
              f"{', '.join(skipped)}", file=sys.stderr)
    if regressions:
        print(f"gate: FAIL — the controller's decisions made "
              f"{len(regressions)} metric(s) worse than the pre-decision "
              f"window", file=sys.stderr)
        return 1
    print(f"gate: PASS — post-decision window holds "
          f"({ctl.get('applied')} applied decision(s))", file=sys.stderr)
    return 0


def build_forensics(fresh, fresh_path, base_path, paths):
    """The obs v6 forensic report a red gate ships with (--explain):
    the baseline->fresh run diff (config delta joined to phase deltas,
    ranked suspects) plus the trajectory changepoint report for this
    metric's unit — so the operator sees not just THAT the gate is red
    but which knob/run moved the metric."""
    runindex, rundiff = _forensics()
    fresh_card = runindex.card_from_bench_path(fresh_path)
    fresh_card["run"] = "fresh"
    base_card = runindex.card_from_bench_path(base_path)
    doc = rundiff.diff_runs(base_card, fresh_card)
    cards = [runindex.card_from_bench_path(p) for p in paths]
    cards.append(fresh_card)
    unit = fresh.get("unit")
    traj = [t for t in rundiff.trajectory_report(cards)
            if t["unit"] == unit]
    return {"diff": doc, "trajectory": traj}


def run(args) -> int:
    fresh = load_record(args.fresh)
    out = {"gate": "bench_regression", "fresh": args.fresh}
    if "error" in fresh:
        if fresh["error"] == "backend_unavailable":
            # an outage is an ENVIRONMENT fact: skip, don't fail — the
            # gate must not turn a missing backend into a fake regression
            out.update(status="skip", reason="backend_unavailable",
                       detail=fresh.get("detail"))
            print(json.dumps(out))
            print(f"gate: SKIP — fresh record is a backend_unavailable "
                  f"outage ({fresh.get('detail')})", file=sys.stderr)
            return 0
        out.update(status="error", reason=fresh["error"],
                   detail=fresh.get("detail"))
        print(json.dumps(out))
        print(f"gate: FAIL — fresh record carries a non-outage error: "
              f"{fresh['error']}", file=sys.stderr)
        return 1
    paths = args.baseline or []
    base, base_path = pick_baseline(fresh, paths)
    if base is None:
        out.update(status="no_baseline", unit=fresh.get("unit"),
                   searched=len(paths))
        print(json.dumps(out))
        print(f"gate: no comparable baseline (unit {fresh.get('unit')!r} "
              f"across {len(paths)} trajectory files) — passing; commit "
              f"this record to start the trajectory", file=sys.stderr)
        return 0
    checks, skipped = metric_checks(fresh, base, args.tol_pct,
                                    args.tol_latency_pct)
    regressions = [c for c in checks if not c["ok"]]
    forensics = None
    if regressions and args.explain:
        forensics = build_forensics(fresh, args.fresh, base_path, paths)
        out["forensics"] = forensics
    out.update(status="regression" if regressions else "ok",
               baseline=base_path, baseline_metric=base.get("metric"),
               checks=checks, skipped_fields=skipped)
    print(json.dumps(out))
    for c in checks:
        arrow = {"up": ">=", "down": "<="}[c["direction"]]
        verdict = "ok" if c["ok"] else "REGRESSION"
        print(f"gate: {c['field']}: fresh {c['fresh']} {arrow} baseline "
              f"{c['base']} (tol {c['tol_pct']:g}%) — {verdict}",
              file=sys.stderr)
    if skipped:
        print(f"gate: skipped (absent on one side): {', '.join(skipped)}",
              file=sys.stderr)
    if regressions:
        print(f"gate: FAIL — {len(regressions)} metric(s) regressed vs "
              f"{base_path}", file=sys.stderr)
        if forensics is not None:
            _, rundiff = _forensics()
            for line in rundiff.format_diff(forensics["diff"]):
                print(f"gate: {line}", file=sys.stderr)
            for line in rundiff.format_trajectory(
                    forensics["trajectory"]):
                print(f"gate: {line}", file=sys.stderr)
        return 1
    print(f"gate: PASS vs {base_path}", file=sys.stderr)
    return 0


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.controller:
        return run_controller(args)
    return run(args)


if __name__ == "__main__":
    sys.exit(main())
