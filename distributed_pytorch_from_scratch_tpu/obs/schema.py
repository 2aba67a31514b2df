"""MetricsWriter event-schema versioning + jsonl validation (ISSUE 10).

Every structured `MetricsWriter.event(...)` record now carries a
`schema_version` field, and this module is the one place that says what a
consumer may rely on: `EVENT_REQUIRED` maps each event tag to the fields
`scripts/summarize_run.py` and `scripts/check_bench_regression.py` key on.
Consumers call `validate_jsonl` BEFORE rendering, so a drifted producer
(a renamed field, a tag emitted without its contract) fails LOUDLY in the
summary instead of silently dropping a section — the exact rot mode the
r4/r5 post-mortems hit with regexes over free-form logs.

Deliberately dependency-free (no jax, no package imports): the validators
must be importable from standalone scripts and from `training/metrics.py`
without creating an import cycle.
"""

from __future__ import annotations

import json
from typing import Dict, List, Tuple

# Bump when an event's field contract changes incompatibly. Version 2 =
# the ISSUE-10 schema: versioned events + the request-trace/flight/skew
# event family. Version 3 = the ISSUE-12 live-telemetry family
# (telemetry_snapshot / fleet_rollup / rotated continuations) plus the
# cross-process request_trace fields (process, t0_wall, clock_offset_ms).
# Version 4 = the ISSUE-15 measured-attribution family
# (profile_attribution / hbm_watermark).
# Version 5 = the ISSUE-16 control-plane family: the decision ledger
# (tuning_decision / controller_decision) every --control advise/act
# actuation lands in.
# Version 6 = the ISSUE-17 run-forensics family: run_card (the archive
# index's normalized per-run summary) and run_diff (the pairwise
# forensic report obs_diff / check_bench_regression --explain emit).
# Version 7 = the ISSUE-20 elastic-reshard family: reshard_event (one
# any-layout->any-layout redistribution — elastic resume, fleet replica
# restart at a new width, or the offline CLI — with its plan summary).
# (Version 1 is retroactively "any pre-versioned event".)
EVENT_SCHEMA_VERSION = 7

# tag -> fields a consumer may key on (presence contract, not types).
# Only EVENT tags appear here — scalar ({"tag", "value", "step"}) and text
# records are TensorBoard-shaped and stay unversioned.
EVENT_REQUIRED: Dict[str, Tuple[str, ...]] = {
    "goodput_summary": ("wall_s", "buckets_s", "goodput", "steps"),
    "cost_analysis": ("flops",),
    "serving_summary": ("requests", "completed", "tokens_per_sec"),
    "paged_kv_stats": ("page_size", "num_pages", "kv_util_mean"),
    "spec_decode_stats": ("speculate_k", "spec_rounds"),
    "serve_request": ("rid", "generated"),
    # -- ISSUE 10: the request-scoped / rank-scoped family ---------------
    "request_trace": ("rid", "trace_id", "spans", "total_ms"),
    "request_exemplars": ("k", "worst_ttft", "worst_tpot"),
    "rank_phase_stats": ("process", "phases_s", "steps"),
    "sentinel/nonfinite": ("reason",),
    "watchdog/stall": ("process", "stalled_for"),
    # -- ISSUE 12: the live-telemetry family -----------------------------
    # periodic exporter registry mirror (obs/telemetry.py); the fleet
    # collector keys on both maps and the producing process index
    "telemetry_snapshot": ("gauges", "counters", "process"),
    # fleet-level aggregation (obs/collector.py); consumers key on the
    # proc count and the cross-proc attainment map
    "fleet_rollup": ("procs", "slo_attainment"),
    # size-based MetricsWriter rotation: the LAST record of a rotated-out
    # file names its continuation; tailers follow `next`
    "rotated": ("next",),
    # -- ISSUE 15: the measured-attribution family -----------------------
    # one parsed jax.profiler capture (training/metrics.py sampler paths
    # via obs/profparse): consumers key on the capture dir, what armed it
    # (duty / anomaly:<tag> / breakdown), and the measured phase-ms map
    # (empty + `error` when the capture failed to parse — still an event,
    # never a silent drop)
    "profile_attribution": ("capture", "trigger", "phases"),
    # live HBM watermark snapshot: `devices` is the per-device
    # memory_stats list, EMPTY with available=false on a statless
    # backend — the silent-zero fix exports 'unavailable' loudly instead
    # of a fake 0-byte watermark
    "hbm_watermark": ("devices", "available"),
    # -- ISSUE 16: the control-plane / decision-ledger family ------------
    # one RetuneAdvisor proposal (obs/control.py): which knob, old->new,
    # the evidence that justified it (per-phase drift ms, HBM headroom,
    # capture id), whether the run was allowed to act on it, and whether
    # it actually did (applied=false under --control advise)
    "tuning_decision": ("knob", "old", "new", "evidence", "mode",
                        "applied"),
    # one online SLO/admission adaptation (serving/controller.py):
    # cross-linked to the telemetry snapshot that triggered it via
    # `snapshot_seq`, so the ledger can replay trigger -> action
    "controller_decision": ("knob", "old", "new", "trigger", "mode",
                            "applied", "snapshot_seq"),
    # -- ISSUE 17: the run-forensics family ------------------------------
    # one normalized run from the archive index (obs/runindex.py):
    # consumers key on which run it is, what shape it came from
    # (bench / multichip / session), and the outage classification —
    # `outage` true means the card can NEVER be a baseline, and
    # baseline_eligible makes that machine-checkable
    "run_card": ("run", "kind", "outage", "baseline_eligible"),
    # one pairwise forensic report (obs/rundiff.py): the config delta
    # joined to its measured consequences, with the ranked suspects list
    "run_diff": ("run_a", "run_b", "config_delta", "suspects"),
    # -- ISSUE 20: the elastic-reshard family ----------------------------
    # one layout redistribution (reshard/): the source and target layout
    # signatures, the bytes the plan actually moved, the per-op schedule
    # counts, and the wall time — forensics joins this into run lineage
    # ("this run's params came from THAT layout")
    "reshard_event": ("src_layout", "dst_layout", "bytes_moved",
                      "plan_ops", "wall_ms"),
    # -- ISSUE 33: an expert model's step counters at the log interval
    # (training/metrics.moe_counters_summary): the held experts' load as
    # max over mean, and the rows held here per token and expert layer
    # beside the rows the grouped products' groups covered (ISSUE 47) and
    # the rows of the chunks the dispatch walked (ISSUE 50), and the
    # windows `sum_held` took a token block of a live chunk (ISSUE 65)
    "moe_counters": ("load_max_over_mean", "rows_here_per_token",
                     "rows_computed_per_token", "rows_walked_per_token",
                     "sum_windows_per_block"),
    # -- ISSUE 66: the counters of a stack passed R times a step at the log
    # interval (training/metrics.loop_counters_summary): the objective, the
    # exit distribution's mean entropy and the mean exit step; beside them
    # `loss_exit_<r>` and `exit_p_<r>`, a pair a pass
    "loop_counters": ("loss_main", "exit_entropy", "exit_step_mean"),
    # -- ISSUE 68: the counters of a dense family whose mixers count, at the
    # log interval (training/metrics.mixer_counters_summary); beside these
    # what the family's mixers count (ISSUE 76: `sscan_decay_min`,
    # `diff_lambda`, `memory_rms`, `shared_kv_readers`; `ssm_decay_min`)
    "mixer_counters": ("loss_main", "resid_rms_last"),
    # -- ISSUE 72: the counters of layers that choose their keys, at the log
    # interval (training/metrics.dsa_counters_summary), beside `moe_counters`
    "dsa_counters": ("kept_share", "index_kl", "index_entropy", "tau_ties"),
    # -- ISSUE 37: `train()`'s step function built again after its steady
    # program was in hand (a tail window, a new sequence bucket), at `step`;
    # beside these `backend_compile_s` (compiled) or `cache_load_s` (`hit`)
    "recompile": ("fun", "trace_s", "lower_s", "hit"),
}


def is_event_record(rec: dict) -> bool:
    """Structured event vs a scalar/text record: events have a tag but
    neither a scalar `value` nor a `text` payload."""
    return ("tag" in rec and "value" not in rec and "text" not in rec)


def validate_record(rec: dict) -> List[str]:
    """Problems with one parsed record (empty list = fine). Scalar/text
    records always pass; unknown event tags only need a sane version."""
    if not isinstance(rec, dict):
        return ["record is not a JSON object"]
    if "tag" not in rec:
        return ["record has no 'tag'"]
    if not is_event_record(rec):
        return []
    tag = rec["tag"]
    problems = []
    v = rec.get("schema_version")
    if v is None:
        problems.append(f"{tag}: missing schema_version (pre-v"
                        f"{EVENT_SCHEMA_VERSION} writer? regenerate, or "
                        f"treat fields as best-effort)")
    elif not isinstance(v, int) or v < 1:
        problems.append(f"{tag}: schema_version {v!r} is not a positive int")
    elif v > EVENT_SCHEMA_VERSION:
        problems.append(f"{tag}: schema_version {v} is NEWER than this "
                        f"reader ({EVENT_SCHEMA_VERSION}) — update the "
                        f"consumer before trusting its rendering")
    for field in EVENT_REQUIRED.get(tag, ()):
        if field not in rec:
            problems.append(f"{tag}: missing required field {field!r}")
    return problems


def validate_jsonl(path: str, max_problems: int = 20) -> List[str]:
    """Validate every line of a metrics*.jsonl file; returns problem
    strings prefixed with the line number (capped at `max_problems` so a
    wholly drifted file does not flood the summary)."""
    problems: List[str] = []
    with open(path, errors="replace") as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                problems.append(f"line {lineno}: unparseable JSON")
                continue
            problems.extend(f"line {lineno}: {p}"
                            for p in validate_record(rec))
            if len(problems) >= max_problems:
                problems.append(f"... (stopped at {max_problems} problems)")
                return problems
    return problems
