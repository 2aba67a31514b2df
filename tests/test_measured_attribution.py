"""obs v4 (ISSUE 15): measured attribution.

The acceptance criteria pinned here:
* the COMMITTED fixture capture (a synthetic trace.json.gz with a known
  event set — tests/profparse_fixtures/) parses into a measured_phases
  report whose per-phase ms match hand arithmetic exactly, and
  reconciles against a hand analytic report with hand-checkable drift
  numbers (the round-trip pin, backend-proof);
* a REAL CPU-backend jax.profiler capture from a tiny serve run parses
  end-to-end: capture -> parse -> versioned profile_attribution event
  -> summarize_run "Measured vs analytic" render, in one test;
* duty-cycle laws: windows open every N ticks, the disk budget stops
  sampling BETWEEN windows (never mid-window) with a counted skip, and
  the off state is exactly zero-cost (no capture dirs, no events);
* the silent-zero HBM fix: a statless backend reports None/'unavailable'
  loudly — never a fake 0-GiB watermark — through device_memory_gib,
  the exporter gauges, the hbm_watermark events, the fleet rollup, and
  the obs_top column;
* schema v4 (profile_attribution / hbm_watermark) validates and drifts
  loudly; the regression gate treats measured ms directionally.
"""

import glob
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import pytest

from distributed_pytorch_from_scratch_tpu.obs import profparse
from distributed_pytorch_from_scratch_tpu.obs.collector import (
    FleetCollector)
from distributed_pytorch_from_scratch_tpu.obs.schema import (
    EVENT_SCHEMA_VERSION, validate_record)
from distributed_pytorch_from_scratch_tpu.training.metrics import (
    DutyCycleProfiler, MetricsWriter, device_memory_gib,
    device_memory_stats, hbm_watermarks, publish_hbm)

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
FIXTURE_CAPTURE = os.path.join(HERE, "profparse_fixtures", "capture")

# the hand analytic report the fixture reconciles against (2 profiled
# steps): compute 5 ms/step, all-reduce 1 ms/step, cp 0.5 ms/step
HAND_ANALYTIC = {
    "phases": [{"name": "compute", "ms": 5.0},
               {"name": "all-reduce", "ms": 1.0},
               {"name": "collective-permute", "ms": 0.5}],
    "total_ms": 6.5,
}


def _load_script(name):
    path = os.path.join(REPO, "scripts", name + ".py")
    spec = importlib.util.spec_from_file_location(f"_ma_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------- the fixture round-trip

def test_fixture_capture_parses_to_hand_checked_phases():
    """The committed trace.json.gz holds 18 ms of device ops on a 20 ms
    lane; every per-phase total is pinned against hand arithmetic (see
    profparse_fixtures/gen_fixture.py for the authored event set)."""
    r = profparse.parse_capture(FIXTURE_CAPTURE)
    assert r["files"] == 1 and r["events"] == 8
    assert r["devices"] == ["/device:TPU:0"]
    ms = profparse.phase_ms_map(r)
    assert ms == {"fusion": 10.0, "dot": 2.0, "all-reduce": 3.0,
                  "collective-permute": 1.0, "copy": 0.5,
                  "transpose": 0.5, "convert": 1.0, "host_gap": 2.0}
    assert r["device_busy_ms"] == pytest.approx(18.0)
    assert r["host_gap_ms"] == pytest.approx(2.0)
    assert r["total_ms"] == pytest.approx(20.0)
    # the python host-callstack event was ignored (no hlo args)
    counts = {p["name"]: p["count"] for p in r["phases"]}
    assert counts["fusion"] == 2


def test_fixture_reconcile_drift_hand_math():
    """The round-trip pin: measured (per 2 steps) vs the hand analytic
    report — compute folds fusion+dot+convert = 13/2 = 6.5 vs 5.0 =
    +30%; all-reduce 1.5 vs 1.0 = +50%; cp exact; copy/transpose/
    host_gap unpriced (drift None); comm 2.0 ms/step; total +53.8%."""
    measured = profparse.parse_capture(FIXTURE_CAPTURE)
    rec = profparse.reconcile(measured, HAND_ANALYTIC, steps=2)
    assert rec["steps"] == 2
    assert rec["phases"] == {
        "compute": 6.5, "all-reduce": 1.5, "collective-permute": 0.5,
        "copy": 0.25, "transpose": 0.25, "host_gap": 1.0}
    by = {r["phase"]: r for r in rec["rows"]}
    assert by["compute"]["drift_pct"] == pytest.approx(30.0)
    assert by["all-reduce"]["drift_pct"] == pytest.approx(50.0)
    assert by["collective-permute"]["drift_pct"] == pytest.approx(0.0)
    assert by["copy"]["drift_pct"] is None          # unpriced: the find
    assert rec["measured_step_ms"] == pytest.approx(10.0)
    assert rec["analytic_step_ms"] == pytest.approx(6.5)
    assert rec["comm_ms"] == pytest.approx(2.0)
    assert rec["total_drift_pct"] == pytest.approx(53.8)
    # worst suspect = the largest absolute gap (compute, 1.5 ms)
    assert rec["suspects"][0]["phase"] == "compute"
    text = profparse.format_reconcile(rec)
    assert "+30.0%" in text and "host_gap" in text


def test_classify_op_taxonomy():
    assert profparse.classify_op("fusion.2047") == "fusion"
    assert profparse.classify_op("%all-reduce-start.1") == "all-reduce"
    assert profparse.classify_op("all_gather.3") == "all-gather"
    assert profparse.classify_op("reduce-scatter.12") == "reduce-scatter"
    assert profparse.classify_op("collective-permute-done.2") == \
        "collective-permute"
    assert profparse.classify_op("dot.2") == "dot"
    assert profparse.classify_op("dynamic-update-slice.9") == "copy"
    assert profparse.classify_op("bitcast-convert.1") == "convert"
    assert profparse.classify_op("wat.77") == "other"


def test_parse_refuses_non_capture_dirs(tmp_path):
    with pytest.raises(ValueError, match="no .*trace.json"):
        profparse.parse_capture(str(tmp_path))


def test_analytic_phase_report_folds_attribution():
    """The analytic fold: compute == the roofline step; each collective
    kind == its records' serialized ms summed — so the analytic side
    lands in the measured taxonomy, joinable by name."""
    from distributed_pytorch_from_scratch_tpu.config import ModelConfig
    from distributed_pytorch_from_scratch_tpu.obs.attribution import (
        attribution)
    cfg = ModelConfig(attn_dim=64, ffn_dim=128, num_heads=4, num_layers=2,
                      vocab_size=256, maxlen=128)
    attr = attribution(cfg, batch=4, t=128, tp=2, sp=True, world=2)
    rep = profparse.analytic_phase_report(attr)
    ms = profparse.phase_ms_map(rep)
    assert ms["compute"] == pytest.approx(attr["analytic_step_ms"],
                                          abs=5.1e-5)  # report rounds to 4dp
    by_kind = {}
    for r in attr["comm"]["records"]:
        by_kind[r["kind"]] = by_kind.get(r["kind"], 0.0) \
            + r["serialized_ms"]
    for kind, total in by_kind.items():
        assert ms[kind] == pytest.approx(total, abs=1e-3)
    assert rep["comm_exposed_ms"] == pytest.approx(
        attr["comm"]["comm_exposed_ms"], abs=1e-3)


# ---------------------------------------- real capture end-to-end (pin)

def test_real_cpu_capture_end_to_end(tmp_path, capsys):
    """The acceptance pin: a REAL jax.profiler capture from a tiny serve
    run on the CPU backend parses end-to-end — capture dir on disk ->
    obs/profparse -> schema-valid profile_attribution event in the
    metrics chain -> summarize_run renders the 'Measured vs analytic'
    section."""
    from distributed_pytorch_from_scratch_tpu.serving import serve as srv
    log_dir = str(tmp_path / "logs")
    srv.main(["--dry_run", "--paged", "--profile_every", "3",
              "--profile_window", "2", "--log_dir", log_dir])
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["profile_captures"], "duty profiler captured nothing"
    assert rec["profile_attributions"] >= 1
    recs = [json.loads(l)
            for l in open(os.path.join(log_dir, "metrics.jsonl"))]
    pa = [r for r in recs if r["tag"] == "profile_attribution"]
    assert pa, "no profile_attribution events landed"
    assert not any(p for r in pa for p in validate_record(r))
    parsed = [r for r in pa if not r.get("error")]
    assert parsed, "every capture failed to parse"
    first = parsed[0]
    assert first["trigger"] == "duty" and first["steps"] == 2
    assert first["phases"], "parsed capture classified no device events"
    assert os.path.isdir(first["capture"])
    assert profparse.find_trace_files(first["capture"])
    # the post-hoc render: summarize_run picks the events up
    sr = _load_script("summarize_run")
    text = sr.summarize(str(tmp_path))
    assert "Measured vs analytic" in text
    assert "duty" in text


# --------------------------------------------------- duty-cycle laws

def _tick_with_device_work(duty, steps, size=64):
    f = jax.jit(lambda a: a @ a)
    x = jnp.ones((size, size))
    for step in range(steps):
        y = f(x)
        jax.block_until_ready(y)
        duty.tick(step, sync=y)


def test_duty_cycle_budget_stops_between_windows(tmp_path):
    """Budget law: a tiny budget exhausts after the FIRST finished
    window; later due windows are skipped (counted), never started, and
    the finished capture is complete (stopped by window mechanics, not
    truncated by the budget)."""
    with MetricsWriter(str(tmp_path), process_index=0) as w:
        duty = DutyCycleProfiler(str(tmp_path), every=3, window=1,
                                 budget_mb=1e-6, writer=w)
        _tick_with_device_work(duty, 14)
        duty.close()
    assert len(duty.captures) == 1          # one window, then exhausted
    assert duty.exhausted
    # due windows at ticks 6, 9, 12 were skipped (3 of them)
    assert duty.windows_skipped >= 2
    assert os.path.isdir(duty.captures[0])
    assert profparse.find_trace_files(duty.captures[0])
    recs = [json.loads(l) for l in open(tmp_path / "metrics.jsonl")]
    pa = [r for r in recs if r["tag"] == "profile_attribution"]
    assert len(pa) == 1
    assert not validate_record(pa[0])


def test_duty_cycle_opens_windows_on_period(tmp_path):
    # generous budget: a CPU capture's size scales with the host
    # callstack (tens of MiB inside the full suite) — this test pins the
    # PERIOD law, the budget law has its own test above
    with MetricsWriter(str(tmp_path), process_index=0) as w:
        duty = DutyCycleProfiler(str(tmp_path), every=4, window=2,
                                 budget_mb=4096.0, writer=w)
        _tick_with_device_work(duty, 13)
        duty.close()
    # windows open at ticks 4, 8, 12 -> 3 captures (last closed early)
    assert len(duty.captures) == 3
    assert duty.windows_skipped == 0 and not duty.exhausted


def test_duty_cycle_counts_dispatches_not_step_numbers(tmp_path):
    """steps_per_dispatch regression pin: the caller's step numbers jump
    by N per dispatch (train.py's spd mode) — the window must still span
    `window` DISPATCHES, not close Nx early in the step-number domain."""
    f = jax.jit(lambda a: a @ a)
    x = jnp.ones((32, 32))
    opened_at = closed_at = None
    with MetricsWriter(str(tmp_path), process_index=0) as w:
        duty = DutyCycleProfiler(str(tmp_path), every=4, window=2,
                                 budget_mb=4096.0, writer=w)
        for i in range(10):
            y = f(x)
            jax.block_until_ready(y)
            duty.tick(i * 8, sync=y)       # spd=8-style step numbers
            if duty._trace is not None and opened_at is None:
                opened_at = i
            if (opened_at is not None and closed_at is None
                    and i > opened_at and duty._trace is None):
                closed_at = i
        duty.close()
    assert opened_at == 4                  # the every-th dispatch
    assert closed_at == 6                  # exactly `window`=2 dispatches


def test_duty_cycle_truncated_window_reports_actual_steps(tmp_path):
    """A close()-forced window covers fewer dispatches than `window`;
    attributing it at the full count would deflate measured_step_ms (the
    number the regression gate checks directionally)."""
    with MetricsWriter(str(tmp_path), process_index=0) as w:
        duty = DutyCycleProfiler(str(tmp_path), every=3, window=3,
                                 budget_mb=4096.0, writer=w)
        _tick_with_device_work(duty, 5)    # window opens at tick 3
        duty.close()                       # ... 1 dispatch (tick 4) in
    assert duty.capture_steps == [1]
    recs = [json.loads(l) for l in open(tmp_path / "metrics.jsonl")]
    pa = [r for r in recs if r["tag"] == "profile_attribution"]
    assert pa and pa[0]["steps"] == 1


def test_duty_cycle_back_to_back_when_window_equals_every(tmp_path):
    """W == N means continuous back-to-back capture: a window finishing
    on a duty boundary must not swallow the window due at that tick
    (that would silently halve the documented cadence)."""
    with MetricsWriter(str(tmp_path), process_index=0) as w:
        duty = DutyCycleProfiler(str(tmp_path), every=2, window=2,
                                 budget_mb=4096.0, writer=w)
        _tick_with_device_work(duty, 9)
        duty.close()
    # windows open at ticks 2, 4, 6, 8 — every boundary, no gaps
    assert len(duty.captures) == 4
    assert duty.windows_skipped == 0


def test_duty_profiler_refusals(tmp_path):
    with MetricsWriter(str(tmp_path), process_index=0) as w:
        with pytest.raises(ValueError, match="profile window"):
            DutyCycleProfiler(str(tmp_path), every=2, window=3, writer=w)
        with pytest.raises(ValueError, match="budget"):
            DutyCycleProfiler(str(tmp_path), every=4, window=2,
                              budget_mb=0, writer=w)
    with pytest.raises(ValueError, match="MetricsWriter"):
        DutyCycleProfiler(str(tmp_path), every=4, window=2, writer=None)


def test_profiler_off_state_is_zero_cost(tmp_path, capsys):
    """Off state: a serve run WITHOUT profile flags writes no capture
    dirs, no profile_attribution events, and the summary record carries
    no profile fields."""
    from distributed_pytorch_from_scratch_tpu.serving import serve as srv
    log_dir = str(tmp_path / "logs")
    srv.main(["--dry_run", "--paged", "--log_dir", log_dir])
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "profile_captures" not in rec
    assert not glob.glob(os.path.join(log_dir, "profile_duty_*"))
    assert not glob.glob(os.path.join(log_dir, "plugins"))
    recs = [json.loads(l)
            for l in open(os.path.join(log_dir, "metrics.jsonl"))]
    assert not any(r["tag"] == "profile_attribution" for r in recs)


# --------------------------------------------- silent-zero HBM fix

def test_device_memory_unavailable_is_none_not_zero():
    """The CPU backend has no memory_stats: every reader must see the
    DISTINCT unavailable value, never 0 (the fake 0-GiB watermark)."""
    assert device_memory_stats() is None
    assert device_memory_gib() is None
    assert hbm_watermarks() is None


def test_publish_hbm_exports_unavailable_loudly(tmp_path):
    from distributed_pytorch_from_scratch_tpu.obs import TelemetryExporter
    tel = TelemetryExporter()
    with MetricsWriter(str(tmp_path), process_index=0) as w:
        marks = publish_hbm(telemetry=tel, writer=w, step=7, event=True,
                            pool_accounted_bytes=4096)
    assert marks is None
    g = tel.snapshot()["gauges"]
    assert g["hbm/available"] == 0.0
    assert "hbm/bytes_in_use" not in g          # no fake zeros
    assert g["hbm/kv_accounted_bytes"] == 4096
    recs = [json.loads(l) for l in open(tmp_path / "metrics.jsonl")]
    hw = [r for r in recs if r["tag"] == "hbm_watermark"]
    assert len(hw) == 1
    assert hw[0]["available"] is False and hw[0]["devices"] == []
    assert not validate_record(hw[0])


def test_train_scalar_never_fakes_zero_memory(tmp_path):
    """memory.py's budget has no fallback either: a backend without
    memory_stats raises instead of sizing remat against an assumed
    16 GiB."""
    from distributed_pytorch_from_scratch_tpu.training import memory
    with pytest.raises(ValueError, match="no memory_stats"):
        memory.hbm_budget_gib()


# -------------------------------- schema v4 + collector + obs_top

def test_schema_v4_profile_attribution_and_hbm_watermark():
    ok = {"tag": "profile_attribution", "schema_version":
          EVENT_SCHEMA_VERSION, "capture": "/x", "trigger": "duty",
          "phases": {"fusion": 1.0}}
    assert validate_record(ok) == []
    missing = dict(ok)
    missing.pop("phases")
    assert any("phases" in p for p in validate_record(missing))
    hbm = {"tag": "hbm_watermark", "schema_version": EVENT_SCHEMA_VERSION,
           "devices": [], "available": False}
    assert validate_record(hbm) == []
    newer = dict(ok, schema_version=EVENT_SCHEMA_VERSION + 1)
    assert any("NEWER" in p for p in validate_record(newer))


def test_fleet_rollup_folds_hbm_and_keeps_unavailable_distinct(tmp_path):
    """2 fake procs: one exports real watermark gauges, one exports
    available=0 — the rollup sums only the real one and counts the
    unavailable proc LOUDLY instead of folding a zero."""
    d0, d1 = tmp_path / "p0", tmp_path / "p1"
    with MetricsWriter(str(d0), process_index=0) as w:
        w.event("telemetry_snapshot", process=0,
                gauges={"serve/tokens_per_sec": 10.0,
                        "hbm/available": 1.0,
                        "hbm/bytes_in_use": 3 * 2**30,
                        "hbm/peak_bytes": 5 * 2**30},
                counters={})
    with MetricsWriter(str(d1), process_index=1) as w:
        w.event("telemetry_snapshot", process=1,
                gauges={"serve/tokens_per_sec": 5.0,
                        "hbm/available": 0.0},
                counters={})
    c = FleetCollector([str(d0), str(d1)])
    assert c.poll() == 2
    r = c.rollup()
    assert r["hbm"] == {"bytes_in_use_total": 3 * 2**30,
                        "peak_bytes_max": 5 * 2**30,
                        "procs_reporting": 1,
                        "procs_unavailable": 1}


def test_collector_folds_hbm_watermark_events(tmp_path):
    with MetricsWriter(str(tmp_path), process_index=0) as w:
        w.event("hbm_watermark", available=True,
                devices=[{"device": "tpu:0", "bytes_in_use": 100,
                          "peak_bytes": 200, "limit_bytes": 400}])
    c = FleetCollector([str(tmp_path)])
    c.poll()
    r = c.rollup()
    assert r["hbm"]["bytes_in_use_total"] == 100
    assert r["hbm"]["peak_bytes_max"] == 200


def test_obs_top_once_renders_hbm_column(tmp_path, capsys):
    d0, d1 = tmp_path / "p0", tmp_path / "p1"
    with MetricsWriter(str(d0), process_index=0) as w:
        w.event("telemetry_snapshot", process=0,
                gauges={"serve/tokens_per_sec": 42.0,
                        "hbm/available": 1.0,
                        "hbm/bytes_in_use": 2 * 2**30,
                        "hbm/peak_bytes": 3 * 2**30},
                counters={})
    with MetricsWriter(str(d1), process_index=1) as w:
        w.event("telemetry_snapshot", process=1,
                gauges={"serve/tokens_per_sec": 7.0,
                        "hbm/available": 0.0},
                counters={})
    top = _load_script("obs_top")
    assert top.main([str(d0), str(d1), "--once"]) == 0
    out = capsys.readouterr().out
    assert "| hbm |" in out
    assert "2.00/3.00G" in out              # the available proc's column
    assert "n/a" in out                     # the statless proc, loudly
    assert "report NO" in out or "HBM:" in out


def test_summarize_renders_hbm_watermarks(tmp_path):
    with MetricsWriter(str(tmp_path), process_index=0) as w:
        w.event("hbm_watermark", available=False, devices=[])
    sr = _load_script("summarize_run")
    text = sr.summarize(str(tmp_path))
    assert "HBM watermarks" in text and "UNAVAILABLE" in text


# ------------------------------------------- the regression gate

def _serving_record(measured_step_ms, comm_ms, phases):
    return {"metric": "serving tokens/sec (x)", "value": 100.0,
            "unit": "tokens/sec (serving)",
            "measured_vs_analytic": {
                "capture": "/x", "steps": 2,
                "measured_step_ms": measured_step_ms,
                "comm_ms": comm_ms, "phases": phases}}


def test_gate_measured_ms_directional(tmp_path):
    gate = _load_script("check_bench_regression")
    base = tmp_path / "base.json"
    base.write_text(json.dumps(
        _serving_record(10.0, 1.0, {"compute": 8.0, "all-reduce": 1.0})))
    ok = tmp_path / "ok.json"
    ok.write_text(json.dumps(
        _serving_record(9.5, 0.9, {"compute": 7.8, "all-reduce": 0.9})))
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(
        _serving_record(20.0, 4.0, {"compute": 17.0, "all-reduce": 4.0})))
    assert gate.main(["--fresh", str(ok), "--baseline", str(base)]) == 0
    rc = gate.main(["--fresh", str(bad), "--baseline", str(base)])
    assert rc == 1
    # the dynamic per-phase checks actually fired
    checks, _ = gate.metric_checks(json.loads(bad.read_text()),
                                   json.loads(base.read_text()),
                                   tol_pct=10.0, tol_latency_pct=25.0)
    fields = {c["field"] for c in checks}
    assert "measured_vs_analytic.measured_step_ms" in fields
    assert "measured_vs_analytic.phases.compute" in fields
    assert any(not c["ok"] for c in checks)


# --------------------------------------------------- CLI refusals

def test_serve_cli_profile_refusals():
    from distributed_pytorch_from_scratch_tpu.serving import serve as srv
    with pytest.raises(SystemExit):       # duty + anomaly collide
        srv.get_serve_args(["--dry_run", "--paged", "--flight_records",
                            "--profile_every", "4",
                            "--profile_on_anomaly", "2"])
    with pytest.raises(SystemExit):       # window > every
        srv.get_serve_args(["--dry_run", "--profile_every", "2",
                            "--profile_window", "4"])
    with pytest.raises(SystemExit):       # no metrics dir
        srv.get_serve_args(["--dry_run", "--profile_every", "4",
                            "--log_dir", ""])
    with pytest.raises(SystemExit):       # budget
        srv.get_serve_args(["--dry_run", "--profile_every", "4",
                            "--profile_window", "2",
                            "--profile_budget_mb", "0"])


def test_bench_cli_profile_refusals():
    import bench
    with pytest.raises(SystemExit):       # --serving gate
        bench.parse_args(["--profile_every", "4"])
    with pytest.raises(SystemExit):       # window > every
        bench.parse_args(["--serving", "--profile_every", "2",
                          "--profile_window", "4"])
    with pytest.raises(SystemExit):       # no metrics dir
        bench.parse_args(["--serving", "--profile_every", "4",
                          "--obs_dir", ""])
    with pytest.raises(SystemExit):       # breakdown-only knob
        bench.parse_args(["--capture_profile"])
    with pytest.raises(SystemExit):       # capture needs device timing
        bench.parse_args(["--breakdown", "--analytic", "--remat", "dots",
                          "--capture_profile"])
    args = bench.parse_args(["--serving", "--profile_every", "6",
                             "--profile_window", "2"])
    assert args.profile_every == 6 and args.profile_window == 2


def test_train_cli_profile_refusals():
    from distributed_pytorch_from_scratch_tpu.train import get_train_args
    with pytest.raises(SystemExit):       # duty excludes fixed window
        get_train_args(["--data_path", "x", "--profile_every", "4",
                        "--profile_steps", "2"])
    with pytest.raises(SystemExit):       # duty excludes anomaly arm
        get_train_args(["--data_path", "x", "--profile_every", "4",
                        "--profile_on_anomaly", "2"])
    with pytest.raises(SystemExit):       # window > every
        get_train_args(["--data_path", "x", "--profile_every", "2",
                        "--profile_window", "8"])
    args = get_train_args(["--data_path", "x", "--profile_every", "8",
                           "--profile_window", "2"])
    assert args.profile_every == 8
