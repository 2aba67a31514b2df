"""Run forensics (ISSUE 17, obs v6): the RunCard index, the cross-run
diff engine, and trajectory changepoint triage.

What is pinned here, against the two committed fixture run dirs under
tests/forensics_fixtures/ (run_a: pages_per_block=4, run_b:
pages_per_block=8 with a degraded copy phase) and the repo's REAL
BENCH_r02 outage fixture:

* RunCard fields for both fixture runs (fingerprint, headline metrics,
  ledger/capture tallies, HBM watermark, graftcheck contracts);
* the ranked-suspect diff: the pages_per_block config delta JOINED to
  the copy-phase delta, above a noise floor derived from the fixtures'
  duty-cycle capture variance;
* changepoint detection over the committed synthetic trajectory flags
  the pinned step (t5) while outage points are listed, never points;
* outage records can NEVER become baselines, and the gate and the index
  share literally the same classifier function;
* schema v6: run_card / run_diff contracts + JSON roundtrip;
* `check_bench_regression --explain` attaches the forensic report on
  failure and stays silent on pass.
"""

import importlib.util
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIX = os.path.join(REPO, "tests", "forensics_fixtures")
# a stand-in repo root holding synthetic driver-style records (its README)
FIX_REPO = os.path.join(FIX, "repo")
RUN_A = os.path.join(FIX, "run_a")
RUN_B = os.path.join(FIX, "run_b")

# the standalone import path scripts use (obs dir on sys.path, no jax) —
# the SAME modules check_bench_regression._forensics loads, so identity
# assertions below are meaningful
OBS_DIR = os.path.join(REPO, "distributed_pytorch_from_scratch_tpu", "obs")
if OBS_DIR not in sys.path:
    sys.path.insert(0, OBS_DIR)
import rundiff  # noqa: E402
import runindex  # noqa: E402
from schema import (EVENT_REQUIRED, EVENT_SCHEMA_VERSION,  # noqa: E402
                    validate_record)


def _load_script(name):
    path = os.path.join(REPO, "scripts", name + ".py")
    spec = importlib.util.spec_from_file_location(f"_fx_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------- RunCard pins --

def test_run_card_pins_fixture_run_a():
    card = runindex.card_from_run_dir(RUN_A)
    assert card["tag"] == "run_card"
    assert card["run"] == "run_a"
    assert card["kind"] == "session"
    assert card["outage"] is False
    assert card["baseline_eligible"] is True
    assert card["legacy"] is False
    # the committed fingerprint IS what the live function computes —
    # the stamp round-trips through the record
    assert card["config_fingerprint"] == "0e6bbad84b3c"
    assert card["config_fingerprint"] == \
        runindex.config_fingerprint(card["config"])
    assert card["git_rev"] == "aaaa111"
    assert card["metrics"]["value"] == 5214.0
    assert card["metrics"]["unit"] == "tokens/sec (serving)"
    assert card["metrics"]["ttft_ms_p95"] == 85.0
    assert card["measured_vs_analytic"]["phases"]["copy"] == 2.01
    # 3 duty captures tallied, with per-step phase samples kept for the
    # noise floor
    assert card["captures"]["count"] == 3
    assert card["captures"]["triggers"] == {"duty": 3}
    assert len(card["profile_phases"]) == 3
    assert card["hbm"] == {"available": True, "devices": 1,
                           "peak_bytes": 9120256}
    assert card["collectives"]["ok"] is True
    assert card["collectives"]["contracts"][
        "expected_collectives:train_step"] is True
    assert card["ledger"]["decisions"] == 0
    assert runindex.validate_card(card) == []


def test_run_card_run_b_ledger():
    card = runindex.card_from_run_dir(RUN_B)
    assert card["config_fingerprint"] == "8961e903d0d6"
    assert card["metrics"]["value"] == 4288.0
    led = card["ledger"]
    assert led["decisions"] == 1 and led["applied"] == 0
    assert led["knobs"]["pages_per_block"]["last"] == [4, 8]
    assert card["hbm"]["peak_bytes"] == 9830400


def test_run_card_legacy_note_not_silent_none():
    """A pre-stamp record (the fixture BENCH_r01) indexes with the loud
    legacy note, and the diff engine refuses to call two fingerprint-less
    configs equal."""
    card = runindex.card_from_bench_path(
        os.path.join(FIX_REPO, "BENCH_r01.json"))
    assert card["legacy"] is True
    assert runindex.LEGACY_NOTE in card["notes"]
    assert card["config_fingerprint"] is None
    delta = rundiff.config_delta(card, card)
    assert delta["available"] is False
    assert any("fingerprint unavailable" in n for n in delta["notes"])


# ------------------------------------------------- one outage classifier --

def test_outage_classifier_is_shared_with_gate():
    """The gate's pick_baseline and the index must use literally the
    same classifier function — the ISSUE 17 no-divergence satellite."""
    gate = _load_script("check_bench_regression")
    gate_runindex, gate_rundiff = gate._forensics()
    assert gate_runindex.outage_reason is runindex.outage_reason
    assert gate_rundiff.diff_runs is rundiff.diff_runs


def test_bench_r02_outage_never_baseline():
    """BENCH_r02 (rc=1, traceback tail, parsed=null) is the pinned
    outage fixture: classified as outage, never selected as baseline."""
    r02 = os.path.join(FIX_REPO, "BENCH_r02.json")
    cls = runindex.classify_path(r02)
    assert cls["outage"] is not None
    assert "rc=1" in cls["outage"]
    card = runindex.card_from_bench_path(r02)
    assert card["outage"] is True and card["baseline_eligible"] is False
    assert runindex.validate_card(card) == []
    # the gate skips it even when it is the ONLY candidate
    gate = _load_script("check_bench_regression")
    fresh = gate.load_record(os.path.join(RUN_A, "bench_paged.json"))
    assert gate.pick_baseline(fresh, [r02]) == (None, None)
    # and a healthy record still wins when both are offered
    fresh_chip = {"metric": "tokens/sec/chip (x)",
                  "unit": "tokens/sec/chip", "value": 1.0}
    rec, path = gate.pick_baseline(
        fresh_chip, [os.path.join(FIX_REPO, "BENCH_r01.json"), r02])
    assert path.endswith("BENCH_r01.json")
    assert rec["unit"] == "tokens/sec/chip"


def test_outage_reason_taxonomy():
    assert runindex.outage_reason(None) == "no parseable record"
    assert runindex.outage_reason(None, rc=3) == \
        "no parseable record (rc=3)"
    assert "backend_unavailable" in runindex.outage_reason(
        {"error": "backend_unavailable", "detail": "no backend"})
    assert runindex.outage_reason({"metric": "x", "value": 1}, rc=1) \
        == "rc=1"
    assert runindex.outage_reason({"value": 1}) == \
        "record carries no metric"
    assert runindex.outage_reason({"metric": "x", "value": 1}) is None
    assert runindex.outage_reason({"metric": "x"}, rc=0) is None


# ------------------------------------------------------ pinned suspect diff --

def test_pinned_ranked_suspect_pages_per_block_to_copy():
    """THE acceptance pin: the pages_per_block config delta is joined to
    the copy-phase delta as the #1 ranked suspect."""
    doc = rundiff.diff_runs(runindex.card_from_run_dir(RUN_A),
                            runindex.card_from_run_dir(RUN_B))
    assert doc["tag"] == "run_diff"
    assert doc["config_delta"]["changed"] == {"pages_per_block": [4, 8]}
    assert len(doc["suspects"]) == 1
    top = doc["suspects"][0]
    assert top["knob"] == "pages_per_block"
    assert top["phase"] == "copy"
    assert top["delta_ms"] == pytest.approx(2.11, abs=1e-6)
    assert top["score"] > 1.0
    assert "copy paid" in top["verdict"]
    # the insignificant compute/host_gap jitters stayed below the
    # capture-variance noise floor — visible in phase_deltas, not suspects
    by_phase = {r["phase"]: r for r in doc["phase_deltas"]}
    assert by_phase["copy"]["significant"] is True
    assert by_phase["compute"]["significant"] is False
    assert by_phase["host_gap"]["significant"] is False
    # measured consequences ride along
    assert doc["hbm"]["delta_bytes"] == 9830400 - 9120256
    assert doc["ledger"]["decisions_b"] == 1
    # human rendering names the suspect
    text = "\n".join(rundiff.format_diff(doc))
    assert "pages_per_block" in text and "suspects (ranked)" in text


def test_unclaimed_phase_delta_blames_code_delta():
    """A significant phase move with NO changed knob is attributed to
    the code/environment delta (git a -> b), not silently dropped."""
    card_a = runindex.card_from_run_dir(RUN_A)
    card_b = runindex.card_from_run_dir(RUN_B)
    # same config on both sides -> no knob can claim the copy delta
    card_b = dict(card_b, config=card_a["config"],
                  config_fingerprint=card_a["config_fingerprint"])
    doc = rundiff.diff_runs(card_a, card_b)
    assert doc["config_delta"]["changed"] == {}
    tops = [s for s in doc["suspects"] if s["phase"] == "copy"]
    assert len(tops) == 1 and tops[0]["knob"] is None
    assert "git aaaa111 -> bbbb222" in tops[0]["verdict"]


def test_noise_floor_from_capture_variance():
    card = runindex.card_from_run_dir(RUN_A)
    floors = rundiff.noise_floor(card)
    # three captures with +/-0.02 ms/step jitter -> a real (clamped)
    # per-phase floor for every phase the duty cycle measured
    assert set(floors) == {"copy", "compute", "host_gap"}
    for v in floors.values():
        assert rundiff.MIN_FLOOR_MS <= v < 0.1


# ----------------------------------------------------- trajectory triage --

def _trajectory_cards():
    doc = json.load(open(os.path.join(FIX, "trajectory.json")))
    cards = []
    for pt in doc["points"]:
        if "outage" in pt:
            cards.append({"run": pt["run"], "outage": True,
                          "outage_reason": pt["outage"],
                          "metrics": {"unit": doc["unit"]}})
        else:
            cards.append({"run": pt["run"], "outage": False,
                          "metrics": {"metric": doc["metric"],
                                      "unit": doc["unit"],
                                      "value": pt["value"]}})
    return doc, cards


def test_changepoint_flags_pinned_trajectory_step():
    doc, cards = _trajectory_cards()
    reports = rundiff.trajectory_report(cards)
    assert len(reports) == 1
    rep = reports[0]
    # outage points are LISTED but never series points
    assert [o["run"] for o in rep["outages"]] == ["t2b", "t5b"]
    assert [p["run"] for p in rep["series"]] == \
        ["t1", "t2", "t3", "t4", "t5", "t6", "t7"]
    cp = rep["changepoint"]
    assert cp is not None
    assert cp["run"] == doc["expected_changepoint_run"] == "t5"
    assert cp["direction"] == "down"
    assert cp["before_mean"] == pytest.approx(100325.0)
    assert cp["after_mean"] == pytest.approx(86066.67, abs=0.01)


def test_changepoint_quiet_on_flat_and_short_series():
    assert rundiff.changepoint([100.0, 100.4, 99.7, 100.1, 99.9,
                                100.2]) is None
    assert rundiff.changepoint([100.0, 50.0]) is None  # < 2*min_seg
    assert rundiff.changepoint([]) is None


# ----------------------------------------------------------- schema v6 pins --

def test_schema_v6_forensics_contracts():
    """The version and both forensics tags' required fields are pinned,
    and real index/diff output round-trips through JSON + validates."""
    assert EVENT_SCHEMA_VERSION == 7  # v7 added the reshard_event family
    assert EVENT_REQUIRED["run_card"] == \
        ("run", "kind", "outage", "baseline_eligible")
    assert EVENT_REQUIRED["run_diff"] == \
        ("run_a", "run_b", "config_delta", "suspects")
    card = runindex.card_from_run_dir(RUN_A)
    doc = rundiff.diff_runs(card, runindex.card_from_run_dir(RUN_B))
    for rec in (card, doc):
        rt = json.loads(json.dumps(rec))
        assert rt == rec  # JSON roundtrip is lossless
        assert validate_record(rt) == []
    bad = {k: v for k, v in doc.items() if k != "suspects"}
    assert any("suspects" in p for p in validate_record(bad))
    bad_card = dict(card, outage=True, baseline_eligible=True)
    assert any("never" in p for p in runindex.validate_card(bad_card))


def test_run_stamp_deterministic():
    cfg = {"model": "45m", "batch": 32, "paged": True}
    s1, s2 = runindex.run_stamp(cfg), runindex.run_stamp(dict(cfg))
    assert s1["config_fingerprint"] == s2["config_fingerprint"]
    assert s1["config"] == s2["config"]
    assert runindex.config_fingerprint(dict(cfg, batch=64)) != \
        s1["config_fingerprint"]


# --------------------------------------------------------- --explain gate --

def test_gate_explain_attaches_forensics_on_failure(capsys):
    gate = _load_script("check_bench_regression")
    rc = gate.main(["--fresh", os.path.join(RUN_B, "bench_paged.json"),
                    "--baseline", os.path.join(RUN_A, "bench_paged.json"),
                    "--tol_pct", "0", "--tol_latency_pct", "0",
                    "--explain"])
    cap = capsys.readouterr()
    assert rc == 1
    out = json.loads(cap.out.splitlines()[0])
    assert out["status"] == "regression"
    forensics = out["forensics"]
    assert forensics["diff"]["suspects"][0]["knob"] == "pages_per_block"
    assert forensics["diff"]["suspects"][0]["phase"] == "copy"
    # the stderr report names the suspect — a red gate ships its triage
    assert "pages_per_block" in cap.err and "suspects" in cap.err


def test_gate_explain_silent_on_pass(capsys):
    gate = _load_script("check_bench_regression")
    rc = gate.main(["--fresh", os.path.join(RUN_A, "bench_paged.json"),
                    "--baseline", os.path.join(RUN_A, "bench_paged.json"),
                    "--explain"])
    cap = capsys.readouterr()
    assert rc == 0
    out = json.loads(cap.out.splitlines()[0])
    assert out["status"] == "ok"
    assert "forensics" not in out


def test_gate_explain_refused_with_controller():
    gate = _load_script("check_bench_regression")
    with pytest.raises(SystemExit) as e:
        gate.parse_args(["--fresh", "x.json", "--controller",
                         "--explain"])
    assert e.value.code not in (0, None)


# ------------------------------------------------------------ obs_diff CLI --

def test_obs_diff_pairwise_cli(capsys):
    od = _load_script("obs_diff")
    rc = od.main([RUN_A, RUN_B])
    cap = capsys.readouterr()
    assert rc == 0
    doc = json.loads(cap.out.strip())
    assert doc["tag"] == "run_diff"
    assert doc["run_a"] == "run_a" and doc["run_b"] == "run_b"
    assert doc["suspects"][0]["knob"] == "pages_per_block"
    assert "suspects (ranked)" in cap.err


def test_obs_diff_card_and_bare_name_resolution(capsys):
    od = _load_script("obs_diff")
    assert od.main(["--card", RUN_A]) == 0
    card = json.loads(capsys.readouterr().out.strip())
    assert card["tag"] == "run_card" and card["run"] == "run_a"
    # bare round names resolve against the repo (r02 -> BENCH_r02.json)
    assert od.main(["--repo", FIX_REPO, "r02", "r01"]) == 0
    doc = json.loads(capsys.readouterr().out.strip())
    assert doc["run_a"] == "BENCH_r02" and doc["run_b"] == "BENCH_r01"
    assert doc["outage_a"] is not None  # r02's outage is carried along
    assert od.main(["--card", "nonexistent_run_xyz"]) == 2
    capsys.readouterr()


def test_obs_diff_triage_picks_comparable_baseline(tmp_path, capsys):
    """--triage auto-picks the best comparable baseline: same unit,
    outages excluded, matching fingerprint preferred."""
    od = _load_script("obs_diff")
    repo = tmp_path / "repo"
    (repo / "runs").mkdir(parents=True)
    # trajectory: r01 healthy (different fingerprint), r02 an outage,
    # r03 healthy with run_b's fingerprint -> triage must pick r03
    a = json.load(open(os.path.join(RUN_A, "bench_paged.json")))
    b = json.load(open(os.path.join(RUN_B, "bench_paged.json")))
    (repo / "BENCH_r01.json").write_text(json.dumps(a))
    (repo / "BENCH_r02.json").write_text(json.dumps(
        {"n": 2, "rc": 1, "tail": "Traceback ...", "parsed": None}))
    (repo / "BENCH_r03.json").write_text(json.dumps(b))
    fresh = tmp_path / "fresh.json"
    fresh.write_text(json.dumps(dict(b, value=3000.0)))
    rc = od.main(["--triage", str(fresh), "--repo", str(repo)])
    cap = capsys.readouterr()
    assert rc == 0
    doc = json.loads(cap.out.strip())
    assert doc["run_a"] == "BENCH_r03"  # fingerprint match beats r01
    assert "baseline BENCH_r03" in cap.err
    # no comparable unit at all -> an answer, not an error
    lonely = tmp_path / "lonely.json"
    lonely.write_text(json.dumps({"metric": "weird", "value": 1.0,
                                  "unit": "furlongs"}))
    assert od.main(["--triage", str(lonely), "--repo", str(repo)]) == 0
    doc = json.loads(capsys.readouterr().out.strip())
    assert doc["note"] == "no comparable baseline"


def test_obs_diff_index_counts_fixture_repo(capsys):
    """--index over the fixture repo root: every BENCH record there gets
    a card, both outage shapes (no record; a backend_unavailable record)
    classify as outages, and no outage is baseline-eligible. The real
    repo's runs/ dirs index cleanly too."""
    od = _load_script("obs_diff")
    assert od.main(["--index"]) == 0
    real = json.loads(capsys.readouterr().out.strip())["cards"]
    assert all(runindex.validate_card(c) == [] for c in real)
    assert od.main(["--repo", FIX_REPO, "--index"]) == 0
    cards = json.loads(capsys.readouterr().out.strip())["cards"]
    by_run = {c["run"]: c for c in cards}
    assert by_run["BENCH_r01"]["baseline_eligible"] is True
    for r in ("BENCH_r02", "BENCH_r03"):
        assert by_run[r]["outage"] is True, r
    assert all(not (c["outage"] and c["baseline_eligible"])
               for c in cards)
    assert all(runindex.validate_card(c) == [] for c in cards)


# --------------------------------------------------- record stamping (e2e) --

def test_serve_record_carries_provenance_stamp(tmp_path, capsys):
    """The serving summary record uniformly stamps config_fingerprint +
    git_rev, and the fingerprint is recomputable from the stamped
    config — the stamp round-trips into a card the index can join on."""
    from distributed_pytorch_from_scratch_tpu.serving import serve as sv
    sv.main(["--dry_run", "--log_dir", str(tmp_path / "logs")])
    rec = None
    for line in capsys.readouterr().out.splitlines():
        if line.startswith("{"):
            obj = json.loads(line)
            if "metric" in obj:
                rec = obj
    assert rec is not None
    assert rec["config_fingerprint"] == \
        runindex.config_fingerprint(rec["config"])
    assert "git_rev" in rec
    card = runindex.card_from_record(rec, run="dry", source="stdout")
    assert card["legacy"] is False
    assert card["baseline_eligible"] is True
    assert card["config_fingerprint"] == rec["config_fingerprint"]
