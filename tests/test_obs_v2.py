"""obs v2 (ISSUE 10): per-request tracing, the anomaly flight recorder,
cross-rank skew attribution, the event-schema contract, and the
bench-regression gate.

The acceptance criteria pinned here:
* every completed request of a traced loadgen run has a CONTIGUOUS span
  timeline whose span sum equals its measured submit->finish wall
  (TTFT + decode wall) within tolerance — including through preemption +
  COW resume and speculative drafter rounds (no orphan spans);
* an induced sentinel non-finite halt and a forced PoolExhausted
  preemption each produce a flight dump containing the triggering event
  plus the preceding ring contents;
* `check_bench_regression.py` exits 0 on the committed trajectory vs
  itself, nonzero on a synthetically degraded record, and 0-with-skip on
  a backend_unavailable record;
* the k-worst exemplar waterfalls render in `summarize_run.py` output.
"""

import glob
import importlib.util
import json
import os
import time

import jax
import pytest

from distributed_pytorch_from_scratch_tpu.config import MeshConfig, ModelConfig
from distributed_pytorch_from_scratch_tpu.obs import (
    EVENT_SCHEMA_VERSION, FlightRecorder, HealthSentinel, HangWatchdog,
    RequestTracer, SpanTracer, TrainingHealthError, rank_skew,
    validate_jsonl, validate_record)
from distributed_pytorch_from_scratch_tpu.models.transformer import Transformer
from distributed_pytorch_from_scratch_tpu.runtime.mesh import make_mesh
from distributed_pytorch_from_scratch_tpu.serving.engine import (
    ContinuousBatchingEngine, PagedEngine, Request)
from distributed_pytorch_from_scratch_tpu.serving.loadgen import (
    run_loadgen, synthetic_requests)
from distributed_pytorch_from_scratch_tpu.training.metrics import MetricsWriter

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = ModelConfig(attn_dim=32, ffn_dim=64, num_heads=8, num_layers=2,
                  vocab_size=96, maxlen=64)
DRAFTER_CFG = ModelConfig(attn_dim=16, ffn_dim=32, num_heads=2,
                          num_layers=1, vocab_size=96, maxlen=64)
BUF = 32
EOS = 1


def _setup(tp=1, seed=3):
    mesh = make_mesh(MeshConfig(dp=1, tp=tp))
    model = Transformer(CFG, tp_size=tp)
    params = jax.device_put(model.init(jax.random.key(seed)),
                            model.shardings(mesh))
    return mesh, model, params


def _load_script(name):
    path = os.path.join(REPO, "scripts", name + ".py")
    spec = importlib.util.spec_from_file_location(f"_obs2_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _assert_contiguous_and_sums(rec, req, tol_ms=0.1):
    """The pinned timeline contract: spans chain end-to-start with no gap
    or overlap, and their sum equals the request's measured wall
    (finish - submit = TTFT + decode wall)."""
    spans = rec["spans"]
    assert spans, rec
    cursor = 0.0
    for s in spans:
        assert abs(s["start_ms"] - cursor) <= 0.01, (s, cursor, spans)
        assert s["dur_ms"] >= 0.0, s
        cursor = s["start_ms"] + s["dur_ms"]
    assert abs(cursor - rec["total_ms"]) <= tol_ms, (cursor, rec["total_ms"])
    wall_ms = (req.finish_t - req.submit_t) * 1e3
    assert abs(rec["total_ms"] - wall_ms) <= tol_ms, (rec["total_ms"],
                                                      wall_ms)
    # wall == TTFT + decode wall, by the Request clock identities
    ttft_ms = (req.first_token_t - req.submit_t) * 1e3
    decode_ms = (req.finish_t - req.first_token_t) * 1e3
    assert abs(rec["total_ms"] - (ttft_ms + decode_ms)) <= tol_ms


# ------------------------------------------------- per-request timelines

def test_paged_request_timelines_contiguous_and_sum_to_wall(tmp_path):
    """Every completed request of a paged run (chunked prefill + COW
    shared prefixes + forced preemption/resume) gets a contiguous
    timeline summing to its wall time; the preempted request's timeline
    shows the `preempted` span and a second `queued` stretch (the COW
    re-admission) — no orphan spans, live set drains to zero."""
    mesh, model, params = _setup(seed=3)
    writer = MetricsWriter(str(tmp_path), process_index=0)
    rt = RequestTracer(writer=writer)
    # the preempt-resume recipe: pool too small for combined growth
    eng = PagedEngine(model, mesh, params, num_slots=3, buf_len=BUF,
                      eos_id=EOS, page_size=8, num_pages=4, prefill_chunk=8,
                      request_tracer=rt, writer=writer)
    shared = [0, 5, 9, 60]
    prompts = [shared + [2, 8, 33], shared + [4, 7, 21],
               shared + [17, 8, 52]]
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=list(p), max_new=12))
    eng.run_to_completion()
    writer.close()
    assert eng.preemptions >= 1            # the churn actually happened
    assert rt.live == 0                    # no orphan timelines
    preempted_seen = False
    for req in eng.completed:
        rec = rt.timeline(req.rid)
        assert rec is not None and rec["trace_id"] == req.trace_id
        _assert_contiguous_and_sums(rec, req)
        names = [s["name"] for s in rec["spans"]]
        assert names[0] == "queued", names
        assert "prefill_chunk" in names and "decode" in names, names
        if req.preemptions:
            preempted_seen = True
            assert "preempted" in names, names
            # resume = a second queued stretch after the preemption
            assert "queued" in names[names.index("preempted"):], names
            assert rec["preemptions"] == req.preemptions
    assert preempted_seen
    # the jsonl mirror: one versioned request_trace event per request
    recs = [json.loads(l) for l in open(tmp_path / "metrics.jsonl")]
    traces = [r for r in recs if r["tag"] == "request_trace"]
    assert len(traces) == len(eng.completed)
    assert all(r["schema_version"] == EVENT_SCHEMA_VERSION for r in traces)
    assert not any(validate_record(r) for r in traces)


def test_slot_engine_request_timelines(tmp_path):
    """The PR 5 slot engine gets the same contract (queued -> prefill ->
    decode), so traced loadgen runs are engine-agnostic."""
    mesh, model, params = _setup(seed=5)
    rt = RequestTracer()
    eng = ContinuousBatchingEngine(model, mesh, params, num_slots=2,
                                   buf_len=BUF, eos_id=EOS,
                                   prefill_bucket=8, request_tracer=rt)
    prompts = [[0, 5, 17, 33], [0, 9, 11], [0, 3, 5, 7, 11]]
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new=6))
    eng.run_to_completion()
    assert rt.live == 0
    for req in eng.completed:
        rec = rt.timeline(req.rid)
        _assert_contiguous_and_sums(rec, req)
        names = [s["name"] for s in rec["spans"]]
        assert names[0] == "queued" and "prefill" in names, names


def test_speculative_request_timelines():
    """Trace-ID propagation through drafter rounds: spec_round spans
    (with accepted counts) + drafter_prefill, still contiguous."""
    from distributed_pytorch_from_scratch_tpu.serving.speculative import (
        SpeculativeEngine)
    mesh, model, params = _setup(seed=2)
    dmodel = Transformer(DRAFTER_CFG, tp_size=1)
    dparams = jax.device_put(dmodel.init(jax.random.key(9)),
                             dmodel.shardings(mesh))
    rt = RequestTracer()
    eng = SpeculativeEngine(model, mesh, params, dmodel, dparams,
                            num_slots=2, buf_len=BUF, eos_id=EOS,
                            speculate_k=2, page_size=8, prefill_chunk=8,
                            request_tracer=rt)
    prompts = [[0, 5, 17, 33, 60], [0, 9, 11, 4]]
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new=8))
    eng.run_to_completion()
    assert rt.live == 0
    for req in eng.completed:
        rec = rt.timeline(req.rid)
        _assert_contiguous_and_sums(rec, req)
        names = [s["name"] for s in rec["spans"]]
        assert "spec_round" in names and "drafter_prefill" in names, names
        rounds = [s for s in rec["spans"] if s["name"] == "spec_round"]
        # accepted counts ride the coalesced spans
        assert all("accepted" in s for s in rounds)


def test_request_tracer_chrome_track(tmp_path):
    """Retired timelines land in the SpanTracer file as complete events
    on a synthetic per-request track plus a flow s/f pair."""
    tracer = SpanTracer(str(tmp_path), process_name="unit")
    clock = time.monotonic
    rt = RequestTracer(tracer=tracer, clock=clock)
    req = Request(rid=7, prompt=[0, 1, 2], max_new=4)
    req.submit_t = clock()
    rt.begin(req)
    rt.mark(req, "queued")
    rt.mark(req, "decode")
    rt.mark(req, "decode")
    req.prompt_len, req.first_token_t = 3, clock()
    req.finish_t = clock()
    rt.retire(req)
    path = tracer.close()
    evs = json.load(open(path))["traceEvents"]
    req_evs = [e for e in evs if e.get("cat") == "request"]
    assert {e["ph"] for e in req_evs} == {"X", "s", "f"}
    xs = [e for e in req_evs if e["ph"] == "X"]
    assert any(e["name"] == "req7:decode" and e["args"]["count"] == 2
               for e in xs)
    # synthetic track, not a host thread id
    assert all(e["tid"] >= 1_000_000 for e in req_evs)


# ---------------------------------------------------- the flight recorder

def test_flight_ring_bound_holds_under_sustained_load(tmp_path):
    fl = FlightRecorder(str(tmp_path), maxlen=64)
    for i in range(10_000):
        fl.record("ev", i=i)
    assert len(fl) == 64 and fl.recorded == 10_000
    path = fl.dump({"kind": "unit"}, tag="unit")
    doc = json.load(open(path))
    assert len(doc["ring"]) == 64
    # the ring holds the MOST RECENT events, oldest first
    assert doc["ring"][0]["i"] == 10_000 - 64
    assert doc["ring"][-1]["i"] == 9_999
    assert doc["trigger"]["kind"] == "unit"
    assert doc["recorded_total"] == 10_000


def test_flight_dump_cap(tmp_path):
    fl = FlightRecorder(str(tmp_path), maxlen=8, max_dumps=2)
    fl.record("ev")
    assert fl.dump({"kind": "a"}) and fl.dump({"kind": "b"})
    assert fl.dump({"kind": "c"}) is None        # capped
    assert fl.dumps_skipped == 1
    assert len(glob.glob(str(tmp_path / "flightdump_*.json"))) == 2


def test_flight_dump_write_failure_is_contained(tmp_path):
    """A diagnostic artifact must never kill the run it diagnoses: a
    dump whose write fails (dump dir's parent is a FILE — robust as
    root) returns None, counts a failure, and does not occupy a
    max_dumps slot or report a phantom path."""
    blocker = tmp_path / "blocker"
    blocker.write_text("i am a file")
    fl = FlightRecorder(str(blocker / "dumps"), maxlen=8, max_dumps=2)
    fl.record("ev")
    assert fl.dump({"kind": "a"}) is None
    assert fl.dump_failures == 1 and fl.dumps == []
    assert fl.dumps_skipped == 0           # a failure is not a cap skip


def test_pool_exhausted_preemption_dumps_flight(tmp_path):
    """The acceptance pin: a forced PoolExhausted preemption produces a
    flight dump whose trigger names the victim and whose ring holds the
    preceding scheduler/pool history."""
    mesh, model, params = _setup(seed=3)
    fl = FlightRecorder(str(tmp_path), maxlen=128)
    eng = PagedEngine(model, mesh, params, num_slots=3, buf_len=BUF,
                      eos_id=EOS, page_size=8, num_pages=4, prefill_chunk=8,
                      flight=fl)
    for i, p in enumerate([[0, 5, 9, 60, 2, 8, 33], [0, 11, 4, 7, 21, 35, 2],
                           [0, 44, 17, 8, 52, 3, 71]]):
        eng.submit(Request(rid=i, prompt=p, max_new=12))
    eng.run_to_completion()
    assert eng.preemptions >= 1
    dumps = sorted(glob.glob(str(tmp_path / "flightdump_pool_exhausted_*")))
    assert dumps, "PoolExhausted preemption produced no flight dump"
    doc = json.load(open(dumps[0]))
    assert doc["trigger"]["kind"] == "pool_exhausted_preempt"
    assert "victim_rid" in doc["trigger"]
    kinds = {ev["kind"] for ev in doc["ring"]}
    # the preceding ring context: admissions AND the preemption decision
    assert "sched_submit" in kinds and "preempt" in kinds, kinds
    assert "pool_exhausted" in kinds, kinds


def test_sentinel_halt_dumps_and_cross_links_flight(tmp_path):
    fl = FlightRecorder(str(tmp_path), maxlen=32)
    fl.record("heartbeat", step=1)
    fl.record("span", bucket="step")
    s = HealthSentinel(str(tmp_path), flight=fl)
    s.check(0, 2.0)
    with pytest.raises(TrainingHealthError) as ei:
        s.check(5, float("nan"))
    sent = json.load(open(ei.value.dump_path))
    flight_path = sent["flight_dump"]
    assert flight_path and os.path.exists(flight_path)
    doc = json.load(open(flight_path))
    assert doc["trigger"]["kind"] == "sentinel_nonfinite"
    assert doc["trigger"]["sentinel_dump"] == ei.value.dump_path
    assert {"heartbeat", "span"} <= {ev["kind"] for ev in doc["ring"]}


def test_watchdog_stall_dumps_and_cross_links_flight(tmp_path):
    fl = FlightRecorder(str(tmp_path), maxlen=32)
    fl.record("heartbeat", step=7)
    stalls = []
    wd = HangWatchdog(timeout_s=0.08, poll_s=0.02, flight=fl,
                      on_stall=lambda rec: stalls.append(rec))
    try:
        wd.beat(step=7)
        deadline = time.monotonic() + 5.0
        while not stalls and time.monotonic() < deadline:
            time.sleep(0.02)
        assert stalls
        flight_path = stalls[0]["flight_dump"]
        assert flight_path and os.path.exists(flight_path)
        doc = json.load(open(flight_path))
        assert doc["trigger"]["kind"] == "watchdog_stall"
        assert doc["trigger"]["last_step"] == 7
    finally:
        wd.close()


# --------------------------------------------- loadgen exemplars + summary

def test_loadgen_exemplars_and_summarize_waterfall(tmp_path):
    """The e2e acceptance pin: a traced loadgen run surfaces the k-worst
    TTFT/TPOT requests WITH timelines, and summarize_run.py renders the
    waterfall (plus flight-dump pointers when one exists)."""
    mesh, model, params = _setup(seed=4)
    writer = MetricsWriter(str(tmp_path), process_index=0)
    fl = FlightRecorder(str(tmp_path), maxlen=64)
    rt = RequestTracer(writer=writer, flight=fl)
    eng = PagedEngine(model, mesh, params, num_slots=3, buf_len=BUF,
                      eos_id=EOS, page_size=8, num_pages=4, prefill_chunk=8,
                      request_tracer=rt, flight=fl, writer=writer)
    reqs = synthetic_requests(5, 4, 10, 10, CFG.vocab_size, seed=2,
                              arrival="burst")
    summary = run_loadgen(eng, reqs, sleep=lambda s: None)
    writer.close()
    assert summary["completed"] == 5
    assert len(summary["worst_ttft_rids"]) == 3
    recs = [json.loads(l) for l in open(tmp_path / "metrics.jsonl")]
    (ex,) = [r for r in recs if r["tag"] == "request_exemplars"]
    assert not validate_record(ex)
    worst = ex["worst_ttft"]
    assert worst[0]["timeline"], worst
    # worst-first ordering
    ttfts = [w["ttft_ms"] for w in worst]
    assert ttfts == sorted(ttfts, reverse=True)
    sr = _load_script("summarize_run")
    text = sr.summarize(str(tmp_path))
    assert "Slowest requests" in text
    assert f"worst TTFT rid {worst[0]['rid']}" in text
    if fl.dumps:
        assert "flight dump" in text.lower()


def test_summarize_renders_flight_and_skew_sections(tmp_path):
    """Synthetic metrics + a flight dump: the summary grows the flight
    pointer and per-rank skew table sections, and schema drift is LOUD."""
    fl = FlightRecorder(str(tmp_path), maxlen=8)
    fl.record("pool_stats", live=3)
    fl.dump({"kind": "slo_attainment_collapse", "slo_class": "interactive"},
            tag="slo_collapse")
    # two ranks' phase stats; p1 is a data_wait straggler
    with MetricsWriter(str(tmp_path), process_index=0) as w:
        w.event("rank_phase_stats", process=0,
                phases_s={"data_wait": 1.0, "step": 10.0}, steps=100,
                tokens=1000, wall_s=12.0)
    with MetricsWriter(str(tmp_path), process_index=1) as w:
        w.event("rank_phase_stats", process=1,
                phases_s={"data_wait": 5.0, "step": 10.2}, steps=100,
                tokens=1000, wall_s=16.0)
    # a drifted record: missing required field + no schema_version
    with open(tmp_path / "metrics.proc9.jsonl", "w") as f:
        f.write(json.dumps({"tag": "request_trace", "ts": 0.0}) + "\n")
    sr = _load_script("summarize_run")
    text = sr.summarize(str(tmp_path))
    assert "slo_attainment_collapse" in text
    assert "Cross-rank phase skew" in text
    assert "straggler suspect: p1" in text and "data_wait" in text
    assert "SCHEMA DRIFT" in text and "missing schema_version" in text


# ------------------------------------------------- cross-rank attribution

def test_rank_skew_ranks_stragglers():
    recs = [
        {"process": 0, "phases_s": {"data_wait": 1.0, "h2d": 0.5,
                                    "step": 10.0}, "steps": 100},
        {"process": 1, "phases_s": {"data_wait": 4.0, "h2d": 0.5,
                                    "step": 10.1}, "steps": 100},
        {"process": 2, "phases_s": {"data_wait": 1.1, "h2d": 0.5,
                                    "step": 9.9}, "steps": 100},
    ]
    rep = rank_skew(recs, tol=0.2)
    assert rep["ranks"] == 3
    assert rep["suspects"][0] == {"process": 1, "phase": "data_wait",
                                  "excess_s": pytest.approx(1.9667,
                                                            abs=1e-3),
                                  "ratio": pytest.approx(1.9672, abs=1e-3)}
    assert rep["phases"]["data_wait"]["max_process"] == 1
    # one skewed phase only -> not persistent
    assert rep["persistent"] == []
    # a rank slow in TWO phases IS persistent
    recs[1]["phases_s"]["h2d"] = 2.0
    rep = rank_skew(recs, tol=0.2)
    assert rep["persistent"] == [1]
    # nothing to compare with one record — or with two records from the
    # SAME process (a re-run staged script's duplicate events must not
    # render a fake one-rank "cross-rank" table)
    assert rank_skew(recs[:1]) is None
    assert rank_skew([recs[0], dict(recs[0])]) is None


# ------------------------------------------------------ schema validation

def test_metrics_events_carry_schema_version_and_validate(tmp_path):
    with MetricsWriter(str(tmp_path), process_index=0) as w:
        w.scalar("train/x", 1.0, 1)  # scalars stay unversioned
        w.event("goodput_summary", wall_s=1.0, buckets_s={}, goodput=0.5,
                steps=10)
    assert validate_jsonl(str(tmp_path / "metrics.jsonl")) == []
    recs = [json.loads(l) for l in open(tmp_path / "metrics.jsonl")]
    assert "schema_version" not in recs[0]
    assert recs[1]["schema_version"] == EVENT_SCHEMA_VERSION


def test_schema_validator_fails_loudly_on_drift(tmp_path):
    bad = tmp_path / "metrics.jsonl"
    with open(bad, "w") as f:
        f.write(json.dumps({"tag": "serving_summary", "ts": 0.0,
                            "schema_version": EVENT_SCHEMA_VERSION,
                            "requests": 4}) + "\n")      # missing fields
        f.write(json.dumps({"tag": "goodput_summary", "ts": 0.0,
                            "wall_s": 1.0, "buckets_s": {}, "goodput": 1.0,
                            "steps": 1}) + "\n")         # pre-versioned
        f.write(json.dumps({"tag": "cost_analysis", "ts": 0.0, "flops": 1,
                            "schema_version": EVENT_SCHEMA_VERSION + 5})
                + "\n")                                  # future version
        f.write("{torn json\n")
    problems = "\n".join(validate_jsonl(str(bad)))
    assert "missing required field 'completed'" in problems
    assert "missing schema_version" in problems
    assert "NEWER than this reader" in problems
    assert "unparseable JSON" in problems


# ------------------------------------------------- the regression gate

GATE = None


def _gate():
    global GATE
    if GATE is None:
        GATE = _load_script("check_bench_regression")
    return GATE


# a synthetic driver-style record (tests/forensics_fixtures/repo/README.md)
BASE_REC = os.path.join(REPO, "tests", "forensics_fixtures", "repo",
                        "BENCH_r01.json")


def test_gate_passes_on_a_record_vs_itself(capsys):
    rc = _gate().main(["--fresh", BASE_REC, "--baseline", BASE_REC])
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["status"] == "ok" and out["checks"]


def test_gate_fails_on_degraded_record(tmp_path, capsys):
    base = json.load(open(BASE_REC))["parsed"]
    degraded = dict(base, value=base["value"] * 0.7,
                    vs_baseline=base["vs_baseline"] * 0.7)
    p = tmp_path / "degraded.json"
    p.write_text(json.dumps(degraded))
    rc = _gate().main(["--fresh", str(p), "--baseline", BASE_REC])
    assert rc == 1
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["status"] == "regression"
    assert any(not c["ok"] for c in out["checks"])
    # within-tolerance wobble still passes
    ok = dict(base, value=base["value"] * 0.95)
    p.write_text(json.dumps(ok))
    assert _gate().main(["--fresh", str(p), "--baseline", BASE_REC]) == 0
    # no baseline named: nothing to compare with, said so, passing
    assert _gate().main(["--fresh", str(p)]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["status"] == "no_baseline"


def test_gate_skips_on_backend_unavailable(tmp_path, capsys):
    p = tmp_path / "outage.json"
    p.write_text(json.dumps({"metric": "bench",
                             "error": "backend_unavailable",
                             "detail": "no backend"}))
    rc = _gate().main(["--fresh", str(p)])
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["status"] == "skip" and out["reason"] == "backend_unavailable"
    # a NON-outage error is a real failure, not a skip
    p.write_text(json.dumps({"metric": "bench", "error": "oom"}))
    assert _gate().main(["--fresh", str(p)]) == 1


def test_gate_serving_latency_direction(tmp_path, capsys):
    """Serving records gate BOTH ways: throughput down OR p95 up past
    tolerance fails; no comparable baseline passes with a note."""
    base = {"metric": "serving x", "value": 1000.0,
            "unit": "tokens/sec (serving)", "vs_baseline": 2.0,
            "ttft_ms_p95": 100.0, "tpot_ms_p95": 10.0}
    bp = tmp_path / "base.json"
    bp.write_text(json.dumps(base))
    worse = dict(base, ttft_ms_p95=200.0)   # latency doubled, rate held
    fp = tmp_path / "fresh.json"
    fp.write_text(json.dumps(worse))
    assert _gate().main(["--fresh", str(fp), "--baseline", str(bp)]) == 1
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    bad = [c for c in out["checks"] if not c["ok"]]
    assert bad and bad[0]["field"] == "ttft_ms_p95"
    # no same-unit baseline at all -> pass with status no_baseline
    assert _gate().main(["--fresh", str(fp), "--baseline"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["status"] == "no_baseline"


# --------------------------------------------------------- CLI coverage

def test_serve_dry_run_with_tracing_and_flight(tmp_path, capsys):
    """--dry_run --paged --trace_requests --flight_records: the CLI smoke
    that keeps the flags from rotting on chip-less images. Every request
    gets a versioned request_trace event; exemplars land in the summary
    record."""
    from distributed_pytorch_from_scratch_tpu.serving import serve as srv
    log_dir = str(tmp_path / "logs")
    srv.main(["--dry_run", "--paged", "--trace_requests",
              "--flight_records", "--log_dir", log_dir])
    out = capsys.readouterr().out.strip().splitlines()
    rec = json.loads(out[-1])
    assert rec["trace_requests"] is True
    assert len(rec["worst_ttft_rids"]) >= 1
    recs = [json.loads(l)
            for l in open(os.path.join(log_dir, "metrics.jsonl"))]
    traces = [r for r in recs if r["tag"] == "request_trace"]
    assert len(traces) == rec["completed"]
    assert not any(p for r in traces for p in validate_record(r))
    assert any(r["tag"] == "request_exemplars" for r in recs)


def test_serve_flight_ring_zero_disables(tmp_path, capsys):
    """--flight_ring 0 disables the recorder (train.py semantics) —
    not a ValueError at engine construction."""
    from distributed_pytorch_from_scratch_tpu.serving import serve as srv
    srv.main(["--dry_run", "--paged", "--flight_records", "--flight_ring",
              "0", "--log_dir", str(tmp_path / "logs")])
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "flight_dumps" not in rec       # recorder was off


def test_serve_refuses_unwritable_trace_dir(tmp_path):
    """Loud refusal, not a silent traceless run: a log_dir that cannot be
    created (parent is a FILE — robust even when running as root, which
    ignores permission bits) dies before any engine work."""
    blocker = tmp_path / "blocker"
    blocker.write_text("i am a file")
    from distributed_pytorch_from_scratch_tpu.serving import serve as srv
    with pytest.raises(SystemExit) as ei:
        srv.main(["--dry_run", "--paged", "--trace_requests",
                  "--log_dir", str(blocker / "logs")])
    assert "not writable" in str(ei.value)


def test_bench_serving_flags_refused_without_serving():
    import bench
    with pytest.raises(SystemExit):
        bench.parse_args(["--trace_requests"])
    with pytest.raises(SystemExit):
        bench.parse_args(["--flight_records"])
    args = bench.parse_args(["--serving", "--trace_requests",
                             "--flight_records", "--obs_dir", "/tmp/x"])
    assert args.trace_requests and args.flight_records
