"""obs v3 (ISSUE 12): the live telemetry plane.

The acceptance criteria pinned here:
* the exporter endpoint serves the registry as JSON and Prometheus text
  for train + both serving engines, refusing a busy port loudly;
* fleet rollup math equals hand-computed completion-weighted attainment
  across 2 fake procs;
* a request whose trace BEGAN in another process merges into ONE
  contiguous waterfall (span sum == measured wall) after clock-offset
  translation — with a deliberately skewed clock;
* an anomaly flight dump cross-links a `jax.profiler` capture that
  actually exists on disk;
* MetricsWriter size rotation chains through schema-valid `rotated`
  events that the collector tailer follows, and a torn trailing line is
  held + resynced (never dropped, never double-counted);
* exporter+collector overhead on a traced loadgen run stays within
  budget of the obs-off run (the 2% pin is asserted on-chip by the
  staged session; CPU CI pins a generous bound against pathology).
"""

import glob
import importlib.util
import json
import os
import socket
import threading
import time
import urllib.request

import jax
import pytest

from distributed_pytorch_from_scratch_tpu.config import (MeshConfig,
                                                         ModelConfig)
from distributed_pytorch_from_scratch_tpu.models.transformer import (
    Transformer)
from distributed_pytorch_from_scratch_tpu.obs import (
    EVENT_SCHEMA_VERSION, FleetCollector, FlightRecorder, JsonlTailer,
    RequestTracer, TelemetryExporter, TraceContext, fleet_slo_attainment,
    merge_traces, validate_jsonl, validate_record)
from distributed_pytorch_from_scratch_tpu.runtime.mesh import make_mesh
from distributed_pytorch_from_scratch_tpu.serving.engine import (
    ContinuousBatchingEngine, PagedEngine, Request)
from distributed_pytorch_from_scratch_tpu.serving.loadgen import (
    run_loadgen, synthetic_requests)
from distributed_pytorch_from_scratch_tpu.training.metrics import (
    AnomalyProfiler, MetricsWriter)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = ModelConfig(attn_dim=32, ffn_dim=64, num_heads=8, num_layers=2,
                  vocab_size=96, maxlen=64)
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
    spec = importlib.util.spec_from_file_location(f"_tel_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _get(port, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=5.0) as r:
        return r.read().decode()


# ------------------------------------------------------ exporter endpoint

def test_exporter_endpoint_json_and_prometheus(tmp_path):
    with MetricsWriter(str(tmp_path), process_index=0) as w:
        tel = TelemetryExporter(writer=w, process_index=0,
                                rollup_interval=0.05)
        port = tel.start(0)
        tel.gauge("serve/kv_util", 0.75)
        tel.counter("slo/interactive/completed", 8)
        tel.count("serve/errors")
        snap = json.loads(_get(port, "/metrics.json"))
        assert snap["gauges"]["serve/kv_util"] == 0.75
        assert snap["counters"]["slo/interactive/completed"] == 8
        assert snap["counters"]["serve/errors"] == 1
        prom = _get(port, "/metrics")
        # names sanitized, process label attached, both metric types
        assert '# TYPE serve_kv_util gauge' in prom
        assert 'serve_kv_util{process="0"} 0.75' in prom
        assert '# TYPE slo_interactive_completed counter' in prom
        # the snapshot thread mirrors into metrics.jsonl
        deadline = time.monotonic() + 5.0
        while tel.snapshots == 0 and time.monotonic() < deadline:
            time.sleep(0.02)
        tel.close()
    recs = [json.loads(l) for l in open(tmp_path / "metrics.jsonl")]
    snaps = [r for r in recs if r["tag"] == "telemetry_snapshot"]
    assert snaps, "no telemetry_snapshot events mirrored"
    assert not any(p for r in snaps for p in validate_record(r))
    assert snaps[-1]["gauges"]["serve/kv_util"] == 0.75


def test_exporter_rate_smooths_counter_into_per_second_gauge():
    clock = [0.0]
    tel = TelemetryExporter(clock=lambda: clock[0])
    tel.rate("serve/tokens_per_sec", 0)
    clock[0] = 1.0
    tel.rate("serve/tokens_per_sec", 100)     # 100 tok/s instantaneous
    snap = tel.snapshot()
    assert snap["gauges"]["serve/tokens_per_sec"] == pytest.approx(100.0)
    assert snap["counters"]["serve/tokens_per_sec_total"] == 100
    clock[0] = 2.0
    tel.rate("serve/tokens_per_sec", 400)     # 300 tok/s -> EWMA between
    v = tel.snapshot()["gauges"]["serve/tokens_per_sec"]
    assert 100.0 < v < 300.0


def test_exporter_busy_port_refuses_loudly():
    blocker = socket.socket()
    try:
        blocker.bind(("127.0.0.1", 0))
        port = blocker.getsockname()[1]
        tel = TelemetryExporter()
        with pytest.raises(SystemExit) as ei:
            tel.start(port)
        assert "cannot bind" in str(ei.value)
    finally:
        blocker.close()


# ------------------------------------------- rotation + the tailer chain

def test_metrics_rotation_chains_through_schema_valid_events(tmp_path):
    with MetricsWriter(str(tmp_path), process_index=0, max_bytes=512) as w:
        for i in range(40):
            w.event("serve_request", rid=i, generated=2)
    gens = sorted(glob.glob(str(tmp_path / "metrics*.jsonl")))
    assert len(gens) > 2, gens                     # it actually rotated
    # every generation validates (the rotated event is schema-valid)
    for g in gens:
        assert validate_jsonl(g) == [], g
    # the chain visits every record exactly once, in order
    t = JsonlTailer(str(tmp_path / "metrics.jsonl"))
    recs = t.poll()
    assert [r["rid"] for r in recs] == list(range(40))
    assert t.rotations == len(gens) - 1
    # the base file's last line is the rotated event naming generation 1
    base_last = json.loads(
        open(tmp_path / "metrics.jsonl").read().splitlines()[-1])
    assert base_last["tag"] == "rotated"
    assert base_last["next"] == "metrics.001.jsonl"


def test_tailer_holds_torn_line_and_resyncs(tmp_path):
    """The satellite pin: a torn trailing jsonl line mid-tail is HELD and
    completed by the next flush — not dropped, not double-counted."""
    p = tmp_path / "metrics.jsonl"
    l1 = json.dumps({"tag": "serve_request", "rid": 0, "generated": 1,
                     "schema_version": EVENT_SCHEMA_VERSION})
    l2 = json.dumps({"tag": "serve_request", "rid": 1, "generated": 2,
                     "schema_version": EVENT_SCHEMA_VERSION})
    with open(p, "w") as f:
        f.write(l1 + "\n" + l2[:17])          # torn mid-record
    t = JsonlTailer(str(p))
    first = t.poll()
    assert [r["rid"] for r in first] == [0]   # the whole record only
    assert t.torn_holds == 1
    assert t.poll() == []                     # still torn: nothing new
    with open(p, "a") as f:
        f.write(l2[17:] + "\n")               # the flush completes it
    second = t.poll()
    assert [r["rid"] for r in second] == [1]  # exactly once
    assert t.poll() == []
    assert t.invalid == 0


def test_tailer_refuses_rotation_cycle(tmp_path):
    """A corrupt/hand-edited chain whose `rotated` event points back at
    an already-read file must terminate the poll (counted as drift), not
    spin it forever."""
    p = tmp_path / "metrics.jsonl"
    p.write_text(json.dumps(
        {"tag": "rotated", "ts": 0.0,
         "schema_version": EVENT_SCHEMA_VERSION,
         "next": "metrics.jsonl", "generation": 1}) + "\n")
    t = JsonlTailer(str(p))
    assert t.poll() == []
    assert t.invalid == 1 and t.rotations == 0


def test_merge_keeps_span_durations_on_overlap():
    """The one-way handshake cannot separate transfer latency from clock
    skew, so an origin's post-export residual can land ON TOP of the
    adopter's first activity: the merge must shift the later span
    forward with its measured duration intact, never trim it."""
    clockA, clockB = [0.0], [0.0]
    rtA = RequestTracer(clock=lambda: clockA[0],
                        wall=lambda: 100.0 + clockA[0], process_index=0)
    rtB = RequestTracer(clock=lambda: clockB[0],
                        wall=lambda: 100.0 + clockB[0], process_index=1)
    ra = _FakeReq(1)
    ra.submit_t = 0.0
    rtA.begin(ra)
    clockA[0] = 0.050
    rtA.mark(ra, "prefill_chunk")
    ctx = rtA.export_context(ra)
    clockA[0] = 0.060                  # 10ms of post-export bookkeeping
    recA = rtA.retire(ra, t=clockA[0])
    rb = _FakeReq(1)
    rb.submit_t = 0.0
    rtB.begin(rb, ctx=ctx)             # adoption pinned to the export stamp
    clockB[0] = 0.040
    rtB.mark(rb, "decode")
    rb.finish_t = 0.040
    recB = rtB.retire(rb)
    m = merge_traces([recA, recB])
    decode = [s for s in m["spans"] if s["name"] == "decode"]
    assert decode and decode[0]["dur_ms"] == pytest.approx(40.0, abs=0.1)
    assert sum(s["dur_ms"] for s in m["spans"]) == pytest.approx(
        m["total_ms"], abs=0.01)
    # total = every process's measured activity: 60ms in A + 40ms in B
    assert m["total_ms"] == pytest.approx(100.0, abs=0.5)


def test_train_and_bench_refuse_bad_rollup_interval():
    from distributed_pytorch_from_scratch_tpu.train import get_train_args
    with pytest.raises(SystemExit):
        get_train_args(["--data_path", "x", "--metrics_port", "0",
                        "--rollup_interval", "0"])
    import bench
    with pytest.raises(SystemExit):
        bench.parse_args(["--serving", "--metrics_port", "0",
                          "--rollup_interval", "0"])


# --------------------------------------------------- fleet rollup math

def test_fleet_rollup_matches_hand_computed_attainment(tmp_path):
    """2 fake procs: completion-weighted fleet attainment, summed
    tokens/s, aggregated pool — against hand math."""
    d0, d1 = tmp_path / "p0", tmp_path / "p1"
    for d, proc, tps, cls_counts, pages in (
            (d0, 0, 120.0, {"interactive": (10, 9), "batch": (4, 4)},
             (6, 16)),
            (d1, 1, 80.0, {"interactive": (40, 10)}, (10, 16))):
        with MetricsWriter(str(d), process_index=proc) as w:
            counters = {}
            for cls, (c, h) in cls_counts.items():
                counters[f"slo/{cls}/completed"] = c
                counters[f"slo/{cls}/hit"] = h
            w.event("telemetry_snapshot", process=proc,
                    gauges={"serve/tokens_per_sec": tps,
                            "serve/pages_in_use": pages[0],
                            "serve/num_pages": pages[1]},
                    counters=counters)
    c = FleetCollector([str(d0), str(d1)],
                       out_path=str(tmp_path / "fleet_rollup.jsonl"))
    assert c.poll() == 2
    r = c.emit()
    assert r["procs"] == 2
    assert r["tokens_per_sec"] == pytest.approx(200.0)
    # hand-computed: interactive (10+40 completed, 9+10 hit) = 19/50
    assert r["slo_attainment"]["interactive"] == {
        "completed": 50, "attained": pytest.approx(0.38)}
    assert r["slo_attainment"]["batch"] == {
        "completed": 4, "attained": 1.0}
    assert r["pool"]["pages_in_use"] == 16 and r["pool"]["num_pages"] == 32
    # the emitted event is schema-valid and lands in the rollup file
    recs = [json.loads(l)
            for l in open(tmp_path / "fleet_rollup.jsonl")]
    assert recs[-1]["tag"] == "fleet_rollup"
    assert not validate_record(recs[-1])


def test_fleet_slo_attainment_pure_math():
    out = fleet_slo_attainment([{"a": (10, 9)}, {"a": (40, 10), "b": (2, 1)}])
    assert out == {"a": {"completed": 50, "attained": 0.38},
                   "b": {"completed": 2, "attained": 0.5}}
    assert fleet_slo_attainment([]) == {}


def test_collector_online_rank_skew(tmp_path):
    """rank_phase_stats from 2 procs surface as the rollup's rank_skew."""
    for proc, dw in ((0, 1.0), (1, 6.0)):
        with MetricsWriter(str(tmp_path), process_index=proc) as w:
            w.event("rank_phase_stats", process=proc,
                    phases_s={"data_wait": dw, "step": 10.0}, steps=50,
                    tokens=500, wall_s=12.0)
    c = FleetCollector([str(tmp_path)])
    c.poll()
    r = c.rollup()
    assert r["rank_skew"]["suspects"][0]["process"] == 1
    assert r["rank_skew"]["suspects"][0]["phase"] == "data_wait"


def test_obs_top_once_renders_and_emits(tmp_path, capsys):
    with MetricsWriter(str(tmp_path), process_index=0) as w:
        w.event("telemetry_snapshot", process=0,
                gauges={"serve/tokens_per_sec": 42.0},
                counters={"slo/interactive/completed": 4,
                          "slo/interactive/hit": 2})
    top = _load_script("obs_top")
    assert top.main([str(tmp_path), "--once"]) == 0
    out = capsys.readouterr().out
    assert "fleet: 1 proc(s)" in out
    assert "interactive 50% of 4" in out
    assert os.path.exists(tmp_path / "fleet_rollup.jsonl")


def test_collector_bounds_hung_endpoint_and_counts_it(tmp_path):
    """Scrape liveness (ISSUE 19): an endpoint that ACCEPTS but never
    responds must not hang the poll loop — the scrape is bounded by
    `scrape_timeout` and the proc counts as unresponsive (mirroring the
    hbm rollup's procs_unavailable: loud, never a folded zero)."""
    import threading

    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    port = srv.getsockname()[1]
    held = []

    def hold_open():
        try:
            conn, _ = srv.accept()
            held.append(conn)               # never respond, never close
        except OSError:
            pass

    threading.Thread(target=hold_open, daemon=True).start()
    try:
        c = FleetCollector(
            [str(tmp_path)],
            endpoints=[f"http://127.0.0.1:{port}/metrics.json"],
            scrape_timeout=0.2)
        t0 = time.monotonic()
        c.poll()
        assert time.monotonic() - t0 < 2.0  # bounded, not hung
        assert c.procs_unresponsive == 1
        assert c.unresponsive_scrapes == 1
        assert c.rollup()["procs_unresponsive"] == 1
        c.poll()                            # still hung: cumulative grows
        assert c.procs_unresponsive == 1
        assert c.unresponsive_scrapes == 2
    finally:
        for conn in held:
            conn.close()
        srv.close()
    with pytest.raises(ValueError):
        FleetCollector([str(tmp_path)], endpoints=["http://x"],
                       scrape_timeout=0.0)


# ------------------------------------- cross-process waterfall (tentpole)

class _FakeReq:
    def __init__(self, rid):
        self.rid = rid
        self.trace_id = None
        self.prompt = [3, 4, 5]
        self.prompt_len = 3
        self.tokens = []
        self.submit_t = None
        self.first_token_t = None
        self.finish_t = None
        self.ttft_s = None
        self.tpot_s = None
        self.preemptions = 0
        self.tenant = "t0"
        self.slo_class = None


def test_crossproc_waterfall_merges_with_deliberate_clock_offset(tmp_path):
    """The acceptance pin: a request whose trace BEGAN in process 0 and
    finished in process 1 — whose wall clock is deliberately 1007.3s
    ahead — renders as ONE contiguous waterfall whose span sum equals
    the measured cross-process wall after offset translation."""
    skew = 1007.3
    clockA, clockB = [0.0], [0.0]
    rtA = RequestTracer(clock=lambda: clockA[0],
                        wall=lambda: 1000.0 + clockA[0], process_index=0)
    rtB = RequestTracer(clock=lambda: clockB[0],
                        wall=lambda: 1000.0 + skew + clockB[0],
                        process_index=1)
    # process 0: submit -> queued -> prefill_chunk -> handoff
    ra = _FakeReq(5)
    ra.submit_t = 0.0
    rtA.begin(ra)
    clockA[0] = 0.010
    rtA.mark(ra, "queued")
    clockA[0] = 0.050
    rtA.mark(ra, "prefill_chunk", positions=3)
    ctx = rtA.export_context(ra)
    recA = rtA.retire(ra, t=clockA[0])
    wire = ctx.to_wire()                       # serializable contract
    assert json.loads(json.dumps(wire)) == wire
    # process 1 adopts 5ms of transfer later (on ITS skewed clock)
    clockB[0] = 0.0
    rb = _FakeReq(5)
    rb.submit_t = 0.0
    rtB.begin(rb, ctx=TraceContext.from_wire(wire))
    assert rb.trace_id == ra.trace_id
    clockB[0] = 0.020
    rtB.mark(rb, "decode")
    clockB[0] = 0.040
    rtB.mark(rb, "decode")
    rb.finish_t = 0.040
    rb.tokens = [7, 8]
    recB = rtB.retire(rb)
    # the raw records carry the handshake: B's offset cancels the skew
    # (modulo the 50ms of genuine elapsed time the fake clocks encode —
    # B's clock was still at 0 when A exported at 0.050)
    assert recB["clock_offset_ms"] == pytest.approx(-(skew - 0.050) * 1e3,
                                                    abs=1.0)
    m = merge_traces([recA, recB])
    # contiguous: spans chain with no gap/overlap, sum == total EXACTLY
    cursor = 0.0
    for s in m["spans"]:
        assert s["start_ms"] == pytest.approx(cursor, abs=0.01)
        cursor += s["dur_ms"]
    assert cursor == pytest.approx(m["total_ms"], abs=0.01)
    # total == measured wall in the ROOT timebase: 50ms in A + 40ms in B
    assert m["total_ms"] == pytest.approx(90.0, abs=0.5)
    assert m["processes"] == [0, 1]
    names = [s["name"] for s in m["spans"]]
    assert names[0] == "queued" and "decode" in names
    assert m["generated"] == 2


def test_summarize_renders_crossproc_waterfall(tmp_path):
    """The two processes' request_trace events land in (proc-tagged)
    metrics files; summarize_run merges + renders them as one line."""
    clockA, clockB = [0.0], [0.0]
    wA = MetricsWriter(str(tmp_path), process_index=0)
    wB = MetricsWriter(str(tmp_path), process_index=1)
    rtA = RequestTracer(writer=wA, clock=lambda: clockA[0],
                        wall=lambda: 500.0 + clockA[0], process_index=0)
    rtB = RequestTracer(writer=wB, clock=lambda: clockB[0],
                        wall=lambda: 777.0 + clockB[0], process_index=1)
    ra = _FakeReq(3)
    ra.submit_t = 0.0
    rtA.begin(ra)
    clockA[0] = 0.030
    rtA.mark(ra, "prefill_chunk")
    ctx = rtA.export_context(ra)
    rtA.retire(ra, t=clockA[0])
    rb = _FakeReq(3)
    rb.submit_t = 0.0
    rtB.begin(rb, ctx=ctx)
    clockB[0] = 0.025
    rtB.mark(rb, "decode")
    rb.finish_t = 0.025
    rtB.retire(rb)
    wA.close()
    wB.close()
    sr = _load_script("summarize_run")
    text = sr.summarize(str(tmp_path))
    assert "Cross-process request waterfalls" in text
    assert "across p0 -> p1" in text
    assert "prefill_chunk" in text and "decode" in text


def test_engine_adopts_wire_context_on_submit(tmp_path):
    """The engine-side contract the router PR will use: a Request
    carrying `trace_ctx` CONTINUES the origin trace instead of opening a
    new one, and the retired record links back to the origin."""
    mesh, model, params = _setup(seed=3)
    rt = RequestTracer(process_index=1)
    eng = PagedEngine(model, mesh, params, num_slots=2, buf_len=BUF,
                      eos_id=EOS, page_size=8, prefill_chunk=8,
                      request_tracer=rt)
    ctx = TraceContext(trace_id="r7.1", rid=7, parent_span="route",
                       origin_process=0, handoff_wall=time.time())
    req = Request(rid=7, prompt=[3, 5, 9], max_new=4,
                  trace_ctx=ctx.to_wire())
    eng.submit(req)
    eng.run_to_completion()
    rec = rt.timeline(7)
    assert rec["trace_id"] == "r7.1" and req.trace_id == "r7.1"
    assert rec["origin"] == {"parent_span": "route", "origin_process": 0}
    assert rec["process"] == 1
    assert abs(rec["clock_offset_ms"]) < 5_000  # same host: near zero


# ------------------------------- anomaly -> profiler window (tentpole)

def test_anomaly_dump_cross_links_profiler_capture(tmp_path):
    """The acceptance pin: a forced PoolExhausted preemption (and the
    online SLO-collapse path) produces a flight dump whose `profile`
    field names a jax.profiler capture that EXISTS on disk."""
    mesh, model, params = _setup(seed=3)
    prof = AnomalyProfiler(str(tmp_path), window_steps=2)
    fl = FlightRecorder(str(tmp_path), maxlen=128, profiler=prof)
    eng = PagedEngine(model, mesh, params, num_slots=3, buf_len=BUF,
                      eos_id=EOS, page_size=8, num_pages=4,
                      prefill_chunk=8, flight=fl)
    for i, p in enumerate([[0, 5, 9, 60, 2, 8, 33],
                           [0, 11, 4, 7, 21, 35, 2],
                           [0, 44, 17, 8, 52, 3, 71]]):
        eng.submit(Request(rid=i, prompt=p, max_new=12))
    eng.run_to_completion()
    prof.close()
    assert eng.preemptions >= 1
    dumps = sorted(glob.glob(str(tmp_path / "flightdump_pool_exhausted_*")))
    assert dumps
    doc = json.load(open(dumps[0]))
    assert doc["profile"], "dump did not cross-link a profile path"
    assert prof.captures and doc["profile"] == prof.captures[0]
    assert os.path.isdir(doc["profile"]), doc["profile"]
    assert os.listdir(doc["profile"]), "profile capture dir is empty"
    # the capture budget: an anomaly storm profiles once, not per dump
    assert len(prof.captures) == 1


def test_online_slo_collapse_dumps_mid_run(tmp_path):
    """PagedEngine detects attainment collapse DURING the run (not only
    in loadgen's post-run check): an impossible deadline collapses the
    class, the flight freezes once per class, and loadgen does not
    double-dump it."""
    mesh, model, params = _setup(seed=4)
    fl = FlightRecorder(str(tmp_path), maxlen=64)
    eng = PagedEngine(model, mesh, params, num_slots=3, buf_len=BUF,
                      eos_id=EOS, page_size=8, prefill_chunk=8,
                      slo_classes={"interactive": 1e-9, "batch": 60.0},
                      default_class="interactive", flight=fl)
    reqs = synthetic_requests(6, 4, 8, 6, CFG.vocab_size, seed=1,
                              arrival="burst",
                              class_mix={"interactive": 1})
    run_loadgen(eng, reqs, sleep=lambda s: None)
    assert "interactive" in eng.slo_collapsed
    dumps = glob.glob(str(tmp_path / "flightdump_slo_collapse_*"))
    assert len(dumps) == 1, dumps              # once, not once per path
    doc = json.load(open(dumps[0]))
    assert doc["trigger"]["slo_class"] == "interactive"
    assert doc["trigger"]["attained"] < 0.5


# -------------------------------------------- engine + CLI exporter smoke

def _scrape_during_run(eng, reqs, port):
    """Drive the engine inline and scrape the endpoint mid-run (after the
    first decode steps), returning the mid-run snapshot."""
    for r in reqs:
        r.submit_t = time.monotonic()
        eng.submit(r)
    snap = None
    while eng.has_work():
        eng.step()
        if snap is None and eng.decode_steps >= 2:
            snap = json.loads(_get(port, "/metrics.json"))
    return snap


def test_paged_engine_publishes_live_gauges(tmp_path):
    mesh, model, params = _setup(seed=5)
    tel = TelemetryExporter()
    port = tel.start(0)
    eng = PagedEngine(model, mesh, params, num_slots=3, buf_len=BUF,
                      eos_id=EOS, page_size=8, prefill_chunk=8,
                      slo_classes={"standard": 10.0}, telemetry=tel)
    reqs = [Request(rid=i, prompt=[0, 3 + i, 7, 11], max_new=6)
            for i in range(3)]
    snap = _scrape_during_run(eng, reqs, port)
    tel.close()
    assert snap is not None
    g = snap["gauges"]
    assert g["serve/live"] >= 1
    assert g["serve/num_pages"] == eng.pool.num_pages
    assert "serve/pages_in_use" in g and "serve/queue_depth" in g
    assert snap["counters"]["serve/decode_steps"] >= 2
    # completions flow into per-class SLO counters
    final = tel.snapshot()
    assert final["counters"]["slo/standard/completed"] == 3


def test_slot_engine_publishes_live_gauges(tmp_path):
    mesh, model, params = _setup(seed=6)
    tel = TelemetryExporter()
    port = tel.start(0)
    eng = ContinuousBatchingEngine(model, mesh, params, num_slots=2,
                                   buf_len=BUF, eos_id=EOS,
                                   prefill_bucket=8, telemetry=tel)
    reqs = [Request(rid=i, prompt=[0, 5 + i, 9], max_new=6)
            for i in range(3)]
    snap = _scrape_during_run(eng, reqs, port)
    tel.close()
    assert snap is not None
    assert snap["gauges"]["serve/live"] >= 1
    assert snap["counters"]["serve/decode_steps"] >= 2


def test_serve_dry_run_with_telemetry_and_profiler(tmp_path, capsys):
    """--dry_run --paged with the full ISSUE-12 flag set: the CLI smoke
    that keeps the flags from rotting on chip-less images. Snapshot
    events land versioned in metrics.jsonl; the record carries the bound
    port; the SLO collapse (dry-run deadlines are tight) cross-links a
    capture."""
    from distributed_pytorch_from_scratch_tpu.serving import serve as srv
    log_dir = str(tmp_path / "logs")
    srv.main(["--dry_run", "--paged", "--trace_requests",
              "--flight_records", "--metrics_port", "0",
              "--rollup_interval", "0.2", "--profile_on_anomaly", "2",
              "--log_dir", log_dir])
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["metrics_port"] > 0
    assert rec["telemetry_snapshots"] >= 1
    recs = [json.loads(l)
            for l in open(os.path.join(log_dir, "metrics.jsonl"))]
    snaps = [r for r in recs if r["tag"] == "telemetry_snapshot"]
    assert snaps and not any(p for r in snaps for p in validate_record(r))
    assert any("serve/tokens_per_sec" in r["gauges"] for r in snaps)
    if rec.get("flight_dumps"):
        assert rec["anomaly_profiles"], rec
        assert os.path.isdir(rec["anomaly_profiles"][0])


def test_serve_dry_run_slot_engine_with_telemetry(tmp_path, capsys):
    from distributed_pytorch_from_scratch_tpu.serving import serve as srv
    log_dir = str(tmp_path / "logs")
    srv.main(["--dry_run", "--metrics_port", "0", "--rollup_interval",
              "0.2", "--log_dir", log_dir])
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["metrics_port"] > 0
    recs = [json.loads(l)
            for l in open(os.path.join(log_dir, "metrics.jsonl"))]
    assert any(r["tag"] == "telemetry_snapshot" for r in recs)


def test_serve_refuses_profiler_without_flight():
    from distributed_pytorch_from_scratch_tpu.serving import serve as srv
    with pytest.raises(SystemExit):
        srv.get_serve_args(["--dry_run", "--paged",
                            "--profile_on_anomaly", "2"])


def test_bench_telemetry_flags_gated_on_serving():
    import bench
    with pytest.raises(SystemExit):
        bench.parse_args(["--metrics_port", "0"])
    with pytest.raises(SystemExit):
        bench.parse_args(["--serving", "--profile_on_anomaly", "2"])
    args = bench.parse_args(["--serving", "--flight_records",
                             "--metrics_port", "0",
                             "--profile_on_anomaly", "2"])
    assert args.metrics_port == 0 and args.profile_on_anomaly == 2


@pytest.mark.slow
def test_train_run_exports_telemetry(tmp_path):
    """Train exporter smoke (slow lane: pays a compile): snapshots carry
    the train gauges the log line prints."""
    import random

    from distributed_pytorch_from_scratch_tpu import train as train_mod
    from distributed_pytorch_from_scratch_tpu.config import (
        BOS_TOKEN, EOS_TOKEN, UNK_TOKEN)
    rng = random.Random(0)
    corpus = {
        "train": [[rng.randint(4, 63) for _ in range(20)]
                  for _ in range(64)],
        "validation": [[rng.randint(4, 63) for _ in range(12)]
                       for _ in range(8)],
        "special_ids": {BOS_TOKEN: 1, EOS_TOKEN: 2, UNK_TOKEN: 3},
        "vocab_size": 64,
    }
    tokens = tmp_path / "tokens.json"
    tokens.write_text(json.dumps(corpus))
    save = str(tmp_path / "ckpts")
    train_mod.main(["--data_path", str(tokens), "--save_dir", save,
                    "--batch_size", "4", "--max_steps", "10",
                    "--log_interval", "2", "--save_interval", "100",
                    "--warmup_steps", "2", "--metrics_port", "0",
                    "--rollup_interval", "0.2",
                    "--attn_dim", "32", "--ffn_dim", "64",
                    "--num_heads", "4", "--num_layers", "2",
                    "--maxlen", "32"])
    recs = [json.loads(l)
            for l in open(os.path.join(save, "logs", "metrics.jsonl"))]
    snaps = [r for r in recs if r["tag"] == "telemetry_snapshot"]
    assert snaps, "train run mirrored no telemetry snapshots"
    last = snaps[-1]
    assert last["gauges"]["train/tokens_per_sec"] > 0
    assert "train/goodput" in last["gauges"]
    assert last["counters"]["train/step"] == 10
    # ISSUE 15 silent-zero pin: the CPU backend has no memory_stats, so
    # the run must export 'unavailable' loudly — no device_memory_gib
    # scalar (previously a fake 0), hbm/available gauge at 0, and
    # hbm_watermark events saying available=false
    assert not any(r.get("tag") == "device_memory_gib" for r in recs)
    assert last["gauges"].get("hbm/available") == 0.0
    hw = [r for r in recs if r["tag"] == "hbm_watermark"]
    assert hw and all(r["available"] is False for r in hw)
    # the collector reads a train fleet too
    c = FleetCollector([os.path.join(save, "logs")])
    c.poll()
    assert c.rollup()["tokens_per_sec"] > 0


# ------------------------------------------------------- overhead pin

class _ByThread:
    """Counts calls by the thread that made them."""

    def __init__(self):
        self.calls = {}

    def hit(self, what):
        key = (what, threading.get_ident())
        self.calls[key] = self.calls.get(key, 0) + 1

    def mine(self, what):
        return self.calls.get((what, threading.get_ident()), 0)

    def others(self, what):
        return sum(n for (w, t), n in self.calls.items()
                   if w == what and t != threading.get_ident())


class _CountingLock(_ByThread):
    def __init__(self):
        super().__init__()
        self._lock = threading.Lock()

    def __enter__(self):
        self.hit("acquire")
        return self._lock.__enter__()

    def __exit__(self, *exc):
        return self._lock.__exit__(*exc)


class _CountingFile(_ByThread):
    """Wraps the real stream, so what is written still lands on disk."""

    def __init__(self, stream):
        super().__init__()
        self._stream = stream

    def write(self, text):
        self.hit("write")
        return self._stream.write(text)

    def flush(self):
        self.hit("flush")
        return self._stream.flush()

    def __getattr__(self, name):      # tell(), close()
        return getattr(self._stream, name)


def test_exported_traced_overhead_within_budget(tmp_path):
    """The overhead pin for the live exporter and the span tracer, by WHAT
    THEY DO on the hot path, counted through an injected lock and file (a
    wall-clock ratio taken under six xdist workers measured the box, not
    the code). Two warmed engines serve the same requests, one of them
    exported and traced. Per decode step, on the engine's thread:

    * the exporter is a bounded handful of lock-guarded dict stores and NO
      I/O: the metrics stream gets not one write more than in the arm
      without it, and every `telemetry_snapshot` line is written by the
      exporter's own thread;
    * the tracer is one buffered write under one lock per event, and a
      flush every `FLUSH_EVERY` events, never one per event."""
    from distributed_pytorch_from_scratch_tpu.obs import SpanTracer
    from distributed_pytorch_from_scratch_tpu.obs.trace import FLUSH_EVERY
    mesh, model, params = _setup(seed=7)

    def build(exported: bool):
        arm = "on" if exported else "off"
        w = MetricsWriter(str(tmp_path / arm), process_index=0)
        w._jsonl = _CountingFile(w._jsonl)
        fl = FlightRecorder(str(tmp_path), maxlen=256)
        tel = tracer = None
        if exported:
            tracer = SpanTracer(str(tmp_path / arm / "timeline"))
            tracer._lock = _CountingLock()
            tel = TelemetryExporter(writer=w, rollup_interval=0.05)
            tel._lock = _CountingLock()
            tel.start(0)
        rt = RequestTracer(writer=w, tracer=tracer, flight=fl)
        eng = PagedEngine(model, mesh, params, num_slots=4, buf_len=BUF,
                          eos_id=EOS, page_size=8, prefill_chunk=8,
                          request_tracer=rt, flight=fl, writer=w,
                          telemetry=tel)
        return eng, tel, tracer, w

    def drive(eng, base_rid):
        for i in range(8):
            r = Request(rid=base_rid + i, prompt=[0, 3 + i, 7, 11, 2],
                        max_new=10, seed=i)
            r.submit_t = time.monotonic()
            eng.submit(r)
        eng.run_to_completion()

    hot_writes, steps = {}, {}
    for exported in (False, True):
        eng, tel, tracer, w = build(exported)
        drive(eng, 0)                      # warm: compiles amortized
        if tracer is not None:             # the stream exists from now on
            tracer._jsonl = _CountingFile(tracer._jsonl)
            acquired0 = tracer._lock.mine("acquire")
        writes0, locks0 = w._jsonl.mine("write"), (
            tel._lock.mine("acquire") if tel else 0)
        s0 = eng.decode_steps
        for round_ in range(1, 4):
            drive(eng, 100 * round_)
        steps[exported] = eng.decode_steps - s0
        hot_writes[exported] = w._jsonl.mine("write") - writes0
        if tel is not None:
            per_step = (tel._lock.mine("acquire") - locks0) / steps[True]
            # 10 stores a decode step (serving/engine._publish_telemetry)
            # plus the per-completion SLO gauges
            assert 1 <= per_step <= 16, per_step
            time.sleep(0.15)               # let the snapshot thread tick
            # ISSUE 15: the watermark gauges ride the same publish path —
            # on the statless CPU backend they must export 'unavailable',
            # never a fake 0-byte gauge
            g = tel.snapshot()["gauges"]
            assert g.get("hbm/available") == 0.0
            assert "hbm/bytes_in_use" not in g
            tel.close()
            # the snapshots were written, and not by the engine's thread
            assert w._jsonl.others("write") >= 1
            events = tracer._jsonl.mine("write")
            assert events >= steps[True]   # it did trace the hot path
            assert tracer._lock.mine("acquire") - acquired0 == events
            assert tracer._jsonl.mine("flush") <= events // FLUSH_EVERY + 1
            assert tracer._jsonl.others("write") == 0
            tracer.close()
        w.close()
    assert steps[True] == steps[False]
    assert hot_writes[True] == hot_writes[False], (
        f"the exporter added {hot_writes[True] - hot_writes[False]} "
        f"write(s) to the engine thread's metrics stream over "
        f"{steps[True]} decode steps — live telemetry is costing the hot "
        f"path I/O")
