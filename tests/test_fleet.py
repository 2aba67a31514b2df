"""Serving fleet v1 correctness — ISSUE 19.

The anchor contract extends across PROCESS boundaries: a 2-replica
router fleet is greedy token-identical to a single PagedEngine (across
shared-prefix batches and a replica restart), and disaggregated
prefill/decode joined by the KV page stream is token-identical to the
same engine colocated — at tp 1->1 and 2->1 (the export/import path
reshards heads), native and int8 pages. Page values depend only on the
prefix, so WHERE a request runs and HOW its pages travel change cost,
never tokens.

Plus the fleet-specific laws: `export_pages`/`import_pages` round-trip
bit-identical across tp widths (and map cp pages through the scratch-
aware array index), the router's shadow prefix index predicts the
replica's ACTUAL prefix_hit_tokens exactly in the concurrently-live
regime, ties break least-loaded, session affinity spills LOUDLY (a
`session_spill` event, never a silent drop), dispatch overhead stays
under 1 ms p50 on CPU, and the PR 12 cross-process waterfall pin
extends to THREE hops (router -> prefill -> transfer -> decode) with
span sum == cross-process wall.
"""

import json
import types

import jax
import numpy as np
import pytest

from distributed_pytorch_from_scratch_tpu.config import MeshConfig, ModelConfig
from distributed_pytorch_from_scratch_tpu.models.transformer import Transformer
from distributed_pytorch_from_scratch_tpu.obs.reqtrace import (
    RequestTracer, TraceContext, merge_traces)
from distributed_pytorch_from_scratch_tpu.runtime.mesh import make_mesh
from distributed_pytorch_from_scratch_tpu.serving.engine import (
    PagedEngine, Request)
from distributed_pytorch_from_scratch_tpu.serving.kv_manager import (
    PagedKVPool)
from distributed_pytorch_from_scratch_tpu.serving.router import FleetRouter
from distributed_pytorch_from_scratch_tpu.serving.scheduler import QueueFull
from distributed_pytorch_from_scratch_tpu.serving.transfer import (
    run_disaggregated)
from distributed_pytorch_from_scratch_tpu.training.metrics import (
    MetricsWriter)

CFG = ModelConfig(attn_dim=32, ffn_dim=64, num_heads=8, num_layers=2,
                  vocab_size=96, maxlen=64)
BUF = 32
EOS = 1
PS = 8

# one full shared page (PS tokens) + distinct tails
_BASE = [0, 5, 17, 33, 60, 2, 4, 6]
PROMPTS = [
    _BASE + [7],
    _BASE + [9, 11],
    _BASE + [3, 5, 7, 11],
    _BASE + [13],
    _BASE + [21, 23],
    _BASE + [25],
]


def _setup(tp, seed=7, cp=1):
    mesh = make_mesh(MeshConfig(dp=1, tp=tp, cp=cp))
    model = Transformer(CFG, tp_size=tp, cp_size=cp)
    params = jax.device_put(model.init(jax.random.key(seed)),
                            model.shardings(mesh))
    return mesh, model, params


def _engine(tp=1, seed=7, **kw):
    mesh, model, params = _setup(tp, seed=seed)
    kw.setdefault("num_slots", 4)
    kw.setdefault("page_size", PS)
    kw.setdefault("prefill_chunk", PS)
    return PagedEngine(model, mesh, params, buf_len=BUF, eos_id=EOS, **kw)


def _reqs(max_new=8, rid0=0):
    return [Request(rid=rid0 + i, prompt=list(p), max_new=max_new)
            for i, p in enumerate(PROMPTS)]


def _assert_drained(eng):
    assert eng.pool.free_pages == eng.pool.num_pages, (
        eng.pool.free_pages, eng.pool.num_pages)
    assert (eng.pool.refcount == 0).all()
    assert not eng.pool._children and not eng.pool._page_keys


# ------------------------------------------- page export/import round-trip

def _pool(tp=1, cp=1, kv_dtype=None, num_pages=8):
    mesh = make_mesh(MeshConfig(dp=1, tp=tp, cp=cp))
    model = types.SimpleNamespace(cfg=CFG, cp_size=cp)
    return PagedKVPool(model, mesh, num_pages, PS, kv_dtype=kv_dtype)


def _rand_like(a, n, rng):
    shape = (a.shape[0], n) + tuple(a.shape[2:])
    if np.issubdtype(np.dtype(a.dtype), np.integer):
        return rng.integers(-100, 100, shape).astype(a.dtype)
    return rng.standard_normal(shape).astype(a.dtype)


def _tree_eq(a, b):
    jax.tree.map(np.testing.assert_array_equal, a, b)


@pytest.mark.parametrize("tp_a,tp_b", [(1, 1), (1, 2), (2, 1)])
@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_export_import_roundtrip_across_tp(tp_a, tp_b, kv_dtype):
    """Host KV pages import -> export bit-identical, then survive a
    SECOND pool at a different tp width unchanged: export is global head
    layout, so the tp reshard is implicit in the device put."""
    rng = np.random.default_rng(3)
    pa = _pool(tp=tp_a, kv_dtype=kv_dtype)
    k = jax.tree.map(lambda a: _rand_like(a, 3, rng), pa.ks)
    v = jax.tree.map(lambda a: _rand_like(a, 3, rng), pa.vs)
    pages = pa.import_pages(k, v)
    assert len(pages) == 3
    assert pa.free_pages == pa.num_pages - 3
    k1, v1 = pa.export_pages(pages)
    _tree_eq(k, k1)
    _tree_eq(v, v1)
    pb = _pool(tp=tp_b, kv_dtype=kv_dtype)
    pages_b = pb.import_pages(k1, v1)
    k2, v2 = pb.export_pages(pages_b)
    _tree_eq(k, k2)
    _tree_eq(v, v2)
    for pool, pgs in ((pa, pages), (pb, pages_b)):
        for p in pgs:
            pool.unref(p)
        assert pool.free_pages == pool.num_pages


def test_import_pages_cp_mapping_and_rollback():
    """cp=2: owners map pages through the scratch-aware array index
    (rank r's pages live past r's scratch row), and a pool too dry for
    the batch rolls back EVERY lease before raising."""
    from distributed_pytorch_from_scratch_tpu.serving.kv_manager import (
        PoolExhausted)
    rng = np.random.default_rng(4)
    pool = _pool(cp=2, num_pages=16)          # 8 per rank
    k = jax.tree.map(lambda a: _rand_like(a, 3, rng), pool.ks)
    v = jax.tree.map(lambda a: _rand_like(a, 3, rng), pool.vs)
    pages = pool.import_pages(k, v, owners=[0, 1, 1])
    assert pages == [0, 8, 9]                 # rank 0 page 0; rank 1 pages
    k1, v1 = pool.export_pages(pages)
    _tree_eq(k, k1)
    _tree_eq(v, v1)
    free_before = pool.free_pages
    big_k = jax.tree.map(lambda a: _rand_like(a, 14, rng), pool.ks)
    big_v = jax.tree.map(lambda a: _rand_like(a, 14, rng), pool.vs)
    with pytest.raises(PoolExhausted):
        pool.import_pages(big_k, big_v)       # 14 > 13 remaining
    assert pool.free_pages == free_before     # full rollback
    for p in pages:
        pool.unref(p)
    assert pool.free_pages == pool.num_pages


# ------------------------------------------------- router token identity

def test_fleet_token_identity_with_restart():
    """2-replica router fleet == single PagedEngine on a shared-prefix
    batch; then r0 is REPLACED (restart) and a second batch still
    matches. Pools drain on every engine."""
    single = _engine(num_slots=4)
    for r in _reqs():
        single.submit(r)
    single.run_to_completion()
    refs = {r.rid: list(r.tokens) for r in single.completed}
    assert len(refs) == len(PROMPTS) and any(refs.values())

    # prefix_weight dialed DOWN so the load term actually spreads the
    # shared-prefix burst across replicas — the identity claim is only
    # interesting when both replicas serve (default weights correctly
    # concentrate a fully-shared burst on the replica holding the page)
    replicas = [_engine(num_slots=2), _engine(num_slots=2)]
    router = FleetRouter(replicas, prefix_weight=0.5)
    done = {}
    for r in _reqs():
        router.submit(r)
        done.update({d.rid: list(d.tokens) for d in router.step()})
    done.update({r.rid: list(r.tokens) for r in router.run_to_completion()})
    assert done == refs
    # the load term spread the burst: both replicas took work
    assert min(router.dispatched.values()) >= 1, router.dispatched

    fresh = _engine(num_slots=2)
    router.replace_replica("r0", fresh)
    for r in _reqs(rid0=100):
        router.submit(r)
    done2 = {r.rid - 100: list(r.tokens)
             for r in router.run_to_completion()}
    assert done2 == refs
    for _, e in router.replicas:
        _assert_drained(e)
    _assert_drained(single)


# --------------------------------------------------------- dispatch laws

def test_shadow_prediction_equals_actual_prefix_hits():
    """The dispatch law: in the concurrently-live regime (slots >=
    burst) the router-side shadow predicts each replica's ACTUAL
    prefix_hit_tokens counter exactly. Plus the CPU overhead pin:
    dispatch p50 under 1 ms."""
    replicas = [_engine(num_slots=8), _engine(num_slots=8)]
    router = FleetRouter(replicas)
    for r in _reqs():
        router.submit(r)
    router.run_to_completion()
    predicted = {}
    for rid, (name, hit) in router.predicted.items():
        predicted[name] = predicted.get(name, 0) + hit
    for name, eng in router.replicas:
        assert predicted.get(name, 0) == eng.prefix_hit_tokens, (
            name, predicted, eng.prefix_hit_tokens)
    # the shared page was predicted at least once (the law isn't 0 == 0)
    assert sum(predicted.values()) >= PS
    st = router.stats()
    assert st["dispatch_ms_p50"] < 1.0, st


def test_router_least_loaded_tiebreak():
    """No prefix signal anywhere -> equal scores break by replica order;
    a queued request then tips the load term toward the idle replica."""
    router = FleetRouter([_engine(num_slots=2), _engine(num_slots=2)])
    # fully distinct prompts (no common lead token): zero prefix signal
    a = Request(rid=0, prompt=[2, 9, 21], max_new=2)
    b = Request(rid=1, prompt=[5, 13, 37], max_new=2)
    assert router.submit(a) == "r0"           # tie -> first replica
    assert router.submit(b) == "r1"           # r0 now loaded
    router.run_to_completion()
    for _, e in router.replicas:
        _assert_drained(e)


def test_session_affinity_and_loud_spill(tmp_path):
    """A session sticks to its replica; when that replica refuses
    (QueueFull) the request SPILLS to the next best with a
    `session_spill` writer event — and only a fleet-wide refusal
    reaches the caller."""
    w = MetricsWriter(str(tmp_path), process_index=0)
    router = FleetRouter([_engine(num_slots=1, max_queue=1),
                          _engine(num_slots=1, max_queue=1)],
                         writer=w)
    a = Request(rid=0, prompt=[0, 9, 21], max_new=2)
    first = router.submit(a, session="s1")
    # same session, pinned replica full -> loud spill to the other
    b = Request(rid=1, prompt=[0, 13, 37], max_new=2)
    spilled = router.submit(b, session="s1")
    assert spilled != first
    assert router.spills == 1
    # both replicas full -> fleet-wide refusal propagates
    with pytest.raises(QueueFull):
        router.submit(Request(rid=2, prompt=[0, 2, 4], max_new=2))
    assert router.rejected == 1
    router.run_to_completion()
    w.close()
    evs = [json.loads(l) for l in open(tmp_path / "metrics.jsonl")]
    spill = [e for e in evs if e.get("tag") == "session_spill"]
    assert len(spill) == 1
    assert spill[0]["session"] == "s1" and spill[0]["pinned"] == first


# ------------------------------------------- disaggregated prefill/decode

@pytest.mark.parametrize("tp_pre,tp_dec,kv_dtype",
                         [(1, 1, None), (2, 1, None), (1, 1, "int8")])
def test_disagg_token_identity(tp_pre, tp_dec, kv_dtype):
    """Prefill-engine -> KV page stream -> decode-engine output equals
    the same engine colocated — including across a tp reshard (2->1)
    and int8 pages (codes+scales travel, dequant math unchanged)."""
    coloc = _engine(tp=tp_dec, kv_dtype=kv_dtype)
    for r in _reqs():
        coloc.submit(r)
    coloc.run_to_completion()
    refs = {r.rid: list(r.tokens) for r in coloc.completed}

    pre = _engine(tp=tp_pre, kv_dtype=kv_dtype, prefill_only=True)
    dec = _engine(tp=tp_dec, kv_dtype=kv_dtype)
    out = run_disaggregated(pre, dec, _reqs())
    done = {r.rid: list(r.tokens) for r in out["completed"]}
    assert done == refs
    # every request's pages crossed the wire and were accounted
    assert len(out["transfers"]) == len(PROMPTS)
    assert out["transferred_pages"] == sum(t["pages"]
                                           for t in out["transfers"])
    assert out["transferred_bytes"] > 0
    assert pre.pages_exported == out["transferred_pages"]
    assert dec.pages_imported == out["transferred_pages"]
    _assert_drained(pre)
    _assert_drained(dec)
    _assert_drained(coloc)


def test_disagg_refuses_mismatched_wire():
    pre = _engine(prefill_only=True)
    dec8 = _engine(kv_dtype="int8")
    with pytest.raises(ValueError, match="kv_dtype mismatch"):
        run_disaggregated(pre, dec8, _reqs())
    dec_ps = _engine(page_size=16, prefill_chunk=16)
    with pytest.raises(ValueError, match="page_size mismatch"):
        run_disaggregated(pre, dec_ps, _reqs())


# ------------------------------------- three-hop cross-process waterfall

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


def test_three_hop_waterfall_span_sum_equals_wall():
    """The PR 12 two-hop pin extended to THREE processes with two
    deliberate clock skews: router (p0) -> prefill (p1, +500s) ->
    decode (p2, -312s). One contiguous waterfall, span sum == total ==
    the cross-process wall in the root timebase."""
    skew1, skew2 = 500.0, -312.0
    c0, c1, c2 = [0.0], [0.0], [0.0]
    rt0 = RequestTracer(clock=lambda: c0[0],
                        wall=lambda: 1000.0 + c0[0], process_index=0)
    rt1 = RequestTracer(clock=lambda: c1[0],
                        wall=lambda: 1000.0 + skew1 + c1[0],
                        process_index=1)
    rt2 = RequestTracer(clock=lambda: c2[0],
                        wall=lambda: 1000.0 + skew2 + c2[0],
                        process_index=2)
    # hop 0: the router scores + dispatches in 10ms
    r0 = _FakeReq(9)
    r0.submit_t = 0.0
    rt0.begin(r0)
    c0[0] = 0.010
    ctx0 = rt0.export_context(r0, "route")
    rec0 = rt0.retire(r0, t=c0[0])
    # hop 1 adopts 5ms later (root time 15ms): 30ms of chunked prefill
    c1[0] = 0.0
    r1 = _FakeReq(9)
    rt1.begin(r1, ctx=TraceContext.from_wire(
        json.loads(json.dumps(ctx0.to_wire()))))
    assert r1.trace_id == r0.trace_id
    c1[0] = 0.030
    rt1.mark(r1, "prefill_chunk", positions=3)
    ctx1 = rt1.export_context(r1, "handoff")
    rec1 = rt1.retire(r1, t=c1[0])
    # hop 2 adopts after 20ms on the wire (root 65ms): 40ms of decode
    c2[0] = 0.0
    r2 = _FakeReq(9)
    rt2.begin(r2, ctx=TraceContext.from_wire(ctx1.to_wire()))
    c2[0] = 0.040
    rt2.mark(r2, "decode")
    r2.finish_t = 0.040
    r2.tokens = [7, 8]
    rec2 = rt2.retire(r2)
    # the handoff handshake anchors each hop's adoption AT the previous
    # hop's export wall (both skews cancel exactly, like the 2-hop pin),
    # so the merged waterfall is contiguous with span sum == total ==
    # the cross-process wall: 10ms route + 30ms prefill + 40ms decode.
    m = merge_traces([rec0, rec1, rec2])
    assert m["processes"] == [0, 1, 2]
    cursor = 0.0
    for s in m["spans"]:
        assert s["start_ms"] == pytest.approx(cursor, abs=0.01), (
            s, m["spans"])
        cursor += s["dur_ms"]
    assert cursor == pytest.approx(m["total_ms"], abs=0.01)
    assert m["total_ms"] == pytest.approx(80.0, abs=0.5)
    names = [s["name"] for s in m["spans"]]
    assert "route" in names                   # hop 0's dispatch span
    assert "handoff" in names                 # hop 1's export span
    assert "prefill_chunk" in names and "decode" in names


def test_three_hop_waterfall_real_path():
    """The real wiring: router tracer exports `route`, the prefill
    engine adopts + exports `handoff` (transfer.py), the decode engine
    adopts at admit — three records, one merged contiguous waterfall."""
    rt0 = RequestTracer(process_index=0)
    rt1 = RequestTracer(process_index=1)
    rt2 = RequestTracer(process_index=2)
    pre = _engine(prefill_only=True, request_tracer=rt1)
    dec = _engine(request_tracer=rt2)
    reqs = _reqs(max_new=4)
    for r in reqs:
        rt0.begin(r)
        ctx = rt0.export_context(r, "route")
        r.trace_ctx = ctx.to_wire()
        rt0.retire(r)
    out = run_disaggregated(pre, dec, reqs)
    assert len(out["completed"]) == len(reqs)
    for r in reqs:
        recs = [rt0.timeline(r.rid), rt1.timeline(r.rid),
                rt2.timeline(r.rid)]
        assert all(rec is not None for rec in recs), r.rid
        assert {rec["trace_id"] for rec in recs} == {r.trace_id}
        m = merge_traces(recs)
        assert m["processes"] == [0, 1, 2]
        cursor = 0.0
        for s in m["spans"]:
            assert s["start_ms"] == pytest.approx(cursor, abs=0.01)
            cursor += s["dur_ms"]
        assert cursor == pytest.approx(m["total_ms"], abs=0.01)
        assert "kv_import" in [s["name"] for s in m["spans"]]
    _assert_drained(pre)
    _assert_drained(dec)


# --------------------------- scripts/serve_fleet.py: one slice per engine --

def _serve_fleet():
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "serve_fleet.py")
    spec = importlib.util.spec_from_file_location("_fleet_cli", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_fleet_engines_get_disjoint_device_slices(capsys):
    """Every replica — and the disaggregated prefill and decode engines —
    builds its mesh on its OWN run of devices. They all used to take
    `jax.devices()[:tp]`: on a four-chip host four "replicas" shared the
    first chips and the rest idled."""
    sf = _serve_fleet()
    args = sf.parse_args(["--random_init", "--model", "tiny", "--slots", "2",
                          "--page_size", "8", "--prefill_chunk", "8"])
    slices = sf._DeviceSlices(jax.devices())
    engines = [sf._build_engine(args, CFG, tp, i + 1, None, None, None, BUF,
                                slices.take(tp))
               for i, tp in enumerate((2, 2, 1))]
    held = [{d.id for d in e.mesh.devices.flat} for e in engines]
    assert [len(h) for h in held] == [2, 2, 1]
    assert not (held[0] & held[1] or held[0] & held[2] or held[1] & held[2])
    # the weights really live there, not on device 0
    for e, h in zip(engines, held):
        leaf = jax.tree.leaves(e._params_in)[0]
        assert {s.device.id for s in leaf.addressable_shards} == h
    # the walk is in order and, out of devices, starts over and says so
    few = sf._DeviceSlices(jax.devices()[:2])
    assert [d.id for d in few.take(1)] == [0]
    assert [d.id for d in few.take(1)] == [1]
    assert capsys.readouterr().err == ""
    assert [d.id for d in few.take(2)] == [0, 1]
    assert "share devices" in capsys.readouterr().err
    with pytest.raises(SystemExit, match="needs 4 devices"):
        few.take(4)
