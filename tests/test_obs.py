"""The obs/ subsystem: span tracer, goodput meter, health sentinel, hang
watchdog, compiled-program introspection — unit level plus the tier-1
end-to-end smoke: a tiny CPU train run must emit a valid Chrome trace, a
goodput summary whose buckets sum to wall time, and a cost-analysis FLOPs
number within 2x of the hand-rolled estimate; an injected NaN loss must
halt training with a state dump."""

import glob
import importlib.util
import json
import os
import random
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from distributed_pytorch_from_scratch_tpu.obs import (
    GoodputMeter, HangWatchdog, HealthSentinel, SpanTracer,
    TrainingHealthError, analyze_compiled, parse_collectives)
from distributed_pytorch_from_scratch_tpu.obs.introspect import _shape_bytes
from distributed_pytorch_from_scratch_tpu.training.metrics import (
    MetricsWriter)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------- tracer

def test_tracer_emits_valid_chrome_trace(tmp_path):
    tr = SpanTracer(str(tmp_path), pid=7, process_name="unit")
    with tr.span("compile", cat="compile", step=0):
        with tr.span("inner", cat="compile"):
            pass
    tr.instant("marker", step=3)
    tr.counter("loss", 4.5)
    done = threading.Event()

    def producer():
        with tr.span("prefetch_window", cat="data_prep"):
            pass
        done.set()

    threading.Thread(target=producer).start()
    assert done.wait(5)
    path = tr.close()
    assert path is not None and os.path.exists(path)
    doc = json.load(open(path))
    evs = doc["traceEvents"]
    names = {e["name"] for e in evs}
    assert {"compile", "inner", "marker", "loss", "prefetch_window",
            "process_name"} <= names
    # timestamps sorted (close() sorts) and non-negative; durations >= 0
    ts = [e["ts"] for e in evs if "ts" in e]
    assert ts == sorted(ts) and all(t >= 0 for t in ts)
    assert all(e.get("dur", 0) >= 0 for e in evs)
    # the producer thread shows up as its own tid
    main_tids = {e["tid"] for e in evs if e["name"] == "compile"}
    prod_tids = {e["tid"] for e in evs if e["name"] == "prefetch_window"}
    assert main_tids and prod_tids and main_tids != prod_tids
    # crash-safe jsonl mirror: one parseable object per line
    for line in open(tmp_path / "trace.jsonl"):
        json.loads(line)
    # idempotent close
    assert tr.close() == path


def test_tracer_disabled_is_noop(tmp_path):
    tr = SpanTracer(str(tmp_path / "sub"), enabled=False)
    with tr.span("x"):
        pass
    tr.instant("y")
    assert tr.close() is None
    assert not os.path.exists(tmp_path / "sub")


# --------------------------------------------------------------- goodput

def test_goodput_buckets_sum_to_wall():
    t = [0.0]
    m = GoodputMeter(clock=lambda: t[0])
    m.account("compile", 2.0)
    m.account("step", 5.0)
    m.account("data_wait", 1.0)
    m.add_progress(tokens=1000, steps=10)
    t[0] = 10.0
    s = m.summary()
    assert s["wall_s"] == pytest.approx(10.0)
    assert sum(s["buckets_s"].values()) == pytest.approx(10.0)
    assert s["buckets_s"]["other"] == pytest.approx(2.0)
    assert s["goodput"] == pytest.approx(0.5)
    assert s["tokens"] == 1000 and s["steps"] == 10
    line = GoodputMeter.format_summary(s)
    assert "goodput 50.0%" in line and "step" in line


def test_goodput_other_clamps_at_zero():
    t = [0.0]
    m = GoodputMeter(clock=lambda: t[0])
    m.account("step", 5.0)  # over-account past wall
    t[0] = 4.0
    s = m.summary()
    assert s["buckets_s"]["other"] == 0.0


# -------------------------------------------------------------- sentinel

def test_sentinel_healthy_run_is_quiet(tmp_path):
    s = HealthSentinel(str(tmp_path))
    for i, loss in enumerate([4.0, 3.5, 3.2, 3.0]):
        s.check(i, loss, grad_norm=1.0)
    assert s.spikes == 0
    assert not glob.glob(str(tmp_path / "sentinel_dump_*"))


def test_sentinel_flags_spike_but_does_not_halt(tmp_path):
    s = HealthSentinel(str(tmp_path), spike_factor=3.0)
    s.check(0, 2.0)
    s.check(1, 2.0)
    s.check(2, 50.0)  # > 3 x EMA
    assert s.spikes == 1
    assert not glob.glob(str(tmp_path / "sentinel_dump_*"))  # no dump


def test_sentinel_nan_halts_with_dump(tmp_path):
    s = HealthSentinel(str(tmp_path))
    s.check(0, 2.0)
    with pytest.raises(TrainingHealthError) as ei:
        s.check(5, float("nan"))
    dump = ei.value.dump_path
    assert dump and os.path.exists(dump)
    rec = json.load(open(dump))
    assert "non-finite" in rec["reason"] and rec["step"] == 5
    assert len(rec["history"]) == 2  # the healthy check + the fatal one


def test_sentinel_nonfinite_grad_norm_halts(tmp_path):
    s = HealthSentinel(str(tmp_path))
    with pytest.raises(TrainingHealthError):
        s.check(1, 2.0, grad_norm=float("inf"))


def test_sentinel_halt_optout(tmp_path):
    s = HealthSentinel(str(tmp_path), halt_on_nonfinite=False)
    s.check(1, float("nan"))  # dumps but does not raise
    assert glob.glob(str(tmp_path / "sentinel_dump_*"))


# -------------------------------------------------------------- watchdog

def test_watchdog_detects_stall_and_recovery():
    stalls = []
    wd = HangWatchdog(timeout_s=0.08, poll_s=0.02,
                      on_stall=lambda rec: stalls.append(rec))
    try:
        wd.beat(step=7)
        deadline = time.monotonic() + 5.0
        while not stalls and time.monotonic() < deadline:
            time.sleep(0.02)
        assert stalls and stalls[0]["last_step"] == 7
        wd.beat(step=8)  # recovery
        assert wd.stall_count >= 1
    finally:
        wd.close()


def test_watchdog_quiet_while_beating():
    stalls = []
    wd = HangWatchdog(timeout_s=0.2, poll_s=0.02,
                      on_stall=lambda rec: stalls.append(rec))
    try:
        for _ in range(10):
            wd.beat(step=1)
            time.sleep(0.02)
        assert not stalls
    finally:
        wd.close()


# ------------------------------------------------------------ introspect

CANNED_HLO = """
  %ar = f32[8,128]{1,0} all-reduce(f32[8,128]{1,0} %p), replica_groups={{0,1},{2,3}}, to_apply=%add
  %ag.1 = bf16[4,256]{1,0} all-gather(bf16[4,64]{1,0} %q), dimensions={1}
  %aas = (f32[16]{0}, f32[16]{0}) all-to-all-start(f32[16]{0} %r)
  %done = f32[8,128]{1,0} all-reduce-done(f32[8,128]{1,0} %ar)
"""


def test_parse_collectives_counts_and_bytes():
    colls = parse_collectives(CANNED_HLO)
    assert colls["all-reduce"]["count"] == 1
    assert colls["all-reduce"]["bytes"] == 8 * 128 * 4
    # who talks to whom: two mesh axes show up as two groupings
    assert colls["all-reduce"]["groups"] == ["{{0,1},{2,3}}"]
    assert colls["all-gather"]["groups"] == []
    assert colls["all-gather"]["count"] == 1
    assert colls["all-gather"]["bytes"] == 4 * 256 * 2
    assert colls["all-to-all"]["count"] == 1
    # async -start tuple = (operand, result): only the result counts, so
    # sync and async lowerings of the same op report the same bytes
    assert colls["all-to-all"]["bytes"] == 16 * 4
    assert _shape_bytes("f32[2,3]{1,0}") == 24
    assert _shape_bytes("pred[]") == 1


def test_analyze_compiled_on_real_program():
    from jax.sharding import PartitionSpec as P
    from distributed_pytorch_from_scratch_tpu import MeshConfig, make_mesh

    mesh = make_mesh(MeshConfig(dp=1, tp=2))
    f = jax.jit(jax.shard_map(lambda x: jax.lax.psum(x @ x.T, "tp"),
                              mesh=mesh, in_specs=(P(None, "tp"),),
                              out_specs=P()))
    compiled = f.lower(jnp.ones((16, 64))).compile()
    a = analyze_compiled(compiled)
    assert a["flops"] is None or a["flops"] > 0
    assert "all-reduce" in a["collectives"]
    assert a["comm_bytes"] >= a["collectives"]["all-reduce"]["bytes"]


# --------------------------------------------------------- MetricsWriter

def test_metrics_writer_context_manager_and_events(tmp_path):
    with MetricsWriter(str(tmp_path), process_index=0) as w:
        w.scalar("train/x", 1.5, 3)
        w.event("goodput_summary", wall_s=10.0, goodput=0.5)
    recs = [json.loads(l) for l in open(tmp_path / "metrics.jsonl")]
    assert recs[0] == pytest.approx(
        {"tag": "train/x", "value": 1.5, "step": 3, "ts": recs[0]["ts"]})
    assert recs[1]["tag"] == "goodput_summary"
    w.scalar("after/close", 1.0, 4)  # silently dropped, no ValueError
    assert len(open(tmp_path / "metrics.jsonl").readlines()) == 2


def test_metrics_writer_tags_nonzero_process(tmp_path):
    with MetricsWriter(str(tmp_path), process_index=2) as w:
        w.scalar("a", 1.0, 0)
    assert os.path.exists(tmp_path / "metrics.proc2.jsonl")
    assert not os.path.exists(tmp_path / "metrics.jsonl")


# ------------------------------------------------- end-to-end train smoke

@pytest.fixture(scope="module")
def token_corpus(tmp_path_factory):
    from distributed_pytorch_from_scratch_tpu.config import (
        BOS_TOKEN, EOS_TOKEN, UNK_TOKEN)
    rng = random.Random(0)
    d = tmp_path_factory.mktemp("obs_corpus")
    data = {
        "train": [[rng.randint(4, 63) for _ in range(rng.randint(8, 30))]
                  for _ in range(64)],
        "validation": [[rng.randint(4, 63) for _ in range(12)]
                       for _ in range(8)],
        "special_ids": {BOS_TOKEN: 1, EOS_TOKEN: 2, UNK_TOKEN: 3},
        "vocab_size": 64,
    }
    path = d / "tokens.json"
    with open(path, "w") as f:
        json.dump(data, f)
    return str(path)


MODEL_FLAGS = ["--attn_dim", "32", "--ffn_dim", "64", "--num_heads", "4",
               "--num_layers", "2", "--maxlen", "32"]


def test_train_run_emits_trace_goodput_and_cost_analysis(token_corpus,
                                                         tmp_path):
    from distributed_pytorch_from_scratch_tpu import train as train_mod

    save = str(tmp_path / "ckpts")
    train_mod.main(["--data_path", token_corpus, "--save_dir", save,
                    "--batch_size", "4", "--max_steps", "30",
                    "--log_interval", "5", "--save_interval", "10",
                    "--warmup_steps", "2", *MODEL_FLAGS])

    # -- trace.json: valid Chrome trace-event format, monotonic timestamps
    doc = json.load(open(os.path.join(save, "logs", "trace.json")))
    evs = doc["traceEvents"]
    ts = [e["ts"] for e in evs if "ts" in e]
    assert ts == sorted(ts) and all(t >= 0 for t in ts)
    cats = {e.get("cat") for e in evs}
    assert {"compile", "data_wait", "h2d", "step", "checkpoint",
            "data_prep"} <= cats
    # the spans live in the modules that do the work, once each per cause,
    # and every one of them says which step caused it
    loop_tid = next(e["tid"] for e in evs if e["name"] == "step")
    for name in ("data_wait", "h2d", "ckpt.loss_sync", "ckpt.snapshot",
                 "ckpt.join_prev"):
        mine = [e for e in evs if e["name"] == name]
        assert mine and all(e["tid"] == loop_tid for e in mine), name
        assert all("step" in e["args"] for e in mine), name
    assert len([e for e in evs if e["name"] == "h2d"]) == 30
    # the async checkpoint writer traced on its own thread, tied to its save
    snapshots = [e["args"]["step"] for e in evs
                 if e["name"] == "ckpt.snapshot"]
    assert snapshots == [10, 20, 30]
    for name in ("ckpt.d2h", "ckpt.write"):
        mine = [e for e in evs if e["name"] == name]
        assert [e["args"]["step"] for e in mine] == snapshots, name
        assert all(e["tid"] != loop_tid and e["cat"] == "checkpoint"
                   for e in mine)
    assert not any(e["name"] in ("checkpoint_write", "schedule_save",
                                 "join_save") for e in evs)

    # -- metrics.jsonl: goodput summary + cost analysis + grad-norm scalars
    recs = [json.loads(l)
            for l in open(os.path.join(save, "logs", "metrics.jsonl"))]
    tags = {r["tag"] for r in recs}
    assert "train/grad_norm" in tags

    (good,) = [r for r in recs if r["tag"] == "goodput_summary"]
    total = sum(good["buckets_s"].values())
    assert total == pytest.approx(good["wall_s"], rel=0.05)
    assert good["steps"] == 30 and good["tokens"] == 30 * 4 * 32
    assert 0 < good["goodput"] <= 1

    (cost,) = [r for r in recs if r["tag"] == "cost_analysis"]
    assert cost["flops"] and cost["expected_program_flops"]
    ratio = cost["flops"] / cost["expected_program_flops"]
    assert 0.5 <= ratio <= 2.0, f"XLA vs hand-rolled FLOPs ratio {ratio}"
    assert cost["collectives"], "expected at least one collective parsed"

    # -- summarize_run integration: the goodput/health reader finds it
    spec = importlib.util.spec_from_file_location(
        "_summarize_run", os.path.join(REPO, "scripts", "summarize_run.py"))
    sr = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sr)
    goodput_rows, health_rows = sr.obs_lines(save)
    assert any("goodput" in r for r in goodput_rows)
    assert any("GFLOPs/program" in r for r in goodput_rows)


def test_nan_loss_halts_training_with_state_dump(token_corpus, tmp_path,
                                                 monkeypatch):
    from distributed_pytorch_from_scratch_tpu import train as train_mod

    real_builder = train_mod.build_train_step

    def nan_builder(*a, **kw):
        fn = real_builder(*a, **kw)

        # jitted like the real step (train AOT-compiles it); the blow-up
        # keys on the optimizer's own step counter
        @jax.jit
        def wrapped(p, o, ids, tgt, pos):
            p, o, (loss, g) = fn(p, o, ids, tgt, pos)
            # blow up mid-run, after healthy intervals
            loss = jnp.where(o.step >= 6, jnp.float32("nan"), loss)
            return p, o, (loss, g)

        return wrapped

    monkeypatch.setattr(train_mod, "build_train_step", nan_builder)
    save = str(tmp_path / "ckpts_nan")
    with pytest.raises(TrainingHealthError) as ei:
        train_mod.main(["--data_path", token_corpus, "--save_dir", save,
                        "--batch_size", "4", "--max_steps", "30",
                        "--log_interval", "5", "--save_interval", "100",
                        "--warmup_steps", "2", *MODEL_FLAGS])
    dump = ei.value.dump_path
    assert dump and os.path.exists(dump)
    rec = json.load(open(dump))
    assert "non-finite" in rec["reason"]
    # the halt still leaves a complete trace + goodput summary behind
    assert os.path.exists(os.path.join(save, "logs", "trace.json"))
    recs = [json.loads(l)
            for l in open(os.path.join(save, "logs", "metrics.jsonl"))]
    assert any(r["tag"] == "sentinel/nonfinite" for r in recs)
    assert any(r["tag"] == "goodput_summary" for r in recs)


def test_sentinel_can_be_disabled(token_corpus, tmp_path, monkeypatch):
    """--no_sentinel: the same NaN injection runs to completion (the
    pre-obs behaviour, for when dying is worse than diverging)."""
    from distributed_pytorch_from_scratch_tpu import train as train_mod

    real_builder = train_mod.build_train_step

    def nan_builder(*a, **kw):
        fn = real_builder(*a, **kw)

        @jax.jit   # train AOT-compiles its step
        def wrapped(p, o, ids, tgt, pos):
            p, o, (loss, g) = fn(p, o, ids, tgt, pos)
            return p, o, (loss * jnp.float32("nan"), g)

        return wrapped

    monkeypatch.setattr(train_mod, "build_train_step", nan_builder)
    save = str(tmp_path / "ckpts_nosent")
    train_mod.main(["--data_path", token_corpus, "--save_dir", save,
                    "--batch_size", "4", "--max_steps", "6",
                    "--log_interval", "3", "--save_interval", "100",
                    "--warmup_steps", "2", "--no_sentinel", *MODEL_FLAGS])
    assert not glob.glob(os.path.join(save, "logs", "sentinel_dump_*"))
