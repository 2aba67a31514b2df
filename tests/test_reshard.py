"""Mesh-elastic checkpoints + any-layout→any-layout redistribution (ISSUE 20).

The acceptance contract: a checkpoint saved under ANY (mesh, PartitionSpec,
ZeRO-stage) layout reloads under any other through `reshard/` with

* BIT-identical global param AND optimizer-moment trees (the redistribution
  is data movement, never arithmetic),
* peak host bytes == ONE leaf, not the tree — `HostMeter`-asserted, the
  streamed-executor law the `host-gather-in-reshard` lint enforces
  statically,
* the planner's minimal-transfer claim pinned by op counts and
  `bytes_moved` (a pure zero-stage change moves ZERO bytes),
* the elastic `train.py --resume` trajectory matching a same-mesh resume,
  with a versioned `reshard_event` in the metrics stream,
* a fleet replica restarted at a DIFFERENT tp width serving token-identical
  greedy output (`reshard_params` device→device + `replace_replica`),
* inexpressible targets and spec-less legacy sources refusing LOUDLY.

The reference cannot do any of this: its rank pickles only reload at the
same tp_size (SURVEY §5.4); a mesh change means retraining or a hand-rolled
conversion script.
"""

import json
import os
import re
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from distributed_pytorch_from_scratch_tpu.config import MeshConfig, ModelConfig
from distributed_pytorch_from_scratch_tpu.models.transformer import Transformer
from distributed_pytorch_from_scratch_tpu.reshard import (
    HostMeter, ReshardError, layouts_equal, make_layout, plan_checkpoint,
    plan_reshard, read_stamp, reshard_checkpoint, reshard_params,
    resolve_source_layout, stream_load)
from distributed_pytorch_from_scratch_tpu.runtime.mesh import make_mesh
from distributed_pytorch_from_scratch_tpu.training.checkpoint import (
    _flatten, latest_step, load_checkpoint, save_checkpoint,
    validate_checkpoint)
from distributed_pytorch_from_scratch_tpu.training.optim import init_adam_state

CFG = ModelConfig(attn_dim=32, ffn_dim=64, num_heads=8, num_layers=2,
                  vocab_size=64, maxlen=16)


def _tree_equal(a, b):
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(
        np.asarray(x), np.asarray(y)), a, b)


def _max_leaf_bytes(params, with_opt):
    n = max(np.asarray(v).nbytes for v in jax.tree.leaves(params))
    return n  # moments shard like params, so the max is the same


def _save_src(tmp, step=3, tp=4, dp=1, zero=0, seed=0, with_opt=True):
    """A stamped source checkpoint with non-trivial optimizer moments."""
    model = Transformer(CFG, tp_size=tp)
    params = model.init(jax.random.key(seed))
    opt = None
    if with_opt:
        opt = init_adam_state(params)
        opt = opt._replace(mu=jax.tree.map(lambda p: p + 0.25, opt.mu),
                           nu=jax.tree.map(lambda p: p * 0.0 + 0.5, opt.nu))
    save_checkpoint(str(tmp), step, 1.0, params, model.specs(), tp_size=tp,
                    opt_state=opt, zero_stage=zero,
                    mesh_axes=(("dp", dp), ("tp", tp)))
    return model, params, opt


# ------------------------------------------------ layout stamping (files) --

def test_save_stamps_layout_and_resolves_exactly(tmp_path):
    model, _, _ = _save_src(tmp_path, tp=4, dp=2, zero=3)
    lay, legacy = resolve_source_layout(str(tmp_path), 3,
                                        echo=lambda *a: None)
    assert not legacy
    want = make_layout((("dp", 2), ("tp", 4)), model.specs(), zero_stage=3)
    assert layouts_equal(lay, want)
    assert lay.describe() == "dp2xtp4 zero3"
    # the stamp is json inside every shard, skipped by pre-stamp readers
    with np.load(os.path.join(
            tmp_path, "tprank-0_iter-3_loss-1.0000.npz")) as npz:
        assert layouts_equal(read_stamp(npz), want)


def test_legacy_unstamped_source_is_loud_never_a_crash(tmp_path):
    model, params, opt = _save_src(tmp_path, tp=4)
    for rank in range(4):
        p = os.path.join(tmp_path, f"tprank-{rank}_iter-3_loss-1.0000.npz")
        d = dict(np.load(p))
        del d["__layout__"]
        np.savez(p, **d)

    # spec-less legacy: refuse, naming the fix
    with pytest.raises(ValueError, match="legacy checkpoint.*canonical_specs"):
        resolve_source_layout(str(tmp_path), 3)

    notes = []
    lay, legacy = resolve_source_layout(
        str(tmp_path), 3, specs=model.specs(),
        echo=lambda *a: notes.append(" ".join(map(str, a))))
    assert legacy and lay.tp == 4
    assert any("layout inferred from filenames" in n for n in notes)

    # and the legacy source still reshards bit-identically, re-stamped
    dst = make_layout((("tp", 2),), model.specs())
    paths, _, info = reshard_checkpoint(
        str(tmp_path), 3, str(tmp_path / "dst"), dst, specs=model.specs(),
        echo=lambda *a: None)
    assert info["legacy"] is True
    with np.load(paths[0]) as npz:
        assert layouts_equal(read_stamp(npz), dst)
    loaded, lopt, _ = load_checkpoint(str(tmp_path / "dst"), 3, params,
                                      model.specs(), with_opt=True)
    _tree_equal(loaded, params)
    _tree_equal(lopt.mu, opt.mu)


# ------------------------------------- file→file matrix, bit-identical ----

MATRIX = {
    # src (mesh, zero) -> dst (mesh, zero): the ISSUE-20 acceptance pairs
    "tp4_to_tp2": (dict(tp=4), dict(tp=2, dp=1, zero=0)),
    "tp4_to_tp1": (dict(tp=4), dict(tp=1, dp=1, zero=0)),
    "z3_train_to_serving": (dict(tp=4, dp=2, zero=3),
                            dict(tp=2, dp=1, zero=0)),
    "z2_to_z0": (dict(tp=2, dp=2, zero=2), dict(tp=2, dp=2, zero=0)),
}


@pytest.mark.parametrize("case", sorted(MATRIX), ids=sorted(MATRIX))
def test_reshard_checkpoint_bit_identical(tmp_path, case):
    src_kw, dst_kw = MATRIX[case]
    model, params, opt = _save_src(tmp_path / "src", **src_kw)
    dst_lay = make_layout((("dp", dst_kw["dp"]), ("tp", dst_kw["tp"])),
                          model.specs(), zero_stage=dst_kw["zero"])
    meter = HostMeter()
    paths, plan, info = reshard_checkpoint(
        str(tmp_path / "src"), 3, str(tmp_path / "dst"), dst_lay,
        meter=meter, echo=lambda *a: None)

    # the output is a first-class checkpoint at the target width
    tp_out, _ = validate_checkpoint(str(tmp_path / "dst"), 3)
    assert tp_out == dst_kw["tp"] == len(paths)
    with np.load(paths[0]) as npz:
        assert layouts_equal(read_stamp(npz), dst_lay)
        assert int(npz["__zero_stage__"]) == dst_kw["zero"]

    # bit-identical global params AND moments
    loaded, lopt, step = load_checkpoint(str(tmp_path / "dst"), 3, params,
                                         model.specs(), with_opt=True)
    assert step == 3
    _tree_equal(loaded, params)
    _tree_equal(lopt.mu, opt.mu)
    _tree_equal(lopt.nu, opt.nu)

    # peak host == one leaf, never the tree (the streamed-executor law)
    assert meter.peak <= info["max_leaf_bytes"]
    assert info["max_leaf_bytes"] == _max_leaf_bytes(params, True)
    assert meter.live == 0

    # a pure zero-stage change re-slices NOTHING: files already identical
    if src_kw.get("tp") == dst_kw["tp"]:
        assert info["bytes_moved"] == 0


def test_plan_op_pins_and_minimal_bytes(tmp_path):
    """The planner's schedule, pinned: op inventory per acceptance pair and
    the bytes_moved minimality facts (same-tp == 0; the graftcheck trace
    contract pins the lowered collective count against these same
    numbers)."""
    _save_src(tmp_path, tp=4, dp=2, zero=3)
    model = Transformer(CFG, tp_size=4)

    plan, src_lay, legacy = plan_checkpoint(
        str(tmp_path), 3, make_layout((("tp", 2),), model.specs()),
        echo=lambda *a: None)
    assert not legacy and src_lay.describe() == "dp2xtp4 zero3"
    s = plan.summary()
    # every leaf coarsens (dp-extension dropped AND tp halved): all gather
    assert s["ops"] == {"gather": 60}
    assert s["n_leaves"] == 60 and s["max_leaf_bytes"] == 16384
    assert s["bytes_moved"] == 307968

    # same mesh, zero3 -> zero3 at half width: params/moments that were
    # replicated across tp stay copies, tp-sharded leaves gather
    plan2, _, _ = plan_checkpoint(
        str(tmp_path), 3,
        make_layout((("dp", 2), ("tp", 2)), model.specs(), zero_stage=3),
        echo=lambda *a: None)
    assert plan2.summary()["ops"] == {"gather": 45, "copy": 15}

    # identity reshard: every leaf a copy, zero bytes
    plan3, _, _ = plan_checkpoint(
        str(tmp_path), 3,
        make_layout((("dp", 2), ("tp", 4)), model.specs(), zero_stage=3),
        echo=lambda *a: None)
    assert plan3.summary() == {
        "src": "dp2xtp4 zero3", "dst": "dp2xtp4 zero3",
        "ops": {"copy": 60}, "bytes_moved": 0, "n_leaves": 60,
        "max_leaf_bytes": 16384}


def test_inexpressible_layout_refuses_loudly(tmp_path):
    _save_src(tmp_path, tp=4)
    model = Transformer(CFG, tp_size=4)
    # vocab 64 does not divide 3 ways: the embedding leaf is inexpressible
    with pytest.raises(ReshardError, match="inexpressible"):
        plan_checkpoint(str(tmp_path), 3,
                        make_layout((("tp", 3),), model.specs()),
                        echo=lambda *a: None)


# --------------------------------------------- file→device (stream_load) --

def test_stream_load_elastic_zero3_bit_identical_and_bounded(tmp_path):
    """dp2xtp4 ZeRO-3 checkpoint lands on a dp2xtp2 ZeRO-3 mesh: one leaf
    on the host at a time, each device_put straight against its TARGET
    sharding — params and both moments bit-identical."""
    from distributed_pytorch_from_scratch_tpu.training.zero import (
        zero3_shardings)

    m4, params, opt = _save_src(tmp_path, step=11, tp=4, dp=2, zero=3)
    m2 = Transformer(CFG, tp_size=2)
    mesh = make_mesh(MeshConfig(dp=2, tp=2))
    p_sh = zero3_shardings(m2, mesh)
    dst_lay = make_layout(mesh, m2.canonical_specs(), zero_stage=3)
    meter = HostMeter()
    out_p, out_o, step, info = stream_load(
        str(tmp_path), 11, params, m2.canonical_specs(), dst_lay, p_sh,
        moment_shardings=p_sh, with_opt=True, meter=meter,
        echo=lambda *a: None)
    assert step == 11
    _tree_equal(out_p, params)
    _tree_equal(out_o.mu, opt.mu)
    _tree_equal(out_o.nu, opt.nu)
    # the leaves actually live under the target sharding
    for got, want in zip(jax.tree.leaves(out_p), jax.tree.leaves(p_sh)):
        assert got.sharding.is_equivalent_to(want, got.ndim)
    assert meter.peak <= info["max_leaf_bytes"] == _max_leaf_bytes(params,
                                                                   True)
    assert info["ops"] == {"gather": 45, "copy": 15}
    assert meter.live == 0


def test_stream_load_refuses_moments_without_shardings(tmp_path):
    _save_src(tmp_path, step=2, tp=2)
    m2 = Transformer(CFG, tp_size=2)
    mesh = make_mesh(MeshConfig(dp=1, tp=2))
    with pytest.raises(ReshardError, match="moment_shardings"):
        stream_load(str(tmp_path), 2, m2.init(jax.random.key(0)),
                    m2.canonical_specs(),
                    make_layout(mesh, m2.canonical_specs()),
                    m2.shardings(mesh), with_opt=True,
                    echo=lambda *a: None)


# ------------------------------------------------- legacy .pth rank span --

def test_pth_span_reshards_through_interop(tmp_path):
    """The reference's torch pickles bridge through interop (loud note,
    documented host-cost exemption) and come out as a stamped npz set at
    the new width — values identical."""
    torch = pytest.importorskip("torch")  # noqa: F841
    from distributed_pytorch_from_scratch_tpu import interop

    model = Transformer(CFG, tp_size=4)
    params = model.init(jax.random.key(5))
    interop.export_reference_checkpoint(params, CFG, 4, str(tmp_path / "pth"),
                                        7, loss=1.0)
    notes = []
    dst_lay = make_layout((("tp", 2),), model.specs())
    paths, _, info = reshard_checkpoint(
        str(tmp_path / "pth"), 7, str(tmp_path / "dst"), dst_lay,
        specs=model.specs(), ext="pth", cfg=CFG,
        echo=lambda *a: notes.append(" ".join(map(str, a))))
    assert info["legacy"] is True
    assert any("not streamable" in n for n in notes)
    tp_out, _ = validate_checkpoint(str(tmp_path / "dst"), 7)
    assert tp_out == 2
    with np.load(paths[0]) as npz:
        assert layouts_equal(read_stamp(npz), dst_lay)
    loaded, _, _ = load_checkpoint(str(tmp_path / "dst"), 7, params,
                                   model.specs())
    _tree_equal(loaded, params)


# ------------------------------------- fleet replica restart at new width --

SCFG = ModelConfig(attn_dim=32, ffn_dim=64, num_heads=8, num_layers=2,
                   vocab_size=96, maxlen=64)
_BASE = [0, 5, 17, 33, 60, 2, 4, 6]
SPROMPTS = [_BASE + [7], _BASE + [9, 11], _BASE + [3, 5, 7, 11],
            _BASE + [13]]


def _sengine(tp=1, seed=7, params=None):
    from distributed_pytorch_from_scratch_tpu.serving.engine import PagedEngine
    mesh = make_mesh(MeshConfig(dp=1, tp=tp))
    model = Transformer(SCFG, tp_size=tp)
    if params is None:
        params = jax.device_put(model.init(jax.random.key(seed)),
                                model.shardings(mesh))
    return PagedEngine(model, mesh, params, buf_len=32, eos_id=1,
                       num_slots=4, page_size=8, prefill_chunk=8)


def _sreqs(rid0):
    from distributed_pytorch_from_scratch_tpu.serving.engine import Request
    return [Request(rid=rid0 + i, prompt=list(p), max_new=6)
            for i, p in enumerate(SPROMPTS)]


def test_fleet_width_restart_token_identical(tmp_path):
    """A live tp1 replica restarts at tp2 mid-traffic (`reshard_params`
    device→device, `replace_replica` under the old name): the second wave
    is greedy token-identical to a single never-restarted engine, and the
    `replica_restart` event carries the reshard plan summary."""
    from distributed_pytorch_from_scratch_tpu.serving.router import (
        FleetRouter)
    from distributed_pytorch_from_scratch_tpu.training.metrics import (
        MetricsWriter)

    single = _sengine(tp=1)
    refs = {}
    for rid0 in (0, 100):
        for r in _sreqs(rid0):
            single.submit(r)
        for r in single.run_to_completion():
            refs[r.rid] = list(r.tokens)
    assert len(refs) == 8 and any(refs.values())

    w = MetricsWriter(str(tmp_path), process_index=0)
    # prefix_weight off so the shared-prefix burst actually spreads and
    # the restarted replica serves wave-B requests
    router = FleetRouter([_sengine(tp=1), _sengine(tp=1)],
                         prefix_weight=0.0, writer=w)
    got = {}
    for r in _sreqs(0):
        router.submit(r)
    for r in router.run_to_completion():
        got[r.rid] = list(r.tokens)

    # restart r1 at DOUBLE width: plan the layout change, re-lay the live
    # params per leaf, attach the new engine under the old name
    old = dict(router.replicas)["r1"]
    assert SCFG.padded_vocab_size(1) == SCFG.padded_vocab_size(2)
    m2 = Transformer(SCFG, tp_size=2)
    flat = _flatten(old._params_in, "param")
    plan = plan_reshard(sorted(flat),
                        {k: tuple(v.shape) for k, v in flat.items()},
                        {k: v.dtype.itemsize for k, v in flat.items()},
                        make_layout((("tp", 1),), old.model.specs()),
                        make_layout((("tp", 2),), m2.specs()))
    # widening is pure slicing: local, no wire collective
    assert set(plan.summary()["ops"]) <= {"slice", "copy"}
    mesh2 = make_mesh(MeshConfig(dp=1, tp=2))
    params2 = reshard_params(old._params_in, mesh2, m2.specs())
    jax.block_until_ready(params2)
    router.replace_replica("r1", _sengine(tp=2, params=params2),
                           reshard=plan.summary())

    before = dict(router.dispatched)
    for r in _sreqs(100):
        router.submit(r)
    for r in router.run_to_completion():
        got[r.rid] = list(r.tokens)
    assert router.dispatched["r1"] > before["r1"], \
        "the restarted tp2 replica never served — the identity claim is vacuous"
    assert got == refs

    w.close()
    evs = [json.loads(l) for l in open(tmp_path / "metrics.jsonl")]
    restart = [e for e in evs if e.get("tag") == "replica_restart"]
    assert len(restart) == 1 and restart[0]["replica"] == "r1"
    assert restart[0]["reshard"]["src"] == "single zero0"
    assert restart[0]["reshard"]["dst"] == "tp2 zero0"


# ------------------------------------------- elastic train.py --resume ----

TEXTS = ["the king rode out at dawn with his men",
         "a quiet morning on the river bank",
         "she sold sea shells by the sea shore",
         "to be or not to be that is the question"] * 4

STEP_RE = re.compile(r"^step (\d+)/\d+ -> avg loss ([0-9.]+)", re.M)


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    from distributed_pytorch_from_scratch_tpu.data.tokenizer import (
        pre_tokenize, train_bpe)
    d = tmp_path_factory.mktemp("reshard_corpus")
    text_json = d / "texts.json"
    with open(text_json, "w") as f:
        json.dump({"train": TEXTS, "validation": TEXTS[:2]}, f)
    tok = d / "tokenizer.json"
    # vocab divisible by 4 AND 2: padded_vocab_size must agree across the
    # two widths or the elastic trees would have different shapes
    train_bpe(str(text_json), str(tok), vocab_size=272)
    tokens = d / "tokens.json"
    pre_tokenize(str(text_json), str(tokens), str(tok))
    return tokens


def _train(args, env):
    return subprocess.run(
        [sys.executable, "-m", "distributed_pytorch_from_scratch_tpu.train"]
        + args, capture_output=True, text=True, timeout=900, env=env)


@pytest.mark.slow
def test_elastic_resume_matches_offline_reshard_resume(corpus, tmp_path):
    """train --resume on a DIFFERENT mesh (dp2xtp4 -> dp2xtp2) routes the
    checkpoint through the in-process reshard plan and continues with
    EXACTLY the loss trajectory of the offline path (scripts/
    reshard_ckpt.py to tp2 files, then a normal same-mesh resume): both
    arms run identical dp2xtp2 math from bit-identical state, so the
    printed losses must agree to every digit. The elastic arm also leaves
    a schema-valid `reshard_event` in the metrics stream.

    (A tp4-arm trajectory is NOT pinned here: Adam's rsqrt(nu) amplifies
    the ~1e-4 cross-width reassociation noise the single-step equivalence
    tests allow into per-mille loss drift within 3 steps — a float fact,
    not a reshard one.)"""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONUNBUFFERED": "1"}
    if "xla_force_host_platform_device_count" not in env.get("XLA_FLAGS", ""):
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=8")
    common = ["--data_path", str(corpus),
              "--attn_dim", "32", "--ffn_dim", "64", "--num_heads", "4",
              "--num_layers", "2", "--maxlen", "32", "--batch_size", "4",
              "--log_interval", "1", "--warmup_steps", "2", "--lr", "1e-3",
              "--dp_size", "2"]
    base_dir = str(tmp_path / "base")
    base = _train(common + ["--save_dir", base_dir, "--tp_size", "4",
                            "--max_steps", "3", "--save_interval", "3"], env)
    assert base.returncode == 0, base.stdout + base.stderr
    assert latest_step(base_dir) == 3

    # arm A: the offline CLI reshards the files to dp2xtp2, then a plain
    # same-mesh resume picks them up (no elastic path involved)
    a_dir, b_dir = str(tmp_path / "a"), str(tmp_path / "b")
    shutil.copytree(base_dir, b_dir)
    cli = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(__file__), os.pardir,
                                      "scripts", "reshard_ckpt.py"),
         "--src", base_dir, "--dst", a_dir, "--tp", "2", "--dp", "2"],
        capture_output=True, text=True, timeout=300, env=env)
    assert cli.returncode == 0, cli.stdout + cli.stderr
    rec = json.loads(cli.stdout.strip().splitlines()[-1])
    assert rec["src"] == "dp2xtp4 zero0" and rec["dst"] == "dp2xtp2 zero0"
    assert rec["peak_host_bytes"] <= rec["max_leaf_bytes"]

    resume = ["--tp_size", "2", "--max_steps", "6", "--save_interval",
              "1000", "--resume"]
    same = _train(common + ["--save_dir", a_dir] + resume, env)
    assert same.returncode == 0, same.stdout + same.stderr
    assert "resumed from iter 3" in same.stdout
    assert "elastic resume" not in same.stdout

    # arm B: the in-process elastic path, straight off the tp4 files
    elastic = _train(common + ["--save_dir", b_dir] + resume, env)
    assert elastic.returncode == 0, elastic.stdout + elastic.stderr
    assert "elastic resume: iter 3" in elastic.stdout
    assert "resharded dp2xtp4 zero0 -> dp2xtp2 zero0" in elastic.stdout

    traj_a = {int(s): float(l) for s, l in STEP_RE.findall(same.stdout)}
    traj_b = {int(s): float(l) for s, l in STEP_RE.findall(elastic.stdout)}
    assert sorted(traj_a) == sorted(traj_b) == [4, 5, 6]
    assert [traj_a[s] for s in (4, 5, 6)] == [traj_b[s] for s in (4, 5, 6)]

    # the lineage record forensics joins on (schema v7)
    evs = []
    logs = os.path.join(b_dir, "logs")
    for name in sorted(os.listdir(logs)):
        if name.endswith(".jsonl"):
            evs += [json.loads(l) for l in open(os.path.join(logs, name))]
    rev = [e for e in evs if e.get("tag") == "reshard_event"]
    assert len(rev) == 1, [e.get("tag") for e in evs]
    assert rev[0]["src_layout"] == "dp2xtp4 zero0"
    assert rev[0]["dst_layout"] == "dp2xtp2 zero0"
    assert rev[0]["bytes_moved"] > 0
    assert rev[0]["plan_ops"] and rev[0]["wall_ms"] >= 0
    assert rev[0]["peak_host_bytes"] > 0


def test_gate_treats_reshard_record_as_latency():
    """The reshard record's headline `value` IS a wall latency (unit
    "ms"): a FASTER second run must pass the gate and a slower-past-band
    one must fail, and reshard_bytes_moved stays must-not-grow — the
    drive that caught `value` riding the throughput branch."""
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "check_bench_regression.py")
    spec = importlib.util.spec_from_file_location("_rs_gate", path)
    gate = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gate)

    base = {"metric": "reshard wall ms (tiny, dp2xtp4 zero3 -> tp2 zero0,"
                      " moments included, streamed leaf-at-a-time)",
            "value": 150.0, "unit": "ms", "reshard_ms": 150.0,
            "reshard_bytes_moved": 9_510_912}
    faster = dict(base, value=90.0, reshard_ms=90.0)
    slower = dict(base, value=300.0, reshard_ms=300.0)
    mover = dict(base, reshard_bytes_moved=19_021_824)

    by = {c["field"]: c for c in gate.metric_checks(faster, base,
                                                    10.0, 25.0)[0]}
    assert by["value"]["direction"] == "down" and by["value"]["ok"]
    assert by["reshard_ms"]["ok"]
    assert by["reshard_bytes_moved"]["direction"] == "down"
    assert by["reshard_bytes_moved"]["ok"]

    by = {c["field"]: c for c in gate.metric_checks(slower, base,
                                                    10.0, 25.0)[0]}
    assert not by["value"]["ok"] and not by["reshard_ms"]["ok"]

    by = {c["field"]: c for c in gate.metric_checks(mover, base,
                                                    10.0, 25.0)[0]}
    assert not by["reshard_bytes_moved"]["ok"]
