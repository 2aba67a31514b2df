"""The ssm_dense family's counts at the published widths
(benchmark/lib/ssm_dense_counts.py) against a hand count, the family file's
reference against the program's at the rehearsal shape, the scope readers on
a small capture made of the real step's `op_name`s
(benchmark/lib/ssm_dense_scopes.py), and the check's controls at the
rehearsal shape (benchmark/tools/ssm_dense_control.py)."""

from types import SimpleNamespace

import numpy as np
import pytest

from benchmark.lib import ssm_dense_counts as counts
from benchmark.lib import ssm_dense_scopes, ssm_scopes, trace
from benchmark.lib.files import load_json, load_module

CELL = "granite-4.0-h-micro.train-pp4stage-b1-t4096"
CONFIG = "granite-4.0-h-micro.json"
NEW_READERS = ("model.mamba_proj_ms", "model.mamba_conv_ms",
               "model.mamba_gate_norm_ms", "resid.rms_last")


@pytest.fixture(scope="module")
def sizes():
    family = load_module("families", "ssm_dense")
    return family.sizes_of(load_json("configs", CONFIG))


# ---- the counts, by hand ----

def test_parameters_of_the_cut_at_the_published_widths(sizes):
    """ISSUE 68's arithmetic: a Mamba mixer 25,847,232 (in 2048 x 8512, conv
    4352 x 4 + 4352, out 4096 x 2048, gated norm 4096, 192 of A_log / D /
    dt_bias), an attention mixer 10,485,760, the SwiGLU 50,331,648 in EVERY
    layer, two norms a layer, an eighth of the TIED table once 25,690,112,
    the final norm: 772,160,448 x 16 B = 12.35 GB = 11.51 GiB."""
    parts = counts.param_counts(sizes)
    d = 2048
    mixer = d * 8512 + 4352 * 4 + 4352 + 4096 * d + 4096 + 192
    assert parts["mamba_mixer"] == mixer == 25_847_232
    assert parts["attn_mixer"] == 2 * d * 64 * (32 + 8) == 10_485_760
    assert parts["mlp"] == 3 * d * 8192 == 50_331_648
    assert parts["mamba_layer"] == 76_182_976
    assert parts["attn_layer"] == 60_821_504
    assert parts["embedding"] == 12_544 * d == 25_690_112
    assert parts["total"] == (9 * 76_182_976 + 60_821_504 + 25_690_112
                              + d) == 772_160_448
    assert parts["total"] * 16 / 1e9 == pytest.approx(12.35, abs=0.005)
    assert parts["total"] * 16 / 2 ** 30 == pytest.approx(11.51, abs=0.005)
    assert (sizes.n_layer, sizes.n_mamba_layer, sizes.n_attn_layer) == (
        10, 9, 1)
    assert sizes.layer_types == ("mamba",) * 5 + ("attention",) + (
        "mamba",) * 4
    # and the published model: four periods and the whole table
    whole = sizes._replace(
        layer_types=tuple(load_json("configs", CONFIG)["layer_types"]),
        vocab=100_352)
    assert counts.param_counts(whole)["total"] == 3_191_396_096


def test_the_program_counts_the_same(sizes):
    """The builder's `param_counts` and the leaves `init` makes."""
    import jax
    family = load_module("families", "ssm_dense")
    built = family.build(load_json("configs", CONFIG), {"dp": 1, "tp": 1},
                         "bfloat16")
    cfg = built.model.cfg
    assert cfg.num_params() == counts.param_counts(sizes)["total"]
    shapes = jax.eval_shape(built.model.init, jax.random.key(0))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) == (
        772_160_448)
    assert "lm_head" not in shapes
    # the program's own FLOPs count differs by the non-matmul leaves alone
    # (6 N there: the taps, the norms, the recurrence's few: 0.04%)
    from distributed_pytorch_from_scratch_tpu.training.metrics import (
        model_flops_per_step)
    ours = counts.train_flops_per_token(sizes, 4096)
    theirs = model_flops_per_step(cfg, 1, 4096, cfg.num_params()) / 4096
    assert 0 < theirs - ours < 5e-4 * ours


def test_every_published_width_stands(sizes):
    config = load_json("configs", CONFIG)
    assert config["reduced"] == ["num_layers", "vocab_size"]
    assert config["published"] == {"vocab_size": 100_352,
                                   "num_hidden_layers": 40,
                                   "parameters": 3_191_396_096}
    for key, want in (
            ("hidden_size", 2048), ("shared_intermediate_size", 8192),
            ("mamba_n_heads", 64), ("mamba_d_head", 64),
            ("mamba_d_state", 128), ("mamba_n_groups", 1),
            ("mamba_d_conv", 4), ("mamba_chunk_size", 256),
            ("mamba_expand", 2), ("num_attention_heads", 32),
            ("num_key_value_heads", 8), ("embedding_multiplier", 12),
            ("residual_multiplier", 0.22),
            ("attention_multiplier", 0.015625), ("logits_scaling", 8),
            ("num_hidden_layers", 40), ("tie_word_embeddings", True),
            ("position_embedding_type", "nope"), ("num_layers", 10),
            ("vocab_size", 12_544)):
        assert config[key] == want, key
    assert len(config["layer_types"]) == 40
    assert [i for i, name in enumerate(config["layer_types"])
            if name == "attention"] == [5, 15, 25, 35]
    assert (sizes.m_inner, sizes.m_conv_channels, sizes.head_dim) == (
        4096, 4352, 64)
    for key in ("initializer_range", "time_step_min", "time_step_max",
                "time_step_floor", "gated_norm", "time_step_limit",
                "recurrence_chunk", "recurrence_state", "initialisation"):
        assert key in config["assumed"], key


def test_flops_per_token(sizes):
    """6 x the matmul parameters (the tied table once), attention at the
    full T^2 in ONE layer, three times the recurrence at chunk 256 with one
    group in nine."""
    matmul = (9 * (2048 * 8512 + 4096 * 2048) + 10_485_760
              + 10 * 50_331_648 + 12_544 * 2048)
    assert counts.matmul_params(sizes) == matmul == 771_883_008
    scan = 2 * 256 * 128 + 64 * (2 * 256 * 64 + 4 * 64 * 128)
    assert counts.ssd_flops_per_token(sizes) == scan == 4_259_840
    assert counts.train_flops_per_token(sizes, 4096) == (
        6.0 * matmul + 12.0 * 32 * 64 * 4096 + 3.0 * 9 * scan)
    # the recurrence is 2.4% of the step's FLOPs and attention 2.1%
    total = counts.train_flops_per_token(sizes, 4096)
    assert 27 * scan / total == pytest.approx(0.0238, abs=0.001)


def test_what_a_layers_recurrence_must_compute_and_move(sizes):
    """4096 tokens: 3 x 4096 x 4.26 MFLOP = 52.3 GFLOP; x and y 2 x 4096 ch
    bf16, B and C 2 x 128 bf16, dt 64 f32, 16 chunk states of 4096 x 128
    f32, each once each way: 184.0 MB. Memory binds on a v5e (0.225 ms
    against 0.266 ms of FLOPs... the FLOPs bind: 52.3 G / 197 T = 0.266 ms
    over 184.0 MB / 819 GB/s = 0.225 ms)."""
    cost = counts.ssd_cost(1, 4096, sizes, 2)
    assert cost.flops == 3.0 * 4096 * 4_259_840
    xy = 4096 * 2 * 4096 * 2
    bc = 4096 * 2 * 128 * 2
    dt = 4096 * 64 * 4
    states = 16 * 4096 * 128 * 4
    assert cost.bytes == 2.0 * (xy + bc + dt + states) == 207_618_048.0
    assert cost.flops / 197e12 > cost.bytes / 819e9


# ---- the family file's reference is the program's ----

def test_the_family_files_reference_is_the_programs():
    """At the rehearsal shape, float32: the benchmark's own copy of the
    reference against the program's loss and every gradient leaf."""
    import jax
    from benchmark.lib.cells import load_cell
    from distributed_pytorch_from_scratch_tpu.config import MeshConfig
    from distributed_pytorch_from_scratch_tpu.runtime.mesh import make_mesh
    workload, config = load_cell(CELL, rehearse=True)
    built = load_module("families", "ssm_dense").build(
        config, workload["mesh"], "float32")
    model = built.model
    mesh = make_mesh(MeshConfig(dp=1, tp=1), devices=jax.devices()[:1])
    params = model.init(jax.random.key(2))
    rng = np.random.default_rng(0)
    ids = rng.integers(0, config["vocab_size"], (2, 81)).astype(np.int32)
    pos = np.tile(np.arange(80, dtype=np.int32), (2, 1))
    with jax.default_matmul_precision("highest"):
        want, want_g = jax.jit(jax.value_and_grad(built.reference_loss))(
            params, ids[:, :-1], ids[:, 1:], pos)
        got, got_g = jax.jit(jax.value_and_grad(model.make_loss(mesh)))(
            params, ids[:, :-1], ids[:, 1:], pos)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(want_g),
                            jax.tree.leaves(got_g)):
        a, b = np.asarray(a), np.asarray(b)
        assert np.max(np.abs(a - b)) <= 5e-5 * max(np.max(np.abs(a)), 1e-6), \
            jax.tree_util.keystr(path)


# ---- the scope split and the readers, on a small capture ----

WHILE = "jit(step)/jit(main)/transpose(jvp(while))/body/"
REMAT = WHILE + "closed_call/checkpoint/rematted_computation/"
FWD = "jit(step)/jit(main)/jvp(while)/body/closed_call/checkpoint/"
# (event name, its instruction, op_name of the step's text, part, mamba part)
OPS = [
    ("fusion.1", "fusion", FWD + "mamba/in_proj/dot_general", "mamba",
     "mamba/in_proj"),
    ("fusion.2", "fusion", REMAT + "mamba/conv/mul", "mamba", "mamba/conv"),
    ("fusion.3", "fusion", REMAT + "mamba/ssd/exp", "mamba", "mamba/ssd"),
    ("fusion.4", "fusion", WHILE + "closed_call/checkpoint/mamba/ssd/while/"
     "body/mul", "mamba", "mamba/ssd"),
    ("fusion.5", "fusion", REMAT + "mamba/gate_norm/rsqrt", "mamba",
     "mamba/gate_norm"),
    ("fusion.6", "fusion", REMAT + "mamba/out_proj/dot_general", "mamba",
     "mamba/out_proj"),
    ("fusion.7", "fusion", REMAT + "mamba/convert_element_type", "mamba",
     "mamba/other"),
    ("fusion.8", "fusion", REMAT + "gqa_attn/dot_general", "gqa_attn", None),
    ("fusion.9", "fusion", REMAT + "dense_ffn/dot_general", "dense_ffn",
     None),
    ("fusion.10", "fusion", WHILE + "closed_call/checkpoint/dense_ffn/mul",
     "dense_ffn", None),
    ("flash_fwd.40", "custom-call tpu_custom_call operands=3",
     REMAT + "flash_fwd", "flash", None),
    ("flash_bwd.2", "custom-call tpu_custom_call operands=6",
     WHILE + "closed_call/checkpoint/flash_bwd", "flash", None),
    ("fusion.15", "fusion", FWD + "head_loss/convert_element_type",
     "head_loss", None),
    ("fusion.16", "fusion", "jit(step)/optimizer/mul", "optimizer", None),
    ("fusion.17", "fusion", "jit(step)/grad_norm/reduce_sum", "grad_norm",
     None),
    # the scaled residual add, the norms: no scope of the list
    ("fusion.18", "fusion", WHILE + "closed_call/checkpoint/mul", "rest",
     None),
    ("copy.7", "copy", None, "unattributed", None),
]


def capture(steps=2, each_ns=1000):
    """`steps` runs of the step program on chip 0, every op of OPS once a
    run, op i lasting (i + 1) * each_ns, back to back."""
    events, runs, t = [], [], 0
    for _ in range(steps):
        start = t
        for i, (name, meta, *_) in enumerate(OPS):
            events.append(trace.Event(name, t, (i + 1) * each_ns, meta))
            t += (i + 1) * each_ns
        runs.append((start, t))
        t += 500                                        # an idle gap
    dev = trace.DeviceTrace(0, (0, runs[-1][1]), steps, events, [])
    return dev, runs, {name: op for name, _, op, *_ in OPS if op}


def test_every_op_falls_in_one_part_and_the_parts_sum_to_busy():
    dev, runs, names = capture()
    parts = ssm_dense_scopes.scope_ns(dev, runs, names)
    assert set(parts) == set(ssm_dense_scopes.PARTS)
    assert sum(parts.values()) == dev.busy_ns()
    want = dict.fromkeys(ssm_dense_scopes.PARTS, 0)
    inner = dict.fromkeys(ssm_scopes.MAMBA_PARTS, 0)
    for i, (_, _, _, part, mamba_part) in enumerate(OPS):
        want[part] += 2 * (i + 1) * 1000
        if mamba_part:
            inner[mamba_part] += 2 * (i + 1) * 1000
    assert parts == want
    # the mixer's time by part is a split of it, not parts beside it
    got = ssm_scopes.mamba_parts_ns(dev, runs, names)
    assert got == inner and sum(got.values()) == parts["mamba"]
    outside = ssm_dense_scopes.scope_ns(dev, runs[:1], names)
    assert outside["other_programs"] == sum(want.values()) // 2


def test_the_readers_read_the_runners_fields(sizes):
    dev, runs, names = capture()
    parts = ssm_dense_scopes.scope_ns(dev, runs, names)
    workload = load_json("workloads", CELL + ".json")
    peak = SimpleNamespace(flops_per_s=197e12, hbm_bytes_per_s=819e9)
    cost = counts.ssd_cost(1, 4096, sizes, 2)
    m = SimpleNamespace(
        devices=[dev], scopes=parts, peak=peak, sizes=sizes,
        mamba_parts=ssm_scopes.mamba_parts_ns(dev, runs, names),
        workload=workload, mesh={"dp": 1, "tp": 1}, chips=1,
        tokens_per_s=17000.0, ssm_decay_min=-61.5, resid_rms_last=1.31,
        flops_per_token=counts.train_flops_per_token(sizes, 4096),
        ssd_cost=cost)
    read = lambda name: load_module("layer_metrics", name).read(m)
    assert read("model.mamba_ms") == pytest.approx(sum(range(1, 8)) * 1e-3)
    ssd_ms = (3 + 4) * 1e-3
    assert read("model.ssd_ms") == pytest.approx(ssd_ms)
    # the new readers: the mixer's other parts, which with `model.ssd_ms`
    # (and what the mixer does under no inner scope) sum to `model.mamba_ms`
    assert read("model.mamba_proj_ms") == pytest.approx((1 + 6) * 1e-3)
    assert read("model.mamba_conv_ms") == pytest.approx(2 * 1e-3)
    assert read("model.mamba_gate_norm_ms") == pytest.approx(5 * 1e-3)
    assert read("resid.rms_last") == 1.31
    assert (read("model.mamba_proj_ms") + read("model.mamba_conv_ms")
            + read("model.mamba_gate_norm_ms") + read("model.ssd_ms")
            + 7 * 1e-3) == pytest.approx(read("model.mamba_ms"))
    # the FLOPs bind at chunk 256 with one group, nine layers
    assert read("model.ssd_roofline") == pytest.approx(
        100 * 9 * (cost.flops / 197e12) / (ssd_ms / 1e3))
    assert read("ssm.decay_min") == -61.5
    # and the readers written for the other runners take this `measured`
    assert read("model.gqa_attn_ms") == pytest.approx(8 * 1e-3)
    assert read("model.dense_ffn_ms") == pytest.approx((9 + 10) * 1e-3)
    assert read("kernels.flash_ms") == pytest.approx((11 + 12) * 1e-3)
    assert read("kernels.flash_fwd_per_bwd") == 1.0
    assert read("kernels.gqa_flash_roofline") > 0
    assert read("train_step.mfu_pct") == pytest.approx(
        100 * m.flops_per_token * 17000 / 197e12)
    assert read("model.xla_ops_ms") > 0 and read("device.step_ms") > 0


def test_the_new_readers_return_nothing_where_there_is_nothing_to_read():
    """A program without the family (the parent's), a runner that hands no
    scope split or another family's, an untraced run: None, not an
    exception."""
    empty = SimpleNamespace(devices=[], peak=None, tokens_per_s=1.0, chips=1)
    bare = SimpleNamespace()
    dev, runs, names = capture()
    from benchmark.lib import scopes
    other = SimpleNamespace(devices=[dev], peak=SimpleNamespace(
        flops_per_s=197e12, hbm_bytes_per_s=819e9),
        scopes=scopes.scope_ns(dev, runs, names))     # no `mamba` in it
    untraced = SimpleNamespace(devices=[], mamba_parts=None,
                               resid_rms_last=None)
    for m in (empty, bare, other, untraced):
        for name in NEW_READERS:
            assert load_module("layer_metrics", name).read(m) is None


def test_the_manifest_lists_the_cell_where_it_reports():
    manifest = load_json("..", "BENCHMARK.json")
    listed = {m["name"] for m in manifest["per_layer"]
              if CELL in m.get("workloads", ())}
    assert set(NEW_READERS) <= listed
    assert {"model.mamba_ms", "model.ssd_ms", "model.ssd_roofline",
            "ssm.decay_min", "model.gqa_attn_ms", "model.dense_ffn_ms",
            "kernels.gqa_flash_roofline", "kernels.flash_ms",
            "kernels.flash_fwd_per_bwd", "train_step.mfu_pct",
            "train_step.step_ms_median", "entry.compiles_in_window",
            "model.xla_ops_ms", "device.step_ms", "device.idle_pct",
            "device.peak_hbm_gib"} <= listed
    for name in NEW_READERS:
        entry = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "tokens_per_s_per_chip"


# ---- the check and its controls, at the rehearsal shape ----

def test_a_reading_over_a_limit_is_not_correct():
    runner = load_module("runners", "train_ssm_dense")
    limits = runner.GRAD_RTOL["bfloat16"]
    assert all(0 < v < 1 for v in limits.values())
    passed = {"ok": True, "rel_err": {}, "rtol": {}}
    rng = np.random.default_rng(0)
    want = {"ssm/mamba_layers_0/A_log": rng.normal(size=(5, 64)),
            "ssm/mamba_layers_1/w_in": rng.normal(size=(4, 600)),
            "attn/attn_layers_0/wq": rng.normal(size=(1, 600)),
            "rest/mamba_layers_0/gate_proj/weight": rng.normal(size=(5, 600)),
            "rest/embedding": rng.normal(size=(1, 600))}
    compare = lambda **off: runner._compare_grads(
        passed, "bfloat16",
        {k: v * off.get(k.split("/")[-1], 1.0) for k, v in want.items()},
        want)
    assert compare()["ok"] and set(compare()["rel_err"]) == set(limits)
    assert compare(A_log=1 + 0.9 * limits["ssm_grad"])["ok"]
    assert not compare(A_log=1 + 1.1 * limits["ssm_grad"])["ok"]
    assert not compare(w_in=1 + 1.1 * limits["ssm_grad"])["ok"]
    assert not compare(wq=1 + 1.1 * limits["attn_grad"])["ok"]
    assert compare(wq=1 + 0.9 * limits["attn_grad"])["ok"]
    assert not compare(embedding=1 + 3 * limits["sampled_grads"])["ok"]
    assert not compare(w_in=np.nan)["ok"]
    assert not runner._compare_grads({**passed, "ok": False}, "bfloat16",
                                     want, want)["ok"]


@pytest.fixture(scope="module")
def control():
    return load_module("tools", "ssm_dense_control")


@pytest.fixture(scope="module")
def sound(control):
    return control.reading(CELL, 5, rehearse=True)


@pytest.mark.parametrize("name,reading", [
    ("bf16_state", "ssm_grad"), ("residual_one", "grad_norm"),
    ("softmax_default", "attn_grad"), ("logits_unscaled", "loss"),
    ("embed_unscaled", "sampled_grads"), ("norm_before_gate", "ssm_grad")])
def test_a_control_reads_over_a_limit_and_is_put_back(control, sound, name,
                                                      reading):
    """Every control is ANOTHER program, read over the limit it is there
    for (the rehearsal's float32 limits), and leaves the sound one behind
    it."""
    from distributed_pytorch_from_scratch_tpu.models import ssm_dense
    from distributed_pytorch_from_scratch_tpu.ops import ssd as op
    from distributed_pytorch_from_scratch_tpu.parallel import mamba
    gate = mamba.Mamba2Mixer._gate_norm
    got = control.reading(CELL, 5, name, rehearse=True)
    assert sound["ok"] and sound["control"] is None
    assert got["control"] == name and not got["ok"]
    assert got["rel_err"][reading] > got["rtol"][reading] > (
        sound["rel_err"][reading])
    assert mamba.ssd is op.ssd and mamba.Mamba2Mixer._gate_norm is gate
    assert isinstance(vars(ssm_dense.SsmDenseTransformer)["residual_scale"],
                      property)
    model = load_module("families", "ssm_dense").build(
        load_json("configs", CONFIG), {"dp": 1, "tp": 1}, "bfloat16").model
    assert (model.embed_scale, model.residual_scale, model.softmax_scale,
            model.logit_scale) == (12.0, 0.22, 0.015625, 0.125)
