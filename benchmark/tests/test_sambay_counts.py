"""The sambay family's counts at the published widths
(benchmark/lib/sambay_counts.py) against a hand count and a brute-force
count at a tiny shape, the family file's reference against the program's at
the rehearsal shape, the scope readers on a small capture made of the real
step's `op_name`s (benchmark/lib/sambay_scopes.py), the manifest's new
entries, and the check's controls at the rehearsal shape
(benchmark/tools/sambay_control.py)."""

from types import SimpleNamespace

import numpy as np
import pytest

from benchmark.lib import sambay_counts as counts
from benchmark.lib import sambay_scopes, trace
from benchmark.lib.files import load_json, load_module

CELL = "phi-4-mini-flash-reasoning.train-b1-t16384"
CONFIG = "phi-4-mini-flash-reasoning.json"
NEW_READERS = ("model.mamba1_ms", "model.sscan_ms", "model.sscan_roofline",
               "model.diff_attn_ms", "model.cross_attn_ms", "model.gmu_ms",
               "kernels.diff_flash_roofline", "diff.lambda_mean",
               "ssm1.decay_min")
PEAK = SimpleNamespace(flops_per_s=197e12, hbm_bytes_per_s=819e9)


@pytest.fixture(scope="module")
def sizes():
    family = load_module("families", "sambay")
    return family.sizes_of(load_json("configs", CONFIG))


# ---- the counts, by hand ----

def test_parameters_of_the_cut_at_the_published_widths(sizes):
    """ISSUE 76's table: a layer by kind, the table's eighth, the final
    norm: 697,094,272; all 32 layers and the whole table 3,852,562,944."""
    parts = counts.param_counts(sizes)
    assert parts == {
        "embedding": 64_020_480, "final_norm": 5120,
        "mamba_layers": 2 * 119_895_040, "swa_layers": 98_322_304,
        "full_layers": 98_322_304, "gmu_layers": 104_867_840,
        "cross_layers": 91_766_144}
    assert sum(parts.values()) == 697_094_272
    assert counts.mamba_params(sizes) == 41_241_600
    assert counts.attn_params(sizes) == 19_668_864
    assert counts.attn_params(sizes, True) == 13_112_704
    assert counts.gmu_params(sizes) == 26_214_400
    family = load_module("families", "sambay")
    kinds = family.layer_kinds(32, 2)
    whole = sizes._replace(layers=tuple(enumerate(kinds)), vocab=200_064)
    assert sum(counts.param_counts(whole).values()) == 3_852_562_944
    assert [whole.count(k) for k in counts.KINDS] == [9, 8, 1, 7, 7]


def test_the_program_counts_the_same(sizes):
    import jax
    model = load_module("families", "sambay").build(
        load_json("configs", CONFIG), {"dp": 1, "tp": 1}, "bfloat16").model
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    assert sum(int(np.prod(x.shape))
               for x in jax.tree.leaves(shapes)) == 697_094_272
    assert type(model).num_params(model.cfg) == 697_094_272
    assert model.cfg.sambay.layers_here == (0, 1, 16, 17, 18, 19)


def test_every_published_width_stands(sizes):
    """The configuration holds the catalog's numbers but the two it states
    as reduced, and the groups ISSUE 76 names."""
    config = load_json("configs", CONFIG)
    published = {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 10240, "layer_norm_eps": 1e-05,
        "max_position_embeddings": 262144, "mb_per_layer": 2,
        "model_type": "phi4flash", "num_attention_heads": 40,
        "num_hidden_layers": 32, "num_key_value_heads": 20,
        "resid_pdrop": 0, "sliding_window": 512,
        "tie_word_embeddings": True, "mlp_bias": False,
        "lm_head_bias": False}
    assert {k: config[k] for k in published} == published
    assert config["reduced"] == ["num_layers", "vocab_size"]
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert config["published"] == {"vocab_size": 200_064,
                                   "num_hidden_layers": 32,
                                   "parameters": 3_852_562_944}
    assert config["num_layers"] == len(config["layers_here"]) == 6
    for key in ("mamba_d_state", "mamba_d_conv", "mamba_expand",
                "mamba_dt_rank", "attention_bias", "lambda_std",
                "head_pairing", "map_of_kinds", "initializer_range",
                "scan_state", "optimizer", "differential_attention"):
        assert key in config["assumed"], key
    assert "697,094,272" in config["deployment"]
    entry = next(c for c in load_json("..", "BENCHMARK.json")["configs"]
                 if c["name"] == "phi-4-mini-flash-reasoning")
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    assert (sizes.d_model, sizes.m_inner, sizes.m_state, sizes.m_rank) == (
        2560, 5120, 16, 160)


def test_flops_per_token(sizes):
    t = 16_384
    matmul = (64_020_480 + 6 * 78_643_200
              + 2 * (41_241_600 - 5120 * 5 - 5120 - 81_920 - 5120)
              + 2 * (19_668_864 - 7680 - 384) + 26_214_400
              + (13_112_704 - 5120 - 384))
    assert counts.matmul_params(sizes) == matmul
    assert counts.train_flops_per_token(sizes, t) == pytest.approx(
        6 * matmul + 6 * 40 * (512 + 2 * t) * 192 + 3 * 2 * 7 * 5120 * 16)


def test_the_counts_are_a_brute_force_count_at_a_tiny_shape():
    """Every (query, key) pair and every (token, channel, state) triple
    walked in Python."""
    s = counts.SambaYSizes(
        d_model=8, d_ff=12, n_head=4, n_kv_head=2, head_dim=2, swa_window=3,
        m_inner=16, m_state=2, m_rank=1, conv=4,
        layers=((0, "mamba"), (1, "swa"), (4, "mamba"), (5, "full"),
                (6, "gmu"), (7, "cross")), vocab=11)
    b, t = 2, 7
    for window in (3, None, 50):
        live = sum(1 for q in range(t) for k in range(t)
                   if k <= q and (window is None or q - k < window))
        assert counts.live_entries(t, window) == live
        cost = counts.diff_flash_call_cost(b, t, s, 2, False, window)
        # a map's entry: q . k over 2 and the pair's value over 4
        assert cost.flops == b * 4 * live * (2 * 2 + 2 * 4)
        assert cost.bytes == 2 * b * t * (4 * 2 + 2 * 2 + 2 * 4 + 4 * 4)
        back = counts.diff_flash_call_cost(b, t, s, 2, True, window)
        assert back.flops == 2.5 * cost.flops
    updates = sum(1 for _ in range(b) for _ in range(t) for _ in range(16)
                  for _ in range(2))
    scan = counts.sscan_cost(b, t, s, 2)
    assert scan.flops == 3 * 7 * updates
    assert scan.bytes == (b * t * 16 * (2 + 8) + b * t * 16 * (4 + 16)
                          + 6 * b * t * 2 * 4)
    assert counts.gmu_cost(b, t, s, 2).flops == 3 * 2 * b * t * 2 * 8 * 16
    assert counts.vector_ops_per_s(PEAK) == pytest.approx(197e12 / 32)


def test_what_a_layers_scan_must_compute_and_move(sizes):
    cost = counts.sscan_cost(1, 16_384, sizes, 2)
    assert cost.flops == 3 * 7 * 16_384 * 5120 * 16
    # the vector unit binds: 4.6 ms against 3.0 of HBM
    vector = cost.flops / (197e12 / 32)
    assert counts.sscan_floor_seconds(cost, PEAK) == pytest.approx(vector)
    assert 0.004 < vector < 0.005 and cost.bytes / 819e9 < vector


def test_the_family_files_reference_is_the_programs():
    """`benchmark/families/sambay.py` (attention in blocks of 512 query
    rows, the recurrence under a checkpoint in blocks of 64 steps) and
    `models/vanilla_sambay.py` (whole score matrices, one scan) compute one
    loss and one gradient on the cell's rehearsal shape."""
    import jax
    from benchmark.lib.cells import load_cell
    from distributed_pytorch_from_scratch_tpu.models import (
        vanilla_sambay as ref)
    workload, config = load_cell(CELL, rehearse=True)
    built = load_module("families", "sambay").build(
        config, workload["mesh"], "float32")
    cfg = built.model.cfg
    params = built.model.init(jax.random.key(1))
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (2, 97)).astype(np.int32)
    ids, tgt = ids[:, :-1], ids[:, 1:]
    with jax.default_matmul_precision("highest"):
        ours, grads = jax.jit(jax.value_and_grad(built.reference_loss))(
            params, ids, tgt, ids)
        theirs, their_grads = jax.jit(jax.value_and_grad(
            lambda p: ref.vanilla_loss(cfg, p, ids, tgt, ids)))(params)
    assert float(ours) == pytest.approx(float(theirs), rel=1e-6)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(their_grads)):
        np.testing.assert_allclose(
            a, b, atol=2e-5 * max(float(np.max(np.abs(b))), 1e-3),
            err_msg=jax.tree_util.keystr(path))


# ---- the scope readers, on a capture made of the step's op_names ----

WHILE = "jit(step)/jit(main)/while/body/"
REMAT = WHILE + "closed_call/checkpoint/rematted_computation/"
# (instruction name, meta, op_name, the part it falls in, its mixer part)
OPS = [
    ("fusion.1", "fusion", WHILE + "mamba1/in_proj/dot_general", "mamba1",
     "mamba1/in_proj"),
    ("fusion.2", "fusion", WHILE + "mamba1/conv/mul", "mamba1",
     "mamba1/conv"),
    ("fusion.3", "fusion", REMAT + "mamba1/x_proj/dot_general", "mamba1",
     "mamba1/x_proj"),
    ("fusion.4", "fusion", REMAT + "mamba1/dt_proj/softplus", "mamba1",
     "mamba1/dt_proj"),
    ("sscan_fwd.1", "custom-call tpu_custom_call operands=5",
     REMAT + "mamba1/sscan/sscan_fwd", "mamba1", "mamba1/sscan"),
    ("sscan_bwd.1", "custom-call tpu_custom_call operands=7",
     WHILE + "closed_call/checkpoint/mamba1/sscan/sscan_bwd", "mamba1",
     "mamba1/sscan"),
    ("fusion.5", "fusion", WHILE + "mamba1/gate/mul", "mamba1",
     "mamba1/gate"),
    ("fusion.6", "fusion", WHILE + "mamba1/out_proj/dot_general", "mamba1",
     "mamba1/out_proj"),
    ("fusion.7", "fusion", WHILE + "mamba1/mul", "mamba1", "mamba1/other"),
    ("fusion.8", "fusion", WHILE + "diff_attn/dot_general", "diff_attn",
     None),
    ("fusion.9", "fusion", WHILE + "cross_attn/dot_general", "cross_attn",
     None),
    ("fusion.10", "fusion", WHILE + "gmu/dot_general", "gmu", None),
    ("fusion.11", "fusion", WHILE + "dense_ffn/dot_general", "dense_ffn",
     None),
    ("flash_fwd_window.1", "custom-call tpu_custom_call operands=3",
     REMAT + "flash_fwd_window", "flash", None),
    ("flash_fwd.2", "custom-call tpu_custom_call operands=3",
     REMAT + "flash_fwd", "flash", None),
    ("flash_bwd_window.1", "custom-call tpu_custom_call operands=6",
     WHILE + "closed_call/checkpoint/flash_bwd_window", "flash", None),
    ("flash_bwd.2", "custom-call tpu_custom_call operands=6",
     WHILE + "closed_call/checkpoint/flash_bwd", "flash", None),
    ("fusion.15", "fusion", "jit(step)/head_loss/convert_element_type",
     "head_loss", None),
    ("fusion.16", "fusion", "jit(step)/optimizer/mul", "optimizer", None),
    ("fusion.17", "fusion", "jit(step)/grad_norm/reduce_sum", "grad_norm",
     None),
    # the LayerNorms, the residual adds, a shared value's summed cotangent
    ("fusion.18", "fusion", WHILE + "closed_call/checkpoint/add_any", "rest",
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
    parts = sambay_scopes.scope_ns(dev, runs, names)
    assert set(parts) == set(sambay_scopes.PARTS)
    assert sum(parts.values()) == dev.busy_ns()
    want = dict.fromkeys(sambay_scopes.PARTS, 0)
    inner = dict.fromkeys(sambay_scopes.MAMBA1_PARTS, 0)
    for i, (_, _, _, part, mixer_part) in enumerate(OPS):
        want[part] += 2 * (i + 1) * 1000
        if mixer_part:
            inner[mixer_part] += 2 * (i + 1) * 1000
    assert parts == want
    got = sambay_scopes.mamba1_parts_ns(dev, runs, names)
    assert got == inner and sum(got.values()) == parts["mamba1"]
    outside = sambay_scopes.scope_ns(dev, runs[:1], names)
    assert outside["other_programs"] == sum(want.values()) // 2


def measured(sizes):
    dev, runs, names = capture()
    return SimpleNamespace(
        devices=[dev], scopes=sambay_scopes.scope_ns(dev, runs, names),
        mamba1_parts=sambay_scopes.mamba1_parts_ns(dev, runs, names),
        peak=PEAK, sizes=sizes, workload=load_json("workloads",
                                                   CELL + ".json"),
        mesh={"dp": 1, "tp": 1}, chips=1, tokens_per_s=12000.0,
        sscan_decay_min=-1.7, diff_lambda_mean=0.61, resid_rms_last=1.0,
        flops_per_token=counts.train_flops_per_token(sizes, 16_384),
        sscan_cost=counts.sscan_cost(1, 16_384, sizes, 2))


def test_the_readers_read_the_runners_fields(sizes):
    m = measured(sizes)
    read = lambda name: load_module("layer_metrics", name).read(m)
    assert read("model.mamba1_ms") == pytest.approx(sum(range(1, 10)) * 1e-3)
    assert read("model.sscan_ms") == pytest.approx((5 + 6) * 1e-3)
    assert read("model.diff_attn_ms") == pytest.approx(10 * 1e-3)
    assert read("model.cross_attn_ms") == pytest.approx(11 * 1e-3)
    assert read("model.gmu_ms") == pytest.approx(12 * 1e-3)
    assert read("diff.lambda_mean") == 0.61
    assert read("ssm1.decay_min") == -1.7
    # two Mamba layers at the vector unit's floor over what the scans took
    floor = counts.sscan_floor_seconds(m.sscan_cost, PEAK)
    assert read("model.sscan_roofline") == pytest.approx(
        100 * 2 * floor / (11 * 1e-6))
    # one window call and one full call each way, at their live entries
    t = 16_384
    seconds = lambda back, window: max(
        c.flops / 197e12, c.bytes / 819e9) if (c := (
            counts.diff_flash_call_cost(1, t, sizes, 2, back, window))
        ) else 0
    least = (seconds(False, 512) + seconds(True, 512) + seconds(False, None)
             + seconds(True, None))
    assert read("kernels.diff_flash_roofline") == pytest.approx(
        100 * least / ((14 + 15 + 16 + 17) * 1e-6))
    # and the readers written for the other runners take this `measured`
    assert read("model.dense_ffn_ms") == pytest.approx(13 * 1e-3)
    assert read("kernels.flash_ms") == pytest.approx(
        (14 + 15 + 16 + 17) * 1e-3)
    assert read("kernels.window_flash_ms") == pytest.approx(
        (14 + 16) * 1e-3)
    assert read("kernels.flash_fwd_per_bwd") == 1.0
    assert read("train_step.mfu_pct") == pytest.approx(
        100 * m.flops_per_token * 12000 / 197e12)
    assert read("model.xla_ops_ms") > 0 and read("device.step_ms") > 0
    # (the swa family's readers find no `window` in these sizes)
    assert read("kernels.window_flash_roofline") is None


def test_the_new_readers_return_nothing_where_there_is_nothing_to_read():
    """A program without the family (the parent's), a runner that hands no
    scope split or another family's, an untraced run: None, not an
    exception."""
    empty = SimpleNamespace(devices=[], peak=None, tokens_per_s=1.0, chips=1)
    bare = SimpleNamespace()
    dev, runs, names = capture()
    from benchmark.lib import scopes
    other = SimpleNamespace(
        devices=[dev], peak=PEAK, scopes=scopes.scope_ns(dev, runs, names),
        sizes=SimpleNamespace(n_head=4, head_dim=8, window=16))
    untraced = SimpleNamespace(devices=[], mamba1_parts=None, scopes=None,
                               peak=PEAK, sizes=SimpleNamespace())
    for m in (empty, bare, other, untraced):
        for name in NEW_READERS:
            assert load_module("layer_metrics", name).read(m) is None


def test_the_manifest_lists_the_cell_where_it_reports():
    manifest = load_json("..", "BENCHMARK.json")
    listed = {m["name"] for m in manifest["per_layer"]
              if CELL in m.get("workloads", ())}
    assert set(NEW_READERS) <= listed
    assert {"train_step.mfu_pct", "train_step.step_ms_median",
            "model.xla_ops_ms", "kernels.flash_ms", "kernels.window_flash_ms",
            "kernels.flash_fwd_per_bwd", "model.dense_ffn_ms",
            "entry.compiles_in_window", "device.step_ms", "device.idle_pct",
            "device.peak_hbm_gib"} <= listed
    for name in NEW_READERS:
        entry = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "tokens_per_s_per_chip"
        assert set(entry) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
    # appended: the new cell is the last of every list it joined
    assert manifest["workloads"][-1]["name"] == CELL
    assert manifest["workloads"][-1]["chips"] == 1
    assert len(manifest["workloads"][-1]["why"]) <= 200
    assert len(manifest["configs"][-1]["why"]) <= 200
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in metric.get("workloads", ()):
            assert metric["workloads"][-1] == CELL, metric["name"]
    for name in ("tokens_per_s_per_chip", "step_ms_p90"):
        entry = next(m for m in manifest["end_to_end"] if m["name"] == name)
        assert CELL in entry["workloads"]
    assert [m["name"] for m in manifest["per_layer"][-len(NEW_READERS):]] == (
        list(NEW_READERS))


# ---- the check and its controls, at the rehearsal shape ----

def test_a_reading_over_a_limit_is_not_correct():
    runner = load_module("runners", "train_sambay")
    limits = runner.GRAD_RTOL["bfloat16"]
    assert all(0 < v < 1 for v in limits.values())
    passed = {"ok": True, "rel_err": {}, "rtol": {}}
    rng = np.random.default_rng(0)
    want = {"scan/mamba_layers_0/A_log": rng.normal(size=(1, 64)),
            "diff/swa_layers_0/wq/weight": rng.normal(size=(1, 600)),
            "diff/swa_layers_0/subln": rng.normal(size=(1, 128)),
            **{f"diff/swa_layers_0/{v}": 1e-3 * rng.normal(size=(1, 64))
               for v in runner.LAMBDAS},
            "shared/full_layers/wk/weight": rng.normal(size=(1, 600)),
            "gmu/gmu_layers_0/w_in": rng.normal(size=(1, 600)),
            "nogradient/full_layers/wk/bias": rng.normal(size=(1, 60)),
            "rest/mamba_layers_0/gate_proj/weight": rng.normal(size=(1, 600)),
            "rest/embedding": rng.normal(size=(1, 600))}
    leaf = lambda k: k.split("/", 2)[-1].replace("/", "_")
    compare = lambda **off: runner._compare_grads(
        passed, "bfloat16",
        {k: v * off.get(leaf(k), 1.0) for k, v in want.items()}, want)
    assert compare()["ok"] and set(compare()["rel_err"]) == set(limits)
    for reading, name in (("scan_grad", "A_log"), ("diff_grad", "wq_weight"),
                          ("shared_grad", "wk_weight"),
                          ("gmu_grad", "w_in")):
        assert compare(**{name: 1 + 0.9 * limits[reading]})["ok"]
        assert not compare(**{name: 1 + 1.1 * limits[reading]})["ok"]
    assert not compare(embedding=1 + 3 * limits["sampled_grads"])["ok"]
    # a bias on the keys has no gradient: whatever rounding left is not read
    assert compare(wk_bias=3.0)["ok"]
    # a layer's four lambda vectors are ONE leaf, over their norm PLUS the
    # norm of the gradient at the heads' norm weight: a scalar's gradient
    # that cancelled to a thousandth of the layer's scale may be off by its
    # own size, and one of the layer's scale may not
    lambdas = compare(lambda_q1=2.0, lambda_k2=0.0)
    assert lambdas["ok"] and "diff/swa_layers_0/lambda_q1" not in (
        lambdas["grad_by_leaf"])
    assert 0 < lambdas["grad_by_leaf"]["diff/swa_layers_0/lambdas"][0] < 1e-2
    assert not compare(lambda_q1=4e3)["ok"]
    assert not compare(A_log=np.nan)["ok"]
    assert not runner._compare_grads({**passed, "ok": False}, "bfloat16",
                                     want, want)["ok"]


@pytest.fixture(scope="module")
def control():
    return load_module("tools", "sambay_control")


@pytest.fixture(scope="module")
def sound(control):
    return control.reading(CELL, 5, rehearse=True)


@pytest.mark.parametrize("name,reading", [
    ("bf16_state", "scan_grad"), ("lambda_at_init", "diff_grad"),
    ("no_out_scale", "loss"), ("memory_after_gate", "gmu_grad"),
    ("cross_own_keys", "diff_grad"), ("window_unbounded", "diff_grad"),
    ("memory_reader_dropped", "shared_grad"),
    ("kv_reader_dropped", "shared_grad")])
def test_a_control_reads_over_a_limit_and_is_put_back(control, sound, name,
                                                      reading):
    """Every control is ANOTHER program, read over the limit it is there
    for (the rehearsal's float32 limits), and leaves the sound one behind
    it."""
    from distributed_pytorch_from_scratch_tpu.models import sambay
    from distributed_pytorch_from_scratch_tpu.ops import (
        selective_scan as op)
    from distributed_pytorch_from_scratch_tpu.parallel import (diff_attention,
                                                               mamba1)
    held = (sambay.SambaYTransformer._mix_sharing,
            sambay.SambaYTransformer._attn_mask,
            diff_attention.DifferentialAttention.lambda_of,
            diff_attention.DifferentialAttention.out_scale)
    got = control.reading(CELL, 5, name, rehearse=True)
    assert sound["ok"] and sound["control"] is None
    assert got["control"] == name and not got["ok"]
    assert got["rel_err"][reading] > got["rtol"][reading] > (
        sound["rel_err"][reading])
    assert mamba1.selective_scan is op.selective_scan
    assert held == (sambay.SambaYTransformer._mix_sharing,
                    sambay.SambaYTransformer._attn_mask,
                    diff_attention.DifferentialAttention.lambda_of,
                    diff_attention.DifferentialAttention.out_scale)
