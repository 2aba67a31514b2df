"""The swa_moe family's counts at the published widths
(benchmark/lib/swa_moe_counts.py), the family file's reference against the
program's at a tiny size, the `train_swa_moe` check's comparison, its
control tool at the rehearsal shape, and the scope and kernel readers on a
small capture made of the real step's instruction names and `op_name`s (as
the step compiled for the v5e carries them)."""

from types import SimpleNamespace

import numpy as np
import pytest

from benchmark.lib import swa_moe_counts as counts
from benchmark.lib import swa_scopes, trace
from benchmark.lib.files import load_json, load_module
from benchmark.lib.mla_moe_counts import expert_products_cost

CELL = "trinity-mini.train-epshare-b2-t8192"
CONFIG = "trinity-mini.json"


@pytest.fixture(scope="module")
def sizes():
    family = load_module("families", "swa_moe")
    return family.sizes_of(load_json("configs", CONFIG))


def test_parameters_of_the_share_at_the_published_widths(sizes):
    parts = counts.param_counts(sizes)
    assert parts["attention"] == 27_263_232     # wq, wg, wo; wk, wv; 2 norms
    assert parts["dense_mlp"] == 37_748_736
    assert parts["expert"] == 6_291_456
    assert parts["ffn"] == 262_144 + 128 + 17 * 6_291_456
    assert parts["dense_layer"] == 65_020_160
    assert parts["expert_layer"] == 134_488_448
    assert parts["embedding_and_head"] == 102_498_304
    assert parts["total"] == 705_474_304
    assert parts["total"] * 16 / 1e9 == pytest.approx(11.29, abs=0.005)
    # the published model: 2 dense and 30 uncut expert layers, the whole
    # vocabulary
    published = (2 * parts["dense_layer"] + 30 * parts["expert_layer_uncut"]
                 + 2 * 200192 * 2048 + 2048)
    assert published / 1e9 == pytest.approx(26.1, abs=0.05)
    assert (sizes.n_layer, sizes.expert_layers, sizes.window_layers,
            sizes.full_layers, sizes.n_head * sizes.head_dim) == (
                5, 4, 4, 1, 4096)


def test_the_program_counts_the_same(sizes):
    from distributed_pytorch_from_scratch_tpu.ops.attention import (
        sliding_window)
    family = load_module("families", "swa_moe")
    built = family.build(load_json("configs", CONFIG), {"dp": 1, "tp": 1},
                         "bfloat16")
    cfg = built.model.cfg
    assert cfg.num_params() == counts.param_counts(sizes)["total"]
    assert cfg.num_experts == 128 and cfg.swa_moe.experts_held == 16
    assert cfg.vocab_size == 25024 and cfg.num_layers == 5
    assert (built.model.head_dim, built.model.kv_dim) == (128, 512)
    assert built.model._pattern == (
        "dense_layers", (("window_layers_0", 3), ("full_layers_0", 1)))
    assert built.model._attn_mask(8192, "window") == sliding_window(2048)
    assert built.model._attn_mask(8192, "full") is None
    assert built.model.router_bias_speed == built.bias_speed == 0.001
    assert built.model.embed_scale == pytest.approx(45.2548, abs=1e-4)
    moe = built.model._mods["moe"]
    assert (moe.score, moe.n_shared, moe.scaling) == ("sigmoid", 1, 2.826)
    # the chunk policy at this share: one mean share of the pairs a chunk
    assert moe.chunk_share == 0.125 and moe.chunk_rows(131072) == 16384
    # the program's FLOPs count attention at each kind's live entries
    from distributed_pytorch_from_scratch_tpu.training.metrics import (
        model_flops_per_step)
    flops = model_flops_per_step(cfg, 2, 8192, cfg.num_params())
    live = 4 * counts.live_entries(8192, 2048) + counts.live_entries(8192,
                                                                     None)
    attention = 12 * 2 * 32 * 128 * live
    assert 0 < attention < flops
    idle = 4 * (16 - 8 * 16 / 128) * 6_291_456
    assert flops == pytest.approx(
        6 * (705_474_304 - 25024 * 2048 - idle) * 16384 + attention)


def test_the_configuration_holds_every_published_number():
    """Every number of the catalog's row under the same key, but those in
    `reduced`, whose published values stand beside them."""
    published = {
        "global_attn_every_n_layers": 4, "head_dim": 128,
        "hidden_size": 2048, "intermediate_size": 6144,
        "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
        "moe_intermediate_size": 1024, "n_group": 1,
        "num_attention_heads": 32, "num_dense_layers": 2,
        "num_expert_groups": 1, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 32,
        "num_key_value_heads": 4, "num_limited_groups": 1,
        "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_theta": 10000,
        "route_scale": 2.826, "sliding_window": 2048, "topk_group": 1,
        "vocab_size": 200192}
    config = load_json("configs", CONFIG)
    differs = {k for k, v in published.items() if config[k] != v}
    assert differs == {"num_dense_layers", "num_experts", "vocab_size"}
    assert (config["score_func"], config["route_norm"],
            config["mup_enabled"], config["tie_word_embeddings"],
            config["model_type"], config["hidden_act"],
            config["rope_scaling"], config["use_grouped_mm"]) == (
                "sigmoid", True, True, False, "afmoe", "silu", None, True)
    assert config["layer_types"] == ["sliding_attention"] * 4 + [
        "full_attention"]
    assert config["published"]["layer_types"] == (
        ["sliding_attention"] * 3 + ["full_attention"]) * 8
    assert sorted(config["reduced"]) == [
        "layer_types", "num_dense_layers", "num_experts", "num_layers",
        "vocab_size"]
    assert {k: config["published"][k] for k in (
        "num_dense_layers", "num_experts", "num_hidden_layers",
        "vocab_size")} == {"num_dense_layers": 2, "num_experts": 128,
                           "num_hidden_layers": 32, "vocab_size": 200192}
    assert config["deployment_share"]["expert_parallel"] == 8
    assert "705,474,304" in config["deployment"]
    manifest = load_json("..", "BENCHMARK.json")
    entry = next(c for c in manifest["configs"] if c["name"] == "trinity-mini")
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]


def test_live_entries_against_a_brute_force_mask():
    for t, w in ((8, 3), (16, 16), (24, 8), (12, 40), (9, 1)):
        i = np.arange(t)
        back = i[:, None] - i[None, :]
        assert ((back >= 0) & (back < w)).sum() == counts.live_entries(t, w)
        assert (back >= 0).sum() == counts.live_entries(t, None)
    assert counts.live_entries(8192, 2048) == 14_681_088
    assert counts.live_entries(8192, None) == 33_558_528
    assert counts.live_entries(8192, 2048) / counts.live_entries(
        8192, None) == pytest.approx(0.4375, abs=2e-4)


def test_flops_per_token(sizes):
    """Forward MFLOP a token: the four projections 54.5 a layer, scores at
    the live entries (4 window layers at 0.44 of the triangle, one full
    one), router 0.5, the shared expert 12.6, routed experts 12.6 for the
    row held, x 4 expert layers, the dense SwiGLU 75.5, the head 102.5."""
    M = 1e6
    assert 2 * counts.attention_matmul_params(sizes) / M == \
        pytest.approx(54.5, abs=0.05)
    assert 2 * counts.expert_params(sizes) / M == pytest.approx(12.6,
                                                                abs=0.05)
    assert 2 * counts.dense_mlp_params(sizes) / M == pytest.approx(75.5,
                                                                   abs=0.05)
    uniform = sizes.expert_layers * sizes.top_k * sizes.n_held / sizes.n_routed
    assert uniform == 4.0
    per_token = counts.live_entries_per_token(sizes, 8192)
    assert per_token == pytest.approx(
        (4 * 14_681_088 + 33_558_528) / 8192)
    forward = counts.forward_flops_per_token(sizes, 8192, uniform)
    scores = 4 * 32 * 128 * per_token
    assert forward == pytest.approx(
        2 * counts.active_matmul_params(sizes, uniform) + scores)
    assert forward / M == pytest.approx(
        5 * 54.5 + 75.5 + 4 * (0.52 + 12.58) + 4 * 12.58 + 102.5
        + scores / M, rel=0.002)
    # the numerator of active_mfu: the full layer at T^2, the window
    # layers at their band
    full = counts.train_flops_per_token(sizes, 8192, uniform)
    assert full == pytest.approx(
        6 * counts.active_matmul_params(sizes, uniform)
        + 12 * 32 * 128 * (8192 + 4 * 14_681_088 / 8192))
    assert counts.train_flops_per_token(sizes, 8192, 5.0) - full == \
        pytest.approx(6 * counts.expert_params(sizes))


def test_flash_and_expert_costs_read_these_sizes(sizes):
    band = 2 * 32 * 14_681_088
    triangle = 2 * 32 * 33_558_528
    q, kv = 2 * 32 * 8192 * 128 * 2, 2 * 4 * 8192 * 128 * 2
    vector = 2 * 32 * 8192 * 4
    for window, entries in ((2048, band), (None, triangle)):
        fwd = counts.flash_call_cost(2, 8192, sizes, 2, False, window)
        bwd = counts.flash_call_cost(2, 8192, sizes, 2, True, window)
        assert fwd.flops == 4 * 128 * entries
        assert bwd.flops == 10 * 128 * entries
        assert fwd.bytes == 2 * q + 2 * kv + vector
        assert bwd.bytes == 4 * q + 4 * kv + 2 * vector
        assert fwd.flops / 197e12 > fwd.bytes / 819e9      # compute-bound
    # 2.44 and 5.58 ms a call at the bf16 peak, forward, window and full
    assert counts.flash_call_cost(2, 8192, sizes, 2, False, 2048).flops \
        / 197e12 * 1e3 == pytest.approx(2.44, abs=0.01)
    assert counts.flash_call_cost(2, 8192, sizes, 2, False, None).flops \
        / 197e12 * 1e3 == pytest.approx(5.58, abs=0.01)
    # the expert products' count reads 16 held experts of 1024
    cost = expert_products_cost(16384, sizes, 2)
    assert cost.flops == 18 * 16384 * 2048 * 1024
    assert cost.bytes == 3 * (16 * 3 * 2048 * 1024 * 2
                              + 2 * 16384 * 2048 * 2)


def test_the_family_files_reference_is_the_programs():
    """The benchmark's own copy and the program's oracle compute the same
    loss and counts on the rehearsal shape (the program's is held to the
    model leaf by leaf in tests/test_swa_moe.py), and the same rule."""
    import jax
    from benchmark.lib.cells import load_cell
    from distributed_pytorch_from_scratch_tpu.models import vanilla_swa_moe
    workload, config = load_cell(CELL, rehearse=True)
    built = load_module("families", "swa_moe").build(
        config, workload["mesh"], "float32")
    params = built.model.init(jax.random.key(1))
    rng = np.random.default_rng(0)
    ids = rng.integers(3, built.sizes.vocab, (2, 73)).astype(np.int32)
    pos = np.tile(np.arange(72, dtype=np.int32), (2, 1))
    with jax.default_matmul_precision("highest"):
        ours, routed = built.reference_routed(params, ids[:, :-1],
                                              ids[:, 1:], pos)
        theirs = vanilla_swa_moe.vanilla_loss(
            built.model.cfg, params, ids[:, :-1], ids[:, 1:], pos)
    assert float(ours) == pytest.approx(float(theirs), rel=1e-6)
    assert routed.shape == (4, 8)           # expert layers, routed experts
    np.testing.assert_array_equal(routed.sum(-1), [2 * 72 * 2] * 4)
    bias = built.bias_in_order(params)
    assert bias.shape == (4, 8) and not np.any(bias)
    np.testing.assert_array_equal(
        built.bias_rule(bias, routed, 0.001),
        vanilla_swa_moe.bias_rule(bias, routed, 0.001))
    moved = np.asarray(built.bias_rule(bias, routed, 0.001))
    assert np.abs(moved).max() <= 0.002 and np.abs(moved.sum(-1)).max() < 1e-8


# ---- the check's comparison ----

def test_a_reading_over_a_limit_is_not_correct():
    runner = load_module("runners", "train_swa_moe")
    limit = runner.SWA_RTOL["bfloat16"]
    assert 0 < limit["routed_moved"] < 0.05 and 0 < limit["attn_grad"] < 1
    assert limit["bias_rule"] == runner.BIAS_ATOL and 0 < runner.BIAS_ATOL <= 1e-6
    passed = {"ok": True, "rel_err": {}, "rtol": {}}
    routed = np.array([[40.0, 24.0, 0.0, 0.0]])
    want = {"wk": np.ones((5, 30), np.float32),
            "wg": np.ones((5, 600), np.float32)}
    bias = np.full((4, 8), 0.001, np.float32)
    compare = lambda r=routed, b=bias, **off: runner._compare_swa(
        passed, "bfloat16", r, routed,
        {k: v * off.get(k, 1.0) for k, v in want.items()}, want, b, bias)
    assert compare()["ok"]
    moved = np.array([[-64.0, 0.0, 64.0, 0.0]]) * limit["routed_moved"]
    assert compare(routed + 0.9 * moved)["ok"]
    assert not compare(routed + 1.1 * moved)["ok"]
    assert compare(wk=1 + 0.9 * limit["attn_grad"])["ok"]
    assert not compare(wk=1 + 1.1 * limit["attn_grad"])["ok"]
    assert not compare(wg=1 + 1.1 * limit["attn_grad"])["ok"]
    assert not compare(wg=np.nan)["ok"]
    # the bias is held to the order of a float32 sum and to no more: an
    # entry's last bit (seed 357092872 on the chip) passes, a step at a
    # speed a thousandth off does not
    ulp = bias.copy()
    ulp[3, 7] = np.nextafter(np.float32(0.001), np.float32(1))
    assert compare(b=ulp)["ok"]
    assert 0 < compare(b=ulp)["rel_err"]["bias_rule"] < 2e-10
    off = bias.copy()
    off[3, 7] *= np.float32(1.001)
    assert not compare(b=off)["ok"]
    assert not compare(b=np.zeros_like(bias))["ok"]     # the rule did not run
    assert not runner._compare_swa({**passed, "ok": False}, "bfloat16",
                                   routed, routed, want, want, bias,
                                   bias)["ok"]
    # with `held` off (the rehearsal) the two relative readings are
    # recorded only; the bias is held all the same
    one = {k: v.copy() for k, v in want.items()}
    one["wk"][4] *= 1 + 1.1 * limit["attn_grad"]
    said = runner._compare_swa(passed, "float32", routed + 2 * moved, routed,
                               one, want, bias, bias, held=False)
    assert said["ok"] and said["rel_err"]["attn_grad"] > limit["attn_grad"]
    assert not runner._compare_swa(passed, "float32", routed, routed, want,
                                   want, off, bias, held=False)["ok"]


def test_the_gradient_samples_are_a_row_a_layer():
    """A large leaf (over 2^20 elements a layer) on every GRAD_STRIDE-th
    element, a small one whole; one row a layer, the keys in sorted order,
    a period's (periods, layers a period) flattened."""
    import jax.numpy as jnp
    runner = load_module("runners", "train_swa_moe")
    leaf = lambda *lead: {
        "wq": {"weight": jnp.ones((*lead, 1024, 1032))},
        "wg": {"weight": jnp.ones((*lead, 8, 16))},
        "wk": {"weight": jnp.ones((*lead, 8, 4))},
        "wv": {"weight": jnp.ones((*lead, 8, 4))},
        "wo": {"weight": jnp.ones((*lead, 16, 8))},
        "norm1": {"scale": jnp.ones((*lead, 8))}}
    tree = {"dense_layers": leaf(1), "window_layers_0": leaf(1, 3),
            "full_layers_0": leaf(1, 1), "norm": {"scale": jnp.ones((8,))}}
    named = runner._attn_named(tree)
    assert sorted(named) == ["wg", "wk", "wo", "wq", "wv"]
    assert named["wk"].shape == (5, 32) and named["wg"].shape == (5, 128)
    assert named["wq"].shape == (5, -(-1024 * 1032 // runner.GRAD_STRIDE))


@pytest.mark.parametrize("control,reading,factor", [
    ("window_as_causal", "attn_grad", 100.0),
    ("fp8_attn_inputs", "attn_grad", 100.0),
    ("fp8_router_inputs", "routed_moved", 50.0),
    ("rule_off", "bias_rule", None),
    ("rule_stilled", "bias_rule", None)])
def test_a_control_reads_worse_than_the_program(control, reading, factor):
    """The limits of `SWA_RTOL` are read at the published widths on the chip
    (PERF.md section 2) and say nothing at the rehearsal shape; what holds
    at every shape is that with the same seed a control reads worse than
    the sound program in the runner's own numbers; with the rule off, or
    stilled, the check is not ok at any shape."""
    tool = load_module("tools", "swa_control")
    sound = tool.reading(CELL, 2147483693, None, rehearse=True)
    bad = tool.reading(CELL, 2147483693, control, rehearse=True)
    assert sound["ok"] and sound["rel_err"]["bias_rule"] <= 1e-9
    assert sound["router_bias_step"] > 0.0
    if factor is None:
        assert not bad["ok"] and bad["rel_err"][reading] > 0.0
    else:
        assert bad["rel_err"][reading] > factor * sound["rel_err"][reading]
        assert bad["rel_err"][reading] > 0.01


# ---- the scope and kernel readers ----

LAYER = ("jit(step)/loss_and_grad/transpose(jvp(jit(shard)))/while/body/"
         "closed_call/checkpoint/")
FWD = "jit(step)/loss_and_grad/jvp(jit(shard))/while/body/closed_call/"
OPS = [
    # (instruction, meta, op_name or None, the part it belongs to)
    ("fusion.3", "fusion", LAYER + "rematted_computation/gqa_attn/mul",
     "gqa_attn"),
    ("fusion.4", "fusion", FWD + "gqa_attn/dot_general", "gqa_attn"),
    ("flash_fwd_window.24", "custom-call tpu_custom_call operands=3",
     LAYER + "rematted_computation/flash_fwd_window", "flash"),
    ("flash_bwd_window.12", "custom-call tpu_custom_call operands=6",
     LAYER + "flash_bwd_window", "flash"),
    ("flash_fwd.3", "custom-call tpu_custom_call operands=3",
     LAYER + "rematted_computation/flash_fwd", "flash"),
    ("flash_bwd.2", "custom-call tpu_custom_call operands=6",
     LAYER + "flash_bwd", "flash"),
    ("fusion.5", "fusion", FWD + "dense_ffn/dot_general", "dense_ffn"),
    ("fusion.7", "fusion", FWD + "moe_route/jit(take_along_axis)/gather",
     "moe_route"),
    ("sort.21", "sort", "sort", "moe_route"),
    ("fusion.8", "fusion", LAYER + "while/body/closed_call/checkpoint/"
     "rematted_computation/cond/branch_1_fun/moe_experts/jit(silu)",
     "moe_experts"),
    ("ragged-dot-none.4", "custom-call tpu_custom_call operands=7",
     "ragged-dot-none", "moe_experts"),
    ("fusion.9", "fusion", FWD + "moe_shared/dot_general", "moe_shared"),
    ("fusion.10", "fusion", "jit(step)/loss_and_grad/jvp(jit(shard))/"
     "head_loss/convert_element_type", "head_loss"),
    ("fusion.11", "fusion", "jit(step)/optimizer/mul", "optimizer"),
    ("fusion.14", "fusion", "jit(step)/optimizer/router_bias/sign",
     "router_bias"),
    ("fusion.12", "fusion", "jit(step)/grad_norm/reduce_sum", "grad_norm"),
    ("fusion.13", "fusion", LAYER + "mul", "rest"),
    ("copy.7", "copy", None, "unattributed"),
]


def capture(steps=2, each_ns=1000):
    """`steps` runs of the step program on chip 0, every op of OPS once a
    run, op i lasting (i + 1) * each_ns, back to back."""
    events, runs, t = [], [], 0
    for _ in range(steps):
        start = t
        for i, (name, meta, _, _) in enumerate(OPS):
            events.append(trace.Event(name, t, (i + 1) * each_ns, meta))
            t += (i + 1) * each_ns
        runs.append((start, t))
        t += 500                                        # an idle gap
    dev = trace.DeviceTrace(0, (0, runs[-1][1]), steps, events, [])
    return dev, runs, {name: op for name, _, op, _ in OPS if op}


def test_every_op_falls_in_one_part_and_the_parts_sum_to_busy():
    dev, runs, names = capture()
    parts = swa_scopes.scope_ns(dev, runs, names)
    assert set(parts) == set(swa_scopes.PARTS)
    assert sum(parts.values()) == dev.busy_ns()
    want = dict.fromkeys(swa_scopes.PARTS, 0)
    for i, (_, _, _, part) in enumerate(OPS):
        want[part] += 2 * (i + 1) * 1000
    assert parts == want
    outside = swa_scopes.scope_ns(dev, runs[:1], names)
    assert outside["other_programs"] == sum(want.values()) // 2
    # a window layer's calls are told from a full layer's by their name
    names_of = lambda backward, window: [
        c.name for c in swa_scopes.flash_calls(dev, backward, window)]
    assert names_of(False, True) == ["flash_fwd_window.24"] * 2
    assert names_of(True, True) == ["flash_bwd_window.12"] * 2
    assert names_of(False, False) == ["flash_fwd.3"] * 2
    assert names_of(True, False) == ["flash_bwd.2"] * 2


def test_the_readers_read_the_runners_fields(sizes):
    from distributed_pytorch_from_scratch_tpu.obs.attribution import (
        flash_tile_stats)
    from distributed_pytorch_from_scratch_tpu.ops.attention import (
        sliding_window)
    dev, runs, names = capture()
    parts = swa_scopes.scope_ns(dev, runs, names)
    workload = load_json("workloads", CELL + ".json")
    peak = SimpleNamespace(flops_per_s=197e12, hbm_bytes_per_s=819e9)
    plan = {name: flash_tile_stats(8192, head_dim=128,
                                   mask=sliding_window(2048),
                                   backward=name == "backward")
            for name in ("forward", "backward")}
    m = SimpleNamespace(devices=[dev], scopes=parts, peak=peak, sizes=sizes,
                        workload=workload, mesh={"dp": 1, "tp": 1}, chips=1,
                        tokens_per_s=26000.0,
                        rows_here_per_layer=[16384.0] * 4,
                        rows_here_per_token=1.0, load_max_over_mean=2.0,
                        active_flops_per_token=2.9e9, window_flash_plan=plan,
                        bias_step_abs_mean=0.00095)
    read = lambda name: load_module("layer_metrics", name).read(m)
    ms = lambda *ops: sum(ops) * 1000 / 1e6
    assert read("model.gqa_attn_ms") == pytest.approx(ms(1, 2))
    assert read("kernels.flash_ms") == pytest.approx(ms(3, 4, 5, 6))
    assert read("kernels.window_flash_ms") == pytest.approx(ms(3, 4))
    assert read("model.dense_ffn_ms") == pytest.approx(ms(7))
    assert read("model.moe_route_ms") == pytest.approx(ms(8, 9))
    assert read("model.moe_experts_ms") == pytest.approx(ms(10, 11))
    assert read("moe.load_max_over_mean") == 2.0
    assert read("moe.rows_here_per_token") == 1.0
    assert read("moe.bias_step_abs_mean") == 0.00095
    assert read("train_step.active_mfu_pct") == pytest.approx(
        100 * 2.9e9 * 26000 / 197e12)
    # the flash shares: one forward and one backward call a run of the
    # capture and kind, each at the kind's live entries over the bf16 peak
    for name, window, ops in (("kernels.window_flash_roofline", 2048, (3, 4)),
                              ("kernels.full_flash_roofline", None, (5, 6))):
        fwd = counts.flash_call_cost(2, 8192, sizes, 2, False, window)
        bwd = counts.flash_call_cost(2, 8192, sizes, 2, True, window)
        assert read(name) == pytest.approx(
            100 * 2 * (fwd.flops + bwd.flops) / 197e12
            / (2 * ms(*ops) / 1e3))
    # what the plans compute over what the window leaves live, both ways
    assert read("window.flash_computed_over_live") == pytest.approx(
        (plan["forward"]["work_elems"] + plan["backward"]["work_elems"])
        / (2 * 14_681_088))
    assert 1.0 < read("window.flash_computed_over_live") < 1.35
    experts = 4 * max(18 * 16384 * 2048 * 1024 / 197e12,
                      expert_products_cost(16384, sizes, 2).bytes / 819e9)
    assert read("model.moe_experts_roofline") == pytest.approx(
        100 * experts / (ms(10, 11) / 1e3))


def test_the_readers_return_nothing_where_there_is_nothing_to_read(sizes):
    """A runner that hands no scope split (the `train` runner), another
    family's (no window among its sizes, no plan, no bias counter), or a
    program whose flash calls carry no `_window` (the parent of PR 46): the
    new readers return None, and do not raise."""
    bare = SimpleNamespace(devices=[], peak=None, tokens_per_s=1.0, chips=1,
                           sizes=SimpleNamespace())
    dev, runs, names = capture()
    other = SimpleNamespace(
        devices=[dev], peak=SimpleNamespace(flops_per_s=1.0,
                                            hbm_bytes_per_s=1.0),
        scopes={"shortconv": 5, "moe_route": 7},
        sizes=SimpleNamespace(n_head=32, n_kv_head=8), workload={}, mesh={})
    for m in (bare, other):
        for name in ("kernels.window_flash_roofline",
                     "kernels.full_flash_roofline",
                     "window.flash_computed_over_live",
                     "moe.bias_step_abs_mean"):
            assert load_module("layer_metrics", name).read(m) is None
    assert load_module("layer_metrics",
                       "kernels.window_flash_ms").read(bare) is None
    # no call named `_window`: the window readers find nothing
    plain = [e for e in dev.ops if "_window" not in e.name]
    unnamed = SimpleNamespace(
        devices=[trace.DeviceTrace(0, dev.window, dev.steps, plain, [])],
        peak=SimpleNamespace(flops_per_s=197e12, hbm_bytes_per_s=819e9),
        sizes=sizes, workload=load_json("workloads", CELL + ".json"),
        mesh={"dp": 1, "tp": 1})
    read = lambda name: load_module("layer_metrics", name).read(unnamed)
    assert read("kernels.window_flash_ms") is None
    assert read("kernels.window_flash_roofline") is None
    assert read("kernels.full_flash_roofline") is not None
