"""The bd_moe family's counts at the published widths
(benchmark/lib/bd_moe_counts.py), the family file's reference against the
program's at a tiny size, the `train_bd_moe` check's comparison, its control
tool at the rehearsal shape, and the scope readers on a small capture made
of the real step's instruction names and `op_name`s (as the step compiled
for the v5e carries them)."""

from types import SimpleNamespace

import numpy as np
import pytest

from benchmark.lib import bd_moe_counts as counts
from benchmark.lib import bd_scopes, trace
from benchmark.lib.files import load_json, load_module
from benchmark.lib.mla_moe_counts import expert_products_cost

CELL = "sdar-30b-a3b.train-ep8share-b2-t4096"
CONFIG = "sdar-30b-a3b.json"


@pytest.fixture(scope="module")
def sizes():
    family = load_module("families", "bd_moe")
    return family.sizes_of(load_json("configs", CONFIG))


def test_parameters_of_the_share_at_the_published_widths(sizes):
    parts = counts.param_counts(sizes)
    assert parts["attention"] == 18_874_624
    assert parts["router"] == 262_144
    assert parts["expert"] == 4_718_592
    assert parts["ffn"] == 262_144 + 75_497_472
    assert parts["ffn_uncut"] == 262_144 + 603_979_776
    assert parts["layer"] == 94_638_336
    assert parts["layer_uncut"] == 623_120_640
    assert parts["embedding_and_head"] == 77_791_232
    assert parts["total"] == 645_623_296
    assert parts["total"] * 16 / 1e9 == pytest.approx(10.33, abs=0.005)
    # the published model: 48 uncut layers and the whole vocabulary
    published = 48 * parts["layer_uncut"] + 2 * 151936 * 2048 + 2048
    assert published / 1e9 == pytest.approx(30.5, abs=0.05)
    assert (sizes.n_layer, sizes.expert_layers, sizes.head_dim,
            sizes.n_head * sizes.head_dim) == (6, 6, 128, 4096)


def test_the_program_counts_the_same(sizes):
    family = load_module("families", "bd_moe")
    built = family.build(load_json("configs", CONFIG), {"dp": 1, "tp": 1},
                         "bfloat16", noise_seed=5)
    cfg = built.model.cfg
    assert cfg.num_params() == counts.param_counts(sizes)["total"]
    assert cfg.num_experts == 128 and cfg.bd_moe.experts_held == 16
    assert cfg.vocab_size == 18992 and cfg.num_layers == 6
    assert (built.model.head_dim, built.model.kv_dim) == (128, 512)
    assert built.model.noise_seed == 5
    mask = built.model._attn_mask(8192)
    assert (mask.kind, mask.block, mask.half) == ("block_diffusion", 4, 4096)
    # the chunk policy at this share: one mean share of the pairs a chunk
    moe = built.model._mods["moe"]
    assert moe.chunk_share == 0.125 and moe.chunk_rows(131072) == 16384


def test_the_configuration_holds_every_published_number():
    """Every number of the catalog's row under the same key, but those in
    `reduced`, whose published values stand beside them."""
    published = {
        "decoder_sparse_step": 1, "head_dim": 128, "hidden_size": 2048,
        "intermediate_size": 6144, "max_position_embeddings": 32768,
        "max_window_layers": 48, "moe_intermediate_size": 768,
        "num_attention_heads": 32, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 48,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_theta": 1000000, "vocab_size": 151936}
    config = load_json("configs", CONFIG)
    differs = {k for k, v in published.items() if config[k] != v}
    assert differs == {"num_experts", "vocab_size"}
    assert (config["attention_bias"], config["norm_topk_prob"],
            config["tie_word_embeddings"], config["use_sliding_window"],
            config["model_type"], config["mlp_only_layers"]) == (
                False, True, False, False, "sdar_moe", [])
    assert sorted(config["reduced"]) == ["num_experts", "num_layers",
                                         "vocab_size"]
    assert config["published"] == {"num_experts": 128,
                                   "num_hidden_layers": 48,
                                   "vocab_size": 151936}
    assert config["num_layers"] == 6 and config["block_length"] == 4
    # the mask token is one of the traffic's reserved ids
    workload = load_json("workloads", CELL + ".json")
    assert 0 <= config["mask_token_id"] < workload["data"]["reserved_ids"]
    for key in ("block_length", "objective", "mask_token_id", "balance_loss",
                "normalisation_epsilon", "initialisation", "optimizer"):
        assert key in config["assumed"]
    manifest = load_json("..", "BENCHMARK.json")
    entry = next(c for c in manifest["configs"] if c["name"] == "sdar-30b-a3b")
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert entry["source"] == config["source"]


def test_live_entries_against_a_brute_force_mask():
    family = load_module("families", "bd_moe")
    for L, B in ((8, 2), (16, 4), (24, 8), (12, 12)):
        rows = np.arange(2 * L)
        live = np.asarray(family.bd_mask(rows, rows, L, B))
        assert live.sum() == counts.live_entries(L, B) == L * (L + B)
        assert live[:L, :L].sum() == L * B                # the block diagonal
        assert live[:L, L:].sum() == L * (L - B) // 2     # earlier clean
        assert live[L:, L:].sum() == L * (L + B) // 2     # clean to clean
        assert not live[L:, :L].any()
    assert counts.live_entries(4096, 4) == 16_793_600    # of 4 x 4096^2


def test_flops_per_token(sizes):
    """Forward MFLOP a DATA token (two rows), as ISSUE 41 counts them:
    projections 2 x 37.7, scores at the live entries 2 x 33.6, router 1,
    routed experts 2 x 9.4 for the rows held, x 6 layers, the head 77.8 on
    the noised row: about 1,050 of work."""
    M = 1e6
    assert 2 * counts.attention_matmul_params(sizes) / M == \
        pytest.approx(37.7, abs=0.05)
    assert 2 * counts.expert_params(sizes) / M == pytest.approx(9.4, abs=0.05)
    scores = 4 * 32 * 128 * counts.live_entries(4096, 4) / 4096
    assert scores / M == pytest.approx(2 * 33.6, abs=0.1)
    uniform = 2 * sizes.n_layer * sizes.top_k * sizes.n_held / sizes.n_routed
    assert uniform == 12.0                   # 2 rows x 6 layers x 1.0
    forward = counts.forward_flops_per_token(sizes, 4096, uniform)
    assert forward / M == pytest.approx(1053, rel=0.005)
    # as computed under the chunk: 98,304 rows a layer for 16,384 rows in
    executed = counts.forward_flops_per_token(sizes, 4096, 6 * 98304 / 8192)
    assert (executed - forward) / M == pytest.approx(6 * 2 * 47.2, rel=0.01)
    # the numerator of active_mfu: 6 x the matmuls' parameters, attention
    # at the mask's live entries
    full = counts.train_flops_per_token(sizes, 4096, uniform)
    assert full == pytest.approx(
        6 * counts.active_matmul_params(sizes, uniform)
        + 12 * 6 * 32 * 128 * 4100)
    assert 3.15e9 < full < 3.17e9
    assert counts.train_flops_per_token(sizes, 4096, 13.0) - full == \
        pytest.approx(6 * counts.expert_params(sizes))


def test_flash_and_expert_costs_read_these_sizes(sizes):
    fwd = counts.bd_flash_call_cost(2, 4096, sizes, 2, backward=False)
    bwd = counts.bd_flash_call_cost(2, 4096, sizes, 2, backward=True)
    entries = 2 * 32 * 4096 * 4100
    assert fwd.flops == 4 * 128 * entries and bwd.flops == 10 * 128 * entries
    q, kv = 2 * 32 * 8192 * 128 * 2, 2 * 4 * 8192 * 128 * 2
    vector = 2 * 32 * 8192 * 4
    assert fwd.bytes == 2 * q + 2 * kv + vector
    assert bwd.bytes == 4 * q + 4 * kv + 2 * vector
    # compute-bound both ways: 2.79 and 6.98 ms a call at the bf16 peak
    assert fwd.flops / 197e12 * 1e3 == pytest.approx(2.79, abs=0.01)
    assert bwd.flops / 197e12 * 1e3 == pytest.approx(6.98, abs=0.01)
    assert fwd.flops / 197e12 > fwd.bytes / 819e9
    # the expert products' count reads 16 held experts of 768
    cost = expert_products_cost(16384, sizes, 2)
    assert cost.flops == 18 * 16384 * 2048 * 768
    assert cost.bytes == 3 * (16 * 3 * 2048 * 768 * 2 + 2 * 16384 * 2048 * 2)


def test_the_family_files_reference_is_the_programs():
    """The benchmark's own copy and the program's oracle compute the same
    loss on the rehearsal shape (the program's is held to the model leaf by
    leaf in tests/test_bd_moe.py), on the program's own draw."""
    import jax
    from benchmark.lib.cells import load_cell
    from distributed_pytorch_from_scratch_tpu.models.vanilla_bd_moe import (
        vanilla_loss)
    workload, config = load_cell(CELL, rehearse=True)
    built = load_module("families", "bd_moe").build(
        config, workload["mesh"], "float32", noise_seed=3)
    params = built.model.init(jax.random.key(1))
    rng = np.random.default_rng(0)
    x0 = rng.integers(3, built.sizes.vocab, (2, 72)).astype(np.int32)
    pos = np.tile(np.arange(72, dtype=np.int32), (2, 1))
    xt, m, p = built.noise(0, x0)
    assert ((np.asarray(xt) == config["mask_token_id"])
            == np.asarray(m)).all()
    with jax.default_matmul_precision("highest"):
        ours, routed = built.reference_routed(params, x0, pos, xt, m, p)
        theirs = vanilla_loss(built.model.cfg, params, x0, pos, xt, m, p)
    assert float(ours) == pytest.approx(float(theirs), rel=1e-6)
    assert routed.shape == (2, 8)                  # layers, routed experts
    np.testing.assert_array_equal(routed.sum(-1), [2 * 2 * 72 * 2] * 2)


# ---- the check's comparison ----

def test_a_reading_over_a_limit_is_not_correct():
    runner = load_module("runners", "train_bd_moe")
    limit = runner.BD_RTOL["bfloat16"]
    assert 0 < limit["routed_moved"] < 0.05 and 0 < limit["attn_grad"] < 1
    passed = {"ok": True, "rel_err": {}, "rtol": {}}
    routed = np.array([[40.0, 24.0, 0.0, 0.0]])
    want = {"wk": np.ones((6, 30), np.float32),
            "wq": np.ones((6, 600), np.float32)}
    compare = lambda r=routed, **off: runner._compare_bd(
        passed, "bfloat16", r, routed,
        {k: v * off.get(k, 1.0) for k, v in want.items()}, want)
    assert compare()["ok"]
    moved = np.array([[-64.0, 0.0, 64.0, 0.0]]) * limit["routed_moved"]
    assert compare(routed + 0.9 * moved)["ok"]
    assert not compare(routed + 1.1 * moved)["ok"]
    assert compare(wk=1 + 0.9 * limit["attn_grad"])["ok"]
    assert not compare(wk=1 + 1.1 * limit["attn_grad"])["ok"]
    assert not compare(wq=1 + 1.1 * limit["attn_grad"])["ok"]
    assert not compare(wq=np.nan)["ok"]
    assert not runner._compare_bd({**passed, "ok": False}, "bfloat16",
                                  routed, routed, want, want)["ok"]
    # one layer of six over the limit is enough
    one = {k: v.copy() for k, v in want.items()}
    one["wk"][5] *= 1 + 1.1 * limit["attn_grad"]
    assert not runner._compare_bd(passed, "bfloat16", routed, routed, one,
                                  want)["ok"]
    # with `held` off (the rehearsal) the readings are recorded only
    said = runner._compare_bd(passed, "float32", routed + 2 * moved, routed,
                              one, want, held=False)
    assert said["ok"] and said["rel_err"]["attn_grad"] > limit["attn_grad"]


def test_the_gradient_samples_are_a_row_a_layer():
    """A large leaf (over 2^20 elements a layer) on every GRAD_STRIDE-th
    element, a small one whole; one row a layer."""
    import jax.numpy as jnp
    runner = load_module("runners", "train_bd_moe")
    tree = {"layers": {
        "wq": {"weight": jnp.ones((3, 1024, 1032))},
        "wk": {"weight": jnp.ones((3, 8, 4))},
        "wv": {"weight": jnp.ones((3, 8, 4))},
        "wo": {"weight": jnp.ones((3, 16, 8))},
        "norm1": {"scale": jnp.ones((3, 8))}}}
    named = runner._attn_named(tree)
    assert sorted(named) == ["wk", "wo", "wq", "wv"]
    assert named["wk"].shape == (3, 32) and named["wo"].shape == (3, 128)
    assert named["wq"].shape == (3, -(-1024 * 1032 // runner.GRAD_STRIDE))


@pytest.mark.parametrize("control", ["mask_off_by_one_block",
                                     "causal_over_rows", "fp8_attn_inputs"])
def test_a_control_reads_worse_than_the_program(control):
    """The limits of `BD_RTOL` are read at the published widths on the chip
    (PERF.md section 2) and say nothing at the rehearsal shape; what holds
    at every shape is that with the same seed a control reads worse than
    the sound program in the runner's own numbers, by `attn_grad`."""
    tool = load_module("tools", "bd_control")
    sound = tool.reading(CELL, 2147483693, None, rehearse=True)
    bad = tool.reading(CELL, 2147483693, control, rehearse=True)
    assert sound["ok"]
    assert bad["rel_err"]["attn_grad"] > 100 * sound["rel_err"]["attn_grad"]
    assert bad["rel_err"]["attn_grad"] > 0.02


# ---- the scope readers ----

LAYER = ("jit(step)/loss_and_grad/transpose(jvp(jit(noised)))/jit(shard)/"
         "while/body/closed_call/checkpoint/")
FWD = "jit(step)/loss_and_grad/jvp(jit(noised))/jit(shard)/while/body/" \
      "closed_call/"
OPS = [
    # (instruction, meta, op_name or None, the part it belongs to)
    ("fusion.1", "fusion", "jit(step)/loss_and_grad/jvp(jit(noised))/"
     "bd_noise/jit(_uniform)/threefry2x32", "bd_noise"),
    ("fusion.2", "fusion", "jit(step)/loss_and_grad/jvp(jit(noised))/"
     "jit(shard)/bd_noise/concatenate", "bd_noise"),
    ("fusion.3", "fusion", LAYER + "rematted_computation/gqa_attn/mul",
     "gqa_attn"),
    ("fusion.4", "fusion", FWD + "gqa_attn/dot_general", "gqa_attn"),
    ("flash_fwd.24", "custom-call tpu_custom_call operands=3",
     LAYER + "rematted_computation/flash_fwd", "flash"),
    ("flash_bwd.12", "custom-call tpu_custom_call operands=6",
     LAYER + "flash_bwd", "flash"),
    ("fusion.7", "fusion", FWD + "moe_route/jit(take_along_axis)/gather",
     "moe_route"),
    ("sort.21", "sort", "sort", "moe_route"),
    ("fusion.8", "fusion", LAYER + "while/body/closed_call/checkpoint/"
     "rematted_computation/cond/branch_1_fun/moe_experts/jit(silu)",
     "moe_experts"),
    ("ragged-dot-none.4", "custom-call tpu_custom_call operands=7",
     "ragged-dot-none", "moe_experts"),
    ("fusion.10", "fusion", "jit(step)/loss_and_grad/jvp(jit(noised))/"
     "jit(shard)/head_loss/convert_element_type", "head_loss"),
    ("fusion.11", "fusion", "jit(step)/optimizer/mul", "optimizer"),
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
    parts = bd_scopes.scope_ns(dev, runs, names)
    assert set(parts) == set(bd_scopes.PARTS)
    assert sum(parts.values()) == dev.busy_ns()
    want = dict.fromkeys(bd_scopes.PARTS, 0)
    for i, (_, _, _, part) in enumerate(OPS):
        want[part] += 2 * (i + 1) * 1000
    assert parts == want
    outside = bd_scopes.scope_ns(dev, runs[:1], names)
    assert outside["other_programs"] == sum(want.values()) // 2


def test_the_readers_read_the_runners_fields(sizes):
    from distributed_pytorch_from_scratch_tpu.obs.attribution import (
        flash_tile_stats)
    from distributed_pytorch_from_scratch_tpu.ops.attention import (
        block_diffusion)
    dev, runs, names = capture()
    parts = bd_scopes.scope_ns(dev, runs, names)
    workload = load_json("workloads", CELL + ".json")
    peak = SimpleNamespace(flops_per_s=197e12, hbm_bytes_per_s=819e9)
    plan = {name: flash_tile_stats(8192, head_dim=128,
                                   mask=block_diffusion(4, 4096),
                                   backward=name == "backward")
            for name in ("forward", "backward")}
    m = SimpleNamespace(devices=[dev], scopes=parts, peak=peak, sizes=sizes,
                        workload=workload, mesh={"dp": 1, "tp": 1}, chips=1,
                        tokens_per_s=9800.0,
                        rows_here_per_layer=[16384.0] * 6,
                        rows_here_per_token=1.0, load_max_over_mean=2.0,
                        active_flops_per_token=3.16e9, flash_plan=plan)
    read = lambda name: load_module("layer_metrics", name).read(m)
    ms = lambda *ops: sum(ops) * 1000 / 1e6
    assert read("model.bd_noise_ms") == pytest.approx(ms(1, 2))
    assert read("model.gqa_attn_ms") == pytest.approx(ms(3, 4))
    assert read("kernels.flash_ms") == pytest.approx(ms(5, 6))
    assert read("model.moe_route_ms") == pytest.approx(ms(7, 8))
    assert read("model.moe_experts_ms") == pytest.approx(ms(9, 10))
    assert read("moe.load_max_over_mean") == 2.0
    assert read("moe.rows_here_per_token") == 1.0
    assert read("train_step.active_mfu_pct") == pytest.approx(
        100 * 3.16e9 * 9800 / 197e12)
    # the flash share: one forward and one backward call a run of the
    # capture, each at the mask's live entries over the bf16 peak
    fwd = counts.bd_flash_call_cost(2, 4096, sizes, 2, False)
    bwd = counts.bd_flash_call_cost(2, 4096, sizes, 2, True)
    assert read("kernels.bd_flash_roofline") == pytest.approx(
        100 * 2 * (fwd.flops + bwd.flops) / 197e12 / (2 * ms(5, 6) / 1e3))
    # what the plans compute over what the mask leaves live, both ways
    assert read("bd.flash_computed_over_live") == pytest.approx(
        (20_971_520 + 18_874_368) / (2 * 16_793_600))
    assert read("bd.flash_computed_over_live") == pytest.approx(1.1863,
                                                                abs=1e-4)
    experts = 6 * max(18 * 16384 * 2048 * 768 / 197e12,
                      expert_products_cost(16384, sizes, 2).bytes / 819e9)
    assert read("model.moe_experts_roofline") == pytest.approx(
        100 * experts / (ms(9, 10) / 1e3))


def test_the_readers_return_nothing_where_there_is_nothing_to_read(sizes):
    """A runner that hands no scope split (the `train` runner), another
    family's split (`train_conv_moe`: no `bd_noise` among its parts, no
    block length among its sizes, no plan) or a program without the scopes
    gets None, not an exception."""
    bare = SimpleNamespace(devices=[], peak=None, tokens_per_s=1.0, chips=1,
                           sizes=SimpleNamespace())
    dev, runs, names = capture()
    other = SimpleNamespace(
        devices=[dev], peak=SimpleNamespace(flops_per_s=1.0,
                                            hbm_bytes_per_s=1.0),
        scopes={"shortconv": 5, "moe_route": 7},
        sizes=SimpleNamespace(n_head=32, n_kv_head=8), workload={}, mesh={})
    for m in (bare, other):
        for name in ("model.bd_noise_ms", "kernels.bd_flash_roofline",
                     "bd.flash_computed_over_live"):
            assert load_module("layer_metrics", name).read(m) is None
