"""The early_moe family's counts at the published widths
(benchmark/lib/early_moe_counts.py), the family file's reference against the
program's at a tiny size, the `train_early_moe` check's comparison, its
control tool at the rehearsal shape, and the scope and kernel readers on a
small capture made of the real step's instruction names and `op_name`s (as
the step compiled for the v5e carries them)."""

from types import SimpleNamespace

import numpy as np
import pytest

from benchmark.lib import early_moe_counts as counts
from benchmark.lib import early_scopes, swa_scopes, trace
from benchmark.lib.files import load_json, load_module
from benchmark.lib.mla_moe_counts import expert_products_cost

CELL = "smallthinker-21b-a3b.train-ep4share-b1-t16384"
CONFIG = "smallthinker-21b-a3b.json"
T = 16384
BAND, TRIANGLE = 58_722_304, 134_225_920


@pytest.fixture(scope="module")
def sizes():
    family = load_module("families", "early_moe")
    return family.sizes_of(load_json("configs", CONFIG))


def test_parameters_of_the_share_at_the_published_widths(sizes):
    parts = counts.param_counts(sizes)
    assert parts["attention"] == 20_971_520         # wq, wo; wk, wv
    assert parts["expert"] == 5_898_240
    assert parts["ffn"] == 163_840 + 16 * 5_898_240 == 94_535_680
    assert parts["layer"] == 115_512_320
    assert parts["embedding_and_head"] == 194_478_080
    assert parts["total"] == 4 * 115_512_320 + 194_478_080 + 2_560 \
        == 656_529_920
    assert parts["total"] * 16 / 1e9 == pytest.approx(10.50, abs=0.005)
    # the published model: 52 uncut layers, the whole vocabulary
    published = 52 * parts["layer_uncut"] + 2 * 151936 * 2560 + 2560
    assert published / 1e9 == pytest.approx(21.5, abs=0.05)
    assert (sizes.n_layer, sizes.expert_layers, sizes.window_layers,
            sizes.full_layers, sizes.n_head * sizes.head_dim,
            sizes.n_head // sizes.n_kv_head) == (4, 4, 3, 1, 3584, 7)


def test_the_program_counts_the_same(sizes):
    from distributed_pytorch_from_scratch_tpu.ops.attention import (
        sliding_window)
    family = load_module("families", "early_moe")
    config = load_json("configs", CONFIG)
    built = family.build(config, {"dp": 1, "tp": 1}, "bfloat16")
    cfg = built.model.cfg
    assert cfg.num_params() == counts.param_counts(sizes)["total"]
    assert sum(type(built.model).param_counts(cfg).values()) == 656_529_920
    assert "656,529,920" in config["deployment"]
    assert cfg.num_experts == 64 and cfg.early_moe.experts_held == 16
    assert cfg.vocab_size == 37984 and cfg.num_layers == 4
    assert cfg.moe_top_k == 6 and cfg.rope_theta == 1.5e6
    assert (built.model.head_dim, built.model.kv_dim) == (128, 512)
    assert built.model._pattern == (
        (("full_layers_0", 1), ("window_layers_0", 3)),)
    assert built.model._attn_mask(T, "window") == sliding_window(4096)
    assert built.model._attn_mask(T, "full") is None
    assert built.model.router_reads_layer_input
    assert built.model.unrotated_kinds == ("full",)
    moe = built.model._mods["moe"]
    assert (moe.score, moe.n_shared, moe.scaling, moe.activation) == (
        "softmax", 0, 1.0, "relu")
    # a held share of a quarter: the one chunk is all 98304 pairs
    assert moe.chunk_share == 1.0 and moe.chunk_rows(T * 6) == 98304
    # the program's FLOPs count attention at each kind's live entries
    from distributed_pytorch_from_scratch_tpu.training.metrics import (
        model_flops_per_step)
    flops = model_flops_per_step(cfg, 1, T, cfg.num_params())
    live = 3 * counts.live_entries(T, 4096) + counts.live_entries(T, None)
    assert live == 3 * BAND + TRIANGLE
    attention = 12 * 28 * 128 * live
    assert 0 < attention < flops
    idle = 4 * (16 - 6 * 16 / 64) * 5_898_240
    assert flops == pytest.approx(
        6 * (656_529_920 - 37984 * 2560 - idle) * T + attention)


def test_the_configuration_holds_every_published_number():
    """Every number of the catalog's row under the same key, but those in
    `reduced`, whose published values stand beside them; and the harness's
    name for the experts a token takes equals the published key's."""
    published = {
        "head_dim": 128, "hidden_size": 2560,
        "max_position_embeddings": 16384, "moe_ffn_hidden_size": 768,
        "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
        "num_attention_heads": 28, "num_hidden_layers": 52,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_theta": 1500000, "sliding_window_size": 4096,
        "vocab_size": 151936}
    config = load_json("configs", CONFIG)
    differs = {k for k, v in published.items() if config[k] != v}
    assert differs == {"moe_num_primary_experts", "vocab_size"}
    assert (config["moe_primary_router_apply_softmax"],
            config["norm_topk_prob"], config["tie_word_embeddings"],
            config["rope_scaling"], config["model_name"]) == (
                True, True, False, None, "smallthinker_21b_instruct")
    assert config["sliding_window_layout"] == config["rope_layout"] \
        == [0, 1, 1, 1]
    assert config["published"]["sliding_window_layout"] \
        == config["published"]["rope_layout"] == [0, 1, 1, 1] * 13
    assert sorted(config["reduced"]) == [
        "moe_num_primary_experts", "num_layers", "rope_layout",
        "sliding_window_layout", "vocab_size"]
    assert {k: config["published"][k] for k in (
        "moe_num_primary_experts", "num_hidden_layers", "vocab_size")} == {
            "moe_num_primary_experts": 64, "num_hidden_layers": 52,
            "vocab_size": 151936}
    # the one key the harness needs: its name for the published key
    assert config["num_experts_per_tok"] \
        == config["moe_num_active_primary_experts"] == 6
    assert "num_experts_per_tok" in config["assumed"]
    assert config["deployment_share"]["expert_parallel"] == 4
    for key in ("router_input", "activation", "secondary_experts",
                "attention", "positions", "window", "balance_loss",
                "initialisation", "precision", "parameters", "optimizer",
                "unread_keys"):
        assert config["assumed"][key]
    manifest = load_json("..", "BENCHMARK.json")
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "smallthinker-21b-a3b")
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    # a rehearsal that named other counts under the two names is refused
    family = load_module("families", "early_moe")
    with pytest.raises(ValueError, match="the harness's name"):
        family.sizes_of({**config, "num_experts_per_tok": 8})


def test_live_entries_and_flops_per_token(sizes):
    assert counts.live_entries(T, 4096) == BAND
    assert counts.live_entries(T, None) == TRIANGLE
    assert BAND / TRIANGLE == pytest.approx(0.4375, abs=1e-4)
    rows = 4 * 1.5              # uniform routing: 6 x 16 / 64 a layer
    active = counts.active_matmul_params(sizes, rows)
    assert active == (4 * (20_971_520 + 163_840) + rows * 5_898_240
                      + 37984 * 2560)
    # the issue's forecast by part, forward MFLOP a token
    mflop = lambda x: x / 1e6
    assert mflop(2 * 4 * 20_971_520) == pytest.approx(168, abs=0.5)
    assert mflop(4 * 28 * 128 * BAND / T) == pytest.approx(51.4, abs=0.05)
    assert mflop(4 * 28 * 128 * TRIANGLE / T) == pytest.approx(117.4,
                                                               abs=0.05)
    assert mflop(2 * rows * 5_898_240) == pytest.approx(71, abs=0.5)
    assert mflop(2 * 37984 * 2560) == pytest.approx(194, abs=0.5)
    forward = counts.forward_flops_per_token(sizes, T, rows)
    assert forward == pytest.approx(
        2 * active + 4 * 28 * 128 * (3 * BAND + TRIANGLE) / T)
    assert mflop(forward) == pytest.approx(706, abs=1.0)   # with the routers
    scores = 4 * 28 * 128 * (3 * BAND + TRIANGLE) / T
    assert (2 * 4 * 20_971_520 + scores) / forward == pytest.approx(
        0.62, abs=0.01)
    # trained: a full layer at T^2 (twice its triangle), a window layer at
    # its live entries
    train = counts.train_flops_per_token(sizes, T, rows)
    assert train == pytest.approx(
        6 * active + 12 * 28 * 128 * (T + 3 * BAND / T))
    # the program counts the triangle where the benchmark's convention
    # counts the square (less by the full layer's other half), and the
    # norms' weights among its parameters (nine vectors of 2560)
    family = load_module("families", "early_moe")
    cfg = family.build(load_json("configs", CONFIG), {"dp": 1, "tp": 1},
                       "bfloat16").model.cfg
    from distributed_pytorch_from_scratch_tpu.training.metrics import (
        model_flops_per_step)
    program = model_flops_per_step(cfg, 1, T, cfg.num_params()) / T
    assert train - program == pytest.approx(
        12 * 28 * 128 * (T - TRIANGLE / T) - 6 * 9 * 2560, rel=1e-9)


def test_flash_and_expert_costs_read_these_sizes(sizes):
    q, kv = 28 * T * 128 * 2, 4 * T * 128 * 2
    vector = 28 * T * 4
    for window, entries in ((4096, 28 * BAND), (None, 28 * TRIANGLE)):
        fwd = counts.flash_call_cost(1, T, sizes, 2, False, window)
        bwd = counts.flash_call_cost(1, T, sizes, 2, True, window)
        assert fwd.flops == 4 * 128 * entries
        assert bwd.flops == 10 * 128 * entries
        assert fwd.bytes == 2 * q + 2 * kv + vector
        assert bwd.bytes == 4 * q + 4 * kv + 2 * vector
        assert fwd.flops / 197e12 > fwd.bytes / 819e9      # compute-bound
    # 4.27 and 9.77 ms a call at the bf16 peak, forward, window and full
    assert counts.flash_call_cost(1, T, sizes, 2, False, 4096).flops \
        / 197e12 * 1e3 == pytest.approx(4.27, abs=0.01)
    assert counts.flash_call_cost(1, T, sizes, 2, False, None).flops \
        / 197e12 * 1e3 == pytest.approx(9.77, abs=0.01)
    # the expert products' count reads 16 held experts of 768 at d 2560
    cost = expert_products_cost(24576, sizes, 2)
    assert cost.flops == 18 * 24576 * 2560 * 768
    assert cost.bytes == 3 * (16 * 3 * 2560 * 768 * 2
                              + 2 * 24576 * 2560 * 2)


def test_the_family_files_reference_is_the_programs():
    """The benchmark's own copy and the program's oracle compute the same
    loss and counts on the rehearsal shape (the program's is held to the
    model leaf by leaf in tests/test_early_moe.py)."""
    import jax
    from benchmark.lib.cells import load_cell
    from distributed_pytorch_from_scratch_tpu.models import (
        vanilla_early_moe)
    workload, config = load_cell(CELL, rehearse=True)
    built = load_module("families", "early_moe").build(
        config, workload["mesh"], "float32")
    assert built.sizes.n_head // built.sizes.n_kv_head == 7
    assert built.sizes.n_held * 2 == built.sizes.n_routed == 8
    assert built.sizes.window < workload["seqlen"]
    params = built.model.init(jax.random.key(1))
    rng = np.random.default_rng(0)
    ids = rng.integers(3, built.sizes.vocab, (2, 73)).astype(np.int32)
    pos = np.tile(np.arange(72, dtype=np.int32), (2, 1))
    with jax.default_matmul_precision("highest"):
        (ours, routed), grads = jax.value_and_grad(
            built.reference_routed, has_aux=True)(
                params, ids[:, :-1], ids[:, 1:], pos)
        theirs, their_grads = jax.value_and_grad(
            lambda p: vanilla_early_moe.vanilla_loss(
                built.model.cfg, p, ids[:, :-1], ids[:, 1:], pos))(params)
    assert float(ours) == pytest.approx(float(theirs), rel=1e-6)
    assert routed.shape == (4, 8)               # layers, routed experts
    np.testing.assert_array_equal(routed.sum(-1), [2 * 72 * 2] * 4)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(their_grads),
                    strict=True):
        np.testing.assert_allclose(a, b, atol=1e-6 * max(
            float(np.abs(b).max()), 1e-3))


# ---- the check's comparison ----

def test_a_reading_over_a_limit_is_not_correct():
    runner = load_module("runners", "train_early_moe")
    limit = runner.EARLY_RTOL["bfloat16"]
    assert set(limit) == {"routed_moved", "router_grad", "expert_grad",
                          "attn_grad"}
    assert all(0 < v < 1 for v in limit.values())
    passed = {"ok": True, "rel_err": {}, "rtol": {}}
    routed = np.array([[40.0, 24.0, 0.0, 0.0]])
    want = {"wk": np.ones((4, 1, 30), np.float32),
            "wq": np.ones((4, 1, 600), np.float32),
            "wv": np.ones((4, 1, 30), np.float32),
            "wo": np.ones((4, 1, 600), np.float32),
            "gate": np.ones((4, 3, 50), np.float32),
            "up": np.ones((4, 3, 50), np.float32),
            "down": np.ones((4, 3, 50), np.float32),
            "router": np.ones((4, 8, 16), np.float32)}
    compare = lambda r=routed, **off: runner._compare_early(
        passed, "bfloat16", r, routed,
        {k: v * off.get(k, 1.0) for k, v in want.items()}, want)
    assert compare()["ok"]
    moved = np.array([[-64.0, 0.0, 64.0, 0.0]]) * limit["routed_moved"]
    assert compare(routed + 0.9 * moved)["ok"]
    assert not compare(routed + 1.1 * moved)["ok"]
    for leaf, reading in (("wk", "attn_grad"), ("wo", "attn_grad"),
                          ("gate", "expert_grad"), ("down", "expert_grad"),
                          ("router", "router_grad")):
        assert compare(**{leaf: 1 + 0.9 * limit[reading]})["ok"]
        said = compare(**{leaf: 1 + 1.1 * limit[reading]})
        assert not said["ok"]
        assert said["rel_err"][reading] == pytest.approx(
            1.1 * limit[reading], rel=1e-5)
    assert not compare(up=np.nan)["ok"]
    # an expert's reading is the MEDIAN over the experts held: one expert
    # of three far off does not move it, two do
    one = {k: v.copy() for k, v in want.items()}
    one["gate"][2, 0] *= 3.0
    assert runner._compare_early(passed, "bfloat16", routed, routed, one,
                                 want)["ok"]
    one["gate"][2, 1] *= 3.0
    assert not runner._compare_early(passed, "bfloat16", routed, routed,
                                     one, want)["ok"]
    assert not runner._compare_early({**passed, "ok": False}, "bfloat16",
                                     routed, routed, want, want)["ok"]
    # with `held` off (the rehearsal) the readings are recorded only
    said = runner._compare_early(passed, "float32", routed + 2 * moved,
                                 routed, one, want, held=False)
    assert said["ok"] and said["rel_err"]["expert_grad"] > 1.0
    assert said["rel_err"]["routed_moved"] > limit["routed_moved"]


def test_the_gradient_samples_are_a_row_a_layer_and_a_group_an_expert():
    """A large slice (over 2^20 elements) on every GRAD_STRIDE-th element, a
    small one whole; one row a layer, the keys in sorted order (full, then
    window: the order the layers run), a period's (periods, layers a
    period) flattened; an expert matrix a group an expert, the router a
    group a routed expert (its column)."""
    import jax.numpy as jnp
    runner = load_module("runners", "train_early_moe")
    leaf = lambda *lead: {
        "wq": {"weight": jnp.ones((*lead, 1024, 1032))},
        "wk": {"weight": jnp.ones((*lead, 8, 4))},
        "wv": {"weight": jnp.ones((*lead, 8, 4))},
        "wo": {"weight": jnp.ones((*lead, 16, 8))},
        "norm1": {"scale": jnp.ones((*lead, 8))},
        "moe": {"router": jnp.arange(8 * 5.0).reshape(8, 5) * jnp.ones(
                    (*lead, 1, 1)),
                "gate": jnp.ones((*lead, 3, 8, 6)),
                "up": jnp.ones((*lead, 3, 8, 6)),
                "down": jnp.ones((*lead, 3, 6, 8))}}
    tree = {"window_layers_0": leaf(1, 3), "full_layers_0": leaf(1, 1),
            "norm": {"scale": jnp.ones((8,))}}
    named = runner._sampled(tree)
    assert sorted(named) == ["down", "gate", "router", "up", "wk", "wo",
                             "wq", "wv"]
    assert named["wk"].shape == (4, 1, 32)
    assert named["wq"].shape == (4, 1, -(-1024 * 1032 // runner.GRAD_STRIDE))
    assert named["gate"].shape == named["down"].shape == (4, 3, 48)
    assert named["router"].shape == (4, 5, 8)
    np.testing.assert_array_equal(named["router"][0, 2], np.arange(8) * 5 + 2)


@pytest.mark.parametrize("control,reading,factor", [
    ("router_post_attention", "routed_moved", None),
    ("router_post_attention", "router_grad", 1000.0),
    ("silu_experts", "expert_grad", 1000.0),
    ("window_as_causal", "attn_grad", 1000.0),
    ("rope_on_full", "attn_grad", 1000.0),
    ("fp8_expert_inputs", "expert_grad", 1000.0),
    ("fp8_router_inputs", "router_grad", 1000.0)])
def test_a_control_reads_worse_than_the_program(control, reading, factor):
    """The limits of `EARLY_RTOL` are read at the published widths on the
    chip (PERF.md section 2) and say nothing at the rehearsal shape; what
    holds at every shape is that with the same seed a control reads worse
    than the sound program in the runner's own numbers (the rehearsal is
    float32: the sound program reads rounding)."""
    tool = load_module("tools", "early_control")
    sound = tool.reading(CELL, 2147483693, None, rehearse=True)
    bad = tool.reading(CELL, 2147483693, control, rehearse=True)
    assert sound["ok"] and sound["rel_err"]["routed_moved"] == 0.0
    assert all(sound["rel_err"][k] < 1e-5 for k in (
        "router_grad", "expert_grad", "attn_grad"))
    if factor is None:
        # the sound reading is 0; a fresh model's attention adds little to
        # the residual stream and a norm is a positive multiple a token, so
        # most pairs stay (the weights do not: `router_grad`)
        assert bad["rel_err"][reading] > 0.01
    else:
        assert bad["rel_err"][reading] > factor * sound["rel_err"][reading]
        assert bad["rel_err"][reading] > 0.01


# ---- the scope and kernel readers ----

LAYER = ("jit(step)/loss_and_grad/transpose(jvp(jit(loss_shard)))/while/"
         "body/closed_call/while/body/closed_call/checkpoint/")
FWD = ("jit(step)/loss_and_grad/jvp(jit(loss_shard))/while/body/"
       "closed_call/while/body/closed_call/")
OPS = [
    # (instruction, meta, op_name or None, the part it belongs to, early?)
    ("fusion.3", "fusion", LAYER + "rematted_computation/gqa_attn/mul",
     "gqa_attn", False),
    ("fusion.4", "fusion", FWD + "gqa_attn/dot_general", "gqa_attn", False),
    ("flash_fwd_window.24", "custom-call tpu_custom_call operands=3",
     LAYER + "rematted_computation/flash_fwd_window", "flash", False),
    ("flash_bwd_dq_window.12", "custom-call tpu_custom_call operands=6",
     LAYER + "flash_bwd_dq_window", "flash", False),
    ("flash_bwd_dkv_window.13", "custom-call tpu_custom_call operands=6",
     LAYER + "flash_bwd_dkv_window", "flash", False),
    ("flash_fwd.3", "custom-call tpu_custom_call operands=3",
     LAYER + "rematted_computation/flash_fwd", "flash", False),
    ("flash_bwd_dq.2", "custom-call tpu_custom_call operands=6",
     LAYER + "flash_bwd_dq", "flash", False),
    ("flash_bwd_dkv.2", "custom-call tpu_custom_call operands=6",
     LAYER + "flash_bwd_dkv", "flash", False),
    ("fusion.7", "fusion", FWD + "moe_route/early/dot_general", "moe_route",
     True),
    ("fusion.17", "fusion", LAYER + "rematted_computation/moe_route/early/"
     "index/reduce_sum", "moe_route", True),
    ("sort.21", "sort", "sort", "moe_route", True),
    ("fusion.18", "fusion", LAYER + "moe_route/jit(take_rows)/gather",
     "moe_route", False),
    ("fusion.8", "fusion", LAYER + "rematted_computation/moe_experts/"
     "jit(relu)/max", "moe_experts", False),
    ("ragged-dot-none.4", "custom-call tpu_custom_call operands=7",
     "ragged-dot-none", "moe_experts", False),
    ("fusion.10", "fusion", "jit(step)/loss_and_grad/jvp(jit(loss_shard))/"
     "head_loss/convert_element_type", "head_loss", False),
    ("fusion.11", "fusion", "jit(step)/optimizer/mul", "optimizer", False),
    ("fusion.12", "fusion", "jit(step)/grad_norm/reduce_sum", "grad_norm",
     False),
    ("fusion.13", "fusion", LAYER + "mul", "rest", False),
    ("copy.7", "copy", None, "unattributed", False),
]


def capture(steps=2, each_ns=1000, ops=OPS):
    """`steps` runs of the step program on chip 0, every op of `ops` once a
    run, op i lasting (i + 1) * each_ns, back to back."""
    events, runs, t = [], [], 0
    for _ in range(steps):
        start = t
        for i, (name, meta, *_) in enumerate(ops):
            events.append(trace.Event(name, t, (i + 1) * each_ns, meta))
            t += (i + 1) * each_ns
        runs.append((start, t))
        t += 500                                        # an idle gap
    dev = trace.DeviceTrace(0, (0, runs[-1][1]), steps, events, [])
    return dev, runs, {name: op for name, _, op, *_ in ops if op}


def test_every_op_falls_in_one_part_and_the_parts_sum_to_busy():
    dev, runs, names = capture()
    parts = early_scopes.scope_ns(dev, runs, names)
    assert set(parts) == set(early_scopes.PARTS)
    assert sum(parts.values()) == dev.busy_ns()
    want = dict.fromkeys(early_scopes.PARTS, 0)
    early = 0
    for i, (_, _, _, part, is_early) in enumerate(OPS):
        want[part] += 2 * (i + 1) * 1000
        early += 2 * (i + 1) * 1000 * is_early
    assert parts == want
    # the early part is a subset of `moe_route`, sorts included
    assert early_scopes.early_route_ns(dev, runs, names) == early
    assert 0 < early < parts["moe_route"]
    assert early_scopes.early_route_ns(dev, runs[:1], names) == early // 2
    outside = early_scopes.scope_ns(dev, runs[:1], names)
    assert outside["other_programs"] == sum(want.values()) // 2
    # a program whose routing names no such scope: nothing, not the sorts
    plain = {k: v.replace("/early", "") for k, v in names.items()}
    assert early_scopes.early_route_ns(dev, runs, plain) is None
    # a window layer's calls are told from a full layer's by their name,
    # in the split kernels too
    names_of = lambda backward, window: [
        c.name for c in swa_scopes.flash_calls(dev, backward, window)]
    assert names_of(False, True) == ["flash_fwd_window.24"] * 2
    assert names_of(True, True) == ["flash_bwd_dq_window.12",
                                    "flash_bwd_dkv_window.13"] * 2
    assert names_of(False, False) == ["flash_fwd.3"] * 2
    assert names_of(True, False) == ["flash_bwd_dq.2", "flash_bwd_dkv.2"] * 2


def test_the_readers_read_the_runners_fields(sizes):
    from distributed_pytorch_from_scratch_tpu.obs.attribution import (
        flash_tile_stats)
    from distributed_pytorch_from_scratch_tpu.ops.attention import (
        sliding_window)
    dev, runs, names = capture()
    parts = early_scopes.scope_ns(dev, runs, names)
    workload = load_json("workloads", CELL + ".json")
    peak = SimpleNamespace(flops_per_s=197e12, hbm_bytes_per_s=819e9)
    plan = {name: flash_tile_stats(T, head_dim=128,
                                   mask=sliding_window(4096),
                                   backward=name == "backward")
            for name in ("forward", "backward")}
    m = SimpleNamespace(devices=[dev], scopes=parts, peak=peak, sizes=sizes,
                        workload=workload, mesh={"dp": 1, "tp": 1}, chips=1,
                        tokens_per_s=30000.0,
                        rows_here_per_layer=[24576.0] * 4,
                        rows_here_per_token=1.5, load_max_over_mean=2.0,
                        active_flops_per_token=2.5e9, window_flash_plan=plan,
                        route_early_ns=early_scopes.early_route_ns(
                            dev, runs, names))
    read = lambda name: load_module("layer_metrics", name).read(m)
    ms = lambda *ops: sum(ops) * 1000 / 1e6
    assert read("model.gqa_attn_ms") == pytest.approx(ms(1, 2))
    assert read("kernels.flash_ms") == pytest.approx(ms(3, 4, 5, 6, 7, 8))
    assert read("kernels.window_flash_ms") == pytest.approx(ms(3, 4, 5))
    assert read("model.moe_route_ms") == pytest.approx(ms(9, 10, 11, 12))
    assert read("model.moe_route_early_ms") == pytest.approx(ms(9, 10, 11))
    assert read("model.moe_experts_ms") == pytest.approx(ms(13, 14))
    assert read("moe.load_max_over_mean") == 2.0
    assert read("moe.rows_here_per_token") == 1.5
    assert read("train_step.active_mfu_pct") == pytest.approx(
        100 * 2.5e9 * 30000 / 197e12)
    # the flash shares: one forward call and ONE backward (two kernels, dq
    # and dkv, for one backward's work) a run of the capture and kind, each
    # at the kind's live entries over the bf16 peak
    for name, window, ops in (
            ("kernels.window_flash_roofline", 4096, (3, 4, 5)),
            ("kernels.full_flash_roofline", None, (6, 7, 8))):
        fwd = counts.flash_call_cost(1, T, sizes, 2, False, window)
        bwd = counts.flash_call_cost(1, T, sizes, 2, True, window)
        assert read(name) == pytest.approx(
            100 * 2 * (fwd.flops + bwd.flops) / 197e12
            / (2 * ms(*ops) / 1e3))
    # what the plans compute over what the window leaves live, both ways
    assert read("window.flash_computed_over_live") == pytest.approx(
        (plan["forward"]["work_elems"] + plan["backward"]["work_elems"])
        / (2 * BAND))
    assert 1.0 < read("window.flash_computed_over_live") < 1.10
    experts = 4 * max(18 * 24576 * 2560 * 768 / 197e12,
                      expert_products_cost(24576, sizes, 2).bytes / 819e9)
    assert read("model.moe_experts_roofline") == pytest.approx(
        100 * experts / (ms(13, 14) / 1e3))


def test_the_new_reader_returns_nothing_where_there_is_nothing_to_read():
    """A runner that hands no such reading (every other family's, and the
    parent's: the driver lays this benchmark over the parent's checkout for
    its traced runs), an untraced run: None, and no raise."""
    read = load_module("layer_metrics", "model.moe_route_early_ms").read
    assert read(SimpleNamespace(devices=[])) is None
    dev, _, _ = capture()
    assert read(SimpleNamespace(devices=[dev], scopes={"moe_route": 7})) \
        is None
    assert read(SimpleNamespace(devices=[dev], route_early_ns=None)) is None
    assert read(SimpleNamespace(devices=[], route_early_ns=5)) is None
