"""The conv_moe family's counts at the published widths
(benchmark/lib/conv_moe_counts.py), the family file's reference against the
program's at a tiny size, the `train_conv_moe` check's comparison, its
control tool at the rehearsal shape, and the scope readers on a small
capture made of the real step's instruction names and `op_name`s (as the
step compiled for a described v5e carries them)."""

from types import SimpleNamespace

import numpy as np
import pytest

from benchmark.lib import conv_moe_counts as counts
from benchmark.lib import conv_scopes, trace
from benchmark.lib.files import load_json, load_module
from benchmark.lib.gdn_moe_counts import gqa_flash_call_cost
from benchmark.lib.mla_moe_counts import expert_products_cost

CELL = "lfm2-8b-a1b.train-ep4share-b2-t8192"
CONFIG = "lfm2-8b-a1b.json"


@pytest.fixture(scope="module")
def sizes():
    family = load_module("families", "conv_moe")
    return family.sizes_of(load_json("configs", CONFIG))


def test_parameters_of_the_share_at_the_published_widths(sizes):
    parts = counts.param_counts(sizes)
    assert parts["shortconv_mixer"] == 16_783_360
    assert parts["attention_mixer"] == 10_485_888
    assert parts["dense_mlp"] == 44_040_192
    assert parts["expert"] == 11_010_048
    assert parts["ffn"] == 88_145_920 + 32           # + the selection bias
    assert parts["ffn_uncut"] == 352_387_072 + 32
    assert parts["dense_conv_layer"] == 60_827_648
    assert parts["conv_layer"] == 104_933_376 + 32
    assert parts["attention_layer"] == 98_635_904 + 32
    assert parts["embedding"] == 33_554_432
    assert parts["total"] == 507_820_288
    assert parts["total"] * 16 / 1e9 == pytest.approx(8.13, abs=0.005)
    assert (sizes.conv_layers, sizes.attn_layers, sizes.expert_layers,
            sizes.n_layer) == (4, 1, 4, 5)
    assert sizes.head_dim == 64


def test_the_program_counts_the_same(sizes):
    family = load_module("families", "conv_moe")
    built = family.build(load_json("configs", CONFIG), {"dp": 1, "tp": 1},
                         "bfloat16")
    cfg = built.model.cfg
    assert cfg.num_params() == counts.param_counts(sizes)["total"]
    assert cfg.num_experts == 32 and cfg.conv_moe.experts_held == 8
    assert cfg.vocab_size == 16384 and cfg.num_layers == 5
    assert built.model._pattern == (
        "dense_layers", (("attn_layers_0", 1), ("conv_layers_0", 3)))
    # the chunk policy at this share: one chunk of ALL pairs
    moe = built.model._mods["moe"]
    assert moe.chunk_share == 1.0 and moe.chunk_rows(65536) == 65536


def test_the_configuration_holds_every_published_number():
    """Every number of the catalog's row under the same key, but those in
    `reduced`, whose published values stand beside them."""
    published = {
        "conv_L_cache": 3, "hidden_size": 2048, "intermediate_size": 7168,
        "max_position_embeddings": 128000, "moe_intermediate_size": 1792,
        "norm_eps": 1e-05, "num_attention_heads": 32, "num_dense_layers": 2,
        "num_experts": 32, "num_experts_per_tok": 4,
        "num_hidden_layers": 24, "num_key_value_heads": 8,
        "rope_theta": 1000000, "routed_scaling_factor": 1,
        "vocab_size": 65536}
    config = load_json("configs", CONFIG)
    differs = {k for k, v in published.items() if config[k] != v}
    assert differs == {"num_dense_layers", "num_experts", "vocab_size"}
    assert (config["conv_bias"], config["norm_topk_prob"],
            config["use_expert_bias"], config["model_type"]) == (
                False, True, True, "lfm2_moe")
    assert sorted(config["reduced"]) == [
        "layer_types", "num_dense_layers", "num_experts", "num_layers",
        "vocab_size"]
    whole = config["published"]
    assert {k: whole[k] for k in ("num_dense_layers", "num_experts",
                                  "num_hidden_layers", "vocab_size")} == {
        "num_dense_layers": 2, "num_experts": 32, "num_hidden_layers": 24,
        "vocab_size": 65536}
    # the cut is the published layers 1-5: one dense layer and one period
    assert config["layer_types"] == whole["layer_types"][1:6] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    assert len(whole["layer_types"]) == 24
    assert [i for i, k in enumerate(whole["layer_types"])
            if k == "full_attention"] == [2, 6, 10, 14, 18, 21]
    assert config["num_experts"] * 4 == 32
    assert config["vocab_size"] * 4 == 65536 and config["num_layers"] == 5
    assert set(config["assumed"]) >= {"tie_word_embeddings", "expert_bias",
                                      "balance_loss", "initialisation"}


def test_flops_per_token(sizes):
    """Forward MFLOP a token, as ISSUE 39 counts them: convolution mixers 4
    x 33.6, dense MLP 88, attention projections 21, causal scores 34,
    routed experts 4 x 22.0 for the rows held (352 as computed under the
    whole chunk), the head 67: 432 of work, 696 executed."""
    M = 1e6
    assert 2 * counts.shortconv_matmul_params(sizes) / M == \
        pytest.approx(33.6, abs=0.05)
    assert 2 * counts.dense_mlp_params(sizes) / M == \
        pytest.approx(88.1, abs=0.05)
    assert 2 * counts.attention_matmul_params(sizes) / M == \
        pytest.approx(21.0, abs=0.05)
    assert 2 * counts.expert_params(sizes) / M == \
        pytest.approx(22.0, abs=0.05)
    uniform = sizes.expert_layers * sizes.top_k * sizes.n_held / sizes.n_routed
    assert uniform == 4.0                                  # 4 x 1.0
    forward = counts.forward_flops_per_token(sizes, 8192, uniform)
    assert forward / M == pytest.approx(432, rel=0.01)
    executed = counts.executed_forward_flops_per_token(sizes, 8192,
                                                       sizes.top_k)
    assert executed / M == pytest.approx(696, rel=0.01)
    assert (executed - forward) / M == pytest.approx(3 * 88.1, rel=0.01)
    # the numerator of active_mfu: 6 x the matmuls' parameters, attention
    # at the full square in the one attention layer
    full = counts.train_flops_per_token(sizes, 8192, uniform)
    assert full == pytest.approx(
        6 * counts.active_matmul_params(sizes, uniform)
        + 12 * 32 * 64 * 8192)
    assert 1.38e9 < full < 1.40e9
    # more rows computed here, more FLOPs: the counter is in the count
    assert counts.train_flops_per_token(sizes, 8192, 5.0) - full == \
        pytest.approx(6 * counts.expert_params(sizes))


def test_shortconv_flash_and_expert_costs_read_these_sizes(sizes):
    conv = counts.shortconv_cost(2, 8192, sizes, 2)
    assert conv.flops == 6 * 16384 * 4 * 2048 * 2048
    assert conv.bytes == 2 * 16384 * 6 * 2048 * 2
    # the flash count written for the gdn_moe family reads 64 / 64 and 32
    # query heads over 8 from this family's sizes
    fwd = gqa_flash_call_cost(2, 8192, sizes, 2, backward=False)
    entries = 2 * 32 * 8192 * 8193 / 2
    assert fwd.flops == 4 * 64 * entries
    q, kv = 2 * 32 * 8192 * 64 * 2, 2 * 8 * 8192 * 64 * 2
    assert fwd.bytes == 2 * q + 2 * kv + 2 * 32 * 8192 * 4
    # and the expert products' count its 8 held experts of 1792
    cost = expert_products_cost(16384, sizes, 2)
    assert cost.flops == 18 * 16384 * 2048 * 1792
    assert cost.bytes == 3 * (8 * 3 * 2048 * 1792 * 2
                              + 2 * 16384 * 2048 * 2)


def test_the_family_files_reference_is_the_programs():
    """The benchmark's own copy and the program's oracle compute the same
    loss on the rehearsal shape (the program's is held to the model leaf by
    leaf in tests/test_conv_moe.py), and the copy's `routed` rows are the
    expert layers' in the order they run."""
    import jax
    from benchmark.lib.cells import load_cell
    from distributed_pytorch_from_scratch_tpu.models.vanilla_conv_moe import (
        vanilla_loss)
    workload, config = load_cell(CELL, rehearse=True)
    built = load_module("families", "conv_moe").build(
        config, workload["mesh"], "float32")
    params = built.model.init(jax.random.key(1))
    rng = np.random.default_rng(0)
    ids = rng.integers(0, built.sizes.vocab, (2, 71)).astype(np.int32)
    pos = np.tile(np.arange(70, dtype=np.int32), (2, 1))
    with jax.default_matmul_precision("highest"):
        ours, routed = built.reference_routed(params, ids[:, :-1],
                                              ids[:, 1:], pos)
        theirs = vanilla_loss(built.model.cfg, params, ids[:, :-1],
                              ids[:, 1:], pos)
    assert float(ours) == pytest.approx(float(theirs), rel=1e-6)
    assert routed.shape == (4, 8)
    np.testing.assert_array_equal(routed.sum(-1), [2 * 70 * 2] * 4)


# ---- the check's comparison ----

def test_a_reading_over_a_limit_is_not_correct():
    runner = load_module("runners", "train_conv_moe")
    limit = runner.CONV_RTOL["bfloat16"]
    assert 0 < limit["routed_moved"] < 0.05 and 0 < limit["conv_grad"] < 1
    passed = {"ok": True, "rel_err": {}, "rtol": {}}
    routed = np.array([[40.0, 24.0, 0.0, 0.0]])
    want = {"conv": np.ones((4, 30), np.float32),
            "w_in": np.ones((4, 600), np.float32)}
    compare = lambda r=routed, **off: runner._compare_conv(
        passed, "bfloat16", r, routed,
        {k: v * off.get(k, 1.0) for k, v in want.items()}, want)
    assert compare()["ok"]
    moved = np.array([[-64.0, 0.0, 64.0, 0.0]]) * limit["routed_moved"]
    assert compare(routed + 0.9 * moved)["ok"]
    assert not compare(routed + 1.1 * moved)["ok"]
    assert compare(conv=1 + 0.9 * limit["conv_grad"])["ok"]
    assert not compare(conv=1 + 1.1 * limit["conv_grad"])["ok"]
    assert not compare(w_in=1 + 1.1 * limit["conv_grad"])["ok"]
    assert not compare(w_in=np.nan)["ok"]
    assert not runner._compare_conv({**passed, "ok": False}, "bfloat16",
                                    routed, routed, want, want)["ok"]
    # one layer of four over the limit is enough
    one = {k: v.copy() for k, v in want.items()}
    one["conv"][3] *= 1 + 1.1 * limit["conv_grad"]
    assert not runner._compare_conv(passed, "bfloat16", routed, routed,
                                    one, want)["ok"]
    # with `held` off (the rehearsal) the readings are recorded only
    said = runner._compare_conv(passed, "float32", routed + 2 * moved,
                                routed, one, want, held=False)
    assert said["ok"] and said["rel_err"]["conv_grad"] > limit["conv_grad"]


def test_the_gradient_samples_pair_up_by_layer():
    """`_conv_named` on a segment's leaves (layers, ...) and a period's
    (periods, layers a period, ...): one row a convolution layer, the
    tree's keys in sorted order."""
    import jax.numpy as jnp
    runner = load_module("runners", "train_conv_moe")
    leaf = lambda *lead: {"conv": {
        "w_in": jnp.ones((*lead, 8, 3, 8)), "conv": jnp.ones((*lead, 8, 3)),
        "w_out": jnp.ones((*lead, 8, 8))}}
    tree = {"dense_layers": leaf(1), "conv_layers_0": leaf(2, 3),
            "attn_layers_0": {"wq": {"weight": jnp.ones((2, 1, 8, 8))}},
            "embedding": {"weight": jnp.ones((16, 8))}}
    named = runner._conv_named(tree)
    assert {k: v.shape for k, v in named.items()} == {
        "w_in": (7, 192), "conv": (7, 24), "w_out": (7, 64)}


@pytest.mark.parametrize("control", ["fp8_conv_inputs", "fp8_router_inputs"])
def test_a_control_reads_worse_than_the_program(control):
    """The limits of `CONV_RTOL` are read at the published widths on the
    chip (PERF.md section 2) and say nothing at the rehearsal shape; what
    holds at every shape is that with the same seed a control (one input
    rounded to float8_e4m3) reads worse than the sound program in the
    runner's own numbers, and is not `ok` by `train`'s own limits there
    (the rehearsal runs in float32)."""
    tool = load_module("tools", "conv_control")
    sound = tool.reading(CELL, 2147483693, None, rehearse=True)
    bad = tool.reading(CELL, 2147483693, control, rehearse=True)
    assert sound["ok"] and not bad["ok"]
    for name in ("routed_moved", "conv_grad"):
        assert bad["rel_err"][name] > 10 * sound["rel_err"][name] + 1e-3


# ---- the scope readers ----

SEG = "jit(step)/loss_and_grad/transpose(jvp(jit(loss_shard)))/while/body/" \
      "closed_call/checkpoint/"
LAYER = ("jit(step)/loss_and_grad/transpose(jvp(jit(loss_shard)))/while/body/"
         "closed_call/while/body/closed_call/checkpoint/")
FWD = ("jit(step)/loss_and_grad/jvp(jit(loss_shard))/while/body/closed_call/"
       "while/body/closed_call/")
OPS = [
    # (instruction, meta, op_name or None, the part it belongs to)
    ("fusion.1", "fusion", SEG + "rematted_computation/shortconv/mul",
     "shortconv"),
    ("fusion.2", "fusion", LAYER + "shortconv/convert_element_type",
     "shortconv"),
    ("fusion.3", "fusion", LAYER + "rematted_computation/gqa_attn/mul",
     "gqa_attn"),
    ("fusion.4", "fusion", FWD + "gqa_attn/mul", "gqa_attn"),
    ("fusion.5", "fusion", SEG + "dense_ffn/dot_general", "dense_ffn"),
    ("flash_fwd.40", "custom-call tpu_custom_call operands=3",
     LAYER + "rematted_computation/flash_fwd", "flash"),
    ("flash_bwd_dq.18", "custom-call tpu_custom_call operands=6",
     LAYER + "flash_bwd_dq", "flash"),
    ("flash_bwd_dkv.18", "custom-call tpu_custom_call operands=6",
     LAYER + "flash_bwd_dkv", "flash"),
    ("fusion.7", "fusion", FWD + "moe_route/jit(take_along_axis)/gather",
     "moe_route"),
    ("sort.21", "sort", "sort", "moe_route"),
    ("fusion.8", "fusion", LAYER + "while/body/closed_call/checkpoint/"
     "rematted_computation/cond/branch_1_fun/moe_experts/jit(silu)",
     "moe_experts"),
    ("ragged-dot-none.4", "custom-call tpu_custom_call operands=7",
     "ragged-dot-none", "moe_experts"),
    ("fusion.10", "fusion", "jit(step)/loss_and_grad/jvp(jit(loss_shard))/"
     "head_loss/convert_element_type", "head_loss"),
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
    parts = conv_scopes.scope_ns(dev, runs, names)
    assert set(parts) == set(conv_scopes.PARTS)
    assert sum(parts.values()) == dev.busy_ns()
    want = dict.fromkeys(conv_scopes.PARTS, 0)
    for i, (_, _, _, part) in enumerate(OPS):
        want[part] += 2 * (i + 1) * 1000
    assert parts == want
    # an op outside every run of the step is another program's
    outside = conv_scopes.scope_ns(dev, runs[:1], names)
    assert outside["other_programs"] == sum(want.values()) // 2


def test_the_readers_read_the_runners_fields(sizes):
    dev, runs, names = capture()
    parts = conv_scopes.scope_ns(dev, runs, names)
    workload = load_json("workloads", CELL + ".json")
    peak = SimpleNamespace(flops_per_s=197e12, hbm_bytes_per_s=819e9)
    m = SimpleNamespace(devices=[dev], scopes=parts, peak=peak, sizes=sizes,
                        workload=workload, mesh={"dp": 1, "tp": 1}, chips=1,
                        tokens_per_s=29000.0,
                        rows_here_per_layer=[16384.0] * 4,
                        rows_here_per_token=1.0, load_max_over_mean=2.0,
                        active_flops_per_token=1.39e9)
    read = lambda name: load_module("layer_metrics", name).read(m)
    ms = lambda *ops: sum(ops) * 1000 / 1e6
    assert read("model.shortconv_ms") == pytest.approx(ms(1, 2))
    assert read("model.gqa_attn_ms") == pytest.approx(ms(3, 4))
    assert read("model.dense_ffn_ms") == pytest.approx(ms(5))
    assert read("kernels.flash_ms") == pytest.approx(ms(6, 7, 8))
    assert read("model.moe_route_ms") == pytest.approx(ms(9, 10))
    assert read("model.moe_experts_ms") == pytest.approx(ms(11, 12))
    assert read("moe.load_max_over_mean") == 2.0
    assert read("moe.rows_here_per_token") == 1.0
    assert read("train_step.active_mfu_pct") == pytest.approx(
        100 * 1.39e9 * 29000 / 197e12)
    # shares of a roofline: least time over the time taken
    conv = counts.shortconv_cost(2, 8192, sizes, 2)
    assert conv.flops / 197e12 > conv.bytes / 819e9      # compute-bound
    assert read("model.shortconv_roofline") == pytest.approx(
        100 * 4 * conv.flops / 197e12 / (ms(1, 2) / 1e3))
    fwd = gqa_flash_call_cost(2, 8192, sizes, 2, False)
    bwd = gqa_flash_call_cost(2, 8192, sizes, 2, True)
    least = lambda c: max(c.flops / 197e12, c.bytes / 819e9)
    # two forward calls, and two kernels (dq; dk, dv) for each backward
    assert read("kernels.gqa_flash_roofline") == pytest.approx(
        100 * (2 * least(fwd) + 2 * least(bwd)) / (2 * ms(6, 7, 8) / 1e3))
    experts = 4 * max(18 * 16384 * 2048 * 1792 / 197e12,
                      expert_products_cost(16384, sizes, 2).bytes / 819e9)
    assert read("model.moe_experts_roofline") == pytest.approx(
        100 * experts / (ms(11, 12) / 1e3))


def test_the_readers_return_nothing_where_there_is_nothing_to_read(sizes):
    """A runner that hands no scope split (the `train` runner), another
    family's split (`train_hybrid`: no `shortconv` among its parts) or a
    program without the scopes gets None, not an exception."""
    bare = SimpleNamespace(devices=[], peak=None, tokens_per_s=1.0, chips=1,
                           sizes=SimpleNamespace())
    dev, runs, names = capture()
    other = SimpleNamespace(
        devices=[dev], peak=SimpleNamespace(flops_per_s=1.0,
                                            hbm_bytes_per_s=1.0),
        scopes={"gdn": 5, "moe_route": 7}, sizes=SimpleNamespace(n_head=32),
        workload={}, mesh={})
    for m in (bare, other):
        for name in ("model.shortconv_ms", "model.gqa_attn_ms",
                     "model.dense_ffn_ms", "model.shortconv_roofline"):
            assert load_module("layer_metrics", name).read(m) is None
