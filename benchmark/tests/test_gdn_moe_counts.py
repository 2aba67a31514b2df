"""The gdn_moe family's counts at the published widths
(benchmark/lib/gdn_moe_counts.py), the family file's reference against the
program's at a tiny size, the `train_hybrid` check's comparison, and the
scope readers on a small capture made of the real step's instruction names
and `op_name`s (as the step compiled for a described v5e carries them)."""

from types import SimpleNamespace

import numpy as np
import pytest

from benchmark.lib import gdn_moe_counts as counts
from benchmark.lib import hybrid_scopes, trace
from benchmark.lib.files import load_json, load_module
from benchmark.lib.mla_moe_counts import expert_products_cost

CELL = "qwen3-next-80b-a3b.train-ep16share-b2-t8192"
CONFIG = "qwen3-next-80b-a3b.json"


@pytest.fixture(scope="module")
def sizes():
    family = load_module("families", "gdn_moe")
    return family.sizes_of(load_json("configs", CONFIG))


def test_parameters_of_the_share_at_the_published_widths(sizes):
    parts = counts.param_counts(sizes)
    assert parts["gdn_mixer"] == 33_718_464
    assert parts["attention_mixer"] == 27_263_488
    assert parts["ffn"] == 104_859_648
    assert parts["ffn_uncut"] == 1_614_809_088
    assert parts["linear_layer"] == 138_582_208
    assert parts["full_layer"] == 132_127_232
    assert parts["embedding_and_head"] == 77_791_232
    assert parts["total"] == 625_667_136
    assert parts["total"] * 16 / 1e9 == pytest.approx(10.01, abs=0.005)
    assert sizes.linear_layers == 3 and sizes.full_layers == 1
    assert sizes.rotary_dim == 64


def test_the_program_counts_the_same(sizes):
    family = load_module("families", "gdn_moe")
    built = family.build(load_json("configs", CONFIG), {"dp": 1, "tp": 1},
                         "bfloat16")
    cfg = built.model.cfg
    assert cfg.num_params() == counts.param_counts(sizes)["total"]
    assert cfg.num_experts == 512 and cfg.gdn_moe.experts_held == 32
    assert cfg.vocab_size == 18992 and cfg.num_layers == 4
    assert built.model.periods == 1


def test_the_configuration_holds_every_published_number():
    """Every number of the catalog's row under the same key, but the three
    in `reduced`, whose published values stand beside them."""
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_size": 2048, "intermediate_size": 5120,
        "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
        "linear_num_key_heads": 16, "linear_num_value_heads": 32,
        "linear_value_head_dim": 128, "max_position_embeddings": 262144,
        "moe_intermediate_size": 512, "num_attention_heads": 16,
        "num_experts": 512, "num_experts_per_tok": 10,
        "num_hidden_layers": 48, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_theta": 10000000, "shared_expert_intermediate_size": 512,
        "vocab_size": 151936}
    config = load_json("configs", CONFIG)
    differs = {k for k, v in published.items() if config[k] != v}
    assert differs == {"num_experts", "vocab_size"}
    assert sorted(config["reduced"]) == ["num_experts", "num_layers",
                                         "vocab_size"]
    assert config["published"] == {"num_experts": 512,
                                   "num_hidden_layers": 48,
                                   "vocab_size": 151936}
    assert config["num_experts"] * 16 == 512
    assert config["vocab_size"] * 8 == 151936 and config["num_layers"] == 4


def test_flops_per_token(sizes):
    """Forward MFLOP a token, as ISSUE 35 counts them: DeltaNet projections
    3 x 67.4, the rule 3 x 5.2, attention projections 54.5, causal scores
    67.1, routers + shared + routed here 4 x 12.3 at 0.625 rows a token and
    layer, the head 77.8: 469 within 1%."""
    M = 1e6
    assert 2 * counts.gdn_matmul_params(sizes) / M == \
        pytest.approx(67.4, abs=0.05)
    assert 2 * counts.attention_matmul_params(sizes) / M == \
        pytest.approx(54.5, abs=0.05)
    assert counts.rule_flops_per_token(sizes) / M == \
        pytest.approx(5.24, abs=0.01)
    uniform = sizes.n_layer * sizes.top_k * sizes.n_held / sizes.n_routed
    assert uniform == 2.5                                 # 4 x 0.625
    forward = counts.forward_flops_per_token(sizes, 8192, uniform)
    assert forward / M == pytest.approx(469, rel=0.01)
    # the numerator of active_mfu: 6 x the matmuls' parameters, attention
    # at the full square, the rule three times
    full = counts.train_flops_per_token(sizes, 8192, uniform)
    assert full == pytest.approx(
        6 * counts.active_matmul_params(sizes, uniform)
        + 12 * 16 * 256 * 8192 + 9 * counts.rule_flops_per_token(sizes))
    assert 1.59e9 < full < 1.61e9
    # more rows computed here, more FLOPs: the counter is in the count
    assert counts.train_flops_per_token(sizes, 8192, 5.0) - full == \
        pytest.approx(6 * 2.5 * counts.expert_params(sizes))


def test_rule_and_flash_costs(sizes):
    rule = counts.rule_cost(2, 8192, sizes, 2)
    assert rule.flops == 3 * 16384 * counts.rule_flops_per_token(sizes)
    rows = 16384 * 32
    assert rule.bytes == 2 * (rows * 512 * 2 + rows * 8
                              + rows / 64 * 128 * 128 * 4)
    fwd = counts.gqa_flash_call_cost(2, 8192, sizes, 2, backward=False)
    bwd = counts.gqa_flash_call_cost(2, 8192, sizes, 2, backward=True)
    entries = 2 * 16 * 8192 * 8193 / 2
    assert fwd.flops == 4 * 256 * entries and bwd.flops == 10 * 256 * entries
    q, kv = 2 * 16 * 8192 * 256 * 2, 2 * 2 * 8192 * 256 * 2
    assert fwd.bytes == 2 * q + 2 * kv + 2 * 16 * 8192 * 4
    # at a group of 1 and these widths it is flops.py's count
    from benchmark.lib.flops import flash_call_cost
    mha = sizes._replace(n_kv_head=sizes.n_head)
    for backward in (False, True):
        assert counts.gqa_flash_call_cost(2, 8192, mha, 2, backward) == \
            flash_call_cost(32, 8192, 256, 2, backward)


def test_the_expert_products_cost_reads_these_sizes(sizes):
    """`model.moe_experts_roofline`'s count (written for the mla_moe
    family's sizes) gives the right cost from this family's unchanged."""
    cost = expert_products_cost(10240, sizes, 2)
    assert cost.flops == 18 * 10240 * 2048 * 512
    assert cost.bytes == 3 * (32 * 3 * 2048 * 512 * 2 + 2 * 10240 * 2048 * 2)


def test_the_family_files_reference_is_the_programs():
    """The benchmark's own copy and the program's oracle compute the same
    loss on the rehearsal shape (the program's is held to the model leaf by
    leaf in tests/test_gdn_moe.py), and the copy's `routed` rows are the
    layers' in the order they run."""
    import jax
    from benchmark.lib.cells import load_cell
    from distributed_pytorch_from_scratch_tpu.models.vanilla_gdn_moe import (
        vanilla_loss)
    workload, config = load_cell(CELL, rehearse=True)
    built = load_module("families", "gdn_moe").build(
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
    runner = load_module("runners", "train_hybrid")
    limit = runner.HYBRID_RTOL["bfloat16"]
    passed = {"ok": True, "rel_err": {}, "rtol": {}}
    routed = np.array([[40.0, 24.0, 0.0, 0.0]])
    want = {"A_log": np.ones((3, 32), np.float32),
            "w_qkvz": np.ones((3, 600), np.float32)}
    compare = lambda r=routed, **off: runner._compare_hybrid(
        passed, "bfloat16", r, routed,
        {k: v * off.get(k, 1.0) for k, v in want.items()}, want)
    assert compare()["ok"]
    moved = np.array([[-64.0, 0.0, 64.0, 0.0]]) * limit["routed_moved"]
    assert compare(routed + 0.9 * moved)["ok"]
    assert not compare(routed + 1.1 * moved)["ok"]
    assert compare(A_log=1 + 0.9 * limit["gdn_grad"])["ok"]
    assert not compare(A_log=1 + 1.1 * limit["gdn_grad"])["ok"]
    assert not compare(w_qkvz=1 + 1.1 * limit["gdn_grad"])["ok"]
    assert not compare(w_qkvz=np.nan)["ok"]
    assert not runner._compare_hybrid({**passed, "ok": False}, "bfloat16",
                                      routed, routed, want, want)["ok"]
    # one layer of three over the limit is enough
    one = {k: v.copy() for k, v in want.items()}
    one["A_log"][2] *= 1 + 1.1 * limit["gdn_grad"]
    assert not runner._compare_hybrid(passed, "bfloat16", routed, routed,
                                      one, want)["ok"]


@pytest.mark.parametrize("seed", [1, 2147483693])
def test_the_fp8_control_reads_worse_than_the_program(seed):
    """The limits of `HYBRID_RTOL` are read at the published widths on the
    chip (PERF.md section 2) and say nothing at the rehearsal shape; what
    holds at every shape is that with the same seed the control (the rule's
    inputs rounded to float8_e4m3, the precision below the cell's bfloat16)
    reads worse than the sound program in the runner's own number."""
    tool = load_module("tools", "hybrid_control")
    sound = tool.reading(CELL, seed, None, rehearse=True)
    control = tool.reading(CELL, seed, "fp8_rule_inputs", rehearse=True)
    through_rule = lambda r: max(r["gdn_grad_by_leaf"]["w_qkvz"])
    assert through_rule(control) > 2 * through_rule(sound), (sound, control)


# ---- the scope readers ----

LAYER = ("jit(step)/loss_and_grad/transpose(jvp(jit(loss_shard)))/while/body/"
         "closed_call/while/body/closed_call/checkpoint/")
FWD = ("jit(step)/loss_and_grad/jvp(jit(loss_shard))/while/body/closed_call/"
       "while/body/closed_call/")
OPS = [
    # (instruction, meta, op_name or None, the part it belongs to)
    ("fusion.1", "fusion", LAYER + "rematted_computation/gdn/"
     "convert_element_type", "gdn"),
    ("fusion.2", "fusion", LAYER + "gdn/checkpoint/rematted_computation/mul",
     "gdn"),
    ("fusion.3", "fusion", FWD + "gdn_rule/closed_call/while/body/"
     "closed_call/checkpoint/triangular_solve", "gdn_rule"),
    ("fusion.4", "fusion", LAYER + "gdn_rule/while/body/closed_call/"
     "checkpoint/rematted_computation/triangular_solve", "gdn_rule"),
    ("fusion.5", "fusion", LAYER + "gdn_rule/while/body/closed_call/"
     "checkpoint/while/body/closed_call/checkpoint/dot_general", "gdn_rule"),
    ("fusion.6", "fusion", LAYER + "rematted_computation/gated_attn/mul",
     "gated_attn"),
    ("flash_fwd.40", "custom-call tpu_custom_call operands=3",
     LAYER + "rematted_computation/flash_fwd", "flash"),
    ("flash_bwd_dq.18", "custom-call tpu_custom_call operands=6",
     LAYER + "flash_bwd_dq", "flash"),
    ("flash_bwd_dkv.18", "custom-call tpu_custom_call operands=6",
     LAYER + "flash_bwd_dkv", "flash"),
    ("fusion.7", "fusion", LAYER + "rematted_computation/moe_route/gather",
     "moe_route"),
    ("sort.21", "sort", "sort", "moe_route"),
    ("fusion.8", "fusion", LAYER + "cond/branch_1_fun/moe_experts/"
     "jit(silu)/add_any", "moe_experts"),
    ("ragged-dot-none.4", "custom-call tpu_custom_call operands=7",
     "ragged-dot-none", "moe_experts"),
    ("fusion.9", "fusion", LAYER + "moe_shared/mul", "moe_shared"),
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
    parts = hybrid_scopes.scope_ns(dev, runs, names)
    assert set(parts) == set(hybrid_scopes.PARTS)
    assert sum(parts.values()) == dev.busy_ns()
    want = dict.fromkeys(hybrid_scopes.PARTS, 0)
    for i, (_, _, _, part) in enumerate(OPS):
        want[part] += 2 * (i + 1) * 1000
    assert parts == want
    # an op outside every run of the step is another program's
    outside = hybrid_scopes.scope_ns(dev, runs[:1], names)
    assert outside["other_programs"] == sum(want.values()) // 2


def test_the_readers_read_the_runners_fields(sizes):
    dev, runs, names = capture()
    parts = hybrid_scopes.scope_ns(dev, runs, names)
    workload = load_json("workloads", CELL + ".json")
    peak = SimpleNamespace(flops_per_s=197e12, hbm_bytes_per_s=819e9)
    m = SimpleNamespace(devices=[dev], scopes=parts, peak=peak, sizes=sizes,
                        workload=workload, mesh={"dp": 1, "tp": 1}, chips=1,
                        tokens_per_s=19000.0,
                        rows_here_per_layer=[10240.0] * 4,
                        rows_here_per_token=0.625, load_max_over_mean=2.0,
                        active_flops_per_token=1.6e9)
    read = lambda name: load_module("layer_metrics", name).read(m)
    ms = lambda *ops: sum(ops) * 1000 / 1e6
    assert read("model.gdn_ms") == pytest.approx(ms(1, 2))
    assert read("model.gdn_rule_ms") == pytest.approx(ms(3, 4, 5))
    assert read("model.gated_attn_ms") == pytest.approx(ms(6))
    assert read("kernels.flash_ms") == pytest.approx(ms(7, 8, 9))
    assert read("model.moe_route_ms") == pytest.approx(ms(10, 11))
    assert read("model.moe_experts_ms") == pytest.approx(ms(12, 13))
    assert read("moe.load_max_over_mean") == 2.0
    assert read("moe.rows_here_per_token") == 0.625
    assert read("train_step.active_mfu_pct") == pytest.approx(
        100 * 1.6e9 * 19000 / 197e12)
    # shares of a roofline: least time over the time taken
    rule = counts.rule_cost(2, 8192, sizes, 2)
    least = 3 * max(rule.flops / 197e12, rule.bytes / 819e9)
    assert read("model.gdn_rule_roofline") == pytest.approx(
        100 * least / (ms(3, 4, 5) / 1e3))
    fwd = counts.gqa_flash_call_cost(2, 8192, sizes, 2, False).flops / 197e12
    bwd = counts.gqa_flash_call_cost(2, 8192, sizes, 2, True).flops / 197e12
    # two forward calls, and two kernels (dq; dk, dv) for each backward
    assert read("kernels.gqa_flash_roofline") == pytest.approx(
        100 * (2 * fwd + 2 * bwd) / (2 * ms(7, 8, 9) / 1e3))
    experts = 4 * max(18 * 10240 * 2048 * 512 / 197e12,
                      expert_products_cost(10240, sizes, 2).bytes / 819e9)
    assert read("model.moe_experts_roofline") == pytest.approx(
        100 * experts / (ms(12, 13) / 1e3))


def test_the_readers_return_nothing_where_there_is_nothing_to_read(sizes):
    """A runner that hands no scope split (the `train` runner), another
    family's split (`train_scopes`: no `gdn` among its parts) or a program
    without the scopes gets None, not an exception."""
    bare = SimpleNamespace(devices=[], peak=None, tokens_per_s=1.0, chips=1,
                           sizes=SimpleNamespace())
    dev, runs, names = capture()
    other = SimpleNamespace(
        devices=[dev], peak=SimpleNamespace(flops_per_s=1.0,
                                            hbm_bytes_per_s=1.0),
        scopes={"mla": 5, "moe_route": 7}, sizes=SimpleNamespace(n_head=32),
        workload={}, mesh={})
    for m in (bare, other):
        for name in ("model.gdn_ms", "model.gdn_rule_ms",
                     "model.gated_attn_ms", "model.gdn_rule_roofline",
                     "kernels.gqa_flash_roofline"):
            assert load_module("layer_metrics", name).read(m) is None
