"""The kda_mla_moe family's counts at the published widths
(benchmark/lib/kda_mla_moe_counts.py) against a hand count, the family
file's reference against the program's at the rehearsal shape, the scope
readers on a small capture made of the real step's `op_name`s
(benchmark/lib/kda_scopes.py), and the check's controls at the rehearsal
shape (benchmark/tools/kda_control.py)."""

from types import SimpleNamespace

import numpy as np
import pytest

from benchmark.lib import kda_mla_moe_counts as counts
from benchmark.lib import kda_scopes, trace
from benchmark.lib.files import load_json, load_module

CELL = "ling-3-flash.train-ep64share-b1-t4096"
CONFIG = "ling-3-flash.json"


@pytest.fixture(scope="module")
def sizes():
    family = load_module("families", "kda_mla_moe")
    return family.sizes_of(load_json("configs", CONFIG))


# ---- the counts, by hand ----

def test_parameters_of_the_share_at_the_published_widths(sizes):
    """ISSUE 59's arithmetic with the norms, the biases and the small
    leaves counted: the delta mixer 63.05M, the latent mixer 31.97M, the
    dense delta layer 110.2M, a delta expert layer 117.5M, the latent expert
    layer 86.4M, an eighth of the vocabulary untied 100.6M: 767.0M."""
    parts = counts.param_counts(sizes)
    d, H = 2560, 32
    kda = (3 * d * 4096 + d * 4096 + d * 4096 + 4096 * d      # q k v, f, g, o
           + d * H + 3 * 4096 * 4 + H + 4096 + 128)
    assert parts["kda_mixer"] == kda == 63_049_888
    mla = (d * H * 192 + d * H + d * 576 + 512 + 512 * H * 256 + 4096 * d)
    assert parts["mla_mixer"] == mla == 31_965_696
    expert = 3 * d * 768
    assert expert == 5_898_240
    ffn = d * 512 + 512 + 9 * expert
    assert parts["ffn"] == ffn == 54_395_392
    assert parts["ffn_uncut"] == d * 512 + 512 + 513 * expert
    assert parts["dense_layer"] == kda + 2 * d + 3 * d * 6144 == 110_240_928
    assert parts["kda_expert_layer"] == kda + 2 * d + ffn == 117_450_400
    assert parts["mla_expert_layer"] == mla + 2 * d + ffn == 86_366_208
    assert parts["mtp_module"] == 86_366_208 + 2 * d * d + 3 * d
    assert round(parts["mtp_module"] / 1e6, 1) == 99.5
    assert parts["embedding_and_head"] == 2 * 19648 * d == 100_597_760
    assert parts["total"] == (110_240_928 + 4 * 117_450_400 + 86_366_208
                              + 100_597_760 + d) == 767_009_056
    assert parts["total"] * 16 / 1e9 == pytest.approx(12.27, abs=0.005)
    assert 11.8 < parts["total"] * 16 / 1e9 < 12.8
    # six layers: five delta to one latent, one of them dense
    assert (sizes.n_layer, sizes.kda_layers, sizes.mla_layers,
            sizes.n_dense_layer, sizes.expert_layers) == (6, 5, 1, 1, 5)


def test_the_program_counts_the_same(sizes):
    config = load_json("configs", CONFIG)
    built = load_module("families", "kda_mla_moe").build(
        config, {"dp": 1, "tp": 1}, "bfloat16")
    cfg = built.model.cfg
    assert cfg.num_params() == counts.param_counts(sizes)["total"]
    km = cfg.kda_mla_moe
    assert cfg.num_experts == 512 and km.experts_held == 8
    assert cfg.vocab_size == 19648 and cfg.num_layers == 6
    # the published two dense layers counted once; no module here
    assert km.first_k_dense_replace == 1 and km.num_nextn_predict_layers == 0
    assert (km.n_group, km.topk_group, km.layer_group_size) == (8, 4, 6)
    assert km.q_lora_rank is None and km.kda_lower_bound == -5.0
    assert built.model._pattern == ("dense_layers", "lead_kda_layers",
                                    "lead_mla_layers")
    assert [(k, n) for k, n, _ in built.model._segments] == [
        ("dense_layers", 1), ("lead_kda_layers", 4), ("lead_mla_layers", 1)]
    # the program's own FLOPs count differs only by its convention
    from distributed_pytorch_from_scratch_tpu.ops.delta_rule import (
        CHUNK, rule_flops_per_token)
    assert CHUNK == counts.RULE_CHUNK
    assert 32 * rule_flops_per_token(128, 128) == \
        counts.rule_flops_per_token(sizes)


def test_every_published_width_stands(sizes):
    """The configuration file holds every number of the catalog's row; what
    differs is in `reduced`, with the published value beside it."""
    import json
    import os
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    config = load_json("configs", CONFIG)
    row = next(r for r in map(json.loads, open(catalog))
               if r["name"] == "Ling-3.0-flash")
    assert config["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if config.get(k) != v}
    assert changed == {"num_experts", "vocab_size",
                       "num_nextn_predict_layers"}
    assert set(config["reduced"]) == changed | {"num_layers"}
    for key in changed:
        assert config["published"][key] == row["config"][key]
    assert config["published"]["num_hidden_layers"] == 42
    # no width is cut
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "head_dim", "kv_lora_rank", "qk_head_dim", "v_head_dim",
                "num_experts_per_tok", "num_attention_heads"):
        assert key not in config["reduced"]


def test_a_kept_layers_swiglu_limit_must_be_zero():
    config = load_json("configs", CONFIG)
    build = load_module("families", "kda_mla_moe").build
    limits = list(config["expert_swiglu_limit_list"])
    limits[3] = 4
    with pytest.raises(ValueError, match="expert_swiglu_limit_list"):
        build({**config, "expert_swiglu_limit_list": limits},
              {"dp": 1, "tp": 1}, "bfloat16")
    with pytest.raises(ValueError, match="kda_safe_gate"):
        build({**config, "kda_safe_gate": False}, {"dp": 1, "tp": 1},
              "bfloat16")
    with pytest.raises(ValueError, match="q_lora_rank"):
        build({**config, "q_lora_rank": 1536}, {"dp": 1, "tp": 1},
              "bfloat16")


def test_flops_per_token(sizes):
    """ISSUE 59's reckoning: about 1.05 GFLOP a token forward, 64% of it in
    the delta mixers."""
    rows = 5 * 8 * 8 / 512                # 0.125 a token and expert layer
    kda = 2 * counts.kda_matmul_params(sizes)
    assert round(kda / 1e6) == 126
    rule = counts.rule_flops_per_token(sizes)
    assert rule == 32 * (4 * 64 * 128 + 64 * 256 + 6 * 128 * 128
                         + 2 * 64 * 128)
    assert round(rule / 1e6, 1) == 5.2
    mla = 2 * counts.mla_matmul_params(sizes)
    assert round(mla / 1e6) == 64
    forward = counts.forward_flops_per_token(sizes, 4096, rows)
    assert forward == pytest.approx(
        5 * (kda + rule) + mla + 32 * 4097 * 320 + 2 * 3 * 2560 * 6144
        + 5 * (2 * 2560 * 512 + 2 * 5_898_240) + rows * 2 * 5_898_240
        + 2 * 19648 * 2560)
    assert 1.0e9 < forward < 1.1e9
    assert 0.60 < 5 * (kda + rule) / forward < 0.66
    train = counts.train_flops_per_token(sizes, 4096, rows)
    assert train == pytest.approx(
        6 * counts.active_matmul_params(sizes, rows)
        + 6 * 32 * 320 * 4096 + 3 * 5 * rule)
    assert 3.1e9 < train < 3.4e9


def test_what_a_layers_rule_must_compute_and_move(sizes):
    cost = counts.rule_cost(1, 4096, sizes, 2)
    rows = 4096 * 32
    assert cost.flops == 3 * 4096 * counts.rule_flops_per_token(sizes)
    qkvo = rows * 4 * 128 * 2
    decay = rows * 129 * 4                 # g a channel, beta a head
    states = rows / 64 * 128 * 128 * 4
    assert cost.bytes == 2 * (qkvo + decay + states)
    # the float32 decay is a fifth of what must move, half of what q, k, v
    # and o are together: 65 times the scalar rule's 2 floats a head and
    # token
    assert 2 * decay / cost.bytes == pytest.approx(0.2, abs=0.01)
    # bound by bandwidth on a v5e: 0.82 ms a layer against 0.33 of compute
    assert cost.bytes / 819e9 == pytest.approx(0.82e-3, rel=0.02)
    assert cost.flops / 197e12 == pytest.approx(0.33e-3, rel=0.02)


def test_the_family_files_reference_is_the_programs():
    """The benchmark's own copy and the program's oracle compute the same
    loss on the rehearsal shape (the program's is held to the model leaf by
    leaf in tests/test_kda_mla_moe.py), with and without the module."""
    import jax
    from benchmark.lib.cells import load_cell
    from distributed_pytorch_from_scratch_tpu.models.vanilla_kda_mla_moe import (
        vanilla_loss)
    workload, config = load_cell(CELL, rehearse=True)
    for mtp in (0, 1):
        built = load_module("families", "kda_mla_moe").build(
            {**config, "num_nextn_predict_layers": mtp,
             "mtp_loss_scaling_factor": 0.3 * mtp}, workload["mesh"],
            "float32")
        params = built.model.init(jax.random.key(1))
        assert ("mtp" in params) == bool(mtp)
        rng = np.random.default_rng(0)
        ids = rng.integers(0, built.sizes.vocab, (2, 81)).astype(np.int32)
        pos = np.tile(np.arange(80, dtype=np.int32), (2, 1))
        with jax.default_matmul_precision("highest"):
            ours, routed = built.reference_routed(params, ids[:, :-1],
                                                  ids[:, 1:], pos)
            theirs = vanilla_loss(built.model.cfg, params, ids[:, :-1],
                                  ids[:, 1:], pos)
        assert float(ours) == pytest.approx(float(theirs), rel=1e-6)
        # every token takes top_k experts in each expert layer, all inside
        # the one group of two it keeps
        assert routed.shape == (2 + mtp, 16)
        np.testing.assert_array_equal(routed.sum(-1), 2 * 80 * 2)


# ---- the scope readers ----

WHILE = "jit(step)/loss_and_grad/transpose(jvp(jit(loss_shard)))/while/body/"
FWD = "jit(step)/loss_and_grad/jvp(jit(loss_shard))/"
OPS = [
    # (instruction, meta, op_name or None, the part, the delta scopes' part)
    ("fusion.1", "fusion", WHILE + "closed_call/checkpoint/"
     "rematted_computation/kda/checkpoint/dot_general", "kda", "kda/other"),
    ("fusion.2", "fusion", WHILE + "closed_call/checkpoint/"
     "rematted_computation/kda/checkpoint/gate/logistic", "kda", "kda/gate"),
    ("fusion.3", "fusion", WHILE + "closed_call/checkpoint/kda_rule/while/"
     "body/checkpoint/operands/exp", "kda_rule", "kda_rule/operands"),
    ("fusion.4", "fusion", WHILE + "closed_call/checkpoint/kda_rule/while/"
     "body/checkpoint/walk/while/body/checkpoint/dot_general", "kda_rule",
     "kda_rule/walk"),
    ("fusion.5", "fusion", WHILE + "closed_call/checkpoint/kda_rule/while/"
     "body/dynamic_slice", "kda_rule", "kda_rule/other"),
    # the latent layer's gate is `mla`'s; its inner scope is no delta part
    ("fusion.6", "fusion", WHILE + "closed_call/checkpoint/"
     "rematted_computation/mla/gate/dot_general", "mla", None),
    ("fusion.7", "fusion", FWD + "while/body/closed_call/dense_ffn/"
     "dot_general", "dense_ffn", None),
    ("fusion.8", "fusion", WHILE + "closed_call/checkpoint/"
     "rematted_computation/moe_route/groups/top_k", "moe_route", None),
    ("sort.21", "sort", "sort", "moe_route", None),
    ("ragged-dot-none.4", "custom-call tpu_custom_call operands=7",
     "ragged-dot-none", "moe_experts", None),
    ("fusion.9", "fusion", WHILE + "closed_call/checkpoint/moe_shared/mul",
     "moe_shared", None),
    ("flash_fwd.40", "custom-call tpu_custom_call operands=3",
     WHILE + "closed_call/checkpoint/rematted_computation/flash_fwd",
     "flash", None),
    ("flash_bwd.2", "custom-call tpu_custom_call operands=6",
     WHILE + "closed_call/checkpoint/flash_bwd", "flash", None),
    # innermost wins: the module's latent layer is `mla`, its head `mtp`
    ("fusion.10", "fusion", FWD + "mtp/mla/reshape", "mla", None),
    ("fusion.11", "fusion", FWD + "mtp/dot_general", "mtp", None),
    ("fusion.12", "fusion", FWD + "head_loss/convert_element_type",
     "head_loss", None),
    ("fusion.13", "fusion", "jit(step)/optimizer/mul", "optimizer", None),
    ("fusion.14", "fusion", WHILE + "closed_call/checkpoint/mul", "rest",
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
    parts = kda_scopes.scope_ns(dev, runs, names)
    assert set(parts) == set(kda_scopes.PARTS)
    assert sum(parts.values()) == dev.busy_ns()
    want = dict.fromkeys(kda_scopes.PARTS, 0)
    inner = dict.fromkeys(kda_scopes.KDA_PARTS, 0)
    for i, (_, _, _, part, delta_part) in enumerate(OPS):
        want[part] += 2 * (i + 1) * 1000
        if delta_part:
            inner[delta_part] += 2 * (i + 1) * 1000
    assert parts == want
    # the delta scopes' time by part is a split of them, not parts beside
    got = kda_scopes.kda_parts_ns(dev, runs, names)
    assert got == inner
    assert sum(got.values()) == parts["kda"] + parts["kda_rule"]
    # an op outside every run of the step is another program's
    outside = kda_scopes.scope_ns(dev, runs[:1], names)
    assert outside["other_programs"] == sum(want.values()) // 2
    # the list holds `lib/scopes.py`'s: its readers read this split
    from benchmark.lib import scopes
    assert set(scopes.SCOPES) < set(kda_scopes.SCOPES)


def test_the_readers_read_the_runners_fields(sizes):
    dev, runs, names = capture()
    parts = kda_scopes.scope_ns(dev, runs, names)
    workload = load_json("workloads", CELL + ".json")
    peak = SimpleNamespace(flops_per_s=197e12, hbm_bytes_per_s=819e9)
    cost = counts.rule_cost(1, 4096, sizes, 2)
    m = SimpleNamespace(devices=[dev], scopes=parts, peak=peak, sizes=sizes,
                        workload=workload, mesh={"dp": 1, "tp": 1}, chips=1,
                        tokens_per_s=10000.0,
                        rows_here_per_layer=[512.0] * 5,
                        rows_here_per_token=0.125, load_max_over_mean=2.0,
                        active_flops_per_token=3.2e9, kda_g_min=-1.25,
                        kda_rule_cost=cost)
    read = lambda name: load_module("layer_metrics", name).read(m)
    assert read("model.kda_ms") == pytest.approx((1 + 2) * 1000 / 1e6)
    rule_ms = (3 + 4 + 5) * 1000 / 1e6
    assert read("model.kda_rule_ms") == pytest.approx(rule_ms)
    assert read("model.kda_rule_roofline") == pytest.approx(
        100 * 5 * (cost.bytes / 819e9) / (rule_ms / 1e3))
    assert read("kda.g_min") == -1.25
    # and the readers written for the other runners take this `measured`
    assert read("model.mla_ms") == pytest.approx((6 + 14) * 1000 / 1e6)
    assert read("model.dense_ffn_ms") == pytest.approx(7 * 1000 / 1e6)
    assert read("model.moe_route_ms") == pytest.approx((8 + 9) * 1000 / 1e6)
    assert read("model.moe_experts_ms") == pytest.approx(10 * 1000 / 1e6)
    assert read("kernels.flash_ms") == pytest.approx((12 + 13) * 1e-3)
    assert read("moe.load_max_over_mean") == 2.0
    assert read("moe.rows_here_per_token") == 0.125
    assert read("train_step.active_mfu_pct") == pytest.approx(
        100 * 3.2e9 * 10000 / 197e12)
    assert read("model.moe_experts_roofline") > 0
    assert read("kernels.mla_flash_roofline") > 0
    assert read("model.xla_ops_ms") > 0 and read("device.step_ms") > 0


def test_the_new_readers_return_nothing_where_there_is_nothing_to_read():
    """A program without the family (the parent's), a runner that hands no
    scope split or another family's, an untraced run: None, not an
    exception."""
    empty = SimpleNamespace(devices=[], peak=None, tokens_per_s=1.0, chips=1)
    dev, runs, names = capture()
    from benchmark.lib import scopes
    other = SimpleNamespace(devices=[dev], peak=SimpleNamespace(
        flops_per_s=197e12, hbm_bytes_per_s=819e9),
        scopes=scopes.scope_ns(dev, runs, names))     # no `kda` in it
    for m in (empty, other):
        for name in ("model.kda_ms", "model.kda_rule_ms",
                     "model.kda_rule_roofline", "kda.g_min"):
            assert load_module("layer_metrics", name).read(m) is None


# ---- the check and its controls, at the rehearsal shape ----

def test_a_reading_over_the_limit_is_not_correct():
    runner = load_module("runners", "train_kda")
    limit = runner.KDA_RTOL["bfloat16"]["kda_grad"]
    passed = {"ok": True, "rel_err": {}, "rtol": {}}
    rng = np.random.default_rng(0)
    want = {"dense_layers/w_f": rng.normal(size=(1, 96)),
            "lead_kda_layers/w_f": rng.normal(size=(4, 96)),
            "lead_kda_layers/A_log": rng.normal(size=(4, 32))}
    compare = lambda held=True, **off: runner._compare_kda(
        passed, "bfloat16",
        {k: v * off.get(k.replace("/", "_"), 1.0) for k, v in want.items()},
        want, held=held)
    assert compare()["ok"] and compare()["rel_err"]["kda_grad"] == 0.0
    assert compare(lead_kda_layers_A_log=1 + 0.9 * limit)["ok"]
    bad = compare(lead_kda_layers_A_log=1 + 1.1 * limit)
    assert not bad["ok"] and bad["rtol"]["kda_grad"] == limit
    assert bad["rel_err"]["kda_grad"] == pytest.approx(1.1 * limit)
    assert len(bad["kda_grad_by_leaf"]["lead_kda_layers/A_log"]) == 4
    assert not compare(dense_layers_w_f=np.nan)["ok"]
    # at the rehearsal shape the reading is logged and decides nothing
    assert compare(held=False, lead_kda_layers_A_log=2.0)["ok"]
    assert not runner._compare_kda({**passed, "ok": False}, "bfloat16", want,
                                   want)["ok"]
    # the tree's delta leaves, a layer at a time; a period's layers in order
    leaf = lambda *shape: {name: ({"scale": np.zeros(shape + (4,))}
                                  if name == "o_norm"
                                  else np.zeros(shape + (3, 2)))
                           for name in runner.KDA_LEAVES}
    named = runner._kda_named({
        "dense_layers": {"kda": leaf(1), "norm1": {}},
        "kda_layers": {"kda": leaf(2, 5)},
        "lead_mla_layers": {"mla": {}}, "norm": {}})
    assert named["dense_layers/w_f"].shape == (1, 6)
    assert named["kda_layers/A_log"].shape == (10, 6)
    assert named["kda_layers/o_norm"].shape == (10, 4)
    assert len(named) == 2 * len(runner.KDA_LEAVES)


def test_a_scalar_decay_reads_worse_than_the_program():
    """The limits are read at the published widths on the chip (PERF.md
    section 2) and say nothing at this shape; what holds at every shape is
    that with the same seed the control that gives a head ONE decay reads
    worse than the sound program in the runner's own numbers, on the leaves
    whose gradient is the channels' difference, and that the control tool
    patches what it says and puts it back."""
    tool = load_module("tools", "kda_control")
    sound = tool.reading(CELL, 1, None, rehearse=True)
    control = tool.reading(CELL, 1, "scalar_decay", rehearse=True)
    assert control["control"] == "scalar_decay" and sound["control"] is None
    assert control["rel_err"]["kda_grad"] > 3 * sound["rel_err"]["kda_grad"]
    worst = lambda r, leaf: max(max(v) for k, v in
                                r["kda_grad_by_leaf"].items()
                                if k.endswith("/" + leaf))
    assert worst(control, "dt_bias") > 5 * worst(sound, "dt_bias")
    again = tool.reading(CELL, 1, None, rehearse=True)
    assert again["rel_err"] == sound["rel_err"]


def test_the_bfloat16_control_patches_the_rule_and_puts_it_back():
    """At this shape bfloat16's rounding of the compute drowns the
    control's (the limit is read on the chip: PERF.md section 2); what holds
    here is that the control runs the rule's own text with its decay, its
    operands and its state rounded, reads another number than the sound
    program, and leaves the program as it found it."""
    from distributed_pytorch_from_scratch_tpu.ops import delta_rule
    tool = load_module("tools", "kda_control")
    before = (delta_rule._running_decay, delta_rule._channel_chunk_operands,
              delta_rule._walk_chunks)
    sound = tool.reading(CELL, 2147483693, None, rehearse=True)
    control = tool.reading(CELL, 2147483693, "bf16_decay_and_state",
                           rehearse=True)
    assert control["rel_err"]["kda_grad"] != sound["rel_err"]["kda_grad"]
    assert np.isfinite(control["rel_err"]["kda_grad"])
    assert before == (delta_rule._running_decay,
                      delta_rule._channel_chunk_operands,
                      delta_rule._walk_chunks)


def test_the_rules_inputs_in_float8_read_worse_than_the_program():
    tool = load_module("tools", "kda_control")
    sound = tool.reading(CELL, 5, None, rehearse=True)
    control = tool.reading(CELL, 5, "fp8_rule_inputs", rehearse=True)
    assert control["rel_err"]["kda_grad"] > 5 * sound["rel_err"]["kda_grad"]


def test_a_selection_without_groups_moves_the_pairs():
    tool = load_module("tools", "kda_control")
    limit = load_module("runners", "train_scopes").MOE_RTOL["bfloat16"][
        "routed_moved"]
    control = tool.reading(CELL, 1, "no_groups", rehearse=True)
    assert control["rel_err"]["routed_moved"] > 10 * limit
    assert not control["ok"]
