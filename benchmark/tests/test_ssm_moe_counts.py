"""The ssm_moe family's counts at the published widths
(benchmark/lib/ssm_moe_counts.py) against a hand count, the family file's
reference against the program's at the rehearsal shape, the scope readers on
a small capture made of the real step's `op_name`s
(benchmark/lib/ssm_scopes.py), and the check's controls at the rehearsal
shape (benchmark/tools/ssm_control.py)."""

from types import SimpleNamespace

import numpy as np
import pytest

from benchmark.lib import ssm_moe_counts as counts
from benchmark.lib import ssm_scopes, trace
from benchmark.lib.files import load_json, load_module

CELL = "nemotron-3-super-120b-a12b.train-tp4ep64share-b1-t4096"
CONFIG = "nemotron-3-super-120b-a12b.json"


@pytest.fixture(scope="module")
def sizes():
    family = load_module("families", "ssm_moe")
    return family.sizes_of(load_json("configs", CONFIG))


# ---- the counts, by hand ----

def test_parameters_of_the_share_at_the_published_widths(sizes):
    """ISSUE 63's arithmetic: a Mamba layer 27.4M (in 4096 x 4640, conv 2560
    x 4 + 2560, out 2048 x 4096, gated norm 2048, norm 4096, 96 of A_log / D
    / dt_bias), the attention layer 9.4M, an expert layer 98.6M (router,
    bias, both latent projections and the 5376-wide shared expert whole, 8
    experts of 2 x 1024 x 2688), an eighth of the vocabulary untied 134.2M:
    773.6M."""
    parts = counts.param_counts(sizes)
    d = 4096
    mamba = d * 4640 + 2560 * 4 + 2560 + 2048 * d + 2048 + d + 96
    assert parts["mamba_layer"] == mamba == 27_413_088
    attn = d * 1024 + 2 * d * 128 + 1024 * d + d
    assert parts["attn_layer"] == attn == 9_441_280
    expert = 2 * 1024 * 2688
    assert expert == counts.expert_params(sizes) == 5_505_024
    moe = (d + d * 512 + 512 + 2 * d * 1024 + 2 * d * 5376 + 8 * expert)
    assert parts["moe_layer"] == moe == 98_570_752
    assert parts["moe_layer_uncut"] == moe + 504 * expert
    assert parts["embedding_and_head"] == 2 * 16384 * d == 134_217_728
    assert parts["total"] == (5 * mamba + 5 * moe + attn + 134_217_728
                              + d) == 773_582_304
    assert parts["total"] * 16 / 1e9 == pytest.approx(12.38, abs=0.005)
    assert parts["total"] * 16 / 2 ** 30 == pytest.approx(11.53, abs=0.005)
    assert (sizes.n_layer, sizes.n_mamba_layer, sizes.n_attn_layer,
            sizes.n_moe_layer, sizes.expert_layers) == (11, 5, 1, 5, 5)


def test_the_program_counts_the_same(sizes):
    """The builder's `param_counts` and the leaves `init` makes."""
    import jax
    family = load_module("families", "ssm_moe")
    built = family.build(load_json("configs", CONFIG), {"dp": 1, "tp": 1},
                         "bfloat16")
    cfg = built.model.cfg
    assert cfg.num_params() == counts.param_counts(sizes)["total"]
    made = jax.eval_shape(built.model.init, jax.random.key(0))
    assert sum(x.size for x in jax.tree.leaves(made)) == 773_582_304
    by_part = type(built.model).param_counts(cfg)
    assert by_part["mamba_layers"] == 5 * 27_413_088
    assert by_part["attn_layers"] == 9_441_280
    assert by_part["moe_layers"] == 5 * 98_570_752
    mixer = built.model._mods["mamba"]
    assert (mixer.heads, mixer.groups, mixer.head_offset) == (32, 2, 0)
    assert (cfg.num_heads, cfg.kv_heads) == (8, 1)


def test_every_published_width_stands(sizes):
    """No width is cut: every number of the catalog's row stands under its
    key but the four the file lists under `reduced`; the share of the heads
    is stated beside the published counts and checked against them."""
    import json
    config = load_json("configs", CONFIG)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == config["source"])
    differ = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differ == {"n_routed_experts", "vocab_size",
                      "num_nextn_predict_layers"}
    assert differ | {"num_layers"} == set(config["reduced"])
    assert (sizes.m_head_dim, sizes.m_state, sizes.head_dim, sizes.d_latent,
            sizes.d_expert, sizes.d_shared, sizes.top_k, sizes.n_routed,
            sizes.chunk, sizes.conv) == (64, 128, 128, 1024, 2688, 5376, 22,
                                         512, 128, 4)
    share = config["deployment_share"]
    assert (sizes.m_head * share["tensor_parallel"],
            sizes.m_group * share["tensor_parallel"],
            sizes.n_head * share["tensor_parallel"]) == (128, 8, 32)
    assert share["layers_here"] in config["hybrid_override_pattern"]
    # the gated norm's group is whole on the share
    assert sizes.m_inner // sizes.m_group == 128 * 64 // 8 == 1024
    family = load_module("families", "ssm_moe")
    for key, value in (("mlp_hidden_act", "silu"), ("n_group", 8)):
        with pytest.raises(ValueError, match=key):
            family.build({**config, key: value}, {"dp": 1, "tp": 1},
                         "bfloat16")
    with pytest.raises(ValueError, match="one rank"):
        family.build({**config, "deployment_share": {
            **share, "mamba_heads_here": 64}}, {"dp": 1, "tp": 1}, "bfloat16")


def test_flops_per_token(sizes):
    d, T, rows = 4096, 4096, 5 * 22 * 8 / 512
    mamba = d * 4640 + 2048 * d
    moe = d * 512 + 2 * d * 1024 + 2 * d * 5376
    active = (5 * mamba + (d * 1024 * 2 + 2 * d * 128) + 5 * moe
              + rows * 2 * 1024 * 2688 + 16384 * d)
    assert counts.active_matmul_params(sizes, rows) == pytest.approx(active)
    scan = 2 * 2 * 128 * 128 + 32 * (2 * 128 * 64 + 4 * 64 * 128)
    assert counts.ssd_flops_per_token(sizes) == scan == 1_638_400
    assert counts.train_flops_per_token(sizes, T, rows) == pytest.approx(
        6 * active + 12 * 8 * 128 * T + 3 * 5 * scan)
    assert counts.forward_flops_per_token(sizes, T, rows) == pytest.approx(
        2 * active + 8 * (T + 1) * 256 + 5 * scan)
    # about 3.1 GFLOP a trained token, 1.0 forward
    assert 2.9e9 < counts.train_flops_per_token(sizes, T, rows) < 3.3e9


def test_what_a_layers_recurrence_must_compute_and_move():
    """At a small size, by hand: 2 sequences of 64 tokens, 4 heads of 8 over
    2 groups, a state 16 wide, chunks of 32, bfloat16."""
    s = counts.SsmMoESizes(
        d_model=32, m_head=4, m_head_dim=8, m_state=16, m_group=2, conv=4,
        chunk=32, n_head=2, n_kv_head=1, head_dim=16, d_latent=16,
        d_expert=24, d_shared=40, n_routed=8, n_held=2, top_k=3,
        pattern="EM*", mtp_pattern="", vocab=100)
    fwd = 2 * (2 * 32 * 16) + 4 * (2 * 32 * 8 + 4 * 8 * 16)
    assert counts.ssd_flops_per_token(s) == fwd == 6144
    cost = counts.ssd_cost(2, 64, s, 2)
    assert cost.flops == 3 * 128 * fwd
    x_and_y = 128 * 2 * 32 * 2
    b_and_c = 128 * 2 * 32 * 2
    dt = 128 * 4 * 4
    states = (128 // 32) * 4 * 8 * 16 * 4
    assert cost.bytes == 2 * (x_and_y + b_and_c + dt + states)
    # the latent experts: two products of l x f a row, forward and twice
    # backward; two matrices an expert and the l-wide rows, three passes
    experts = counts.latent_expert_products_cost(10.0, s, 2)
    assert experts.flops == 3 * 4 * 10 * 16 * 24 == 12 * 10 * 16 * 24
    assert experts.bytes == 3 * (2 * 2 * 16 * 24 * 2 + 2 * 10 * 16 * 2)


def test_the_family_files_reference_is_the_programs():
    """The benchmark's own copy and the program's oracle compute the same
    loss and counts on the rehearsal shape (the program's is held to the
    model leaf by leaf in tests/test_ssm_moe.py), with and without the
    module."""
    import jax
    from benchmark.lib.cells import load_cell
    from distributed_pytorch_from_scratch_tpu.models.vanilla_ssm_moe import (
        vanilla_loss)
    workload, config = load_cell(CELL, rehearse=True)
    for mtp in (0, 1):
        built = load_module("families", "ssm_moe").build(
            {**config, "num_nextn_predict_layers": mtp}, workload["mesh"],
            "float32")
        params = built.model.init(jax.random.key(1))
        assert ("mtp" in params) == bool(mtp)
        # the second rank's heads: A_log starts at log(5)
        np.testing.assert_allclose(
            params["mamba_layers_0"]["mamba"]["A_log"][0, 0],
            np.log([5.0, 6.0, 7.0, 8.0]), rtol=1e-6)
        rng = np.random.default_rng(0)
        ids = rng.integers(0, built.sizes.vocab, (2, 71)).astype(np.int32)
        pos = np.tile(np.arange(70, dtype=np.int32), (2, 1))
        with jax.default_matmul_precision("highest"):
            ours, routed = built.reference_routed(params, ids[:, :-1],
                                                  ids[:, 1:], pos)
            theirs = vanilla_loss(built.model.cfg, params, ids[:, :-1],
                                  ids[:, 1:], pos)
        assert float(ours) == pytest.approx(float(theirs), rel=1e-6)
        assert routed.shape == (2 + mtp, 16)
        np.testing.assert_array_equal(routed.sum(-1), 2 * 70 * 3)


# ---- the scope readers ----

WHILE = "jit(step)/loss_and_grad/transpose(jvp(jit(loss_shard)))/while/body/"
FWD = "jit(step)/loss_and_grad/jvp(jit(loss_shard))/"
REMAT = WHILE + "closed_call/checkpoint/rematted_computation/"
OPS = [
    # (instruction, meta, op_name or None, the part, the mixer's part)
    ("fusion.1", "fusion", REMAT + "mamba/in_proj/dot_general", "mamba",
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
    ("fusion.9", "fusion", REMAT + "moe_latent/down/dot_general",
     "moe_latent", None),
    ("fusion.10", "fusion", REMAT + "moe_latent/up/dot_general",
     "moe_latent", None),
    ("fusion.11", "fusion", REMAT + "moe_route/top_k", "moe_route", None),
    ("sort.21", "sort", "sort", "moe_route", None),
    ("ragged-dot-none.4", "custom-call tpu_custom_call operands=7",
     "ragged-dot-none", "moe_experts", None),
    ("fusion.12", "fusion", WHILE + "closed_call/checkpoint/moe_shared/mul",
     "moe_shared", None),
    ("flash_fwd.40", "custom-call tpu_custom_call operands=3",
     REMAT + "flash_fwd", "flash", None),
    ("flash_bwd.2", "custom-call tpu_custom_call operands=6",
     WHILE + "closed_call/checkpoint/flash_bwd", "flash", None),
    # innermost wins: the module's attention layer is `gqa_attn`
    ("fusion.13", "fusion", FWD + "mtp/gqa_attn/reshape", "gqa_attn", None),
    ("fusion.14", "fusion", FWD + "mtp/dot_general", "mtp", None),
    ("fusion.15", "fusion", FWD + "head_loss/convert_element_type",
     "head_loss", None),
    ("fusion.16", "fusion", "jit(step)/optimizer/mul", "optimizer", None),
    ("fusion.17", "fusion", WHILE + "closed_call/checkpoint/mul", "rest",
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
    parts = ssm_scopes.scope_ns(dev, runs, names)
    assert set(parts) == set(ssm_scopes.PARTS)
    assert sum(parts.values()) == dev.busy_ns()
    want = dict.fromkeys(ssm_scopes.PARTS, 0)
    inner = dict.fromkeys(ssm_scopes.MAMBA_PARTS, 0)
    for i, (_, _, _, part, mamba_part) in enumerate(OPS):
        want[part] += 2 * (i + 1) * 1000
        if mamba_part:
            inner[mamba_part] += 2 * (i + 1) * 1000
    assert parts == want
    # the mixer's time by part is a split of it, not parts beside it
    got = ssm_scopes.mamba_parts_ns(dev, runs, names)
    assert got == inner and sum(got.values()) == parts["mamba"]
    outside = ssm_scopes.scope_ns(dev, runs[:1], names)
    assert outside["other_programs"] == sum(want.values()) // 2


def test_the_readers_read_the_runners_fields(sizes):
    dev, runs, names = capture()
    parts = ssm_scopes.scope_ns(dev, runs, names)
    workload = load_json("workloads", CELL + ".json")
    peak = SimpleNamespace(flops_per_s=197e12, hbm_bytes_per_s=819e9)
    cost = counts.ssd_cost(1, 4096, sizes, 2)
    experts = [counts.latent_expert_products_cost(176.0, sizes, 2)] * 5
    m = SimpleNamespace(devices=[dev], scopes=parts, peak=peak, sizes=sizes,
                        mamba_parts=ssm_scopes.mamba_parts_ns(dev, runs,
                                                              names),
                        workload=workload, mesh={"dp": 1, "tp": 1}, chips=1,
                        tokens_per_s=20000.0,
                        rows_here_per_layer=[176.0] * 5,
                        rows_here_per_token=0.34, load_max_over_mean=2.0,
                        active_flops_per_token=3.1e9, ssm_decay_min=-41.5,
                        ssd_cost=cost, latent_expert_costs=experts)
    read = lambda name: load_module("layer_metrics", name).read(m)
    assert read("model.mamba_ms") == pytest.approx(sum(range(1, 8)) * 1e-3)
    ssd_ms = (3 + 4) * 1e-3
    assert read("model.ssd_ms") == pytest.approx(ssd_ms)
    # memory binds: 143.7 MB over 819 GB/s against 20.1 GFLOP over 197T
    assert cost.bytes / 819e9 > cost.flops / 197e12
    assert read("model.ssd_roofline") == pytest.approx(
        100 * 5 * (cost.bytes / 819e9) / (ssd_ms / 1e3))
    experts_ms = 13 * 1e-3
    assert read("model.latent_experts_roofline") == pytest.approx(
        100 * 5 * (experts[0].bytes / 819e9) / (experts_ms / 1e3))
    assert read("ssm.decay_min") == -41.5
    # and the readers written for the other runners take this `measured`
    assert read("model.gqa_attn_ms") == pytest.approx((8 + 17) * 1e-3)
    assert read("model.moe_route_ms") == pytest.approx((11 + 12) * 1e-3)
    assert read("model.moe_experts_ms") == pytest.approx(experts_ms)
    assert read("kernels.flash_ms") == pytest.approx((15 + 16) * 1e-3)
    assert read("kernels.flash_fwd_per_bwd") == 1.0
    assert read("kernels.gqa_flash_roofline") > 0
    assert read("moe.load_max_over_mean") == 2.0
    assert read("moe.rows_here_per_token") == 0.34
    assert read("train_step.active_mfu_pct") == pytest.approx(
        100 * 3.1e9 * 20000 / 197e12)
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
        scopes=scopes.scope_ns(dev, runs, names))     # no `mamba` in it
    for m in (empty, other):
        for name in ("model.mamba_ms", "model.ssd_ms", "model.ssd_roofline",
                     "model.latent_experts_roofline", "ssm.decay_min"):
            assert load_module("layer_metrics", name).read(m) is None


# ---- the check and its controls, at the rehearsal shape ----

def test_a_reading_over_the_limit_is_not_correct():
    runner = load_module("runners", "train_ssm_moe")
    limit = runner.SSM_RTOL["bfloat16"]["ssm_grad"]
    assert 0 < limit < 1
    passed = {"ok": True, "rel_err": {}, "rtol": {}}
    rng = np.random.default_rng(0)
    want = {"mamba_layers_0/A_log": rng.normal(size=(5, 32)),
            "mamba_layers_0/w_in": rng.normal(size=(5, 600))}
    compare = lambda held=True, **off: runner._compare_ssm(
        passed, "bfloat16",
        {k: v * off.get(k.split("/")[1], 1.0) for k, v in want.items()},
        want, held=held)
    assert compare()["ok"] and compare()["rel_err"]["ssm_grad"] == 0.0
    assert compare(A_log=1 + 0.9 * limit)["ok"]
    assert not compare(A_log=1 + 1.1 * limit)["ok"]
    assert not compare(w_in=1 + 1.1 * limit)["ok"]
    assert not compare(w_in=np.nan)["ok"]
    # the rehearsal records the reading and holds nothing to it
    assert compare(held=False, A_log=3.0)["ok"]
    assert not runner._compare_ssm({**passed, "ok": False}, "bfloat16", want,
                                   want)["ok"]


@pytest.fixture(scope="module")
def control():
    return load_module("tools", "ssm_control")


@pytest.fixture(scope="module")
def sound(control):
    return control.reading(CELL, 5, rehearse=True)


def test_one_group_for_every_head_reads_worse_than_the_program(control,
                                                                sound):
    got = control.reading(CELL, 5, "one_group", rehearse=True)
    assert sound["control"] is None and got["control"] == "one_group"
    assert sound["rel_err"]["ssm_grad"] < 0.1
    assert got["rel_err"]["ssm_grad"] > 5 * sound["rel_err"]["ssm_grad"]


def test_relu_experts_fail_the_expert_leaves(control, sound):
    got = control.reading(CELL, 5, "relu_experts", rehearse=True)
    assert sound["ok"] and not got["ok"]
    limit = got["rtol"]["moe_grad"]
    assert got["rel_err"]["moe_grad"] > limit > sound["rel_err"]["moe_grad"]


def test_the_bfloat16_control_patches_the_recurrence_and_puts_it_back(
        control):
    """At the rehearsal shape a chunk's sums stay small and bfloat16 reads
    as the program (tests/test_ssm_moe.py holds the state to float32 where
    they do not); the control must still have run ANOTHER program and left
    the sound one behind it."""
    from distributed_pytorch_from_scratch_tpu.ops import ssd as op
    from distributed_pytorch_from_scratch_tpu.parallel import mamba
    got = control.reading(CELL, 5, "bf16_state", rehearse=True)
    assert got["control"] == "bf16_state"
    assert np.isfinite(got["rel_err"]["ssm_grad"])
    assert mamba.ssd is op.ssd
    (_, name, patched), = control.CONTROLS["bf16_state"]()
    assert name == "ssd" and patched.keywords["state_dtype"].__name__ == (
        "bfloat16")
