"""The mla_moe family's counts at the published widths
(benchmark/lib/mla_moe_counts.py), the family file's reference against the
program's at a tiny size, and the scope readers on a small capture made of
the real step's instruction names and `op_name`s."""

from types import SimpleNamespace

import pytest

from benchmark.lib import mla_moe_counts as counts
from benchmark.lib import scopes, trace
from benchmark.lib.files import load_json, load_module

CELL = "joyai-llm-flash.train-ep16share-b4-t4096"


@pytest.fixture(scope="module")
def sizes():
    family = load_module("families", "mla_moe")
    return family.sizes_of(load_json("configs", "joyai-llm-flash.json"))


def test_parameters_of_the_share_at_the_published_widths(sizes):
    parts = counts.param_counts(sizes)
    assert round(parts["attention"] / 1e6, 2) == 26.35
    assert round(parts["expert_layer"] / 1e6, 1) == 107.1
    assert round(parts["dense_layer"] / 1e6, 1) == 70.4
    assert round(parts["mtp_module"] / 1e6, 1) == 115.5
    assert round(parts["embedding_and_head"] / 1e6, 1) == 66.2
    assert parts["total"] == 680_441_088                 # 680.4M
    assert parts["total"] * 16 / 1e9 == pytest.approx(10.9, abs=0.02)


def test_the_program_counts_the_same(sizes):
    family = load_module("families", "mla_moe")
    built = family.build(load_json("configs", "joyai-llm-flash.json"),
                         {"dp": 1, "tp": 1}, "bfloat16")
    cfg = built.model.cfg
    assert cfg.num_params() == counts.param_counts(sizes)["total"]
    assert cfg.num_experts == 256 and cfg.latent_moe.experts_held == 16
    assert cfg.vocab_size == 16160 and cfg.num_layers == 5


def test_flops_per_token(sizes):
    """Forward MFLOP a token, as ISSUE 33 counts them: latent projections
    6 x 52.7, dense MLP 88, shared experts and routers 52, routed experts
    here 5 x 4.7 at 0.5 rows a token and layer, two heads 132, the
    module's projection 17; attention at the full T^2."""
    M = 1e6
    assert 2 * counts.attention_matmul_params(sizes) / M == \
        pytest.approx(52.7, abs=0.05)
    assert 2 * counts.expert_params(sizes) / M == pytest.approx(9.4, abs=0.05)
    uniform = sizes.expert_layers * sizes.top_k * sizes.n_held / sizes.n_routed
    assert uniform == 2.5
    matmuls = 2 * counts.active_matmul_params(sizes, uniform) / M
    assert matmuls == pytest.approx(316.2 + 88.1 + 52.4 + 23.6 + 132.4
                                    + 16.8, abs=1.0)
    full = counts.train_flops_per_token(sizes, 4096, uniform)
    attention = 6 * 6 * 32 * (192 + 128) * 4096
    assert full == pytest.approx(3 * matmuls * M + attention)
    assert 3.3e9 < full < 3.5e9
    # more rows computed here, more FLOPs: the counter is in the count
    assert counts.train_flops_per_token(sizes, 4096, 5.0) - full == \
        pytest.approx(6 * 2.5 * counts.expert_params(sizes))


def test_flash_cost_at_two_widths():
    fwd = counts.flash_call_cost(128, 4096, 192, 128, 2, backward=False)
    bwd = counts.flash_call_cost(128, 4096, 192, 128, 2, backward=True)
    entries = 128 * 4096 * 4097 / 2
    assert fwd.flops == 2 * (192 + 128) * entries
    assert bwd.flops == (3 * 2 * 192 + 2 * 2 * 128) * entries
    assert fwd.bytes == 128 * 4096 * (2 * 192 * 2 + 2 * 128 * 2 + 4)
    # at equal widths it is flops.py's count
    from benchmark.lib.flops import flash_call_cost
    for backward in (False, True):
        assert counts.flash_call_cost(192, 1024, 64, 64, 2, backward) == \
            flash_call_cost(192, 1024, 64, 2, backward)


def test_expert_products_cost(sizes):
    cost = counts.expert_products_cost(8192, sizes, 2)
    assert cost.flops == 18 * 8192 * 2048 * 768
    assert cost.bytes == 3 * (16 * 3 * 2048 * 768 * 2 + 2 * 8192 * 2048 * 2)


def test_the_family_files_reference_is_the_programs(sizes):
    """The benchmark's own copy and the program's oracle compute the same
    loss on the rehearsal shape (the program's is held to the model leaf by
    leaf in tests/test_mla_moe.py)."""
    import jax
    import numpy as np
    from benchmark.lib.cells import load_cell
    from distributed_pytorch_from_scratch_tpu.models.vanilla_mla_moe import (
        vanilla_loss)
    workload, config = load_cell(CELL, rehearse=True)
    built = load_module("families", "mla_moe").build(
        config, workload["mesh"], "float32")
    params = built.model.init(jax.random.key(1))
    rng = np.random.default_rng(0)
    ids = rng.integers(0, built.sizes.vocab, (2, 33)).astype(np.int32)
    pos = np.tile(np.arange(32, dtype=np.int32), (2, 1))
    with jax.default_matmul_precision("highest"):
        ours = built.reference_loss(params, ids[:, :-1], ids[:, 1:], pos)
        theirs = vanilla_loss(built.model.cfg, params, ids[:, :-1],
                              ids[:, 1:], pos)
    assert float(ours) == pytest.approx(float(theirs), rel=1e-6)


# ---- the scope readers ----

WHILE = "jit(step)/loss_and_grad/transpose(jvp(jit(loss_shard)))/while/body/"
OPS = [
    # (instruction, meta, op_name or None, the part it belongs to)
    ("fusion.1", "fusion", WHILE + "closed_call/checkpoint/"
     "rematted_computation/mla/reshape", "mla"),
    ("fusion.2", "fusion", "jit(step)/loss_and_grad/jvp(jit(loss_shard))/"
     "mtp/while/body/closed_call/mla/dot_general", "mla"),   # innermost wins
    ("fusion.3", "fusion", "jit(step)/loss_and_grad/jvp(jit(loss_shard))/"
     "mtp/jit(_take)/gather", "mtp"),
    ("fusion.4", "fusion", WHILE + "closed_call/checkpoint/"
     "rematted_computation/moe_route/gather", "moe_route"),
    ("sort.21", "sort", "sort", "moe_route"),
    ("fusion.5", "fusion", WHILE + "closed_call/checkpoint/cond/"
     "branch_1_fun/moe_experts/jit(silu)/add_any", "moe_experts"),
    ("ragged-dot-none.4", "custom-call tpu_custom_call operands=7",
     "ragged-dot-none", "moe_experts"),
    ("fusion.6", "fusion", WHILE + "closed_call/checkpoint/moe_shared/mul",
     "moe_shared"),
    ("flash_fwd.40", "custom-call tpu_custom_call operands=3",
     WHILE + "closed_call/checkpoint/rematted_computation/flash_fwd",
     "flash"),
    ("flash_bwd_dq.18", "custom-call tpu_custom_call operands=6",
     WHILE + "closed_call/checkpoint/flash_bwd_dq", "flash"),
    ("flash_bwd_dkv.18", "custom-call tpu_custom_call operands=6",
     WHILE + "closed_call/checkpoint/flash_bwd_dkv", "flash"),
    ("fusion.7", "fusion", "jit(step)/loss_and_grad/jvp(jit(loss_shard))/"
     "head_loss/convert_element_type", "head_loss"),
    ("fusion.8", "fusion", "jit(step)/optimizer/mul", "optimizer"),
    ("fusion.9", "fusion", WHILE + "closed_call/checkpoint/mul", "rest"),
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
    parts = scopes.scope_ns(dev, runs, names)
    assert set(parts) == set(scopes.PARTS)
    assert sum(parts.values()) == dev.busy_ns()
    want = dict.fromkeys(scopes.PARTS, 0)
    for i, (_, _, _, part) in enumerate(OPS):
        want[part] += 2 * (i + 1) * 1000
    assert parts == want
    # an op outside every run of the step is another program's
    outside = scopes.scope_ns(dev, runs[:1], names)
    assert outside["other_programs"] == sum(want.values()) // 2


def test_the_readers_read_the_runners_fields(sizes):
    dev, runs, names = capture()
    parts = scopes.scope_ns(dev, runs, names)
    workload = load_json("workloads", CELL + ".json")
    peak = SimpleNamespace(flops_per_s=197e12, hbm_bytes_per_s=819e9)
    m = SimpleNamespace(devices=[dev], scopes=parts, peak=peak, sizes=sizes,
                        workload=workload, mesh={"dp": 1, "tp": 1}, chips=1,
                        tokens_per_s=20000.0,
                        rows_here_per_layer=[8192.0] * 5,
                        rows_here_per_token=0.5, load_max_over_mean=2.0,
                        active_flops_per_token=3.4e9)
    read = lambda name: load_module("layer_metrics", name).read(m)
    assert read("model.mla_ms") == pytest.approx((1 + 2) * 1000 / 1e6)
    assert read("model.moe_route_ms") == pytest.approx((4 + 5) * 1000 / 1e6)
    assert read("model.moe_experts_ms") == pytest.approx((6 + 7) * 1000 / 1e6)
    assert read("model.mtp_ms") == pytest.approx(3 * 1000 / 1e6)
    assert read("kernels.flash_ms") == pytest.approx((9 + 10 + 11) * 1e-3)
    assert read("moe.load_max_over_mean") == 2.0
    assert read("moe.rows_here_per_token") == 0.5
    assert read("train_step.active_mfu_pct") == pytest.approx(
        100 * 3.4e9 * 20000 / 197e12)
    # shares of a roofline: least time over the time taken
    least = 5 * max(18 * 8192 * 2048 * 768 / 197e12,
                    counts.expert_products_cost(8192, sizes, 2).bytes / 819e9)
    assert read("model.moe_experts_roofline") == pytest.approx(
        100 * least / (13e-6))
    fwd = counts.flash_call_cost(128, 4096, 192, 128, 2, False).flops / 197e12
    bwd = counts.flash_call_cost(128, 4096, 192, 128, 2, True).flops / 197e12
    # two forward calls, and two kernels (dq; dk, dv) for each backward
    assert read("kernels.mla_flash_roofline") == pytest.approx(
        100 * (2 * fwd + 2 * bwd) / (2 * 30e-6))


def test_the_readers_return_nothing_where_there_is_nothing_to_read():
    """A runner that hands no scope split or counters (the `train` runner,
    or a program without the scopes) gets None, not an exception."""
    m = SimpleNamespace(devices=[], peak=None, tokens_per_s=1.0, chips=1)
    for name in ("model.mla_ms", "model.moe_route_ms", "model.moe_experts_ms",
                 "model.mtp_ms", "model.moe_experts_roofline",
                 "kernels.mla_flash_roofline", "train_step.active_mfu_pct",
                 "moe.load_max_over_mean", "moe.rows_here_per_token"):
        assert load_module("layer_metrics", name).read(m) is None
