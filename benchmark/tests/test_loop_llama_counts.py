"""The loop_llama family's counts at the published widths
(benchmark/lib/loop_llama_counts.py), the family file's reference against
the program's at the rehearsal shape, the `train_loop` check's comparison,
its control tool at the rehearsal shape, and the scope readers on a small
capture made of the step's instruction names and `op_name`s (as the step
compiled for the v5e carries them)."""

from types import SimpleNamespace

import numpy as np
import pytest

from benchmark.lib import loop_llama_counts as counts
from benchmark.lib import loop_scopes, trace
from benchmark.lib.files import load_json, load_module

CELL = "ouro-2.6b.train-loop4-b1-t4096"
CONFIG = "ouro-2.6b.json"
T = 4096


@pytest.fixture(scope="module")
def sizes():
    family = load_module("families", "loop_llama")
    return family.sizes_of(load_json("configs", CONFIG))


def test_parameters_of_the_cut_at_the_published_widths(sizes):
    parts = counts.param_counts(sizes)
    assert parts["layer"] == 4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048 \
        == 51_388_416
    assert parts["embedding_and_head"] == 201_326_592
    assert (parts["final_norm"], parts["exit_gate"]) == (2048, 2049)
    assert parts["total"] == 612_438_017
    assert parts["total"] * 16 / 1e9 == pytest.approx(9.80, abs=0.005)
    published = sizes._replace(n_layer=48)
    assert counts.param_counts(published)["total"] == 2_667_974_657
    assert (sizes.n_layer, sizes.passes, sizes.n_head, sizes.n_kv_head,
            sizes.head_dim) == (8, 4, 16, 16, 128)


def test_a_steps_flops_count_the_passes(sizes):
    a_pass = counts.matmul_params_per_pass(sizes)
    assert a_pass == 511_705_088
    full = counts.train_flops_per_token(sizes, T)
    assert full == 4 * (6.0 * a_pass + 12.0 * 8 * 16 * 128 * T)
    assert full / 1e9 == pytest.approx(15.50, abs=0.01)
    # attention at the causal triangle: the reckoning that sized the window
    causal = counts.causal_train_flops_per_token(sizes, T)
    assert causal / 1e9 == pytest.approx(13.89, abs=0.01)
    # NOT 6 N: the parameter count says a quarter of the matmuls' work
    once = sizes._replace(passes=1)
    assert full == 4 * counts.train_flops_per_token(once, T)
    assert 6.0 * counts.param_counts(sizes)["total"] < full / 3


def test_the_program_counts_the_same(sizes):
    family = load_module("families", "loop_llama")
    config = load_json("configs", CONFIG)
    built = family.build(config, {"dp": 1, "tp": 1}, "bfloat16")
    cfg = built.model.cfg
    assert cfg.num_params() == counts.param_counts(sizes)["total"]
    assert sum(type(built.model).param_counts(cfg).values()) == 612_438_017
    assert "612,438,017" in config["deployment"]
    assert config["reduced"] == ["num_layers"]
    assert config["published"] == {"num_hidden_layers": 48}
    assert config["num_hidden_layers"] == 48 and config["num_layers"] == 8
    assert (cfg.loop_llama.loop_steps, cfg.loop_llama.exit_entropy_coef,
            cfg.loop_llama.rms_norm_eps, cfg.rope_theta) == (4, 0.05, 1e-6,
                                                             1e6)
    assert built.model.loop_steps == 4 and not built.model.decodable
    # the program's own count of a step is this file's
    flops = type(built.model).flops_per_step(cfg, 1, T, cfg.num_params())
    assert flops == T * counts.train_flops_per_token(sizes, T)


def test_the_family_files_reference_is_the_programs():
    """The benchmark's own copy (blocks, a scan of layers inside a Python
    loop of passes) and the program's oracle (Python loops, full tensors)
    compute one loss, one set of exit losses and one gradient on the
    rehearsal shape (the program's is held to the model leaf by leaf in
    tests/test_loop_llama.py)."""
    import jax
    from benchmark.lib.cells import load_cell
    from distributed_pytorch_from_scratch_tpu.models import (
        vanilla_loop_llama)
    workload, config = load_cell(CELL, rehearse=True)
    built = load_module("families", "loop_llama").build(
        config, workload["mesh"], "float32")
    assert built.sizes.passes == 3
    params = built.model.init(jax.random.key(1))
    rng = np.random.default_rng(0)
    ids = rng.integers(3, built.sizes.vocab, (2, 129)).astype(np.int32)
    pos = np.tile(np.arange(128, dtype=np.int32), (2, 1))
    with jax.default_matmul_precision("highest"):
        (ours, more), grads = jax.value_and_grad(
            built.reference_detail, has_aux=True)(
                params, ids[:, :-1], ids[:, 1:], pos)
        (theirs, detail), their_grads = jax.value_and_grad(
            lambda p: vanilla_loop_llama.vanilla_loss(
                built.model.cfg, p, ids[:, :-1], ids[:, 1:], pos,
                detail=True), has_aux=True)(params)
    assert float(ours) == pytest.approx(float(theirs), rel=1e-6)
    np.testing.assert_allclose(more["loss_exit"], detail["loss_exit"],
                               rtol=1e-6)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(their_grads),
                    strict=True):
        np.testing.assert_allclose(a, b, atol=1e-5 * max(
            float(np.abs(b).max()), 1e-3))


# ---- the check's comparison ----

def test_a_reading_over_a_limit_is_not_correct():
    runner = load_module("runners", "train_loop")
    limit = runner.LOOP_RTOL["bfloat16"]
    assert set(limit) == {"exit_losses", "gate_grad", "shared_grad",
                          "sampled_grads"}
    assert all(0 < v < 1 for v in limit.values())
    passed = {"ok": True, "rel_err": {}, "rtol": {}}
    exits = np.array([10.9, 10.8, 10.7, 10.6])
    rng = np.random.default_rng(0)
    want = {"layers/wq/weight": rng.normal(size=(8, 600)),
            "layers/norm1/scale": rng.normal(size=(8, 64)),
            "exit_gate": rng.normal(size=(1, 65)),
            "embedding/weight": rng.normal(size=(1, 900)),
            "lm_head/weight": rng.normal(size=(1, 900))}

    def compare(e=exits, **off):
        got = {k: v * off.get(k, 1.0) for k, v in want.items()}
        return runner._compare_loop(passed, "bfloat16", e, exits, got, want)

    sound = compare()
    assert sound["ok"] and set(sound["rel_err"]) == set(limit)
    assert compare(exits * (1 + 0.9 * limit["exit_losses"]))["ok"]
    assert not compare(exits * (1 + 1.1 * limit["exit_losses"]))["ok"]
    # a step that counted another number of exits
    short = compare(exits[:3])
    assert not short["ok"] and short["rel_err"]["exit_losses"] == np.inf
    for leaf, reading in (("layers/wq/weight", "shared_grad"),
                          ("layers/norm1/scale", "shared_grad"),
                          ("exit_gate", "gate_grad"),
                          ("lm_head/weight", "sampled_grads")):
        room = 1.5 if reading == "sampled_grads" else 1.0   # half the sample
        assert compare(**{leaf: 1 + 0.9 * limit[reading]})["ok"]
        over = compare(**{leaf: 1 + 1.1 * room * limit[reading]})
        assert not over["ok"], leaf
        assert over["rel_err"][reading] > limit[reading]
    # one layer's leaf off is the reading: the worst layer, not the mean
    one = {k: v.copy() for k, v in want.items()}
    one["layers/wq/weight"][5] *= 1.2
    assert not runner._compare_loop(passed, "bfloat16", exits, exits, one,
                                    want)["ok"]
    assert not compare(**{"exit_gate": np.nan})["ok"]


@pytest.mark.parametrize("control,reading", [
    (None, None), ("one_pass_short", "exit_losses"),
    ("p_detached", "gate_grad"), ("bf16_grad_sum", "shared_grad")])
def test_the_control_tool_reads_the_check(control, reading):
    """At the rehearsal shape (float32, held to float32's limits): the sound
    program passes; a control fails by the reading it is there for."""
    tool = load_module("tools", "loop_control")
    got = tool.reading(CELL, 5, control, rehearse=True)
    assert got["control"] == control and got["ok"] == (control is None)
    if reading:
        assert not got["rel_err"][reading] <= got["rtol"][reading]


# ---- the scope split ----

FWD = "jit(step)/loss_and_grad/jvp(shard_map)/"
BWD = "jit(step)/loss_and_grad/transpose(jvp(shard_map))/"
PASS = "loop_pass/while/body/closed_call/"
# (name, meta, op_name, the part it falls in)
OPS = [
    ("fusion.1", "fusion", FWD + "while/body/" + PASS + "checkpoint/mul",
     "loop_pass"),
    ("fusion.2", "fusion", BWD + "while/body/" + PASS
     + "checkpoint/rematted_computation/dense_ffn/dot_general", "dense_ffn"),
    ("fusion.3", "fusion", FWD + "while/body/head_loss/mul", "head_loss"),
    ("fusion.4", "fusion", FWD + "head_loss/while/body/checkpoint/"
     "dot_general", "head_loss"),
    ("fusion.5", "fusion", BWD + "head_loss/while/body/checkpoint/"
     "rematted_computation/dot_general", "head_loss"),
    # innermost wins: the gate lies inside the exits' scope
    ("fusion.6", "fusion", FWD + "head_loss/exit_gate/reduce_sum",
     "exit_gate"),
    ("flash_fwd.3", "custom-call tpu_custom_call operands=3",
     FWD + "while/body/" + PASS + "checkpoint/flash_fwd", "flash"),
    ("flash_bwd.2", "custom-call tpu_custom_call operands=6",
     BWD + "while/body/" + PASS + "checkpoint/flash_bwd", "flash"),
    ("fusion.7", "fusion", "jit(step)/optimizer/mul", "optimizer"),
    ("fusion.8", "fusion", "jit(step)/grad_norm/reduce_sum", "grad_norm"),
    ("fusion.9", "fusion", FWD + "convert_element_type", "rest"),
    ("copy.7", "copy", None, "unattributed"),
]


def capture(steps=2, each_ns=1000):
    events, runs, t = [], [], 0
    for _ in range(steps):
        start = t
        for i, (name, meta, *_) in enumerate(OPS):
            events.append(trace.Event(name, t, (i + 1) * each_ns, meta))
            t += (i + 1) * each_ns
        runs.append((start, t))
        t += 500                                        # an idle gap
    dev = trace.DeviceTrace(0, (0, runs[-1][1]), steps, events, [])
    return dev, runs, {name: op for name, _, op, _ in OPS if op}


def test_every_op_falls_in_one_part_and_the_parts_sum_to_busy():
    dev, runs, names = capture()
    parts = loop_scopes.scope_ns(dev, runs, names)
    assert set(parts) == set(loop_scopes.PARTS)
    assert sum(parts.values()) == dev.busy_ns()
    want = dict.fromkeys(loop_scopes.PARTS, 0)
    for i, (*_, part) in enumerate(OPS):
        want[part] += 2 * (i + 1) * 1000
    assert parts == want
    outside = loop_scopes.scope_ns(dev, runs[:1], names)
    assert outside["other_programs"] == sum(want.values()) // 2


def test_the_readers_read_the_runners_fields(sizes):
    dev, runs, names = capture()
    parts = loop_scopes.scope_ns(dev, runs, names)
    workload = load_json("workloads", CELL + ".json")
    peak = SimpleNamespace(flops_per_s=197e12, hbm_bytes_per_s=819e9)
    m = SimpleNamespace(devices=[dev], scopes=parts, peak=peak, sizes=sizes,
                        workload=workload, mesh={"dp": 1, "tp": 1}, chips=1,
                        tokens_per_s=6000.0, exit_step_mean=1.9,
                        flops_per_token=counts.train_flops_per_token(sizes,
                                                                     T))
    read = lambda name: load_module("layer_metrics", name).read(m)
    assert read("model.loop_layers_ms") == pytest.approx((1 + 2) * 1e-3)
    assert read("model.dense_ffn_ms") == pytest.approx(2 * 1e-3)
    assert read("model.loop_exits_ms") == pytest.approx((3 + 4 + 5 + 6) * 1e-3)
    assert read("model.exit_gate_ms") == pytest.approx(6 * 1e-3)
    assert read("loop.exit_step_mean") == 1.9
    assert read("kernels.flash_ms") == pytest.approx((7 + 8) * 1e-3)
    assert read("kernels.flash_fwd_per_bwd") == 1.0
    assert 0 < read("kernels.flash_roofline")
    assert read("train_step.mfu_pct") == pytest.approx(
        100 * 6000.0 * 15.50e9 / 197e12, rel=1e-3)
    # another runner's `measured` (no such parts, no such counter): nothing,
    # and nothing raised
    other = SimpleNamespace(devices=[dev], scopes={"head_loss": 5})
    for name in ("model.loop_layers_ms", "model.loop_exits_ms",
                 "model.exit_gate_ms", "loop.exit_step_mean"):
        assert load_module("layer_metrics", name).read(other) is None
        assert load_module("layer_metrics", name).read(
            SimpleNamespace(devices=[])) is None
