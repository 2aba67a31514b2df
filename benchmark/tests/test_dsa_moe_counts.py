"""The dsa_moe family's counts at the published widths
(benchmark/lib/dsa_moe_counts.py), the family file's reference against the
program's at a tiny size, the `train_dsa_moe` check's comparison, its
control tool at the rehearsal shape, and the scope and kernel readers on a
small capture made of the real step's instruction names and `op_name`s (as
the step compiled for the v5e carries them)."""

from types import SimpleNamespace

import numpy as np
import pytest

from benchmark.lib import dsa_moe_counts as counts
from benchmark.lib import dsa_scopes, trace
from benchmark.lib.files import load_json, load_module
from benchmark.lib.mla_moe_counts import expert_products_cost

CELL = "keye-vl-2.0-30b-a3b.train-ep8share-b1-t16384"
CONFIG = "keye-vl-2.0-30b-a3b.json"
T = 16384
KEPT, TRIANGLE = 31_458_304, 134_225_920


@pytest.fixture(scope="module")
def sizes():
    family = load_module("families", "dsa_moe")
    return family.sizes_of(load_json("configs", CONFIG))


def test_parameters_of_the_share_at_the_published_widths(sizes):
    parts = counts.param_counts(sizes)
    assert parts["attention"] == 18_874_624         # wq, wo; wk, wv; 2 norms
    assert parts["indexer"] == 2048 * (1024 + 64 + 16) + 128 == 2_261_120
    assert parts["expert"] == 4_718_592
    assert parts["ffn"] == 262_144 + 16 * 4_718_592
    # cell 8's layer and one indexer
    assert parts["layer"] == 94_638_336 + 2_261_120 == 96_899_456
    assert parts["embedding_and_head"] == 77_791_232
    assert parts["total"] == 6 * 96_899_456 + 77_791_232 + 2048 \
        == 659_190_016
    assert parts["total"] * 16 / 1e9 == pytest.approx(10.55, abs=0.005)
    published = 48 * parts["layer_uncut"] + 2 * 151936 * 2048 + 2048
    assert published / 1e9 == pytest.approx(30.6, abs=0.1)
    assert (sizes.n_layer, sizes.n_head // sizes.n_kv_head, sizes.index_heads,
            sizes.index_dim, sizes.index_topk) == (6, 8, 16, 64, 2048)


def test_the_mathematics_is_counted_not_the_walk(sizes):
    assert counts.kept_pairs(T, 2048) == KEPT
    assert counts.triangle_pairs(T) == TRIANGLE
    assert KEPT / TRIANGLE == pytest.approx(0.2344, abs=1e-4)
    assert counts.kept_pairs(1000, 2048) == counts.triangle_pairs(1000)
    per = counts.mechanism_flops_per_token(sizes, T)
    # a layer, forward: the dense triangle would be 2.2 TFLOP, the kept
    # pairs are 0.52, the indexer's triangle 0.27
    assert 4 * 32 * 128 * TRIANGLE / 1e12 == pytest.approx(2.2, abs=0.01)
    assert per["attend"] * T / 3 / 1e12 == pytest.approx(0.515, abs=0.005)
    assert per["index_select"] * T / 1e12 == pytest.approx(0.275, abs=0.005)
    assert per["index_loss"] == pytest.approx(
        3 * per["index_select"] + per["attend"] / 6)
    flops = counts.train_flops_per_token(sizes, T, 6.0)
    assert flops == pytest.approx(
        6 * counts.active_matmul_params(sizes, 6.0) + 6 * sum(per.values()))
    fwd = counts.dsa_flash_cost(1, T, sizes, 2, False)
    bwd = counts.dsa_flash_cost(1, T, sizes, 2, True)
    assert fwd.flops == 4 * 128 * 32 * KEPT and bwd.flops == 2.5 * fwd.flops
    assert counts.dsa_select_cost(1, T, sizes, 2).flops \
        == 2 * 16 * 64 * TRIANGLE
    assert counts.dsa_index_loss_cost(1, T, sizes, 2).flops \
        == 6 * 16 * 64 * TRIANGLE + 2 * 32 * 128 * KEPT


def test_the_program_counts_the_same(sizes):
    family = load_module("families", "dsa_moe")
    config = load_json("configs", CONFIG)
    built = family.build(config, {"dp": 1, "tp": 1}, "bfloat16")
    cfg = built.model.cfg
    assert cfg.num_params() == counts.param_counts(sizes)["total"]
    assert "659,190,016" in config["deployment"]
    assert cfg.num_experts == 128 and cfg.dsa_moe.experts_held == 16
    assert cfg.vocab_size == 18992 and cfg.num_layers == 6
    assert cfg.moe_top_k == 8 and cfg.rope_theta == 1e7
    assert (cfg.dsa_moe.indexer_num_heads, cfg.dsa_moe.indexer_head_dim,
            cfg.dsa_moe.topk) == (16, 64, 2048)
    # the program's own count of a step at uniform routing is the
    # yardstick's at the rows a uniform router holds here
    uniform = 6 * 8 * 16 / 128
    ours = counts.train_flops_per_token(sizes, T, uniform) * T
    theirs = type(built.model).flops_per_step(cfg, 1, T, cfg.num_params())
    # (the program's 6 N counts its norms' few parameters too)
    assert theirs == pytest.approx(ours, rel=1e-4)
    cost = expert_products_cost(16384, sizes, 2)
    assert cost.flops == 18 * 16384 * 2048 * 768


def test_the_configuration_states_every_published_number():
    import json
    config = load_json("configs", CONFIG)
    for line in open("/opt/skills/guides/model-configs/architectures.jsonl"):
        row = json.loads(line)
        if row["name"] == "Keye-VL-2.0-30B-A3B":
            break
    else:
        pytest.skip("no catalog here")
    assert config["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if config[k] != v}
    assert changed == {"num_experts", "vocab_size"}
    assert set(config["reduced"]) == changed | {"num_layers"}
    assert config["sa_config"] == row["config"]["sa_config"]
    for key in ("qk_head_norms", "indexer", "indexer_positions", "tie_rule",
                "objective", "initialisation", "parameters", "unread_keys"):
        assert key in config["assumed"], key
    assert "vision tower" in config["not_built"]


def test_the_family_files_reference_is_the_programs():
    """The benchmark's own copy and the program's oracle compute the same
    loss, parts and gradients on the rehearsal shape, on their own choice
    and on a choice handed to them (the program's is held to the model leaf
    by leaf in tests/test_dsa_moe.py)."""
    import jax
    from benchmark.lib.cells import load_cell
    from distributed_pytorch_from_scratch_tpu.models import vanilla_dsa_moe
    workload, config = load_cell(CELL, rehearse=True)
    built = load_module("families", "dsa_moe").build(
        config, workload["mesh"], "float32")
    assert built.sizes.n_held * 2 == built.sizes.n_routed == 8
    assert built.sizes.index_topk < workload["seqlen"]
    params = built.model.init(jax.random.key(1))
    rng = np.random.default_rng(0)
    ids = rng.integers(3, built.sizes.vocab, (2, 65)).astype(np.int32)
    pos = np.tile(np.arange(64, dtype=np.int32), (2, 1))
    # a choice that is nobody's own: every row's first keys
    handed = np.tril(np.ones((64, 64), np.int8))[None, None].repeat(
        2, 0).repeat(2, 1) * (np.arange(64) < 16)
    for given in (None, handed):
        with jax.default_matmul_precision("highest"):
            (ours, parts), grads = jax.value_and_grad(
                built.reference_parts, has_aux=True)(
                    params, ids[:, :-1], ids[:, 1:], pos, given)
            (theirs, their_parts), their_grads = jax.value_and_grad(
                lambda p: vanilla_dsa_moe.vanilla_parts(
                    built.model.cfg, p, ids[:, :-1], ids[:, 1:], pos, given),
                has_aux=True)(params)
        assert float(ours) == pytest.approx(float(theirs), rel=1e-6)
        for name in ("ce", "index_kl", "routed", "pairs", "score_rows"):
            np.testing.assert_allclose(parts[name], their_parts[name],
                                       rtol=1e-6, atol=1e-7, err_msg=name)
        for a, b in zip(jax.tree.leaves(grads),
                        jax.tree.leaves(their_grads), strict=True):
            np.testing.assert_allclose(a, b, atol=1e-6 * max(
                float(np.abs(b).max()), 1e-3))
    own, given_pairs, both, _ = np.asarray(parts["pairs"]).T
    assert (given_pairs == handed[0].sum()).all() and (both < own).all()


# ---- the check's comparison ----

def test_a_reading_over_a_limit_is_not_correct():
    runner = load_module("runners", "train_dsa_moe")
    limit = runner.DSA_RTOL["bfloat16"]
    assert set(limit) == {"index_score", "select_miss", "select_count",
                          "tie_rows", "own_loss",
                          "routed_moved", "moe_grad", "attn_grad",
                          "index_grad"}
    assert all(0 < v < 1 for v in limit.values())
    passed = {"ok": True, "rel_err": {}, "rtol": {}}
    routed = np.array([[40.0, 24.0, 0.0, 0.0]])
    rows = np.ones((1, 1, 4, 8), np.float32)
    leaves = {name: np.ones((1, 1, 30), np.float32)
              for name in runner.ATTN_LEAVES + runner.INDEX_LEAVES}
    leaves.update({name: np.ones((1, 3, 50), np.float32)
                   for name in runner.EXPERT_LEAVES})
    leaves["router"] = np.ones((1, 4, 16), np.float32)

    def compare(loss=2.0, moved=0.0, missed=0.0, score=1.0, given=100.0,
                ties=0.0, **off):
        want = {"routed": routed, "grads": leaves, "own_loss": 2.0,
                "probe_rows": rows * score, "score_rows": rows,
                "pairs": [[100.0, given, given * (1 - missed), 2.0]],
                "ce": 1.9, "index_kl": [0.1]}
        got = {k: v * off.get(k, 1.0) for k, v in leaves.items()}
        shift = np.array([[-64.0, 0.0, 64.0, 0.0]]) * moved
        counters = {"routed": routed + shift, "dsa_rows": [1000.0],
                    "dsa_tau_ties": [2.0 + 1000.0 * ties]}
        return runner._compare_dsa(passed, "bfloat16", loss, counters, got,
                                   want)

    assert compare()["ok"]
    for reading, over, under in (
            ("own_loss", dict(loss=2 * (1 + 1.1 * limit["own_loss"])),
             dict(loss=2 * (1 + 0.9 * limit["own_loss"]))),
            ("routed_moved", dict(moved=1.1 * limit["routed_moved"]),
             dict(moved=0.9 * limit["routed_moved"])),
            ("select_miss", dict(missed=1.1 * limit["select_miss"]),
             dict(missed=0.9 * limit["select_miss"])),
            ("tie_rows", dict(ties=1.1 * limit["tie_rows"]),
             dict(ties=0.9 * limit["tie_rows"])),
            ("select_count", dict(given=100.0 * (1 + 2 * limit["select_count"])),
             dict(given=100.0)),
            ("index_score", dict(score=1 + 1.1 * limit["index_score"]),
             dict(score=1 + 0.9 * limit["index_score"])),
            ("attn_grad", dict(wk=1 + 1.1 * limit["attn_grad"]),
             dict(wk=1 + 0.9 * limit["attn_grad"])),
            ("moe_grad", dict(up=1 + 1.1 * limit["moe_grad"]),
             dict(up=1 + 0.9 * limit["moe_grad"])),
            ("index_grad", dict(index_w=1 + 1.1 * limit["index_grad"]),
             dict(index_w=1 + 0.9 * limit["index_grad"]))):
        assert compare(**under)["ok"], reading
        said = compare(**over)
        assert not said["ok"], reading
        assert said["rel_err"][reading] > limit[reading]
    assert not compare(index_norm_bias=float("nan"))["ok"]
    # with the limits not held the readings are recorded and decide nothing
    loose = runner._compare_dsa(
        passed, "float32", 2.0,
        {"routed": routed, "dsa_rows": [8.0], "dsa_tau_ties": [0.0]},
        {k: 3 * v for k, v in leaves.items()},
        {"routed": routed, "grads": leaves, "own_loss": 2.0,
         "probe_rows": rows, "score_rows": rows,
         "pairs": [[1.0, 1.0, 1.0, 0.0]],
         "ce": 1.9, "index_kl": [0.1]}, held=False)
    assert loose["ok"] and loose["rel_err"]["attn_grad"] == pytest.approx(2)


@pytest.mark.parametrize("control,readings", [
    (None, ()),
    ("topk_one_short", ("select_count",)),
    ("batch_shared_selection", ("select_miss",)),
    ("index_loss_leaks_out", ("attn_grad",)),
])
def test_the_control_tool_reads_a_fault_at_the_rehearsal_shape(
        control, readings):
    """float32, tiny: the sound program reads rounding everywhere, a row
    one key short or a choice that is another sequence's misses the
    reference's pairs, and an indexer that reads the layer's input itself
    sends its loss's gradient into the attention leaves upstream. (The
    fourth control, a bfloat16 score, is a fault of precision: read on the
    chip, PERF.md section 2.)"""
    tool = load_module("tools", "dsa_control")
    said = tool.reading(CELL, 3000000019, control, rehearse=True)
    err = said["rel_err"]
    if control is None:
        assert err["select_miss"] == 0 and err["own_loss"] < 1e-6
        assert max(err["attn_grad"], err["moe_grad"], err["index_grad"],
                   err["index_score"]) < 1e-5
        assert said["pairs_own_given_both_tied"][0][0] \
            == 2 * counts.kept_pairs(128, 16)
        assert err["tie_rows"] == 0 and err["select_count"] == 0
    for reading in readings:
        assert err[reading] > 1e-4, (control, reading, err)


# ---- the scope and kernel readers ----

LAYER = ("jit(step)/loss_and_grad/transpose(jvp(jit(loss_shard)))/jit(shard)/"
         "while/body/closed_call/checkpoint/")
FWD = "jit(step)/loss_and_grad/jvp(jit(loss_shard))/jit(shard)/while/body/" \
      "closed_call/"
OPS = [
    # (instruction, meta, op_name or None, the part it belongs to)
    ("fusion.1", "fusion", FWD + "gqa_attn/dot_general", "gqa_attn"),
    ("fusion.2", "fusion", LAYER + "rematted_computation/dsa_index/mul",
     "dsa_index"),
    ("dsa_select.3", "custom-call tpu_custom_call operands=4",
     FWD + "dsa_select/dsa_select", "dsa_select"),
    ("fusion.4", "fusion", FWD + "dsa_select/squeeze", "dsa_select"),
    ("dsa_flash_fwd.5", "custom-call tpu_custom_call operands=8",
     FWD + "dsa_attend/dsa_flash_fwd", "dsa_flash"),
    ("dsa_flash_bwd_dq.6", "custom-call tpu_custom_call operands=11",
     LAYER + "dsa_attend/dsa_flash_bwd_dq", "dsa_flash"),
    ("dsa_flash_bwd_dkv.7", "custom-call tpu_custom_call operands=11",
     LAYER + "dsa_attend/dsa_flash_bwd_dkv", "dsa_flash"),
    ("fusion.8", "fusion", LAYER + "dsa_attend/reduce_sum", "dsa_attend"),
    ("dsa_index_loss.9", "custom-call tpu_custom_call operands=9",
     FWD + "dsa_index_loss/dsa_index_loss", "dsa_index_loss"),
    ("fusion.10", "fusion", LAYER + "dsa_index_loss/mul", "dsa_index_loss"),
    ("fusion.11", "fusion", FWD + "moe_route/jit(take_along_axis)/gather",
     "moe_route"),
    ("sort.21", "sort", "sort", "moe_route"),
    ("ragged-dot-none.4", "custom-call tpu_custom_call operands=7",
     "ragged-dot-none", "moe_experts"),
    ("fusion.14", "fusion", "jit(step)/loss_and_grad/jvp(jit(loss_shard))/"
     "jit(shard)/head_loss/convert_element_type", "head_loss"),
    ("fusion.15", "fusion", "jit(step)/optimizer/mul", "optimizer"),
    ("fusion.16", "fusion", "jit(step)/grad_norm/reduce_sum", "grad_norm"),
    ("fusion.17", "fusion", LAYER + "mul", "rest"),
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
    parts = dsa_scopes.scope_ns(dev, runs, names)
    assert set(parts) == set(dsa_scopes.PARTS)
    assert sum(parts.values()) == dev.busy_ns()
    want = dict.fromkeys(dsa_scopes.PARTS, 0)
    for i, (_, _, _, part) in enumerate(OPS):
        want[part] += 2 * (i + 1) * 1000
    assert parts == want
    assert parts["flash"] == 0
    # none of the step's kernels reads as a static-mask flash call
    from benchmark.lib.kernels import FLASH
    assert not any(FLASH.search(name) or FLASH.search(meta)
                   for name, meta, _, _ in OPS)


def test_the_readers_read_the_runners_fields(sizes):
    dev, runs, names = capture()
    parts = dsa_scopes.scope_ns(dev, runs, names)
    workload = load_json("workloads", CELL + ".json")
    peak = SimpleNamespace(flops_per_s=197e12, hbm_bytes_per_s=819e9)
    runner = load_module("runners", "train_dsa_moe")
    walk = runner._walk_pairs(T, 2048)
    m = SimpleNamespace(devices=[dev], scopes=parts, peak=peak, sizes=sizes,
                        workload=workload, mesh={"dp": 1, "tp": 1}, chips=1,
                        tokens_per_s=12000.0,
                        rows_here_per_layer=[16384.0] * 6,
                        rows_here_per_token=1.0, load_max_over_mean=2.0,
                        active_flops_per_token=2.0e9, dsa_walk=walk,
                        dsa_kept_share=KEPT / TRIANGLE,
                        dsa_select_overlap=0.97)
    read = lambda name: load_module("layer_metrics", name).read(m)
    ms = lambda *ops: sum(ops) * 1000 / 1e6
    assert read("model.gqa_attn_ms") == pytest.approx(ms(1))
    assert read("model.dsa_index_ms") == pytest.approx(ms(2))
    assert read("model.dsa_select_ms") == pytest.approx(ms(3, 4))
    assert read("model.dsa_index_loss_ms") == pytest.approx(ms(9, 10))
    assert read("model.moe_route_ms") == pytest.approx(ms(11, 12))
    assert read("model.moe_experts_ms") == pytest.approx(ms(13))
    assert read("kernels.flash_ms") is None
    assert read("train_step.active_mfu_pct") == pytest.approx(
        100 * 2.0e9 * 12000 / 197e12)
    # one forward, one backward (two kernels), one choice and one loss walk
    # a run of the capture, each over the bf16 peak
    fwd = counts.dsa_flash_cost(1, T, sizes, 2, False)
    bwd = counts.dsa_flash_cost(1, T, sizes, 2, True)
    assert read("kernels.dsa_flash_roofline") == pytest.approx(
        100 * 2 * (fwd.flops + bwd.flops) / 197e12 / (2 * ms(5, 6, 7) / 1e3))
    assert read("model.dsa_index_roofline") == pytest.approx(
        100 * 2 * counts.dsa_select_cost(1, T, sizes, 2).flops / 197e12
        / (2 * ms(3) / 1e3))
    assert read("kernels.dsa_index_loss_roofline") == pytest.approx(
        100 * 2 * counts.dsa_index_loss_cost(1, T, sizes, 2).flops / 197e12
        / (2 * ms(9) / 1e3))
    # the walk computes the triangle and its diagonal tiles' upper halves
    assert walk["blocks"] == [128, 512] and walk["kept"] == KEPT
    assert TRIANGLE < walk["computed"] < 1.05 * TRIANGLE
    assert read("dsa.flash_computed_over_live") == pytest.approx(
        walk["computed"] / KEPT)
    assert read("dsa.flash_computed_over_live") == pytest.approx(4.3, abs=0.1)
    assert read("dsa.kept_share") == pytest.approx(0.2344, abs=1e-4)
    assert read("dsa.select_overlap") == 0.97


def test_the_readers_return_nothing_where_there_is_nothing_to_read(sizes):
    """A runner that hands no scope split (the `train` runner), another
    family's split (no such part among its parts, no index sizes, no walk)
    or a program without the kernels gets None, not an exception."""
    bare = SimpleNamespace(devices=[], peak=None, tokens_per_s=1.0, chips=1,
                           sizes=SimpleNamespace())
    dev, runs, names = capture()
    other = SimpleNamespace(
        devices=[dev], peak=SimpleNamespace(flops_per_s=1.0,
                                            hbm_bytes_per_s=1.0),
        scopes={"shortconv": 5, "moe_route": 7},
        sizes=SimpleNamespace(n_head=32, n_kv_head=8), workload={}, mesh={})
    for m in (bare, other):
        for name in ("model.dsa_index_ms", "model.dsa_select_ms",
                     "model.dsa_index_loss_ms", "kernels.dsa_flash_roofline",
                     "model.dsa_index_roofline",
                     "kernels.dsa_index_loss_roofline",
                     "dsa.flash_computed_over_live", "dsa.kept_share",
                     "dsa.select_overlap"):
            assert load_module("layer_metrics", name).read(m) is None
    # a capture of another program: the sizes are this family's, the
    # kernels are not there
    theirs = SimpleNamespace(
        devices=[trace.DeviceTrace(0, (0, 10), 1, [
            trace.Event("fusion.1", 0, 10, "fusion")], [])],
        peak=SimpleNamespace(flops_per_s=1.0, hbm_bytes_per_s=1.0),
        sizes=sizes, mesh={}, workload=load_json("workloads", CELL + ".json"))
    assert load_module("layer_metrics",
                       "kernels.dsa_flash_roofline").read(theirs) is None
