"""The mhc_mla_moe family's counts at the published widths
(benchmark/lib/mhc_mla_moe_counts.py) against a hand count, the family
file's reference against the program's at the rehearsal shape, the scope
readers on a small capture made of the real step's `op_name`s
(benchmark/lib/mhc_scopes.py), and the check's control at the rehearsal
shape (benchmark/tools/mhc_control.py)."""

from types import SimpleNamespace

import numpy as np
import pytest

from benchmark.lib import mhc_mla_moe_counts as counts
from benchmark.lib import mhc_scopes, trace
from benchmark.lib.files import load_json, load_module

CELL = "xing4-29b-a4b.train-ep8share-b1-t4096"
CONFIG = "xing4-29b-a4b.json"


@pytest.fixture(scope="module")
def sizes():
    family = load_module("families", "mhc_mla_moe")
    return family.sizes_of(load_json("configs", CONFIG))


# ---- the counts, by hand ----

def test_parameters_of_the_share_at_the_published_widths(sizes):
    """ISSUE 57's arithmetic with the norms counted: MLA 28.41M a layer,
    the dense layer 128.2M, an expert layer of 8 held experts 128.4M, a
    mixer 0.34M, an eighth of the vocabulary untied 117.4M."""
    parts = counts.param_counts(sizes)
    d, n = 3584, 4
    mla = (d * 768 + 768 * 32 * 192 + d * 576 + 512 * 32 * 256
           + 32 * 128 * d)
    assert mla == 28_409_856
    assert parts["attention"] == mla + 768 + 512 + 2 * d
    mixer = n * d * 24 + 3 + 24
    assert counts.mixer_params(sizes) == mixer == 344_091
    assert counts.exit_params(sizes) == n * d * n + 1 + n
    assert round((parts["dense_layer"] + 2 * mixer) / 1e6, 1) == 128.2
    assert round((parts["expert_layer"] + 2 * mixer) / 1e6, 1) == 128.4
    assert round(parts["embedding_and_head"] / 1e6, 1) == 117.4
    assert parts["stream_mixers"] == 10 * mixer + n * d * n + 1 + n
    assert parts["total"] == 759_403_795                 # 759.4M
    assert parts["total"] * 16 / 1e9 == pytest.approx(12.15, abs=0.005)
    assert 11.5 < parts["total"] * 16 / 1e9 < 12.5


def test_the_program_counts_the_same(sizes):
    config = load_json("configs", CONFIG)
    built = load_module("families", "mhc_mla_moe").build(
        config, {"dp": 1, "tp": 1}, "bfloat16")
    cfg = built.model.cfg
    assert cfg.num_params() == counts.param_counts(sizes)["total"]
    assert cfg.num_experts == 64 and cfg.latent_moe.experts_held == 8
    assert cfg.vocab_size == 16384 and cfg.num_layers == 5
    lm = cfg.latent_moe
    # one leading dense layer held of the published two; no module here
    assert config["first_k_dense_replace"] == 2
    assert lm.first_k_dense_replace == 1 == sizes.n_dense_layer
    assert lm.num_nextn_predict_layers == 0 == sizes.n_mtp
    assert lm.hyper.hc_mult == 4 and lm.hyper.hc_sinkhorn_iters == 20
    assert (lm.hyper.mhc_h_res_clamp_min,
            lm.hyper.mhc_h_res_clamp_max) == (-30.0, 30.0)
    assert lm.rope_scaling.factor == 64.0
    assert lm.rope_scaling.original_max_position_embeddings == 4096
    assert built.model.attention.softmax_scale == pytest.approx(2.00474,
                                                                abs=1e-4)
    assert built.model.residual_streams == 4


def test_every_published_width_stands(sizes):
    """The catalog's row, key by key: only `reduced` differs."""
    import json
    config = load_json("configs", CONFIG)
    row = None
    try:
        with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Xing4.0-29B-A4B")
    except OSError:
        pytest.skip("no catalog beside the guide here")
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    assert config["source"] == row["source_url"]
    assert sorted(config["reduced"]) == sorted(
        ["n_routed_experts", "num_layers", "vocab_size",
         "num_nextn_predict_layers"])


def test_flops_per_token(sizes):
    """Forward MFLOP a token: `mla_moe`'s count at these widths (latent
    projections 5 x 56.8, dense MLP 198, shared experts and routers 4 x
    23.9, routed experts here 4 x 22 at 0.5 rows a token and layer, one
    head 117) and the mixers: ten products with W of 0.69M a token each,
    the exit's, and the weighted sums."""
    M = 1e6
    uniform = sizes.expert_layers * sizes.top_k * sizes.n_held / sizes.n_routed
    assert uniform == 2.0
    base = counts.base.train_flops_per_token(sizes, 4096, uniform)
    full = counts.train_flops_per_token(sizes, 4096, uniform)
    n, c = 4, 3584
    w_products = 10 * n * c * 24 + n * c * n
    sums = 10 * (2 * n * c + 2 * n * (n + 1) * c) + 2 * n * c
    assert counts.mixer_matmul_params(sizes) == w_products
    assert counts.stream_sum_flops_per_token(sizes) == sums
    assert full - base == 6 * w_products + 3 * sums
    assert 2 * w_products / M == pytest.approx(7.0, abs=0.05)
    assert sums / M == pytest.approx(1.75, abs=0.01)
    # (ISSUE 57 reckoned "about 3.8 GFLOP a token") attention at 4096 rows
    # is 5 layers x 32 heads x 320 x 4096 x 6
    attention = 6 * 5 * 32 * (192 + 128) * 4096
    assert base == pytest.approx(
        6 * counts.base.active_matmul_params(sizes, uniform) + attention)
    assert 3.3e9 < full < 3.7e9      # 3.49 GFLOP a token
    assert (full - base) / full < 0.02       # the mixers are bytes, not FLOPs


def test_the_bytes_the_mixers_must_move(sizes):
    """(2n + 2) C forward and (3n + 2) C backward a mixer and token at the
    compute dtype, (n + 1) C and (2n + 1) C the exit, W thrice in float32;
    one forward and one backward, no recompute."""
    n, c, tokens = 4, 3584, 4096
    cost = counts.mixers_step_cost(sizes, tokens, 2)
    streams = tokens * 2 * c * (10 * ((2 * n + 2) + (3 * n + 2))
                                + (n + 1) + (2 * n + 1))
    weights = 3 * 4 * (10 * n * c * 24 + n * c * n)
    assert cost.bytes == streams + weights
    assert streams / 1e9 == pytest.approx(7.46, abs=0.01)
    assert cost.flops == tokens * (6 * counts.mixer_matmul_params(sizes)
                                   + 3 * counts.stream_sum_flops_per_token(
                                       sizes))
    # the bytes bind: 9.1 ms at the HBM peak against under 0.4 ms of FLOPs
    assert cost.bytes / 819e9 > 10 * cost.flops / 197e12
    assert cost.bytes / 819e9 == pytest.approx(9.1e-3, rel=0.02)
    # in float32 the streams cost twice
    assert counts.mixers_step_cost(sizes, tokens, 4).bytes == \
        2 * streams + weights


def test_the_family_files_reference_is_the_programs():
    """The benchmark's own copy and the program's oracle compute the same
    loss on the rehearsal shape (the program's is held to the model leaf by
    leaf in tests/test_mhc_mla_moe.py), with and without the module."""
    import jax
    from benchmark.lib.cells import load_cell
    from distributed_pytorch_from_scratch_tpu.models.vanilla_mhc_mla_moe import (
        vanilla_loss)
    workload, config = load_cell(CELL, rehearse=True)
    for mtp in (0, 1):
        built = load_module("families", "mhc_mla_moe").build(
            {**config, "num_nextn_predict_layers": mtp}, workload["mesh"],
            "float32")
        params = built.model.init(jax.random.key(1))
        assert ("mtp" in params) == bool(mtp)
        rng = np.random.default_rng(0)
        ids = rng.integers(0, built.sizes.vocab, (2, 49)).astype(np.int32)
        pos = np.tile(np.arange(48, dtype=np.int32), (2, 1))
        with jax.default_matmul_precision("highest"):
            ours, routed = built.reference_routed(params, ids[:, :-1],
                                                  ids[:, 1:], pos)
            theirs = vanilla_loss(built.model.cfg, params, ids[:, :-1],
                                  ids[:, 1:], pos)
        assert float(ours) == pytest.approx(float(theirs), rel=1e-6)
        # every token takes top_k experts in each expert layer
        assert routed.shape == (2 + mtp, 8)
        np.testing.assert_array_equal(routed.sum(-1), 2 * 48 * 2)


# ---- the scope readers ----

WHILE = "jit(step)/loss_and_grad/transpose(jvp(jit(loss_shard)))/while/body/"
FWD = "jit(step)/loss_and_grad/jvp(jit(loss_shard))/"
OPS = [
    # (instruction, meta, op_name or None, the part, the mixer's part)
    ("fusion.1", "fusion", WHILE + "closed_call/checkpoint/"
     "rematted_computation/mhc/maps/dot_general", "mhc", "maps"),
    ("fusion.2", "fusion", WHILE + "closed_call/checkpoint/"
     "rematted_computation/mhc/sinkhorn/div", "mhc", "sinkhorn"),
    ("fusion.3", "fusion", FWD + "while/body/closed_call/mhc/pre/"
     "reduce_sum", "mhc", "pre"),
    ("fusion.4", "fusion", WHILE + "closed_call/checkpoint/mhc/post/"
     "mul", "mhc", "post"),
    ("fusion.5", "fusion", FWD + "mhc/exit/reduce_sum", "mhc", "exit"),
    # innermost wins: the module's own exit is the mixers', not `mtp`'s
    ("fusion.6", "fusion", FWD + "mtp/mhc/exit/mul", "mhc", "exit"),
    ("fusion.7", "fusion", WHILE + "closed_call/checkpoint/mhc/"
     "convert_element_type", "mhc", "other"),
    ("fusion.8", "fusion", WHILE + "closed_call/checkpoint/"
     "rematted_computation/mla/reshape", "mla", None),
    ("fusion.9", "fusion", WHILE + "closed_call/checkpoint/"
     "rematted_computation/moe_route/gather", "moe_route", None),
    ("sort.21", "sort", "sort", "moe_route", None),
    ("ragged-dot-none.4", "custom-call tpu_custom_call operands=7",
     "ragged-dot-none", "moe_experts", None),
    ("fusion.10", "fusion", WHILE + "closed_call/checkpoint/moe_shared/mul",
     "moe_shared", None),
    ("flash_fwd.40", "custom-call tpu_custom_call operands=3",
     WHILE + "closed_call/checkpoint/rematted_computation/flash_fwd",
     "flash", None),
    ("flash_bwd.18", "custom-call tpu_custom_call operands=6",
     WHILE + "closed_call/checkpoint/flash_bwd", "flash", None),
    ("fusion.11", "fusion", FWD + "head_loss/convert_element_type",
     "head_loss", None),
    ("fusion.12", "fusion", "jit(step)/optimizer/mul", "optimizer", None),
    ("fusion.13", "fusion", WHILE + "closed_call/checkpoint/mul", "rest",
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
    parts = mhc_scopes.scope_ns(dev, runs, names)
    assert set(parts) == set(mhc_scopes.PARTS)
    assert sum(parts.values()) == dev.busy_ns()
    want = dict.fromkeys(mhc_scopes.PARTS, 0)
    inner = dict.fromkeys(mhc_scopes.MHC_PARTS + ("other",), 0)
    for i, (_, _, _, part, mixer_part) in enumerate(OPS):
        want[part] += 2 * (i + 1) * 1000
        if mixer_part:
            inner[mixer_part] += 2 * (i + 1) * 1000
    assert parts == want
    # the mixers' time by part is a split of `mhc`, not parts beside it
    got = mhc_scopes.mhc_parts_ns(dev, runs, names)
    assert got == inner and sum(got.values()) == parts["mhc"]
    # an op outside every run of the step is another program's
    outside = mhc_scopes.scope_ns(dev, runs[:1], names)
    assert outside["other_programs"] == sum(want.values()) // 2
    # the list is `lib/scopes.py`'s with `mhc`: its readers read this split
    from benchmark.lib import scopes
    assert set(mhc_scopes.SCOPES) == set(scopes.SCOPES) | {"mhc"}


def test_the_readers_read_the_runners_fields(sizes):
    dev, runs, names = capture()
    parts = mhc_scopes.scope_ns(dev, runs, names)
    workload = load_json("workloads", CELL + ".json")
    peak = SimpleNamespace(flops_per_s=197e12, hbm_bytes_per_s=819e9)
    cost = counts.mixers_step_cost(sizes, 4096, 2)
    m = SimpleNamespace(devices=[dev], scopes=parts, peak=peak, sizes=sizes,
                        workload=workload, mesh={"dp": 1, "tp": 1}, chips=1,
                        tokens_per_s=10000.0,
                        rows_here_per_layer=[2048.0] * 4,
                        rows_here_per_token=0.5, load_max_over_mean=2.0,
                        active_flops_per_token=3.8e9, sinkhorn_err=3e-5,
                        mhc_cost=cost)
    read = lambda name: load_module("layer_metrics", name).read(m)
    mhc_ms = sum(range(1, 8)) * 1000 / 1e6
    assert read("model.mhc_ms") == pytest.approx(mhc_ms)
    assert read("model.mhc_roofline") == pytest.approx(
        100 * (cost.bytes / 819e9) / (mhc_ms / 1e3))
    assert read("mhc.sinkhorn_err") == 3e-5
    # and the readers written for `train_scopes` take this `measured`
    assert read("model.mla_ms") == pytest.approx(8 * 1000 / 1e6)
    assert read("model.moe_route_ms") == pytest.approx((9 + 10) * 1000 / 1e6)
    assert read("model.moe_experts_ms") == pytest.approx(11 * 1000 / 1e6)
    assert read("kernels.flash_ms") == pytest.approx((13 + 14) * 1e-3)
    assert read("moe.load_max_over_mean") == 2.0
    assert read("moe.rows_here_per_token") == 0.5
    assert read("train_step.active_mfu_pct") == pytest.approx(
        100 * 3.8e9 * 10000 / 197e12)
    assert read("model.moe_experts_roofline") > 0
    assert read("kernels.mla_flash_roofline") > 0


def test_the_new_readers_return_nothing_where_there_is_nothing_to_read():
    """A program without the family (the parent's), a runner that hands no
    scope split, an untraced run: None, not an exception."""
    empty = SimpleNamespace(devices=[], peak=None, tokens_per_s=1.0, chips=1)
    dev, runs, names = capture()
    from benchmark.lib import scopes
    other = SimpleNamespace(devices=[dev], peak=SimpleNamespace(
        flops_per_s=197e12, hbm_bytes_per_s=819e9),
        scopes=scopes.scope_ns(dev, runs, names))     # no `mhc` in it
    for m in (empty, other):
        for name in ("model.mhc_ms", "model.mhc_roofline",
                     "mhc.sinkhorn_err"):
            assert load_module("layer_metrics", name).read(m) is None


# ---- the check and its control, at the rehearsal shape ----

def test_a_reading_over_the_limit_is_not_correct():
    runner = load_module("runners", "train_mhc")
    limit = runner.HC_RTOL["bfloat16"]["hc_grad"]
    passed = {"ok": True, "rel_err": {}, "rtol": {}}
    rng = np.random.default_rng(0)
    want = {"layers/hc_attn/w": rng.normal(size=(2, 96)),
            "layers/hc_attn/alpha": rng.normal(size=(2, 3)),
            "layers/hc_attn/b": rng.normal(size=(2, 24)),
            "hc_exit/w": rng.normal(size=(1, 16)),
            "hc_exit/alpha": rng.normal(size=(1, 1)),
            "hc_exit/b": rng.normal(size=(1, 4))}
    compare = lambda colsum=1.2e-6, **off: runner._compare_hc(
        passed, "bfloat16",
        {k: v * off.get(k.replace("/", "_"), 1.0) for k, v in want.items()},
        want, colsum)
    assert compare()["ok"] and compare()["rel_err"]["hc_grad"] == 0.0
    assert compare(layers_hc_attn_w=1 + 0.9 * limit)["ok"]
    bad = compare(layers_hc_attn_w=1 + 1.1 * limit)
    assert not bad["ok"] and bad["rtol"]["hc_grad"] == limit
    assert bad["rel_err"]["hc_grad"] == pytest.approx(1.1 * limit)
    assert not compare(layers_hc_attn_w=np.nan)["ok"]
    # the small leaves and the exit's are logged, not held (HC_RTOL's note)
    logged = compare(layers_hc_attn_b=1.5, hc_exit_w=1.5)
    assert logged["ok"] and logged["rel_err"]["hc_grad"] == 0.0
    assert logged["hc_grad_by_leaf"]["hc_exit/w"][0] > 0.2
    assert max(logged["hc_grad_by_leaf"]["layers/hc_attn/b"]) == \
        pytest.approx(0.5)
    # a small leaf whose own gradient is near nothing is logged against its
    # kind's median norm, not against itself
    tiny = {**want, "hc_exit/alpha": np.full((1, 1), 1e-9)}
    off = {**tiny, "hc_exit/alpha": np.full((1, 1), 3e-9)}
    assert runner._compare_hc(passed, "bfloat16", off, tiny, 1e-6)[
        "hc_grad_by_leaf"]["hc_exit/alpha"][0] < 1e-8
    # the columns' sums: float32's reading passes, bfloat16's does not
    assert compare(colsum=1.4e-6)["ok"]
    assert compare(colsum=1.4e-6)["rel_err"]["hc_colsum"] == 1.4e-6
    assert not compare(colsum=4.9e-3)["ok"]
    assert not compare(colsum=float("nan"))["ok"]
    assert not runner._compare_hc({**passed, "ok": False}, "bfloat16", want,
                                  want, 1e-6)["ok"]
    # the tree's mixers, a layer at a time, the exits among them
    named = runner._hc_named({
        "layers": {"hc_attn": {"w": np.zeros((2, 8, 24))},
                   "hc_ffn": {"b": np.zeros((2, 24))}, "norm1": {}},
        "mtp": {"hc_exit": {"alpha": np.zeros((1,))}, "hnorm": {}},
        "hc_exit": {"w": np.zeros((8, 4))}, "norm": {}})
    assert {k: v.shape for k, v in named.items()} == {
        "layers/hc_attn/w": (2, 192), "layers/hc_ffn/b": (2, 24),
        "mtp/hc_exit/alpha": (1, 1), "hc_exit/w": (1, 32)}


@pytest.mark.parametrize("seed", [1, 2147483693])
def test_plain_rope_reads_worse_than_the_program(seed):
    """The limits are read at the published widths on the chip (PERF.md
    section 2) and say nothing at this shape; what holds at every shape is
    that with the same seed the control that leaves YaRN's blended
    frequencies out reads worse than the sound program in the runner's own
    numbers, and that the control tool patches what it says and puts it
    back."""
    tool = load_module("tools", "mhc_control")
    sound = tool.reading(CELL, seed, None, rehearse=True)
    control = tool.reading(CELL, seed, "plain_rope", rehearse=True)
    assert control["control"] == "plain_rope" and sound["control"] is None
    assert control["rel_err"]["moe_grad"] > 1.5 * sound["rel_err"]["moe_grad"]
    assert control["rel_err"]["hc_grad"] > 2 * sound["rel_err"]["hc_grad"]
    again = tool.reading(CELL, seed, None, rehearse=True)
    assert again["rel_err"] == sound["rel_err"]


def test_bf16_maps_break_what_float32_maps_keep():
    """The other control computes every mixer's maps in bfloat16: the
    columns of H, normalised last, then sum to one to bfloat16's rounding
    and not to float32's, which the step's own counter shows at any shape
    and `hc_colsum`'s limit stands between."""
    tool = load_module("tools", "mhc_control")
    limit = load_module("runners", "train_mhc").HC_RTOL["bfloat16"][
        "hc_colsum"]
    sound = tool.reading(CELL, 1, None, rehearse=True)
    control = tool.reading(CELL, 1, "bf16_maps", rehearse=True)
    assert sound["rel_err"]["hc_colsum"] < limit / 20
    assert control["rel_err"]["hc_colsum"] > 20 * limit
    assert not control["ok"]
