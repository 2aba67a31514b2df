"""The `swa_moe` family (models/swa_moe.py): a grouped-query expert decoder
whose attention layers are of two kinds over one parameter tree,
sliding-window layers (RoPE) three to one with full-attention layers (no
positions), an output gate, four norms a layer, the embedding's rows times
sqrt(width), and a sigmoid router whose selection bias a rule updates after
every optimizer step. CPU, tiny sizes.

* the program against the plain reference (models/vanilla_swa_moe.py, whose
  layers are looped and whose mask is a boolean matrix from `i - j`): logits,
  loss and EVERY gradient leaf, float32 tight at tp 1 and tp 2 and under
  the flash kernels (the interpreter), bfloat16 loose, with 2 dense layers
  and two periods, on a job that holds a slice of the experts;
* what a kind means, exactly: a window layer is blind past its window and
  a full layer is not, a full layer takes no positions and a window layer
  does, and a window that covers the sequence is the causal call;
* the shares test: the parts all four shares give, the shared expert
  counted once, add up to the uncut layer's output;
* the bias rule: hand-made counts -> the exact delta, zero-mean, untouched
  by the gradient and by Adam, restored from a checkpoint, and the load's
  max over mean falling over 200 tiny steps with the rule on against off;
* what the family does not run is refused with a message; the CLI;
* the counts at the published widths (705,474,304 in the cut, 26.1 B
  published).
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from family_recipe import Recipe, apply_moe, hold_leaves, hold_loss, token_file

from distributed_pytorch_from_scratch_tpu.config import (
    ModelConfig, OptimizerConfig, SwaMoEConfig, model_preset)
from distributed_pytorch_from_scratch_tpu.models import build_model
from distributed_pytorch_from_scratch_tpu.models.swa_moe import (
    SlidingWindowMoETransformer)
from distributed_pytorch_from_scratch_tpu.models.vanilla_swa_moe import (
    bias_rule, vanilla_loss)
from distributed_pytorch_from_scratch_tpu.ops.attention import sliding_window
from distributed_pytorch_from_scratch_tpu.parallel.moe import SharedRoutedFFN
from distributed_pytorch_from_scratch_tpu.training.metrics import (
    model_flops_per_step, moe_counters_summary)
from distributed_pytorch_from_scratch_tpu.training.optim import (
    init_adam_state, router_bias_step)
from distributed_pytorch_from_scratch_tpu.training.train_step import (
    build_grad_accum_step, build_train_step)

PUBLISHED = (("sliding_attention",) * 3 + ("full_attention",)) * 8


# the family's own: its reference (sequences of 64 from id 3 up: the recipe's)
R = Recipe("swa_moe", vanilla_loss)
tiny, batch, on_mesh = R.tiny, R.batch, R.on_mesh


def small(**facts):
    """One dense window layer, then one period of (window, full)."""
    return dataclasses.replace(
        tiny(layer_types=("sliding_attention",) * 2 + ("full_attention",),
             num_dense_layers=1, **facts), num_layers=3)


# ---- the program against the plain reference ----

@pytest.mark.parametrize("tp,impl,t", [(1, "xla", 64), (2, "xla", 64),
                                       (1, "flash_interpret", 128)])
def test_loss_and_every_gradient_leaf_equal_the_reference(tp, impl, t):
    """A dense segment of two layers and a period block of two periods
    SCANNED (the program) against ten layers LOOPED (the reference), on a
    job that holds experts 2..5 of 8; a window of 16 rows in a sequence of
    64 (or 128 under the kernels). Leaves to 1e-5 of their largest
    entry."""
    cfg = tiny(experts_held=4, expert_offset=2)
    params, (want, want_g) = R.reference(cfg, t)
    got, got_g = R.program(cfg, tp=tp, t=t, attn_impl=impl)
    model = build_model("swa_moe", cfg, tp_size=tp)
    assert model._pattern == (
        "dense_layers", (("window_layers_0", 3), ("full_layers_0", 1)))
    assert [model._kind(k) for k in model._layer_keys] == [
        "window", "window", "full"]
    hold_loss(want, got)
    assert len(hold_leaves(want_g, got_g, 1e-5)[0]) == 55
    # the selection bias is a leaf no gradient reaches; both kinds of layer
    # hold the same parameters, four norms and a gate of their own
    bias = got_g["window_layers_0"]["moe"]["bias"]
    assert bias.shape == (2, 3, 8) and not np.any(bias)
    assert set(params["window_layers_0"]) == set(params["full_layers_0"])
    assert params["full_layers_0"]["wg"]["weight"].shape == (2, 1, 64, 128)
    assert params["dense_layers"]["norm4"]["scale"].shape == (2, 64)
    assert params["window_layers_0"]["moe"]["shared"]["gate"].shape == (
        2, 3, 64, 32)
    assert "lm_head" in params


def test_logits_equal_the_reference_and_bfloat16_stays_near():
    cfg = tiny(experts_held=4, expert_offset=2)
    mesh, model = on_mesh(cfg, 1, attn_impl="xla")
    params, (want, want_g) = R.reference(cfg)
    want = float(want)
    ids, tgt, pos = batch(cfg)
    with jax.default_matmul_precision("highest"):
        logits = model.make_forward(mesh)(params, ids, pos)
    # the loss from the program's own logits is the reference's
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0]
    assert abs(float(jnp.mean(lse - picked)) - want) <= 1e-5 * want
    half = build_model("swa_moe", tiny("bfloat16", experts_held=4,
                                       expert_offset=2), attn_impl="xla")
    got, grads = jax.jit(jax.value_and_grad(half.make_loss(mesh)))(
        params, ids, tgt, pos)
    assert abs(float(got) - want) <= 2e-2 * want
    norm = lambda g: float(jnp.sqrt(sum(
        jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g))))
    assert abs(norm(grads) - norm(want_g)) <= 0.1 * norm(want_g)


def test_no_top_k_choice_sits_on_a_tie():
    cfg = tiny()
    moe = SharedRoutedFFN(cfg.attn_dim, 32, cfg.num_experts, cfg.moe_top_k)
    p = moe.init(jax.random.key(3))
    x = jax.random.normal(jax.random.key(1), (512, cfg.attn_dim))
    s = np.sort(np.asarray(jax.nn.sigmoid(x @ p["router"])), axis=-1)
    assert np.min(s[:, -cfg.moe_top_k] - s[:, -cfg.moe_top_k - 1]) > 1e-6


# ---- what a kind means ----

def _hidden(cfg, params, ids, pos):
    mesh, model = on_mesh(cfg, 1, attn_impl="xla")
    with jax.default_matmul_precision("highest"):
        return np.asarray(model.make_forward(mesh)(params, ids, pos))


def test_a_window_layer_is_blind_past_its_window_and_a_full_layer_is_not():
    """All layers windows of 16: with 2 layers a row's logits depend on at
    most 2 x 15 rows back, so changing token 0 leaves rows 31.. alone; one
    full layer among them and every later row moves."""
    base = dict(num_dense_layers=1, experts_held=None)
    windows = dataclasses.replace(
        tiny(layer_types=("sliding_attention",) * 2, **base), num_layers=2)
    mixed = dataclasses.replace(
        tiny(layer_types=("sliding_attention", "full_attention"), **base),
        num_layers=2)
    ids, _, pos = batch(windows)
    other = ids.copy()
    other[:, 0] = (other[:, 0] + 7) % windows.vocab_size
    for cfg, blind in ((windows, True), (mixed, False)):
        params = build_model("swa_moe", cfg).init(jax.random.key(0))
        a, b = _hidden(cfg, params, ids, pos), _hidden(cfg, params, other,
                                                       pos)
        moved = np.abs(a - b).max(axis=(0, 2))
        assert moved[0] > 1e-4 and moved[15] > 0 and moved[30] > 0
        assert (moved[31:].max() == 0.0) == blind


def test_a_full_layer_takes_no_positions_and_a_window_layer_does():
    """Shift every position by 5: RoPE is relative, so neither kind's
    logits move; SCALE them by 2 and a window layer's do, a full layer's
    do not (it never reads them)."""
    base = dict(num_dense_layers=0, experts_held=None, sliding_window=1000)
    for name, reads in (("sliding_attention", True),
                        ("full_attention", False)):
        cfg = dataclasses.replace(tiny(layer_types=(name,), **base),
                                  num_layers=1)
        params = build_model("swa_moe", cfg).init(jax.random.key(0))
        ids, _, pos = batch(cfg)
        a = _hidden(cfg, params, ids, pos)
        np.testing.assert_allclose(_hidden(cfg, params, ids, pos + 5), a,
                                   atol=2e-4)
        assert (np.abs(_hidden(cfg, params, ids, 2 * pos) - a).max()
                > 1e-3) == reads


def test_a_window_over_the_whole_sequence_is_the_causal_call():
    model = build_model("swa_moe", tiny())
    assert model._attn_mask(64, "window") == sliding_window(16)
    assert model._attn_mask(16, "window") is None
    assert model._attn_mask(64, "full") is None
    assert model.unrotated_kinds == ("full",)
    assert model.embed_scale == 8.0 and model.router_bias_speed == 0.001
    assert build_model("swa_moe", tiny(mup_enabled=False)).embed_scale is None
    assert build_model("swa_moe", tiny(
        load_balance_coeff=None)).router_bias_speed is None


def test_the_published_pattern_is_a_segment_and_two_period_blocks():
    cfg = dataclasses.replace(tiny(layer_types=PUBLISHED), num_layers=32)
    model = build_model("swa_moe", cfg)
    assert model._pattern == (
        "dense_layers", (("window_layers_0", 1), ("full_layers_0", 1)),
        (("window_layers_1", 3), ("full_layers_1", 1)))
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    assert shapes["window_layers_1"]["wq"]["weight"].shape == (7, 3, 64, 128)
    with pytest.raises(ValueError, match="the swa_moe family has"):
        build_model("swa_moe", dataclasses.replace(
            tiny(layer_types=("conv",) * 10)))
    with pytest.raises(ValueError, match="names 3 layers"):
        build_model("swa_moe", tiny(layer_types=("full_attention",) * 3))


# ---- the shares ----

def test_the_four_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Four jobs hold two experts each of one layer's 8, each beside the
    shared expert. Their routed parts and the shared expert ONCE are the
    layer a job holding all 8 computes: the weights are normalised over
    all chosen experts, held or not, and scaled by `route_scale`; the
    router, the bias and the counts are the same on every share."""
    d, f, E, k = 32, 16, 8, 2
    kw = dict(n_shared=1, score="sigmoid", scaling=2.826)
    whole = SharedRoutedFFN(d, f, E, k, **kw)
    p = whole.init(jax.random.key(0))
    p["bias"] = 0.01 * jax.random.normal(jax.random.key(2), (E,))
    x = jax.random.normal(jax.random.key(1), (2, 64, d))
    no_routed = {**p, **{n: jnp.zeros_like(p[n])
                         for n in ("gate", "up", "down")}}
    with jax.default_matmul_precision("highest"):
        want, counted = apply_moe(whole, p, x)
        shared, _ = apply_moe(whole, no_routed, x)      # the shared expert
        parts = []
        for share in range(4):
            lo = 2 * share
            held = SharedRoutedFFN(d, f, E, k, held=2, offset=lo, **kw)
            ps = {**p, **{n: p[n][lo:lo + 2] for n in ("gate", "up", "down")}}
            out, c = apply_moe(held, ps, x)
            np.testing.assert_array_equal(c["routed"], counted["routed"])
            parts.append(out - shared)
    np.testing.assert_allclose(sum(parts) + shared, want, atol=2e-5)
    assert float(jnp.abs(shared).max()) > 1e-3


# ---- the bias rule ----

def test_the_rule_is_the_three_lines_on_hand_made_counts():
    routed = jnp.asarray([[10.0, 2.0, 4.0, 0.0], [3.0, 3.0, 3.0, 3.0],
                          [9.0, 1.0, 1.0, 1.0]])
    delta = np.asarray(router_bias_step(routed, 0.001))
    # mean 4: under -> +, over -> -, on it -> 0; then zero-mean
    np.testing.assert_allclose(delta[0], np.asarray(
        [-0.001, 0.001, 0.0, 0.001]) - 0.00025, atol=1e-9)
    np.testing.assert_array_equal(delta[1], 0.0)
    np.testing.assert_allclose(delta[2], np.asarray(
        [-0.001, 0.001, 0.001, 0.001]) - 0.0005, atol=1e-9)
    np.testing.assert_allclose(delta.sum(-1), 0.0, atol=1e-9)
    assert delta.dtype == np.float32
    bias = jnp.full((3, 4), 0.5)
    np.testing.assert_array_equal(bias_rule(bias, routed, 0.001),
                                  bias + delta)


@functools.lru_cache(maxsize=None)
def _steps(cfg, n, with_counters=True):
    """`n` steps on one repeated batch: (model, [(parameters before, after,
    optimizer state after, what the step returned), ...]) on the host."""
    mesh, model = on_mesh(cfg, 1, attn_impl="xla")
    params = model.init(jax.random.key(0))
    opt = init_adam_state(params)
    step = build_train_step(model, mesh,
                            OptimizerConfig(lr=3e-3, warmup_steps=2),
                            with_grad_norm=True, with_counters=with_counters)
    ids, tgt, pos = batch(cfg, b=4, t=64)
    out = []
    for _ in range(n):
        before = jax.device_get(params)
        params, opt, rest = step(params, opt, ids, tgt, pos)
        out.append((before, *jax.device_get((params, opt, rest))))
    return model, out


def test_the_step_moves_the_bias_by_the_rule_and_by_nothing_else():
    """After a step every bias leaf is the old one plus the rule's delta of
    the counts THAT step returned, exactly; Adam's moments of the leaf stay
    zero; the counter says how far an entry moved."""
    cfg = tiny(experts_held=4, expert_offset=2)
    model, steps = _steps(cfg, 8)
    for before, after, opt, (_, _, c) in steps[:3]:
        assert c["routed"].shape == (8, 8)      # eight expert layers
        rows = model.expert_layer_rows(before, c["routed"])
        assert sorted(rows) == ["full_layers_0", "window_layers_0"]
        assert rows["window_layers_0"].shape == (2, 3, 8)
        # rows follow the layers: period 0's three windows, its full, ...
        np.testing.assert_array_equal(rows["full_layers_0"][1, 0],
                                      c["routed"][7])
        np.testing.assert_array_equal(rows["window_layers_0"][1, 2],
                                      c["routed"][6])
        moved = []
        for key, n in rows.items():
            want = before[key]["moe"]["bias"] + router_bias_step(n, 0.001)
            np.testing.assert_array_equal(after[key]["moe"]["bias"], want)
            np.testing.assert_allclose(
                np.asarray(after[key]["moe"]["bias"]).sum(-1), 0.0,
                atol=1e-7)
            assert not np.any(opt.mu[key]["moe"]["bias"])
            assert not np.any(opt.nu[key]["moe"]["bias"])
            moved.append(np.abs(np.asarray(router_bias_step(n, 0.001))))
        assert float(c["router_bias_step"]) == pytest.approx(
            float(np.mean(np.concatenate([m.reshape(-1) for m in moved]))))
        assert 0.0 < float(c["router_bias_step"]) <= 0.002
    said = moe_counters_summary(jax.device_get(steps[-1][3][2]), cfg, 4 * 64)
    assert said["router_bias_step"] > 0.0


def test_the_rule_runs_without_counters_asked_and_not_without_a_speed():
    on = _steps(small(), 1, with_counters=False)[1][0]
    assert np.any(on[1]["window_layers_0"]["moe"]["bias"])
    assert len(on[3]) == 2                      # (loss, grad norm) only
    off = _steps(small(load_balance_coeff=None), 1)[1][0]
    assert not np.any(off[1]["window_layers_0"]["moe"]["bias"])
    assert "router_bias_step" not in off[3][2]
    mesh, model = on_mesh(small(), 1)
    with pytest.raises(ValueError, match="ONE step's counts"):
        build_grad_accum_step(model, mesh, OptimizerConfig())


def test_the_bias_is_restored_from_a_checkpoint(tmp_path):
    from distributed_pytorch_from_scratch_tpu.training.checkpoint import (
        load_checkpoint, save_checkpoint)
    model, steps = _steps(tiny(experts_held=4, expert_offset=2), 8)
    params = steps[-1][1]
    assert np.any(params["full_layers_0"]["moe"]["bias"])
    save_checkpoint(str(tmp_path), 2, 1.0, model.to_canonical(params),
                    model.canonical_specs(), 1)
    fresh = model.init(jax.random.key(9))
    restored, _, at = load_checkpoint(str(tmp_path), 2, fresh,
                                      model.canonical_specs())
    assert at == 2
    for key in ("window_layers_0", "full_layers_0"):
        np.testing.assert_array_equal(restored[key]["moe"]["bias"],
                                      params[key]["moe"]["bias"])


def test_the_load_falls_with_the_rule_on_against_off():
    """200 steps on one repeated skewed batch at a speed large enough to
    matter at this size (three layers): the experts' load, max over mean,
    averaged over the expert layers, starts the same (the first step reads
    a bias of zeros either way) and ends lower with the rule than
    without."""
    def run(speed):
        cfg = small(load_balance_coeff=speed)
        mesh, model = on_mesh(cfg, 1, attn_impl="xla")
        params = model.init(jax.random.key(0))
        opt = init_adam_state(params)
        step = build_train_step(model, mesh, OptimizerConfig(
            lr=1e-3, warmup_steps=10), with_counters=True)
        rng = np.random.default_rng(0)
        ids = (rng.zipf(1.3, (4, 33)) % 500 + 3).astype(np.int32)
        pos = np.tile(np.arange(32, dtype=np.int32), (4, 1))
        loads = []
        for _ in range(200):
            params, opt, (_, c) = step(params, opt, ids[:, :-1], ids[:, 1:],
                                       pos)
            loads.append(moe_counters_summary(jax.device_get(c), cfg,
                                              128)["load_max_over_mean"])
        return loads[0], float(np.mean(loads[-20:]))
    first_on, last_on = run(0.01)
    first_off, last_off = run(None)
    assert first_on == first_off
    assert last_on < last_off and last_on < first_on


# ---- what the family does not run ----

@pytest.mark.parametrize("kw,message", [
    (dict(pp_size=2), "pp_size > 1"),
    (dict(cp_size=2), "cp_size > 1"),
    (dict(ep_size=2), "ep_size > 1"),
    (dict(tp_size=2, sequence_parallel=True), "sequence_parallel=True"),
    (dict(tp_size=2, tp_overlap="ring"), "does not compose with MoE"),
    (dict(attn_t_real=100), "attn_t_real"),
    (dict(zero3_axis="dp"), "ZeRO stage 3"),
])
def test_what_the_family_does_not_run_is_refused_where_it_is_built(
        kw, message):
    with pytest.raises(ValueError, match=message):
        build_model("swa_moe", tiny(), **kw)


@pytest.mark.parametrize("cfg,message", [
    (model_preset("tiny"), "needs cfg.swa_moe"),
    (dataclasses.replace(tiny(), num_experts=0), "num_experts > 0"),
    (tiny(sliding_window=0), "sees itself"),
    (tiny(num_dense_layers=10), "must leave an expert layer"),
])
def test_a_family_needs_its_own_facts(cfg, message):
    with pytest.raises(ValueError, match=message):
        build_model("swa_moe", cfg)


def test_decode_is_refused_with_the_reason():
    from distributed_pytorch_from_scratch_tpu.models.decode import (
        GreedyDecoder)
    mesh, model = on_mesh(tiny(), 1)
    assert not model.decodable
    with pytest.raises(ValueError, match="cannot be decoded or served"):
        GreedyDecoder(model, mesh, 32)


# ---- the step, the memory facts, the CLI ----

def test_the_train_step_trains_and_counts_rows():
    cfg = tiny(experts_held=4, expert_offset=2)
    model, steps = _steps(cfg, 8)
    losses = [float(rest[0]) for *_, rest in steps]
    assert np.isfinite(losses).all() and min(losses[-3:]) < losses[0]
    c = steps[-1][3][2]
    np.testing.assert_array_equal(c["routed"].sum(-1),
                                  np.full(8, 4 * 64 * cfg.moe_top_k))
    np.testing.assert_array_equal(c["rows_here"],
                                  c["routed"][:, 2:6].sum(-1))
    np.testing.assert_array_equal(c["rows_computed"], c["rows_here"])
    flops = model_flops_per_step(cfg, 4, 64, model.num_params(cfg))
    # attention at each kind's live entries: 8 window layers of 16 rows, 2
    # full layers of the triangle
    live = 8 * (16 * (2 * 64 - 15) // 2) + 2 * (64 * 65 // 2)
    assert flops == pytest.approx(
        SlidingWindowMoETransformer.flops_per_step(cfg, 4, 64,
                                                   model.num_params(cfg)))
    without = SlidingWindowMoETransformer.flops_per_step(
        dataclasses.replace(cfg, num_heads=0), 4, 64, model.num_params(cfg))
    assert flops - without == 12 * 4 * 4 * live * 32


def test_train_cli_runs_the_family(tmp_path, capsys):
    import json
    from distributed_pytorch_from_scratch_tpu import train as train_mod
    tokens = token_file(tmp_path)
    train_mod.main([
        "--family", "swa_moe", "--model", "tiny-swa-moe", "--tp_size", "2",
        "--data_path", str(tokens), "--save_dir", str(tmp_path / "ckpt"),
        "--batch_size", "4", "--maxlen", "64", "--max_steps", "4",
        "--log_interval", "2", "--save_interval", "100",
        "--warmup_steps", "2"])
    out = capsys.readouterr().out
    assert "model[swa_moe]" in out and "rows_here_per_token" in out
    assert "rows_computed_per_token" in out
    assert "rows_walked_per_token" in out
    assert "router_bias_step" in out
    events = [json.loads(line) for line in
              open(tmp_path / "ckpt" / "logs" / "metrics.jsonl")]
    moe = [e for e in events if e.get("tag") == "moe_counters"]
    assert moe and moe[-1]["router_bias_step"] > 0.0
    with pytest.raises(SystemExit, match="reads the config field"):
        train_mod.main(["--family", "bd_moe", "--model", "tiny-swa-moe",
                        "--data_path", str(tokens),
                        "--save_dir", str(tmp_path / "x")])


def test_the_memory_facts_count_the_gate_the_chunk_and_the_shared_expert():
    eighth = build_model("swa_moe", tiny(experts_held=1))
    moe = eighth._mods["moe"]
    assert moe.chunk_share == 1 / 8 and moe.n_shared == 1
    assert moe.scaling == 2.826 and moe.score == "sigmoid"
    attn = 7 * 4 * 32 + 6 * 2 * 32
    # (the last term: what the chip counts beside these, set from cell 9)
    assert eighth.layer_extra_elems_per_token == attn + (0.125 * 2 + 1) * (
        6 * 64 + 5 * 32) - 1.69 * 64
    assert (eighth.head_dim, eighth.kv_dim) == (32, 64)


# ---- the counts at the published widths ----

def published(**facts):
    sw = dict(layer_types=PUBLISHED, head_dim=128, moe_intermediate_size=1024,
              sliding_window=2048, num_dense_layers=2, route_scale=2.826,
              load_balance_coeff=0.001)
    sw.update(facts)
    return ModelConfig(
        attn_dim=2048, ffn_dim=6144, num_heads=32, num_kv_heads=4,
        num_layers=len(sw["layer_types"]), vocab_size=200192, maxlen=131072,
        rope_theta=10000.0, num_experts=128, moe_top_k=8,
        compute_dtype="bfloat16", swa_moe=SwaMoEConfig(**sw))


def test_the_cut_at_the_published_widths_counts_705_474_304():
    whole = published()
    n = SlidingWindowMoETransformer.num_params(whole)
    # the bias's 128 a layer is state the published count does not list
    assert 26.0e9 < n < 26.2e9
    cut = dataclasses.replace(
        published(layer_types=("sliding_attention",) * 4
                  + ("full_attention",), num_dense_layers=1,
                  experts_held=16), vocab_size=25024)
    counts = SlidingWindowMoETransformer.param_counts(cut)
    attn = 3 * 8_388_608 + 2 * 1_048_576 + 256
    assert attn == 27_263_232
    assert counts["dense_layers"] == attn + 8_192 + 37_748_736 == 65_020_160
    an_expert_layer = attn + 8_192 + 262_144 + 128 + 17 * 6_291_456
    assert an_expert_layer == 134_488_448
    assert counts["window_layers"] == 3 * an_expert_layer
    assert counts["full_layers"] == an_expert_layer
    assert counts["embedding_and_head"] == 102_498_304
    assert sum(counts.values()) == 705_474_304
    model = build_model("swa_moe", cut)
    assert model._pattern == (
        "dense_layers", (("window_layers_0", 3), ("full_layers_0", 1)))
    leaves = jax.tree.leaves(jax.eval_shape(model.init, jax.random.key(0)))
    assert sum(x.size for x in leaves) == 705_474_304
