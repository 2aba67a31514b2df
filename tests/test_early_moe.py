"""The `early_moe` family (models/early_moe.py): a grouped-query expert
decoder whose router reads the LAYER'S INPUT, before attention, over
ReLU-gated experts, with a full-attention layer (no positions) and three
sliding-window layers (RoPE) a period, two norms a layer, no shared expert.
CPU, tiny sizes.

* the program against the plain reference (models/vanilla_early_moe.py,
  whose layers are looped, whose mask is a boolean matrix from `i - j` and
  whose router's product from the layer's input is written in the open):
  logits, loss and EVERY gradient leaf, float32 tight at tp 1 and tp 2 and
  under the flash kernels (the interpreter, a group of 3), bfloat16 inside a
  stated band, on a job that holds a slice of the experts;
* the early router is real: the attention's weights do not move `routed`,
  the router's gradient reaches the layer's input past the attention half,
  and a reference whose router reads the post-attention stream (or the
  normed input) is another model by far more than the tolerance; so is one
  with SiLU in the experts;
* every rung of the remat ladder gives the same loss and gradients;
* the shares test: the parts all four shares of a layer give add up to the
  uncut layer's output (no shared expert to count once), through both
  movers' paths and under the early router;
* what the family does not run is refused with a message; the CLI;
* the counts at the published widths (656,529,920 in the cut, 21.5 B
  published).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from family_recipe import Recipe, apply_moe, token_file, worst_leaf

from distributed_pytorch_from_scratch_tpu.config import (
    EarlyMoEConfig, ModelConfig, OptimizerConfig, model_preset)
from distributed_pytorch_from_scratch_tpu.models import build_model
from distributed_pytorch_from_scratch_tpu.models.early_moe import (
    EarlyRouterMoETransformer)
from distributed_pytorch_from_scratch_tpu.models.stack import REMAT_RUNGS
from distributed_pytorch_from_scratch_tpu.models.vanilla_early_moe import (
    reference_loss_routed, sizes_of, vanilla_loss)
from distributed_pytorch_from_scratch_tpu.ops.attention import sliding_window
from distributed_pytorch_from_scratch_tpu.parallel.moe import SharedRoutedFFN
from distributed_pytorch_from_scratch_tpu.training.metrics import (
    model_flops_per_step)
from distributed_pytorch_from_scratch_tpu.training.train_step import (
    build_train_step)

PERIOD = (0, 1, 1, 1)


# the family's own: its reference (sequences of 64 from id 3 up: the recipe's)
R = Recipe("early_moe", vanilla_loss)
tiny, batch, on_mesh, reference = R.tiny, R.batch, R.on_mesh, R.reference


def one_period(**facts):
    return dataclasses.replace(
        tiny(sliding_window_layout=PERIOD, rope_layout=PERIOD, **facts),
        num_layers=4)


# ---- the program against the plain reference ----

@pytest.mark.parametrize("tp,impl,t", [(1, "xla", 64), (2, "xla", 64),
                                       (1, "flash_interpret", 128)])
def test_loss_and_every_gradient_leaf_equal_the_reference(tp, impl, t):
    """A period block of two periods SCANNED (the program) against eight
    layers LOOPED (the reference), on a job that holds experts 2..5 of 8; a
    window of 16 rows in a sequence of 64 (or 128 under the kernels, 6
    query heads over 2: a group of 3). Leaves to 1e-5 of their largest
    entry."""
    cfg = tiny(experts_held=4, expert_offset=2)
    params, (want, want_g) = reference(cfg, t)
    got, got_g = R.program(cfg, tp=tp, t=t, attn_impl=impl)
    model = build_model("early_moe", cfg)
    assert model._pattern == ((("full_layers_0", 1), ("window_layers_0", 3)),)
    assert [model._kind(k) for k in model._layer_keys] == ["full", "window"]
    assert abs(float(got) - float(want)) <= 1e-5 * abs(float(want))
    assert len(jax.tree.leaves(got_g)) == 23
    err, at = worst_leaf(want_g, got_g)
    assert err <= 1e-5, at
    # both kinds of layer hold the same parameters: two norms, four
    # projections with no bias, a router with no bias leaf, no shared expert
    assert set(params["window_layers_0"]) == set(params["full_layers_0"]) == {
        "norm1", "wq", "wk", "wv", "wo", "norm2", "moe"}
    assert set(params["full_layers_0"]["moe"]) == {"router", "gate", "up",
                                                   "down"}
    assert params["window_layers_0"]["wq"]["weight"].shape == (2, 3, 64, 192)
    assert params["full_layers_0"]["moe"]["gate"].shape == (2, 1, 4, 64, 32)
    assert params["full_layers_0"]["moe"]["router"].shape == (2, 1, 64, 8)
    assert "lm_head" in params
    # the router's gradient is alive in both kinds of layer
    for key in ("full_layers_0", "window_layers_0"):
        assert float(jnp.abs(got_g[key]["moe"]["router"]).max()) > 1e-6


def test_logits_equal_the_reference_and_bfloat16_stays_in_its_band():
    """float32 logits to 2e-5; bfloat16 compute over the same float32
    parameters: the loss within 2% and the gradient's norm within 10% (a
    softmax top-2 of 8 with nothing between the router and the residual
    stream flips a few pairs at this size under bfloat16)."""
    cfg = tiny(experts_held=4, expert_offset=2)
    mesh, model = on_mesh(cfg, 1, attn_impl="xla")
    params, (want, want_g) = reference(cfg)
    want = float(want)
    ids, tgt, pos = batch(cfg)
    with jax.default_matmul_precision("highest"):
        logits = model.make_forward(mesh)(params, ids, pos)
        ref_logits = reference_loss_routed(
            params, ids, tgt, pos, sizes=sizes_of(cfg), expert_offset=2,
            rope_theta=cfg.rope_theta, eps=cfg.early_moe.rms_norm_eps,
            logits_too=True)[2]
    np.testing.assert_allclose(logits, ref_logits, atol=2e-5)
    half = build_model("early_moe", tiny("bfloat16", experts_held=4,
                                         expert_offset=2), attn_impl="xla")
    got, grads = jax.jit(jax.value_and_grad(half.make_loss(mesh)))(
        params, ids, tgt, pos)
    assert abs(float(got) - want) <= 2e-2 * want
    norm = lambda g: float(jnp.sqrt(sum(
        jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g))))
    assert abs(norm(grads) - norm(want_g)) <= 0.1 * norm(want_g)


# ---- the early router is real ----

def _routed(cfg, params, **kw):
    mesh, model = on_mesh(cfg, 1, attn_impl="xla", **kw)
    ids, tgt, pos = batch(cfg)
    with jax.default_matmul_precision("highest"):
        _, counters = jax.jit(model.make_loss(mesh, with_counters=True))(
            params, ids, tgt, pos)
    return np.asarray(counters["routed"])


def test_the_attention_weights_do_not_move_the_first_layers_routing():
    """The first layer's router reads the embedding's rows: whatever its
    attention computes, its `routed` counts stand; and they are the counts
    of `top_k(embedding @ router)` computed here by hand. A later layer's
    input holds the earlier layers' attention, so its counts may move."""
    cfg = one_period()
    params = build_model("early_moe", cfg).init(jax.random.key(0))
    before = _routed(cfg, params)
    shaken = jax.tree.map(lambda a: a, params)
    for key in ("full_layers_0", "window_layers_0"):
        for name in ("wq", "wk", "wv", "wo"):
            w = shaken[key][name]["weight"]
            shaken[key][name] = {"weight": w + 0.5 * jax.random.normal(
                jax.random.key(7), w.shape)}
    after = _routed(cfg, shaken)
    np.testing.assert_array_equal(after[0], before[0])
    assert np.abs(after[1:] - before[1:]).sum() > 0
    ids, _, _ = batch(cfg)
    x = np.asarray(params["embedding"]["weight"])[ids].reshape(-1, 64)
    logits = x @ np.asarray(params["full_layers_0"]["moe"]["router"][0, 0])
    chosen = np.argsort(-logits, axis=-1)[:, :cfg.moe_top_k]
    np.testing.assert_array_equal(
        before[0], np.bincount(chosen.reshape(-1), minlength=8))


def test_the_routers_gradient_reaches_the_layers_input(monkeypatch):
    """d loss / d embedding with the router's input cut off from autodiff
    (`stop_gradient` on `router_x`) is another gradient: the router's
    cotangent enters the residual stream at the layer's input, before
    attention. The router's own leaf gets the same gradient either way."""
    cfg = dataclasses.replace(
        tiny(sliding_window_layout=(0,), rope_layout=(0,)), num_layers=1)
    mesh, model = on_mesh(cfg, 1, attn_impl="xla")
    params = model.init(jax.random.key(0))
    ids, tgt, pos = batch(cfg)

    def grads():
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.grad(model.make_loss(mesh)))(
                params, ids, tgt, pos)

    with_router = grads()
    whole = SharedRoutedFFN.apply
    monkeypatch.setattr(
        SharedRoutedFFN, "apply",
        lambda self, p, x, dtype, router_x: whole(
            self, p, x, dtype, jax.lax.stop_gradient(router_x)))
    without = grads()
    moved = np.abs(np.asarray(with_router["embedding"]["weight"])
                   - np.asarray(without["embedding"]["weight"])).max()
    assert moved > 1e-6
    np.testing.assert_allclose(
        with_router["full_layers_0"]["moe"]["router"],
        without["full_layers_0"]["moe"]["router"], atol=1e-7)


@pytest.mark.parametrize("variant,loss_apart", [
    (dict(router_input="post_attention"), 1e-4),
    (dict(router_input="normed_input"), 1e-5),
    (dict(activation=jax.nn.silu), 1e-4)])
def test_another_routers_input_or_activation_is_another_model(variant,
                                                              loss_apart):
    """The program equals the reference to 1e-5 (above); a reference whose
    router reads the post-attention normed stream (what every other family
    does), or the NORMED layer input (the other reading of the published
    code), or whose experts gate by SiLU, differs from the program by ten
    times that in the loss (a fresh model's loss is ln(vocabulary) and
    hardly sees its layers; the normed input, a positive multiple a token
    of the input itself, chooses the SAME experts and only weighs them
    otherwise: its loss is just outside the tolerance) and by a thousand
    times in the gradients."""
    cfg = tiny(experts_held=4, expert_offset=2)
    params, (want, want_g) = reference(cfg)
    _, (other, other_g) = reference(cfg, **variant)
    got, got_g = R.program(cfg, attn_impl="xla")
    assert abs(float(got) - float(want)) <= 1e-5 * float(want)
    assert abs(float(got) - float(other)) > loss_apart * float(want)
    assert worst_leaf(other_g, got_g)[0] > 1e-2


def test_the_activation_is_the_familys_fact_through_both_movers():
    """ReLU against SiLU in `SharedRoutedFFN` itself, the layer's output and
    the gradient of every leaf, at a held share of a half and of an eighth
    (`walk_chunks` at both since PR 71, whose transpose is written by hand
    and takes the activation as its second static argument; `take_held` in,
    `sum_held` back): each against the held experts applied one by one with
    the activation in the open."""
    d, f, E, k = 32, 16, 8, 2
    x = jax.random.normal(jax.random.key(1), (2, 64, d))
    for held, name, act in ((4, "relu", lambda z: jnp.maximum(z, 0)),
                            (1, "relu", lambda z: jnp.maximum(z, 0)),
                            (1, "silu", jax.nn.silu)):
        layer = SharedRoutedFFN(d, f, E, k, held=held, offset=1, n_shared=0,
                                score="softmax", activation=name)
        assert layer.chunk_share == held / 8
        p = layer.init(jax.random.key(0))

        def plain(p, x):
            xf = x.reshape(-1, d)
            top, chosen = jax.lax.top_k(xf @ p["router"], k)
            w = jax.nn.softmax(top, -1)
            out = 0.0
            for e in range(held):
                w_e = jnp.sum(jnp.where(chosen == e + 1, w, 0.0), -1)
                out += w_e[:, None] * (
                    (act(xf @ p["gate"][e]) * (xf @ p["up"][e]))
                    @ p["down"][e])
            return out.reshape(x.shape)

        probe = jax.random.normal(jax.random.key(2), x.shape)
        with jax.default_matmul_precision("highest"):
            want, want_g = jax.value_and_grad(
                lambda p, x: jnp.sum(plain(p, x) * probe), (0, 1))(p, x)
            got, got_g = jax.value_and_grad(
                lambda p, x: jnp.sum(apply_moe(layer, p, x)[0] * probe),
                (0, 1))(p, x)
        assert abs(float(got) - float(want)) <= 1e-4 * abs(float(want))
        assert worst_leaf(want_g, got_g)[0] <= 1e-4
    with pytest.raises(ValueError, match="activation must be one of"):
        SharedRoutedFFN(d, f, E, k, activation="gelu")


# ---- the remat ladder ----

@pytest.mark.parametrize("remat", [False, *REMAT_RUNGS])
def test_every_remat_rung_gives_the_same_loss_and_gradients(remat):
    """The layer's input rides past the attention half to the router under
    every rung (it is the remat boundary's own operand): loss and every
    gradient leaf equal the reference's, tp 2 at the rungs that name the
    row-linear's output."""
    cfg = one_period(experts_held=4, expert_offset=2)
    params, (want, want_g) = reference(cfg)
    tp = 2 if remat in ("attn_proj", "dots") else 1
    got, got_g = R.program(cfg, tp, attn_impl="xla", remat=remat)
    assert abs(float(got) - float(want)) <= 1e-5 * abs(float(want))
    err, at = worst_leaf(want_g, got_g)
    assert err <= 1e-5, at


# ---- the shares ----

def test_the_four_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Four jobs hold two experts each of one layer's 8 (a quarter: the
    cell's share). Their parts are the layer a job holding all 8 computes,
    with no shared expert to count once: the weights are a softmax over
    the chosen, held or not; the router reads `router_x`, another tensor
    than the experts read, and the counts are the same on every share."""
    d, f, E, k = 32, 16, 8, 2
    kw = dict(n_shared=0, score="softmax", activation="relu")
    whole = SharedRoutedFFN(d, f, E, k, **kw)
    p = whole.init(jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2, 64, d))
    router_x = jax.random.normal(jax.random.key(2), (2, 64, d))
    with jax.default_matmul_precision("highest"):
        want, counted = apply_moe(whole, p, x, router_x)
        same_input, _ = apply_moe(whole, p, x)
        parts = []
        for share in range(4):
            lo = 2 * share
            held = SharedRoutedFFN(d, f, E, k, held=2, offset=lo, **kw)
            assert held.chunk_share == 0.25
            ps = {**p, **{n: p[n][lo:lo + 2] for n in ("gate", "up", "down")}}
            out, c = apply_moe(held, ps, x, router_x)
            np.testing.assert_array_equal(c["routed"], counted["routed"])
            parts.append(out)
    np.testing.assert_allclose(sum(parts), want, atol=2e-5)
    assert float(jnp.abs(want - same_input).max()) > 1e-2
    assert "shared" not in p and "bias" not in p


# ---- what a kind means ----

def test_a_window_over_the_whole_sequence_is_the_causal_call():
    model = build_model("early_moe", tiny())
    assert model._attn_mask(64, "window") == sliding_window(16)
    assert model._attn_mask(16, "window") is None
    assert model._attn_mask(64, "full") is None
    assert model.unrotated_kinds == ("full",)
    assert model.router_reads_layer_input and model.embed_scale is None
    assert model.router_bias_speed is None
    moe = model._mods["moe"]
    assert (moe.score, moe.activation, moe.n_shared) == ("softmax", "relu", 0)


def test_the_published_pattern_is_one_block_of_thirteen_periods():
    cfg = dataclasses.replace(
        tiny(sliding_window_layout=PERIOD * 13, rope_layout=PERIOD * 13),
        num_layers=52)
    model = build_model("early_moe", cfg)
    assert model._pattern == ((("full_layers_0", 1), ("window_layers_0", 3)),)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    assert shapes["window_layers_0"]["wq"]["weight"].shape == (13, 3, 64, 192)
    assert shapes["full_layers_0"]["wk"]["weight"].shape == (13, 1, 64, 64)


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
        build_model("early_moe", tiny(), **kw)


@pytest.mark.parametrize("cfg,message", [
    (model_preset("tiny"), "needs cfg.early_moe"),
    (dataclasses.replace(tiny(), num_experts=0), "num_experts > 0"),
    (tiny(sliding_window_size=0), "sees itself"),
    (tiny(sliding_window_layout=PERIOD), "names 4 layers"),
    (tiny(rope_layout=(1,) * 8), "rope_layout must equal"),
    (tiny(sliding_window_layout=(2,) * 8, rope_layout=(2,) * 8),
     "the early_moe family has"),
])
def test_a_family_needs_its_own_facts(cfg, message):
    with pytest.raises(ValueError, match=message):
        build_model("early_moe", cfg)


def test_decode_and_the_hand_reduced_gradients_are_refused():
    from distributed_pytorch_from_scratch_tpu.models.decode import (
        GreedyDecoder)
    mesh, model = on_mesh(tiny(), 1)
    assert not model.decodable and not model.hand_reduced_grads
    with pytest.raises(ValueError, match="cannot be decoded or served"):
        GreedyDecoder(model, mesh, 32)
    with pytest.raises(ValueError, match="ZeRO stage 2 is not made to work"):
        build_train_step(model, mesh, OptimizerConfig(), zero=2)


# ---- the step, the memory facts, the CLI ----

def test_the_train_step_trains_and_counts_rows():
    cfg = tiny(experts_held=4, expert_offset=2)
    losses, (_, _, c), (_, model, *_) = R.train(
        cfg, tp=1, steps=8, b=4, max_steps=20000, attn_impl="xla")
    assert np.isfinite(losses).all() and min(losses[-3:]) < losses[0]
    c = jax.device_get(c)
    assert c["routed"].shape == (8, 8)          # a row a layer, in order
    np.testing.assert_array_equal(c["routed"].sum(-1),
                                  np.full(8, 4 * 64 * cfg.moe_top_k))
    np.testing.assert_array_equal(c["rows_here"],
                                  c["routed"][:, 2:6].sum(-1))
    np.testing.assert_array_equal(c["rows_computed"], c["rows_here"])
    n = model.num_params(cfg)
    flops = model_flops_per_step(cfg, 4, 64, n)
    assert flops == pytest.approx(
        EarlyRouterMoETransformer.flops_per_step(cfg, 4, 64, n))
    # attention at each kind's live entries: 6 window layers of 16 rows, 2
    # full layers of the triangle
    live = 6 * (16 * (2 * 64 - 15) // 2) + 2 * (64 * 65 // 2)
    without = EarlyRouterMoETransformer.flops_per_step(
        dataclasses.replace(cfg, num_heads=0), 4, 64, n)
    assert flops - without == 12 * 4 * 6 * live * 32


def test_train_cli_runs_the_family(tmp_path, capsys):
    from distributed_pytorch_from_scratch_tpu import train as train_mod
    tokens = token_file(tmp_path)
    train_mod.main([
        "--family", "early_moe", "--model", "tiny-early-moe",
        "--tp_size", "2", "--data_path", str(tokens),
        "--save_dir", str(tmp_path / "ckpt"), "--batch_size", "4",
        "--maxlen", "64", "--max_steps", "4", "--log_interval", "2",
        "--save_interval", "100", "--warmup_steps", "2"])
    out = capsys.readouterr().out
    assert "model[early_moe]" in out and "rows_here_per_token" in out
    assert "rows_walked_per_token" in out
    with pytest.raises(SystemExit, match="reads the config field"):
        train_mod.main(["--family", "swa_moe", "--model", "tiny-early-moe",
                        "--data_path", str(tokens),
                        "--save_dir", str(tmp_path / "x")])


def test_the_memory_facts_count_the_chunk_at_the_held_share():
    quarter = build_model("early_moe", tiny(experts_held=2))
    moe = quarter._mods["moe"]
    assert moe.chunk_share == 0.25          # one mean share of the pairs
    attn = 5 * 6 * 32 + 6 * 2 * 32 - 2 * 64
    # (the last term: what the chip counts beside these, set from cell 10
    # at a chunk of a quarter of the pairs: PR 71)
    assert quarter.layer_extra_elems_per_token == attn + 0.25 * 2 * (
        6 * 64 + 5 * 32) + 9.5 * 64
    assert (quarter.head_dim, quarter.kv_dim) == (32, 64)
    eighth = build_model("early_moe", tiny(experts_held=1))
    assert eighth._mods["moe"].chunk_share == 1 / 8


# ---- the counts at the published widths ----

def published(layout=PERIOD * 13, **facts):
    return ModelConfig(
        attn_dim=2560, ffn_dim=0, num_heads=28, num_kv_heads=4,
        num_layers=len(layout), vocab_size=151936, maxlen=16384,
        rope_theta=1.5e6, num_experts=64, moe_top_k=6,
        compute_dtype="bfloat16", early_moe=EarlyMoEConfig(
            sliding_window_layout=layout, rope_layout=layout, head_dim=128,
            moe_ffn_hidden_size=768, sliding_window_size=4096, **facts))


def test_the_cut_at_the_published_widths_counts_656_529_920():
    n = EarlyRouterMoETransformer.num_params(published())
    assert 21.4e9 < n < 21.6e9
    cut = dataclasses.replace(published(PERIOD, experts_held=16),
                              vocab_size=37984)
    counts = EarlyRouterMoETransformer.param_counts(cut)
    attn = 2 * 2560 * 3584 + 2 * 2560 * 512
    assert attn == 20_971_520
    a_layer = attn + 5_120 + 163_840 + 16 * 3 * 2560 * 768
    assert a_layer == 115_512_320
    assert counts["window_layers"] == 3 * a_layer
    assert counts["full_layers"] == a_layer
    assert counts["embedding_and_head"] == 194_478_080
    assert sum(counts.values()) == 656_529_920
    model = build_model("early_moe", cut)
    assert model.num_params(cut) == 656_529_920
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == \
        656_529_920
    assert model._mods["moe"].chunk_share == 0.25
    # a chunk is one mean share of the 98,304 pairs a layer: four of them
    S, k = 16384, 6
    assert model._mods["moe"].chunk_rows(S * k) == S * k // 4 == 24576
