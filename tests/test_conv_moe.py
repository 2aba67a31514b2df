"""The `conv_moe` family (models/conv_moe.py): gated short-convolution
mixers and grouped-query attention layers with q/k norms in one family, a
leading dense segment and then periods of two lengths, a sigmoid router with
no shared expert over held experts, a tied head. CPU, tiny sizes, float32.

* the program against the plain reference (models/vanilla_conv_moe.py, which
  LOOPS its twelve layers): loss and EVERY gradient leaf, at tp 1 and tp 2,
  on a job that holds a slice of the experts; no top-k choice sits on a tie
  (the margin is asserted);
* the mixer against a loop over tokens: outputs and all three gradients,
  the first two positions (the padding), lengths 1 and 2; GDN's four taps
  and the mixer's three are one function;
* the pattern: the published 24 `layer_types` give the blocks the issue
  names, a pattern scanned equals the same layers looped, the cut is its
  first dense layer and first period;
* the share test (no shared expert to count once; a token with no choice
  held gets zero) and the forced router;
* the tied embedding's gradient is the sum of its two uses;
* the flash kernel at head 64 and a group of 4 against the XLA path;
* what the family does not run is refused with a message;
* `mla_moe` and `gdn_moe` lower to the text they lowered to before the
  pattern declaration;
* the counts at the published widths (507,820,288 in the cut).
"""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from family_recipe import (Recipe, apply_moe, hold_leaves, hold_loss,
                           lowered_text, mesh_of, token_file)
from jax.sharding import PartitionSpec as P

from distributed_pytorch_from_scratch_tpu.config import (
    ConvMoEConfig, ModelConfig, OptimizerConfig, model_preset)
from distributed_pytorch_from_scratch_tpu.models import build_model
from distributed_pytorch_from_scratch_tpu.models.conv_moe import (
    ConvMoETransformer, layer_blocks, layers_in_order)
from distributed_pytorch_from_scratch_tpu.models.vanilla_conv_moe import (
    vanilla_loss)
from distributed_pytorch_from_scratch_tpu.ops.attention import (
    causal_attention_xla)
from distributed_pytorch_from_scratch_tpu.ops.conv import (
    causal_depthwise_conv)
from distributed_pytorch_from_scratch_tpu.ops.pallas.flash_attention import (
    flash_attention)
from distributed_pytorch_from_scratch_tpu.parallel.moe import SharedRoutedFFN
from distributed_pytorch_from_scratch_tpu.parallel.shortconv import ShortConv
from distributed_pytorch_from_scratch_tpu.training.metrics import (
    model_flops_per_step, moe_counters_summary)
from distributed_pytorch_from_scratch_tpu.training.optim import (
    init_adam_state)
from distributed_pytorch_from_scratch_tpu.training.train_step import (
    build_train_step)

PUBLISHED = (("conv",) * 2 + ("full_attention", "conv", "conv", "conv") * 4
             + ("full_attention", "conv", "conv") * 2)


# the family's own: its reference, and sequences of 128 from id 0 up
R = Recipe("conv_moe", vanilla_loss, t=128, low=0)
tiny, batch, on_mesh = R.tiny, R.batch, R.on_mesh


# ---- the program against the plain reference ----

@pytest.mark.parametrize("tp,impl", [(1, "xla"), (2, "xla"),
                                     (1, "flash_interpret")])
def test_loss_and_every_gradient_leaf_equal_the_reference(tp, impl):
    """A dense segment and two period blocks SCANNED (the program) against
    twelve layers LOOPED (the reference), on a job that holds experts 2..5
    of 8. Leaves to 1e-5 of their largest entry."""
    cfg = tiny(experts_held=4, expert_offset=2)
    # (the parameters and the reference are one for the three layouts)
    params, (want, want_g) = R.reference(cfg)
    got, got_g = R.program(cfg, tp=tp, attn_impl=impl)
    model = build_model("conv_moe", cfg, tp_size=tp)
    assert model._pattern == (
        "dense_layers", (("attn_layers_0", 1), ("conv_layers_0", 2)),
        (("attn_layers_1", 1), ("conv_layers_1", 1)))
    hold_loss(want, got)
    assert len(hold_leaves(want_g, got_g, 1e-5)[0]) == 56
    # the selection bias is a leaf no gradient reaches; there is no shared
    # expert and no head of its own
    bias = got_g["conv_layers_0"]["moe"]["bias"]
    assert bias.shape == (2, 2, 8) and not np.any(bias)
    assert "shared" not in params["conv_layers_0"]["moe"]
    assert "lm_head" not in params
    assert params["dense_layers"]["conv"]["w_in"].shape == (2, 64, 3, 64)
    assert params["attn_layers_0"]["wq"]["weight"].shape == (2, 1, 64, 64)
    assert params["attn_layers_1"]["q_norm"]["scale"].shape == (2, 1, 16)
    assert params["conv_layers_1"]["conv"]["conv"].shape == (2, 1, 64, 3)


def test_no_top_k_choice_sits_on_a_tie():
    cfg = tiny()
    moe = SharedRoutedFFN(cfg.attn_dim, 32, cfg.num_experts, cfg.moe_top_k,
                          n_shared=0)
    p = moe.init(jax.random.key(3))
    x = jax.random.normal(jax.random.key(4), (256, cfg.attn_dim))
    with jax.default_matmul_precision("highest"):
        s = np.sort(np.asarray(jax.nn.sigmoid(x @ p["router"])), axis=-1)
    margin = s[:, -cfg.moe_top_k] - s[:, -cfg.moe_top_k - 1]
    assert margin.min() > 1e-5


def test_the_published_depth_builds_and_runs_at_a_tiny_width():
    """All 24 published `layer_types` with 2 dense layers, 8 channels a
    head: the program (a segment and two period blocks of 4 and 2 periods)
    against the looped reference, loss and the worst gradient leaf."""
    cfg = ModelConfig(
        attn_dim=32, ffn_dim=64, num_heads=4, num_kv_heads=2, num_layers=24,
        vocab_size=256, maxlen=64, rope_theta=1e6, num_experts=8,
        moe_top_k=2, conv_moe=ConvMoEConfig(
            layer_types=PUBLISHED, moe_intermediate_size=16,
            experts_held=2, expert_offset=4))
    params, (want, want_g) = R.reference(cfg)
    got, got_g = R.program(cfg)
    assert params["attn_layers_0"]["wo"]["weight"].shape[:2] == (4, 1)
    assert params["conv_layers_0"]["conv"]["w_out"].shape[:2] == (4, 3)
    assert params["conv_layers_1"]["conv"]["w_out"].shape[:2] == (2, 2)
    hold_loss(want, got)
    hold_leaves(want_g, got_g, 2e-5)


# ---- the mixer against a loop over tokens ----

def mixer_by_tokens(p, x):
    """The gated short convolution one token at a time, the last `taps - 1`
    gated inputs carried as a state that starts at zero."""
    taps = p["conv"].shape[-1]
    out = []
    for b in range(x.shape[0]):
        state = [jnp.zeros(x.shape[-1])] * (taps - 1)
        for t in range(x.shape[1]):
            B, C, u = (x[b, t] @ p["w_in"][:, i] for i in range(3))
            window = state + [B * u]
            c = sum(p["conv"][:, j] * window[j] for j in range(taps))
            out.append((C * c) @ p["w_out"])
            state = window[1:]
    return jnp.stack(out).reshape(x.shape)


def apply_on_one(module, params, x):
    mesh = mesh_of()
    fn = jax.shard_map(lambda p, x: module.apply(p, x), mesh=mesh,
                       in_specs=(module.specs(), P()), out_specs=P())
    return fn(params, x)


@pytest.mark.parametrize("t", [1, 2, 9])
def test_the_mixer_equals_a_loop_over_tokens(t):
    mixer = ShortConv(16, taps=3)
    p = mixer.init(jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2, t, 16))
    score = lambda f: lambda p, x: jnp.sum(jnp.sin(f(p, x)))
    with jax.default_matmul_precision("highest"):
        got = apply_on_one(mixer, p, x)
        want = mixer_by_tokens(p, x)
        got_g = jax.grad(score(lambda p, x: apply_on_one(mixer, p, x)))(p, x)
        want_g = jax.grad(score(mixer_by_tokens))(p, x)
    np.testing.assert_allclose(got, want, atol=1e-5)
    # the first two positions read the padding's zeros
    np.testing.assert_allclose(got[:, :2], want[:, :2], atol=1e-5)
    assert set(got_g) == {"w_in", "conv", "w_out"}
    for name in got_g:
        np.testing.assert_allclose(got_g[name], want_g[name], atol=2e-5)


def test_one_causal_depthwise_convolution_serves_three_and_four_taps():
    """`ops/conv.causal_depthwise_conv` over (b, t, channels) and over (b,
    t, heads, channels), against numpy's `convolve`: tap `taps - 1` reads
    the token itself."""
    u = np.asarray(jax.random.normal(jax.random.key(0), (2, 7, 3, 5)))
    w4 = np.asarray(jax.random.normal(jax.random.key(1), (3, 5, 4)))
    got4 = np.asarray(causal_depthwise_conv(jnp.asarray(u), jnp.asarray(w4)))
    got3 = np.asarray(causal_depthwise_conv(jnp.asarray(u[:, :, 0]),
                                            jnp.asarray(w4[0, :, :3])))
    assert got4.dtype == got3.dtype == np.float32
    for b in range(2):
        for c in range(5):
            np.testing.assert_allclose(
                got3[b, :, c],
                np.convolve(u[b, :, 0, c], w4[0, c, 2::-1])[:7], atol=1e-5)
            for h in range(3):
                np.testing.assert_allclose(
                    got4[b, :, h, c],
                    np.convolve(u[b, :, h, c], w4[h, c, ::-1])[:7],
                    atol=1e-5)
    # bfloat16 in, float32 sums out
    assert causal_depthwise_conv(jnp.asarray(u, jnp.bfloat16),
                                 jnp.asarray(w4)).dtype == jnp.float32


# ---- the pattern ----

def test_the_published_layer_types_give_a_segment_and_two_period_blocks():
    blocks = layer_blocks(PUBLISHED, 2)
    assert blocks == (
        (None, (("dense_layers", "conv", True, 2),)),
        (4, (("attn_layers_0", "attn", False, 1),
             ("conv_layers_0", "conv", False, 3))),
        (2, (("attn_layers_1", "attn", False, 1),
             ("conv_layers_1", "conv", False, 2))))
    # full attention at the published layers 2, 6, 10, 14, 18, 21
    assert [i for i, k in enumerate(PUBLISHED) if k != "conv"] == [
        2, 6, 10, 14, 18, 21]
    # the benchmark's cut, published layers 1-5: its first dense layer and
    # its first period, the same declaration
    assert layer_blocks(PUBLISHED[1:6], 1) == (
        (None, (("dense_layers", "conv", True, 1),)),
        (1, blocks[1][1]))
    # no leading dense layer; a pattern that opens with convolutions
    assert layer_blocks(("conv", "conv", "full_attention") * 2, 0) == (
        (2, (("conv_layers_0", "conv", False, 2),
             ("attn_layers_0", "attn", False, 1))),)
    with pytest.raises(ValueError, match="layer_types holds 'mamba'"):
        layer_blocks(("conv", "mamba"), 0)
    with pytest.raises(ValueError, match="one kind of mixer"):
        layer_blocks(("conv", "full_attention", "conv"), 2)


def test_a_pattern_scanned_equals_the_same_layers_looped():
    """The stack's blocks (a segment's scan, a scan over periods of scans)
    against a Python loop over `layers_in_order`, through the one layer
    body: the same hidden state, and the counters one row an expert layer
    in the order the layers ran."""
    cfg = tiny()
    mesh, model = on_mesh(cfg, 1, attn_impl="xla", remat=False)
    params = model.init(jax.random.key(0))
    ids, _, pos = batch(cfg, t=32)

    def scanned(params):
        x, aux, _ = model._resolved(32)._trunk(params, ids, pos)
        return x, aux["routed"], aux["rows_here"]

    def looped(params):
        m = model._resolved(32)
        x = m.embedding.apply(params["embedding"], ids)
        x, layer_pos = m._positions(params, x, pos, jnp.float32)
        routed, rows = [], []
        for lp in layers_in_order(params, m._blocks):
            x, aux = m._layer_body(x, lp, layer_pos, pos, jnp.float32)
            if aux is not None:
                routed.append(aux["routed"])
                rows.append(aux["rows_here"])
        return x, jnp.stack(routed), jnp.stack(rows)

    run = lambda f: jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=(model.specs(),), out_specs=P()))(params)
    with jax.default_matmul_precision("highest"):
        got, want = run(scanned), run(looped)
    np.testing.assert_allclose(got[0], want[0], atol=1e-5)
    assert got[1].shape == (10, 8) and got[2].shape == (10,)
    np.testing.assert_array_equal(got[1], want[1])
    np.testing.assert_array_equal(got[2], want[2])


# ---- the expert layer: shares, and no drop ----

def test_the_four_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Four jobs hold eight experts each of one layer's 32. Their routed
    parts (there is no shared expert to count once) are the layer a job
    holding all 32 computes: the sigmoid weights are normalised over all
    chosen experts, held or not; and a token none of whose four choices a
    share holds gets exactly zero from it."""
    d, f, E, k = 32, 16, 32, 4
    whole = SharedRoutedFFN(d, f, E, k, n_shared=0)
    p = whole.init(jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2, 64, d))
    with jax.default_matmul_precision("highest"):
        want, counted = apply_moe(whole, p, x)
        chosen, _ = whole.route(p, x.reshape(-1, d))
        parts, rows = [], 0.0
        for share in range(4):
            lo = 8 * share
            held = SharedRoutedFFN(d, f, E, k, held=8, offset=lo, n_shared=0)
            ps = {**p, **{n: p[n][lo:lo + 8] for n in ("gate", "up", "down")}}
            out, c = apply_moe(held, ps, x)
            none_held = ~np.any((np.asarray(chosen) >= lo)
                                & (np.asarray(chosen) < lo + 8), axis=-1)
            assert none_held.any()
            assert not np.any(np.asarray(out).reshape(-1, d)[none_held])
            np.testing.assert_array_equal(c["routed"], counted["routed"])
            parts.append(out)
            rows += float(c["rows_here"])
    np.testing.assert_allclose(sum(parts), want, atol=1e-5)
    assert rows == float(counted["rows_here"]) == 2 * 64 * k


def test_a_router_forced_onto_the_same_four_experts_drops_nothing():
    d, f, E, k = 32, 16, 32, 4
    moe = SharedRoutedFFN(d, f, E, k, held=8, n_shared=0)
    p = moe.init(jax.random.key(0))
    # the selection bias picks experts 0..3 for every token, all held
    p["bias"] = jnp.where(jnp.arange(E) < k, 100.0, 0.0)
    x = jax.random.normal(jax.random.key(1), (2, 64, d))
    xf = x.reshape(-1, d)
    with jax.default_matmul_precision("highest"):
        out, c = apply_moe(moe, p, x)
        s = jax.nn.sigmoid(xf @ p["router"])[:, :k]
        w = s / jnp.sum(s, axis=-1, keepdims=True)
        want = sum(w[:, e:e + 1] * (
            (jax.nn.silu(xf @ p["gate"][e]) * (xf @ p["up"][e]))
            @ p["down"][e]) for e in range(k))
    assert float(c["rows_here"]) == float(c["rows_computed"]) == 2 * 64 * k
    np.testing.assert_array_equal(c["routed"][:k], np.full(k, 128.0))
    np.testing.assert_allclose(out.reshape(-1, d), want, atol=1e-5)


# ---- the tied head ----

def test_the_tied_embeddings_gradient_is_the_sum_of_its_two_uses():
    """The same weights as TWO leaves (the lookup's and the head's) give
    two gradients whose sum is the tied leaf's."""
    cfg = tiny()
    mesh, model = on_mesh(cfg, 1, attn_impl="xla")
    params = model.init(jax.random.key(0))
    ids, tgt, pos = batch(cfg, t=32)

    class Untied(type(model)):
        def _head_logits(self, params, x, dtype):
            return super()._head_logits(
                {"embedding": params["head"]}, x, dtype)

        def specs(self):
            s = super().specs()
            return {**s, "head": s["embedding"]}

    untied = Untied(cfg, attn_impl="xla")
    with jax.default_matmul_precision("highest"):
        tied_g = jax.jit(jax.grad(model.make_loss(mesh)))(
            params, ids, tgt, pos)["embedding"]["weight"]
        g = jax.jit(jax.grad(untied.make_loss(mesh)))(
            {**params, "head": params["embedding"]}, ids, tgt, pos)
    lookup, head = g["embedding"]["weight"], g["head"]["weight"]
    assert np.any(lookup) and np.any(head)
    np.testing.assert_allclose(tied_g, lookup + head, atol=1e-7)


# ---- the flash kernel at head 64 and a group of 4 ----

def test_flash_at_head_64_and_a_group_of_4_equals_the_xla_path():
    """8 query heads over 2 key-value heads, 64 wide, 384 tokens in blocks
    of 128: a multi-block grid (the split backward, dk/dv summed over the
    group of 4)."""
    key = jax.random.key(0)
    t = 384
    q = jax.random.normal(jax.random.fold_in(key, 1), (1, 8, t, 64))
    k = jax.random.normal(jax.random.fold_in(key, 2), (1, 2, t, 64))
    v = jax.random.normal(jax.random.fold_in(key, 3), (1, 2, t, 64))
    blocks = dict.fromkeys(
        ("block_q", "block_k", "bwd_block_q", "bwd_block_k"), 128)
    flash = lambda q, k, v: jnp.sum(jnp.sin(flash_attention(
        q, k, v, interpret=True, **blocks)))
    plain = lambda q, k, v: jnp.sum(jnp.sin(causal_attention_xla(q, k, v)))
    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(flash, (0, 1, 2))(q, k, v)
        want = jax.value_and_grad(plain, (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(a, b, atol=5e-5)


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
        build_model("conv_moe", tiny(), **kw)


@pytest.mark.parametrize("cfg,message", [
    (model_preset("tiny"), "needs cfg.conv_moe"),
    (dataclasses.replace(tiny(), num_experts=0), "num_experts > 0"),
    (dataclasses.replace(tiny(), num_layers=11), "names 12 layers"),
    (tiny(num_dense_layers=12), "must leave an expert layer"),
    (tiny(num_dense_layers=3), "one kind of mixer"),
])
def test_a_family_needs_its_own_facts_and_a_pattern_it_can_cut(cfg, message):
    with pytest.raises(ValueError, match=message):
        build_model("conv_moe", cfg)


# ---- the step: counters, memory facts ----

def test_the_train_step_trains_and_counts_a_row_an_expert_layer():
    cfg = tiny(experts_held=4, expert_offset=2)
    losses, (_, _, counters), (_, model, *_) = R.train(
        cfg, tp=1, steps=8, max_steps=20000, attn_impl="xla")
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    assert counters["routed"].shape == (10, 8)       # ten expert layers
    assert counters["rows_here"].shape == (10,)
    np.testing.assert_array_equal(counters["routed"].sum(-1),
                                  np.full(10, 2 * 64 * cfg.moe_top_k))
    np.testing.assert_array_equal(counters["rows_here"],
                                  counters["routed"][:, 2:6].sum(-1))
    # the grouped products' groups cover the held rows and nothing more
    np.testing.assert_array_equal(counters["rows_computed"],
                                  counters["rows_here"])
    said = moe_counters_summary(counters, cfg, 2 * 64)
    assert 0.3 < said["rows_here_per_token"] < 2.0
    assert said["rows_computed_per_token"] == said["rows_here_per_token"]
    # whole chunks: never fewer rows than are held
    assert said["rows_walked_per_token"] >= said["rows_here_per_token"]
    assert model_flops_per_step(cfg, 2, 64, model.num_params(cfg)) > 0


def test_train_cli_runs_the_family(tmp_path, capsys):
    import json
    from distributed_pytorch_from_scratch_tpu import train as train_mod
    tokens = token_file(tmp_path)
    train_mod.main([
        "--family", "conv_moe", "--model", "tiny-conv-moe", "--tp_size", "2",
        "--data_path", str(tokens), "--save_dir", str(tmp_path / "ckpt"),
        "--batch_size", "4", "--maxlen", "64", "--max_steps", "4",
        "--log_interval", "2", "--save_interval", "100",
        "--warmup_steps", "2"])
    out = capsys.readouterr().out
    assert "model[conv_moe]" in out and "rows_here_per_token" in out
    assert "rows_computed_per_token" in out
    assert "rows_walked_per_token" in out
    events = [json.loads(line) for line in
              open(tmp_path / "ckpt" / "logs" / "metrics.jsonl")]
    assert any(e.get("tag") == "moe_counters" for e in events)
    with pytest.raises(SystemExit, match="reads the config field"):
        train_mod.main(["--family", "gdn_moe", "--model", "tiny-conv-moe",
                        "--data_path", str(tokens),
                        "--save_dir", str(tmp_path / "x")])
    with pytest.raises(ValueError, match="names 12 layers"):
        train_mod.main(["--family", "conv_moe", "--model", "tiny-conv-moe",
                        "--num_layers", "8", "--data_path", str(tokens),
                        "--save_dir", str(tmp_path / "y")])


def test_the_whole_chunk_is_what_the_memory_facts_count():
    """A chunk of the dispatch is one mean share of the pairs at every held
    share (`chunk_share` 1/4 at a quarter held; 1 until PR 71):
    `layer_extra_elems_per_token` sizes its buffers by that share of top_k
    rows a token, at 1/16 by 1/16 of them."""
    quarter = build_model("conv_moe", tiny(experts_held=2))
    sixteenth = build_model("conv_moe", dataclasses.replace(
        tiny(experts_held=2), num_experts=32))
    assert quarter._mods["moe"].chunk_share == 2 / 8
    assert sixteenth._mods["moe"].chunk_share == 2 / 32
    assert quarter._mods["moe"].chunk_rows(4096) == 1024
    # (the second term: what the chip counts beside these, set from cell 7)
    conv = 12.0 * 64 - 18.96 * 64
    rows = lambda m: (m.layer_extra_elems_per_token - conv) / (
        2 * 64 + 5 * 32)
    assert rows(quarter) == pytest.approx(2 * 2 / 8)
    assert rows(sixteenth) == pytest.approx(2 * 2 / 32)
    assert quarter.ffn_inputs == 2 and quarter.tied_head
    assert quarter.stacked_layers == 12


# ---- the other pattern families lower to what they lowered to ----

LOWERED_BEFORE = {"mla_moe": ("tiny-mla-moe", "latent_moe",
                              "f9b301bf8433853c"),
                  "gdn_moe": ("tiny-gdn-moe", "gdn_moe", "0aac1ca5da621858")}


@pytest.mark.parametrize("family", sorted(LOWERED_BEFORE))
def test_the_pattern_declaration_left_the_other_families_text_alone(family):
    """`mla_moe`'s two segments and `gdn_moe`'s one period are the one
    declaration (`DecoderStack._pattern`) and lower to the StableHLO they
    lowered to at the commit before it (PR 38's tree; locations stripped;
    sha256, first 16 digits), at a shape whose dispatch walks SEVERAL
    chunks, as cells 5 and 6 do (one expert of eight held, 4 x 256 tokens:
    a loop over up to four chunks of 512 rows since PR 50, a `scan` over two
    under a `cond` before it): a
    dispatch of ONE chunk, which only the fifth family's cell has, lost its
    `lax.cond` in PR 39. The optimised HLO of both tiny presets was
    compared once, parent and change, and was the same (PR 39). A PR that
    means to change either family's program changes the digest with it:
    PR 42 did (the dispatch's row movers and the inverse permutation)
    and PR 43 (its index work without a scalar gather or scatter) and PR
    47 (the grouped products' groups end at the held rows; `rows_computed`
    beside `rows_here`) and PR 50 (a chunk is one mean share of the pairs
    where under a sixth of the experts are held, walked by `walk_chunks` up
    to the last held row; `rows_walked`) and PR 65 (a chunk of a share's
    rows come back by `sum_held`, no row scatter-add; `sum_windows`,
    `sum_blocks`: every standing family's digest in the five files that
    pin them)."""
    preset, facts, digest = LOWERED_BEFORE[family]
    cfg = model_preset(preset)
    cfg = dataclasses.replace(cfg, **{facts: dataclasses.replace(
        getattr(cfg, facts), experts_held=1, expert_offset=3)})
    moe = build_model(family, cfg)._mods["moe"]
    assert moe.chunk_rows(4 * 256 * moe.top_k) < 4 * 256 * moe.top_k
    text = lowered_text(family, cfg)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


# cell 7's rehearsal shape (benchmark/workloads/lfm2-8b-a1b...: `rehearse`),
# three steps of the parent's tree (PR 49) on the CPU, run side by side
# with PR 50's: loss and gradient norm, the same to the bit
PARENTS_THREE_STEPS = [("0x1.8f1b4c0000000p+2", "0x1.c6cb860000000p+1"),
                       ("0x1.9031100000000p+2", "0x1.0395da0000000p+2"),
                       ("0x1.8eb4e20000000p+2", "0x1.9b247a0000000p+1")]


def test_the_quarter_share_cells_steps_are_the_parents_whatever_the_grain(
        monkeypatch):
    """Cell 7 holds a quarter of its experts. Until PR 71 its one chunk was
    ALL the pairs, moved by gathers; since then it walks chunks of a
    quarter of them by `take_held` / `sum_held`, the same sums in another
    order. At its rehearsal shape (float32; 256 pairs a layer, under the
    grouped kernel's tile, so ONE chunk under any grain) three steps still
    read the losses and gradient norms PR 49's tree read, to a float32's
    last digits, and read the SAME BITS under the grain the policy had
    (six shares) and under a quarter of a share: no grain reaches this
    shape."""
    from distributed_pytorch_from_scratch_tpu.parallel import moe as moe_mod

    cfg = ModelConfig(
        attn_dim=64, ffn_dim=128, num_heads=4, num_kv_heads=2, num_layers=5,
        vocab_size=503, maxlen=64, rope_theta=1e6, compute_dtype="float32",
        num_experts=8, moe_top_k=2, conv_moe=ConvMoEConfig(
            layer_types=("conv", "full_attention", "conv", "conv", "conv"),
            moe_intermediate_size=32, num_dense_layers=1, experts_held=2))

    def three_steps():
        mesh, model = on_mesh(cfg, 1)
        moe = model._mods["moe"]
        assert moe.chunk_rows(256) == 256       # under any grain
        params = model.init(jax.random.key(0))
        opt = init_adam_state(params)
        step = build_train_step(model, mesh, OptimizerConfig(),
                                with_grad_norm=True, with_counters=True)
        rng, read = np.random.default_rng(0), []
        for _ in range(3):
            ids = rng.integers(0, cfg.vocab_size, (2, 65)).astype(np.int32)
            pos = np.tile(np.arange(64, dtype=np.int32), (2, 1))
            params, opt, (loss, gnorm, c) = step(params, opt, ids[:, :-1],
                                                 ids[:, 1:], pos)
            np.testing.assert_array_equal(c["rows_walked"], [256.0] * 4)
            read.append((float(loss), float(gnorm)))
        return read

    got = three_steps()
    np.testing.assert_allclose(
        got, [[float.fromhex(v) for v in row] for row in PARENTS_THREE_STEPS],
        rtol=2e-6)
    for grain in (6, 0.25):
        monkeypatch.setattr(moe_mod, "CHUNK_SHARES", grain)
        assert three_steps() == got


def test_a_chunk_of_all_the_pairs_is_computed_whatever_is_routed():
    """Every held share walks chunks of one mean share under a loop that
    stops at the last held row (PR 50 under a sixth held, PR 71 a quarter
    and more: until then the one chunk of a quarter share was ALL the
    pairs, computed with no `cond` around it whatever was routed); the one
    chunk of a job that holds EVERY expert is still all the pairs, always
    live. A step that routes nothing to the experts held walks NO chunk: no
    row is computed, the output zero, every gradient a finite zero."""
    d, f, E, k = 32, 16, 32, 4
    x = jax.random.normal(jax.random.key(1), (2, 512, d))
    quarter = SharedRoutedFFN(d, f, E, k, held=8, n_shared=0)
    sixteenth = SharedRoutedFFN(d, f, E, k, held=2, n_shared=0)
    whole = SharedRoutedFFN(d, f, E, k, n_shared=0)
    assert quarter.chunk_rows(4096) == 1024
    assert sixteenth.chunk_rows(4096) == 512
    assert whole.chunk_rows(4096) == 4096
    count = lambda moe, op: str(jax.make_jaxpr(lambda p, x: apply_moe(
        moe, p, x))(moe.init(jax.random.key(0)), x)).count(f" {op}[")
    # the walk over the live chunks, and inside it `sum_held`'s loop over a
    # token block's windows past its first (PR 65)
    for moe in (quarter, sixteenth, whole):
        assert count(moe, "cond") == 0 and count(moe, "while") == 2
    _, c = apply_moe(whole, whole.init(jax.random.key(0)), x)
    assert float(c["rows_here"]) == float(c["rows_walked"]) == 4096
    p = quarter.init(jax.random.key(0))
    # the selection bias sends every token to experts 8..11: none held
    p["bias"] = jnp.where((jnp.arange(E) >= 8) & (jnp.arange(E) < 12),
                          100.0, 0.0)
    out, c = apply_moe(quarter, p, x)
    assert float(c["rows_here"]) == float(c["rows_computed"]) == 0
    assert float(c["rows_walked"]) == 0         # the walk stopped at once
    assert not np.any(out)
    grads = jax.grad(lambda p: jnp.sum(apply_moe(quarter, p, x)[0] ** 2))(p)
    assert all(np.all(np.isfinite(g)) and not np.any(g)
               for g in jax.tree.leaves(grads))


# ---- the counts ----

def test_the_cut_at_the_published_widths_counts_507_820_288():
    cfg = ModelConfig(
        attn_dim=2048, ffn_dim=7168, num_heads=32, num_kv_heads=8,
        num_layers=5, vocab_size=16384, maxlen=128000, rope_theta=1e6,
        num_experts=32, moe_top_k=4, conv_moe=ConvMoEConfig(
            layer_types=PUBLISHED[1:6], moe_intermediate_size=1792,
            num_dense_layers=1, experts_held=8))
    counts = ConvMoETransformer.param_counts(cfg)
    assert counts == {"embedding": 33_554_432, "final_norm": 2048,
                      "dense_layers": 60_827_648,
                      "conv_layers": 3 * (104_933_376 + 32),
                      "attn_layers": 98_635_904 + 32}
    assert sum(counts.values()) == 507_820_288 == cfg.num_params()
    shapes = jax.eval_shape(build_model("conv_moe", cfg).init,
                            jax.random.key(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 507_820_288
    # the published model, every expert held, the tied embedding once
    whole = dataclasses.replace(
        cfg, num_layers=24, vocab_size=65536,
        conv_moe=dataclasses.replace(cfg.conv_moe, layer_types=PUBLISHED,
                                     num_dense_layers=2, experts_held=None))
    assert round(whole.num_params() / 1e9, 2) == 8.34       # "8.3B"
