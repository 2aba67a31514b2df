"""The `mhc_mla_moe` family (models/mhc_mla_moe.py): `mla_moe`'s block with
its residual state as hyper-connection streams (parallel/hyper.py) and YaRN
positions (ops/rope.py, parallel/mla.py). CPU, tiny sizes, seeded weights.

* the program against the plain reference (models/vanilla_mhc_mla_moe.py):
  loss, logits and EVERY gradient leaf, with and without the
  multi-token-prediction module, at tp 1 and tp 2, through the flash
  kernel's interpreter and through the mixers' kernels' interpreter,
  float32; the loss in bfloat16;
* the mixer alone: H's rows and columns sum to one within the counter's own
  reading after 20 rounds, the clamp holds at +-30, the maps are float32
  whatever the streams' dtype, an exit mixer has `pre` alone;
* the eight shares of one expert layer add up to the uncut layer, with the
  attention half, the shared expert and the mixers counted once;
* YaRN's tables against the formula, bit-equal to plain RoPE at
  `rope_scaling` None, and the softmax scale's mscale^2;
* the mixers' parameters round-trip through `to_canonical` /
  `from_canonical` and a checkpoint;
* **what must not move**: the eight standing families' train steps lower to
  the text they lowered to before `stream_mixer` was a fact of the stack,
  and `training/memory.py` picks them the rung it picked, from the same
  estimate; the new family's rung is chosen with a kept input n x d wide;
* the step's counters, the entry point, the refusals, the counts at the
  published widths.
"""

import dataclasses
import functools
import hashlib
import json
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from family_recipe import (Recipe, hold_leaves, hold_loss, jitted,
                           lowered_text, on_one_device, picked_rung,
                           token_file)
from jax.sharding import PartitionSpec as P

from distributed_pytorch_from_scratch_tpu.config import (
    IGNORE_INDEX, HyperConnectionConfig, LatentMoEConfig, ModelConfig,
    model_preset)
from distributed_pytorch_from_scratch_tpu.models import (build_model,
                                                         facts_family)
from distributed_pytorch_from_scratch_tpu.models.mhc_mla_moe import (
    HyperLatentMoETransformer)
from distributed_pytorch_from_scratch_tpu.models.vanilla_mhc_mla_moe import (
    mixer_maps, vanilla_logits, vanilla_loss, yarn_tables)
from distributed_pytorch_from_scratch_tpu.ops.rope import (YarnScaling,
                                                           rope_angles,
                                                           yarn_inv_freq)
from distributed_pytorch_from_scratch_tpu.parallel.hyper import StreamMixer
from distributed_pytorch_from_scratch_tpu.training import memory
from distributed_pytorch_from_scratch_tpu.training.metrics import (
    moe_counters_summary)

FAMILY = "mhc_mla_moe"
# the family's own: its reference, sequences of 128 from id 0 up, and one
# target that is no target (the loss's mask is in every comparison)
R = Recipe(FAMILY, vanilla_loss, vanilla_logits, t=128, low=0,
           ignore=((0, 5),))
tiny, batch, on_mesh = R.tiny, R.batch, R.on_mesh


class MixersInterpreted(HyperLatentMoETransformer):
    """The family with its mixers' kernels under the Pallas interpreter:
    steered here, the program has no option for it (on a TPU the mixers take
    their kernels by themselves)."""

    @functools.cached_property
    def stream_mixer(self):
        return dataclasses.replace(
            HyperLatentMoETransformer.stream_mixer.func(self), interpret=True)


# ---- the program against the plain reference ----

@pytest.mark.parametrize("mtp", [1, 0], ids=["module", "no-module"])
@pytest.mark.parametrize("tp,impl", [(1, "xla"), (2, "xla"),
                                     (1, "flash_interpret"),
                                     (1, "mixers_interpret")])
def test_loss_logits_and_every_gradient_leaf_equal_the_reference(tp, impl,
                                                                 mtp):
    """A job that holds experts 2..5 of 8; four streams, 20 Sinkhorn
    rounds, YaRN over an original context shorter than the sequence.
    `mixers_interpret`: the mixers' Pallas kernels under the interpreter
    (ops/pallas/stream_mixer.py), at a width they hold."""
    cfg = tiny(experts_held=4, expert_offset=2, num_nextn_predict_layers=mtp)
    how = dict(attn_impl=impl)
    if impl == "mixers_interpret":
        cfg = dataclasses.replace(cfg, attn_dim=128)
        how = dict(attn_impl="xla", cls=MixersInterpreted)
        model = MixersInterpreted(cfg)
        assert model.stream_mixer.interpret and model.exit_mixer.interpret
    assert batch(cfg)[1][0, 5] == IGNORE_INDEX
    # (the reference and the parameters are one per `mtp` and width: the
    # four layouts of a `mtp` compare with the same numbers)
    _, (want, want_g) = R.reference(cfg)
    got, got_g, got_logits = R.program(cfg, tp=tp, logits=True, **how)
    hold_loss(want, got)
    np.testing.assert_allclose(got_logits[..., :cfg.vocab_size],
                               R.reference_logits(cfg), atol=2e-4)
    # (a mixer's alpha is ONE number, a sum over every token and stream that
    # cancels to a hundredth of its terms or less. The text's transpose adds
    # the reference's terms in the reference's order; the kernels' backward
    # does not, and is held to 1e-5 of what a mixer's b and W read, 3e-4,
    # where the sum itself is smaller: the text reads the same 1.6e-9 off
    # the reference once x64 reorders it)
    names, moved = hold_leaves(
        want_g, got_g, 1e-5, lambda name: 3e-4 if (
            impl == "mixers_interpret" and "alpha" in name) else 1e-6)
    assert len(names) > 45
    # every mixer leaf is reached: W, alpha and b of each joint; two mixers
    # a segment's layers (stacked: 3 leaves each) and the exit, of the model
    # and of the module
    mixers = [name for name in names if "hc_" in name]
    assert set(mixers) <= set(moved)
    assert len(mixers) == (2 + mtp) * 6 + (1 + mtp) * 3


def test_the_loss_in_bfloat16_is_near_the_float32_reference():
    cfg = tiny("bfloat16", experts_held=4, expert_offset=2)
    mesh, model = on_mesh(cfg, 1, attn_impl="xla")
    params = R.params(cfg)
    ids, tgt, pos = batch(cfg)
    got = model.make_loss(mesh)(params, ids, tgt, pos)
    want = jitted(lambda p: vanilla_loss(cfg, p, ids, tgt, pos), params)
    hold_loss(want, got, 2e-2)
    # the streams are carried in the compute dtype, the maps in float32
    mixer = model.stream_mixer
    X = jnp.ones((4, 1, 8, 64), jnp.bfloat16)
    p = mixer.init(jax.random.key(0))
    maps = mixer.maps(p, X)
    assert {m.dtype for m in maps[:3]} == {jnp.dtype("float32")}
    assert mixer.pre(maps, X).dtype == mixer.post(
        maps, X, X[0]).dtype == jnp.bfloat16


# ---- the mixer alone ----

def streams(n=4, b=2, t=16, c=32, seed=0, scale=1.0):
    return scale * jax.random.normal(jax.random.key(seed), (n, b, t, c))


def test_h_is_doubly_stochastic_within_the_counters_own_reading():
    mixer = StreamMixer(32, 4)
    p = mixer.init(jax.random.key(1))
    X = streams()
    maps = mixer.maps(p, X)
    res = np.asarray(maps.res)                      # (n, n, T)
    counted = mixer.counters(maps)
    err = float(counted["hc_sinkhorn_err"])
    assert np.abs(res.sum(0) - 1).max() <= err + 1e-7
    assert np.abs(res.sum(1) - 1).max() <= err + 1e-7
    assert err < 1e-4 and res.min() > 0
    # the columns were normalised last: theirs is hc_eps over a column's
    # sum and float32's rounding, and the counter says so
    cols = float(counted["hc_colsum_err"])
    assert np.abs(res.sum(0) - 1).max() <= cols + 1e-7 and cols < 5e-6
    # visibly not the identity, and not the same for every token
    off = float(counted["hc_res_offdiag"])
    assert 0.2 < off < 0.95
    assert np.std(res[0, 0]) > 1e-2
    np.testing.assert_allclose(
        off, np.mean(res.sum((0, 1)) - np.trace(res)) / 4, rtol=1e-5)
    # pre is more than one stream, post twice a sigmoid
    assert np.asarray(maps.pre).min() > 1e-6
    assert 0 < np.asarray(maps.post).min() and np.asarray(
        maps.post).max() < 2
    # and the reference's maps of the same streams are these
    cfg = tiny()
    cfg = dataclasses.replace(cfg, attn_dim=32)
    pre, post, H = mixer_maps(cfg, p, X.transpose(1, 2, 0, 3))
    np.testing.assert_allclose(
        res.reshape(4, 4, 2, 16).transpose(2, 3, 0, 1), H, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(maps.pre).reshape(4, 2, 16).transpose(1, 2, 0), pre,
        atol=1e-6)


def test_fewer_rounds_leave_a_larger_error_the_counter_shows():
    X = streams()
    errs = []
    for rounds in (1, 3, 20):
        mixer = StreamMixer(32, 4, sinkhorn_iters=rounds)
        maps = mixer.maps(mixer.init(jax.random.key(1)), X)
        errs.append(float(mixer.counters(maps)["hc_sinkhorn_err"]))
    assert errs[0] > errs[1] > errs[2]
    assert errs[0] > 1e-2


def test_the_clamp_holds_at_plus_and_minus_30():
    """alpha_2 at 1000 sends every entry of H~ far past the clamp: exp
    stays finite (exp(1000) would not), H is a permutation-like matrix of
    finite entries, and the entries beyond the clamp pass no gradient to
    W, alpha or b's H part."""
    mixer = StreamMixer(32, 4)
    p = mixer.init(jax.random.key(1))
    p["alpha"] = jnp.array([1.0, 1.0, 1000.0])
    X = streams()
    res = mixer.maps(p, X).res
    assert np.isfinite(np.asarray(res)).all()
    # the same H as clamping by hand: entries are exp(+-30) before rounds
    m = mixer._m(p, X)
    h = jnp.clip(1000.0 * m[8:] + p["b"][8:, None], -30.0, 30.0)
    assert float(jnp.abs(h).max()) == 30.0
    np.testing.assert_array_equal(res, mixer.sinkhorn(h.reshape(4, 4, -1)))
    g = jax.grad(lambda b: jnp.sum(jnp.sin(mixer.maps(
        {**p, "b": b}, X).res)))(p["b"])
    beyond = np.asarray(jnp.all(jnp.abs(1000.0 * m[8:] + p["b"][8:, None])
                                > 30.0, axis=1))
    assert beyond.any()
    assert not np.any(np.asarray(g)[8:][beyond])


def test_an_exit_mixer_has_pre_alone():
    mixer = StreamMixer(32, 4, exit_only=True)
    p = mixer.init(jax.random.key(2))
    assert p["w"].shape == (4 * 32, 4) and p["alpha"].shape == (1,)
    assert mixer.num_params() == sum(x.size for x in jax.tree.leaves(p))
    X = streams()
    maps = mixer.maps(p, X)
    assert maps.post is None and maps.res is None
    h = mixer.exit(p, X)
    want = jnp.einsum("nt,ntc->tc", maps.pre, X.reshape(4, -1, 32))
    np.testing.assert_allclose(h.reshape(-1, 32), want, atol=1e-5)
    full = StreamMixer(32, 4)
    assert full.num_params() == sum(
        x.size for x in jax.tree.leaves(full.init(jax.random.key(0))))


# ---- the eight shares add up ----

def test_the_eight_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """One expert layer of 8 routed experts; eight jobs hold one expert
    each (shares 0..7). A share's layer is X' = A(X) + R_share, where A is
    everything a deployment replicates (the attention half with its mixer,
    the FFN half's mixer, the shared expert) and R_share what the held
    expert adds through `post`. The eight R's, with A counted once, are the
    uncut layer of the plain reference."""
    cfg = tiny(first_k_dense_replace=0, num_nextn_predict_layers=0)
    cfg = dataclasses.replace(cfg, num_layers=1)
    ids, _, pos = batch(cfg, t=64)
    params = R.params(cfg, 5)

    def last_streams(model, p):
        """The streams behind the one layer, before the exit: the program's
        own trunk."""
        def shard(p, ids, pos):
            return model._resolved(ids.shape[1])._trunk(p, ids, pos)[0]
        return on_one_device(shard, (model.specs(), P(), P()), P())(
            p, ids, pos)

    def no_routed(p):
        """The same tree with the held experts' down projections at zero:
        what the layer computes without any routed expert."""
        moe = {**p["layers"]["moe"],
               "down": jnp.zeros_like(p["layers"]["moe"]["down"])}
        return {**p, "layers": {**p["layers"], "moe": moe}}

    with jax.default_matmul_precision("highest"):
        from distributed_pytorch_from_scratch_tpu.models import (
            vanilla_mhc_mla_moe as plain)
        full = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        want = plain._trunk(cfg, full, ids, pos)         # (b, t, n, C)
        base, total = None, 0.0
        for e in range(8):
            share_cfg = dataclasses.replace(
                cfg, latent_moe=dataclasses.replace(
                    cfg.latent_moe, experts_held=1, expert_offset=e))
            share = build_model(FAMILY, share_cfg, attn_impl="xla")
            moe = {**params["layers"]["moe"],
                   **{k: params["layers"]["moe"][k][:, e:e + 1]
                      for k in ("gate", "up", "down")}}
            p = {**params, "layers": {**params["layers"], "moe": moe}}
            got = last_streams(share, p)
            if base is None:
                base = last_streams(share, no_routed(p))
            total = total + (got - base)
        got = (base + total).transpose(1, 2, 0, 3)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # and the routed part is not nothing
    assert float(jnp.abs(total).max()) > 1e-3


# ---- YaRN ----

PUBLISHED_YARN = YarnScaling(factor=64.0,
                             original_max_position_embeddings=4096,
                             beta_fast=32.0, beta_slow=1.0, mscale=1.0,
                             mscale_all_dim=1.0)


def test_yarn_frequencies_are_the_formulas():
    """Xing4.0's keys on 64 rotary dimensions, theta 10000: pairs 0..10
    keep their frequency (more than 32 turns in 4096 positions), pairs 23
    and up are divided by 64 (under one turn), a linear ramp between."""
    got = np.asarray(yarn_inv_freq(64, 10000.0, PUBLISHED_YARN))
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    turns = lambda pair: 4096 * plain[pair] / (2 * math.pi)
    assert turns(10) > 32 > turns(11) and turns(22) > 1 > turns(23)
    np.testing.assert_allclose(got[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], plain[23:] / 64, rtol=1e-6)
    ramp = (np.arange(11, 23) - 10) / 13
    np.testing.assert_allclose(
        got[11:23], plain[11:23] * (1 - ramp) + plain[11:23] / 64 * ramp,
        rtol=1e-5)
    assert PUBLISHED_YARN.table_scale == 1.0
    assert PUBLISHED_YARN.softmax_scale == (0.1 * math.log(64) + 1) ** 2
    # mscale_all_dim 0 (DeepSeek-V2-Lite's): the tables carry the scale
    lite = PUBLISHED_YARN._replace(mscale=0.707, mscale_all_dim=0.0)
    assert lite.softmax_scale == 1.0
    assert lite.table_scale == 0.1 * 0.707 * math.log(64) + 1


def test_the_tables_equal_the_references_and_plain_rope_bit_for_bit():
    pos = jnp.tile(jnp.arange(128)[None], (2, 1))
    cfg = tiny()
    cos, sin = rope_angles(pos, 8, 10000.0, cfg.latent_moe.rope_scaling)
    want_cos, want_sin, softmax = yarn_tables(cfg, pos)
    np.testing.assert_allclose(cos, want_cos[:, 0], atol=1e-6)
    np.testing.assert_allclose(sin, want_sin[:, 0], atol=1e-6)
    assert softmax == cfg.latent_moe.rope_scaling.softmax_scale
    # not plain RoPE's tables: two of the four pairs turn slower
    plain = rope_angles(pos, 8, 10000.0)
    assert float(jnp.abs(cos - plain[0]).max()) > 0.5
    # None is the code path it has always been: the same bits
    theta = 1.0 / (10000.0 ** (jnp.arange(0, 8, 2, dtype=jnp.float32) / 8))
    ang = pos.astype(jnp.float32)[..., None] * theta
    for scaling in ((), (None,)):
        got = rope_angles(pos, 8, 10000.0, *scaling)
        np.testing.assert_array_equal(got[0], jnp.cos(ang))
        np.testing.assert_array_equal(got[1], jnp.sin(ang))
    # and a model without the fact makes no multiply of q
    model = build_model("mla_moe", model_preset("tiny-mla-moe"))
    assert model.attention.softmax_scale == 1.0
    scaled = build_model(FAMILY, cfg)
    assert scaled.attention.softmax_scale == pytest.approx(
        (0.1 * math.log(8) + 1) ** 2)


# ---- the parameter tree: canonical layout and a checkpoint ----

def test_the_mixers_round_trip_through_canonical_and_a_checkpoint(tmp_path):
    from distributed_pytorch_from_scratch_tpu.training.checkpoint import (
        load_checkpoint, save_checkpoint)
    model = build_model(FAMILY, tiny())
    params = R.params(tiny(), 4)
    specs = model.specs()
    is_spec = lambda x: isinstance(x, jax.sharding.PartitionSpec)
    assert (jax.tree.structure(params)
            == jax.tree.structure(specs, is_leaf=is_spec))
    for seg in ("dense_layers", "layers", "mtp_layers"):
        assert {"hc_attn", "hc_ffn"} <= set(params[seg])
        layers = params[seg]["norm1"]["scale"].shape[0]
        assert params[seg]["hc_attn"]["w"].shape == (layers, 4 * 64, 24)
        assert params[seg]["hc_ffn"]["alpha"].shape == (layers, 3)
    assert params["hc_exit"]["w"].shape == (4 * 64, 4)
    assert params["mtp"]["hc_exit"]["b"].shape == (4,)
    # the two joints of a layer, and two layers, start apart
    assert np.abs(params["layers"]["hc_attn"]["w"]
                  - params["layers"]["hc_ffn"]["w"]).max() > 1e-3
    assert np.abs(params["layers"]["hc_attn"]["w"][0]
                  - params["layers"]["hc_attn"]["w"][1]).max() > 1e-3
    canonical = model.to_canonical(params)
    back = model.from_canonical(canonical)
    jax.tree.map(np.testing.assert_array_equal, back, params)
    save_checkpoint(str(tmp_path), 3, 1.0, canonical,
                    model.canonical_specs(), 1)
    fresh = R.params(tiny(), 9)
    restored, _, at = load_checkpoint(str(tmp_path), 3, fresh,
                                      model.canonical_specs())
    assert at == 3
    jax.tree.map(np.testing.assert_array_equal, restored, params)


# ---- what must not move ----

# the eight standing families' train steps at their tiny presets: the
# StableHLO's digest (locations stripped; sha256, first 16 digits) at the
# commit before `stream_mixer` was a fact (PR 56's tree), and what
# `select_remat_traced` picked there with its estimate (bfloat16, 4 x 256
# tokens, a budget of 0.02 GiB, so that the ladder is walked; THE PICKS AND
# ESTIMATES ARE PR 62'S, which meant to move them: no reserve is held where
# the floor cannot take a snapshot (gpt2's and conv_moe's rungs rose by
# that), a rung's names are charged over the layers that tag them, and each
# drawn family's count is set from its cell's chip reading). tests/
# test_bd_moe.py and tests/test_conv_moe.py hold five of the digests too; a
# PR that means to change a family's program changes them together.
STANDING = {
    # (PR 77's pick, which meant to move it: the climb passes over the
    # MLP's stacks, 0.0220 of a usable 0.0186, and keeps the flash outputs
    # behind them; `true` at 0.018050289154052733 until then)
    "llama": ("tiny", "14bb75356a403459", "true+flash",
              0.018569087982177733),
    "gpt2": ("tiny", "557e9d12313622a3", "dots", 0.01830301284790039),
    "mla_moe": ("tiny-mla-moe", "83b0575bcf151845", "flash",
                0.01279906988143921),
    "gdn_moe": ("tiny-gdn-moe", "6d83ef8f30d65710", "true",
                0.019560834169387815),
    "conv_moe": ("tiny-conv-moe", "64b65f649e0393d9", "ffn",
                 0.018542855978012085),
    "bd_moe": ("tiny-bd-moe", "35cad4194c7a5c5e", "dots",
               0.01311171531677246),
    "swa_moe": ("tiny-swa-moe", "9c832b8734e8942b", "true",
                0.023941473960876467),
    "early_moe": ("tiny-early-moe", "fba41f5652566548", "true",
                  0.02028942108154297),
}


def lowered_digest(family, cfg):
    text = lowered_text(family, cfg)
    return hashlib.sha256(text.encode()).hexdigest()[:16], text


@pytest.mark.parametrize("family", sorted(STANDING))
def test_a_standing_family_lowers_to_the_text_it_lowered_to(family):
    preset, digest, _, _ = STANDING[family]
    got, text = lowered_digest(family, model_preset(preset))
    assert got == digest
    assert "mhc" not in text and "hc_" not in text


@pytest.mark.parametrize("family", sorted(STANDING))
def test_a_standing_family_is_picked_the_rung_it_was(family, monkeypatch):
    preset, _, rung, estimate = STANDING[family]
    model, got, fields = picked_rung(
        monkeypatch, family, model_preset(preset, compute_dtype="bfloat16"),
        0.02)
    assert model.residual_streams == 1 and model.stream_mixer is None
    assert (got, fields["estimate_gib"]) == (rung, estimate)


def test_the_new_familys_rung_is_sized_with_a_kept_input_n_by_d_wide(
        monkeypatch):
    cfg = tiny("bfloat16")
    model, rung, fields = picked_rung(monkeypatch, FAMILY, cfg, 0.02)
    assert model.residual_streams == 4
    kw = dict(param_count=1e6, layer_param_count=5e5, b=4, t=256, d=64,
              kd=64, f=128, heads=4, head_dim=16, layers=4, vocab=1024,
              dtype_bytes=2)
    one = memory.step_bytes("true", **kw)
    four = memory.step_bytes("true", residual_streams=4, **kw)
    assert one["stacks"] == 4 * (4 * 256 * 64 * 2)
    assert four["stacks"] == 4 * one["stacks"]
    assert memory.step_bytes("flash", residual_streams=4, **kw)["stacks"] \
        - four["stacks"] == memory.step_bytes("flash", **kw)["stacks"] \
        - one["stacks"]
    # the model's own estimate carries both: the stacks four wide and what
    # a mixer's backward holds (`layer_extra_elems_per_token`)
    base = build_model("mla_moe", model_preset("tiny-mla-moe",
                                               compute_dtype="bfloat16"))
    # (each family's own term set from its cell's chip reading, PR 62)
    assert model.layer_extra_elems_per_token == pytest.approx(
        base.layer_extra_elems_per_token - 18.07 * 64 + 2.02 * 4 * 64)
    assert fields["estimate_gib.true"] > STANDING["mla_moe"][3] * 0 + (
        4 * 4 * 256 * 64 * 2 * 3) / 2 ** 30


# ---- the step, its counters, the entry point ----

def test_the_train_step_counts_the_mixers_a_row_a_layer_and_the_loss_falls():
    cfg = tiny()
    losses, (_, gnorm, c), _ = R.train(cfg)
    assert losses[-1] < losses[0] and np.isfinite(float(gnorm))
    # 1 dense + 2 expert layers + the module's: the mixers count in all
    # four, the router in the three expert layers
    assert c["hc_sinkhorn_err"].shape == c["hc_res_offdiag"].shape == (4,)
    assert c["hc_colsum_err"].shape == (4,)
    assert float(c["hc_colsum_err"].max()) < 5e-6
    assert c["routed"].shape == (3, 8) and c["rows_here"].shape == (3,)
    assert 0 < float(c["hc_sinkhorn_err"].max()) < 1e-3
    assert 0.05 < float(c["hc_res_offdiag"].min()) < 1.0
    summary = moe_counters_summary(jax.device_get(c), cfg, 2 * 64)
    assert summary["hc_sinkhorn_err"] == float(c["hc_sinkhorn_err"].max())
    assert summary["rows_here_per_token"] == 2.0


def test_the_step_names_the_mixers_scopes():
    mesh, model = on_mesh(tiny(), 1)
    params = jax.eval_shape(model.init, jax.random.key(0))
    ids = jax.ShapeDtypeStruct((2, 64), np.int32)
    hlo = jax.jit(jax.value_and_grad(model.make_loss(mesh))).lower(
        params, ids, ids, ids).compile().as_text()
    for scope in ("mhc/maps", "mhc/sinkhorn", "mhc/pre", "mhc/post",
                  "mhc/exit", "mtp/mhc/exit", "mla"):
        assert re.search(rf'op_name="[^"]*{scope}[/"]', hlo), scope


def test_train_cli_runs_the_family(tmp_path, capsys):
    from distributed_pytorch_from_scratch_tpu import train as train_mod
    tokens = token_file(tmp_path)
    train_mod.main([
        "--family", FAMILY, "--model", "tiny-mhc-mla-moe",
        "--data_path", str(tokens), "--save_dir", str(tmp_path / "ckpt"),
        "--batch_size", "4", "--maxlen", "64", "--max_steps", "4",
        "--log_interval", "2", "--save_interval", "100",
        "--warmup_steps", "2"])
    out = capsys.readouterr().out
    assert f"model[{FAMILY}]" in out and "hc_sinkhorn_err" in out
    events = [json.loads(line) for line in
              open(tmp_path / "ckpt" / "logs" / "metrics.jsonl")]
    assert any(e.get("tag") == "moe_counters" and "hc_res_offdiag" in e
               for e in events)
    # the two families that read `latent_moe` take their own presets only
    for family, preset in (("mla_moe", "tiny-mhc-mla-moe"),
                           (FAMILY, "tiny-mla-moe")):
        with pytest.raises(SystemExit, match="reads the config field"):
            train_mod.main(["--family", family, "--model", preset,
                            "--data_path", str(tokens),
                            "--save_dir", str(tmp_path / "x")])


# ---- what is refused, and which family a configuration is ----

@pytest.mark.parametrize("kw,message", [
    (dict(pp_size=2), "pp_size > 1"),
    (dict(cp_size=2), "cp_size > 1"),
    (dict(ep_size=2), "ep_size > 1"),
    (dict(sequence_parallel=True), "sequence_parallel=True"),
    (dict(attn_t_real=32), "attn_t_real"),
    (dict(zero3_axis="dp"), "ZeRO stage 3"),
])
def test_the_model_refuses_what_it_does_not_run(kw, message):
    with pytest.raises(ValueError, match=message):
        build_model(FAMILY, tiny(), **kw)


def test_the_facts_name_their_family():
    assert facts_family(tiny()) is HyperLatentMoETransformer
    assert facts_family(model_preset("tiny-mla-moe")).family == "mla_moe"
    assert tiny().num_params() == HyperLatentMoETransformer.num_params(
        tiny())
    with pytest.raises(ValueError, match="cfg.latent_moe.hyper"):
        build_model("mla_moe", tiny())
    assert not HyperLatentMoETransformer.decodable


def test_streams_refuse_a_pipeline_whatever_the_family():
    """The stack's own refusal, for a family that declares streams and
    refuses nothing itself."""
    from distributed_pytorch_from_scratch_tpu.models.transformer import (
        Transformer)

    @dataclasses.dataclass(frozen=True)
    class Streamed(Transformer):
        stream_mixer = StreamMixer(32, 2)

    cfg = ModelConfig(attn_dim=32, ffn_dim=64, num_heads=4, num_layers=4,
                      vocab_size=96, maxlen=64)
    with pytest.raises(ValueError, match="2 residual streams"):
        Streamed(cfg, pp_size=2)
    assert Streamed(cfg).residual_streams == 2


# ---- the counts at the published widths ----

def published(mtp=0):
    return ModelConfig(
        attn_dim=3584, ffn_dim=9216, num_heads=32, num_layers=5,
        vocab_size=16384, maxlen=262144, rope_theta=10000.0,
        compute_dtype="bfloat16", num_experts=64, moe_top_k=4,
        latent_moe=LatentMoEConfig(
            q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=128,
            qk_rope_head_dim=64, v_head_dim=128, moe_intermediate_size=1024,
            first_k_dense_replace=1, routed_scaling_factor=2.0,
            experts_held=8, num_nextn_predict_layers=mtp,
            rope_scaling=PUBLISHED_YARN, hyper=HyperConnectionConfig()))


def test_parameter_counts_at_the_published_widths():
    """The cut of benchmark/configs/xing4-29b-a4b.json: 8 of 64 experts, 1
    dense + 4 expert layers, an eighth of the vocabulary, no module."""
    cfg = published()
    counts = HyperLatentMoETransformer.param_counts(cfg)
    mixer = 4 * 3584 * 24 + 3 + 24
    assert counts["stream_mixers"] == 10 * mixer + 4 * 3584 * 4 + 1 + 4
    assert counts["embedding_and_head"] == 2 * 16384 * 3584
    total = HyperLatentMoETransformer.num_params(cfg)
    assert total == 759_403_795
    assert 11.5e9 < 16 * total < 12.5e9
    model = build_model(FAMILY, cfg)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    assert total == sum(x.size for x in jax.tree.leaves(shapes))
    # with the module the state is what no batch fits beside
    with_module = HyperLatentMoETransformer.num_params(published(mtp=1))
    assert 16 * with_module > 14.5e9
    assert model.stacked_layers == 5 and model.residual_streams == 4
