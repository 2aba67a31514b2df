"""The `ssm_moe` family (models/ssm_moe.py: the Nemotron-H architecture as
Nemotron 3 publishes it) against its plain float32 reference
(models/vanilla_ssm_moe.py), on the CPU at small sizes with seeded weights:

* **the program against the reference**: loss and every leaf's gradient,
  periods SCANNED against layers LOOPED, the chunked recurrence against the
  token-by-token one, the sorted dispatch in a latent against experts applied
  one by one, with and without the multi-token-prediction module, in float32
  and in bfloat16;
* **the chunked SSD** (ops/ssd.py) against the recurrence at a length no
  chunk divides, at several groups, and at a decay where a bfloat16 state
  fails;
* **the shares**: the four head shares of a Mamba and of an attention layer,
  and the expert shares of an expert layer (router, latent projections and
  shared expert counted once), add up to the uncut layer of the reference;
* **what the stack and the expert FFN gained** at their defaults are the
  programs they were: the lowered text of one step of each of the eight
  standing expert families and of GPT-2 is the parent's.
"""

import dataclasses
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from family_recipe import (Recipe, apply_moe, hold_leaves, hold_loss, jitted,
                           lowered_text, on_one_device, outputs_and_grads,
                           token_file)
from jax.sharding import PartitionSpec as P

from distributed_pytorch_from_scratch_tpu.config import (
    ModelConfig, SsmMoEConfig, model_preset)
from distributed_pytorch_from_scratch_tpu.models import (FAMILIES,
                                                         build_model)
from distributed_pytorch_from_scratch_tpu.models import vanilla_ssm_moe as ref
from distributed_pytorch_from_scratch_tpu.models.ssm_moe import (
    SsmMoETransformer, layer_counts)
from distributed_pytorch_from_scratch_tpu.ops.ssd import (
    ssd, ssd_flops_per_token)
from distributed_pytorch_from_scratch_tpu.parallel.moe import (
    ACTIVATIONS, SharedRoutedFFN)
from distributed_pytorch_from_scratch_tpu.training import memory
from distributed_pytorch_from_scratch_tpu.training.checkpoint import (
    load_checkpoint, save_checkpoint)
from distributed_pytorch_from_scratch_tpu.training.metrics import (
    model_flops_per_step, moe_counters_summary)

FAMILY = "ssm_moe"


# the family's own: its reference, and sequences of 80 from id 0 up
R = Recipe(FAMILY, ref.vanilla_loss, t=80, low=0)
batch = R.batch


def tiny(dtype="float32", **facts):
    """(its own: the pattern's length is the model's depth)"""
    cfg = R.tiny(dtype, **facts)
    if "hybrid_override_pattern" in facts:
        cfg = dataclasses.replace(
            cfg, num_layers=len(facts["hybrid_override_pattern"]))
    return cfg


def on_mesh(cfg, dp=1, **kw):
    """(its own: the family refuses `tp`, so a file's second axis is dp)"""
    return R.on_mesh(cfg, dp=dp, **kw)


def rel(a, b):
    """The relative L2 error of a against b (its own: what bfloat16 and the
    kernels are held by; the recipe's measure is a leaf's largest entry)."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


# ---- the program against the plain reference ----

@pytest.mark.parametrize("dp,impl,mtp", [
    (1, "xla", 1), (1, "xla", 0), (2, "xla", 1), (1, "flash_interpret", 1)])
def test_loss_and_every_gradient_leaf_equal_the_reference(dp, impl, mtp):
    """A period SCANNED (the program) against seven layers LOOPED (the
    reference), the chunked recurrence (chunks of 32 over 80 tokens: the
    last one padded) against the token-by-token one, the sorted dispatch in
    the latent against experts applied one by one, on a job that holds
    experts 4..11 of 16. Leaves to 5e-5 of their largest entry: float32 sums
    in another order, as every family's (the recurrence's `A_log` and
    `dt_bias`, whose gradients exist only through the decays, among them)."""
    cfg = tiny(experts_held=8, expert_offset=4,
               num_nextn_predict_layers=mtp)
    # (the parameters and the reference are one per `mtp`)
    _, (want, want_g) = R.reference(cfg)
    got, got_g = R.program(cfg, dp=dp, attn_impl=impl)
    hold_loss(want, got)
    names, moved = hold_leaves(want_g, got_g, 5e-5)
    # every leaf but the selection biases has a gradient
    biases = sum(name.endswith("['moe']['bias']") for name in names)
    assert biases == 1 + mtp and len(moved) == len(names) - biases


def test_in_bfloat16_loss_and_gradients_are_the_references_to_its_rounding():
    """bfloat16 compute over float32 parameters: the loss to 2e-3 (the
    operands' 2^-9 through eleven sublayers), the leaves no choice of the
    router reaches (the mixers', the attention's, the norms', the
    embedding and head) to 0.06 in relative L2, the expert layers' to 0.4:
    a score within bfloat16's rounding of the third flips a choice, and a
    fresh model's rows pull an expert's gradient every way (PERF.md section
    2: `moe_grad`'s sound readings)."""
    cfg = tiny("bfloat16")
    mesh, model = on_mesh(cfg)
    params, (want, want_g) = R.reference(cfg, t=96, seed=0)
    ids, tgt, pos = batch(cfg, t=96)
    # (the program at the backend's own products: not `R.program`'s)
    got, got_g = jax.jit(jax.value_and_grad(model.make_loss(mesh)))(
        params, ids, tgt, pos)
    hold_loss(want, got, 2e-3)
    hold_leaves(want_g, got_g, lambda name: 0.4 if "moe" in name else 0.06,
                err=lambda b, a: rel(b, a) if np.any(a) else 0.0)


def test_the_module_is_its_own_loss_term_and_counts_its_expert_layer():
    cfg = tiny()
    mesh, model = on_mesh(cfg)
    params = R.params(cfg, 1)
    ids, tgt, pos = batch(cfg)
    loss, c = model.make_loss(mesh, with_counters=True)(params, ids, tgt, pos)
    np.testing.assert_allclose(
        float(loss), float(c["loss_main"]) + 0.3 * float(c["loss_mtp"]),
        rtol=1e-6)
    without = dataclasses.replace(cfg, ssm_moe=dataclasses.replace(
        cfg.ssm_moe, num_nextn_predict_layers=0))
    main = jitted(lambda p: ref.vanilla_loss(without, p, ids, tgt, pos),
                  params)
    np.testing.assert_allclose(float(c["loss_main"]), float(main), rtol=1e-5)
    # three expert layers and the module's; three Mamba layers, none of the
    # module's (its pattern is `*E`)
    assert c["routed"].shape == (4, 16) and c["ssm_decay_min"].shape == (3,)
    assert model._mtp_keys == ("mtp_attn_layers", "mtp_moe_layers")


# ---- the chunked recurrence ----

def ssd_inputs(t, H, G, Pd=8, N=4, seed=0, dt_scale=1.0):
    k = jax.random.split(jax.random.key(seed), 5)
    x = jax.random.normal(k[0], (2, t, H, Pd))
    dt = dt_scale * jax.nn.softplus(jax.random.normal(k[1], (2, t, H)) - 2.0)
    A = -jnp.exp(jax.random.uniform(k[2], (H,), minval=0.0, maxval=2.5))
    B = jax.random.normal(k[3], (2, t, G, N))
    C = jax.random.normal(k[4], (2, t, G, N))
    return x, dt, A, B, C


def token_by_token(x, dt, A, B, C):
    R = x.shape[2] // B.shape[2]
    return ref.recurrence(x, dt, A, jnp.repeat(B, R, axis=2),
                          jnp.repeat(C, R, axis=2))


@pytest.mark.parametrize("t,chunk,H,G", [
    (100, 32, 4, 2), (128, 128, 4, 1), (257, 128, 8, 4), (50, 16, 6, 3),
    (7, 16, 2, 2)])
def test_the_chunked_recurrence_equals_the_token_by_token_one(t, chunk, H, G):
    """Values and every input's gradient, at lengths no chunk divides (the
    padding rows have dt = 0), at one and several groups, at a sequence
    shorter than a chunk. To 2e-5 of the largest entry: float32 sums in
    another order."""
    args = ssd_inputs(t, H, G)
    w = jax.random.normal(jax.random.key(9), (2, t, H, 8))
    weigh = lambda y, *_: jnp.sum(y * w)
    # (a side's value and its five gradients are one compiled program)
    (want_y,), want_g = outputs_and_grads(
        lambda *a: (token_by_token(*a),), weigh, *args)
    (y, decay_min), got_g = outputs_and_grads(
        lambda *a: ssd(*a, chunk=chunk), weigh, *args)
    np.testing.assert_allclose(weigh(y), weigh(want_y), rtol=2e-5)
    for a, b in zip(got_g, want_g):
        assert np.max(np.abs(a - b)) <= 2e-5 * np.max(np.abs(b))
    assert y.shape == (2, t, H, 8) and float(decay_min) < 0.0


def kernel_inputs(t, H, G, dtype=jnp.float32, dt_scale=1.0):
    """`ssd_inputs` at the kernels' widths (heads of 64, a state of 128),
    x, B and C in `dtype`."""
    x, dt, A, B, C = ssd_inputs(t, H, G, Pd=64, N=128, dt_scale=dt_scale)
    return x.astype(dtype), dt, A, B.astype(dtype), C.astype(dtype)


@pytest.mark.parametrize("t,chunk,H,G,dtype,dt_scale,tol", [
    # several groups, a length no chunk divides
    (300, 128, 4, 2, jnp.float32, 1.0, 5e-5),
    # one group of many heads (two blocks of 8), shorter than a chunk
    (100, 256, 16, 1, jnp.float32, 1.0, 5e-5),
    # groups of six heads (a block of three pairs), two chunks of 256
    (300, 256, 12, 2, jnp.float32, 1.0, 5e-5),
    # a large decay: a chunk's sums reach -60 and beyond
    (256, 128, 4, 2, jnp.float32, 4.0, 1e-4),
    (300, 128, 4, 1, jnp.bfloat16, 1.0, None),
    (300, 256, 16, 2, jnp.bfloat16, 1.0, None)])
def test_the_kernels_equal_the_text_and_the_token_by_token_recurrence(
        t, chunk, H, G, dtype, dt_scale, tol):
    """The Pallas kernels under the interpreter (`interpret=True`: the
    path a TPU takes, asked for by name) against the XLA text and against
    the token-by-token recurrence: the value, all five inputs' gradients
    and `decay_min`. In float32 to `tol` of the largest entry (float32 sums
    in another order; 1e-4 at the large decay, which is what float32
    holds). With bfloat16 operands the products round, in the kernels and in
    the text alike: each is held to the float32 recurrence on the same
    numbers, and the kernels may stand twice as far from it as the text."""
    args = kernel_inputs(t, H, G, dtype, dt_scale)
    w = jax.random.normal(jax.random.key(9), (2, t, H, 64))
    f32 = lambda a: a.astype(jnp.float32)

    def run(fn, args):
        """[y, the five gradients, decay_min (None of the recurrence)] of
        one compiled program (three eager runs of `fn` a side until PR
        74)."""
        (y, low), grads = outputs_and_grads(
            lambda *a: (*fn(*a), None)[:2],
            lambda y, _: jnp.sum(f32(y) * w), *args)
        return [y, *grads, low]

    *want, _ = run(lambda *a: (token_by_token(*a),), [f32(a) for a in args])
    *text, low_text = run(lambda *a: ssd(*a, chunk=chunk), args)
    *got, low_got = run(lambda *a: ssd(*a, chunk=chunk, interpret=True),
                        args)
    assert float(low_text) == float(low_got) < 0.0
    assert got[0].dtype == dtype and got[0].shape == (2, t, H, 64)
    for name, a, b, c in zip(("y", "dx", "ddt", "dA", "dB", "dC"), got, text,
                             want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        limit = tol if tol else max(2 * rel(b, c), 2e-3)
        assert rel(a, c) <= limit, (name, rel(a, c), rel(b, c))
        if tol:
            assert rel(a, b) <= tol, (name, rel(a, b))


def test_what_the_kernels_do_not_hold_is_the_texts_or_is_refused():
    """A state in bfloat16 (the benchmark's control) and a shape outside
    `ops/pallas/ssd.holds` take the text on every backend; asking for the
    interpreter there raises, as the channel rule's does."""
    args = kernel_inputs(128, 4, 2)
    narrow = ssd_inputs(128, 4, 2)
    for bad, kw in ((args, dict(chunk=128, state_dtype=jnp.bfloat16)),
                    (args, dict(chunk=64)), (narrow, dict(chunk=128)),
                    (kernel_inputs(128, 3, 3), dict(chunk=128))):
        assert "pallas_call" not in str(jax.make_jaxpr(
            lambda *a: ssd(*a, **kw))(*bad))
        with pytest.raises(ValueError, match="heads of 64 in pairs"):
            ssd(*bad, interpret=True, **kw)
    assert "pallas_call" in str(jax.make_jaxpr(
        lambda *a: ssd(*a, chunk=128, interpret=True))(*args))


def test_a_head_reads_its_own_groups_b_and_c():
    """With every group's B and C group 0's, the chunked form computes
    another function: heads of the later groups differ, group 0's do not."""
    x, dt, A, B, C = ssd_inputs(64, 4, 2)
    first = lambda a: jnp.broadcast_to(a[:, :, :1], a.shape)
    y, _ = ssd(x, dt, A, B, C, chunk=32)
    z, _ = ssd(x, dt, A, first(B), first(C), chunk=32)
    np.testing.assert_allclose(y[:, :, :2], z[:, :, :2], rtol=1e-6)
    assert rel(z[:, :, 2:], y[:, :, 2:]) > 0.5


def test_at_a_large_decay_a_bfloat16_state_fails_and_float32_holds():
    """dt A of about -0.5 a token: a chunk's sums reach -60, where
    bfloat16's step is 0.25 and the decays made from differences of two
    such sums are off by a quarter. The float32 sums hold 1e-4; a state
    kept in bfloat16 reads 1e-2 or worse."""
    x, dt, A, B, C = ssd_inputs(256, 4, 2, dt_scale=4.0)
    with jax.default_matmul_precision("highest"):
        want = token_by_token(x, dt, A, B, C)
        sound, decay_min = ssd(x, dt, A, B, C, chunk=128)
        rounded, _ = ssd(x, dt, A, B, C, chunk=128, state_dtype=jnp.bfloat16)
    assert float(decay_min) < -60.0
    assert rel(sound, want) < 1e-4
    assert rel(rounded, want) > 1e-2


def test_the_recurrences_flops_by_hand():
    # a chunk's C B^T once a group of 16 heads, the scores times dt x, the
    # chunk's own state and the entering state's part, a head and token
    assert ssd_flops_per_token(64, 128, 16, 128) == (
        2 * 128 * 128 / 16 + 2 * 128 * 64 + 4 * 64 * 128)


# ---- the expert FFN's new facts, and its defaults ----

def test_the_expert_ffns_defaults_are_the_leaves_it_always_made():
    moe = SharedRoutedFFN(32, 16, 8, top_k=2)
    p = moe.init(jax.random.key(0))
    assert sorted(p) == ["bias", "down", "gate", "router", "shared", "up"]
    assert sorted(p["shared"]) == ["down", "gate", "up"]
    assert p["gate"].shape == (8, 32, 16) and p["down"].shape == (8, 16, 32)
    assert moe.gated and moe.latent is None and moe.shared_f == 16
    assert set(ACTIVATIONS) == {"silu", "relu", "relu2"}
    np.testing.assert_array_equal(
        ACTIVATIONS["relu2"](jnp.array([-2.0, 0.0, 3.0])), [0.0, 0.0, 9.0])


@pytest.mark.parametrize("top_k", [3, 22])
def test_two_matrix_experts_in_a_latent_equal_experts_applied_one_by_one(
        top_k):
    """`gated=False`, `latent`, `shared_width` and `relu2` through both
    movers' paths (all 32 experts held: the one chunk of all pairs; 4 held:
    chunks of a share) at top-3 and at the published top-22, against the
    reference's expert layer. To 3e-5: float32 sums in another order."""
    d, l, f, E = 32, 16, 24, 32
    whole = SharedRoutedFFN(d, f, E, top_k=top_k, scaling=5.0,
                            activation="relu2", gated=False, latent=l,
                            shared_width=40)
    p = whole.init(jax.random.key(1))
    assert "gate" not in p and "gate" not in p["shared"]
    assert p["up"].shape == (E, l, f) and p["down"].shape == (E, f, l)
    assert p["latent"]["down"].shape == (d, l)
    assert p["shared"]["up"].shape == (d, 40)
    x = jax.random.normal(jax.random.key(2), (2, 48, d))
    def s(offset):
        sizes = ref.sizes_of(tiny())
        sizes.top_k, sizes.scaling, sizes.expert_offset = top_k, 5.0, offset
        return sizes

    with jax.default_matmul_precision("highest"):
        want, routed = ref._expert_ffn(p, x, s(0))
        got, c = apply_moe(whole, p, x)
        np.testing.assert_allclose(got, want, atol=3e-5)
        np.testing.assert_array_equal(c["routed"], routed)
        share = dataclasses.replace(whole, held=4, offset=8)
        ps = {**p, "up": p["up"][8:12], "down": p["down"][8:12]}
        want, _ = ref._expert_ffn(ps, x, s(8))
        got, c = apply_moe(share, ps, x)
    np.testing.assert_allclose(got, want, atol=3e-5)
    assert float(c["rows_here"]) == float(routed[8:12].sum())


# ---- the shares add up to the uncut layer ----

def sublayer(model, lp, x):
    """What the program's layer adds to the residual stream: `_layer_body`
    on one layer's parameters, less its input."""
    pos = jnp.zeros(x.shape[:2], jnp.int32)
    specs = jax.tree.map(lambda _: P(), lp)

    def body(lp, x):
        out, _ = model._resolved(x.shape[1])._layer_body(
            x, lp, (), pos, jnp.float32)
        return out - x

    return on_one_device(body, (specs, P()), P())(lp, x)


def one_layer(letter, **facts):
    """A model of one layer of kind `letter` and that layer's parameters."""
    cfg = tiny(hybrid_override_pattern=letter, num_nextn_predict_layers=0,
               **{k: v for k, v in facts.items()
                  if k not in ("num_heads", "num_kv_heads")})
    cfg = dataclasses.replace(cfg, **{k: v for k, v in facts.items()
                                      if k in ("num_heads", "num_kv_heads")})
    model = build_model(FAMILY, cfg)
    params = R.params(cfg, 5)
    key = model._layer_keys[0]
    return cfg, model, jax.tree.map(lambda a: a[0, 0], params[key])


def test_the_four_head_shares_of_a_mamba_layer_add_up_to_the_uncut_layer():
    """8 heads over 4 groups, cut four ways by heads: share r holds heads
    2r, 2r + 1 with group r's B and C, its columns of `w_in`, its channels
    of the convolution, its rows of `w_out`, and the gated norm's group is
    whole on it. The shares' out projections summed are the uncut
    reference's layer (to 2e-5: float32 sums in another order), and a
    share's fresh `A_log` is the uncut mixer's at its heads."""
    cfg, model, lp = one_layer("M", mamba_num_heads=8, n_groups=4)
    H, Pd, G, N = 8, 16, 4, 8
    x = jax.random.normal(jax.random.key(6), (2, 48, cfg.attn_dim))
    with jax.default_matmul_precision("highest"):
        y = ref._norm(lp["norm1"], x, 1e-5)
        want = ref._mamba(lp["mamba"], y, ref.sizes_of(cfg))
        total = 0.0
        for r in range(4):
            heads, chans = np.arange(2 * r, 2 * r + 2), np.arange(Pd)
            inner = (heads[:, None] * Pd + chans).reshape(-1)    # x or z
            grp = r * N + np.arange(N)
            conv = np.concatenate([inner, H * Pd + grp,
                                   H * Pd + G * N + grp])
            cols = np.concatenate([inner, H * Pd + conv,
                                   2 * H * Pd + 2 * G * N + heads])
            m = lp["mamba"]
            share = {"w_in": m["w_in"][:, cols], "conv": m["conv"][conv],
                     "conv_bias": m["conv_bias"][conv],
                     "A_log": m["A_log"][heads], "D": m["D"][heads],
                     "dt_bias": m["dt_bias"][heads],
                     "norm": m["norm"][inner], "w_out": m["w_out"][inner]}
            _, part, _ = one_layer("M", mamba_num_heads=2, n_groups=1,
                                   mamba_head_offset=2 * r)
            np.testing.assert_allclose(share["A_log"], jnp.log(1.0 + heads),
                                       rtol=1e-6)
            fresh = part._mods["mamba"].init(jax.random.key(0))
            np.testing.assert_allclose(fresh["A_log"], jnp.log(1.0 + heads),
                                       rtol=1e-6)
            total = total + sublayer(part, {"norm1": lp["norm1"],
                                            "mamba": share}, x)
    np.testing.assert_allclose(total, want, atol=2e-5)


def test_the_four_head_shares_of_an_attention_layer_add_up_to_the_uncut():
    """8 query heads over 2 key-value heads, cut four ways: share r holds
    query heads 2r, 2r + 1 and key-value head r // 2 (a key-value head
    stands on two shares); no positions anywhere."""
    cfg, model, lp = one_layer("*", num_heads=8, num_kv_heads=2)
    h = cfg.ssm_moe.head_dim
    x = jax.random.normal(jax.random.key(7), (2, 48, cfg.attn_dim))
    with jax.default_matmul_precision("highest"):
        y = ref._norm(lp["norm1"], x, 1e-5)
        want = ref._attention(lp, y, ref.sizes_of(cfg))
        total = 0.0
        for r in range(4):
            q = np.arange(2 * r * h, (2 * r + 2) * h)
            kv = np.arange((r // 2) * h, (r // 2 + 1) * h)
            share = {"norm1": lp["norm1"],
                     "wq": {"weight": lp["wq"]["weight"][:, q]},
                     "wk": {"weight": lp["wk"]["weight"][:, kv]},
                     "wv": {"weight": lp["wv"]["weight"][:, kv]},
                     "wo": {"weight": lp["wo"]["weight"][q]}}
            _, part, _ = one_layer("*", num_heads=2, num_kv_heads=1)
            total = total + sublayer(part, share, x)
    np.testing.assert_allclose(total, want, atol=2e-5)


def test_the_expert_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Four jobs hold four experts each of the layer's 16. Their routed
    parts, through the SAME up-projection of the latent (it is linear, and
    whole on every share), plus the shared expert counted once, are the
    uncut reference's layer: the router, the weights' normalisation, the
    down-projection and the shared expert see all of it on every share."""
    cfg, model, lp = one_layer("E")
    x = jax.random.normal(jax.random.key(8), (2, 48, cfg.attn_dim))
    with jax.default_matmul_precision("highest"):
        y = ref._norm(lp["norm1"], x, 1e-5)
        want, _ = ref._expert_ffn(lp["moe"], y, ref.sizes_of(cfg))
        sh = lp["moe"]["shared"]
        shared_only = ref._relu2(y, sh["up"], sh["down"])
        total = shared_only
        for lo in range(0, 16, 4):
            _, part, _ = one_layer("E", experts_held=4, expert_offset=lo)
            share = {**lp["moe"], "up": lp["moe"]["up"][lo:lo + 4],
                     "down": lp["moe"]["down"][lo:lo + 4]}
            total = total + (sublayer(part, {"norm1": lp["norm1"],
                                             "moe": share}, x) - shared_only)
    np.testing.assert_allclose(total, want, atol=3e-5)


# ---- the parameters' other forms ----

def test_parameters_round_trip_through_the_canonical_form_and_a_checkpoint(
        tmp_path):
    cfg = tiny()
    mesh, model = on_mesh(cfg, 2)
    params = R.params(cfg, 1)
    assert layer_counts(cfg) == {"mamba": 3, "attn": 1, "moe": 3,
                                 "mtp_mamba": 0, "mtp_attn": 1, "mtp_moe": 1}
    assert model._pattern == ((("moe_layers_0", 1), ("mamba_layers_0", 1)),
                              (("attn_layers_1", 1),))
    assert params["mamba_layers_0"]["mamba"]["w_in"].shape == (3, 1, 64, 164)
    assert sorted(params["moe_layers_0"]) == ["moe", "norm1"]
    assert sorted(params["attn_layers_1"]) == ["norm1", "wk", "wo", "wq",
                                               "wv"]
    np.testing.assert_allclose(
        params["mamba_layers_0"]["mamba"]["A_log"][1, 0],
        np.log([1.0, 2.0, 3.0, 4.0]), rtol=1e-6)
    canonical = model.to_canonical(params)
    jax.tree.map(np.testing.assert_array_equal,
                 model.from_canonical(canonical), params)
    save_checkpoint(str(tmp_path), 3, 1.0, canonical,
                    model.canonical_specs(), 1)
    restored, _, at = load_checkpoint(str(tmp_path), 3, R.params(cfg, 9),
                                      model.canonical_specs())
    assert at == 3
    jax.tree.map(np.testing.assert_array_equal, restored, params)


# ---- the step, its counters, the entry point ----

def test_the_train_step_returns_the_decays_rows_and_the_loss_falls():
    cfg = tiny()
    losses, (_, gnorm, c), _ = R.train(cfg, tp=1, dp=2)
    assert losses[-1] < losses[0] and np.isfinite(float(gnorm))
    # a row a Mamba layer (3), a row an expert layer (3 and the module's)
    assert c["ssm_decay_min"].shape == (3,) and c["routed"].shape == (4, 16)
    assert float(jnp.max(c["ssm_decay_min"])) < 0.0
    np.testing.assert_array_equal(c["routed"].sum(-1), [2 * 64 * 3] * 4)
    summary = moe_counters_summary(jax.device_get(c), cfg, 2 * 64)
    assert summary["rows_here_per_token"] == 3.0    # all experts held
    assert summary["ssm_decay_min"] == float(jnp.min(c["ssm_decay_min"]))


def test_train_cli_runs_the_family(tmp_path, capsys):
    from distributed_pytorch_from_scratch_tpu import train as train_mod
    tokens = token_file(tmp_path)
    train_mod.main([
        "--family", FAMILY, "--model", "tiny-ssm-moe",
        "--data_path", str(tokens), "--save_dir", str(tmp_path / "ckpt"),
        "--batch_size", "4", "--maxlen", "64", "--max_steps", "4",
        "--log_interval", "2", "--save_interval", "100",
        "--warmup_steps", "2"])
    out = capsys.readouterr().out
    assert f"model[{FAMILY}]" in out and "ssm_decay_min" in out
    events = [json.loads(line) for line in
              open(tmp_path / "ckpt" / "logs" / "metrics.jsonl")]
    assert any(e.get("tag") == "moe_counters" for e in events)


# ---- what is refused ----

@pytest.mark.parametrize("kw,message", [
    (dict(tp_size=2), "tp_size > 1"),
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


def test_decoding_and_the_hand_reduced_gradients_are_refused():
    from distributed_pytorch_from_scratch_tpu.models.decode import (
        require_decodable)
    _, model = on_mesh(tiny())
    assert not model.decodable and not model.hand_reduced_grads
    with pytest.raises(ValueError, match="cannot be decoded or served"):
        require_decodable(model)


@pytest.mark.parametrize("cfg,message", [
    (lambda: dataclasses.replace(tiny(), ssm_moe=None), "needs cfg.ssm_moe"),
    (lambda: dataclasses.replace(tiny(), num_layers=6), "names 7 layers"),
    (lambda: tiny(hybrid_override_pattern="EM-M*"), "holds '-'"),
    (lambda: tiny(mtp_hybrid_override_pattern="**"), "one of each kind"),
    (lambda: tiny(mamba_num_heads=3), "whole groups"),
    (lambda: tiny(num_nextn_predict_layers=2), "depth 0 or 1"),
])
def test_a_family_needs_its_own_facts_and_a_pattern_it_can_run(cfg, message):
    with pytest.raises(ValueError, match=message):
        build_model(FAMILY, cfg())


def test_one_sublayer_takes_one_stream_and_the_layers_own_input():
    """The one-sublayer fact and the two facts that want a layer of two."""
    class Early(SsmMoETransformer):
        router_reads_layer_input = True

    with pytest.raises(ValueError, match="one sublayer each"):
        Early(tiny())
    assert SsmMoETransformer.one_sublayer
    assert not any(cls.one_sublayer for name, cls in FAMILIES.items()
                   if name != FAMILY)


def test_the_published_pattern_is_cut_into_blocks_the_stack_scans():
    """The published 88 letters lower to periods of at most two runs; the
    benchmark's cut is one of its periods and the attention layer."""
    pattern = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
               "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
    assert len(pattern) == 88 and "EMEMEMEMEM*" in pattern
    assert (pattern.count("M"), pattern.count("E"),
            pattern.count("*")) == (40, 40, 8)
    model = build_model(FAMILY, tiny(hybrid_override_pattern=pattern,
                                     num_nextn_predict_layers=0))
    layers = sum(n * (repeats or 1) for repeats, parts in model._blocks
                 for _, _, _, n in parts)
    assert layers == 88
    cut = build_model(FAMILY, tiny(hybrid_override_pattern="EMEMEMEMEM*",
                                   num_nextn_predict_layers=0))
    assert cut._pattern == ((("moe_layers_0", 1), ("mamba_layers_0", 1)),
                            (("attn_layers_1", 1),))
    assert [r for r, _ in cut._blocks] == [5, 1]


# ---- the counts at the published widths ----

def published(held=8, mtp=0):
    """The benchmark's cut: one tensor-parallel rank of four (32 of 128 SSM
    heads with 2 of 8 groups, 8 of 32 query heads over 1 of 2 key-value
    heads), 8 of 512 experts, an eighth of the vocabulary, one period."""
    return ModelConfig(
        attn_dim=4096, ffn_dim=5376, num_heads=8, num_kv_heads=1,
        num_layers=11, vocab_size=16384, maxlen=262144, num_experts=512,
        moe_top_k=22, ssm_moe=SsmMoEConfig(
            hybrid_override_pattern="EMEMEMEMEM*", mamba_num_heads=32,
            mamba_head_dim=64, ssm_state_size=128, n_groups=2, head_dim=128,
            moe_intermediate_size=2688, moe_latent_size=1024,
            moe_shared_expert_intermediate_size=5376,
            routed_scaling_factor=5.0, experts_held=held,
            num_nextn_predict_layers=mtp))


def test_parameter_counts_at_the_published_widths():
    """ISSUE 63's arithmetic, as `init` makes the leaves."""
    cfg = published()
    model = build_model(FAMILY, cfg)
    mixer = model._mods["mamba"]
    assert mixer.num_params() == (4096 * 4640 + 2560 * 4 + 2560
                                  + 2048 * 4096 + 2048 + 96)
    parts = SsmMoETransformer.param_counts(cfg)
    assert parts["mamba_layers"] == 5 * 27_413_088
    assert parts["attn_layers"] == 9_441_280
    assert parts["moe_layers"] == 5 * 98_570_752
    assert parts["embedding_and_head"] == 2 * 16384 * 4096 == 134_217_728
    assert cfg.num_params() == 773_582_304
    made = jax.eval_shape(model.init, jax.random.key(0))
    assert sum(x.size for x in jax.tree.leaves(made)) == cfg.num_params()
    # x 16 bytes (weights, gradients, two Adam moments): 12.38 GB
    assert cfg.num_params() * 16 / 1e9 == pytest.approx(12.38, abs=0.005)
    # the module: one attention and one expert layer and the 8192 -> 4096
    # projection with its three norms
    with_module = published(mtp=1).num_params() - cfg.num_params()
    assert with_module == (9_441_280 + 98_570_752 + 2 * 4096 * 4096
                           + 3 * 4096) == 141_578_752
    # uncut, an expert layer holds 512 experts of 2 x 1024 x 2688
    assert (SsmMoETransformer.param_counts(published(held=512))["moe_layers"]
            - parts["moe_layers"]) == 5 * 504 * 5_505_024
    flops = model_flops_per_step(cfg, 1, 4096, cfg.num_params())
    assert 0.9e9 < flops / 4096 / 3 < 1.3e9


def test_remat_auto_sizes_the_benchmarks_cell(capsys):
    """`remat="auto"` at the cell's shapes on a v5e's 15.75 GiB: with no
    reserve held beside 8.65 GiB of state every rung fits, and the top one
    keeps the one attention layer's q, k, v and flash outputs."""
    cfg = dataclasses.replace(published(), compute_dtype="bfloat16")
    model = build_model(FAMILY, cfg, remat_budget_gib=15.748)
    assert model.tagged_layers["flash_out"] == 1 == \
        model.tagged_layers["q_proj"] and not model.tagged_layers["ffn_gate"]
    assert model.stacked_layers == 11
    layer_params = cfg.num_params() - 2 * 16384 * 4096 - 4096
    memory.select_remat_traced.cache_clear()
    rung = memory.select_remat_traced(model, cfg.num_params(), layer_params,
                                      1, 4096)
    said = capsys.readouterr().err
    assert rung == "dots" and "reserve_held=False" in said, said
    estimate = float(said.split(f"{rung}=")[1].split("GiB")[0])
    assert 13.0 < estimate < 14.65, said


# ---- what must not move ----

# the train step of every standing family at its tiny preset, as tests/
# test_mhc_mla_moe.py and tests/test_kda_mla_moe.py hold theirs: the
# StableHLO's digest (locations stripped; sha256, first 16 digits) on the
# PARENT'S tree (PR 62's), before `parallel/moe.py` learned experts of two
# matrices, `relu2`, a latent and a shared width, and `models/stack.py` the
# one-sublayer fact: every one of them at its default.
STANDING = {
    "gpt2": ("tiny", "557e9d12313622a3"),
    "mla_moe": ("tiny-mla-moe", "83b0575bcf151845"),
    "gdn_moe": ("tiny-gdn-moe", "6d83ef8f30d65710"),
    "conv_moe": ("tiny-conv-moe", "64b65f649e0393d9"),
    "bd_moe": ("tiny-bd-moe", "35cad4194c7a5c5e"),
    "swa_moe": ("tiny-swa-moe", "9c832b8734e8942b"),
    "early_moe": ("tiny-early-moe", "fba41f5652566548"),
    "mhc_mla_moe": ("tiny-mhc-mla-moe", "6060959548dcf1c3"),
    # PR 64 MEANT to move this one (29fbd62e921a3dd5 before it): the delta
    # mixer's own checkpoint went and its q, k, v projections took the
    # ladder's names (parallel/kda.py); the other eight are the parent's
    "kda_mla_moe": ("tiny-kda-mla-moe", "7e367f7a5ca3d3da"),
}


@pytest.mark.parametrize("family", sorted(STANDING))
def test_a_standing_family_lowers_to_the_text_the_parent_lowered_it_to(
        family):
    preset, digest = STANDING[family]
    text = lowered_text(family, model_preset(preset))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest
    assert "mamba" not in text and "moe_latent" not in text


def test_the_new_familys_step_names_its_scopes():
    """The named scopes a device trace splits the step by are the name
    stacks of the lowered text's debug info."""
    text = lowered_text(FAMILY, tiny(), shape=(2, 128), debug_info=True)
    for scope in ("mamba/in_proj", "mamba/conv", "mamba/ssd",
                  "mamba/gate_norm", "mamba/out_proj", "gqa_attn",
                  "moe_latent/down", "moe_latent/up", "moe_route/",
                  "moe_experts", "moe_shared", "mtp/", "head_loss"):
        assert scope in text, scope
    assert FAMILY in FAMILIES and len(FAMILIES) >= 12
