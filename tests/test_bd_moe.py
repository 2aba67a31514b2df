"""The `bd_moe` family (models/bd_moe.py): a grouped-query expert decoder
trained by block diffusion over the rows `[noised ; clean]` of a sequence
under a declared attention mask. CPU, tiny sizes, float32.

* the program against the plain reference (models/vanilla_bd_moe.py, whose
  mask is a boolean matrix from the three rules) on a SHARED draw: loss and
  EVERY gradient leaf, at tp 1 and tp 2, on a job that holds a slice of the
  experts; no top-k choice sits on a tie (the margin is asserted);
* the mask means what it says, exactly: the clean half does not see the
  noised one and equals a run on `x0` alone under the block-causal mask; a
  noised block is blind to its own and later clean blocks and to other
  noised blocks, and sees earlier clean blocks; with B = L the noised half
  is a bidirectional run on `xt` alone;
* the flash kernels under the declared mask (the interpreter) against the
  dense XLA path, forward and all three gradients, both walks, and the
  plan's counts;
* the noise: a function of (seed, step, batch) only, one level a sequence,
  the masked share inside its binomial band, the weights 1 / p;
* the share test (softmax scores, no shared expert to count once);
* what the family does not run is refused with a message;
* every other family lowers to the text it lowered to before the mask was a
  declaration;
* the counts at the published widths (645,623,296 in the cut).
"""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from family_recipe import (Recipe, apply_moe, hold_leaves, hold_loss,
                           lowered_text, outputs_and_grads, token_file)

from distributed_pytorch_from_scratch_tpu.config import (
    BdMoEConfig, ModelConfig, OptimizerConfig, model_preset)
from distributed_pytorch_from_scratch_tpu.models import build_model
from distributed_pytorch_from_scratch_tpu.models.bd_moe import (
    BlockDiffusionMoETransformer, block_diffusion_noise)
from distributed_pytorch_from_scratch_tpu.models.vanilla_bd_moe import (
    bd_mask, reference_hidden, sizes_of, vanilla_loss)
from distributed_pytorch_from_scratch_tpu.obs.attribution import (
    flash_tile_stats)
from distributed_pytorch_from_scratch_tpu.ops.attention import (
    CAUSAL, block_diffusion, mask_matrix, masked_attention_xla)
from distributed_pytorch_from_scratch_tpu.ops.pallas import (
    flash_attention as fa_mod)
from distributed_pytorch_from_scratch_tpu.parallel.moe import SharedRoutedFFN
from distributed_pytorch_from_scratch_tpu.training.metrics import (
    model_flops_per_step, moe_counters_summary)
from distributed_pytorch_from_scratch_tpu.training.optim import (
    init_adam_state)
from distributed_pytorch_from_scratch_tpu.training.train_step import (
    build_train_step)


R = Recipe("bd_moe", vanilla_loss)
tiny, on_mesh = R.tiny, R.on_mesh


def batch(cfg, b=2, L=32, seed=0):
    """(its own: a batch is clean rows and positions, no targets: the family
    makes its targets of the draw, so the recipe's reference and program,
    which take (ids, targets, positions), do not serve and the comparison
    below hands both sides the draw itself)"""
    rng = np.random.default_rng(seed)
    x0 = rng.integers(3, cfg.vocab_size, (b, L)).astype(np.int32)
    return x0, np.tile(np.arange(L, dtype=np.int32), (b, 1))


def draw(cfg, x0, seed=7, step=3):
    bd = cfg.bd_moe
    return block_diffusion_noise(seed, step, x0, bd.mask_token_id,
                                 bd.noise_eps)


# ---- the program against the plain reference ----

@pytest.mark.parametrize("tp,impl", [(1, "xla"), (2, "xla")])
def test_loss_and_every_gradient_leaf_equal_the_reference(tp, impl):
    """On a job that holds experts 2..5 of 8, on one draw handed to both.
    Leaves to 1e-5 of their largest entry."""
    cfg = tiny(experts_held=4, expert_offset=2)
    mesh, model = on_mesh(cfg, tp, attn_impl=impl)
    params = R.params(cfg)
    x0, pos = batch(cfg)
    xt, m, p = draw(cfg, x0)
    with jax.default_matmul_precision("highest"):
        want, want_g = jax.jit(jax.value_and_grad(
            lambda pr: vanilla_loss(cfg, pr, x0, pos, xt, m, p)))(params)
        given = model.make_loss(mesh, given_noise=True)
        got, got_g = jax.jit(jax.value_and_grad(
            lambda pr: given(pr, x0, pos, xt, m, p)))(
                jax.device_put(params, model.shardings(mesh)))
    hold_loss(want, got)
    assert len(hold_leaves(want_g, got_g, 1e-5)[0]) == 15
    # heads x width is not the model's width; no bias, no shared expert
    assert params["layers"]["wq"]["weight"].shape == (2, 64, 128)
    assert params["layers"]["wk"]["weight"].shape == (2, 64, 64)
    assert params["layers"]["wo"]["weight"].shape == (2, 128, 64)
    assert params["layers"]["q_norm"]["scale"].shape == (2, 32)
    assert set(params["layers"]["moe"]) == {"router", "gate", "up", "down"}
    assert params["layers"]["moe"]["gate"].shape == (2, 4, 64, 32)
    assert params["lm_head"]["weight"].shape == (64, 1024)


def test_no_top_k_choice_sits_on_a_tie():
    cfg = tiny()
    moe = SharedRoutedFFN(cfg.attn_dim, 32, cfg.num_experts, cfg.moe_top_k,
                          n_shared=0, score="softmax")
    p = moe.init(jax.random.key(3))
    x = jax.random.normal(jax.random.key(4), (256, cfg.attn_dim))
    with jax.default_matmul_precision("highest"):
        s = np.sort(np.asarray(jax.nn.softmax(x @ p["router"])), axis=-1)
    margin = s[:, -cfg.moe_top_k] - s[:, -cfg.moe_top_k - 1]
    assert margin.min() > 1e-6


def test_the_step_draws_its_noise_from_the_seed_and_the_step_count():
    """`make_loss`'s own function (the fifth argument is the optimizer's
    step count) equals the loss on that draw handed in as arrays, and
    another count or another seed is another draw."""
    cfg = tiny()
    mesh, model = on_mesh(cfg, 1, noise_seed=11)
    params = model.init(jax.random.key(0))
    x0, pos = batch(cfg)
    own = model.make_loss(mesh)
    given = model.make_loss(mesh, given_noise=True)
    at3 = float(own(params, x0, x0, pos, jnp.int32(3)))
    np.testing.assert_allclose(
        at3, float(given(params, x0, pos, *draw(cfg, x0, 11, 3))), rtol=1e-6)
    assert at3 == float(own(params, x0, x0 * 0, pos, jnp.int32(3)))  # no tgt
    assert abs(at3 - float(own(params, x0, x0, pos, jnp.int32(4)))) > 1e-3
    _, other = on_mesh(cfg, 1, noise_seed=12)
    assert abs(at3 - float(other.make_loss(mesh)(
        params, x0, x0, pos, jnp.int32(3)))) > 1e-3


# ---- the mask means what it says ----

def logits_of(cfg, params, rows, positions):
    """The program's logits for rows ALREADY doubled (`make_forward`)."""
    mesh, model = on_mesh(cfg, 1, attn_impl="xla")
    with jax.default_matmul_precision("highest"):
        return np.asarray(model.make_forward(mesh)(params, rows, positions))


def reference_logits(cfg, params, rows, positions, live):
    with jax.default_matmul_precision("highest"):
        x, _ = reference_hidden(
            params, rows, positions, sizes=sizes_of(cfg),
            expert_offset=0, rope_theta=cfg.rope_theta,
            eps=cfg.bd_moe.rms_norm_eps, live=live)
        scale = params["norm"]["scale"]
        x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                              + cfg.bd_moe.rms_norm_eps) * scale
        return np.asarray(x @ params["lm_head"]["weight"])


def test_the_clean_half_never_sees_the_noised_half():
    """The clean rows' logits are the same numbers whatever `xt` is, and
    are a run on `x0` ALONE under the block-causal mask."""
    cfg = tiny()
    _, model = on_mesh(cfg, 1)
    params = model.init(jax.random.key(1))
    L, B = 32, cfg.bd_moe.block_length
    x0, pos = batch(cfg, L=L)
    xt, _, _ = draw(cfg, x0)
    other, _, _ = draw(cfg, x0, seed=8)
    assert (np.asarray(xt) != np.asarray(other)).any()
    pos2 = np.concatenate([pos, pos], 1)
    a = logits_of(cfg, params, np.concatenate([xt, x0], 1), pos2)
    b = logits_of(cfg, params, np.concatenate([other, x0], 1), pos2)
    np.testing.assert_array_equal(a[:, L:], b[:, L:])
    assert np.abs(a[:, :L] - b[:, :L]).max() > 1e-3
    alone = reference_logits(
        cfg, params, x0, pos,
        lambda q, k: (k[None, :] // B) <= (q[:, None] // B))
    np.testing.assert_allclose(a[:, L:], alone, rtol=2e-5, atol=2e-5)


def test_a_noised_block_reads_earlier_clean_blocks_and_its_own_noised_one():
    cfg = tiny()
    _, model = on_mesh(cfg, 1)
    params = model.init(jax.random.key(1))
    L, B = 32, cfg.bd_moe.block_length
    x0, pos = batch(cfg, b=1, L=L)
    xt, _, _ = draw(cfg, x0)
    xt = np.asarray(xt)
    pos2 = np.concatenate([pos, pos], 1)
    blk = 3                                     # noised positions 12..15
    own = slice(blk * B, (blk + 1) * B)
    base = logits_of(cfg, params, np.concatenate([xt, x0], 1), pos2)

    def moved(change_x0=None, change_xt=None):
        c0, ct = x0.copy(), xt.copy()
        if change_x0 is not None:
            c0[0, change_x0] = 5 + (c0[0, change_x0] + 7) % 900
        if change_xt is not None:
            ct[0, change_xt] = 5 + (ct[0, change_xt] + 7) % 900
        out = logits_of(cfg, params, np.concatenate([ct, c0], 1), pos2)
        return np.abs(out[0, own] - base[0, own]).max()

    # its own clean block, a later one, another noised block: not a bit
    assert moved(change_x0=own) == 0.0
    assert moved(change_x0=slice((blk + 1) * B, L)) == 0.0
    assert moved(change_xt=slice(0, blk * B)) == 0.0
    assert moved(change_xt=slice((blk + 1) * B, L)) == 0.0
    # an earlier clean block, and its own noised block: it does
    assert moved(change_x0=slice(0, B)) > 1e-4
    assert moved(change_xt=own) > 1e-4


def test_one_block_a_sequence_is_a_bidirectional_run_on_the_noised_rows():
    """B = L: no earlier block exists, so the noised half is `xt` alone
    with every position seeing every other."""
    L = 32
    cfg = tiny(block_length=L)
    _, model = on_mesh(cfg, 1)
    params = model.init(jax.random.key(1))
    x0, pos = batch(cfg, L=L)
    xt, _, _ = draw(cfg, x0)
    got = logits_of(cfg, params, np.concatenate([xt, x0], 1),
                    np.concatenate([pos, pos], 1))
    alone = reference_logits(
        cfg, params, xt, pos, lambda q, k: jnp.ones((q.size, k.size), bool))
    np.testing.assert_allclose(got[:, :L], alone, rtol=2e-5, atol=2e-5)


def test_the_declaration_is_the_three_rules():
    for L, B in ((8, 2), (12, 4), (16, 16)):
        live = np.asarray(mask_matrix(block_diffusion(B, L), 2 * L))
        rows = np.arange(2 * L)
        np.testing.assert_array_equal(live, bd_mask(rows, rows, L, B))
        assert live.sum() == L * (L + B)            # a quarter of 4 L^2
        assert not live[L:, :L].any()               # clean to noised: dead
        assert live.any(axis=1).all()               # every row sees a key
    assert np.asarray(mask_matrix(CAUSAL, 5)).sum() == 15
    with pytest.raises(ValueError, match="must divide"):
        block_diffusion(3, 8)
    with pytest.raises(ValueError, match="takes 16 rows"):
        mask_matrix(block_diffusion(4, 8), 12)


# ---- the flash kernels under the declared mask ----

@pytest.mark.parametrize("L,B,block,hq,hkv,walk", [
    (256, 4, 128, 2, 2, "row"), (256, 4, 128, 2, 2, "grid"),
    (384, 32, 128, 8, 1, "row"), (384, 32, 128, 8, 1, "grid"),
    (128, 128, 128, 1, 1, "row"),
    (256, 4, 128, 2, 2, "once"), (384, 32, 128, 8, 1, "once")])
def test_the_kernels_under_the_declared_mask_equal_the_dense_path(
        L, B, block, hq, hkv, walk, monkeypatch, flash_bwd_calls):
    """Several tiles a half, groups of 1 and 8, block lengths 4, 32 and the
    sub-tile's own edge; the head resident (`row`: the forward's row walk
    and the one backward kernel; `once`: that kernel with its whole-row
    blocks single-buffered, what a head over the first budget takes since
    PR 56) and the gridded walk with the split backward. Forward and all
    three gradients."""
    if walk == "grid":
        monkeypatch.setattr(fa_mod, "KV_ROW_VMEM_BYTES", 0)
        monkeypatch.setattr(fa_mod, "BWD_ROW_ONCE_VMEM_BYTES", 0)
    if walk in ("grid", "once"):
        monkeypatch.setattr(fa_mod, "BWD_ROW_VMEM_BYTES", 0)
    mask = block_diffusion(B, L)
    key = jax.random.key(0)
    shape = lambda h: (1, h, 2 * L, 64)
    q = jax.random.normal(jax.random.fold_in(key, 1), shape(hq))
    k = jax.random.normal(jax.random.fold_in(key, 2), shape(hkv))
    v = jax.random.normal(jax.random.fold_in(key, 3), shape(hkv))
    w = jax.random.normal(jax.random.fold_in(key, 4), shape(hq))
    kernel = lambda q, k, v: fa_mod.flash_attention(
        q, k, v, block, block, block, block, interpret=True, mask=mask)
    dense = lambda q, k, v: masked_attention_xla(q, k, v, mask)
    # (a side's output and its three gradients are one compiled program)
    weigh = lambda o: jnp.sum(o * w)
    (o,), got = outputs_and_grads(lambda *a: (kernel(*a),), weigh, q, k, v,
                                  precision=None)
    (o_dense,), want = outputs_and_grads(lambda *a: (dense(*a),), weigh, q, k,
                                         v, precision=None)
    np.testing.assert_allclose(o, o_dense, atol=2e-5)
    assert flash_bwd_calls(kernel, q, k, v) == {
        "row": [("flash_bwd", [None] * 9)], "once": [("flash_bwd", [1] * 9)],
        "grid": [("flash_bwd_dq", [None] * 7), ("flash_bwd_dkv", [None] * 8)],
    }[walk if L > block else "row"]
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-4)


@pytest.mark.parametrize("L,B,block", [(256, 4, 128), (1024, 32, 256),
                                       (4096, 4, 1024)])
def test_the_plan_counts_what_the_mask_leaves_live(L, B, block):
    """Brute force over the plan's own rectangles: every live entry lies in
    a computed sub-tile, an unmasked sub-tile holds live entries only, the
    clean-query / noised-key quadrant is skipped whole, and the counts are
    what `flash_tile_stats` says."""
    mask = block_diffusion(B, L)
    n = 2 * L // block
    live_total = 0
    for backward in (False, True):
        work = 0
        for qb in range(n):
            for kb in range(n):
                plan = fa_mod.subtile_plan(mask, block, block, qb, kb,
                                           2 * L, 64, backward, n)
                if qb >= n // 2 and kb < n // 2:
                    assert not plan.bands and plan.work_elems == 0
                work += plan.work_elems
                if L > 1024:
                    continue
                rows = qb * block + np.arange(block)
                cols = kb * block + np.arange(block)
                live = bd_mask(rows, cols, L, B)
                covered = np.zeros_like(live)
                for r0, nr, rects in plan.bands:
                    for c0, nc, masked in rects:
                        covered[r0:r0 + nr, c0:c0 + nc] = True
                        if not masked:
                            assert live[r0:r0 + nr, c0:c0 + nc].all()
                assert not (live & ~covered).any()
                live_total += live.sum() if not backward else 0
        stats = flash_tile_stats(2 * L, block, block, head_dim=64, mask=mask,
                                 backward=backward)
        assert stats["work_elems"] == work >= stats["ideal_elems"]
        assert stats["ideal_elems"] == L * (L + B)
    if L <= 1024:
        assert live_total == L * (L + B)


def test_the_cells_shape_plans_a_quarter_over_the_live_entries():
    """2 x 4096 rows at head 128, blocks of 1024: the forward's plan
    computes 1.249 of the live entries, the backward's 1.124; a causal plan
    over the same rows computes twice the forward's."""
    mask = block_diffusion(4, 4096)
    fwd = flash_tile_stats(8192, head_dim=128, mask=mask)
    bwd = flash_tile_stats(8192, head_dim=128, mask=mask, backward=True)
    assert (fwd["block_q"], fwd["sub_q"], fwd["sub_k"]) == (1024, 256, 512)
    assert fwd["ideal_elems"] == 4096 * 4100 == bwd["ideal_elems"]
    assert fwd["work_elems"] == 20_971_520 and bwd["work_elems"] == 18_874_368
    assert flash_tile_stats(8192, head_dim=128)["work_elems"] \
        == 35_651_584 > 1.6 * fwd["work_elems"]


def test_what_the_kernels_cannot_plan_is_refused():
    q = jnp.zeros((1, 1, 256, 64))
    with pytest.raises(ValueError, match="divides 128"):
        fa_mod.flash_attention(jnp.zeros((1, 1, 384, 64)), *2 * (jnp.zeros(
            (1, 1, 384, 64)),), interpret=True, mask=block_diffusion(3, 192))
    with pytest.raises(ValueError, match="multiple of the grid block"):
        fa_mod.flash_attention(jnp.zeros((1, 1, 128, 64)), *2 * (jnp.zeros(
            (1, 1, 128, 64)),), interpret=True, mask=block_diffusion(4, 64))
    with pytest.raises(ValueError, match="takes 512 rows"):
        fa_mod.flash_attention(q, q, q, interpret=True,
                               mask=block_diffusion(4, 256))
    with pytest.raises(ValueError, match="no t_real"):
        fa_mod.flash_attention(q, q, q, interpret=True, t_real=200,
                               mask=block_diffusion(4, 128))


# ---- the noise ----

def test_the_noise_is_a_function_of_seed_step_and_batch_alone():
    x0 = np.full((64, 512), 9, np.int32)
    xt, m, p = (np.asarray(a) for a in block_diffusion_noise(
        5, 17, x0, 1, 1e-3))
    again = block_diffusion_noise(5, jnp.int32(17), x0, 1, 1e-3)
    for a, b in zip((xt, m, p), again):
        np.testing.assert_array_equal(a, b)
    # another seed, another count, or ONE other token anywhere in the batch
    # (two tokens exchanged too: the checksum reads the order) is another
    # draw, under one seed and count
    other, swapped = x0.copy(), x0.copy()
    other[63, 511] = 10
    swapped[0, :2] = 8, 10
    for seed, step, data in ((6, 17, x0), (5, 18, x0), (5, 17, other),
                             (5, 17, swapped), (5, 17, swapped[:, ::-1])):
        assert (np.asarray(block_diffusion_noise(seed, step, data, 1,
                                                 1e-3)[1]) != m).mean() > 0.2
    # one level a sequence, inside [eps, 1]
    assert p.shape == (64,) and p.min() >= 1e-3 and p.max() <= 1.0
    assert len(np.unique(p)) == 64
    # the mask token where masked, the data elsewhere
    np.testing.assert_array_equal(xt, np.where(m, 1, 9))
    # a sequence's masked share inside five deviations of Binomial(L, p)
    share = m.mean(-1)
    assert (np.abs(share - p) <= 5 * np.sqrt(p * (1 - p) / 512) + 1e-9).all()
    assert 0.35 < m.mean() < 0.65


def test_the_loss_weights_are_one_over_the_level():
    """On a model whose logits are the same at every position the loss is
    `CE x sum(m / p) / (b L)`: the counters carry the sum."""
    cfg = tiny()
    mesh, model = on_mesh(cfg, 1)
    params = jax.tree.map(jnp.zeros_like, model.init(jax.random.key(0)))
    x0, pos = batch(cfg, b=4, L=64)
    xt, m, p = draw(cfg, x0)
    loss, counters = model.make_loss(mesh, with_counters=True,
                                     given_noise=True)(
        params, x0, pos, xt, m, p)
    m, p = np.asarray(m), np.asarray(p)
    weight_sum = (m / p[:, None]).sum()
    np.testing.assert_allclose(float(counters["weight_sum"]), weight_sum,
                               rtol=1e-6)
    np.testing.assert_allclose(
        float(loss), np.log(cfg.vocab_size) * weight_sum / m.size, rtol=1e-5)
    assert float(counters["masked"]) == m.sum()
    assert float(counters["positions"]) == m.size
    np.testing.assert_allclose(float(counters["p_sum"]) / m.size, p.mean(),
                               rtol=1e-5)


# ---- the share test ----

def test_the_eight_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Eight jobs hold four experts each of one layer's 32. Their routed
    parts (there is no shared expert to count once) are the layer a job
    holding all 32 computes: the softmax weights are normalised over all
    chosen experts, held or not; and a row none of whose choices a share
    holds gets exactly zero from it."""
    d, f, E, k = 32, 16, 32, 4
    kw = dict(n_shared=0, score="softmax")
    whole = SharedRoutedFFN(d, f, E, k, **kw)
    p = whole.init(jax.random.key(0))
    assert "bias" not in p and "shared" not in p
    x = jax.random.normal(jax.random.key(1), (2, 64, d))
    with jax.default_matmul_precision("highest"):
        want, counted = apply_moe(whole, p, x)
        chosen, weights = whole.route(p, x.reshape(-1, d))
        np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0,
                                   rtol=1e-6)
        parts, rows = [], 0.0
        for share in range(8):
            lo = 4 * share
            held = SharedRoutedFFN(d, f, E, k, held=4, offset=lo, **kw)
            ps = {**p, **{n: p[n][lo:lo + 4] for n in ("gate", "up", "down")}}
            out, c = apply_moe(held, ps, x)
            none_held = ~np.any((np.asarray(chosen) >= lo)
                                & (np.asarray(chosen) < lo + 4), axis=-1)
            assert none_held.any()
            assert not np.any(np.asarray(out).reshape(-1, d)[none_held])
            np.testing.assert_array_equal(c["routed"], counted["routed"])
            parts.append(out)
            rows += float(c["rows_here"])
    np.testing.assert_allclose(sum(parts), want, atol=1e-5)
    assert rows == float(counted["rows_here"]) == 2 * 64 * k


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
        build_model("bd_moe", tiny(), **kw)


@pytest.mark.parametrize("cfg,message", [
    (model_preset("tiny"), "needs cfg.bd_moe"),
    (dataclasses.replace(tiny(), num_experts=0), "num_experts > 0"),
    (tiny(mask_token_id=5000), "not in the vocabulary"),
])
def test_a_family_needs_its_own_facts(cfg, message):
    with pytest.raises(ValueError, match=message):
        build_model("bd_moe", cfg)


# ---- the step: counters, memory facts, the CLI ----

def test_the_train_step_trains_and_counts_rows_and_masked_positions():
    cfg = tiny(experts_held=4, expert_offset=2)
    mesh, model = on_mesh(cfg, 1, attn_impl="xla", noise_seed=5)
    params = model.init(jax.random.key(0))
    opt = init_adam_state(params)
    step = build_train_step(model, mesh, OptimizerConfig(lr=3e-3,
                                                         warmup_steps=2),
                            with_grad_norm=True, with_counters=True)
    x0, pos = batch(cfg, b=4, L=64)
    means, masked = [], []
    for _ in range(8):
        params, opt, (loss, norm, c) = step(params, opt, x0, x0, pos)
        means.append(float(loss) * float(c["positions"])
                     / float(c["weight_sum"]))
        masked.append(float(c["masked"]))
    assert np.isfinite(means).all() and means[-1] < means[0]
    assert len(set(masked)) > 1                 # a fresh draw every step
    # a layer sees 2L rows a sequence: 2 x 4 x 64 rows x top_k pairs
    assert c["routed"].shape == (2, 8) and c["rows_here"].shape == (2,)
    np.testing.assert_array_equal(c["routed"].sum(-1),
                                  np.full(2, 2 * 4 * 64 * cfg.moe_top_k))
    np.testing.assert_array_equal(c["rows_here"],
                                  c["routed"][:, 2:6].sum(-1))
    # the grouped products' groups cover the held rows and nothing more
    np.testing.assert_array_equal(c["rows_computed"], c["rows_here"])
    assert float(c["positions"]) == 4 * 64
    said = moe_counters_summary(c, cfg, 4 * 64)
    assert 1.0 < said["rows_here_per_token"] < 3.0      # per DATA token
    assert said["rows_computed_per_token"] == said["rows_here_per_token"]
    # whole chunks: never fewer rows than are held
    assert said["rows_walked_per_token"] >= said["rows_here_per_token"]
    assert model_flops_per_step(cfg, 4, 64, model.num_params(cfg)) > 0


def test_train_cli_runs_the_family(tmp_path, capsys):
    import json
    from distributed_pytorch_from_scratch_tpu import train as train_mod
    tokens = token_file(tmp_path)
    train_mod.main([
        "--family", "bd_moe", "--model", "tiny-bd-moe", "--tp_size", "2",
        "--data_path", str(tokens), "--save_dir", str(tmp_path / "ckpt"),
        "--batch_size", "4", "--maxlen", "64", "--max_steps", "4",
        "--log_interval", "2", "--save_interval", "100",
        "--warmup_steps", "2"])
    out = capsys.readouterr().out
    assert "model[bd_moe]" in out and "rows_here_per_token" in out
    assert "rows_computed_per_token" in out
    assert "rows_walked_per_token" in out
    assert "masked_share" in out
    events = [json.loads(line) for line in
              open(tmp_path / "ckpt" / "logs" / "metrics.jsonl")]
    assert any(e.get("tag") == "moe_counters" for e in events)
    bd = [e for e in events if e.get("tag") == "bd_counters"]
    assert bd and 0.0 < bd[-1]["masked_share"] < 1.0 and "p_mean" in bd[-1]
    with pytest.raises(SystemExit, match="reads the config field"):
        train_mod.main(["--family", "conv_moe", "--model", "tiny-bd-moe",
                        "--data_path", str(tokens),
                        "--save_dir", str(tmp_path / "x")])


def test_the_memory_facts_count_the_rows_the_chunk_and_half_the_logits():
    """At a held share of 1/8 the chunk is one mean share, an eighth of
    all pairs; the head reads half of the rows the stack sees."""
    from distributed_pytorch_from_scratch_tpu.training.memory import (
        step_bytes)
    eighth = build_model("bd_moe", tiny(experts_held=1))
    moe = eighth._mods["moe"]
    assert moe.chunk_share == 1 / 8
    assert moe.chunk_rows(131072) == 16384
    attn = 5 * 4 * 32 + 6 * 2 * 32 - 2 * 64
    # (the last term: what the chip counts beside these, set from cell 8)
    assert eighth.layer_extra_elems_per_token == attn + 0.125 * 2 * (
        6 * 64 + 5 * 32) + 12.91 * 64
    assert (eighth.head_dim, eighth.kv_dim) == (32, 64)
    assert eighth.head_rows_share == 0.5 and eighth.draws_noise
    kw = dict(param_count=1e6, layer_param_count=5e5, b=2, t=8192, d=64,
              kd=64, f=32, heads=4, head_dim=32, layers=2, vocab=1024)
    whole = step_bytes("true", **kw)["head"]
    assert step_bytes("true", head_rows_share=0.5, **kw)["head"] == whole / 2


# ---- every other family lowers to what it lowered to ----

LOWERED_BEFORE = {"llama": ("tiny", "14bb75356a403459"),
                  "gpt2": ("tiny", "557e9d12313622a3"),
                  "mla_moe": ("tiny-mla-moe", "83b0575bcf151845"),
                  "gdn_moe": ("tiny-gdn-moe", "6d83ef8f30d65710"),
                  "conv_moe": ("tiny-conv-moe", "64b65f649e0393d9")}


@pytest.mark.parametrize("family", sorted(LOWERED_BEFORE))
def test_the_mask_declaration_left_the_other_families_text_alone(family):
    """The five families' train steps at their tiny presets lower to the
    StableHLO they lowered to at the commit before the mask was a
    declaration and the loss could be weighted (PR 40's tree; locations
    stripped; sha256, first 16 digits). Their optimised HLO was compared
    once, parent and change, and was the same, and so was the jaxpr of the
    causal flash kernels, bodies included, at eight shapes (every walk, a
    real length, groups; PR 41). A PR that means to change a family's
    program changes the digest with it: PR 42 changed the three expert
    families' (the dispatch's row movers and the inverse permutation)
    and so did PR 43 (its index work without a scalar gather or scatter)
    and PR 47 (the grouped products' groups end at the held rows, and the
    layer counts `rows_computed`) and PR 50 (the layer counts `rows_walked`;
    every tiny preset holds all its experts, so its one chunk is all the
    pairs as before); `llama` and `gpt2`, which run no expert
    layer, stand as PR 40 left them."""
    preset, digest = LOWERED_BEFORE[family]
    text = lowered_text(family, model_preset(preset))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


# ---- the counts at the published widths ----

def test_the_cut_at_the_published_widths_counts_645_623_296():
    cfg = ModelConfig(
        attn_dim=2048, ffn_dim=768, num_heads=32, num_kv_heads=4,
        num_layers=6, vocab_size=18992, num_experts=128, moe_top_k=8,
        bd_moe=BdMoEConfig(head_dim=128, moe_intermediate_size=768,
                           experts_held=16))
    parts = BlockDiffusionMoETransformer.param_counts(cfg)
    assert parts["layers"] == 6 * 94_638_336
    assert parts["embedding_and_head"] == 77_791_232
    assert cfg.num_params() == sum(parts.values()) == 645_623_296
    uncut = dataclasses.replace(
        cfg, num_layers=48, vocab_size=151936,
        bd_moe=dataclasses.replace(cfg.bd_moe, experts_held=None))
    assert 30.4e9 < uncut.num_params() < 30.6e9          # the published 30B
