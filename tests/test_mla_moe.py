"""The `mla_moe` family (models/mla_moe.py): latent attention, the sigmoid
router over held experts with a shared expert, the layer pattern, the
multi-token-prediction module. CPU, tiny sizes, float32.

* the program against the plain reference (models/vanilla_mla_moe.py): loss
  and EVERY gradient leaf, at tp 1 and tp 2, on a job that holds a slice of
  the experts; no top-k choice sits on a tie (the margin is asserted);
* the flash kernel at unequal q/k and v widths against the XLA path,
  forward and backward, one tile and a multi-block grid;
* the share test: the routed parts of all the shares of a layer plus the
  shared expert once add up to the uncut layer;
* a router forced onto the same experts drops nothing;
* the grouped products' groups cover the held rows and nothing more, and no
  row past them is read: the layer with NaN in every such row (what the
  chip's kernel may leave there) equals the plain run, every gradient too;
* what the family does not run is refused with a message;
* the counts: parameters at the published widths (680.4M in all).
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_from_scratch_tpu.config import (
    IGNORE_INDEX, LatentMoEConfig, MeshConfig, ModelConfig, OptimizerConfig,
    model_preset)
from distributed_pytorch_from_scratch_tpu.models import build_model
from distributed_pytorch_from_scratch_tpu.models.mla_moe import (
    LatentMoETransformer)
from distributed_pytorch_from_scratch_tpu.models.vanilla_mla_moe import (
    vanilla_loss)
from distributed_pytorch_from_scratch_tpu.ops.attention import (
    causal_attention_xla)
from distributed_pytorch_from_scratch_tpu.ops.pallas.flash_attention import (
    flash_attention)
from distributed_pytorch_from_scratch_tpu.ops.rope import (
    apply_rotary_interleaved, rope_angles)
from distributed_pytorch_from_scratch_tpu.parallel.moe import SharedRoutedFFN
from distributed_pytorch_from_scratch_tpu.runtime.mesh import make_mesh
from distributed_pytorch_from_scratch_tpu.training.metrics import (
    model_flops_per_step, moe_counters_summary)
from distributed_pytorch_from_scratch_tpu.training.optim import (
    init_adam_state)
from distributed_pytorch_from_scratch_tpu.training.train_step import (
    build_train_step)


def tiny(**latent):
    cfg = model_preset("tiny-mla-moe")
    return dataclasses.replace(
        cfg, latent_moe=dataclasses.replace(cfg.latent_moe, **latent))


def batch(cfg, b=2, t=128, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, cfg.vocab_size, (b, t + 1)).astype(np.int32)
    pos = np.tile(np.arange(t, dtype=np.int32), (b, 1))
    return ids[:, :-1], ids[:, 1:], pos


def on_mesh(cfg, tp, **kw):
    mesh = make_mesh(MeshConfig(dp=1, tp=tp), devices=jax.devices()[:tp])
    model = build_model("mla_moe", cfg, tp_size=tp, **kw)
    return mesh, model


# ---- the program against the plain reference ----

@pytest.mark.parametrize("tp,impl", [(1, "xla"), (2, "xla"),
                                     (1, "flash_interpret")])
def test_loss_and_every_gradient_leaf_equal_the_reference(tp, impl):
    """A job that holds experts 2..5 of 8: what the absent ones would add
    is left out by program and reference alike."""
    cfg = tiny(experts_held=4, expert_offset=2)
    mesh, model = on_mesh(cfg, tp, attn_impl=impl)
    params = model.init(jax.random.key(3))
    ids, tgt, pos = batch(cfg)
    tgt = tgt.copy()
    tgt[0, 5] = IGNORE_INDEX            # an ignored target in the middle
    with jax.default_matmul_precision("highest"):
        want, want_g = jax.jit(jax.value_and_grad(
            lambda p: vanilla_loss(cfg, p, ids, tgt, pos)))(params)
        got, got_g = jax.jit(jax.value_and_grad(model.make_loss(mesh)))(
            jax.device_put(params, model.shardings(mesh)), ids, tgt, pos)
    assert abs(float(got) - float(want)) <= 1e-5 * abs(float(want))
    flat = jax.tree_util.tree_leaves_with_path(want_g)
    assert len(flat) == len(jax.tree.leaves(got_g)) > 40
    for (path, a), b in zip(flat, jax.tree.leaves(got_g)):
        a, b = np.asarray(a), np.asarray(b)
        assert np.max(np.abs(a - b)) <= 1e-5 * max(np.max(np.abs(a)), 1e-6), \
            jax.tree_util.keystr(path)
    # the selection bias is read by top-k alone: no gradient reaches it
    assert not np.any(np.asarray(got_g["layers"]["moe"]["bias"]))


def test_no_top_k_choice_sits_on_a_tie():
    """The comparison above means something only if float32 rounding cannot
    flip a choice: the k-th and (k+1)-th scores are apart at every token of
    the first expert layer's input (seeded weights, the test's batch)."""
    cfg = tiny()
    moe = SharedRoutedFFN(cfg.attn_dim, 32, cfg.num_experts, cfg.moe_top_k)
    p = moe.init(jax.random.key(3))
    x = jax.random.normal(jax.random.key(4), (256, cfg.attn_dim))
    with jax.default_matmul_precision("highest"):
        s = np.sort(np.asarray(jax.nn.sigmoid(x @ p["router"])), axis=-1)
    margin = s[:, -cfg.moe_top_k] - s[:, -cfg.moe_top_k - 1]
    assert margin.min() > 1e-5


def test_interleaved_rope_turns_pairs():
    """Pair (x_2i, x_2i+1) times e^{i pos theta_i}, as complex numbers."""
    x = jax.random.normal(jax.random.key(0), (2, 3, 8, 6))
    pos = jnp.tile(jnp.arange(8)[None], (2, 1))
    cos, sin = rope_angles(pos, 6, 100.0)
    got = np.asarray(apply_rotary_interleaved(x, cos, sin))
    z = np.asarray(x[..., 0::2]) + 1j * np.asarray(x[..., 1::2])
    theta = 100.0 ** (-np.arange(0, 6, 2) / 6)
    w = z * np.exp(1j * np.arange(8)[None, None, :, None] * theta)
    np.testing.assert_allclose(got[..., 0::2], w.real, atol=1e-5)
    np.testing.assert_allclose(got[..., 1::2], w.imag, atol=1e-5)


# ---- the flash kernel at two widths ----

@pytest.mark.parametrize("t,block,kv_heads", [(128, None, 2), (384, 128, 2),
                                              (256, 128, 1)])
def test_flash_at_unequal_widths_equals_the_xla_path(t, block, kv_heads):
    """q/k 48 wide against v of 32: one tile (the fused backward) and a
    multi-block grid (scratch across key blocks, the split backward), with
    and without grouped kv heads."""
    key = jax.random.key(0)
    q = jax.random.normal(jax.random.fold_in(key, 1), (1, 2, t, 48))
    k = jax.random.normal(jax.random.fold_in(key, 2), (1, kv_heads, t, 48))
    v = jax.random.normal(jax.random.fold_in(key, 3), (1, kv_heads, t, 32))
    blocks = dict.fromkeys(
        ("block_q", "block_k", "bwd_block_q", "bwd_block_k"), block)
    flash = lambda q, k, v: jnp.sum(jnp.sin(flash_attention(
        q, k, v, interpret=True, **(blocks if block else {}))))
    plain = lambda q, k, v: jnp.sum(jnp.sin(causal_attention_xla(q, k, v)))
    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(flash, (0, 1, 2))(q, k, v)
        want = jax.value_and_grad(plain, (0, 1, 2))(q, k, v)
    assert flash_attention(q, k, v, interpret=True).shape == (1, 2, t, 32)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_flash_refuses_q_and_k_of_different_widths():
    x = jnp.zeros((1, 1, 128, 16))
    with pytest.raises(ValueError, match="q and k widths differ"):
        flash_attention(x, jnp.zeros((1, 1, 128, 8)), x, interpret=True)


# ---- the expert layer: shares, and no drop ----

def apply_moe(moe, params, x):
    mesh = make_mesh(MeshConfig(dp=1, tp=1), devices=jax.devices()[:1])
    from jax.sharding import PartitionSpec as P
    fn = jax.shard_map(lambda p, x: moe.apply(p, x), mesh=mesh,
                       in_specs=(moe.specs(), P()), out_specs=(P(), P()))
    return jax.jit(fn)(params, x)


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Four jobs hold two experts each of one layer's eight. Their routed
    parts, plus the shared expert once, are the layer a job holding all
    eight computes: the weights are normalised over all chosen experts,
    held or not, so the parts are parts of one sum."""
    d, f, E = 32, 16, 8
    whole = SharedRoutedFFN(d, f, E, top_k=3, scaling=2.5)
    p = whole.init(jax.random.key(1))
    x = jax.random.normal(jax.random.key(2), (2, 64, d))
    with jax.default_matmul_precision("highest"):
        want, counters = apply_moe(whole, p, x)
        xf, sh = x.reshape(-1, d), p["shared"]
        shared_only = ((jax.nn.silu(xf @ sh["gate"]) * (xf @ sh["up"]))
                       @ sh["down"]).reshape(x.shape)
        total, rows = shared_only, 0.0
        for lo in range(0, E, 2):
            share = dataclasses.replace(whole, held=2, offset=lo)
            ps = {**p, **{n: p[n][lo:lo + 2] for n in ("gate", "up", "down")}}
            y, c = apply_moe(share, ps, x)
            total = total + (y - shared_only)
            rows += float(c["rows_here"])
            np.testing.assert_array_equal(c["routed"], counters["routed"])
    np.testing.assert_allclose(total, want, atol=2e-5)
    assert rows == float(counters["rows_here"]) == 2 * 64 * 3


def test_a_router_forced_onto_the_same_experts_drops_nothing():
    """The selection bias sends EVERY token to experts 0..2, all held and
    far over any mean share: each (token, choice) pair is computed, in
    several chunks of the sorted pairs, and the layer equals the dense
    sum over those experts."""
    d, f, E, k = 32, 16, 64, 3
    moe = SharedRoutedFFN(d, f, E, top_k=k, held=4)
    p = moe.init(jax.random.key(1))
    p["bias"] = jnp.where(jnp.arange(E) < k, 10.0, 0.0)
    x = jax.random.normal(jax.random.key(2), (4, 214, d))
    # 2568 pairs, a chunk of 4/64 of them rounded up to the kernel's
    # 512-row tile: six live chunks, the last of which runs past the pairs
    assert moe.chunk_rows(4 * 214 * k) == 512
    with jax.default_matmul_precision("highest"):
        got, counters = apply_moe(moe, p, x)
        xf = x.reshape(-1, d)
        s = jax.nn.sigmoid(xf @ p["router"])[:, :k]
        w = s / jnp.sum(s, axis=-1, keepdims=True)
        ffn = lambda g, u, dn: (jax.nn.silu(xf @ g) * (xf @ u)) @ dn
        want = sum(w[:, e:e + 1] * ffn(p["gate"][e], p["up"][e], p["down"][e])
                   for e in range(k))
        sh = p["shared"]
        want = want + ffn(sh["gate"], sh["up"], sh["down"])
    assert float(counters["rows_here"]) == 4 * 214 * k     # 2568 pairs
    assert float(counters["rows_computed"]) == 4 * 214 * k
    assert float(counters["rows_walked"]) == 6 * 512       # every chunk live
    np.testing.assert_array_equal(
        counters["routed"], np.where(np.arange(E) < k, 4 * 214, 0))
    np.testing.assert_allclose(got.reshape(-1, d), want, atol=2e-5)


# ---- the dispatch's row movers: gathers both ways ----

def held(tok, n):
    """The chunk's first n rows (the held pairs'); the rest is padding."""
    return (jnp.arange(tok.shape[0]) < n)[:, None]


def plain_take(x, tok, idx, n):
    """The gather as the layer wrote it before the movers, its select
    with it (the transpose is autodiff's: a select and a row
    scatter-add)."""
    return jnp.where(held(tok, n), jnp.take(x, tok, axis=0), 0)


def plain_sum(y, r, tok, idx, n):
    """The combine as the layer wrote it before the movers: a row
    scatter-add over the chunk's tokens, of rows selected to zeros past
    the held pairs'."""
    return y.at[tok].add(jnp.where(held(tok, n), r, 0))


def a_chunk(S, k, E, H, M, c, seed=0):
    """Chunk `c` of a random routing's sorted pairs, by `apply`'s own
    formulae: `tok` (padded past the pairs), `idx`, `n = rows_here - lo`."""
    chosen = jnp.argsort(jax.random.uniform(jax.random.key(seed), (S, E)),
                         axis=-1)[:, :k]
    key = jnp.where(chosen < H, chosen, H).reshape(-1)
    order = jnp.argsort(key, stable=True)
    rows_here = int(jnp.sum(key < H))
    pos = jnp.argsort(order).reshape(S, k)
    chunks = -(-S * k // M)
    tok = jnp.pad(order // k, (0, chunks * M - S * k))[c * M:(c + 1) * M]
    lo = c * M
    idx = jnp.where((pos < rows_here) & (pos >= lo) & (pos < lo + M),
                    pos - lo, M)
    return tok, idx, rows_here - lo, rows_here


@pytest.mark.parametrize("tp", [1, 2])
@pytest.mark.parametrize("case,S,k,E,H,M,c", [
    ("one chunk of all the pairs", 64, 4, 8, 2, 256, 0),
    ("first of three, all its rows held", 64, 4, 8, 5, 96, 0),
    ("the chunk the held rows end in", 64, 4, 8, 5, 96, 1),
    ("a chunk that runs past the pairs", 60, 3, 8, 8, 64, 2),
    ("a chunk no held row reaches", 64, 4, 8, 2, 96, 2),
])
def test_the_row_movers_are_the_plain_forms_and_each_other_s_transpose(
        case, S, k, E, H, M, c, tp):
    """`take_rows` / `sum_rows` against `jax.vjp` of the plain gather and
    the plain row scatter-add, values and all three cotangents, on chunks whose tokens repeat (a token with several held
    experts), with pairs not held, padding rows past `rows_here` and past
    the pairs THAT HOLD NaN going in and on the cotangent side (what a
    grouped product may leave there on the chip: selected away, never
    multiplied); under `shard_map` with the rows varying over tp, as the
    layer's are."""
    from jax.sharding import PartitionSpec as P
    from distributed_pytorch_from_scratch_tpu.ops.collectives import copy_to
    from distributed_pytorch_from_scratch_tpu.parallel.moe import (
        sum_rows, take_rows)

    d = 16
    tok, idx, n, rows_here = a_chunk(S, k, E, H, M, c)
    held_here = int(jnp.sum(idx < M))
    assert held_here == max(0, min(rows_here - c * M, M))
    if case == "a chunk no held row reaches":
        assert held_here == 0
    elif case.startswith("first of three"):
        assert held_here == M
    else:                               # padding rows past the held pairs
        assert 0 < held_here < M
    if c == 0:      # a token of several held experts is in the chunk twice
        assert int(jnp.max(jnp.sum(idx < M, axis=1))) >= 2
    keys = jax.random.split(jax.random.key(1), 5)
    x, y, gy = (jax.random.normal(kk, (S, d)) for kk in keys[:3])
    # what the grouped products leave in padding rows on the chip: anything
    r, gr = (jnp.where(held(tok, n), jax.random.normal(kk, (M, d)), jnp.nan)
             for kk in keys[3:])
    mesh = make_mesh(MeshConfig(dp=1, tp=tp), devices=jax.devices()[:tp])

    def both(take, add):
        def shard(x, y, r, gr, gy):
            # rows that differ between the tp ranks, like a partial sum
            rank = 1.0 + jax.lax.axis_index("tp")
            vary = lambda a: copy_to(a, "tp") * rank
            out, pull = jax.vjp(lambda x, y, r: (
                take(x, tok, idx, n), add(y, r, tok, idx, n)),
                vary(x), vary(y), vary(r))
            return jax.tree.map(lambda a: a[None],
                                (out, pull((vary(gr), vary(gy)))))
        return jax.jit(jax.shard_map(shard, mesh=mesh, in_specs=P(),
                                     out_specs=P("tp")))(x, y, r, gr, gy)

    got, want = both(take_rows, sum_rows), both(plain_take, plain_sum)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape and a.shape[0] == tp
        np.testing.assert_allclose(a, b, atol=1e-6)
    if held_here == 0:
        assert not np.any(np.asarray(got[1][0]))      # no cotangent to x
        np.testing.assert_array_equal(                # nothing combined
            got[0][1], y[None] * (1.0 + np.arange(tp))[:, None, None])


@pytest.mark.parametrize("tp", [1, 2])
@pytest.mark.parametrize("dtype,tol", [("float32", 1e-6), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("case,E,H,k,forced,chunks,gathers", [
    ("one chunk of all the pairs", 8, 2, 2, False, 1, True),
    ("an eighth held, six live chunks", 24, 3, 3, True, 6, False),
    ("a sixteenth held, six live chunks", 64, 4, 3, True, 6, False),
    ("chunks the routing does not reach", 64, 4, 3, False, 6, False),
])
def test_the_layer_equals_the_scatter_form_in_value_and_every_gradient(
        monkeypatch, case, E, H, k, forced, chunks, gathers, dtype, tol, tp):
    """`SharedRoutedFFN.apply` moving its rows by the movers, whatever its
    shape rule would pick (under 1.6 pairs a row of the chunk: gathers,
    which since chunks are a share or less is the one chunk of all the
    pairs; the rule's own verdict is asserted, then set aside, so the
    gathers still walk several chunks HERE), against itself
    with the plain gather and row scatter-add in their place (the form
    the layer had, kept HERE as the oracle): a scalar of the output and
    the gradient of every leaf and of the input, float32 to 1e-6 of a
    leaf's largest entry, bfloat16 (whose scatter-add sums in bf16 where
    `sum_rows` sums in float32) within the family tests' tolerance."""
    from jax.sharding import PartitionSpec as P
    from distributed_pytorch_from_scratch_tpu.parallel import moe as moe_mod

    d, f = 32, 16
    moe = SharedRoutedFFN(d, f, E, top_k=k, held=H, tp_size=tp)
    p = moe.init(jax.random.key(1))
    if forced:
        p["bias"] = jnp.where(jnp.arange(E) < k, 10.0, 0.0)
    x = jax.random.normal(jax.random.key(2), (4, 214, d))
    pairs = 4 * 214 * k
    assert -(-pairs // moe.chunk_rows(pairs)) == chunks
    assert (pairs * moe_mod.ROW_GATHER_NS
            <= moe.chunk_rows(pairs) * moe_mod.ROW_SCATTER_NS) == gathers
    monkeypatch.setattr(moe_mod, "ROW_SCATTER_NS", 10 ** 9)
    mesh = make_mesh(MeshConfig(dp=1, tp=tp), devices=jax.devices()[:tp])

    def value_and_grads():
        def loss(p, x):
            y, _ = jax.shard_map(
                lambda p, x: moe.apply(p, x, jnp.dtype(dtype)), mesh=mesh,
                in_specs=(moe.specs(), P()), out_specs=(P(), P()))(p, x)
            return jnp.sum(jnp.sin(y.astype(jnp.float32)))
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(p, x)

    got, got_g = value_and_grads()
    monkeypatch.setattr(moe_mod, "take_rows", plain_take)
    monkeypatch.setattr(moe_mod, "sum_rows", plain_sum)
    want, want_g = value_and_grads()
    assert abs(float(got) - float(want)) <= tol * max(abs(float(want)), 1.0)
    flat = jax.tree_util.tree_leaves_with_path(want_g)
    assert len(flat) == len(jax.tree.leaves(got_g)) == 9
    for (path, a), b in zip(flat, jax.tree.leaves(got_g)):
        a, b = np.asarray(a), np.asarray(b)
        assert np.max(np.abs(a - b)) <= tol * max(np.max(np.abs(a)), 1e-6), \
            jax.tree_util.keystr(path)


def garbage_past_the_groups(seen):
    """`lax.ragged_dot` as the chip runs it: a row no group holds comes
    back as NaN, from the product and from its operand's cotangent (the
    CPU lowering zero-fills both). `seen` gets (sum of the sizes, rows) of
    every product the forward pass runs."""
    real = jax.lax.ragged_dot

    def poison(rows, sizes):
        inside = jnp.arange(rows.shape[0]) < jnp.sum(sizes)
        return jnp.where(inside[:, None], rows, jnp.nan)

    @jax.custom_vjp
    def ragged_dot(lhs, rhs, sizes):
        jax.debug.callback(
            lambda n, m=lhs.shape[0]: seen.append((int(n), m)),
            jnp.sum(sizes))
        return poison(real(lhs, rhs, sizes), sizes)

    def bwd(res, g):
        lhs, rhs, sizes = res
        d_lhs, d_rhs = jax.vjp(lambda l, r: real(l, r, sizes), lhs, rhs)[1](g)
        return poison(d_lhs, sizes), d_rhs, None

    ragged_dot.defvjp(
        lambda lhs, rhs, sizes: (ragged_dot(lhs, rhs, sizes),
                                 (lhs, rhs, sizes)), bwd)
    return ragged_dot


@pytest.mark.parametrize("case,E,H,k,S,forced,chunks,gathers,rows", [
    ("gathers, a quarter held, one chunk", 8, 2, 2, 856, (), 1, True, None),
    ("scatter-add, an eighth held, every chunk live", 24, 3, 3, 856,
     (0, 1, 2), 6, False, 2568),
    ("scatter-add, a sixteenth held, every chunk live", 64, 4, 3, 856,
     (0, 1, 2), 6, False, 2568),
    ("scatter-add, chunks the routing does not reach", 64, 4, 3, 856, (), 6,
     False, None),
    ("no held row, one chunk", 8, 2, 2, 856, (4, 5), 1, True, 0),
    ("no held row, every chunk skipped", 64, 4, 3, 856, (8, 9, 10), 6, False,
     0),
    ("the held rows end on the kernel's tile", 8, 2, 2, 512, (0, 4), 1, True,
     512),
    ("the held rows end on a chunk's last row", 64, 4, 2, 1024, (0, 8), 4,
     False, 1024),
    ("every pair held", 4, 4, 2, 856, (), 1, True, 1712),
])
def test_no_row_past_the_groups_is_read_anywhere(
        monkeypatch, case, E, H, k, S, forced, chunks, gathers, rows):
    """The groups the two products are handed cover the chunk's HELD rows
    and nothing more (their sizes are read where the products are called),
    and the layer reads no other row of what the products and their
    transposes return: with NaN in every row past the groups, as the chip
    may leave there (PR 33), the output and the gradient of every leaf and
    of the input are finite and are the plain run's."""
    from distributed_pytorch_from_scratch_tpu.parallel import moe as moe_mod

    d, f = 32, 16
    moe = SharedRoutedFFN(d, f, E, top_k=k, held=H)
    p = moe.init(jax.random.key(1))
    if forced:
        p["bias"] = jnp.zeros(E).at[jnp.array(forced)].set(10.0)
    x = jax.random.normal(jax.random.key(2), (2, S // 2, d))
    M = moe.chunk_rows(S * k)
    assert -(-S * k // M) == chunks
    assert (S * k * moe_mod.ROW_GATHER_NS
            <= M * moe_mod.ROW_SCATTER_NS) == gathers

    def value_and_grads():
        def loss(p, x):
            y, c = apply_moe(moe, p, x)
            return jnp.sum(jnp.sin(y)), (y, c)
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(p, x)

    (_, (want, _)), want_g = value_and_grads()
    seen = []
    monkeypatch.setattr(jax.lax, "ragged_dot", garbage_past_the_groups(seen))
    with jax.default_matmul_precision("highest"):
        got, c = apply_moe(moe, p, x)
        jax.effects_barrier()
    forward = list(seen)
    (_, (again, _)), got_g = value_and_grads()

    held = float(c["rows_here"])
    assert held == float(c["rows_computed"])
    # the movers and the passes walked whole chunks up to the last held row
    assert float(c["rows_walked"]) == (M if chunks == 1
                                       else M * -(-int(held) // M))
    if rows is not None:
        assert held == rows
    else:
        assert 0 < held < S * k
    # two products a live chunk, none for a chunk the `cond` skips
    live = chunks if chunks == 1 else -(-int(held) // M)
    assert len(forward) == 2 * live
    assert all(n <= m == M for n, m in forward)
    assert sum(n for n, _ in forward) == 2 * held
    np.testing.assert_array_equal(got, again)
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, want, atol=1e-6)
    flat = jax.tree_util.tree_leaves_with_path(want_g)
    assert len(flat) == len(jax.tree.leaves(got_g)) == 9
    for (path, a), b in zip(flat, jax.tree.leaves(got_g)):
        a, b = np.asarray(a), np.asarray(b)
        assert np.all(np.isfinite(b)), jax.tree_util.keystr(path)
        assert np.max(np.abs(a - b)) <= 1e-6 * max(np.max(np.abs(a)), 1e-6), \
            jax.tree_util.keystr(path)


@pytest.mark.parametrize("cell,E,H,k,chunk,chunks,gathers", [
    ("joyai-llm-flash.train-ep16share-b4-t4096", 256, 16, 8, 8192, 16,
     False),
    ("qwen3-next-80b-a3b.train-ep16share-b2-t8192", 512, 32, 10, 10240, 16,
     False),
    ("lfm2-8b-a1b.train-ep4share-b2-t8192", 32, 8, 4, 65536, 1, True),
    ("sdar-30b-a3b.train-ep8share-b2-t4096", 128, 16, 8, 16384, 8, False),
    ("trinity-mini.train-epshare-b2-t8192", 128, 16, 8, 16384, 8, False),
])
def test_the_gradient_s_text_scatters_rows_only_where_the_rule_says(
        cell, E, H, k, chunk, chunks, gathers):
    """The chunk rule at each expert cell's routing (16,384 tokens of 2048
    in bf16, the cell's experts, held share and k): a chunk is ONE mean
    share of the pairs where under a sixth of the experts are held, walked
    by a loop up to the last held row, and ALL the pairs with no `cond`
    anywhere where a sixth or more are (cell 7). The lowered gradient holds
    NO scatter whose
    updates are rows of d where the shape rule picks the gathers (the one
    chunk of all pairs), and holds the transposed gather's where it keeps
    the scatter-add (every chunk of a share or less); a scalar scatter is
    nowhere."""
    import re
    from jax.sharding import PartitionSpec as P
    from distributed_pytorch_from_scratch_tpu.parallel import moe as moe_mod

    d = 2048
    moe = SharedRoutedFFN(d, 128, E, top_k=k, held=H, n_shared=0)
    pairs = 16384 * k
    assert moe.chunk_rows(pairs) == chunk and chunk % 512 == 0
    assert -(-pairs // chunk) == chunks
    assert chunk == (pairs if 6 * H >= E else
                     moe_mod.CHUNK_SHARES * pairs * H // E)
    assert (pairs * moe_mod.ROW_GATHER_NS
            <= chunk * moe_mod.ROW_SCATTER_NS) == gathers
    mesh = make_mesh(MeshConfig(dp=1, tp=1), devices=jax.devices()[:1])
    params = jax.eval_shape(moe.init, jax.random.key(0))
    x = jax.ShapeDtypeStruct((2, 8192, d), jnp.bfloat16)

    def loss(p, x):
        y, _ = jax.shard_map(
            lambda p, x: moe.apply(p, x, jnp.bfloat16), mesh=mesh,
            in_specs=(moe.specs(), P()), out_specs=(P(), P()))(p, x)
        return jnp.sum(y.astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(params, x).as_text()
    updates = [sig.split(", ")[-1] for sig in re.findall(
        r"stablehlo\.scatter.*?\}\) : \((.*?)\) ->", text, re.S)]
    assert updates == ([] if gathers else [f"tensor<{chunk}x{d}xbf16>"])
    # no `cond` in either: the one chunk of all pairs runs bare, the chunks
    # of a share are a loop that STOPS at the last held row
    assert "stablehlo.case" not in text and "stablehlo.if" not in text
    assert chunks == 1 or "stablehlo.while" in text


# ---- chunks of a share or less: the walk stops where the held rows do ----

def one_expert_at_a_time(moe, params, x):
    """The layer's routed part as a sum over the held experts, each a
    dense FFN over EVERY token times the token's weight for it (zero where
    it was not chosen): no sort, no group, no chunk (`scripts/
    tpu_checks.py` holds the layer to the same form on the chip)."""
    xf = x.reshape(-1, x.shape[-1])
    chosen, w = moe.route(params, xf)
    y = 0.0
    for e in range(moe.num_held):
        w_e = jnp.sum(jnp.where(chosen == moe.offset + e, w, 0), axis=-1)
        h = jax.nn.silu(xf @ params["gate"][e]) * (xf @ params["up"][e])
        y = y + w_e[:, None] * (h @ params["down"][e])
    return y.reshape(x.shape)


@pytest.mark.parametrize("case,forced,S,rows,live", [
    ("every token on two held experts: every chunk live", (0, 1), 1024, 2048,
     4),
    ("no token on a held expert: no chunk live", (8, 9), 1024, 0, 0),
    ("the held rows end exactly on a chunk's edge", (0, 8), 1024, 1024, 2),
    ("one row past a chunk's edge", (0, 8), 1025, 1025, 3),
    ("an untrained router: the chunks past the held rows skipped", (), 1024,
     None, None),
])
def test_fine_chunks_equal_one_expert_at_a_time_in_value_and_every_gradient(
        case, forced, S, rows, live):
    """A sixteenth of the experts held, so chunks of 512 rows (the grain's
    floor at this size) walked up to the last held row: the output and the
    gradient
    of every leaf and of the input are the dense sum's over the held
    experts whichever chunks are live, every pair that exists is computed
    (`rows_computed == rows_here`), and the movers and passes walked whole
    chunks up to the last held row and no further (`rows_walked == M *
    ceil(rows_here / M)`)."""
    d, f, E, H, k = 32, 16, 64, 4, 2
    moe = SharedRoutedFFN(d, f, E, top_k=k, held=H, n_shared=0)
    p = moe.init(jax.random.key(1))
    if forced:
        p["bias"] = jnp.zeros(E).at[jnp.array(forced)].set(10.0)
    x = jax.random.normal(jax.random.key(2), (1, S, d))
    M = moe.chunk_rows(S * k)
    assert M == 512 and -(-S * k // M) >= 4

    def value_and_grads(layer):
        def loss(p, x):
            y, c = layer(p, x)
            return jnp.sum(jnp.sin(y)), (y, c)
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(p, x)

    (_, (got, c)), got_g = value_and_grads(lambda p, x: apply_moe(moe, p, x))
    (_, (want, _)), want_g = value_and_grads(
        lambda p, x: (one_expert_at_a_time(moe, p, x), None))
    held = int(c["rows_here"])
    assert held == int(c["rows_computed"])
    if rows is None:
        live = -(-held // M)
        assert 0 < live < -(-S * k // M)
    else:
        assert held == rows
    assert int(c["rows_walked"]) == M * live == M * -(-held // M)
    np.testing.assert_allclose(got, want, atol=2e-5)
    flat = jax.tree_util.tree_leaves_with_path(want_g)
    assert len(flat) == len(jax.tree.leaves(got_g)) == 6
    for (path, a), b in zip(flat, jax.tree.leaves(got_g)):
        a, b = np.asarray(a), np.asarray(b)
        assert np.max(np.abs(a - b)) <= 2e-5 * max(np.max(np.abs(a)), 1.0), \
            jax.tree_util.keystr(path)


@pytest.mark.parametrize("dp,tp", [(2, 1), (1, 2), (2, 2)])
def test_the_walk_stops_at_each_data_shards_own_last_held_row(dp, tp):
    """Under a mesh the loop's length is a data shard's own (`rows_here`
    differs between them, so no collective may run inside it: the float
    operands are cast to one set of mesh axes before the walk and the
    sums over them happen once, outside): batch rows over dp, the experts'
    width over tp, against one expert at a time on one device, in value
    and every gradient; the shards' counters add up."""
    from jax.sharding import PartitionSpec as P
    d, f, E, H, k, S = 32, 16, 64, 4, 2, 1024
    moe = SharedRoutedFFN(d, f, E, top_k=k, held=H, n_shared=0, tp_size=tp)
    whole = SharedRoutedFFN(d, f, E, top_k=k, held=H, n_shared=0)
    p = whole.init(jax.random.key(1))
    # the first sequence's tokens favour a held expert, the second's none
    x = jax.random.normal(jax.random.key(2), (2, S, d))
    p["router"] = p["router"].at[:, 0].set(0.0)
    x = x.at[0, :, 0].set(3.0).at[1, :, 0].set(-3.0)
    p["router"] = p["router"].at[0, 0].set(4.0)
    mesh = make_mesh(MeshConfig(dp=dp, tp=tp), devices=jax.devices()[:dp * tp])

    def layer(p, x):
        def shard(p, x):
            y, c = moe.apply(p, x)
            return y, jax.tree.map(lambda a: jax.lax.psum(a, "dp"), c)
        return jax.shard_map(shard, mesh=mesh, in_specs=(moe.specs(), P("dp")),
                             out_specs=(P("dp"), P()))(p, x)

    def value_and_grads(fn):
        def loss(p, x):
            y, c = fn(p, x)
            return jnp.sum(jnp.sin(y)), (y, c)
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(loss, argnums=(0, 1),
                                              has_aux=True))(p, x)

    (_, (got, c)), got_g = value_and_grads(layer)
    (_, (want, _)), want_g = value_and_grads(
        lambda p, x: (one_expert_at_a_time(whole, p, x), None))
    per_shard = [int(jnp.sum(whole.route(p, xs.reshape(-1, d))[0] < H))
                 for xs in x.reshape(dp, -1, S, d)]
    assert len(set(per_shard)) == dp            # the shards' loops differ
    M = moe.chunk_rows(2 * S * k // dp)
    assert int(c["rows_here"]) == int(c["rows_computed"]) == sum(per_shard)
    assert int(c["rows_walked"]) == sum(M * -(-n // M) for n in per_shard)
    np.testing.assert_allclose(got, want, atol=2e-5)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(want_g),
                            jax.tree.leaves(got_g)):
        a, b = np.asarray(a), np.asarray(b)
        assert np.max(np.abs(a - b)) <= 2e-5 * max(np.max(np.abs(a)), 1.0), \
            jax.tree_util.keystr(path)


@pytest.mark.parametrize("E,H,k,pairs,chunk", [
    (256, 16, 8, 131072, 8192), (512, 32, 10, 163840, 10240),
    (128, 16, 8, 131072, 16384), (64, 4, 3, 2568, 512), (24, 3, 3, 2568, 512),
    (7, 1, 2, 8192, 1536), (6, 1, 2, 8192, 8192), (32, 8, 4, 65536, 65536),
    (8, 8, 2, 4096, 4096), (4, 1, 2, 256, 256),
])
def test_a_chunk_is_a_mean_share_or_all_the_pairs(E, H, k, pairs, chunk):
    """The grain of the dispatch: under a sixth of the experts held, ONE
    of the job's mean shares of the pairs up to the grouped kernel's 512-row
    tile (the expert cells 5, 6, 8 and 9, and the tests' tiny shapes at the
    tile's floor), walked by a loop that stops at the last held row; a
    sixth or more, ALL the pairs in one chunk (cell 7, every all-held
    shape). Neither text has a `cond`."""
    moe = SharedRoutedFFN(32, 16, E, top_k=k, held=H, n_shared=0)
    assert moe.chunk_rows(pairs) == chunk
    assert (moe.chunk_share == 1.0) == (6 * H >= E) == (chunk == pairs)
    x = jax.ShapeDtypeStruct((1, pairs // k, 32), jnp.float32)
    text = str(jax.make_jaxpr(lambda p, x: apply_moe(moe, p, x))(
        jax.eval_shape(moe.init, jax.random.key(0)), x))
    assert " cond[" not in text
    assert (" while[" in text) == (chunk < pairs)


# ---- the dispatch's index work: no scalar gather, no scalar scatter ----

def plain_route(moe, params, xf):
    """`SharedRoutedFFN.route` with the chosen scores taken by
    `take_along_axis`: the plain form, the oracle."""
    logits = jnp.dot(xf.astype(jnp.float32), params["router"],
                     precision=jax.lax.Precision.HIGHEST)
    if moe.score == "softmax":
        s = jax.nn.softmax(logits, axis=-1)
        _, chosen = jax.lax.top_k(s, moe.top_k)
    else:
        s = jax.nn.sigmoid(logits)
        _, chosen = jax.lax.top_k(
            s + jax.lax.stop_gradient(params["bias"]), moe.top_k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * moe.scaling
    return chosen, w


def plain_index(moe, chosen, w):
    """`SharedRoutedFFN.index` by `argsort`, `bincount`, `w[order]` and a
    second sort."""
    H = moe.num_held
    local = chosen - moe.offset
    key = jnp.where((local >= 0) & (local < H), local, H).reshape(-1)
    order = jnp.argsort(key, stable=True)
    ends = jnp.cumsum(jnp.bincount(key, length=H + 1)[:H])
    pos = jnp.argsort(order).reshape(chosen.shape)
    routed = jnp.bincount(chosen.reshape(-1), length=moe.num_experts)
    return order, w.reshape(-1)[order], ends, pos, routed


INDEX_CASES = [
    # case, score, experts, held, offset, k, experts the bias forces
    ("cell 5's router: sigmoid + bias top-8 of 256, held 16",
     "sigmoid", 256, 16, 32, 8, None),
    ("cell 6's router: softmax top-10 of 512, held 32",
     "softmax", 512, 32, 480, 10, None),
    ("cell 7's router: sigmoid top-4 of 32, held 8",
     "sigmoid", 32, 8, 8, 4, None),
    ("cell 8's router: softmax top-8 of 128, held 16",
     "softmax", 128, 16, 0, 8, None),
    ("every token on one held expert", "sigmoid", 8, 2, 4, 2, (1, 5)),
    ("no pair held", "sigmoid", 8, 2, 4, 2, (0, 7)),
]


@pytest.mark.parametrize("case,score,E,H,offset,k,forced", INDEX_CASES,
                         ids=[c[0] for c in INDEX_CASES])
def test_the_index_work_equals_the_plain_gathers_and_counts(
        case, score, E, H, offset, k, forced):
    """`route` and `index` against `take_along_axis`, `bincount`,
    `argsort` and `w[order]`: every selection and every integer EXACTLY
    (`chosen`, `w`, `order`, `w_sorted`, `ends`, `pos`, `routed`), and the
    cotangents of the router's weights and of the tokens through `w` and
    through `w_sorted`, and of the scores through the pick alone, to 1e-6
    of autodiff's of the plain forms, in float32."""
    from distributed_pytorch_from_scratch_tpu.parallel.moe import pick_scores

    S, d = 96, 16
    moe = SharedRoutedFFN(d, 8, E, top_k=k, held=H, offset=offset,
                          score=score, n_shared=0, scaling=2.5)
    p = moe.init(jax.random.key(3))
    if forced is not None:
        p["bias"] = jnp.zeros((E,)).at[jnp.array(forced)].set(10.0)
    xf = jax.random.normal(jax.random.key(4), (S, d))

    # op by op: a fused sigmoid rounds otherwise beside one form than
    # beside the other, by an ulp, and the pick is to be read alone
    chosen, w = moe.route(p, xf)
    want_chosen, want_w = plain_route(moe, p, xf)
    np.testing.assert_array_equal(chosen, want_chosen)
    np.testing.assert_array_equal(w, want_w)
    got = jax.jit(lambda c, w: moe.index(c, w, inverse=True))(chosen, w)
    want = jax.jit(lambda c, w: plain_index(moe, c, w))(chosen, w)
    for name, a, b in zip(("order", "w_sorted", "ends", "pos", "routed"),
                          got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        np.testing.assert_array_equal(a, b, err_msg=name)
    assert moe.index(chosen, w, inverse=False)[3] is None
    held = int(got[2][-1])
    if forced is not None:
        assert sorted(np.unique(chosen).tolist()) == sorted(forced)
        assert held == (S if case.startswith("every token") else 0)
    else:
        assert 0 < held < S * k and int(np.max(got[4])) < S

    # cotangents through w (unsorted) and through w_sorted
    cw, cs = (jax.random.normal(kk, (S * k,))
              for kk in jax.random.split(jax.random.key(5)))

    def loss(route, index):
        def f(router, xf):
            chosen, w = route({**p, "router": router}, xf)
            w_sorted = index(chosen, w)[1]
            return jnp.sum(w.reshape(-1) * cw) + jnp.sum(jnp.sin(w_sorted) * cs)
        return jax.jit(jax.grad(f, argnums=(0, 1)))(p["router"], xf)

    got_g = loss(moe.route, lambda c, w: moe.index(c, w, inverse=True))
    want_g = loss(lambda p, x: plain_route(moe, p, x),
                  lambda c, w: plain_index(moe, c, w))
    for a, b in zip(got_g, want_g):
        assert np.max(np.abs(a - b)) <= 1e-6 * max(np.max(np.abs(b)), 1e-6)
    assert np.max(np.abs(want_g[0])) > 0
    # the scores' cotangent through the pick alone
    s = jax.random.uniform(jax.random.key(6), (S, E))
    g = jax.random.normal(jax.random.key(7), (S, k))
    ds = jax.grad(lambda s: jnp.sum(pick_scores(s, chosen) * g))(s)
    want_ds = jax.grad(lambda s: jnp.sum(
        jnp.take_along_axis(s, chosen, axis=-1) * g))(s)
    np.testing.assert_array_equal(ds, want_ds)


@pytest.mark.parametrize("regime,E,H,k", [("gathers", 8, 2, 2),
                                          ("the row scatter-add", 64, 4, 3)])
def test_no_scalar_gather_or_scatter_is_left_in_the_layer(regime, E, H, k):
    """The jaxpr of the value-and-gradient of `apply` under
    `jax.checkpoint` (forward, recompute and backward), in both mover
    regimes: no `scatter` / `scatter-add` whose update is a scalar, no
    `gather` of one-element slices. What is left are the movers' row
    gathers (`x[tok]` and its like, `sum_rows`' columns) and, in the
    scatter regime, the forward's row scatter-add and the transposed
    gather's."""
    from jax.sharding import PartitionSpec as P
    from distributed_pytorch_from_scratch_tpu.parallel import moe as moe_mod

    d = 32
    moe = SharedRoutedFFN(d, 16, E, top_k=k, held=H)
    pairs = 4 * 214 * k
    assert (pairs * moe_mod.ROW_GATHER_NS <= moe.chunk_rows(pairs)
            * moe_mod.ROW_SCATTER_NS) == (regime == "gathers")
    p = moe.init(jax.random.key(1))
    x = jax.random.normal(jax.random.key(2), (4, 214, d))

    mesh = make_mesh(MeshConfig(dp=1, tp=1), devices=jax.devices()[:1])

    def layer(p, x):
        y, _ = jax.shard_map(
            jax.checkpoint(moe.apply), mesh=mesh,
            in_specs=(moe.specs(), P()), out_specs=(P(), P()))(p, x)
        return jnp.sum(jnp.sin(y))

    found = {"gather": [], "scatter": [], "scatter-add": []}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in found:
                found[eqn.primitive.name].append(eqn)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(jax.value_and_grad(layer, argnums=(0, 1)))(p, x).jaxpr)
    for eqn in found["gather"]:
        assert eqn.params["slice_sizes"] == (1, d), eqn
    for eqn in found["scatter"] + found["scatter-add"]:
        assert eqn.invars[2].aval.shape[-1] == d, eqn
    rows_scattered = len(found["scatter"]) + len(found["scatter-add"])
    assert rows_scattered == (0 if regime == "gathers" else 2)
    assert len(found["gather"]) >= 3


# ---- the step, its counters, the entry point ----

def test_the_train_step_returns_counters_when_asked_and_the_loss_falls():
    cfg = tiny()
    mesh, model = on_mesh(cfg, 2)
    params = jax.device_put(model.init(jax.random.key(0)),
                            model.shardings(mesh))
    opt = init_adam_state(params)
    ocfg = OptimizerConfig(lr=3e-3, warmup_steps=2, max_steps=20)
    step = build_train_step(model, mesh, ocfg, with_grad_norm=True,
                            with_counters=True)
    ids, tgt, pos = batch(cfg, t=64)
    losses = []
    for _ in range(6):
        params, opt, (loss, gnorm, c) = step(params, opt, ids, tgt, pos)
        losses.append(float(loss))
    assert losses[-1] < losses[0] and np.isfinite(float(gnorm))
    assert c["routed"].shape == (3, 8) and c["rows_here"].shape == (3,)
    # every token takes top_k experts in each of the 2 + 1 expert layers
    np.testing.assert_array_equal(c["routed"].sum(-1), [2 * 64 * 2] * 3)
    assert abs(float(c["loss_main"] + 0.3 * c["loss_mtp"]) - losses[-1]) \
        < 1e-5
    summary = moe_counters_summary(jax.device_get(c), cfg, 2 * 64)
    assert summary["rows_here_per_token"] == 2.0    # all experts held
    assert summary["rows_computed_per_token"] == 2.0
    assert summary["rows_walked_per_token"] == 2.0  # one chunk of all pairs
    assert summary["load_max_over_mean"] >= 1.0
    # off by default: the step's output is what it has always been
    plain = build_train_step(model, mesh, ocfg, with_grad_norm=True)
    assert len(plain(params, opt, ids, tgt, pos)[2]) == 2


def test_train_cli_runs_the_family(tmp_path, capsys):
    from chip_smoke import write_tokens
    from distributed_pytorch_from_scratch_tpu import train as train_mod
    tokens = tmp_path / "tokens.json"
    write_tokens(str(tokens), 503, 16, 65)
    train_mod.main([
        "--family", "mla_moe", "--model", "tiny-mla-moe", "--tp_size", "2",
        "--data_path", str(tokens), "--save_dir", str(tmp_path / "ckpt"),
        "--batch_size", "4", "--maxlen", "64", "--max_steps", "4",
        "--log_interval", "2", "--save_interval", "100",
        "--warmup_steps", "2"])
    out = capsys.readouterr().out
    assert "model[mla_moe]" in out and "rows_here_per_token" in out
    assert "rows_computed_per_token" in out
    assert "rows_walked_per_token" in out
    events = [json.loads(line) for line in
              open(tmp_path / "ckpt" / "logs" / "metrics.jsonl")]
    assert any(e.get("tag") == "moe_counters" for e in events)
    with pytest.raises(SystemExit, match="reads the config field"):
        train_mod.main(["--family", "llama", "--model", "tiny-mla-moe",
                        "--data_path", str(tokens),
                        "--save_dir", str(tmp_path / "x")])


# ---- what is refused ----

@pytest.mark.parametrize("kw,message", [
    (dict(pp_size=2), "pp_size > 1"),
    (dict(cp_size=2), "cp_size > 1"),
    (dict(ep_size=2), "ep_size > 1"),
    (dict(sequence_parallel=True), "sequence_parallel=True"),
    (dict(tp_size=2, tp_overlap="ring"), "does not compose with MoE"),
    (dict(attn_t_real=32), "attn_t_real"),
    (dict(zero3_axis="dp"), "ZeRO stage 3"),
])
def test_the_model_refuses_what_it_does_not_run(kw, message):
    with pytest.raises(ValueError, match=message):
        build_model("mla_moe", tiny(), **kw)


def test_a_family_needs_its_own_facts():
    with pytest.raises(ValueError, match="needs cfg.latent_moe"):
        build_model("mla_moe", ModelConfig(num_experts=8))


# ---- the counts at the published widths ----

def published(held=16, vocab=16160, layers=5):
    return ModelConfig(
        attn_dim=2048, ffn_dim=7168, num_heads=32, num_layers=layers,
        vocab_size=vocab, maxlen=4096, rope_theta=3.2e7, num_experts=256,
        moe_top_k=8, latent_moe=LatentMoEConfig(
            q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
            qk_rope_head_dim=64, v_head_dim=128, moe_intermediate_size=768,
            routed_scaling_factor=2.5, experts_held=held,
            num_nextn_predict_layers=1))


def test_parameter_counts_at_the_published_widths():
    """One chip's share (16 of 256 experts, an eighth of the vocabulary, 1
    + 4 layers, the module): 680.4M, as `init` makes them."""
    cfg = published()
    parts = LatentMoETransformer.param_counts(cfg)
    assert round(parts["dense_layers"] / 1e6, 1) == 70.4
    assert round(parts["expert_layers"] / 4e6, 1) == 107.1
    assert round(parts["mtp"] / 1e6, 1) == 115.5
    assert round(parts["embedding_and_head"] / 1e6, 1) == 66.2
    assert cfg.num_params() == 680_441_088          # 680.4M
    model = build_model("mla_moe", cfg)
    made = jax.eval_shape(model.init, jax.random.key(0))
    assert sum(x.size for x in jax.tree.leaves(made)) == cfg.num_params()
    # uncut, an expert layer is 1,239.6M
    uncut = LatentMoETransformer.param_counts(published(held=None))
    assert round(uncut["expert_layers"] / 4e6, 1) == 1239.6
    # the step's FLOPs count the held experts at a token's mean share of
    # them (8 x 16/256 = 0.5 an expert layer), not all sixteen
    flops = model_flops_per_step(cfg, 4, 4096, cfg.num_params())
    assert 3.2e9 < flops / (4 * 4096) < 3.7e9
