"""Pallas flash-attention kernel vs the naive XLA oracle.

The reference has no fused attention at all (naive O(T^2) masked softmax,
`/root/reference/models/model.py:73-77`); the oracle here is our XLA
mirror of that math, so equivalence to it is equivalence to the reference.
Every kernel call here asks for the Pallas interpreter by name (the same
kernel code is compiled by Mosaic on TPU; scripts/tpu_checks.py checks that
there). Without the opt-in, a non-TPU backend is an error — pinned below.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from distributed_pytorch_from_scratch_tpu import (MeshConfig, ModelConfig,
                                                  Transformer, make_mesh)
from distributed_pytorch_from_scratch_tpu.ops.attention import (
    causal_attention_xla)
from distributed_pytorch_from_scratch_tpu.ops.pallas import (
    flash_attention as fa_mod)

flash_attention = functools.partial(fa_mod.flash_attention, interpret=True)


def test_kernels_refuse_non_tpu_backend_without_interpreter_opt_in():
    """No silent interpreter, no silent XLA: asked for by name off-TPU, the
    Mosaic kernels raise — at the kernel and at the dispatcher."""
    from distributed_pytorch_from_scratch_tpu.ops.attention import (
        causal_attention, resolve_attention_impl)
    q = jnp.zeros((1, 2, 128, 16))
    pos = jnp.zeros((1, 128), jnp.int32)
    with pytest.raises(ValueError, match="needs a TPU backend"):
        fa_mod.flash_attention(q, q, q)
    with pytest.raises(ValueError, match="needs a TPU backend"):
        fa_mod.block_attention(q, q, q, pos, pos)
    with pytest.raises(ValueError, match="needs a TPU backend"):
        causal_attention(q, q, q, impl="flash")
    assert resolve_attention_impl("auto") == "xla"   # and says so
    assert resolve_attention_impl("flash_interpret") == "flash_interpret"
    with pytest.raises(ValueError, match="unknown attention impl"):
        resolve_attention_impl("cuda")


@pytest.mark.parametrize("shape", [(2, 4, 128, 64), (1, 2, 300, 64),
                                   (2, 2, 513, 32), (1, 8, 1000, 64)])
def test_forward_matches_oracle_f32(shape):
    b, h, t, d = shape
    kq, kk, kv = jax.random.split(jax.random.key(t), 3)
    q = jax.random.normal(kq, shape, jnp.float32)
    k = jax.random.normal(kk, shape, jnp.float32)
    v = jax.random.normal(kv, shape, jnp.float32)
    ref = causal_attention_xla(q, k, v)
    out = flash_attention(q, k, v)
    assert jnp.abs(ref - out).max() < 1e-5


def test_forward_matches_oracle_bf16():
    shape = (2, 4, 256, 64)
    kq, kk, kv = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(kq, shape, jnp.bfloat16)
    k = jax.random.normal(kk, shape, jnp.bfloat16)
    v = jax.random.normal(kv, shape, jnp.bfloat16)
    ref = causal_attention_xla(q, k, v).astype(jnp.float32)
    out = flash_attention(q, k, v).astype(jnp.float32)
    # bf16 storage + f32-vs-bf16 score accumulation: ~1e-2 quantisation
    assert jnp.abs(ref - out).max() < 3e-2


def test_gradients_match_oracle():
    shape = (2, 2, 320, 64)
    kq, kk, kv, kg = jax.random.split(jax.random.key(1), 4)
    q = jax.random.normal(kq, shape, jnp.float32)
    k = jax.random.normal(kk, shape, jnp.float32)
    v = jax.random.normal(kv, shape, jnp.float32)
    g = jax.random.normal(kg, shape, jnp.float32)

    gr = jax.grad(lambda *a: jnp.vdot(causal_attention_xla(*a), g), (0, 1, 2))(q, k, v)
    gf = jax.grad(lambda *a: jnp.vdot(flash_attention(*a), g), (0, 1, 2))(q, k, v)
    for a, b in zip(gr, gf):
        assert jnp.abs(a - b).max() < 1e-4


def test_gradients_match_oracle_multiblock():
    """Small explicit block sizes force the split dq/dkv backward kernels —
    the fused single-block backward handles every default-sized case, so
    without this the multi-block path would lose coverage."""
    shape = (1, 2, 320, 64)
    kq, kk, kv, kg = jax.random.split(jax.random.key(5), 4)
    q = jax.random.normal(kq, shape, jnp.float32)
    k = jax.random.normal(kk, shape, jnp.float32)
    v = jax.random.normal(kv, shape, jnp.float32)
    g = jax.random.normal(kg, shape, jnp.float32)

    def fl(*a):
        return flash_attention(*a, block_q=128, block_k=128,
                               bwd_block_q=128, bwd_block_k=128)

    gr = jax.grad(lambda *a: jnp.vdot(causal_attention_xla(*a), g), (0, 1, 2))(q, k, v)
    gf = jax.grad(lambda *a: jnp.vdot(fl(*a), g), (0, 1, 2))(q, k, v)
    for a, b in zip(gr, gf):
        assert jnp.abs(a - b).max() < 1e-4


def test_flash_under_shard_map():
    """The kernel runs per-shard inside shard_map (local heads), like in
    the TP transformer."""
    mesh = make_mesh(MeshConfig(dp=1, tp=4))
    shape = (2, 8, 256, 32)
    kq, kk, kv = jax.random.split(jax.random.key(2), 3)
    q = jax.random.normal(kq, shape, jnp.float32)
    k = jax.random.normal(kk, shape, jnp.float32)
    v = jax.random.normal(kv, shape, jnp.float32)

    fn = jax.jit(jax.shard_map(
        lambda q, k, v: flash_attention(q, k, v),
        mesh=mesh, in_specs=(P(None, "tp"),) * 3, out_specs=P(None, "tp")))
    out = fn(q, k, v)
    ref = causal_attention_xla(q, k, v)
    assert jnp.abs(ref - out).max() < 1e-5

    # backward under shard_map too (exercises the vma tags on the dq/dk/dv
    # pallas_call out_shapes, which only fail at trace time on TPU otherwise)
    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    g_fl = jax.jit(jax.grad(loss(
        jax.shard_map(flash_attention, mesh=mesh,
                      in_specs=(P(None, "tp"),) * 3,
                      out_specs=P(None, "tp"))), argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss(causal_attention_xla), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fl):
        assert jnp.abs(a - b).max() < 1e-4


def test_transformer_attn_impl_flash_matches_xla():
    """Full TP model forward with attn_impl='flash' == attn_impl='xla'."""
    cfg = ModelConfig(attn_dim=64, ffn_dim=128, num_heads=4, num_layers=2,
                      vocab_size=128, maxlen=160, compute_dtype="float32")
    mesh = make_mesh(MeshConfig(dp=2, tp=4))
    m_xla = Transformer(cfg, tp_size=4, attn_impl="xla")
    m_fla = Transformer(cfg, tp_size=4, attn_impl="flash_interpret")
    params = m_xla.init(jax.random.key(0))
    params = jax.device_put(params, m_xla.shardings(mesh))

    b, t = 4, 160
    ids = jax.random.randint(jax.random.key(3), (b, t), 0, cfg.vocab_size)
    pos = jnp.tile(jnp.arange(t, dtype=jnp.int32)[None, :], (b, 1))

    lo_x = m_xla.make_forward(mesh)(params, ids, pos)
    lo_f = m_fla.make_forward(mesh)(params, ids, pos)
    assert jnp.abs(lo_x - lo_f).max() < 1e-4


# ---- grouped-query (GQA) kernel routing: no K/V repeat in HBM ----


@pytest.mark.parametrize("t,block", [(64, 128), (200, 128)])
def test_gqa_kernel_matches_repeat_oracle(t, block):
    """hkv < hq routed inside the kernels (fused single-block at t=64,
    split dq/dkv kernels at t=200) vs the repeat+dense oracle."""
    from distributed_pytorch_from_scratch_tpu.ops.attention import (
        causal_attention_xla)

    key = jax.random.key(5)
    b, hq, hkv, d = 2, 8, 2, 16
    q = jax.random.normal(jax.random.fold_in(key, 1), (b, hq, t, d))
    k = jax.random.normal(jax.random.fold_in(key, 2), (b, hkv, t, d))
    v = jax.random.normal(jax.random.fold_in(key, 3), (b, hkv, t, d))
    ref = causal_attention_xla(q, k, v)
    out = flash_attention(q, k, v, block_q=block, block_k=block)
    np.testing.assert_allclose(out, ref, atol=2e-5)

    loss = lambda fn: lambda *a: jnp.sum(fn(*a) ** 2)
    g_ref = jax.grad(loss(causal_attention_xla), argnums=(0, 1, 2))(q, k, v)
    g_out = jax.grad(
        loss(lambda q, k, v: flash_attention(q, k, v, block_q=block,
                                             block_k=block)),
        argnums=(0, 1, 2))(q, k, v)
    for name, a, b_ in zip("qkv", g_ref, g_out):
        np.testing.assert_allclose(b_, a, atol=5e-5, err_msg=f"d{name}")
        # dk/dv stay at the kv head count — nothing materialised the repeat
    assert g_out[1].shape == k.shape and g_out[2].shape == v.shape


def test_gqa_rejects_nondivisible_heads():
    q = jnp.zeros((1, 6, 64, 16))
    kv = jnp.zeros((1, 4, 64, 16))
    with pytest.raises(ValueError, match="multiple of kv heads"):
        flash_attention(q, kv, kv)


# ---- positional block kernel (ring attention building block) ----


def test_block_attention_matches_xla_block():
    """Pallas positional kernel vs the dense XLA block math, including an
    all-dead query row (position earlier than every kv) and GQA heads."""
    block_attention = functools.partial(fa_mod.block_attention,
                                        interpret=True)
    from distributed_pytorch_from_scratch_tpu.ops.ring_attention import (
        _BIG_NEG, _block_attn_xla)

    key = jax.random.key(7)
    b, hq, hkv, tq, tk, d = 2, 4, 2, 96, 160, 16
    q = jax.random.normal(jax.random.fold_in(key, 1), (b, hq, tq, d))
    k = jax.random.normal(jax.random.fold_in(key, 2), (b, hkv, tk, d))
    v = jax.random.normal(jax.random.fold_in(key, 3), (b, hkv, tk, d))
    qp = jax.random.randint(jax.random.fold_in(key, 4), (b, tq), 100, 500)
    qp = qp.at[:, 0].set(0)  # row 0: sees nothing (all kv_pos >= 100)
    kp = jax.random.randint(jax.random.fold_in(key, 5), (b, tk), 100, 500)
    scale = 1.0 / np.sqrt(d)

    o_ref, lse_ref = _block_attn_xla(q, k, v, qp, kp, scale)
    o_k, lse_k = block_attention(q, k, v, qp, kp)
    assert bool((lse_ref[:, :, 0] <= _BIG_NEG / 2).all()), "dead row expected"
    np.testing.assert_allclose(o_k, o_ref, atol=2e-5)
    alive = lse_ref > _BIG_NEG / 2
    np.testing.assert_allclose(jnp.where(alive, lse_k, 0.0),
                               jnp.where(alive, lse_ref, 0.0), atol=2e-5)

    def loss(fn):
        def inner(q, k, v):
            o, lse = fn(q, k, v)
            keep = lse > _BIG_NEG / 2
            return jnp.sum(o.astype(jnp.float32) ** 2) + jnp.sum(
                jnp.where(keep, lse, 0.0) ** 2)
        return inner

    g_ref = jax.grad(loss(lambda q, k, v: _block_attn_xla(q, k, v, qp, kp,
                                                          scale)),
                     argnums=(0, 1, 2))(q, k, v)
    g_k = jax.grad(loss(lambda q, k, v: block_attention(q, k, v, qp, kp)),
                   argnums=(0, 1, 2))(q, k, v)
    for name, a, b_ in zip("qkv", g_ref, g_k):
        np.testing.assert_allclose(b_, a, atol=5e-5, err_msg=f"d{name}")


# ---- pad-aware t_real path (sequence bucketing) ----


def test_t_real_matches_sliced_oracle():
    """t_real < t: rows below t_real match the oracle on the SLICED inputs
    exactly; rows at/after t_real are hard zeros (the bucketing contract —
    flash_attention docstring)."""
    b, h, t, d, tr = 1, 2, 320, 32, 300
    kq, kk, kv = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(kq, (b, h, t, d))
    k = jax.random.normal(kk, (b, h, t, d))
    v = jax.random.normal(kv, (b, h, t, d))
    ref = causal_attention_xla(q[:, :, :tr], k[:, :, :tr], v[:, :, :tr])
    for blocks in ({}, dict(block_q=128, block_k=128,
                            bwd_block_q=128, bwd_block_k=128)):
        out = flash_attention(q, k, v, t_real=tr, **blocks)
        assert jnp.abs(out[:, :, :tr] - ref).max() < 1e-5
        assert jnp.abs(out[:, :, tr:]).max() == 0.0


def test_t_real_grads_exact_even_with_tail_cotangent():
    """Gradients through the t_real path equal the sliced oracle's, and a
    NONZERO cotangent on the pad rows contributes exactly zero (the pad
    outputs are constants) — the invariant that keeps bucketing exact
    under losses that touch every row (e.g. MoE aux sums)."""
    b, h, t, d, tr = 1, 2, 320, 32, 300
    keys = jax.random.split(jax.random.key(1), 4)
    q, k, v, g = (jax.random.normal(kk, (b, h, t, d)) for kk in keys)

    gr = jax.grad(
        lambda *a: jnp.vdot(causal_attention_xla(*a), g[:, :, :tr]),
        (0, 1, 2))(q[:, :, :tr], k[:, :, :tr], v[:, :, :tr])
    # g carries nonzero values on rows >= tr on purpose
    gf = jax.grad(
        lambda *a: jnp.vdot(flash_attention(*a, t_real=tr), g),
        (0, 1, 2))(q, k, v)
    for a, b_ in zip(gr, gf):
        assert jnp.abs(a - b_[:, :, :tr]).max() < 1e-4
        assert jnp.abs(b_[:, :, tr:]).max() == 0.0


def test_t_real_validation():
    q = jnp.zeros((1, 2, 128, 16))
    with pytest.raises(ValueError, match="t_real"):
        flash_attention(q, q, q, t_real=0)
    with pytest.raises(ValueError, match="t_real"):
        flash_attention(q, q, q, t_real=129)


@pytest.mark.slow
def test_t_real_parity_reference_shape():
    """The acceptance case: t=1000 real tokens in a t=1024 bucket equals
    the plain t=1000 path and the vanilla oracle, at the reference head
    shape (fwd; CPU interpreter)."""
    b, h, t_pad, d, tr = 1, 8, 1024, 64, 1000
    kq, kk, kv = jax.random.split(jax.random.key(2), 3)
    q = jax.random.normal(kq, (b, h, t_pad, d))
    k = jax.random.normal(kk, (b, h, t_pad, d))
    v = jax.random.normal(kv, (b, h, t_pad, d))
    ref = causal_attention_xla(q[:, :, :tr], k[:, :, :tr], v[:, :, :tr])
    plain = flash_attention(q[:, :, :tr], k[:, :, :tr], v[:, :, :tr])
    bucketed = flash_attention(q, k, v, t_real=tr,
                               block_q=256, block_k=256)
    assert jnp.abs(plain - ref).max() < 1e-5
    assert jnp.abs(bucketed[:, :, :tr] - ref).max() < 1e-5
    assert jnp.abs(bucketed[:, :, tr:]).max() == 0.0


# ---- block-shape autotuner table + cache ----


@pytest.fixture
def block_table():
    """Snapshot/restore the module-global tuned-block table around a test."""
    from distributed_pytorch_from_scratch_tpu.ops.pallas import (
        flash_attention as fa)

    saved, saved_loaded = dict(fa._BLOCK_TABLE), fa._cache_loaded
    fa._cache_loaded = True  # keep tests off the real user cache file
    yield fa
    fa._BLOCK_TABLE.clear()
    fa._BLOCK_TABLE.update(saved)
    fa._cache_loaded = saved_loaded


def test_block_config_defaults_and_override(block_table):
    fa = block_table
    cfg = fa.get_block_config(333, 64, jnp.float32)
    assert cfg == fa.BlockConfig()  # no entry -> the swept defaults
    fa.set_block_config(333, 64, jnp.float32, fa.BlockConfig(128, 256,
                                                             128, 128))
    # t buckets by the padded pow2: 333 and 500 share the 512 entry
    assert fa.get_block_config(500, 64, jnp.float32).block_k == 256
    assert fa.get_block_config(600, 64, jnp.float32) == fa.BlockConfig()


def test_block_cache_roundtrip(block_table, tmp_path):
    fa = block_table
    path = str(tmp_path / "blocks.json")
    fa.set_block_config(256, 32, jnp.bfloat16, fa.BlockConfig(256, 128,
                                                              128, 128))
    fa.save_block_cache(path)
    fa._BLOCK_TABLE.clear()
    assert fa.get_block_config(256, 32, jnp.bfloat16) == fa.BlockConfig()
    assert fa.load_block_cache(path) >= 1
    assert fa.get_block_config(256, 32, jnp.bfloat16).block_q == 256
    # a garbled cache is ignored, not fatal
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert fa.load_block_cache(str(bad)) == 0


def test_tuned_blocks_drive_the_kernel(block_table):
    """flash_attention with no explicit blocks must consult the table —
    and stay correct with a deliberately odd tuned entry."""
    fa = block_table
    b, h, t, d = 1, 2, 300, 32
    kq, kk, kv = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(kq, (b, h, t, d))
    k = jax.random.normal(kk, (b, h, t, d))
    v = jax.random.normal(kv, (b, h, t, d))
    fa.set_block_config(t, d, q.dtype, fa.BlockConfig(128, 256, 128, 128))
    out = flash_attention(q, k, v)  # blocks=None -> table entry
    ref = causal_attention_xla(q, k, v)
    assert jnp.abs(out - ref).max() < 1e-5


def test_autotune_caches_winner(block_table, tmp_path, monkeypatch):
    """autotune_block_config sweeps, records the winner in the table, and
    persists it through the JSON cache when asked."""
    fa = block_table
    monkeypatch.setenv("FLASH_BLOCKS_CACHE", str(tmp_path / "fb.json"))
    best = fa.autotune_block_config(128, 16, jnp.float32, batch_heads=2,
                                    sweep=(128,), iters=1, warmup=0,
                                    write_cache=True, interpret=True)
    assert best == fa.BlockConfig(128, 128, 128, 128)
    assert fa.get_block_config(128, 16, jnp.float32) == best
    fa._BLOCK_TABLE.clear()
    assert fa.load_block_cache() >= 1  # reads FLASH_BLOCKS_CACHE
    assert fa.get_block_config(128, 16, jnp.float32) == best


# ---- model-level sequence bucketing (attn_t_real) ----


@pytest.mark.parametrize("attn_impl", ["xla", "flash_interpret"])
def test_model_seq_bucket_matches_unbucketed(attn_impl):
    """A bucket-padded batch (t=200 real in a t=256 buffer, IGNORE_INDEX
    pad targets) through a model with attn_t_real must reproduce the plain
    model's loss AND grads exactly — the pad-aware bucketing acceptance
    bar at model level."""
    from distributed_pytorch_from_scratch_tpu.config import IGNORE_INDEX

    cfg = ModelConfig(attn_dim=64, ffn_dim=128, num_heads=4, num_layers=2,
                      vocab_size=128, maxlen=200, compute_dtype="float32")
    mesh = make_mesh(MeshConfig(dp=1, tp=2))
    tr, tp_ = 200, 256
    m_plain = Transformer(cfg, tp_size=2, attn_impl=attn_impl, remat=False)
    m_buck = Transformer(cfg, tp_size=2, attn_impl=attn_impl, remat=False,
                         attn_t_real=tr)
    params = jax.device_put(m_plain.init(jax.random.key(0)),
                            m_plain.shardings(mesh))
    b = 4
    ids = jax.random.randint(jax.random.key(3), (b, tr), 0, cfg.vocab_size)
    tgt = jnp.roll(ids, -1, axis=1)
    pos = jnp.tile(jnp.arange(tr, dtype=jnp.int32)[None], (b, 1))
    ids_p = jnp.pad(ids, ((0, 0), (0, tp_ - tr)))
    tgt_p = jnp.pad(tgt, ((0, 0), (0, tp_ - tr)),
                    constant_values=IGNORE_INDEX)
    pos_p = jnp.pad(pos, ((0, 0), (0, tp_ - tr)), mode="edge")

    l0 = m_plain.make_loss(mesh)(params, ids, tgt, pos)
    l1 = m_buck.make_loss(mesh)(params, ids_p, tgt_p, pos_p)
    np.testing.assert_allclose(float(l1), float(l0), atol=1e-6)
    g0 = jax.grad(lambda p: m_plain.make_loss(mesh)(p, ids, tgt, pos))(
        params)
    g1 = jax.grad(lambda p: m_buck.make_loss(mesh)(p, ids_p, tgt_p,
                                                   pos_p))(params)
    jax.tree.map(lambda a, b_: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b_), atol=1e-5), g0, g1)


def test_model_t_real_requires_cp1():
    cfg = ModelConfig(attn_dim=32, ffn_dim=64, num_heads=4, num_layers=2,
                      vocab_size=64, maxlen=64)
    with pytest.raises(ValueError, match="cp_size"):
        Transformer(cfg, cp_size=2, attn_t_real=48)
    with pytest.raises(ValueError, match="attn_t_real"):
        Transformer(cfg, attn_t_real=0)
    # MoE: the router sees every position — pad tokens would claim expert
    # capacity and inflate the aux losses, so bucketing must refuse
    import dataclasses
    moe_cfg = dataclasses.replace(cfg, num_experts=4)
    with pytest.raises(ValueError, match="MoE"):
        Transformer(moe_cfg, attn_t_real=48)


@pytest.mark.slow
@pytest.mark.parametrize("group", [1, 2, 4])
@pytest.mark.parametrize("t", [96, 256])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gqa_kernel_shape_sweep(group, t, dtype):
    """Broader (group, t, dtype) sweep of the GQA-routed kernels ahead of
    hardware: forward vs the repeat+dense oracle at both the fused
    (t<=128) and split block paths."""
    from distributed_pytorch_from_scratch_tpu.ops.attention import (
        causal_attention_xla)

    key = jax.random.key(group * 1000 + t)
    b, hkv, d = 2, 2, 32
    hq = hkv * group
    q = jax.random.normal(jax.random.fold_in(key, 1), (b, hq, t, d), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 2), (b, hkv, t, d), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 3), (b, hkv, t, d), dtype)
    ref = causal_attention_xla(q, k, v)
    out = flash_attention(q, k, v, block_q=128, block_k=128)
    atol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(out.astype(jnp.float32),
                               ref.astype(jnp.float32), atol=atol)
