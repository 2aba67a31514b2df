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
from family_recipe import outputs_and_grads
from jax.sharding import PartitionSpec as P

from distributed_pytorch_from_scratch_tpu import (MeshConfig, ModelConfig,
                                                  Transformer, make_mesh)
from distributed_pytorch_from_scratch_tpu.obs.attribution import (
    flash_tile_stats)
from distributed_pytorch_from_scratch_tpu.ops.attention import (
    CAUSAL, block_diffusion, causal_attention_xla, sliding_window)
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


# ---- the blocks a call runs with (flash_blocks) ----

_B = 1024       # flash_attention.DEFAULT_BLOCK, spelt out: the cases pin it


@pytest.mark.parametrize("t,d,mask,asked,want", [
    # the eleven cells' attention shapes (benchmark/configs, benchmark/
    # workloads), pinned to the blocks each has run with since its PR
    pytest.param(1024, 64, CAUSAL, {}, (1024, _B, _B, _B, _B),
                 id="gpt2-medium.train-b12-t1024"),
    pytest.param(1024, 64, CAUSAL, {}, (1024, _B, _B, _B, _B),
                 id="gpt2-large.train-dp2-tp2"),
    pytest.param(1024, 64, CAUSAL, {}, (1024, _B, _B, _B, _B),
                 id="gpt2-medium.train-ckpt-every40"),
    # latent attention: q/k 192 wide over a v of 128; asked by q's width
    pytest.param(4096, 192, CAUSAL, {}, (4096, _B, _B, _B, _B),
                 id="joyai-llm-flash.train-ep16share-b4-t4096"),
    pytest.param(4096, 192, CAUSAL, {}, (4096, _B, _B, _B, _B),
                 id="xing4-29b-a4b.train-ep8share-b1-t4096"),
    pytest.param(4096, 192, CAUSAL, {}, (4096, _B, _B, _B, _B),
                 id="ling-3-flash.train-ep64share-b1-t4096"),
    pytest.param(8192, 256, CAUSAL, {}, (8192, _B, _B, _B, _B),
                 id="qwen3-next-80b-a3b.train-ep16share-b2-t8192"),
    pytest.param(8192, 64, CAUSAL, {}, (8192, _B, _B, _B, _B),
                 id="lfm2-8b-a1b.train-ep4share-b2-t8192"),
    # 2 x 4096 rows [xt ; x0] under the block-diffusion mask, blocks of 4
    pytest.param(8192, 128, block_diffusion(4, 4096), {},
                 (8192, _B, _B, _B, _B),
                 id="sdar-30b-a3b.train-ep8share-b2-t4096"),
    pytest.param(8192, 128, sliding_window(2048), {}, (8192, _B, _B, _B, _B),
                 id="trinity-mini.train-epshare-b2-t8192-window"),
    pytest.param(8192, 128, CAUSAL, {}, (8192, _B, _B, _B, _B),
                 id="trinity-mini.train-epshare-b2-t8192-global"),
    pytest.param(16384, 128, sliding_window(4096), {},
                 (16384, _B, _B, _B, _B),
                 id="smallthinker-21b-a3b.train-ep4share-b1-t16384-window"),
    pytest.param(16384, 128, CAUSAL, {}, (16384, _B, _B, _B, _B),
                 id="smallthinker-21b-a3b.train-ep4share-b1-t16384-global"),
    # the rule's edges
    pytest.param(333, 64, CAUSAL, {}, (512, 512, 512, 512, 512),
                 id="t-not-a-power-of-two-pads-to-the-clamped-block"),
    pytest.param(1000, 64, CAUSAL, {"t_real": 900}, (1024, _B, _B, _B, _B),
                 id="causal-takes-a-t_real"),
    pytest.param(512, 64, sliding_window(512), {}, (512, 512, 512, 512, 512),
                 id="a-window-that-covers-t-is-causal"),
    pytest.param(700, 64, CAUSAL, {"block_q": 128, "block_k": 256},
                 (1024, 128, 256, 1024, 1024),
                 id="explicit-forward-blocks-share-the-backwards-t_pad"),
    pytest.param(2048, 64, CAUSAL, {"bwd_block_q": 512, "bwd_block_k": 256},
                 (2048, _B, _B, 512, 256), id="explicit-backward-blocks"),
    pytest.param(8192, 128, sliding_window(2048),
                 {"block_q": 512, "block_k": 2048}, (8192, 512, 512, _B, _B),
                 id="a-masks-blocks-are-square-the-smaller-asked"),
    pytest.param(1024, 128, block_diffusion(4, 512), {},
                 (1024, 512, 512, 512, 512),
                 id="block-diffusion-clamps-to-the-masks-half"),
    pytest.param(1024, 64, CAUSAL, {"block_q": 192}, "block_q must be a power",
                 id="a-block-not-a-multiple-of-128-raises"),
    pytest.param(1024, 64, CAUSAL, {"bwd_block_k": 384},
                 "bwd_block_k must be a power",
                 id="a-block-not-a-power-of-two-raises"),
    pytest.param(8192, 128, sliding_window(2048), {"t_real": 8000},
                 "takes no t_real", id="a-mask-takes-no-t_real"),
    pytest.param(384, 64, sliding_window(128), {}, "multiple of the grid",
                 id="a-window-over-rows-its-block-does-not-divide-raises"),
])
def test_flash_blocks_is_the_one_rule(t, d, mask, asked, want):
    """`flash_blocks` alone says the (t_pad, blocks) a call runs with: host
    arithmetic on its arguments, no table, file or environment behind it.
    `flash_tile_stats` reports the same for either direction (it asks; a
    mirror of the clamp regrown there would part from the kernel's t_pad at
    the explicit-forward case)."""
    assert fa_mod.DEFAULT_BLOCK == _B
    if isinstance(want, str):
        with pytest.raises(ValueError, match=want):
            fa_mod.flash_blocks(t, d, mask, **asked)
        return
    *run, folded = fa_mod.flash_blocks(t, d, mask, **asked)
    assert tuple(run) == want
    covered = mask.kind == "sliding_window" and mask.window >= t
    assert folded == (CAUSAL if covered else mask)
    for backward, names in ((False, ("block_q", "block_k")),
                            (True, ("bwd_block_q", "bwd_block_k"))):
        # the stats take one direction's blocks; the other keeps its default
        own = {n: v for n, v in asked.items() if n in names + ("t_real",)}
        run = fa_mod.flash_blocks(t, d, mask, **own)
        stats = flash_tile_stats(
            t, own.get(names[0]), own.get(names[1]), own.get("t_real"),
            head_dim=d, mask=mask, backward=backward)
        assert (stats["t_pad"], stats["block_q"], stats["block_k"]) == (
            run[0], *(run[3:5] if backward else run[1:3]))


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


# ---- the causal sub-tile plan inside a grid tile ----
#
# At the default blocks a sequence of 512 tokens or more is ONE grid tile a
# head whose plan has several sub-tiles (forward 128 x 256, backward 256 x
# 256): the cases below run at such shapes, so the kernels skip, mask and
# leave unmasked what the plan says.


def _qkv(key, b, h, hkv, t, d, dtype=jnp.float32, n=3, dv=None):
    """q, k of width d; v and (n=4) a cotangent of width dv, d if None."""
    dv = dv or d
    ks = jax.random.split(jax.random.key(key), 4)
    shapes = [(b, h, t, d), (b, hkv, t, d), (b, hkv, t, dv), (b, h, t, dv)]
    return [jax.random.normal(kk, s, dtype) for kk, s in zip(ks, shapes)][:n]


def _sliced_oracle(q, k, v, tr):
    """XLA attention on the first `tr` tokens, kv heads repeated for GQA,
    zeros on the rows past them."""
    group = q.shape[1] // k.shape[1]
    o = causal_attention_xla(q[:, :, :tr],
                             jnp.repeat(k[:, :, :tr], group, axis=1),
                             jnp.repeat(v[:, :, :tr], group, axis=1))
    return jnp.pad(o, ((0, 0), (0, 0), (0, q.shape[2] - tr), (0, 0)))


@pytest.mark.parametrize("block_q,block_k,sub,backward", [
    (1024, 1024, (128, 256), False), (1024, 1024, (256, 256), True),
    (512, 512, (128, 128), True), (512, 1024, (256, 128), False),
    (1024, 512, (128, 512), True), (256, 256, (256, 256), False),
    (1024, 1024, (1024, 1024), True)])
def test_subtile_plan_covers_live_and_unmasks_no_dead(
        monkeypatch, block_q, block_k, sub, backward):
    """The plan alone, over every grid tile of a 2048-token sequence and a
    sweep of t_real: the computed sub-tiles cover every live (row, col), a
    sub-tile marked unmasked holds no dead one, a skipped one no live one,
    and the two views (bands, columns) and the counts describe one set."""
    monkeypatch.setattr(fa_mod, "BWD_SUBTILE" if backward else "FWD_SUBTILE",
                        sub)
    fa_mod.causal_subtile_plan.cache_clear()
    t_pad = 2048
    for t_real in (1, 127, 128, 129, 700, 1000, 1024, 1025, 1536, 2048):
        row = np.arange(t_pad)[:, None]
        col = np.arange(t_pad)[None, :]
        live = (col <= row) & (row < t_real)
        covered = np.zeros_like(live)
        for qb in range(t_pad // block_q):
            for kb in range(t_pad // block_k):
                plan = fa_mod.causal_subtile_plan(
                    block_q, block_k, qb, kb, t_real, 64, backward)
                by_bands = np.zeros((block_q, block_k), np.int8)
                for r0, rows, rects in plan.bands:
                    for c0, cols, masked in rects:
                        assert not by_bands[r0:r0 + rows, c0:c0 + cols].any()
                        by_bands[r0:r0 + rows, c0:c0 + cols] = 1 + masked
                by_cols = np.zeros_like(by_bands)
                for c0, cols, rects in plan.columns:
                    for r0, rows, masked in rects:
                        by_cols[r0:r0 + rows, c0:c0 + cols] = 1 + masked
                assert (by_bands == by_cols).all()
                tile = live[qb * block_q:(qb + 1) * block_q,
                            kb * block_k:(kb + 1) * block_k]
                assert tile[by_bands == 1].all()        # unmasked: all live
                assert not tile[by_bands == 0].any()    # skipped: all dead
                cell = plan.sub_q * plan.sub_k
                assert (by_bands == 1).sum() == plan.computed_unmasked * cell
                assert (by_bands == 2).sum() == plan.computed_masked * cell
                assert (by_bands == 0).sum() == plan.skipped * cell
                assert plan.work_elems == (by_bands > 0).sum()
                # the mask of a masked rectangle is the live set itself
                r = np.arange(block_q)[:, None]
                c = np.arange(block_k)[None, :]
                assert (((c <= r + plan.diag) & (r < plan.cut)) == tile).all()
                covered[qb * block_q:(qb + 1) * block_q,
                        kb * block_k:(kb + 1) * block_k] = by_bands > 0
        assert covered[live].all()
    fa_mod.causal_subtile_plan.cache_clear()


@pytest.mark.parametrize("block,sub", [
    (512, (128, 256)), (1024, (256, 512)), (256, (256, 256)),
    (512, (128, 128)), (1024, (512, 256))])
def test_row_walk_runs_each_tiles_plan_once(monkeypatch, block, sub):
    """The looped stretch: query block i walks its diagonal tile's plan and,
    for key tiles 0 .. i - 1, the one plan `_row_walk_plans` paired with it.
    Each is THE plan of the tile it stands in (which the test above holds
    to the live set), no tile right of the diagonal has one, so every live
    entry is computed once and no dead one unmasked; and what is walked is
    what `causal_plan_stats` counts."""
    monkeypatch.setattr(fa_mod, "FWD_SUBTILE_WIDE", sub)
    fa_mod.causal_subtile_plan.cache_clear()
    t_pad, n = 2048, 2048 // block
    tile = lambda qb, kb, t_real: fa_mod.causal_subtile_plan(
        block, block, qb, kb, t_real, 64, num_kb=n)
    for t_real in (1, 127, 128, 129, 700, 1000, 1024, 1025, 1536, 2048):
        pairs = fa_mod._row_walk_plans(
            fa_mod._tile_plans(block, block, n, n, t_real, 64))
        row = np.arange(t_pad)[:, None]
        live = (np.arange(t_pad)[None, :] <= row) & (row < t_real)
        times = np.zeros(live.shape, np.int8)
        walked = {"computed_unmasked": 0, "computed_masked": 0}

        def walk(plan, qb, kb):
            assert plan == tile(qb, kb, t_real)
            for r0, rows, rects in plan.bands:
                for c0, cols, _ in rects:
                    times[qb * block + r0:qb * block + r0 + rows,
                          kb * block + c0:kb * block + c0 + cols] += 1
            walked["computed_unmasked"] += plan.computed_unmasked
            walked["computed_masked"] += plan.computed_masked

        for qi in range(n):
            mine = [(p, left) for p, left in pairs
                    if fa_mod._plan_is(p, qi, qi, block, block, t_real)]
            assert len(mine) == (qi * block < t_real)
            for plan, left in mine:
                walk(plan, qi, qi)
                for kb in range(qi):        # the kernel's fori_loop(0, qi)
                    walk(left, qi, kb)
            assert not any(tile(qi, kb, t_real).bands
                           for kb in range(qi + 1, n))
        assert (times[live] == 1).all() and times.max() == 1
        stats = fa_mod.causal_plan_stats(t_pad, block, block, t_real, 64)
        assert walked == {k: stats[k] for k in walked}
    fa_mod.causal_subtile_plan.cache_clear()


def test_subtile_plan_at_the_benchmark_shape():
    """t = 1024, head_dim 64, one tile a head: the forward walks 20 of 32
    128 x 256 sub-tiles (8 masked), the backward 10 of 16 256 x 256 (4
    masked) as four key sub-columns of a masked square over one unmasked
    rectangle; a 128-token tile is one masked sub-tile."""
    fwd = fa_mod.causal_subtile_plan(1024, 1024, 0, 0, 1024, 64)
    assert (fwd.computed_unmasked, fwd.computed_masked, fwd.skipped) == (
        12, 8, 12)
    assert all(cols == 256 for _, _, rects in fwd.bands
               for _, cols, _ in rects)
    bwd = fa_mod.causal_subtile_plan(1024, 1024, 0, 0, 1024, 64, True)
    assert (bwd.computed_unmasked, bwd.computed_masked, bwd.skipped) == (
        6, 4, 6)
    assert bwd.columns[0] == (0, 256, ((0, 256, True), (256, 768, False)))
    assert bwd.columns[-1] == (768, 256, ((768, 256, True),))
    small = fa_mod.causal_subtile_plan(128, 128, 0, 0, 128, 64)
    assert small.bands == ((0, 128, ((0, 128, True),)),)


def test_subtile_plan_at_the_latent_benchmark_shape():
    """t = 4096, q/k 192 against v 128, blocks 1024 (`DEFAULT_BLOCK`), K
    and V resident: a head walks 72 of 128 256 x 512 sub-tiles, 16 of them
    masked (it was 272 of 512 at 128 x 256). Each of the four query blocks runs the one-tile plan on its
    diagonal tile, 6 sub-tiles, and loops over the tiles left of it with a
    body of two unmasked sub-tiles a sub-row; `flash_tile_stats` reports the
    same walk."""
    stats = fa_mod.causal_plan_stats(4096, 1024, 1024, 4096, 192)
    assert stats == {"computed_unmasked": 56, "computed_masked": 16,
                     "skipped": 56, "work_elems": 72 * 256 * 512,
                     "sub_q": 256, "sub_k": 512}
    # several key blocks a head take the wide sub-tile at every width; one
    # tile a head only where q/k pass the MXU's 128 rows
    assert fa_mod.causal_plan_stats(4096, 1024, 1024, 4096, 64) == stats
    shape = lambda t, d: tuple(fa_mod.causal_plan_stats(
        t, 1024, 1024, t, d)[k] for k in ("sub_q", "sub_k"))
    assert shape(1024, 64) == shape(1024, 128) == (128, 256)
    assert shape(1024, 192) == shape(2048, 128) == (256, 512)
    (diag, left), = fa_mod._row_walk_plans(
        fa_mod._tile_plans(1024, 1024, 4, 4, 4096, 192))
    assert diag == fa_mod.causal_subtile_plan(1024, 1024, 0, 0, 1024, 192,
                                              num_kb=4)
    assert diag.bands == (
        (0, 256, ((0, 512, True),)), (256, 256, ((0, 512, True),)),
        (512, 256, ((0, 512, False), (512, 512, True))),
        (768, 256, ((0, 512, False), (512, 512, True))))
    assert left.bands == tuple(
        (r0, 256, ((0, 512, False), (512, 512, False)))
        for r0 in (0, 256, 512, 768))
    # 4 diagonal tiles + 6 tiles left of them
    assert 4 * 6 + 6 * 8 == 72
    s = flash_tile_stats(4096, head_dim=192)
    assert (s["block_q"], s["block_k"], s["sub_q"], s["sub_k"]) == (
        1024, 1024, 256, 512)
    assert (s["live_tiles"], s["masked_tiles"], s["total_tiles"]) == (
        72, 16, 128)
    assert 1.12 < s["waste_ratio"] < 1.13
    # the backward at these widths keeps the shape swept at 64
    bwd = fa_mod.causal_plan_stats(4096, 1024, 1024, 4096, 192, True)
    assert (bwd["sub_q"], bwd["sub_k"]) == (256, 256)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("t", [512, 1024])
def test_subtile_forward_matches_oracle(t, dtype, tol):
    q, k, v = _qkv(t, 1, 2, 2, t, 32, dtype)
    ref = causal_attention_xla(q, k, v).astype(jnp.float32)
    out = flash_attention(q, k, v).astype(jnp.float32)
    assert jnp.abs(ref - out).max() < tol


@pytest.mark.parametrize("t", [512, 1024])
def test_subtile_gradients_match_oracle(t):
    """One tile a head, several sub-tiles: the fused backward's walk of key
    sub-columns, dk and dv formed transposed."""
    q, k, v, g = _qkv(t + 1, 1, 2, 2, t, 32, n=4)
    _, gr = _out_and_grads(lambda *a: causal_attention_xla(*a), g, q, k, v)
    _, gf = _out_and_grads(lambda *a: flash_attention(*a), g, q, k, v)
    for a, b in zip(gr, gf):
        assert jnp.abs(a - b).max() < 1e-4


@pytest.mark.parametrize("t_real", [
    300,    # cuts through a sub-tile of both plans
    384,    # on a forward sub-row edge, inside a backward sub-tile
    256,    # on a sub-tile edge of both
    511])   # one pad row
def test_subtile_t_real_exact_zeros_with_tail_cotangent(t_real):
    """t_real against the sub-tiles of one 512-token tile: live rows match
    the sliced oracle, dead rows are exact zeros, and a nonzero cotangent on
    them yields exact zero gradients — whether the edge cuts a sub-tile
    (masked) or falls between two (the dead ones are never computed)."""
    q, k, v, g = _qkv(t_real, 1, 2, 2, 512, 32, n=4)
    ref, gr = _out_and_grads(lambda *a: _sliced_oracle(*a, t_real), g, q, k, v)
    out, gf = _out_and_grads(
        lambda *a: flash_attention(*a, t_real=t_real), g, q, k, v)
    assert jnp.abs(out - ref).max() < 1e-5
    assert jnp.abs(out[:, :, t_real:]).max() == 0.0
    for a, b in zip(gr, gf):
        assert jnp.abs(a - b).max() < 1e-4
        assert jnp.abs(b[:, :, t_real:]).max() == 0.0


@pytest.mark.parametrize("group", [1, 2, 4])
def test_subtile_gqa_groups(group):
    """Grouped query heads through the sub-tile walk: the fused backward
    accumulates dk/dv of a kv head over its group's grid steps."""
    q, k, v, g = _qkv(group, 1, 4, 4 // group, 512, 32, n=4)
    tr = 500
    ref, gr = _out_and_grads(lambda *a: _sliced_oracle(*a, tr), g, q, k, v)
    out, gf = _out_and_grads(
        lambda *a: flash_attention(*a, t_real=tr), g, q, k, v)
    assert jnp.abs(out - ref).max() < 1e-5
    for a, b in zip(gr, gf):
        assert jnp.abs(a - b).max() < 1e-4


@pytest.mark.parametrize("blocks,t_real", [
    ((512, 512), 1024), ((512, 512), 900), ((512, 1024), 700),
    ((1024, 512), 1024)])
def test_subtile_multiblock_diagonal_tiles(blocks, t_real):
    """A grid of several tiles: those the diagonal or the t_real edge
    crosses walk their plan (forward with the online softmax through
    scratch, backward in the split dq / dkv kernels), those under it run
    unmasked, and the kernel picks each tile's plan from the program ids."""
    q, k, v, g = _qkv(t_real, 1, 2, 1, 1024, 32, n=4)
    kw = dict(block_q=blocks[0], block_k=blocks[1], bwd_block_q=blocks[0],
              bwd_block_k=blocks[1], t_real=t_real)
    ref, gr = _out_and_grads(lambda *a: _sliced_oracle(*a, t_real), g, q, k, v)
    out, gf = _out_and_grads(lambda *a: flash_attention(*a, **kw), g, q, k, v)
    assert jnp.abs(out - ref).max() < 1e-5
    for a, b in zip(gr, gf):
        assert jnp.abs(a - b).max() < 1e-4
        assert not jnp.any(b[:, :, t_real:])


def test_subtile_under_shard_map():
    """One tile of several sub-tiles per shard, heads over tp: forward, and
    the backward through the split kernels (the fused one is gated off under
    the interpreter inside shard_map)."""
    mesh = make_mesh(MeshConfig(dp=1, tp=4))
    q, k, v = _qkv(7, 1, 4, 4, 512, 32)
    sm = jax.shard_map(lambda q, k, v: flash_attention(q, k, v, t_real=400),
                       mesh=mesh, in_specs=(P(None, "tp"),) * 3,
                       out_specs=P(None, "tp"))
    out = jax.jit(sm)(q, k, v)
    assert jnp.abs(out - _sliced_oracle(q, k, v, 400)).max() < 1e-5
    loss = lambda fn: lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)
    g_fl = jax.jit(jax.grad(loss(sm), argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss(lambda *a: _sliced_oracle(*a, 400)),
                     argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fl):
        assert jnp.abs(a - b).max() < 1e-4


# ------------------------------------------------- several blocks a head
#
# A head whose padded sequence spans several square blocks and whose K and V
# fit `KV_ROW_VMEM_BYTES` keeps them resident: grid (b*h, query blocks, 1),
# the diagonal tile's static plan plus a loop over the key tiles left of it
# (`_fwd_kernel`'s row walk). The cases below run 4 and 8 query blocks at
# two pairs of widths, one under each of the forward's two sub-tile shapes.


def _fwd_pallas_call(q, k, v, **kw):
    """The forward's pallas_call equation (q, k, v: arrays or shapes)."""
    def calls(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                yield e
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from calls(sub)

    jaxpr = jax.make_jaxpr(lambda *a: flash_attention(*a, **kw))(q, k, v)
    return next(calls(jaxpr.jaxpr))


def _fwd_grid(q, k, v, **kw):
    """(grid, K's block shape) of the forward's pallas_call."""
    gm = _fwd_pallas_call(q, k, v, **kw).params["grid_mapping"]
    return tuple(gm.grid), tuple(getattr(b, "block_size", b) for b in
                                 gm.block_mappings[1].block_shape)


def _out_and_grads(fn, g, *args):
    """(`fn(*args)`, the gradients of its product with the cotangent `g` in
    every argument) in one compiled program, at the backend's own products
    (the output is the kernel's own call, the gradients go through its
    forward rule: both are held)."""
    (out,), grads = outputs_and_grads(
        lambda *a: (fn(*a),),
        lambda out: jnp.vdot(out, g).real.astype(jnp.float32), *args,
        precision=None)
    return out, grads


def _rel(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.abs(a - b).max() / jnp.abs(a).max())


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("blocks", [4, 8])
@pytest.mark.parametrize("d,dv", [(24, 16), (192, 128)])
def test_row_walk_matches_oracle(d, dv, blocks, dtype, tol):
    """Forward and gradients against the XLA oracle, q/k and v of two
    widths: the walk is taken (K's block is the whole row, one grid step a
    query block), a query block's stretch runs 0 .. blocks - 1 key tiles."""
    t, blk = 256 * blocks, 256
    q, k, v, g = _qkv(blocks, 1, 1, 1, t, d, dtype, n=4, dv=dv)
    kw = dict(block_q=blk, block_k=blk, bwd_block_q=blk, bwd_block_k=blk)
    assert _fwd_grid(q, k, v, **kw) == ((1, blocks, 1), (1, t, d))
    ref, gr = _out_and_grads(causal_attention_xla, g, q, k, v)
    out, gf = _out_and_grads(lambda *a: flash_attention(*a, **kw), g, q, k, v)
    assert out.shape == (1, 1, t, dv) and out.dtype == dtype
    assert jnp.abs(ref.astype(jnp.float32)
                   - out.astype(jnp.float32)).max() < tol
    for a, b in zip(gr, gf):
        assert _rel(a, b) < 10 * tol


@pytest.mark.parametrize("t_real", [
    600,    # in the middle of the third block, cutting a sub-tile
    512,    # on a block edge
    100])   # in the first block: no tile left of any live diagonal tile
def test_row_walk_t_real_exact_zeros_with_tail_cotangent(t_real):
    """t_real against four resident blocks: live rows match the sliced
    oracle, rows at or past it read o = 0 and lse = MASK exactly, and a
    nonzero cotangent on them yields exact zero gradients."""
    q, k, v, g = _qkv(t_real, 1, 2, 2, 1024, 24, n=4, dv=16)
    kw = dict(block_q=256, block_k=256, bwd_block_q=256, bwd_block_k=256,
              t_real=t_real)
    ref, gr = _out_and_grads(lambda *a: _sliced_oracle(*a, t_real), g, q, k, v)
    out, gf = _out_and_grads(lambda *a: flash_attention(*a, **kw), g, q, k, v)
    assert jnp.abs(out - ref).max() < 1e-5
    assert jnp.abs(out[:, :, t_real:]).max() == 0.0
    flat = lambda x: x.reshape(2, 1024, x.shape[-1])
    o, lse = fa_mod._fwd_call(flat(q), flat(k), flat(v), t_real=t_real,
                              block_q=256, block_k=256, hq=2, hkv=2,
                              interpret=True)
    assert (lse[:, t_real:] == fa_mod.MASK).all()
    assert (lse[:, :t_real] > fa_mod.MASK / 2).all()
    assert not o[:, t_real:].any()
    for a, b in zip(gr, gf):
        assert jnp.abs(a - b).max() < 1e-4
        assert jnp.abs(b[:, :, t_real:]).max() == 0.0


@pytest.mark.parametrize("group", [1, 2, 4])
def test_row_walk_gqa_groups(group):
    """Grouped query heads: the K / V row routing lives in an index map
    that ignores the query block."""
    q, k, v, g = _qkv(group, 2, 4, 4 // group, 1024, 24, n=4, dv=16)
    tr = 900
    kw = dict(block_q=256, block_k=256, bwd_block_q=256, bwd_block_k=256,
              t_real=tr)
    assert _fwd_grid(q, k, v, **kw) == ((8, 4, 1), (1, 1024, 24))
    ref, gr = _out_and_grads(lambda *a: _sliced_oracle(*a, tr), g, q, k, v)
    out, gf = _out_and_grads(lambda *a: flash_attention(*a, **kw), g, q, k, v)
    assert jnp.abs(out - ref).max() < 1e-5
    for a, b in zip(gr, gf):
        assert jnp.abs(a - b).max() < 1e-4


def test_row_walk_under_shard_map():
    """Four resident blocks a shard, heads over tp: the loop's bound is a
    program id and its carry is tp-varying."""
    mesh = make_mesh(MeshConfig(dp=1, tp=4))
    q, k, v = _qkv(11, 1, 4, 4, 1024, 24, dv=16)
    kw = dict(block_q=256, block_k=256, bwd_block_q=256, bwd_block_k=256,
              t_real=700)
    sm = jax.shard_map(lambda q, k, v: flash_attention(q, k, v, **kw),
                       mesh=mesh, in_specs=(P(None, "tp"),) * 3,
                       out_specs=P(None, "tp"))
    out = jax.jit(sm)(q, k, v)
    assert jnp.abs(out - _sliced_oracle(q, k, v, 700)).max() < 1e-5
    loss = lambda fn: lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)
    g_fl = jax.jit(jax.grad(loss(sm), argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss(lambda *a: _sliced_oracle(*a, 700)),
                     argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fl):
        assert jnp.abs(a - b).max() < 1e-4


@pytest.mark.parametrize("blocks,t_real", [((256, 256), 1024),
                                           ((256, 256), 600),
                                           ((256, 512), 1024)])
def test_gridded_walk_where_the_row_does_not_fit(monkeypatch, blocks, t_real):
    """K and V of a head over the VMEM budget (or blocks that are not
    square): the grid walks the key blocks, (m, l, acc) through scratch,
    the K / V index maps clamped to the diagonal. Same oracle."""
    if blocks[0] == blocks[1]:
        monkeypatch.setattr(fa_mod, "KV_ROW_VMEM_BYTES", 0)
    q, k, v, g = _qkv(t_real, 1, 2, 1, 1024, 24, n=4, dv=16)
    kw = dict(block_q=blocks[0], block_k=blocks[1], bwd_block_q=blocks[0],
              bwd_block_k=blocks[1], t_real=t_real)
    assert _fwd_grid(q, k, v, **kw) == (
        (2, 1024 // blocks[0], 1024 // blocks[1]), (1, blocks[1], 24))
    ref, gr = _out_and_grads(lambda *a: _sliced_oracle(*a, t_real), g, q, k, v)
    out, gf = _out_and_grads(lambda *a: flash_attention(*a, **kw), g, q, k, v)
    assert jnp.abs(out - ref).max() < 1e-5
    assert not out[:, :, t_real:].any()
    for a, b in zip(gr, gf):
        assert jnp.abs(a - b).max() < 1e-4


def test_the_row_fits_by_bytes_alone(monkeypatch):
    """The walk is chosen from what the call sees: one byte under a head's
    double-buffered K and V (each width padded to VMEM's 128 lanes) and the
    grid walks; at it, the row is resident."""
    q, k, v = _qkv(3, 1, 1, 1, 1024, 24, dv=16)
    kw = dict(block_q=256, block_k=256)
    need = 2 * 1024 * (128 + 128) * 4
    monkeypatch.setattr(fa_mod, "KV_ROW_VMEM_BYTES", need)
    assert _fwd_grid(q, k, v, **kw) == ((1, 4, 1), (1, 1024, 24))
    monkeypatch.setattr(fa_mod, "KV_ROW_VMEM_BYTES", need - 1)
    assert _fwd_grid(q, k, v, **kw) == ((1, 4, 4), (1, 256, 24))
    # one key block a head is neither: the grid `DEFAULT_BLOCK` has had
    assert _fwd_grid(q, k, v) == ((1, 1, 1), (1, 1024, 24))
    # the shipped budget is what a chip run has read beside the gridded walk
    # (PR 52), not what Mosaic's default scoped limit compiles: 32 MiB.
    # Inside it, in bf16: the latent shape (4096 at 192 / 128: 6 MiB), 8192
    # at 192 / 128 (12), the two cells whose rows are 16 (16,384 at 128 /
    # 128, 8192 at 256 / 256), and at the edge 32,768 at 128 / 128 and
    # 16,384 at 256 / 256; outside it either edge shape at twice the length
    # (64), which nothing has timed
    monkeypatch.undo()
    mib = lambda t, d, dv: fa_mod._fwd_resident_bytes(t, d, dv, 2) / 2 ** 20
    assert (mib(4096, 192, 128), mib(8192, 192, 128)) == (6, 12)
    assert mib(16384, 128, 128) == mib(8192, 256, 256) == 16
    assert mib(32768, 128, 128) == mib(16384, 256, 256) == 32 \
        == fa_mod.KV_ROW_VMEM_BYTES / 2 ** 20
    assert mib(65536, 128, 128) == mib(32768, 256, 256) == 64
    # a row the default scoped limit compiles asks Mosaic for nothing, as
    # before PR 52; a row over it asks for its size and the body's room
    assert fa_mod.KV_ROW_SCOPED_BYTES == 8 * 2 ** 20 < fa_mod.KV_ROW_VMEM_BYTES
    assert fa_mod._vmem_limit(16 * 2 ** 20) == 40 * 2 ** 20


def _fwd_limit(t, d, dv, **kw):
    """The scoped VMEM the forward's pallas_call asks Mosaic for (None:
    nothing asked, the default)."""
    shape = lambda w: jax.ShapeDtypeStruct((1, 1, t, w), jnp.bfloat16)
    params = _fwd_pallas_call(shape(d), shape(d), shape(dv), interpret=True,
                              **kw).params["compiler_params"]
    return params["mosaic_tpu"].vmem_limit_bytes


@pytest.mark.parametrize("t,d,dv,limit", [
    (1024, 64, 64, None),           # one tile a head
    (4096, 192, 128, None),         # the latent cell's row, 6 MiB
    (8192, 128, 128, None),         # 8 MiB: the most the default compiles
    (8192, 192, 128, 36),           # 12 MiB: over it, so it asks
    (16384, 128, 128, 40),          # the sixteen-thousand-row cell's
    (8192, 256, 256, 40),           # the hybrid cell's
    (32768, 128, 128, 56),          # at the budget
    (65536, 128, 128, None)])       # over the budget: gridded, asks nothing
def test_the_row_asks_for_scoped_vmem_only_past_the_default(t, d, dv, limit):
    """`_fwd_call` hands Mosaic a `vmem_limit_bytes` where the resident row
    is over what the default scoped VMEM compiles, and nowhere else: the
    calls of every row up to 8 MiB are the ones they were."""
    got = _fwd_limit(t, d, dv, block_q=1024, block_k=1024)
    assert got == (limit and limit * 2 ** 20)


# ------------------------------- the backward of several blocks a head
#
# Where the blocks are square and what a head's backward keeps in VMEM fits
# `BWD_ROW_VMEM_BYTES`, ONE kernel holds the head and makes dq, dk and dv
# from one s and one dp a rectangle (`_bwd_row_kernel`, the call named
# `flash_bwd`); a head over it that fits `BWD_ROW_ONCE_VMEM_BYTES` with its
# whole-row blocks kept once takes the same kernel single-buffered (PR 56);
# rows over both keep `_dq_kernel` + `_dkv_kernel`, one tile a head keeps
# `_bwd_fused_kernel`. The cases below run two and four
# blocks a head: block 512 is four sub-tiles a tile (two key sub-columns,
# the under-diagonal tile's fused into one), block 256 one.


def _bwd_kernels(q, k, v, **kw):
    """Names of the backward's pallas_calls, in order."""
    def calls(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                yield e.params["name"]
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from calls(sub)

    grad = jax.grad(lambda *a: jnp.sum(flash_attention(*a, **kw)
                                       .astype(jnp.float32)), (0, 1, 2))
    return [n for n in calls(jax.make_jaxpr(grad)(q, k, v).jaxpr)
            if n.startswith("flash_bwd")]


def _no_row_walk(monkeypatch, once=False):
    """Both of the resident backward's budgets shut: the split kernels. With
    `once`, the second is left as shipped: the row walk, its blocks kept
    once."""
    monkeypatch.setattr(fa_mod, "BWD_ROW_VMEM_BYTES", 0)
    if not once:
        monkeypatch.setattr(fa_mod, "BWD_ROW_ONCE_VMEM_BYTES", 0)


def _bwd_call_jaxpr(bh, bhkv, t, d, dv, interpret):
    """`_bwd_call` traced at bf16 shapes alone, `DEFAULT_BLOCK`'s blocks."""
    sds = jax.ShapeDtypeStruct
    args = (sds((bh, t, d), jnp.bfloat16), sds((bhkv, t, d), jnp.bfloat16),
            sds((bhkv, t, dv), jnp.bfloat16), sds((bh, t, dv), jnp.bfloat16),
            sds((bh, t, 1), jnp.float32), sds((bh, t, dv), jnp.bfloat16))
    return jax.make_jaxpr(lambda *a: fa_mod._bwd_call(
        *a, t_real=t, block_q=1024, block_k=1024, hq=bh // bhkv, hkv=1,
        interpret=interpret))(*args)


def _square(block, **kw):
    return dict(block_q=block, block_k=block, bwd_block_q=block,
                bwd_block_k=block, **kw)


def _grads(fn, q, k, v, g):
    return jax.jit(jax.grad(
        lambda *a: jnp.vdot(fn(*a), g).real.astype(jnp.float32),
        (0, 1, 2)))(q, k, v)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("block,blocks", [(512, 2), (256, 4)])
@pytest.mark.parametrize("d,dv", [(64, 64), (128, 128), (192, 128)])
def test_bwd_row_walk_matches_oracle(d, dv, block, blocks, dtype, tol):
    """Gradients against the XLA oracle at the three pairs of widths the
    benchmark's cells run, two and four blocks a head: the one kernel is
    taken (one `flash_bwd` call where the split walk is two)."""
    t = block * blocks
    q, k, v, g = _qkv(d + blocks, 1, 1, 1, t, d, dtype, n=4, dv=dv)
    kw = _square(block)
    assert _bwd_kernels(q, k, v, **kw) == ["flash_bwd"]
    gr = _grads(causal_attention_xla, q, k, v, g)
    gf = _grads(lambda *a: flash_attention(*a, **kw), q, k, v, g)
    for a, b in zip(gr, gf):
        assert b.dtype == dtype and _rel(a, b) < 10 * tol


@pytest.mark.parametrize("group", [1, 4, 8])
def test_bwd_row_walk_gqa_groups(group):
    """Grouped query heads: dk and dv of a kv head accumulate in float32
    scratch over the group's grid steps, dq leaves a query head a step."""
    q, k, v, g = _qkv(group, 2, 8, 8 // group, 1024, 24, n=4, dv=16)
    tr = 900
    kw = _square(256, t_real=tr)
    assert _bwd_kernels(q, k, v, **kw) == ["flash_bwd"]
    gr = _grads(lambda *a: _sliced_oracle(*a, tr), q, k, v, g)
    gf = _grads(lambda *a: flash_attention(*a, **kw), q, k, v, g)
    for a, b in zip(gr, gf):
        assert jnp.abs(a - b).max() < 1e-4
        assert not jnp.any(b[:, :, tr:])


@pytest.mark.parametrize("block,t_real", [
    (256, 600),     # in the third of four blocks, cutting its one sub-tile
    (256, 512),     # on a block edge: two whole key tiles, two dead
    (256, 100),     # in the first block: the cut key tile alone
    (512, 700),     # in the second of two blocks, cutting a sub-tile
    (512, 768),     # on a sub-tile edge inside the second block
    (512, 1023)])   # one pad row
@pytest.mark.parametrize("group", [1, 2])
def test_bwd_row_walk_t_real_exact_zeros_with_tail_cotangent(block, t_real,
                                                             group):
    """t_real against a resident head: live rows match the sliced oracle,
    and a nonzero cotangent on the rows at or past it yields exact zero
    gradients — from the key tiles never walked, from the cut tile's masked
    rectangles and, under grouped heads, from the accumulators' zeros."""
    q, k, v, g = _qkv(t_real, 1, 2, 2 // group, 1024, 24, n=4, dv=16)
    kw = _square(block, t_real=t_real)
    assert _bwd_kernels(q, k, v, **kw) == ["flash_bwd"]
    gr = _grads(lambda *a: _sliced_oracle(*a, t_real), q, k, v, g)
    gf = _grads(lambda *a: flash_attention(*a, **kw), q, k, v, g)
    for a, b in zip(gr, gf):
        assert jnp.abs(a - b).max() < 1e-4
        assert jnp.abs(b[:, :, t_real:]).max() == 0.0


@pytest.mark.parametrize("block,sub", [
    (512, (256, 256)), (1024, (256, 256)), (256, (256, 256)),
    (512, (128, 128)), (1024, (512, 256)), (1024, (256, 512))])
def test_bwd_row_walk_runs_each_tiles_plan_once(monkeypatch, block, sub):
    """The kernel's walk, by `_col_walk_plans` and `_rects_at` as it makes
    it: key tile j left of t_real's tile walks the whole diagonal plan at
    (j, j), the whole under-diagonal plan at query tiles j + 1 .. and the
    cut one's at t_real's tile, sub-column by sub-column of the diagonal
    plan; t_real's key tile walks its diagonal tile alone. Each plan is THE
    plan of the tile it stands in, every live entry is computed once, no
    dead one unmasked, no tile above the diagonal has a plan, and what is
    walked is what `causal_plan_stats` counts."""
    monkeypatch.setattr(fa_mod, "BWD_SUBTILE", sub)
    fa_mod.causal_subtile_plan.cache_clear()
    t_pad, n = 2048, 2048 // block
    tile = lambda qb, kb, t_real: fa_mod.causal_subtile_plan(
        block, block, qb, kb, t_real, 64, True, n)
    for t_real in (1, 127, 128, 129, 700, 1000, 1024, 1025, 1536, 2048):
        full, edge = fa_mod._col_walk_plans(
            fa_mod._tile_plans(block, block, n, n, t_real, 64,
                               backward=True), block)
        n_full = t_real // block
        assert (full is None) == (n_full == 0)
        assert (edge is None) == (t_real % block == 0)
        row = np.arange(t_pad)[:, None]
        live = (np.arange(t_pad)[None, :] <= row) & (row < t_real)
        times = np.zeros(live.shape, np.int8)
        unmasked = np.zeros(live.shape, bool)
        elems = 0

        def walk(plan, qb, kb, c0, cols):
            nonlocal elems
            assert plan == tile(qb, kb, t_real)
            for r0, rows, masked in fa_mod._rects_at(plan, c0):
                at = (slice(qb * block + r0, qb * block + r0 + rows),
                      slice(kb * block + c0, kb * block + c0 + cols))
                times[at] += 1
                unmasked[at] |= not masked
                elems += rows * cols

        def key_tile(kb, diag, under, cut_tile):
            for c0, cols, _ in diag.columns:
                walk(diag, kb, kb, c0, cols)
                for qi in range(kb + 1, n_full):    # the kernel's fori_loop
                    walk(under, qi, kb, c0, cols)
                if cut_tile is not None:
                    walk(cut_tile, n_full, kb, c0, cols)

        for kb in range(n_full):                    # the kernel's fori_loop
            if n_full > 1:
                assert full[1] is not None
            key_tile(kb, full[0], full[1], edge and edge[1])
        if edge:
            key_tile(n_full, edge[0], None, None)
        assert not any(tile(qb, kb, t_real).bands
                       for qb in range(n) for kb in range(qb + 1, n))
        assert (times[live] == 1).all() and times.max() == 1
        assert live[unmasked].all()
        stats = fa_mod.causal_plan_stats(t_pad, block, block, t_real, 64,
                                         backward=True)
        assert elems == stats["work_elems"]
    fa_mod.causal_subtile_plan.cache_clear()


@pytest.mark.parametrize("block,t_real,group", [
    (512, 1024, 1), (512, 900, 2), (256, 600, 1), (256, 1024, 4)])
def test_split_kernels_where_the_head_does_not_fit(monkeypatch, block,
                                                   t_real, group):
    """A head over both budgets keeps `_dq_kernel` and `_dkv_kernel`, square
    blocks too, and they still match the oracle."""
    _no_row_walk(monkeypatch)
    q, k, v, g = _qkv(t_real, 1, 4, 4 // group, 1024, 24, n=4, dv=16)
    kw = _square(block, t_real=t_real)
    assert _bwd_kernels(q, k, v, **kw) == ["flash_bwd_dq", "flash_bwd_dkv"]
    gr = _grads(lambda *a: _sliced_oracle(*a, t_real), q, k, v, g)
    gf = _grads(lambda *a: flash_attention(*a, **kw), q, k, v, g)
    for a, b in zip(gr, gf):
        assert jnp.abs(a - b).max() < 1e-4
        assert not jnp.any(b[:, :, t_real:])


@pytest.mark.parametrize("block,t_real,group", [
    (512, 1024, 1), (512, 900, 2), (256, 600, 1), (256, 1024, 4),
    (256, 1024, 7)])
def test_the_row_walk_kept_once_where_the_head_does_not_fit_twice(
        monkeypatch, flash_bwd_calls, block, t_real, group):
    """A head over the first budget and inside the second takes the ONE
    kernel with each of its nine whole-row blocks single-buffered (PR 56):
    the same numbers as the double-buffered walk's to the bit (the body is
    the same and the interpreter has no pipeline), the split kernels' and
    the oracle's to rounding."""
    q, k, v, g = _qkv(t_real, 1, 2 * group, 2, 1024, 24, n=4, dv=16)
    kw = _square(block, t_real=t_real)
    flash = lambda *a: flash_attention(*a, **kw)
    grads = lambda: _grads(flash, q, k, v, g)
    assert flash_bwd_calls(flash, q, k, v) == [("flash_bwd", [None] * 9)]
    twice = grads()
    _no_row_walk(monkeypatch, once=True)
    assert flash_bwd_calls(flash, q, k, v) == [("flash_bwd", [1] * 9)]
    once = grads()
    _no_row_walk(monkeypatch)
    assert _bwd_kernels(q, k, v, **kw) == ["flash_bwd_dq", "flash_bwd_dkv"]
    split = grads()
    oracle = _grads(lambda *a: _sliced_oracle(*a, t_real), q, k, v, g)
    for a, b, c, d in zip(once, twice, split, oracle):
        assert jnp.array_equal(a, b)
        assert jnp.abs(a - c).max() < 1e-4 and jnp.abs(a - d).max() < 1e-4
        assert not jnp.any(a[:, :, t_real:])


def test_the_head_fits_by_bytes_alone(monkeypatch, flash_bwd_calls):
    """The backward's walk is chosen from what `_bwd_call` sees: one byte
    under what the head keeps in VMEM (nine whole-row blocks double-buffered,
    each width padded to 128 lanes, lse and delta 128 lanes of one value,
    and the float32 accumulators) and the blocks are kept once, if THAT fits
    the second budget; one byte under what it keeps so and the grid walks;
    at either, one kernel."""
    q, k, v = _qkv(3, 1, 2, 1, 1024, 24, dv=16)
    kw = _square(256)
    lanes = 128
    blocks = 1024 * ((4 * lanes + 3 * lanes) * 4 + 2 * lanes * 4)
    need = 2 * blocks + 1024 * 4 * (lanes + lanes + lanes)
    assert fa_mod._bwd_resident_bytes(1024, 24, 16, 4, 2) == need
    assert fa_mod._bwd_resident_bytes(1024, 24, 16, 4, 2, buffers=1) \
        == need - blocks
    # without grouped heads dk and dv leave from values: one accumulator
    assert fa_mod._bwd_resident_bytes(1024, 24, 16, 4, 1) \
        == need - 1024 * 4 * 2 * lanes
    once = [("flash_bwd", [1] * 9)]
    calls = lambda: flash_bwd_calls(lambda *a: flash_attention(*a, **kw),
                                    q, k, v)
    monkeypatch.setattr(fa_mod, "BWD_ROW_VMEM_BYTES", need)
    assert calls() == [("flash_bwd", [None] * 9)]
    monkeypatch.setattr(fa_mod, "BWD_ROW_VMEM_BYTES", need - 1)
    assert calls() == once
    monkeypatch.setattr(fa_mod, "BWD_ROW_ONCE_VMEM_BYTES", need - blocks)
    assert calls() == once
    monkeypatch.setattr(fa_mod, "BWD_ROW_ONCE_VMEM_BYTES", need - blocks - 1)
    assert _bwd_kernels(q, k, v, **kw) == ["flash_bwd_dq", "flash_bwd_dkv"]
    # blocks that are not square leave the diagonal crossing several tiles
    monkeypatch.undo()
    assert _bwd_kernels(q, k, v, block_q=256, block_k=256, bwd_block_q=256,
                        bwd_block_k=512) == ["flash_bwd_dq", "flash_bwd_dkv"]
    # the benchmark's cells: latent attention at 4k (34 MiB) and a group of
    # four heads of 64 or eight of 128 at 8k (56) fit the shipped budget
    # twice; a group of eight heads of 256 at 8k (96) and seven of 128 at
    # 16k (112) do not, and fit the second kept once (60 and 68); four times
    # that row (272 kept once) fits neither
    mib = lambda *a, **kw: fa_mod._bwd_resident_bytes(*a, **kw) / 2 ** 20
    first, second = (fa_mod.BWD_ROW_VMEM_BYTES / 2 ** 20,
                     fa_mod.BWD_ROW_ONCE_VMEM_BYTES / 2 ** 20)
    assert mib(4096, 192, 128, 2, 1) == 34 and mib(8192, 64, 64, 2, 4) == 56
    assert mib(8192, 128, 128, 2, 8) == 56 <= first
    assert mib(8192, 256, 256, 2, 8) == 96 > first
    assert mib(16384, 128, 128, 2, 7) == 112 > first
    assert mib(8192, 256, 256, 2, 8, buffers=1) == 60 <= second
    assert mib(16384, 128, 128, 2, 7, buffers=1) == 68 <= second
    assert mib(65536, 128, 128, 2, 7, buffers=1) == 272 > second
    # and what the kernel asks Mosaic for leaves the body its room, inside
    # the chip's 128 MiB
    assert fa_mod._vmem_limit(56 * 2 ** 20) == 80 * 2 ** 20
    assert fa_mod._vmem_limit(fa_mod.BWD_ROW_ONCE_VMEM_BYTES) == 92 * 2 ** 20


def test_flash_bwd_walk_instant_says_which_walk(tmp_path):
    """At trace time `_bwd_call` says on the program's tracer which walk it
    took, with how many buffers a whole-row block, the bytes as taken and
    the budget they were held to: `row` for a resident head (`buffers` 2
    where it fits twice, 1 where only once), `grid` for one over both
    budgets (or blocks not square), `tile` for one tile."""
    import json

    from distributed_pytorch_from_scratch_tpu.obs.trace import SpanTracer
    trace = functools.partial(_bwd_call_jaxpr, interpret=True)
    tracer = SpanTracer(str(tmp_path))
    try:
        trace(2, 2, 4096, 192, 128)     # the latent-attention cell's head
        trace(8, 1, 8192, 256, 256)     # the hybrid cell's: fits once
        trace(2, 2, 1024, 64, 64)       # the GPT-2 cells': one tile
        trace(4, 1, 8192, 64, 64)       # the conv cell's group of four
        trace(8, 1, 8192, 128, 128)     # the window cells' group of eight
        trace(7, 1, 16384, 128, 128)    # the 16k cell's: fits once
        trace(7, 1, 65536, 128, 128)    # four times that: over both
    finally:
        tracer.close()
    events = [json.loads(line)["args"] for line in
              open(tmp_path / "trace.jsonl")
              if json.loads(line)["name"] == "flash_bwd_walk"]
    first, second = (fa_mod.BWD_ROW_VMEM_BYTES // 2 ** 20,
                     fa_mod.BWD_ROW_ONCE_VMEM_BYTES // 2 ** 20)
    assert [(e["walk"], e["buffers"], e["t"], e["d"], e["dv"], e["group"],
             e["resident_bytes"] // 2 ** 20, e["budget_bytes"] // 2 ** 20,
             e["kept_once_bytes"] // 2 ** 20) for e in events] == [
        ("row", 2, 4096, 192, 128, 1, 34, first, 19),
        ("row", 1, 8192, 256, 256, 8, 60, second, 60),
        ("tile", 2, 1024, 64, 64, 1, 6, first, 3),
        ("row", 2, 8192, 64, 64, 4, 56, first, 34),
        ("row", 2, 8192, 128, 128, 8, 56, first, 34),
        ("row", 1, 16384, 128, 128, 7, 68, second, 68),
        ("grid", 2, 65536, 128, 128, 7, 448, first, 272)]


def test_flash_fwd_walk_instant_says_which_walk(tmp_path, monkeypatch):
    """At trace time `_fwd_call` says on the program's tracer which walk it
    took and the bytes it reckoned: `tile` for one key block a head, `row`
    for a head whose K and V stay resident, `grid` for one over the budget
    (or blocks not square)."""
    import json

    from distributed_pytorch_from_scratch_tpu.obs.trace import SpanTracer
    from distributed_pytorch_from_scratch_tpu.ops.attention import (
        sliding_window)

    def trace(hq, hkv, t, d, dv, block_k=1024, **kw):
        arg = lambda rows, w: jax.ShapeDtypeStruct((rows, t, w), jnp.bfloat16)
        jax.eval_shape(lambda *a: fa_mod._fwd_call(
            *a, t_real=t, block_q=1024, block_k=block_k, hq=hq, hkv=hkv,
            interpret=True, **kw), arg(hq, d), arg(hkv, d), arg(hkv, dv))

    tracer = SpanTracer(str(tmp_path))
    try:
        trace(2, 2, 1024, 64, 64)       # the GPT-2 cells': one tile
        trace(2, 2, 4096, 192, 128)     # the latent-attention cell's head
        trace(8, 1, 8192, 256, 256)     # the hybrid cell's, 16 MiB
        trace(7, 1, 16384, 128, 128, mask=sliding_window(4096))
        trace(1, 1, 65536, 128, 128)    # four times that: over the budget
        trace(2, 2, 4096, 64, 64, block_k=2048)     # blocks not square
        monkeypatch.setattr(fa_mod, "KV_ROW_VMEM_BYTES", 0)
        trace(2, 2, 4096, 192, 128)
    finally:
        tracer.close()
    events = [json.loads(line)["args"] for line in
              open(tmp_path / "trace.jsonl")
              if json.loads(line)["name"] == "flash_fwd_walk"]
    assert [(e["walk"], e["t"], e["d"], e["dv"], e["group"],
             e["resident_bytes"] // 2 ** 20, e["mask"], e["window"])
            for e in events] == [
        ("tile", 1024, 64, 64, 1, 1, "causal", 0),
        ("row", 4096, 192, 128, 1, 6, "causal", 0),
        ("row", 8192, 256, 256, 8, 16, "causal", 0),
        ("row", 16384, 128, 128, 7, 16, "sliding_window", 4096),
        ("grid", 65536, 128, 128, 1, 64, "causal", 0),
        ("grid", 4096, 64, 64, 1, 4, "causal", 0),
        ("grid", 4096, 192, 128, 1, 6, "causal", 0)]
    assert [e["budget_bytes"] for e in events] == [32 * 2 ** 20] * 6 + [0]


# `_bwd_call`'s jaxpr, kernel bodies and all, as the commit before the
# resident walk traced it (PR 39's tree; sha256 of the text with object
# addresses stripped, first 16 digits): (q heads, kv heads, t, d, dv). The
# second shape fits the second budget since PR 56 and is traced with both
# shut: the split kernels' text is what a head over both still runs.
BWD_JAXPR_BEFORE = {
    "one tile a head (the GPT-2 cells)": ((4, 4, 1024, 64, 64),
                                          "3375b09bb17b328d"),
    "a head over the budget (the hybrid cell)": ((16, 2, 8192, 256, 256),
                                                "11ba9961703d20f2"),
}


@pytest.mark.parametrize("which", sorted(BWD_JAXPR_BEFORE))
def test_bwd_call_keeps_the_other_walks_text(which, monkeypatch):
    """The resident walk took the rows it fits and nothing else: one tile a
    head traces the one-tile kernel and a head over the budgets the two
    split kernels, to the character what they were. A PR that means to
    change either changes the digest with it."""
    if "over the budget" in which:
        _no_row_walk(monkeypatch)
    import hashlib
    import re
    shape, digest = BWD_JAXPR_BEFORE[which]
    text = str(_bwd_call_jaxpr(*shape, interpret=False))
    text = re.sub(r" at 0x[0-9a-f]+", "", text)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest
