"""Ring-decomposed collective matmul + bucketed gradient reduction
(ops/overlap.py, ISSUE 4).

Three invariants pinned on the virtual 8-device CPU mesh:

1. `ag_matmul` / `matmul_rs` equal their monolithic oracles
   (`all_gather`+dot, dot+`psum_scatter`) on values AND gradients (jacrev),
   for tp in {2, 4} — the ring is a pure re-scheduling of the same math,
   up to float summation order.
2. The model-level `tp_overlap='ring'` path matches the monolithic SP path
   fwd + grads, for both families, INSIDE the pipeline's live-gating (the
   ring's ppermutes run unconditionally on bubble steps — the acceptance
   bar of ISSUE 4).
3. The bucketed DP grad reduce equals the whole-tree transpose-derived
   reduction exactly (f32 wire), and within pinned tolerance on a bf16
   wire. A jax upgrade that changes shard_map's psum-transpose semantics
   breaks parity here LOUDLY (training/zero.build_bucketed_grad_fn
   normalises a trace-time-measured inflation factor).
4. The default step's own sum over 'dp' (`exchange_grads`: a typed gather
   and a sum in rank order, PR 32) gives the gradients the transpose's psum
   gives, the same bits on every replica, and is not there at dp 1 or
   inside the hand-reduced builders.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from distributed_pytorch_from_scratch_tpu.config import (
    IGNORE_INDEX, MeshConfig, ModelConfig)
from distributed_pytorch_from_scratch_tpu.models.gpt2 import GPT2Transformer
from distributed_pytorch_from_scratch_tpu.models.stack import DecoderStack
from distributed_pytorch_from_scratch_tpu.models.transformer import (
    Transformer, resolve_tp_layout)
from distributed_pytorch_from_scratch_tpu.models.vanilla import (
    VanillaGPT2, VanillaTransformer)
from distributed_pytorch_from_scratch_tpu.ops.collectives import (
    gather_from, reduce_scatter, split_to)
from distributed_pytorch_from_scratch_tpu.ops.overlap import (
    ag_matmul, bucket_partition, matmul_rs, ring_order)
from distributed_pytorch_from_scratch_tpu.runtime.mesh import make_mesh
from distributed_pytorch_from_scratch_tpu.training.zero import (
    build_bucketed_grad_fn)

CFG = ModelConfig(attn_dim=32, ffn_dim=64, num_heads=8, num_layers=2,
                  vocab_size=96, maxlen=64)


def make_batch(key, batch=4, t=32, vocab=96):
    k1, k2 = jax.random.split(key)
    input_ids = jax.random.randint(k1, (batch, t), 0, vocab)
    target_ids = jax.random.randint(k2, (batch, t), 0, vocab)
    mask = jax.random.bernoulli(jax.random.fold_in(key, 9), 0.2, (batch, t))
    target_ids = jnp.where(mask, IGNORE_INDEX, target_ids)
    position_ids = jnp.tile(jnp.arange(t)[None, :], (batch, 1))
    return input_ids, target_ids, position_ids


def assert_trees_close(a, b, rtol=1e-4, atol=1e-5):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=rtol, atol=atol)


# ------------------------------------------------ kernel-level vs oracles ----

@pytest.mark.parametrize("tp", [2, 4])
@pytest.mark.parametrize("nw", [1, 3])
def test_ag_matmul_matches_gather_dot_oracle(tp, nw):
    """ag_matmul == all_gather(x, seq) @ w, values and jacrev grads, for a
    single weight and for the fused multi-weight ring (wq/wk/wv shape). The
    ring's outputs come in ring order (ops/overlap.py); `ring_order` puts
    them in rank order, where the position-dependent coefficients apply."""
    mesh = make_mesh(MeshConfig(dp=1, tp=tp))
    b, t, d = 2, 8, 6
    key = jax.random.key(0)
    x = jax.random.normal(key, (b, t, d))
    ws = tuple(jax.random.normal(jax.random.fold_in(key, j), (d, 4 + 2 * j))
               for j in range(nw))
    coefs = tuple(jax.random.normal(jax.random.fold_in(key, 50 + j),
                                    (b, t, 4 + 2 * j)) for j in range(nw))

    def ring_loss(x, ws):
        ys = ag_matmul(x, ws, "tp")
        return sum(jnp.sum(ring_order(y, "tp") * c)
                   for y, c in zip(ys, coefs))

    def mono_loss(x, ws):
        xf = gather_from(x, "tp", tiled_axis=-2)
        return sum(jnp.sum((xf @ w) * c) for w, c in zip(ws, coefs))

    specs = (P(None, "tp", None), P())
    # check_vma=False: with REPLICATED weights (this oracle's setup; the
    # model's are tp-sharded) every rank ends up with the same product, but
    # assembled from ppermuted (ring) or all-gathered (oracle) chunks, both
    # typed varying — the replication out_specs P() asks for is true and
    # cannot be inferred statically
    smap = lambda fn: jax.shard_map(fn, mesh=mesh, in_specs=specs,
                                    out_specs=P(), check_vma=False)
    np.testing.assert_allclose(jax.jit(smap(ring_loss))(x, ws),
                               jax.jit(smap(mono_loss))(x, ws), rtol=1e-5)
    g_ring = jax.jit(jax.jacrev(smap(ring_loss), argnums=(0, 1)))(x, ws)
    g_mono = jax.jit(jax.jacrev(smap(mono_loss), argnums=(0, 1)))(x, ws)
    assert_trees_close(g_ring, g_mono)


@pytest.mark.parametrize("tp", [2, 4])
def test_matmul_rs_matches_dot_scatter_oracle(tp):
    """matmul_rs == psum_scatter(x @ w, seq), values and jacrev grads (the
    row-parallel seq_sharded pattern: split input, partial dot, reduce);
    the ring reads its input in ring order."""
    mesh = make_mesh(MeshConfig(dp=1, tp=tp))
    b, t, f, o = 2, 8, 8, 10
    key = jax.random.key(1)
    x = jax.random.normal(key, (b, t, f))
    w = jax.random.normal(jax.random.fold_in(key, 1), (f, o))
    tgt = jax.random.normal(jax.random.fold_in(key, 2), (b, t, o))

    def ring_loss(x, w, tgt):
        y = matmul_rs(ring_order(split_to(x, "tp"), "tp"), w, "tp")
        return jax.lax.psum(jnp.sum((y - tgt) ** 2), "tp")

    def mono_loss(x, w, tgt):
        y = reduce_scatter(split_to(x, "tp") @ w, "tp", scatter_axis=-2)
        return jax.lax.psum(jnp.sum((y - tgt) ** 2), "tp")

    specs = (P(), P("tp", None), P(None, "tp", None))
    run = lambda fn: jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=specs, out_specs=P()))
    np.testing.assert_allclose(run(ring_loss)(x, w, tgt),
                               run(mono_loss)(x, w, tgt), rtol=1e-5)
    g_ring = jax.jit(jax.jacrev(jax.shard_map(
        ring_loss, mesh=mesh, in_specs=specs, out_specs=P()),
        argnums=(0, 1)))(x, w, tgt)
    g_mono = jax.jit(jax.jacrev(jax.shard_map(
        mono_loss, mesh=mesh, in_specs=specs, out_specs=P()),
        argnums=(0, 1)))(x, w, tgt)
    assert_trees_close(g_ring, g_mono)


def test_uneven_seq_chunks_refused_loudly():
    """matmul_rs must refuse a sequence the ring cannot chunk evenly, and
    both ops must refuse shape-incompatible weights — at TRACE time, not
    as a wrong answer on the chip."""
    mesh = make_mesh(MeshConfig(dp=1, tp=4))
    x = jnp.ones((2, 6, 8))   # t=6, tp=4: uneven
    with pytest.raises(ValueError, match="not divisible"):
        jax.jit(jax.shard_map(
            lambda x, w: matmul_rs(x, w, "tp"), mesh=mesh,
            in_specs=(P(), P()), out_specs=P(None, "tp", None)))(
                x, jnp.ones((8, 4)))
    with pytest.raises(ValueError, match="does not contract"):
        jax.jit(jax.shard_map(
            lambda x, w: ag_matmul(x, (w,), "tp")[0], mesh=mesh,
            in_specs=(P(None, "tp", None), P()),
            out_specs=P(None, None, None)))(jnp.ones((2, 8, 6)),
                                            jnp.ones((5, 4)))
    with pytest.raises(ValueError, match="non-empty tuple"):
        jax.jit(jax.shard_map(
            lambda x: ag_matmul(x, (), "tp"), mesh=mesh,
            in_specs=(P(None, "tp", None),),
            out_specs=P()))(jnp.ones((2, 8, 6)))


# ---------------------------------------------- model-level ring overlap ----

@pytest.mark.parametrize("family,tp", [
    ("llama", 2), ("llama", 4), ("gpt2", 4),
    # covered by the three above (family x tp both exercised); slow lane
    # keeps the full matrix without costing the tier-1 870s window
    pytest.param("gpt2", 2, marks=pytest.mark.slow),
])
def test_model_ring_overlap_matches_monolithic(family, tp):
    """tp_overlap='ring' == 'off' on loss and every grad leaf, with SP on —
    the ISSUE 4 acceptance pin (tp in {2, 4})."""
    cls = GPT2Transformer if family == "gpt2" else Transformer
    mesh = make_mesh(MeshConfig(dp=1, tp=tp))
    mono = cls(CFG, tp_size=tp, sequence_parallel=True)
    ring = cls(CFG, tp_size=tp, sequence_parallel=True, tp_overlap="ring")
    params = mono.init(jax.random.key(0))
    ids, tgt, pos = make_batch(jax.random.key(2))
    l0, g0 = jax.value_and_grad(mono.make_loss(mesh))(params, ids, tgt, pos)
    l1, g1 = jax.value_and_grad(ring.make_loss(mesh))(params, ids, tgt, pos)
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-5)
    assert_trees_close(g1, g0)


@pytest.mark.parametrize("pp,tp", [
    (2, 2), pytest.param(2, 4, marks=pytest.mark.slow)])
def test_model_ring_overlap_matches_inside_pipeline(pp, tp):
    """The ring path inside the pipeline's live-gating: the tp rings run
    unconditionally on bubble steps (a stage-divergent cond around a
    ppermute deadlocks), garbage flows only into garbage — loss and grads
    still match the monolithic pipelined path."""
    mesh = make_mesh(MeshConfig(pp=pp, tp=tp))
    kw = dict(tp_size=tp, pp_size=pp, pp_microbatches=4,
              sequence_parallel=True)
    mono = Transformer(CFG, **kw)
    ring = Transformer(CFG, tp_overlap="ring", **kw)
    params = mono.init(jax.random.key(0))
    ids, tgt, pos = make_batch(jax.random.key(2))
    l0, g0 = jax.value_and_grad(mono.make_loss(mesh))(params, ids, tgt, pos)
    l1, g1 = jax.value_and_grad(ring.make_loss(mesh))(params, ids, tgt, pos)
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-5)
    assert_trees_close(g1, g0)


@pytest.mark.slow
def test_model_ring_overlap_matches_inside_ring_cp_pipeline():
    """The deepest composition: pp x cp(ring) x tp with SP + tp_overlap —
    BOTH ring families (cp attention ring, tp collective-matmul rings)
    execute their ppermutes on every pipeline step."""
    mesh = make_mesh(MeshConfig(pp=2, cp=2, tp=2))
    kw = dict(tp_size=2, cp_size=2, pp_size=2, pp_microbatches=4,
              sequence_parallel=True)
    mono = Transformer(CFG, **kw)
    ring = Transformer(CFG, tp_overlap="ring", **kw)
    params = mono.init(jax.random.key(0))
    ids, tgt, pos = make_batch(jax.random.key(3))
    l0, g0 = jax.value_and_grad(mono.make_loss(mesh))(params, ids, tgt, pos)
    l1, g1 = jax.value_and_grad(ring.make_loss(mesh))(params, ids, tgt, pos)
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-5)
    assert_trees_close(g1, g0, rtol=2e-4, atol=2e-5)


# -------------------------------- the layout the model picks for itself ----

MOE_CFG = ModelConfig(attn_dim=32, ffn_dim=64, num_heads=8, num_layers=2,
                      vocab_size=96, maxlen=64, num_experts=4)


@pytest.mark.parametrize("kw,t,want", [
    # nothing said: on, over the rings, wherever tp splits a dense model's
    # sequence; both off on one chip, for MoE and for a sequence that does
    # not divide; the monolithic path under pp
    (dict(tp_size=2), 32, (True, "ring")),
    (dict(tp_size=1), 32, (False, "off")),
    (dict(tp_size=4), 30, (False, "off")),
    (dict(tp_size=2, cfg=MOE_CFG), 32, (False, "off")),
    (dict(tp_size=2, pp_size=2), 32, (True, "off")),
    # an explicit value does what it always did
    (dict(tp_size=2, sequence_parallel=True), 32, (True, "off")),
    (dict(tp_size=4, sequence_parallel=True), 30, (True, "off")),
    (dict(tp_size=2, sequence_parallel=False), 32, (False, "off")),
    (dict(tp_size=2, tp_overlap="off"), 32, (True, "off")),
    (dict(tp_size=2, tp_overlap="ring_q"), 32, (True, "ring_q")),
    (dict(tp_size=2, cfg=MOE_CFG, sequence_parallel=True), 32,
     (True, "off")),
])
@pytest.mark.parametrize("cls", [Transformer, GPT2Transformer])
def test_tp_layout_resolution(cls, kw, t, want):
    kw = dict(kw)
    model = cls(kw.pop("cfg", CFG), **kw)
    assert model.tp_layout(t) == want
    resolved = model._resolved(t)
    assert (resolved.sequence_parallel, resolved.tp_overlap) == want
    assert resolved._resolved(t) is resolved
    assert resolve_tp_layout(
        model.sequence_parallel, model.tp_overlap, tp_size=model.tp_size,
        t_local=t, dense=not model.is_moe, pp_size=model.pp_size) == want


@pytest.mark.parametrize("family", ["llama", "gpt2"])
def test_default_model_at_tp2_rides_the_rings_and_matches_vanilla(family):
    """A model built with nothing but its tp size picks sequence parallelism
    and the ring collective matmuls at tp 2 (its program is, text for text,
    the one that asks for both by name) and computes what
    the unsharded reference computes; at tp 1 the default lowers to the text
    of an explicit off."""
    cls, oracle = ((GPT2Transformer, VanillaGPT2(CFG)) if family == "gpt2"
                   else (Transformer, VanillaTransformer(CFG)))
    mesh = make_mesh(MeshConfig(dp=2, tp=2), devices=jax.devices()[:4])
    model = cls(CFG, tp_size=2)
    params = model.init(jax.random.key(0))
    ids, tgt, pos = make_batch(jax.random.key(2))
    grad = jax.jit(jax.value_and_grad(model.make_loss(mesh)))
    text = grad.lower(params, ids, tgt, pos).as_text()
    assert "collective_permute" in text
    explicit = cls(CFG, tp_size=2, sequence_parallel=True, tp_overlap="ring")
    assert text == jax.jit(jax.value_and_grad(explicit.make_loss(mesh))).lower(
        params, ids, tgt, pos).as_text()
    l_sh, g_sh = grad(params, ids, tgt, pos)
    l_ref, g_ref = jax.value_and_grad(oracle.loss)(params, ids, tgt, pos)
    np.testing.assert_allclose(float(l_sh), float(l_ref), rtol=1e-5)
    assert_trees_close(g_sh, g_ref)

    one = make_mesh(MeshConfig(dp=1, tp=1), devices=jax.devices()[:1])
    lower = lambda m: jax.jit(jax.value_and_grad(m.make_loss(one))).lower(
        params, ids, tgt, pos).as_text()
    assert lower(cls(CFG)) == lower(
        cls(CFG, sequence_parallel=False, tp_overlap="off"))


def test_indivisible_sequence_is_off_by_default_and_raises_when_asked():
    mesh = make_mesh(MeshConfig(dp=1, tp=4))
    params = Transformer(CFG, tp_size=4).init(jax.random.key(0))
    ids, tgt, pos = make_batch(jax.random.key(2), t=30)     # 30 % 4 != 0
    off = Transformer(CFG, tp_size=4, sequence_parallel=False)
    np.testing.assert_allclose(
        float(Transformer(CFG, tp_size=4).make_loss(mesh)(
            params, ids, tgt, pos)),
        float(off.make_loss(mesh)(params, ids, tgt, pos)), rtol=1e-6)
    for kw in (dict(sequence_parallel=True), dict(tp_overlap="ring")):
        with pytest.raises(ValueError, match="sequence_parallel"):
            Transformer(CFG, tp_size=4, **kw).make_loss(mesh)(
                params, ids, tgt, pos)


def test_tp_overlap_validation():
    with pytest.raises(ValueError, match="requires sequence_parallel"):
        Transformer(CFG, tp_size=2, sequence_parallel=False,
                    tp_overlap="ring")
    with pytest.raises(ValueError, match="'off', 'ring' or 'ring_q'"):
        Transformer(CFG, tp_size=2, sequence_parallel=True,
                    tp_overlap="mesh")
    with pytest.raises(ValueError, match="True, False or 'auto'"):
        Transformer(CFG, tp_size=2, sequence_parallel="on")
    with pytest.raises(ValueError, match="MoE"):
        Transformer(MOE_CFG, tp_size=2, sequence_parallel=True,
                    tp_overlap="ring")


# ------------------------------------------------- bucketed grad reduce ----

def test_bucket_partition_bounds_and_covers():
    sizes = [10, 10, 100, 1, 1, 1, 50]
    buckets = bucket_partition(sizes, bucket_bytes=80, itemsize=4)
    flat = [i for b in buckets for i in b]
    assert flat == list(range(len(sizes)))          # covers, in order
    for b in buckets:
        if len(b) > 1:                              # multi-leaf buckets fit
            assert sum(sizes[i] * 4 for i in b) <= 80
    assert [2] in buckets                           # oversize leaf: own bucket


@pytest.mark.parametrize("dp,cp,tp,sp", [
    (8, 1, 1, False), (2, 1, 2, True),
    # the cp and tp4 compositions ride the slow lane (the two defaults
    # already pin the pure-dp and the SP tp-replicated-leaf rules)
    pytest.param(2, 2, 2, True, marks=pytest.mark.slow),
    pytest.param(2, 1, 4, True, marks=pytest.mark.slow)])
def test_bucketed_reduce_matches_whole_tree_psum(dp, cp, tp, sp):
    """build_bucketed_grad_fn == value_and_grad(make_loss) on loss and every
    grad leaf — tiny buckets force many psums, so the schedule itself is
    exercised. This is also the canary for the psum-transpose semantics the
    reducer normalises (see its docstring): a jax upgrade that changes them
    fails HERE, not silently in training."""
    mesh = make_mesh(MeshConfig(dp=dp, cp=cp, tp=tp))
    model = Transformer(CFG, tp_size=tp, cp_size=cp, sequence_parallel=sp)
    params = model.init(jax.random.key(0))
    ids, tgt, pos = make_batch(jax.random.key(2), batch=8)
    l0, g0 = jax.jit(jax.value_and_grad(
        model.make_loss(mesh)))(params, ids, tgt, pos)
    l1, g1 = jax.jit(build_bucketed_grad_fn(
        model, mesh, bucket_mb=0.001))(params, ids, tgt, pos)
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-6)
    assert_trees_close(g1, g0, rtol=1e-5, atol=1e-6)


def test_bucketed_reduce_bf16_wire_tolerance():
    """The EQuARX-style bf16 wire: grads stay f32 OUTSIDE the collective
    and land within bf16 rounding of the f32 reduction — |err| bounded by
    ~2^-8 relative (bf16 has 8 mantissa bits) plus the dp-deep reduced-
    precision accumulation. The bound is pinned so a silent dtype leak
    (f32 master accumulate lost) fails the suite."""
    mesh = make_mesh(MeshConfig(dp=4, tp=2))
    model = Transformer(CFG, tp_size=2, sequence_parallel=True)
    params = model.init(jax.random.key(0))
    ids, tgt, pos = make_batch(jax.random.key(2), batch=8)
    _, g32 = jax.jit(build_bucketed_grad_fn(
        model, mesh, bucket_mb=1.0))(params, ids, tgt, pos)
    _, g16 = jax.jit(build_bucketed_grad_fn(
        model, mesh, bucket_mb=1.0,
        reduce_dtype=jnp.bfloat16))(params, ids, tgt, pos)
    for a, b in zip(jax.tree.leaves(g16), jax.tree.leaves(g32)):
        assert a.dtype == jnp.float32  # wire-only compression
        scale = max(float(jnp.max(jnp.abs(b))), 1e-8)
        err = float(jnp.max(jnp.abs(a - b))) / scale
        assert err < 2.0 ** -7, f"bf16 wire error {err} out of bounds"


def test_bucketed_reduce_scope_refusals():
    mesh = make_mesh(MeshConfig(dp=2, tp=2))
    with pytest.raises(ValueError, match="sequence_parallel"):
        build_bucketed_grad_fn(
            Transformer(CFG, tp_size=2, sequence_parallel=False), mesh)
    mesh_pp = make_mesh(MeshConfig(pp=2, tp=2))
    with pytest.raises(ValueError, match="pp_size"):
        build_bucketed_grad_fn(
            Transformer(CFG, tp_size=2, pp_size=2, sequence_parallel=True),
            mesh_pp)
    moe_cfg = ModelConfig(attn_dim=32, ffn_dim=64, num_heads=8, num_layers=2,
                          vocab_size=96, maxlen=64, num_experts=4)
    mesh_ep = make_mesh(MeshConfig(dp=2, ep=2, tp=2))
    with pytest.raises(ValueError, match="MoE"):
        build_bucketed_grad_fn(
            Transformer(moe_cfg, tp_size=2, ep_size=2), mesh_ep)


# ------------------------------------------ the dp gradient exchange (PR 32) --

def _psum_reducer(monkeypatch):
    """The parent's form, kept as the oracle: with the hand-over off, a
    layer's weight cotangents are summed over 'dp' by the psum that the
    transpose of the varying cast (and `_like_primal`) inserts."""
    monkeypatch.setattr(DecoderStack, "_exchanges_dp_grads",
                        lambda self, x: False)


def _loss_and_grads(family, mesh_sizes, kw, params=None):
    cls = GPT2Transformer if family == "gpt2" else Transformer
    mesh = make_mesh(MeshConfig(**mesh_sizes))
    model = cls(CFG, tp_size=mesh_sizes.get("tp", 1),
                cp_size=mesh_sizes.get("cp", 1), **kw)
    if params is None:
        params = jax.device_put(model.init(jax.random.key(0)),
                                model.shardings(mesh))
    batch = make_batch(jax.random.key(2), batch=8)
    fn = jax.jit(jax.value_and_grad(model.make_loss(mesh)))
    return model, params, fn, batch


EXCHANGE_CASES = [
    ("gpt2", dict(dp=2, tp=2), {}),
    ("llama", dict(dp=2, tp=2), {}),
    ("gpt2", dict(dp=4, tp=1), {}),
    ("llama", dict(dp=2, tp=2), dict(tp_overlap="ring_q")),
    ("gpt2", dict(dp=2, cp=2), {}),
]
EXCHANGE_IDS = ["gpt2-dp2tp2", "llama-dp2tp2", "gpt2-dp4tp1",
                "llama-dp2tp2-ring_q", "gpt2-dp2cp2"]


@pytest.mark.parametrize("family,mesh_sizes,kw", EXCHANGE_CASES,
                         ids=EXCHANGE_IDS)
def test_dp_exchange_matches_the_psum_reducer(family, mesh_sizes, kw,
                                              monkeypatch):
    """Every gradient leaf of the default path against the psum reducer. At
    dp 2 a leaf summed over 'dp' alone (one sharded over 'tp', with no cp)
    is `a + b` either way: equal to the bit. A leaf the other axes sum too
    (norm gains and row biases under sequence parallelism, everything under
    cp) adds four terms in another order: the last place of a float32. At
    dp 4 the rank-ordered sum against the all-reduce's order: the bound the
    bucketed reducer is held to."""
    model, params, fn, batch = _loss_and_grads(family, mesh_sizes, kw)
    l1, g1 = fn(params, *batch)
    _psum_reducer(monkeypatch)
    _, _, oracle, _ = _loss_and_grads(family, mesh_sizes, kw, params)
    l0, g0 = oracle(params, *batch)
    assert float(l1) == float(l0)
    layer_specs = jax.tree.leaves(model.specs()["layers"],
                                  is_leaf=lambda x: isinstance(x, P))
    if mesh_sizes["dp"] == 2:
        assert_trees_close(g1, g0, rtol=1e-6, atol=1e-8)
        if "cp" not in mesh_sizes:
            for a, b, spec in zip(jax.tree.leaves(g1["layers"]),
                                  jax.tree.leaves(g0["layers"]),
                                  layer_specs):
                if "tp" in jax.tree.leaves(tuple(spec)):
                    np.testing.assert_array_equal(np.asarray(a),
                                                  np.asarray(b))
    else:
        assert_trees_close(g1, g0, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("mesh_sizes", [dict(dp=2, tp=2), dict(dp=4, tp=2)],
                         ids=["dp2", "dp4"])
def test_dp_exchange_leaves_replicas_bitwise_identical(mesh_sizes):
    """The optimizer runs on every replica: each has to hold the same
    gradient to the bit. Every rank adds the gathered copies in rank order,
    so they do; read from the devices' own buffers, shard by shard."""
    _, params, fn, batch = _loss_and_grads("gpt2", mesh_sizes, {})
    _, grads = fn(params, *batch)
    for g in jax.tree.leaves(grads):
        by_index = {}
        for shard in g.addressable_shards:
            by_index.setdefault(str(shard.index), []).append(
                np.asarray(shard.data))
        assert all(len(copies) >= mesh_sizes["dp"]
                   for copies in by_index.values())
        for copies in by_index.values():
            for c in copies[1:]:
                np.testing.assert_array_equal(c, copies[0])


@pytest.mark.parametrize("mesh_sizes,kw,engages", [
    (dict(dp=1, tp=2), {}, False),
    (dict(dp=1, tp=1), {}, False),
    (dict(dp=2, tp=2), {}, True),
    # pp > 1 keeps the psum it has (untimed either way)
    (dict(dp=2, pp=2), dict(pp_size=2), False),
], ids=["dp1tp2", "dp1tp1", "dp2tp2", "dp2pp2"])
def test_dp_exchange_engages_from_the_axis_size_alone(mesh_sizes, kw,
                                                      engages, monkeypatch):
    """At dp 1 (and under pp) the lowered loss-and-gradient is the text it
    is with the hand-over off: no new collective, no new op; at dp 2 the
    typed gather is there and the layers' psum over dp is not."""
    mesh = make_mesh(MeshConfig(**mesh_sizes))
    params = jax.eval_shape(GPT2Transformer(CFG).init, jax.random.key(0))
    batch = make_batch(jax.random.key(2), batch=8)

    def lowered():
        model = GPT2Transformer(CFG, tp_size=mesh_sizes.get("tp", 1), **kw)
        return jax.jit(jax.value_and_grad(model.make_loss(mesh))).lower(
            params, *batch).as_text()

    text = lowered()
    _psum_reducer(monkeypatch)
    off = lowered()
    assert (text != off) == engages
    gathers = lambda t: t.count("stablehlo.all_gather")
    assert (gathers(text) > gathers(off)) == engages
