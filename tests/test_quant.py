"""Quantized wires and caches (ISSUE 8): int8 DP-reduce wire, ring_q
collective matmuls, int8 paged KV, int8 decode weights.

The pins, mirroring the PR 4 bf16-canary style:

1. Round-trip oracles for the shared quantization rule (ops/quant.py):
   per-block worst-case error amax/254, all-zero blocks EXACT, a single
   outlier poisons only its own block.
2. The int8 DP-reduce wire (`bucketed_psum(reduce_dtype=jnp.int8)` ->
   `quantized_allreduce`): grads within 2^-4 of the f32 reduce (the n
   requantizations bound), f32 OUTSIDE the wire, and a multi-step train
   run whose loss tracks the f32-wire run.
3. `tp_overlap='ring_q'` forward/backward bounds at tp in {2, 4}, kernel-
   and model-level, both families; `off`/`ring` stay exactly as before
   (their equivalence tests live in test_overlap.py and still pass).
4. int8 paged KV: greedy decode TOP-1 UNCHANGED (token-identical output)
   on a fixed prompt set with the per-step full-vocab logit deviation
   pinned, COW copies carry the scale array, refcounts drain.
5. The equal-HBM capacity win: at the SAME byte budget the int8 pool
   leases ~2x the pages — the burst the native pool PoolExhausted's on
   fits the int8 pool.
6. int8 decode weights: weight round-trip bound + engine logit deviation
   bound + outputs exact on the fixed set; CLI refusals + dry-run smoke.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from distributed_pytorch_from_scratch_tpu.config import (
    IGNORE_INDEX, MeshConfig, ModelConfig, OptimizerConfig)
from distributed_pytorch_from_scratch_tpu.models.decode import GreedyDecoder
from distributed_pytorch_from_scratch_tpu.models.gpt2 import GPT2Transformer
from distributed_pytorch_from_scratch_tpu.models.transformer import Transformer
from distributed_pytorch_from_scratch_tpu.ops.collectives import (
    gather_from, reduce_scatter, split_to)
from distributed_pytorch_from_scratch_tpu.ops.overlap import (
    ag_matmul, matmul_rs, quantized_allreduce, ring_order)
from distributed_pytorch_from_scratch_tpu.ops.quant import (
    dequantize_decode_params, dequantize_groups, dequantize_rows,
    quantize_decode_params, quantize_groups, quantize_rows)
from distributed_pytorch_from_scratch_tpu.runtime.mesh import make_mesh
from distributed_pytorch_from_scratch_tpu.serving.engine import (
    ContinuousBatchingEngine, PagedEngine, Request)
from distributed_pytorch_from_scratch_tpu.serving.kv_manager import (
    PagedKVPool, PoolExhausted, page_bytes)
from distributed_pytorch_from_scratch_tpu.training.zero import (
    build_bucketed_grad_fn)

CFG = ModelConfig(attn_dim=32, ffn_dim=64, num_heads=8, num_layers=2,
                  vocab_size=96, maxlen=64)
BUF, EOS = 32, 1
PROMPTS = [
    [0, 5, 17, 33, 60],
    [0, 95],
    [0, 2, 4, 6, 8, 10, 12, 14],    # page-boundary prompt at ps=8
    [0, 7],
]


def make_batch(key, batch=4, t=32, vocab=96):
    k1, k2 = jax.random.split(key)
    ids = jax.random.randint(k1, (batch, t), 0, vocab)
    tgt = jax.random.randint(k2, (batch, t), 0, vocab)
    mask = jax.random.bernoulli(jax.random.fold_in(key, 9), 0.2, (batch, t))
    tgt = jnp.where(mask, IGNORE_INDEX, tgt)
    pos = jnp.tile(jnp.arange(t)[None, :], (batch, 1))
    return ids, tgt, pos


def rel_err(a, b):
    return (float(jnp.max(jnp.abs(a - b)))
            / max(float(jnp.max(jnp.abs(b))), 1e-8))


# ------------------------------------------------- round-trip oracles ----

def test_quantize_roundtrip_oracles():
    """The shared int8 rule: per-block error <= amax/254; all-zero blocks
    exact; a single outlier inflates only its own block's error."""
    key = jax.random.key(0)
    x = jax.random.normal(key, (6, 40)) * jnp.exp(
        jax.random.normal(jax.random.fold_in(key, 1), (6, 1)))
    q, sc = quantize_rows(x)
    assert q.dtype == jnp.int8 and sc.dtype == jnp.float32
    back = dequantize_rows(q, sc, jnp.float32)
    amax = np.max(np.abs(np.asarray(x)), axis=-1)
    err = np.max(np.abs(np.asarray(back - x)), axis=-1)
    assert (err <= amax / 254 + 1e-12).all(), (err, amax / 254)

    # all-zero block: EXACT round-trip (scale falls back to 1, q = 0)
    z = jnp.zeros((3, 16))
    qz, sz = quantize_rows(z)
    assert (np.asarray(qz) == 0).all()
    assert (np.asarray(dequantize_rows(qz, sz, jnp.float32)) == 0).all()

    # grouped 1-D rule + outlier isolation: a 1e4 spike in group 0 must
    # not budge the error bound of far groups
    flat = jnp.ones((3000,)) * 0.01
    flat = flat.at[3].set(1e4)
    qg, sg = quantize_groups(flat, group=512)
    back = dequantize_groups(qg, sg, 3000, group=512)
    assert float(jnp.max(jnp.abs(back[512:] - flat[512:]))) <= 0.01 / 254
    # the spike itself round-trips within ITS block's bound
    assert abs(float(back[3]) - 1e4) <= 1e4 / 254


# ------------------------------------------------- int8 DP-reduce wire ----

def test_quantized_allreduce_matches_psum():
    """The EQuARX ring == psum within the n-requantization bound, on a
    single axis and a multi-axis product; replica-identical output (the
    optimizer contract); zeros exact."""
    mesh = make_mesh(MeshConfig(dp=4, tp=2))
    v = jax.random.normal(jax.random.key(5), (8, 3001))

    def q(z):  # every rank's copy comes back, stacked: (ranks, n)
        return quantized_allreduce(z[0], ("dp", "tp"))[None]

    def p(z):
        return jax.lax.psum(z[0], ("dp", "tp"))

    spec = (P(("dp", "tp")),)
    ring = jax.jit(jax.shard_map(q, mesh=mesh, in_specs=spec,
                                 out_specs=P(("dp", "tp"))))
    rq = ring(v)
    rp = jax.jit(jax.shard_map(p, mesh=mesh, in_specs=spec,
                               out_specs=P()))(v)
    assert rel_err(rq[0], rp) < 2.0 ** -4
    # replica-identity, checked on the values (the ring's result is built
    # from ppermutes, so shard_map cannot infer it): all 8 copies equal
    np.testing.assert_array_equal(np.asarray(rq),
                                  np.broadcast_to(np.asarray(rq[0]), rq.shape))
    assert float(jnp.max(jnp.abs(ring(jnp.zeros((8, 777)))))) == 0.0


def test_bucketed_reduce_int8_wire_tolerance():
    """The int8-wire analogue of the bf16 2^-7 canary: grads from the
    int8-wire bucketed reducer stay f32 OUTSIDE the wire and land within
    2^-4 of the f32 reduction (n quantizations of running partials at
    dp4; tests/test_overlap.py pins the bf16 sibling)."""
    mesh = make_mesh(MeshConfig(dp=4, tp=2))
    model = Transformer(CFG, tp_size=2, sequence_parallel=True)
    params = model.init(jax.random.key(0))
    ids, tgt, pos = make_batch(jax.random.key(2), batch=8)
    _, g32 = jax.jit(build_bucketed_grad_fn(
        model, mesh, bucket_mb=1.0))(params, ids, tgt, pos)
    _, g8 = jax.jit(build_bucketed_grad_fn(
        model, mesh, bucket_mb=1.0,
        reduce_dtype=jnp.int8))(params, ids, tgt, pos)
    for a, b in zip(jax.tree.leaves(g8), jax.tree.leaves(g32)):
        assert a.dtype == jnp.float32   # wire-only compression
        scale = max(float(jnp.max(jnp.abs(b))), 1e-8)
        err = float(jnp.max(jnp.abs(a - b))) / scale
        assert err < 2.0 ** -4, f"int8 wire error {err} out of bounds"


@pytest.mark.slow
def test_int8_wire_multi_step_loss_tracks_f32():
    """A 3-step train run on the int8 wire tracks the f32-wire run's loss
    trajectory (the multi-step pin: quantization noise must not compound
    into divergence at these scales)."""
    from distributed_pytorch_from_scratch_tpu.training.optim import (
        init_adam_state)
    from distributed_pytorch_from_scratch_tpu.training.train_step import (
        build_train_step)

    mesh = make_mesh(MeshConfig(dp=4, tp=2))
    model = Transformer(CFG, tp_size=2, sequence_parallel=True)
    ocfg = OptimizerConfig()
    losses = {}
    for name, wire in (("f32", None), ("int8", jnp.int8)):
        params = jax.device_put(model.init(jax.random.key(0)),
                                model.shardings(mesh))
        opt = init_adam_state(params)
        step = build_train_step(model, mesh, ocfg,
                                dp_reduce_bucket_mb=1.0,
                                dp_reduce_dtype=wire)
        traj = []
        for i in range(3):
            ids, tgt, pos = make_batch(jax.random.key(10 + i), batch=8)
            params, opt, loss = step(params, opt, ids, tgt, pos)
            traj.append(float(loss))
        losses[name] = traj
    for a, b in zip(losses["int8"], losses["f32"]):
        assert abs(a - b) / abs(b) < 0.02, losses


# --------------------------------------------------------- ring_q bounds ----

@pytest.mark.parametrize("tp", [2, 4])
def test_ring_q_kernels_match_oracles_within_bound(tp):
    """ag_matmul/matmul_rs(quantized=True) vs the monolithic oracles:
    forward within 2^-6 relative (one rounding per gather chunk, n-1 for
    the reduce accumulator), jacrev grads within 2^-4 — and the
    UNQUANTIZED paths still match at test_overlap.py's exact tolerances
    (checked there; here we only pin the quantized deltas)."""
    mesh = make_mesh(MeshConfig(dp=1, tp=tp))
    b, t, d = 2, 8, 16
    key = jax.random.key(0)
    x = jax.random.normal(key, (b, t, d))
    w = jax.random.normal(jax.random.fold_in(key, 1), (d, 12))

    def ring_loss(x, w):
        return jnp.sum(ag_matmul(x, (w,), "tp", True)[0] ** 2)

    def mono_loss(x, w):
        return jnp.sum((gather_from(x, "tp", tiled_axis=-2) @ w) ** 2)

    specs = (P(None, "tp", None), P())
    # check_vma=False: replicated w makes both losses replicated, but from
    # ppermuted / all-gathered chunks typed varying (see the unquantized
    # oracle in tests/test_overlap.py)
    smap = lambda fn: jax.shard_map(fn, mesh=mesh, in_specs=specs,
                                    out_specs=P(), check_vma=False)
    assert rel_err(jax.jit(smap(ring_loss))(x, w),
                   jax.jit(smap(mono_loss))(x, w)) < 2.0 ** -6
    gq = jax.jit(jax.jacrev(smap(ring_loss), argnums=(0, 1)))(x, w)
    gm = jax.jit(jax.jacrev(smap(mono_loss), argnums=(0, 1)))(x, w)
    for a, bb in zip(gq, gm):
        assert rel_err(a, bb) < 2.0 ** -4

    xr = jax.random.normal(jax.random.fold_in(key, 2), (b, t, d))
    wr = jax.random.normal(jax.random.fold_in(key, 3), (d, 10))

    def rs_q(x, w):
        # the ring reads its input in ring order (ops/overlap.py)
        return matmul_rs(ring_order(split_to(x, "tp"), "tp"), w, "tp", True)

    def rs_m(x, w):
        return reduce_scatter(split_to(x, "tp") @ w, "tp", scatter_axis=-2)

    out = P(None, "tp", None)
    sp = (P(), P("tp", None))
    yq = jax.jit(jax.shard_map(rs_q, mesh=mesh, in_specs=sp,
                               out_specs=out))(xr, wr)
    ym = jax.jit(jax.shard_map(rs_m, mesh=mesh, in_specs=sp,
                               out_specs=out))(xr, wr)
    assert rel_err(yq, ym) < 2.0 ** -6


@pytest.mark.parametrize("family,tp", [
    ("llama", 2), ("gpt2", 2),
    pytest.param("llama", 4, marks=pytest.mark.slow),
    pytest.param("gpt2", 4, marks=pytest.mark.slow)])
def test_model_ring_q_matches_off_within_bound(family, tp):
    """tp_overlap='ring_q' loss/grads vs 'off' at the model level — the
    ISSUE 8 acceptance pin for the quantized tp wire (both families, tp
    in {2, 4}; the int8 payloads perturb the loss < 1e-4 relative and
    every grad leaf < 2^-4 at this scale)."""
    cls = GPT2Transformer if family == "gpt2" else Transformer
    cfg = CFG if family == "llama" else ModelConfig(
        attn_dim=32, ffn_dim=64, num_heads=4, num_layers=2,
        vocab_size=96, maxlen=64)
    mesh = make_mesh(MeshConfig(dp=1, tp=tp))
    mono = cls(cfg, tp_size=tp, sequence_parallel=True)
    ring = cls(cfg, tp_size=tp, sequence_parallel=True, tp_overlap="ring_q")
    params = mono.init(jax.random.key(0))
    ids, tgt, pos = make_batch(jax.random.key(2))
    l0, g0 = jax.value_and_grad(mono.make_loss(mesh))(params, ids, tgt, pos)
    l1, g1 = jax.value_and_grad(ring.make_loss(mesh))(params, ids, tgt, pos)
    assert abs(float(l1) - float(l0)) / abs(float(l0)) < 1e-4
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g0)):
        if float(jnp.max(jnp.abs(b))) < 1e-8:
            # an analytically zero gradient (gpt2's key bias shifts every
            # score of a row alike and softmax cancels it): both sides are
            # float noise ~1e-10, which has no relative error to bound
            assert float(jnp.max(jnp.abs(a))) < 1e-8
            continue
        assert rel_err(a, b) < 2.0 ** -4


def test_ring_q_refusals():
    """ring_q inherits ring's scope: SP required, no MoE; unknown modes
    still refused; CLI parsers refuse the unsupported combos loudly."""
    with pytest.raises(ValueError, match="sequence_parallel"):
        Transformer(CFG, tp_size=2, sequence_parallel=False,
                    tp_overlap="ring_q")
    moe_cfg = ModelConfig(attn_dim=32, ffn_dim=64, num_heads=8,
                          num_layers=2, vocab_size=96, maxlen=64,
                          num_experts=4)
    with pytest.raises(ValueError, match="MoE"):
        Transformer(moe_cfg, tp_size=2, sequence_parallel=True,
                    tp_overlap="ring_q")
    import bench
    with pytest.raises(SystemExit):
        bench.parse_args(["--tp_overlap", "ring_q"])   # no SP
    with pytest.raises(SystemExit):
        bench.parse_args(["--dp_reduce_dtype", "int8"])  # no bucket
    with pytest.raises(SystemExit):
        bench.parse_args(["--kv_dtype", "int8"])       # no --serving
    from distributed_pytorch_from_scratch_tpu.serving.serve import (
        get_serve_args)
    with pytest.raises(SystemExit):
        get_serve_args(["--dry_run", "--kv_dtype", "int8"])  # no --paged


# ----------------------------------------------------------- int8 KV ----

def _setup(tp=1, seed=7):
    mesh = make_mesh(MeshConfig(dp=1, tp=tp))
    model = Transformer(CFG, tp_size=tp)
    params = jax.device_put(model.init(jax.random.key(seed)),
                            model.shardings(mesh))
    return mesh, model, params


def _drive(eng, prompts=PROMPTS, max_new=10):
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=list(p), max_new=max_new))
    eng.run_to_completion()
    return {r.rid: r.tokens for r in eng.completed}


@pytest.mark.parametrize("tp", [1, 2])
def test_int8_kv_greedy_pin(tp):
    """The greedy-quality pin: int8-KV paged decode emits the SAME tokens
    as the native pool (top-1 unchanged at every step of the fixed prompt
    set) with the per-step full-vocab logit deviation bounded — captured
    through the debug-host-sampler path, which materialises the logits
    the fused sampler consumes."""
    from distributed_pytorch_from_scratch_tpu.serving import engine as em

    mesh, model, params = _setup(tp)
    dec = GreedyDecoder(model, mesh, BUF)
    refs = [dec.decode(params, p, EOS, max_total_len=len(p) + 10)
            for p in PROMPTS]

    captured = {}
    orig = em.host_sample_tokens

    def run(kv_dtype, tag):
        captured[tag] = []

        def spy(model_, logits, *a, **kw):
            captured[tag].append(np.asarray(logits))
            return orig(model_, logits, *a, **kw)

        em.host_sample_tokens = spy
        try:
            eng = PagedEngine(model, mesh, params, num_slots=2, buf_len=BUF,
                              eos_id=EOS, page_size=8, prefill_chunk=4,
                              kv_dtype=kv_dtype, debug_host_sampler=True)
            return _drive(eng)
        finally:
            em.host_sample_tokens = orig

    native = run(None, "native")
    int8 = run("int8", "int8")
    for i, ref in enumerate(refs):
        assert int8[i] == ref, (tp, i, int8[i], ref)    # top-1 unchanged
        assert native[i] == ref
    # per-step logit deviation pinned: the two runs took identical
    # trajectories, so step logits align pairwise
    assert len(captured["int8"]) == len(captured["native"])
    worst = max(float(np.max(np.abs(a - b))) for a, b in
                zip(captured["int8"], captured["native"]))
    assert worst < 0.05, worst


def test_int8_kv_cow_copies_scales_and_drains():
    """Two identical prompts with a partial tail page: the second shares
    the donor's pages, its first decode write COW-copies BOTH the codes
    and the scale array (one bucketed dispatch), outputs stay identical,
    and the pool drains to zero (scales freed through the same refcount
    path)."""
    mesh, model, params = _setup()
    eng = PagedEngine(model, mesh, params, num_slots=4, buf_len=BUF,
                      eos_id=EOS, page_size=8, prefill_chunk=16,
                      kv_dtype="int8")
    p = [0, 2, 4, 6, 8, 10, 12, 14, 3, 5]
    got = _drive(eng, [p, list(p)], max_new=6)
    assert got[0] == got[1]
    st = eng.stats()
    assert st["cow_copies"] >= 1
    assert st["prefix_hit_tokens"] > 0
    assert st["kv_dtype"] == "int8"
    assert eng.pool.free_pages == eng.pool.num_pages
    assert (eng.pool.refcount == 0).all()


def test_int8_kv_capacity_win_at_equal_hbm():
    """The ISSUE 8 capacity criterion at pool level: at the SAME byte
    budget the int8 pool leases ~2x the pages — the lease burst that
    PoolExhausted's the native pool fits the int8 pool (CFG's hd=4 f32
    pages price at exactly 2x: 16 vs 8 bytes per head-vector) — and at
    engine level the same byte budget admits the whole burst live at
    once where the native pool has to interleave."""
    mesh, model, params = _setup(seed=3)
    ps = 8
    budget = 8 * page_bytes(model.cfg, ps)            # 8 native pages
    n_native = budget // page_bytes(model.cfg, ps)
    n_int8 = budget // page_bytes(model.cfg, ps, "int8")
    assert n_int8 >= 1.8 * n_native, (n_int8, n_native)

    native = PagedKVPool(model, mesh, int(n_native), ps)
    quant = PagedKVPool(model, mesh, int(n_int8), ps, kv_dtype="int8")
    with pytest.raises(PoolExhausted):
        for _ in range(int(n_native) + 1):
            native.alloc()
    for _ in range(int(n_native) + 1):                # same burst fits
        quant.alloc()

    # engine level: 6 x 2-page requests = 12 pages live. The int8 engine
    # (16 pages at the same bytes) runs all 6 concurrently; the native
    # engine (8 pages) cannot — its max concurrent live tokens stay
    # under the burst's demand.
    prompts = [[0, i + 2, i + 3, i + 5, i + 7, 11, 13, 2] for i in range(6)]
    refs = [GreedyDecoder(model, mesh, BUF).decode(
        params, p, EOS, max_total_len=len(p) + 8) for p in prompts]

    def drive(kv_dtype, pages):
        eng = PagedEngine(model, mesh, params, num_slots=6, buf_len=BUF,
                          eos_id=EOS, page_size=ps, num_pages=int(pages),
                          prefill_chunk=8, kv_dtype=kv_dtype)
        got = _drive(eng, prompts, max_new=8)
        return eng, got

    neng, ngot = drive(None, n_native)
    qeng, qgot = drive("int8", n_int8)
    for i, ref in enumerate(refs):                    # outputs exact
        assert qgot[i] == ref, (i, qgot[i], ref)
        assert ngot[i] == ref
    assert qeng.max_live == 6                         # whole burst live
    assert qeng.max_live > neng.max_live or neng.preemptions > 0


# ---------------------------------------------------- int8 decode weights ----

def test_int8_decode_weight_roundtrip_and_specs():
    """Per-output-channel weight quantization: round-trip error bounded
    by each column's amax/254; 1-D leaves pass through untouched; the
    derived spec tree shards codes like the weight and scales like the
    weight minus its contraction dim."""
    model = Transformer(CFG, tp_size=2, sequence_parallel=True)
    params = model.init(jax.random.key(0))
    qp, qs = quantize_decode_params(params, model.specs())
    back = dequantize_decode_params(qp)
    for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_leaves_with_path(params),
            jax.tree_util.tree_leaves_with_path(back)):
        if a.ndim >= 2:
            amax = np.max(np.abs(np.asarray(a)), axis=-2, keepdims=True)
            err = np.max(np.abs(np.asarray(b - a)), axis=-2, keepdims=True)
            assert (err <= amax / 254 + 1e-12).all(), pa
        else:
            assert (np.asarray(a) == np.asarray(b)).all(), pa  # untouched
    # spec shapes: lm_head weight P(None, 'tp') -> scale P(None, 'tp')
    assert qs["lm_head"]["weight"]["qweight"] == P(None, "tp")
    assert tuple(qs["lm_head"]["weight"]["scale"]) == (None, "tp")


@pytest.mark.parametrize("paged", [False, True])
def test_int8_decode_weights_engine_pin(paged):
    """Both engines serve int8 decode weights: outputs on the fixed
    prompt set stay token-identical to full-precision weights at this
    scale (logit margins dwarf the per-channel rounding), pinned so a
    quantization regression that DOES move tokens fails loudly."""
    tp = 2
    mesh, model, params = _setup(tp)
    dec = GreedyDecoder(model, mesh, BUF)
    refs = [dec.decode(params, p, EOS, max_total_len=len(p) + 10)
            for p in PROMPTS]
    if paged:
        eng = PagedEngine(model, mesh, params, num_slots=2, buf_len=BUF,
                          eos_id=EOS, page_size=8, prefill_chunk=4,
                          decode_weight_dtype="int8")
    else:
        eng = ContinuousBatchingEngine(
            model, mesh, params, num_slots=2, buf_len=BUF, eos_id=EOS,
            prefill_bucket=8, max_prefill_batch=2,
            decode_weight_dtype="int8")
    got = _drive(eng)
    for i, ref in enumerate(refs):
        assert got[i] == ref, (paged, i, got[i], ref)
    with pytest.raises(ValueError, match="decode_weight_dtype"):
        PagedEngine(model, mesh, params, num_slots=2, buf_len=BUF,
                    eos_id=EOS, decode_weight_dtype="fp4")


# ------------------------------------------------------------ CLI smoke ----

def test_quant_serve_dry_run_smoke(tmp_path):
    """`serve.py --dry_run --paged --kv_dtype int8 --decode_weight_dtype
    int8` end-to-end on CPU: the record carries both dtypes and the
    paged_kv_stats event carries kv_dtype (the rot guard for chip-less
    images, like the r9/r10 smokes)."""
    import json
    import os

    from distributed_pytorch_from_scratch_tpu.serving import serve as sm

    log_dir = str(tmp_path / "serve_quant")
    summary = sm.main(["--dry_run", "--paged", "--kv_dtype", "int8",
                       "--decode_weight_dtype", "int8",
                       "--log_dir", log_dir])
    assert summary["completed"] == summary["requests"] > 0
    assert summary["kv_dtype"] == "int8"
    recs = [json.loads(l)
            for l in open(os.path.join(log_dir, "metrics.jsonl"))]
    kv = next(r for r in recs if r["tag"] == "paged_kv_stats")
    assert kv["kv_dtype"] == "int8"
