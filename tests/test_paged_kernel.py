"""Pallas paged-attention kernel correctness — ISSUE 14.

Two layers of pins, mirroring how the kernel is layered:

* **block-level oracle**: `ops.pallas.paged_attention.paged_attention`
  (interpret mode) against the dense attend the gather path runs — the
  gathered page view + masked softmax einsum — across page sizes,
  pages_per_block, GQA groups, chunk widths, per-row cursors/qlen, int8
  (codes, scales) pools, and the cp-adoption `pos_offset` hook. Garbage
  rows (free slots at cursor 0, pad chunk columns) must stay finite.

* **engine token identity** (the acceptance contract): a PagedEngine /
  SpeculativeEngine built with `paged_attn_impl='pallas'` (interpreter
  opt-in) emits greedy output TOKEN-IDENTICAL to the gather impl — across
  page sizes {8, 16}, kv_dtype {native, int8}, tp ∈ {1, 2}, GQA, both
  model families, speculative rounds, and preempt/COW-resume. The gather
  impl stays the oracle; a kernel bug must show up as a token diff here,
  never as a silent perf lie.

Plus the perf-attribution pins: `obs/attribution.paged_decode_hbm_bytes`
prices the pallas dispatch at exactly the gather dispatch MINUS the
gather-copy bytes (the eliminated view write+read), the bench `--serving
--paged_attn pallas` record carries the A/B with those numbers, and
`check_bench_regression` treats the bytes metric directionally (up =
fail). CLI scope refusals round it out.
"""

import importlib.util
import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_from_scratch_tpu.config import MeshConfig, ModelConfig
from distributed_pytorch_from_scratch_tpu.models.decode import GreedyDecoder
from distributed_pytorch_from_scratch_tpu.models.transformer import Transformer
from distributed_pytorch_from_scratch_tpu.ops.pallas import paged_attention as pa
from distributed_pytorch_from_scratch_tpu.runtime.mesh import make_mesh
from distributed_pytorch_from_scratch_tpu.serving.engine import (
    PagedEngine, Request)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = ModelConfig(attn_dim=32, ffn_dim=64, num_heads=8, num_layers=2,
                  vocab_size=96, maxlen=64)
BUF, EOS = 32, 1
PROMPTS = [
    [0, 5, 17, 33, 60],
    [0, 95],
    [0, 2, 4, 6, 8, 10, 12, 14],    # page-boundary prompt at ps=8
    [0, 7],
    [0, 9, 11],
    [0, 3, 5, 7, 11, 13, 17],
]


def _load_script(name):
    path = os.path.join(REPO, "scripts", name + ".py")
    spec = importlib.util.spec_from_file_location(f"_pk_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------- block-level oracle ----


def _dense_oracle(q, k_pool, v_pool, tbl, start, ps, pos_offset=0):
    """The gather path's math: dense page view + masked f32 softmax."""
    b, h, cw, hd = q.shape
    if isinstance(k_pool, tuple):
        kc, ksc = k_pool
        vc, vsc = v_pool
        kvh = kc.shape[1]
        kview = kc[tbl].astype(jnp.float32) * ksc[tbl][..., None]
        vview = vc[tbl].astype(jnp.float32) * vsc[tbl][..., None]
    else:
        kvh = k_pool.shape[1]
        kview = k_pool[tbl].astype(jnp.float32)
        vview = v_pool[tbl].astype(jnp.float32)
    mp = tbl.shape[1]
    kview = kview.transpose(0, 2, 1, 3, 4).reshape(b, kvh, mp * ps, hd)
    vview = vview.transpose(0, 2, 1, 3, 4).reshape(b, kvh, mp * ps, hd)
    g = h // kvh
    qg = q.reshape(b, kvh, g, cw, hd).astype(jnp.float32)
    s = jnp.einsum("bkgqd,bktd->bkgqt", qg, kview) / math.sqrt(hd)
    pos = start[:, None] + jnp.arange(cw)[None, :]
    vis = (pos_offset + jnp.arange(mp * ps)[None, None, None, :, None]
           <= pos[:, None, None, None, :]).transpose(0, 1, 2, 4, 3)
    s = jnp.where(vis, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqt,bktd->bkgqd", p, vview)
    return o.reshape(b, h, cw, hd)


def _pool(rng, pages, kvh, ps, hd, int8=False):
    if int8:
        kp = (jnp.asarray(rng.integers(-127, 128, (pages + 1, kvh, ps, hd)),
                          jnp.int8),
              jnp.asarray(rng.uniform(0.01, 0.05, (pages + 1, kvh, ps)),
                          jnp.float32))
        vp = (jnp.asarray(rng.integers(-127, 128, (pages + 1, kvh, ps, hd)),
                          jnp.int8),
              jnp.asarray(rng.uniform(0.01, 0.05, (pages + 1, kvh, ps)),
                          jnp.float32))
        return kp, vp
    kp = jnp.asarray(rng.normal(size=(pages + 1, kvh, ps, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(pages + 1, kvh, ps, hd)), jnp.float32)
    return kp, vp


@pytest.mark.parametrize("ps,n_blk,g", [(8, 1, 1), (8, 2, 4), (16, 3, 2)])
def test_kernel_decode_matches_dense_oracle(ps, n_blk, g):
    """q_len=1 (the decode dispatch) over a scattered page walk: per-row
    cursors at page boundaries, mid-page, and 0 (the free-slot garbage
    row) — kernel == dense attend at every row, incl. odd
    pages_per_block that force a padded walk."""
    rng = np.random.default_rng(ps * 10 + n_blk + g)
    kvh, hd, mp, b = 2, 16, 4, 4
    kp, vp = _pool(rng, 10, kvh, ps, hd)
    tbl = jnp.asarray(rng.integers(0, 10, (b, mp)), jnp.int32)
    cur = jnp.asarray([ps - 1, 2 * ps, mp * ps - 1, 0], jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, kvh * g, 1, hd)), jnp.float32)
    o = pa.paged_attention(q, kp, vp, tbl, cur, page_size=ps,
                           pages_per_block=n_blk, interpret=True)
    r = _dense_oracle(q, kp, vp, tbl, cur, ps)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=1e-5)
    assert np.isfinite(np.asarray(o)).all()


@pytest.mark.parametrize("int8", [False, True])
def test_kernel_chunk_matches_dense_oracle(int8):
    """The chunk/verify dispatch (cw=4, per-row start + qlen): valid
    columns match the dense attend exactly; pad columns (>= qlen, whose
    page walk is skipped) stay finite garbage like the gather path."""
    rng = np.random.default_rng(7 if int8 else 3)
    ps, mp, b, kvh, g, hd, cw = 8, 4, 3, 2, 2, 16, 4
    kp, vp = _pool(rng, 10, kvh, ps, hd, int8=int8)
    tbl = jnp.asarray(rng.integers(0, 10, (b, mp)), jnp.int32)
    start = jnp.asarray([2, 9, 0], jnp.int32)
    qlen = jnp.asarray([4, 2, 1], jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, kvh * g, cw, hd)), jnp.float32)
    o = np.asarray(pa.paged_attention(q, kp, vp, tbl, start, page_size=ps,
                                      qlen=qlen, pages_per_block=2,
                                      interpret=True))
    r = np.asarray(_dense_oracle(q, kp, vp, tbl, start, ps))
    for i in range(b):
        n = int(qlen[i])
        np.testing.assert_allclose(o[i, :, :n], r[i, :, :n], atol=1e-5,
                                   err_msg=f"row {i}")
    assert np.isfinite(o).all()   # pad columns: garbage, never NaN/inf


def test_kernel_pos_offset_shifts_page_positions():
    """The cp-adoption hook: `pos_offset` declares the global position of
    the LOCAL pool's first slot — a kernel over the table's SECOND half
    with pos_offset = span/2 must equal the corresponding rows of the
    whole-table attend (the exact call a cp-sharded pool makes)."""
    rng = np.random.default_rng(11)
    ps, mp, b, kvh, hd = 8, 4, 2, 2, 16
    kp, vp = _pool(rng, 10, kvh, ps, hd)
    tbl = jnp.asarray(rng.integers(0, 10, (b, mp)), jnp.int32)
    cur = jnp.asarray([mp * ps - 1, 3 * ps], jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, kvh, 1, hd)), jnp.float32)
    # full attend == online-combine of the two half walks; verify the
    # SECOND half's masking uses the shifted positions by comparing its
    # standalone result against a dense oracle with the same offset
    half = tbl[:, mp // 2:]
    o = pa.paged_attention(q, kp, vp, half, cur, page_size=ps,
                           pos_offset=(mp // 2) * ps, interpret=True)
    r = _dense_oracle(q, kp, vp, half, cur, ps, pos_offset=(mp // 2) * ps)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=1e-5)


# ------------------------------------------------ engine token identity --


def _setup(tp, seed=7, cfg=CFG, family="llama"):
    mesh = make_mesh(MeshConfig(dp=1, tp=tp))
    if family == "gpt2":
        from distributed_pytorch_from_scratch_tpu.models.gpt2 import (
            GPT2Transformer)
        model = GPT2Transformer(cfg, tp_size=tp)
    else:
        model = Transformer(cfg, tp_size=tp)
    params = jax.device_put(model.init(jax.random.key(seed)),
                            model.shardings(mesh))
    return mesh, model, params


def _drive(eng, prompts=PROMPTS, max_new=10, stagger=True):
    reqs = [Request(rid=i, prompt=p, max_new=max_new)
            for i, p in enumerate(prompts)]
    if stagger:
        eng.submit(reqs[0])
        eng.submit(reqs[1])
        for _ in range(3):
            eng.step()
        for r in reversed(reqs[2:]):
            eng.submit(r)
    else:
        for r in reqs:
            eng.submit(r)
    eng.run_to_completion()
    return {r.rid: r.tokens for r in eng.completed}


def _ab(mesh, model, params, **kw):
    """Gather vs pallas(interpret) through otherwise-identical engines."""
    got = {}
    for impl in ("gather", "pallas"):
        eng = PagedEngine(model, mesh, params, eos_id=EOS,
                          paged_attn_impl=impl,
                          paged_attn_interpret=impl == "pallas", **kw)
        assert eng.paged_attn_impl == impl   # interpret opt-in: no fallback
        got[impl] = _drive(eng)
    return got


@pytest.mark.parametrize("tp,ps", [(2, 8), (1, 16)])
def test_pallas_matches_gather_greedy(tp, ps):
    """The anchor: staggered admissions + slot churn + chunked prefill +
    COW sharing through 2 slots — pallas greedy tokens == gather greedy
    tokens for every request. Pairwise over tp {1,2} x ps {8,16} (the
    (2,16)/(1,8) corners add compile time, not lowering coverage: tp
    changes the collectives, ps the page walk, independently)."""
    mesh, model, params = _setup(tp)
    got = _ab(mesh, model, params, num_slots=2, buf_len=BUF,
              page_size=ps, prefill_chunk=4)
    assert len(got["pallas"]) == len(PROMPTS)
    for i in range(len(PROMPTS)):
        assert got["pallas"][i] == got["gather"][i], (tp, ps, i)


@pytest.mark.parametrize("tp", [2])
def test_pallas_matches_gather_int8_kv(tp):
    """int8 (codes, scales) pools: the kernel's FUSED dequant must emit
    the same tokens as the gather path's dequantized HBM view."""
    mesh, model, params = _setup(tp)
    got = _ab(mesh, model, params, num_slots=2, buf_len=BUF,
              page_size=8, prefill_chunk=4, kv_dtype="int8")
    for i in range(len(PROMPTS)):
        assert got["pallas"][i] == got["gather"][i], (tp, i)


def test_pallas_matches_gather_gqa():
    """Grouped-query heads (8 q heads onto 2 kv heads): the kernel's
    q-row grouping must route exactly like the gather path's reshape."""
    cfg = ModelConfig(attn_dim=32, ffn_dim=64, num_heads=8, num_kv_heads=2,
                      num_layers=2, vocab_size=96, maxlen=64)
    mesh, model, params = _setup(2, seed=5, cfg=cfg)
    got = _ab(mesh, model, params, num_slots=2, buf_len=BUF,
              page_size=8, prefill_chunk=4)
    for i in range(len(PROMPTS)):
        assert got["pallas"][i] == got["gather"][i], i


def test_pallas_matches_gather_gpt2():
    """The second family (learned positions, LayerNorm, gelu, tied head)
    through the kernelized chunk/step programs."""
    cfg = ModelConfig(attn_dim=32, ffn_dim=64, num_heads=4, num_layers=2,
                      vocab_size=96, maxlen=64)
    mesh, model, params = _setup(2, seed=9, cfg=cfg, family="gpt2")
    got = _ab(mesh, model, params, num_slots=2, buf_len=BUF,
              page_size=8, prefill_chunk=4)
    for i in range(len(PROMPTS)):
        assert got["pallas"][i] == got["gather"][i], i


def test_pallas_matches_gather_speculative():
    """Speculative rounds on the kernel: drafter scan, K+1 verify, and
    drafter chunk prefill all walk their page tables in place — emitted
    tokens identical to the gather-impl speculative engine (hence, by PR
    7's pin, to the plain paged engine)."""
    from distributed_pytorch_from_scratch_tpu.serving.speculative import (
        SpeculativeEngine)
    dcfg = ModelConfig(attn_dim=16, ffn_dim=32, num_heads=2, num_layers=1,
                       vocab_size=96, maxlen=64)
    mesh, model, params = _setup(2)
    dmodel = Transformer(dcfg, tp_size=2)
    dparams = jax.device_put(dmodel.init(jax.random.key(9)),
                             dmodel.shardings(mesh))
    got = {}
    for impl in ("gather", "pallas"):
        eng = SpeculativeEngine(
            model, mesh, params, dmodel, dparams, num_slots=2, buf_len=BUF,
            eos_id=EOS, speculate_k=3, page_size=8, prefill_chunk=4,
            paged_attn_impl=impl, paged_attn_interpret=impl == "pallas")
        got[impl] = _drive(eng, prompts=PROMPTS[:4], max_new=8,
                           stagger=False)
        assert eng.spec_rounds > 0
    assert got["pallas"] == got["gather"]


def test_pallas_preempt_cow_resume_identity():
    """Through page exhaustion: preempted victims resume via COW prefill
    on the kernel path with outputs token-identical to uninterrupted solo
    GreedyDecoder decodes (the PR 6 contract, now on the kernel)."""
    mesh, model, params = _setup(2, seed=3)
    dec = GreedyDecoder(model, mesh, BUF)
    prompts = [[0, 5, 9, 60, 2, 8, 33], [0, 11, 4, 7, 21, 35, 2],
               [0, 44, 17, 8, 52, 3, 71]]
    refs = [dec.decode(params, p, EOS, max_total_len=len(p) + 12)
            for p in prompts]
    eng = PagedEngine(model, mesh, params, num_slots=3, buf_len=BUF,
                      eos_id=EOS, page_size=8, num_pages=4,
                      prefill_chunk=8, paged_attn_impl="pallas",
                      paged_attn_interpret=True)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new=12))
    eng.run_to_completion()
    got = {r.rid: r.tokens for r in eng.completed}
    for i, ref in enumerate(refs):
        assert got[i] == ref, (i, got[i], ref)
    assert eng.stats()["preemptions"] >= 1
    assert eng.stats()["paged_attn"] == "pallas"


# ------------------------------------------------ resolution / refusals --


def test_pallas_without_interpreter_is_an_error_off_tpu():
    """'pallas' without the interpreter opt-in on a non-TPU backend raises:
    it never degrades to gather, so a run that asked for the kernel and
    exits 0 ran the kernel."""
    with pytest.raises(ValueError, match="needs a TPU backend"):
        pa.check_paged_attn_impl("pallas")
    assert pa.check_paged_attn_impl("gather") == "gather"
    assert pa.check_paged_attn_impl("pallas", interpret=True) == "pallas"
    with pytest.raises(ValueError, match="paged_attn impl"):
        pa.check_paged_attn_impl("cuda")
    mesh, model, params = _setup(1)
    with pytest.raises(ValueError, match="needs a TPU backend"):
        PagedEngine(model, mesh, params, num_slots=2, buf_len=BUF,
                    eos_id=EOS, page_size=8, paged_attn_impl="pallas")


def test_serve_cli_refuses_paged_attn_without_paged():
    from distributed_pytorch_from_scratch_tpu.serving.serve import (
        get_serve_args)
    with pytest.raises(SystemExit):
        get_serve_args(["--dry_run", "--paged_attn", "pallas"])


def test_bench_cli_refuses_paged_attn_without_serving():
    import bench
    with pytest.raises(SystemExit):
        bench.parse_args(["--model", "tiny", "--paged_attn", "pallas"])


def test_serve_cli_refuses_pallas_off_tpu(tmp_path):
    """--paged --paged_attn pallas on the CPU backend: exit non-zero with
    the reason, before any weights load; no record, no gather run."""
    p = subprocess.run(
        [sys.executable, "-m",
         "distributed_pytorch_from_scratch_tpu.serving.serve",
         "--dry_run", "--paged", "--paged_attn", "pallas",
         "--log_dir", str(tmp_path / "logs")],
        capture_output=True, text=True, timeout=500, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert "needs a TPU backend" in p.stderr
    assert not p.stdout.strip()


# ------------------------------------------- pricing / A/B / gate pins ---


def test_paged_decode_hbm_bytes_drops_gather_copy():
    """The acceptance pricing: at the same dense span, pallas total ==
    gather total MINUS the gather-copy bytes (the dequantized view's HBM
    write+read); with live_tokens the kernel's block skip prices BELOW
    that. int8 pools shrink the pool-read term but the gather copy stays
    compute-dtype (the view dequantizes)."""
    from distributed_pytorch_from_scratch_tpu.obs.attribution import (
        paged_decode_hbm_bytes)
    kw = dict(slots=8, max_pages=4, page_size=16)
    g = paged_decode_hbm_bytes(CFG, paged_attn="gather", **kw)
    p = paged_decode_hbm_bytes(CFG, paged_attn="pallas", **kw)
    assert g["gather_copy_bytes"] > 0
    assert p["gather_copy_bytes"] == 0
    assert p["total_bytes"] == g["total_bytes"] - g["gather_copy_bytes"]
    # live-context skip prices strictly below the dense walk
    p_live = paged_decode_hbm_bytes(CFG, paged_attn="pallas",
                                    live_tokens=64, **kw)
    assert p_live["kv_pool_read_bytes"] < p["kv_pool_read_bytes"]
    # int8: smaller pool read, same compute-dtype gather copy
    g8 = paged_decode_hbm_bytes(CFG, paged_attn="gather", kv_dtype="int8",
                                **kw)
    assert g8["kv_pool_read_bytes"] < g["kv_pool_read_bytes"]
    assert g8["gather_copy_bytes"] == g["gather_copy_bytes"]
    # int8 weights hold the PR 8 weight-read floor
    w8 = paged_decode_hbm_bytes(CFG, paged_attn="pallas",
                                decode_weight_dtype="int8", **kw)
    assert w8["weight_bytes"] < p["weight_bytes"]
    with pytest.raises(ValueError, match="paged_attn"):
        paged_decode_hbm_bytes(CFG, paged_attn="triton", **kw)


def test_serving_bench_refuses_pallas_off_tpu():
    """`bench.py --serving --paged_attn pallas` on the CPU backend is a
    failed run: non-zero exit, the reason on stderr, and NO record — it
    used to time the gather path twice and file the result under the
    kernel's flag. (The A/B's pricing is pinned on the function above; the
    record itself needs the chip.)"""
    p = subprocess.run(
        [sys.executable, "-c", (
            "import jax; jax.config.update('jax_platforms','cpu');"
            "import bench;"
            "bench.main(['--model','tiny','--serving','--tp','1',"
            "'--slots','2','--serve_requests','3','--prompt_len','12',"
            "'--gen_tokens','6','--page_size','8','--prefill_chunk','16',"
            "'--paged_attn','pallas'])")],
        capture_output=True, text=True, timeout=500, cwd=REPO)
    assert p.returncode != 0
    assert "needs a TPU backend" in p.stderr
    assert not p.stdout.strip()


def test_gate_fails_when_decode_bytes_grow():
    """check_bench_regression treats decode_hbm_bytes_per_step
    directionally: a serving record whose per-step bytes GREW past the
    band fails even with tokens/s flat (the silent-fallback canary)."""
    gate = _load_script("check_bench_regression")
    base = {"metric": "serving tokens/sec (x)", "value": 100.0,
            "unit": "tokens/sec (serving)",
            "decode_hbm_bytes_per_step": 1_000_000}
    fresh_ok = dict(base, decode_hbm_bytes_per_step=900_000)
    fresh_bad = dict(base, decode_hbm_bytes_per_step=2_000_000)
    checks, _ = gate.metric_checks(fresh_ok, base, 10.0, 25.0)
    by = {c["field"]: c for c in checks}
    assert by["decode_hbm_bytes_per_step"]["ok"]
    assert by["decode_hbm_bytes_per_step"]["direction"] == "down"
    checks, _ = gate.metric_checks(fresh_bad, base, 10.0, 25.0)
    by = {c["field"]: c for c in checks}
    assert not by["decode_hbm_bytes_per_step"]["ok"]


def test_paged_block_config_cache_roundtrip(tmp_path, monkeypatch):
    """The autotuner table persists and reloads through the JSON cache
    (`ops/pallas/block_cache.py`): set -> save ->
    clear -> load -> same config; garbled files are ignored."""
    path = str(tmp_path / "paged_blocks.json")
    monkeypatch.setenv("PAGED_BLOCKS_CACHE", path)
    # pin the lazy once-per-process load as already-done: this test must
    # not depend on run order, and the lazy load would read the
    # developer's REAL cache (or re-read the file this test just saved)
    monkeypatch.setattr(pa, "_cache_loaded", True)
    # writer/reader key parity: the autotuner stores native entries under
    # kv_dtype=None and every float pool dtype must normalize to the SAME
    # key, else the kernel's default lookup silently misses tuned entries
    assert pa._table_key(16, 64, None) == pa._table_key(16, 64, "native")
    assert pa._table_key(16, 64, None) == pa._table_key(16, 64, jnp.float32)
    assert pa._table_key(16, 64, None) != pa._table_key(16, 64, "int8")
    key = pa._table_key(16, 64, "int8")
    try:
        pa.set_paged_block_config(16, 64, "int8", pa.PagedBlockConfig(4))
        assert pa.save_paged_block_cache() == path
        pa._PAGED_TABLE.pop(key, None)
        assert pa.get_paged_block_config(16, 64, "int8").pages_per_block == 1
        assert pa.load_paged_block_cache() >= 1
        assert pa.get_paged_block_config(16, 64, "int8").pages_per_block == 4
        # garbled cache: ignored, table keeps defaults
        with open(path, "w") as f:
            f.write("{not json")
        assert pa.load_paged_block_cache() == 0
    finally:
        pa._PAGED_TABLE.pop(key, None)


def test_autotune_paged_blocks_interpret_smoke():
    """The sweep itself runs chip-free under the interpreter (tiny shape)
    and records a winner in the table."""
    key = pa._table_key(8, 16, None)
    try:
        cfg = pa.autotune_paged_block_config(
            8, head_dim=16, slots=2, max_pages=2, kv_heads=2,
            sweep=(1, 2), iters=1, warmup=0, interpret=True)
        assert cfg.pages_per_block in (1, 2)
        assert pa.get_paged_block_config(8, 16).pages_per_block == \
            cfg.pages_per_block
    finally:
        pa._PAGED_TABLE.pop(key, None)
