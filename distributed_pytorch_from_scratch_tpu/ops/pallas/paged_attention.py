"""Paged attention for TPU, written in Pallas: attend over the page pool
IN PLACE, never materializing the gathered logical view.

The serving decode path (`models/decode.py`) historically attended through
`_gather_page_view`: a dense HBM copy of every live slot's whole context —
pages gathered out of the pool, int8 K/V dequantized OUTSIDE the attend —
re-materialized per layer, per step, for decode, chunked prefill, and the
speculative K+1 verify. At the 45M scale decode is HBM-bound, so that copy
IS the serving latency floor once weights are int8 (ROADMAP item 2). This
kernel family is the same move the training side made with the flash
kernel (flash_attention.py, PR 3): stream the K/V blocks through VMEM with
an online softmax, so the only HBM traffic is the pages themselves.

Mechanics (one kernel, three dispatch shapes):

* **page walk via scalar prefetch** — the `(slots, max_pages)` page table
  rides in as a `PrefetchScalarGridSpec` scalar operand, and the K/V
  BlockSpec index maps read `tbl[row, j]` to aim each grid step's block at
  the PHYSICAL page — the logical view is never built. Dead table entries
  aim at the scratch page and are position-masked to exact-zero weight
  (the same quarantine argument as the gather path).
* **per-row cursor masking** — a scalar-prefetched per-row max-visible
  position both masks (`kpos <= qpos`) and SKIPS whole page blocks past
  the cursor (`pl.when(block_live)`): dead pages/rows contribute nothing,
  and cost nothing but grid overhead.
* **online softmax across page blocks** — the flash recurrence (running
  max / rescaled accumulator / row sum) over the sequential page-block
  grid dimension; masked rows with zero visible K/V emit exact zeros.
* **fused int8 dequant** — a quantized pool's `(codes, scales)` tuples
  arrive as parallel block operands and dequantize INSIDE the block loop,
  in VMEM, at the moment of use; the dense compute-dtype view the gather
  path wrote to HBM simply never exists.
* **GQA-grouped query heads** — grid is (rows, kv_heads, page_blocks);
  the `group` query heads of each kv head stack into the kernel's q-row
  dimension, so grouped attention needs no K/V repeat anywhere.

Dispatch shapes: decode (q_len=1, `start` = the per-row cursor), chunked
prefill (q_len=cw, causal within the chunk via `start + i`), and the
speculative K+1 verify (the chunk shape with per-row `start`/`qlen`; the
caller scores all positions). All three share this one lowering.

cp-shardability (ROADMAP item 3): the page pool and page table are plain
positional operands, and `pos_offset` shifts the GLOBAL position the local
pool's pages represent — a cp shard passes its local pool slice, its local
table, and `axis_index('cp') * local_span`; nothing in the kernel assumes
the pool is whole.

Block shapes default to a cached autotuner table keyed on
`(page_size, head_dim, kv_dtype, backend)` (`get_paged_block_config` /
`autotune_paged_block_config`, JSON cache in `block_cache.py`,
`scripts/tune_flash_blocks.py --paged` sweeps it on hardware). The one
knob that matters is `pages_per_block`: how many (scattered) pages each
grid step fetches and scores together — more pages per step amortize the
VMEM pipeline, fewer skip dead context at finer grain.

Runs compiled on TPU and — ONLY when explicitly asked (`interpret=True`)
— under the Pallas interpreter on CPU, which is how the identity tests
pin it token-for-token against the gather oracle without a chip. A
non-TPU backend withOUT interpret is an error (`check_paged_attn_impl`):
neither the interpreter nor the gather path stands in for the kernel
unasked.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .block_cache import default_cache_path, load_json_table, save_json_table
from .flash_attention import MASK, _out_struct

IMPLS = ("gather", "pallas")

# dead-row lse sentinel — matches ops/ring_attention._BIG_NEG so a cp
# shard with no visible K/V for a row combines with exactly zero weight
_LSE_DEAD = -1e30


def _interpret_backend() -> bool:
    return jax.default_backend() != "tpu"


# ------------------------------------------------------------------ kernel


def _head_row(block: jax.Array, head) -> jax.Array:
    """Row `head` of a (kv_heads, page_size) scale block as (1, page_size),
    by a masked sublane sum: exact in f32, and neither a dynamic sublane
    slice nor a transpose (libtpu 0.0.34's Mosaic hangs compiling
    `ref[0, pl.ds(head, 1), :]` followed by a (1, ps) -> (ps, 1) relayout;
    measured on the v5e, PR 21)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, block.shape, 0)
    return jnp.sum(jnp.where(rows == head, block, 0.0), axis=0,
                   keepdims=True)


def _paged_kernel(tbl_ref, start_ref, vmax_ref, base_ref, q_ref, *refs,
                  scale: float, ps: int, n_pages: int, cw: int,
                  num_blocks: int, quantized: bool, out_dtype,
                  want_lse: bool = False):
    """One (row, kv_head) pair's walk over `n_pages` pages per grid step.

    refs: n_pages x (k[,k_scale], v[,v_scale]) page blocks, then o_ref
    (and lse_ref when `want_lse`), then the online-softmax scratch
    (acc, m, l). Scalar operands: page table (unused here — consumed by
    the index maps), per-row chunk start, per-row max visible position,
    global position base."""
    per = 4 if quantized else 2
    kv_refs = refs[:per * n_pages]
    o_ref = refs[per * n_pages]
    lse_ref = refs[per * n_pages + 1] if want_lse else None
    acc_ref, m_ref, l_ref = refs[per * n_pages + (2 if want_lse else 1):]
    b = pl.program_id(0)
    hi = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, MASK)
        l_ref[:] = jnp.zeros_like(l_ref)

    # whole block past the row's cursor: skip (dead pages cost nothing)
    block_live = (base_ref[0] + j * n_pages * ps) <= vmax_ref[b]

    @pl.when(block_live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                   # (R, hd)
        R = q.shape[0]
        # q row r = gi*cw + qi sits at absolute position start + qi
        qpos = start_ref[b] + jax.lax.broadcasted_iota(
            jnp.int32, (R, ps), 0) % cw
        col = jax.lax.broadcasted_iota(jnp.int32, (R, ps), 1)
        # The block's pages take the online-softmax update one at a time:
        # every operand keeps its page-block layout (no lane concatenate),
        # and a quantized page's scales — one per key — multiply along the
        # score tile's lane axis instead of being relaid into a column.
        for n in range(n_pages):
            k = kv_refs[per * n][0, 0].astype(jnp.float32)    # (ps, hd)
            v = kv_refs[per * n + per // 2][0, 0].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale    # (R, ps)
            if quantized:
                # fused dequant: q.(code*scale) == (q.code)*scale, applied
                # in VMEM at the moment of use — no dequantized view in HBM
                s = s * _head_row(kv_refs[per * n + 1][0], hi)
            # the page's keys sit at base + (j*n_pages + n)*ps + t
            kpos = base_ref[0] + (j * n_pages + n) * ps + col
            live = kpos <= qpos                      # causality: key <= query
            s = jnp.where(live, s, MASK)
            m_prev = m_ref[:]
            l_prev = l_ref[:]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            # clamp: rows with nothing visible in ANY page so far keep
            # m = MASK, and exp(MASK - MASK) = 1 would resurrect masked
            # entries (the flash kernels' guard); hard-zero to be safe
            m_safe = jnp.maximum(m_new, MASK / 2)
            alpha = jnp.exp(m_prev - m_safe)
            p = jnp.where(live, jnp.exp(s - m_safe), 0.0)
            l_ref[:] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            m_ref[:] = m_new
            if quantized:
                # p.(code*scale) == (p*scale).code, after the row sum
                p = p * _head_row(kv_refs[per * n + 3][0], hi)
            pv = jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc_ref[:] = acc_ref[:] * alpha + pv

    @pl.when(j == num_blocks - 1)
    def _finalize():
        l = l_ref[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)  # rows with no visible kv
        o_ref[0, 0] = (acc_ref[:] / l_safe).astype(out_dtype)
        if want_lse:
            # logsumexp of the row's visible scores — the cp combine's
            # currency (ring_attention's (o, lse) contract): dead rows
            # (nothing visible on THIS pool shard) emit the same big-neg
            # sentinel the ring's block math uses, so exp(lse - max)
            # underflows them to an exact-zero combine weight
            lse_ref[0, 0] = jnp.where(l == 0.0, _LSE_DEAD,
                                      m_ref[:] + jnp.log(l_safe))


def paged_attention(q: jax.Array, k_pool, v_pool, page_tbl: jax.Array,
                    start, *, page_size: int, qlen=None,
                    pages_per_block: Optional[int] = None,
                    pos_offset=0, return_lse: bool = False,
                    interpret: bool = False):
    """Attend `q` over the paged K/V pool through the page table, in place.

    q: (b, heads, cw, hd) — cw = 1 is the decode step, cw > 1 a prefill
    chunk / speculative verify window. k_pool/v_pool: one LAYER's pool
    slice, (num_pages+1, kv_heads, page_size, hd), or a (codes int8,
    scales f32) tuple for a quantized pool (kv_manager.PagedKVPool
    layout; the scales are (num_pages+1, kv_heads, page_size)). page_tbl:
    (b, max_pages) int32 physical page ids (dead entries at the scratch
    page). start: scalar or (b,) — the absolute position of q column 0
    (the decode cursor at cw=1). qlen: optional (b,) valid-query count
    per row; columns >= qlen compute garbage-into-garbage like the gather
    path (their block walk is also SKIPPED past start+qlen-1, so pad
    columns cost nothing). pos_offset: the global position of the LOCAL
    pool's first page slot — 0 for a whole pool; a cp shard passes its
    chunk offset (cp-shardable by construction, ROADMAP item 3).
    return_lse=True additionally returns the per-query logsumexp of the
    visible scores, (b, heads, cw) f32 with dead rows at -1e30 — the
    (out, lse) pair a cp shard's partial result combines through (ISSUE
    18); the default single-output shape is unchanged for every existing
    caller.

    Value contract: identical math to `_gather_page_view` + the dense
    attend block (f32 scores, softmax over visible positions, f32
    accumulate) — greedy outputs are pinned TOKEN-IDENTICAL to the gather
    path in tests/test_paged_kernel.py. The gathered view itself is never
    built: per step the kernel moves only the pages, once, pool->VMEM.

    `interpret=True` runs the Pallas interpreter (CPU-testable); on a
    non-TPU backend withOUT it this call would fail to compile — callers
    go through `check_paged_attn_impl` first.
    """
    b, h, cw, hd = q.shape
    quantized = isinstance(k_pool, tuple)
    kvh = (k_pool[0] if quantized else k_pool).shape[1]
    if h % kvh:
        raise ValueError(f"q heads {h} must be a multiple of kv heads {kvh}")
    g = h // kvh
    mp = page_tbl.shape[1]
    ps = page_size
    if pages_per_block is None:
        # quantized pools key as 'int8'; any float pool keys as 'native'
        # — the SAME normalization _table_key applies to the autotuner's
        # kv_dtype=None writes, so tuned entries are actually consulted
        # (a concrete-dtype key here would silently miss them)
        pages_per_block = get_paged_block_config(
            ps, hd, "int8" if quantized else None).pages_per_block
    N = max(1, min(int(pages_per_block), mp))
    scratch_page = (k_pool[0] if quantized else k_pool).shape[0] - 1
    mp_pad = -(-mp // N) * N
    if mp_pad != mp:
        # pad the walk to whole blocks with scratch entries; their
        # positions are >= buf_len, so the cursor mask kills them
        page_tbl = jnp.pad(page_tbl, ((0, 0), (0, mp_pad - mp)),
                           constant_values=scratch_page)
    num_blocks = mp_pad // N
    R = g * cw
    start = jnp.broadcast_to(jnp.asarray(start, jnp.int32), (b,))
    if qlen is not None:
        vmax = start + jnp.maximum(jnp.asarray(qlen, jnp.int32), 1) - 1
    else:
        vmax = start + (cw - 1)
    base = jnp.asarray(pos_offset, jnp.int32).reshape(1)
    # (b, h, cw, hd) -> (b, kvh, g*cw, hd): row r = gi*cw + qi, matching
    # the gather path's head-major q.reshape(b, kvh, g, cw, hd) grouping
    qr = q.reshape(b, kvh, g, cw, hd).reshape(b, kvh, R, hd)

    q_spec = pl.BlockSpec((1, 1, R, hd),
                          lambda bi, hi, j, *s: (bi, hi, 0, 0))
    kv_specs, ops = [], []
    for n in range(N):
        page_ix = (lambda bi, hi, j, tbl, st, vm, ba, n=n:
                   (tbl[bi, j * N + n], hi, 0, 0))
        if quantized:
            # a page's scales ride in for ALL kv heads, (1, kvh, ps): the
            # block's last two dims must equal the array's (Mosaic rejects
            # a (1, 1, ps) slice of the head axis); the kernel picks its
            # head's row (_head_row)
            sc_ix = (lambda bi, hi, j, tbl, st, vm, ba, n=n:
                     (tbl[bi, j * N + n], 0, 0))
            kv_specs += [pl.BlockSpec((1, 1, ps, hd), page_ix),
                         pl.BlockSpec((1, kvh, ps), sc_ix),
                         pl.BlockSpec((1, 1, ps, hd), page_ix),
                         pl.BlockSpec((1, kvh, ps), sc_ix)]
            ops += [k_pool[0], k_pool[1], v_pool[0], v_pool[1]]
        else:
            kv_specs += [pl.BlockSpec((1, 1, ps, hd), page_ix),
                         pl.BlockSpec((1, 1, ps, hd), page_ix)]
            ops += [k_pool, v_pool]

    out_block = pl.BlockSpec((1, 1, R, hd),
                             lambda bi, hi, j, *s: (bi, hi, 0, 0))
    out_shape = _out_struct((b, kvh, R, hd), q.dtype, q)
    out_specs = out_block
    if return_lse:
        lse_block = pl.BlockSpec((1, 1, R, 1),
                                 lambda bi, hi, j, *s: (bi, hi, 0, 0))
        out_shape = (out_shape,
                     _out_struct((b, kvh, R, 1), jnp.float32, q))
        out_specs = (out_block, lse_block)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, kvh, num_blocks),
        in_specs=[q_spec] + kv_specs,
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((R, hd), jnp.float32),
                        pltpu.VMEM((R, 1), jnp.float32),
                        pltpu.VMEM((R, 1), jnp.float32)])
    kernel = functools.partial(
        _paged_kernel, scale=1.0 / math.sqrt(hd), ps=ps, n_pages=N, cw=cw,
        num_blocks=num_blocks, quantized=quantized, out_dtype=q.dtype,
        want_lse=return_lse)
    # causal per-row work: each row reads ~its live context once
    flops = 4 * b * h * cw * mp * ps * hd
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=flops,
            bytes_accessed=2 * b * mp * ps * kvh * hd
            * (1 if quantized else q.dtype.itemsize),
            transcendentals=b * h * cw * mp * ps),
        interpret=interpret,
    )(page_tbl, start, vmax, base, qr, *ops)
    if return_lse:
        o, lse = out
        o = o.reshape(b, kvh, g, cw, hd).reshape(b, h, cw, hd)
        lse = lse.reshape(b, kvh, g, cw).reshape(b, h, cw)
        return o, lse
    return out.reshape(b, kvh, g, cw, hd).reshape(b, h, cw, hd)


# ---------------------------------------------------------------- impl gate


def check_paged_attn_impl(impl: str, interpret: bool = False) -> str:
    """Validate the impl the serving programs will build; returns it.
    'pallas' is compiled by Mosaic: on a non-TPU backend without the
    explicit interpreter opt-in it is an error. It never degrades to
    'gather' — a run that asked for the kernel and exits 0 ran the kernel."""
    if impl not in IMPLS:
        raise ValueError(f"paged_attn impl must be one of {IMPLS}, got "
                         f"{impl!r}")
    if impl == "pallas" and not interpret and jax.default_backend() != "tpu":
        raise ValueError(
            f"paged_attn 'pallas' is compiled by Mosaic and needs a TPU "
            f"backend (got {jax.default_backend()!r}); use 'gather' "
            f"off-TPU. Library callers can run the kernel under the Pallas "
            f"interpreter on purpose with paged_attn_interpret=True (the "
            f"tests do); there is no CLI switch for it")
    return impl


# ------------------------------------------- block autotuner (paged family)
#
# A small cached table keyed on the shape facts the best block depends on,
# with JSON persistence so one hardware sweep (scripts/tune_flash_blocks.py
# --paged --write_cache) serves every later run.


@dataclasses.dataclass(frozen=True)
class PagedBlockConfig:
    """One paged-kernel block choice: how many (scattered) pages each
    grid step fetches and scores together. More pages per step amortize
    the VMEM pipeline and grow the MXU dot; fewer skip dead context at
    finer grain (the cursor-mask block skip is block-granular)."""

    pages_per_block: int = 1

    def as_tuple(self) -> Tuple[int]:
        return (self.pages_per_block,)


# (page_size, head_dim, kv_dtype_name, backend) -> PagedBlockConfig
_PAGED_TABLE: Dict[Tuple[int, int, str, str], PagedBlockConfig] = {}
# key -> {source: sweep|online, capture, ts} provenance (ISSUE 16)
_PAGED_META: Dict[Tuple[int, int, str, str], dict] = {}
_cache_loaded = False


def _parse_cache_key(parts):
    return (int(parts[0]), int(parts[1]), parts[2], parts[3])


def _parse_cache_cfg(blocks):
    return PagedBlockConfig(*(int(x) for x in blocks))


def paged_block_cache_path() -> str:
    return default_cache_path("PAGED_BLOCKS_CACHE", "paged_blocks.json")


def _table_key(page_size: int, head_dim: int,
               kv_dtype) -> Tuple[int, int, str, str]:
    """Every float pool normalizes to 'native' (the pool stores the
    compute dtype — bf16 on chips, f32 in CPU tests; one tuned entry
    serves both because only the TPU entry is ever swept), int8 pools to
    'int8'. `paged_attention`'s default lookup applies the SAME rule, so
    writer and reader cannot disagree on the key."""
    if kv_dtype in ("int8", jnp.int8):
        name = "int8"
    else:
        name = "native"
    return (int(page_size), int(head_dim), name, jax.default_backend())


def load_paged_block_cache(path: Optional[str] = None) -> int:
    """Merge the JSON cache into the table; returns entries read.
    Garbled files are ignored (defaults still apply)."""
    return load_json_table(
        path or paged_block_cache_path(), _PAGED_TABLE,
        _parse_cache_key, _parse_cache_cfg, meta=_PAGED_META)


def save_paged_block_cache(path: Optional[str] = None) -> str:
    return save_json_table(path or paged_block_cache_path(), _PAGED_TABLE,
                           meta=_PAGED_META)


def record_online_paged_config(page_size: int, head_dim: int, kv_dtype,
                               config: PagedBlockConfig,
                               capture: Optional[str] = None,
                               force: bool = False,
                               path: Optional[str] = None) -> str:
    """Adopt an ONLINE-retuned pages_per_block: set it in-memory (the
    next dispatch reads it — a host-side table, no retrace) and persist
    it with {source: online, capture, ts} provenance (ISSUE 16).
    Refuses (ValueError) to shadow a swept cache entry without `force`."""
    from .block_cache import write_online_entry
    key = _table_key(page_size, head_dim, kv_dtype)
    out = write_online_entry(path or paged_block_cache_path(), key, config,
                             _parse_cache_key, _parse_cache_cfg,
                             capture=capture, force=force)
    _PAGED_TABLE[key] = config
    _PAGED_META[key] = {"source": "online", "capture": capture, "ts": None}
    return out


def set_paged_block_config(page_size: int, head_dim: int, kv_dtype,
                           config: PagedBlockConfig) -> None:
    _PAGED_TABLE[_table_key(page_size, head_dim, kv_dtype)] = config


def get_paged_block_config(page_size: int, head_dim: int,
                           kv_dtype=None) -> PagedBlockConfig:
    """Tuned blocks for this (page_size, head_dim, kv_dtype) on the
    current backend, defaulting to one page per step. Loads the JSON
    cache once per process (the flash table's convention)."""
    global _cache_loaded
    if not _cache_loaded:
        _cache_loaded = True
        load_paged_block_cache()
    return _PAGED_TABLE.get(_table_key(page_size, head_dim, kv_dtype),
                            PagedBlockConfig())


def autotune_paged_block_config(page_size: int, head_dim: int = 64,
                                kv_dtype=None, slots: int = 8,
                                max_pages: int = 16, kv_heads: int = 8,
                                group: int = 1,
                                sweep: Tuple[int, ...] = (1, 2, 4, 8),
                                iters: int = 20, warmup: int = 3,
                                interpret: bool = False,
                                write_cache: bool = False
                                ) -> PagedBlockConfig:
    """Time a decode dispatch (q_len=1 over a synthetic pool at the
    serving shape) per `pages_per_block` candidate on the CURRENT
    backend, record the winner in the table (and optionally the JSON
    cache). Candidates above max_pages dedupe to max_pages."""
    import time

    key = jax.random.key(0)
    num_pages = slots * max_pages
    hd, ps, kvh = head_dim, page_size, kv_heads
    quant = kv_dtype in ("int8", jnp.int8)
    if quant:
        kp = (jax.random.randint(jax.random.fold_in(key, 1),
                                 (num_pages + 1, kvh, ps, hd), -127, 127,
                                 jnp.int8),
              jnp.ones((num_pages + 1, kvh, ps), jnp.float32) * 0.02)
        vp = (jax.random.randint(jax.random.fold_in(key, 2),
                                 (num_pages + 1, kvh, ps, hd), -127, 127,
                                 jnp.int8),
              jnp.ones((num_pages + 1, kvh, ps), jnp.float32) * 0.02)
    else:
        dt = jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
        kp = jax.random.normal(jax.random.fold_in(key, 1),
                               (num_pages + 1, kvh, ps, hd), dt)
        vp = jax.random.normal(jax.random.fold_in(key, 2),
                               (num_pages + 1, kvh, ps, hd), dt)
    q = jax.random.normal(jax.random.fold_in(key, 3),
                          (slots, kvh * group, 1, hd), jnp.float32)
    tbl = jax.random.randint(jax.random.fold_in(key, 4),
                             (slots, max_pages), 0, num_pages, jnp.int32)
    cur = jnp.full((slots,), max_pages * ps - 1, jnp.int32)  # full walk

    best = None
    for n in sorted({min(n, max_pages) for n in sweep}):
        fn = jax.jit(functools.partial(
            paged_attention, page_size=ps, pages_per_block=n,
            interpret=interpret))
        try:
            for _ in range(warmup):
                jax.block_until_ready(fn(q, kp, vp, tbl, cur))
            t0 = time.perf_counter()
            for _ in range(iters):
                out = fn(q, kp, vp, tbl, cur)
            jax.block_until_ready(out)
            secs = (time.perf_counter() - t0) / iters
        except Exception:  # noqa: BLE001 — an invalid combo just loses
            continue
        if best is None or secs < best[0]:
            best = (secs, n)
    if best is None:
        raise RuntimeError(
            f"paged block autotune: every candidate failed at "
            f"page_size={page_size} hd={head_dim}")
    cfg = PagedBlockConfig(best[1])
    set_paged_block_config(page_size, head_dim, kv_dtype, cfg)
    if write_cache:
        save_paged_block_cache()
    return cfg
