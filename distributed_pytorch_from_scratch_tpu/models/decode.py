"""KV-cache autoregressive decoding — one XLA program per generation.

The reference has NO KV cache: its greedy decode re-runs the full growing
sequence through the model for every generated token
(`/root/reference/test.py:141-161`; SURVEY §7 lists "KV cache" as a
reference non-goal). This module is the TPU-native upgrade, two levels deep:

1. **KV cache**: a prefill pass over the padded prompt buffer produces
   per-layer K/V tensors; each generated token then costs a single-token
   forward against the cache — O(t) per token instead of O(t^2).
2. **On-device generation loop**: prefill + a `lax.while_loop` of
   single-token steps + greedy argmax + per-row EOS early-exit all compile
   into ONE dispatch (`make_generate`). A host-driven token loop pays a
   host->device round-trip per token; the fused loop runs at device speed
   and returns once per prompt.

Layout: caches are (num_layers, b, local_KV_heads, buf_len, head_dim),
sharded over 'tp' on the heads dim — the same head partitioning as training,
so the same checkpoint params work unchanged; under grouped-query attention
the caches are num_heads/num_kv_heads x smaller than the query-head count
(the GQA decode memory win). With a cp-sharded model (ring + contiguous
layout) the PREFILL also shards the prompt over 'cp' and runs ring
attention — long-context generation — while the per-token loop stays
replicated on the gathered caches (`_prefill_cp`).

The decoder is generic over the model FAMILY via three hooks each family
class declares (`uses_rope`, `attn_norm_key`, `ffn_norm_key`) plus duck
typing on the module dict: the gpt2 family (learned position embeddings
added at the input, LayerNorm, gelu MLP, TIED lm_head) decodes through the
same prefill + fused-loop machinery as llama (VERDICT r2 #6). Families with
learned positions expose `max_decode_positions`; the buffer must fit it.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..config import resolve_dtype
from ..ops.attention import MASK_VALUE, causal_attention
from ..ops.collectives import gather_from, ring_permute
from ..ops.quant import quantize_rows
from ..ops.ring_attention import _BIG_NEG, _block_attn_xla, ring_attention
from ..ops.rope import apply_rotary, rope_tables
from .transformer import NEG_INF, Transformer

Params = Dict[str, Any]


def require_decodable(model) -> None:
    """The decoder reads a family's `wq`/`wk`/`wv` by name and keeps k and v
    per head in its caches; a family that says `decodable = False` (latent
    attention, whose cache would hold the latents) is refused here, by
    every entry point of this file and by the serving engines."""
    if not getattr(model, "decodable", True):
        raise ValueError(
            f"the {type(model).__name__} family cannot be decoded or served "
            f"yet: models/decode.py and serving/engine.py cache k and v per "
            f"head from wq/wk/wv, and this family's attention is latent "
            f"(ROADMAP: serving with latent pages). It trains "
            f"(train.py, training/train_step.py) and evaluates its loss.")


def _qkv(model: Transformer, lp: Params, y: jax.Array, dtype):
    """Project y (b, t, d) -> q (b, local_heads, t, hd) and k, v at
    (b, local_KV_heads, t, hd).

    Under grouped-query attention k/v stay at the (smaller) kv-head count —
    the caches then hold kv_heads entries, which is the GQA decode memory
    win (num_heads/num_kv_heads x smaller KV cache). Query head i reads kv
    head i // group, matching training's `jnp.repeat(k, group, axis=1)`
    layout (models/transformer.py)."""
    m = model._mods
    b, t, _ = y.shape
    h = model.cfg.head_dim
    split = lambda z, nh: z.reshape(b, t, nh, h).transpose(0, 2, 1, 3)
    q = split(m["wq"].apply(lp["wq"], y, dtype), model.num_local_heads)
    k = split(m["wk"].apply(lp["wk"], y, dtype), model.num_local_kv_heads)
    v = split(m["wv"].apply(lp["wv"], y, dtype), model.num_local_kv_heads)
    return q, k, v


def _embed(model, params: Params, ids: jax.Array, pos: jax.Array, dtype):
    """Token embedding (+ the learned position embedding for families
    without RoPE — gpt2's positions enter HERE, mirroring
    `GPT2Transformer.forward_shard`)."""
    x = model.embedding.apply(params["embedding"], ids)
    if not model.uses_rope:
        x = x + jnp.take(params["pos_embedding"]["weight"], pos, axis=0,
                         mode="clip")
    return x.astype(dtype)


def _finish_block(model: Transformer, lp: Params, x: jax.Array,
                  o: jax.Array, dtype) -> jax.Array:
    """Residual + wo, then the FFN sublayer (shared by prefill and decode)."""
    m = model._mods
    b, t = x.shape[0], x.shape[1]
    o = o.transpose(0, 2, 1, 3).reshape(b, t, model.num_local_heads * model.cfg.head_dim)
    x = x + m["wo"].apply(lp["wo"], o, dtype)
    nk = model.ffn_norm_key
    y = m[nk].apply(lp[nk], x)
    if model.is_moe:
        ff, _ = m["moe"].apply(lp["moe"], y, dtype)  # aux unused at decode
        # Decode replicates the batch over 'ep' (in_specs P(None, None))
        # while expert weights stay ep-sharded, so every ep shard computes
        # the same ff values under an ep-varying vma tag. pmean averages
        # the identical copies: value-identity, clears the tag so the scan
        # carry and the P(None, None) out_specs stay ep-invariant.
        return x + lax.pmean(ff, "ep")
    if "fc" in m:  # gpt2 family: gelu MLP
        h = jax.nn.gelu(m["fc"].apply(lp["fc"], y, dtype), approximate=True)
        return x + m["proj"].apply(lp["proj"], h, dtype)
    g = m["gate_proj"].apply(lp["gate_proj"], y, dtype)
    u = m["up_proj"].apply(lp["up_proj"], y, dtype)
    return x + m["down_proj"].apply(lp["down_proj"], jax.nn.silu(g) * u, dtype)


def _logits_tokens(model: Transformer, params: Params, x: jax.Array,
                   dtype) -> jax.Array:
    """Final norm + head on (b, t, d); returns the LOCAL vocab shard
    (b, t, vocab_padded/tp) with padded columns masked (mirrors
    forward_shard). A family that says `tied_head` ties the head to the
    vocab-parallel token embedding (gpt2) — same local-logits layout either
    way. t = 1 is the single-position decode step; the speculative verify
    step asks for all k+1 positions at once."""
    x = model.final_norm.apply(params["norm"], x)
    if not model.tied_head:
        logits = model.lm_head.apply(params["lm_head"], x, dtype)
    else:
        w = params["embedding"]["weight"].astype(dtype)   # (vp/tp, d)
        logits = x.astype(dtype) @ w.T
    if model.vocab_padded != model.cfg.vocab_size:
        local_v = logits.shape[-1]
        start = lax.axis_index("tp") * local_v
        col = start + jnp.arange(local_v)
        logits = jnp.where(col[None, None, :] < model.cfg.vocab_size, logits,
                           jnp.asarray(NEG_INF, logits.dtype))
    return logits


def _logits_last(model: Transformer, params: Params, x_last: jax.Array,
                 dtype) -> jax.Array:
    """`_logits_tokens` at t = 1: (b, 1, d) -> (b, vocab_padded/tp)."""
    return _logits_tokens(model, params, x_last, dtype)[:, 0, :]


def _prefill(model: Transformer, params: Params, buf: jax.Array,
             prompt_len: jax.Array, cos_t, sin_t, dtype):
    """Causal full-buffer forward: returns (ks, vs) stacked per layer and the
    PER-ROW logits at position prompt_len[i]-1 (prompt_len: (b,)). Same
    `causal_attention` kernel as training (flash on TPU). K/V of positions
    >= prompt_len hold padding — they are re-written by decode steps before
    any query can attend to them."""
    b, t = buf.shape
    pos = jnp.tile(jnp.arange(t, dtype=jnp.int32)[None, :], (b, 1))
    x = _embed(model, params, buf, pos, dtype)
    if model.uses_rope:
        cos = jnp.take(cos_t, pos, axis=0, mode="clip")
        sin = jnp.take(sin_t, pos, axis=0, mode="clip")

    def body(x, lp):
        nk = model.attn_norm_key
        y = model._mods[nk].apply(lp[nk], x)
        q, k, v = _qkv(model, lp, y, dtype)
        if model.uses_rope:
            q, k = apply_rotary(q, k, cos, sin)
        # grouped k/v pass straight through: every causal_attention impl
        # routes query-head groups onto the kv heads itself (ops/attention.py)
        o = causal_attention(q, k, v, impl=model.attn_impl)
        x = _finish_block(model, lp, x, o, dtype)
        return x, (k, v)  # caches stay at kv_heads (see _qkv)

    x, (ks, vs) = lax.scan(body, x, params["layers"])
    last = jnp.take_along_axis(
        x, (prompt_len - 1)[:, None, None].astype(jnp.int32), axis=1)
    return ks.astype(dtype), vs.astype(dtype), _logits_last(model, params, last, dtype)


def _prefill_cp(model: Transformer, params: Params, buf: jax.Array,
                prompt_len: jax.Array, cos_t, sin_t, dtype):
    """Context-parallel prefill: the buffer's sequence dim shards over the
    'cp' mesh axis (contiguous chunks) and every layer's attention runs the
    ring (`ops/ring_attention.ring_attention`) — the same long-context path
    training uses, so a prompt far longer than one chip's O(t^2) budget
    prefills across the cp group. The per-layer K/V chunks are then
    `lax.all_gather`ed back to full length: the decode LOOP stays
    replicated over cp (each single-token step is cheap and identical on
    every shard), which keeps cache-write indexing trivial while the
    quadratic prefill work and its activations split cp-ways.

    `buf` here is the REPLICATED (b, buf_len) buffer; each shard slices its
    contiguous chunk by `axis_index('cp')`. Returns full-length (ks, vs)
    and the per-row logits at prompt_len-1, exactly like `_prefill` — the
    outputs are cp-INVARIANT (the chunk psum below clears the tag), so
    the caller's decode loop runs unchanged."""
    b, t = buf.shape
    cp = lax.axis_size("cp")
    tl = t // cp
    i = lax.axis_index("cp")
    local = lax.dynamic_slice_in_dim(buf, i * tl, tl, axis=1)
    pos = i * tl + jnp.tile(jnp.arange(tl, dtype=jnp.int32)[None, :], (b, 1))
    x = _embed(model, params, local, pos, dtype)
    if model.uses_rope:
        cos = jnp.take(cos_t, pos, axis=0, mode="clip")
        sin = jnp.take(sin_t, pos, axis=0, mode="clip")

    def body(x, lp):
        nk = model.attn_norm_key
        y = model._mods[nk].apply(lp[nk], x)
        q, k, v = _qkv(model, lp, y, dtype)
        if model.uses_rope:
            q, k = apply_rotary(q, k, cos, sin)
        o = ring_attention(q, k, v, q_pos=pos, axis="cp",
                           impl=model.attn_impl).astype(x.dtype)
        x = _finish_block(model, lp, x, o, dtype)
        return x, (k, v)

    x, (ks, vs) = lax.scan(body, x, params["layers"])

    # Chunks -> full length in ONE collective that also clears the
    # cp-varying tag: each shard scatters its chunk into a zeros
    # full-length buffer and the psum of the disjoint chunks IS the
    # concatenation (psum output is cp-invariant, so the decode loop
    # below runs identically on every shard with no extra casts).
    def to_full(z, seq_axis):
        shape = z.shape[:seq_axis] + (t,) + z.shape[seq_axis + 1:]
        full = lax.dynamic_update_slice_in_dim(
            jnp.zeros(shape, z.dtype), z, i * tl, axis=seq_axis)
        return lax.psum(full, "cp")

    ks = to_full(ks, 3)                      # (L, b, kvh, t, hd)
    vs = to_full(vs, 3)
    # The logits need ONE position per row (prompt_len-1): the shard whose
    # chunk holds it contributes the (b, 1, d) slice and the psum selects
    # it — no full-length (b, t, d) gather on the long-context path.
    idx = (prompt_len - 1).astype(jnp.int32)             # (b,) global
    in_chunk = (idx >= i * tl) & (idx < (i + 1) * tl)    # (b,)
    sel = jnp.take_along_axis(
        x, jnp.clip(idx - i * tl, 0, tl - 1)[:, None, None], axis=1)
    last = lax.psum(jnp.where(in_chunk[:, None, None], sel, 0), "cp")
    return ks.astype(dtype), vs.astype(dtype), _logits_last(
        model, params, last, dtype)


def _decode_one(model: Transformer, params: Params, cache_k, cache_v,
                token: jax.Array, cur: jax.Array, buf_len: int,
                cos_t, sin_t, dtype):
    """One single-token step: writes each row's token K/V into the caches at
    that row's position, attends over cache[0..cur_row], returns
    (k', v', logits).

    `cur` may be a scalar (the fused whole-generation loop's shared cursor)
    or a (b,) vector (the serving engine's per-slot cursors — every live
    slot sits at its own position). Per-row math is identical either way:
    the scalar case is just the broadcast vector, so both drivers share
    this one lowering."""
    b = token.shape[0]
    shared_cur = jnp.ndim(cur) == 0   # static: the fused loop's scalar case
    cur_scalar = cur
    cur = jnp.broadcast_to(jnp.asarray(cur, jnp.int32), (b,))
    p1 = cur[:, None]
    x = _embed(model, params, token[:, None], p1, dtype)
    if model.uses_rope:
        cos = jnp.take(cos_t, p1, axis=0, mode="clip")
        sin = jnp.take(sin_t, p1, axis=0, mode="clip")
    visible = (jnp.arange(buf_len)[None, :] <= cur[:, None])[:, None, None, :]
    rows = jnp.arange(b)

    def write_cache(cache, z):
        # per-row scatter (row i writes position cur[i]); a SHARED scalar
        # cursor keeps the old dynamic-update-slice lowering — cheaper on
        # TPU than trusting XLA to pattern-match the all-equal scatter —
        # with identical written values either way
        if shared_cur:
            return lax.dynamic_update_slice_in_dim(
                cache, z.astype(cache.dtype), cur_scalar, axis=2)
        return cache.at[rows, :, cur, :].set(z[:, :, 0, :].astype(cache.dtype))

    def body(x, layer_in):
        lp, k_cache, v_cache = layer_in
        nk = model.attn_norm_key
        y = model._mods[nk].apply(lp[nk], x)
        q, k, v = _qkv(model, lp, y, dtype)   # q: (b, h, 1, hd); kv: kvh
        if model.uses_rope:
            q, k = apply_rotary(q, k, cos, sin)
        k_cache = write_cache(k_cache, k)
        v_cache = write_cache(v_cache, v)
        # grouped attention against the kv-head caches: query head
        # kv_idx*g + g_idx reads kv head kv_idx (g == 1 reduces to plain
        # MHA — the reshapes are identities)
        kvh = model.num_local_kv_heads
        g = model.num_local_heads // kvh
        hd = model.cfg.head_dim
        qg = q[:, :, 0, :].reshape(b, kvh, g, hd)
        s = jnp.einsum("bkgd,bktd->bkgt", qg, k_cache,
                       preferred_element_type=jnp.float32)
        s = s / jnp.sqrt(jnp.asarray(hd, jnp.float32))
        s = jnp.where(visible, s, MASK_VALUE)
        p = jax.nn.softmax(s, axis=-1).astype(dtype)
        o = jnp.einsum("bkgt,bktd->bkgd", p, v_cache)
        o = o.reshape(b, kvh * g, hd)[:, :, None, :]   # (b, h, 1, hd)
        x = _finish_block(model, lp, x, o, dtype)
        return x, (k_cache, v_cache)

    x, (k_new, v_new) = lax.scan(body, x, (params["layers"], cache_k, cache_v))
    return k_new, v_new, _logits_last(model, params, x, dtype)


def _paged_cache_write(cache, zi, dst_page, dst_off):
    """Scatter head-vectors into a page-pool layer slice. `zi` is shaped
    like the advanced-index result of `cache[dst_page, :, dst_off]` —
    (b, kvh, hd) for the single-token step, (b, cw, kvh, hd) for a chunk.

    A quantized pool arrives as a (codes int8, scales f32) tuple: the
    incoming vectors quantize HERE (one symmetric scale per head-vector,
    ops/quant.quantize_rows) and codes + scales scatter through the same
    index maps — append-only, so no earlier position ever requantizes."""
    if isinstance(cache, tuple):
        codes, sc = cache
        q, s = quantize_rows(zi)
        return (codes.at[dst_page, :, dst_off, :].set(q),
                sc.at[dst_page, :, dst_off].set(s))
    return cache.at[dst_page, :, dst_off, :].set(zi.astype(cache.dtype))


def _gather_page_view(cache, page_tbl: jax.Array, dtype) -> jax.Array:
    """Page pool layer slice (pages, kvh, page, hd) + per-row page lists
    (b, max_pages) -> the dense logical cache view (b, kvh, max_pages*page,
    hd) the attention einsums consume.

    The gathered view is VALUE-identical to a slot-granular cache row at
    every position a request has written (pages hold exactly the K/V the
    prefill/decode scatters put there); positions beyond the cursor gather
    whatever the mapped page holds (a freshly allocated page's zeros, the
    scratch page, or a COW donor's later tokens) — all finite, all masked
    to exact-zero attention weight before anything reads them, the same
    garbage-flows-only-into-garbage argument as the slot engine's free
    rows.

    A quantized pool (codes, scales) dequantizes INSIDE the gather — the
    attend math downstream is byte-for-byte the same einsum block, only
    the view operand changed (kv_dtype='int8', ISSUE 8).

    This materialized copy is the paged decode path's HBM floor, and
    since ISSUE 14 it is the ORACLE impl (`paged_attn_impl='gather'`):
    `ops.pallas.paged_attention` attends over the paged layout in place
    — same tokens, no dense view — and is what a TPU serving config
    should run (`--paged_attn pallas`)."""
    b, mp = page_tbl.shape
    if isinstance(cache, tuple):
        codes, sc = cache
        _, kvh, ps, hd = codes.shape
        view = (codes[page_tbl].astype(jnp.float32)
                * sc[page_tbl][..., None]).astype(dtype)
    else:
        _, kvh, ps, hd = cache.shape
        view = cache[page_tbl]                  # (b, mp, kvh, ps, hd)
    return view.transpose(0, 2, 1, 3, 4).reshape(b, kvh, mp * ps, hd)


def _cp_pool_view(pool_k, page_tbl, page_size: int, cp: int):
    """This cp rank's slice of the paged world (call inside shard_map with
    a cp-sharded pool, ISSUE 18): the rank's `max_pages/cp` page-table
    columns translated to LOCAL pool indices, the global position of its
    first column, and the local real-page count.

    Layout (kv_manager.PagedKVPool, cp > 1): page-table column j belongs
    to rank j // (max_pages/cp) — contiguous position spans — and rank r's
    local pool slab holds global pages [r*ppr, (r+1)*ppr) plus one local
    scratch at index ppr; any id the rank does not own translates to that
    scratch (`local_page_ids`), which visibility masks to zero weight."""
    from ..serving.kv_manager import local_page_ids

    mp = page_tbl.shape[1]
    mpp = mp // cp                     # page-table columns per rank
    ppr = (pool_k[0] if isinstance(pool_k, tuple)
           else pool_k).shape[1] - 1   # local real pages (+1 = scratch)
    r = lax.axis_index("cp")
    tbl_r = lax.dynamic_slice_in_dim(page_tbl, r * mpp, mpp, axis=1)
    to_local = lambda ids: local_page_ids(ids, ppr)
    base = r * (mpp * page_size)       # global position of local column 0
    return to_local(tbl_r), base, to_local


def _cp_combine(o, lse, axis: str = "cp"):
    """Merge per-rank partial attention (o f32-normalized within the rank,
    lse over the rank's visible scores) into the exact global softmax —
    ONE pmax + two psums of decode-step-sized tensors, never pages.

    o_r = acc_r / l_r and lse_r = m_r + log l_r give
    sum_r o_r * exp(lse_r - m) / sum_r exp(lse_r - m)
      = sum_r acc_r * exp(m_r) / sum_r l_r * exp(m_r): the single-pool
    softmax bit for bit up to float reassociation. Dead ranks (lse at the
    -1e30 sentinel) underflow to exactly zero weight; an all-dead row
    (free slot) returns 0 like the cp=1 path. The psum outputs are
    cp-invariant, so the caller's residual stream stays replicated."""
    m = lax.pmax(lse, axis)
    w = jnp.exp(lse - m)               # all-dead rows: w = 1 on every rank
    denom = lax.psum(w, axis)
    return lax.psum(o * w[..., None], axis) / denom[..., None]


def _paged_decode_one(model: Transformer, params: Params, pool_k, pool_v,
                      token: jax.Array, cur: jax.Array, page_tbl: jax.Array,
                      page_size: int, cos_t, sin_t, dtype,
                      attn_impl: str = "gather",
                      attn_interpret: bool = False, cp: int = 1):
    """`_decode_one` through a page table: one single-token step where each
    row's K/V write lands in the PAGE mapped for its cursor position
    (pool.at[page, :, offset, :]) and the attention reads the row's page
    list. Two attend impls, token-identical by contract:

    * `attn_impl='gather'` (the oracle): materialize the dense logical
      view (`_gather_page_view`) and run the same einsum block
      `_decode_one` lowers — MASK_VALUE mask, f32 scores.
    * `attn_impl='pallas'` (ISSUE 14): `ops.pallas.paged_attention` walks
      the page table in place — per-row cursor masking, online softmax
      across page blocks, int8 dequant fused into the block loop — so the
      per-step HBM copy of every slot's whole context never happens.
      `attn_interpret` runs the kernel under the Pallas interpreter (the
      CPU identity tests); callers resolve the impl up front via
      `ops.pallas.paged_attention.check_paged_attn_impl`.

    pool_k/pool_v: (L, num_pages+1, kvh, page_size, hd); page_tbl:
    (b, max_pages) int32 page ids (free rows map every entry at the scratch
    page, whose content is never attended).

    `cp > 1` (ISSUE 18): the pool is page-sharded over the 'cp' mesh axis
    (kv_manager.CP_POOL_SPEC) and this function runs per-rank inside the
    engine's shard_map. Each rank writes the token's K/V only if it owns
    the cursor's page (everyone else scatters to their LOCAL scratch),
    attends over its own `max_pages/cp` page-table columns with the rank's
    global base as `pos_offset`, and the per-rank partial (out, lse) pairs
    merge through `_cp_combine` — the step's only cp collective is that
    decode-sized reduction; page data never moves."""
    b = token.shape[0]
    mp = page_tbl.shape[1]
    buf_len = mp * page_size
    cur = jnp.asarray(cur, jnp.int32)
    p1 = cur[:, None]
    x = _embed(model, params, token[:, None], p1, dtype)
    if model.uses_rope:
        cos = jnp.take(cos_t, p1, axis=0, mode="clip")
        sin = jnp.take(sin_t, p1, axis=0, mode="clip")
    visible = (jnp.arange(buf_len)[None, :] <= cur[:, None])[:, None, None, :]
    rows = jnp.arange(b)
    # the physical destination of each row's write: its cursor's page + the
    # offset inside that page (free rows' tables aim at the scratch page)
    dst_page = page_tbl[rows, cur // page_size]        # (b,)
    dst_off = cur % page_size                          # (b,)
    if cp > 1:
        tbl_cp, base_cp, to_local = _cp_pool_view(pool_k, page_tbl,
                                                  page_size, cp)
        # rows whose cursor page lives on another rank write their token's
        # K/V to the local scratch — exactly one rank lands the real write
        dst_page = to_local(dst_page)
        t_cp = tbl_cp.shape[1] * page_size
        kv_pos_cp = jnp.broadcast_to(
            base_cp + jnp.arange(t_cp, dtype=jnp.int32), (b, t_cp))

    def write_cache(cache, z):
        # per-row scatter into the page pool (row i writes page dst_page[i]
        # at offset dst_off[i]); duplicate scratch targets are harmless —
        # the scratch page is never read. Quantized pools code the vector
        # on the way in (_paged_cache_write).
        return _paged_cache_write(cache, z[:, :, 0, :], dst_page, dst_off)

    def body(x, layer_in):
        lp, k_cache, v_cache = layer_in
        nk = model.attn_norm_key
        y = model._mods[nk].apply(lp[nk], x)
        q, k, v = _qkv(model, lp, y, dtype)   # q: (b, h, 1, hd); kv: kvh
        if model.uses_rope:
            q, k = apply_rotary(q, k, cos, sin)
        k_cache = write_cache(k_cache, k)
        v_cache = write_cache(v_cache, v)
        if attn_impl == "pallas":
            # walk the page table in place (writes above land in the pool
            # first, so the pending token is visible like the gather path)
            from ..ops.pallas.paged_attention import paged_attention
            if cp > 1:
                # local columns only; pos_offset anchors this rank's pages
                # at their global positions, so the kernel's causal mask and
                # block-skip logic run unchanged against the local slab
                o, olse = paged_attention(q, k_cache, v_cache, tbl_cp, cur,
                                          page_size=page_size,
                                          pos_offset=base_cp,
                                          return_lse=True,
                                          interpret=attn_interpret)
                o = _cp_combine(o.astype(jnp.float32), olse).astype(dtype)
            else:
                o = paged_attention(q, k_cache, v_cache, page_tbl, cur,
                                    page_size=page_size,
                                    interpret=attn_interpret).astype(dtype)
            x = _finish_block(model, lp, x, o, dtype)
            return x, (k_cache, v_cache)
        if cp > 1:
            # per-rank partial over the local gathered view; the causal
            # mask is positional (kv_pos carries the global base), dead
            # ranks (cursor before their span) emit the lse sentinel and
            # vanish in the combine
            k_view = _gather_page_view(k_cache, tbl_cp, dtype)
            v_view = _gather_page_view(v_cache, tbl_cp, dtype)
            o, olse = _block_attn_xla(q, k_view, v_view, cur[:, None],
                                      kv_pos_cp,
                                      model.cfg.head_dim ** -0.5)
            o = _cp_combine(o, olse).astype(dtype)     # (b, h, 1, hd)
            x = _finish_block(model, lp, x, o, dtype)
            return x, (k_cache, v_cache)
        k_view = _gather_page_view(k_cache, page_tbl, dtype)
        v_view = _gather_page_view(v_cache, page_tbl, dtype)
        # identical attend block to _decode_one (same einsums, same mask,
        # same f32 scores) — only the cache OPERAND is gathered, not sliced
        kvh = model.num_local_kv_heads
        g = model.num_local_heads // kvh
        hd = model.cfg.head_dim
        qg = q[:, :, 0, :].reshape(b, kvh, g, hd)
        s = jnp.einsum("bkgd,bktd->bkgt", qg, k_view,
                       preferred_element_type=jnp.float32)
        s = s / jnp.sqrt(jnp.asarray(hd, jnp.float32))
        s = jnp.where(visible, s, MASK_VALUE)
        p = jax.nn.softmax(s, axis=-1).astype(dtype)
        o = jnp.einsum("bkgt,bktd->bkgd", p, v_view)
        o = o.reshape(b, kvh * g, hd)[:, :, None, :]   # (b, h, 1, hd)
        x = _finish_block(model, lp, x, o, dtype)
        return x, (k_cache, v_cache)

    x, (k_new, v_new) = lax.scan(body, x, (params["layers"], pool_k, pool_v))
    return k_new, v_new, _logits_last(model, params, x, dtype)


def _cp_ring_attend(q, k_cache, v_cache, tbl_cp, base_cp, kv_pos_cp,
                    start, qlen, pos, page_size: int, cp: int, dtype,
                    attn_impl: str, attn_interpret: bool):
    """Ring the chunk's QUERIES around the cp axis over a page-sharded pool
    (one layer's attend inside `_paged_prefill_chunk`, ISSUE 18).

    Rank r starts with sub-block r of the chunk (cw/cp queries) and walks
    cp hops: attend the carried sub-block against the rank's LOCAL pages —
    partial out f32-normalized within the hop plus its lse — merge into the
    carry by the logaddexp recurrence (ops/ring_attention.block_into), and
    collective-permute the carry (queries, their global positions, out,
    lse, chunk offset) one rank forward. After cp hops every sub-block has
    visited every slab; a position-scatter + psum('cp') reassembles the
    full (b, h, cw, hd) output, cp-invariant for the replicated residual
    stream. Communication: (cp-1) ppermute hops of sub-block-sized carry +
    one chunk-sized psum — pages never move.

    Dead hops (no local position visible to a query) emit the -1e30 lse
    sentinel and merge at exactly zero weight; a query dead on EVERY hop is
    a pad column (>= qlen), whose finite garbage flows only into pad
    logits, same as the cp=1 chunk."""
    b, h, cw, hd = q.shape
    cws = cw // cp
    r = lax.axis_index("cp")
    off = jnp.asarray(r * cws, jnp.int32)[None]          # (1,) carried
    qh = lax.dynamic_slice_in_dim(q, r * cws, cws, axis=2)
    qph = lax.dynamic_slice_in_dim(pos, r * cws, cws, axis=1)
    zero = qh.astype(jnp.float32).sum() * 0.0            # cp-varying 0
    o = jnp.zeros((b, h, cws, hd), jnp.float32) + zero
    lse = jnp.full((b, h, cws), _BIG_NEG, jnp.float32) + zero
    if attn_impl != "pallas":
        k_view = _gather_page_view(k_cache, tbl_cp, dtype)
        v_view = _gather_page_view(v_cache, tbl_cp, dtype)
    for hop in range(cp):
        if attn_impl == "pallas":
            from ..ops.pallas.paged_attention import paged_attention
            # the carried sub-block's queries sit at chunk offset off:
            # global start start+off, per-row real length qlen-off (clipped
            # to the sub-block); dead rows surface the lse sentinel
            bo, blse = paged_attention(
                qh, k_cache, v_cache, tbl_cp, start + off[0],
                page_size=page_size,
                qlen=jnp.clip(qlen - off[0], 0, cws),
                pos_offset=base_cp, return_lse=True,
                interpret=attn_interpret)
            bo = bo.astype(jnp.float32)
        else:
            bo, blse = _block_attn_xla(qh, k_view, v_view, qph, kv_pos_cp,
                                       hd ** -0.5)
        lse_new = jnp.logaddexp(lse, blse)
        o = (o * jnp.exp(lse - lse_new)[..., None]
             + bo * jnp.exp(blse - lse_new)[..., None])
        lse = lse_new
        if hop < cp - 1:
            qh, qph, o, lse, off = [ring_permute(t, "cp")
                                    for t in (qh, qph, o, lse, off)]
    # rank r now holds sub-block (r - cp + 1) mod cp fully attended; put
    # every sub-block back at its chunk offset and sum the disjoint slots
    full = jnp.zeros((b, h, cw, hd), jnp.float32) + zero
    full = lax.dynamic_update_slice_in_dim(full, o, off[0], axis=2)
    return lax.psum(full, "cp")


def _paged_prefill_chunk(model: Transformer, params: Params, pool_k, pool_v,
                         chunk: jax.Array, start: jax.Array,
                         qlen: jax.Array, page_tbl: jax.Array,
                         dst_page: jax.Array, dst_off: jax.Array,
                         page_size: int, cos_t, sin_t, dtype,
                         all_logits: bool = False,
                         attn_impl: str = "gather",
                         attn_interpret: bool = False, cp: int = 1):
    """One CHUNK of an incremental prefill: process `chunk` (b, cw) tokens
    occupying absolute positions start..start+qlen-1 (columns >= qlen are
    pad), write their K/V into the pages `dst_page`/`dst_off` (b, cw) map
    (pad columns aim at the scratch page), and attend each chunk query over
    the row's FULL gathered page view — prior chunks, a COW-shared prefix
    prefilled by another request, and the chunk's own earlier positions all
    arrive through the same page table. Returns the per-row logits at the
    chunk's LAST real position (qlen-1), which for the final chunk of a
    prompt are the first-token sampling logits.

    This is `_paged_decode_one` generalised from 1 query to cw queries:
    position p's activations depend only on positions <= p (causality), so
    chunk-at-a-time prefill is value-identical to the whole-buffer
    `_prefill` — chunking changes cost and stall, never tokens.

    `all_logits=True` (build-time) returns the logits at EVERY chunk
    position (b, cw, local_v) instead of only the last — the speculative
    VERIFY step (serving/speculative.py): the target model scores all k+1
    draft positions in this one dispatch, each row starting at its own
    cursor (`start` is per-row), with page growth/COW already resolved by
    the host through the same `dst_page`/`dst_off` maps a prefill chunk
    uses.

    `cp > 1` (ISSUE 18): the pool is page-sharded over 'cp' and the chunk's
    QUERIES ring around the cp axis instead of the pages. Every rank runs
    the full-chunk qkv/norm/MLP math replicated (no collectives — the
    residual stream stays cp-invariant), writes only the K/V of chunk
    columns whose destination pages it owns (the rest aim at the local
    scratch), then splits the chunk into cp sub-blocks of cw/cp queries:
    rank r starts with sub-block r, attends it against its LOCAL pages
    (online-softmax partial + lse), and collective-permutes the carry
    (queries, positions, partial out, lse, offset) one rank forward, cp
    hops total. Each hop's attend covers cw/cp queries x T/cp keys, so the
    per-rank attend FLOPs are 1/cp of the dense chunk attend — the
    long-prompt full-mesh-FLOPs win. The hop merge is the same logaddexp
    recurrence ring_attention uses; a final position-scatter + psum
    reassembles the full (b, h, cw, hd) output replicated, bit-for-bit the
    single-pool softmax up to float reassociation. Requires cw % cp == 0
    (the engine rounds chunk widths up to a cp multiple)."""
    b, cw = chunk.shape
    mp = page_tbl.shape[1]
    buf_len = mp * page_size
    pos = start[:, None] + jnp.arange(cw, dtype=jnp.int32)[None, :]  # (b, cw)
    x = _embed(model, params, chunk, pos, dtype)
    if model.uses_rope:
        cos = jnp.take(cos_t, pos, axis=0, mode="clip")
        sin = jnp.take(sin_t, pos, axis=0, mode="clip")
    # query at (row, i) sees cache position t iff t <= start[row] + i;
    # everything later (incl. garbage pages) masks to exact-zero weight
    visible = (jnp.arange(buf_len)[None, None, :]
               <= pos[:, :, None])[:, None, None, :, :]  # (b,1,1,cw,T)
    if cp > 1:
        if cw % cp:
            raise ValueError(f"cp prefill needs chunk width {cw} divisible "
                             f"by cp={cp}")
        cws = cw // cp
        tbl_cp, base_cp, to_local = _cp_pool_view(pool_k, page_tbl,
                                                  page_size, cp)
        dst_page = to_local(dst_page)       # non-owned columns -> scratch
        t_cp = tbl_cp.shape[1] * page_size
        kv_pos_cp = jnp.broadcast_to(
            base_cp + jnp.arange(t_cp, dtype=jnp.int32), (b, t_cp))

    def write_cache(cache, z):
        # z: (b, kvh, cw, hd) -> scatter token i of row r to
        # cache[dst_page[r, i], :, dst_off[r, i], :] (quantized pools code
        # each head-vector on the way in)
        return _paged_cache_write(cache, z.transpose(0, 2, 1, 3),
                                  dst_page, dst_off)

    def body(x, layer_in):
        lp, k_cache, v_cache = layer_in
        nk = model.attn_norm_key
        y = model._mods[nk].apply(lp[nk], x)
        q, k, v = _qkv(model, lp, y, dtype)   # q: (b, h, cw, hd)
        if model.uses_rope:
            q, k = apply_rotary(q, k, cos, sin)
        k_cache = write_cache(k_cache, k)
        v_cache = write_cache(v_cache, v)
        if cp > 1:
            o = _cp_ring_attend(q, k_cache, v_cache, tbl_cp, base_cp,
                                kv_pos_cp, start, qlen, pos, page_size,
                                cp, dtype, attn_impl, attn_interpret)
            x = _finish_block(model, lp, x, o.astype(dtype), dtype)
            return x, (k_cache, v_cache)
        if attn_impl == "pallas":
            # the chunk's own K/V are in the pool (writes above), so the
            # kernel's start+i causality reproduces `visible` exactly;
            # pad columns (>= qlen) stay garbage-into-garbage like the
            # gather path, and their page walk is skipped
            from ..ops.pallas.paged_attention import paged_attention
            o = paged_attention(q, k_cache, v_cache, page_tbl, start,
                                page_size=page_size, qlen=qlen,
                                interpret=attn_interpret).astype(dtype)
            x = _finish_block(model, lp, x, o, dtype)
            return x, (k_cache, v_cache)
        k_view = _gather_page_view(k_cache, page_tbl, dtype)
        v_view = _gather_page_view(v_cache, page_tbl, dtype)
        kvh = model.num_local_kv_heads
        g = model.num_local_heads // kvh
        hd = model.cfg.head_dim
        qg = q.reshape(b, kvh, g, cw, hd)
        s = jnp.einsum("bkgqd,bktd->bkgqt", qg, k_view,
                       preferred_element_type=jnp.float32)
        s = s / jnp.sqrt(jnp.asarray(hd, jnp.float32))
        s = jnp.where(visible, s, MASK_VALUE)
        p = jax.nn.softmax(s, axis=-1).astype(dtype)
        o = jnp.einsum("bkgqt,bktd->bkgqd", p, v_view)
        o = o.reshape(b, kvh * g, cw, hd)
        x = _finish_block(model, lp, x, o, dtype)
        return x, (k_cache, v_cache)

    x, (k_new, v_new) = lax.scan(body, x, (params["layers"], pool_k, pool_v))
    if all_logits:
        return k_new, v_new, _logits_tokens(model, params, x, dtype)
    last = jnp.take_along_axis(
        x, jnp.maximum(qlen - 1, 0)[:, None, None].astype(jnp.int32), axis=1)
    return k_new, v_new, _logits_last(model, params, last, dtype)


def validate_sampling(cfg, temperature: float, top_k: int,
                      top_p: float) -> None:
    """Build-time sampling-knob validation shared by `make_generate` and the
    serving engine (serving/engine.py) — one contract, one error text."""
    if temperature < 0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")
    if top_k < 0 or top_k > cfg.vocab_size:
        raise ValueError(f"top_k must be in [0, vocab_size], got {top_k}")
    if not 0.0 <= top_p <= 1.0:
        raise ValueError(f"top_p must be in [0, 1] (0 = off), got {top_p}")


def _full_vocab_logits(model: Transformer, logits: jax.Array) -> jax.Array:
    """Local vocab-shard logits -> full (..., vocab_size) f32 logits
    (gathers the tp shards along the LAST dim; every shard holds the same
    values afterwards). Works on the (b, local_v) single-position case and
    the verify step's (b, k+1, local_v) block alike."""
    full = gather_from(logits.astype(jnp.float32), "tp")
    return full[..., : model.cfg.vocab_size]


def _filter_logits(scaled: jax.Array, top_k: int, top_p: float) -> jax.Array:
    """top-k then top-p (nucleus) filtering on temperature-scaled logits;
    filtered-out entries become -inf. Both filters compose: top-k prunes
    first, then top-p."""
    if top_k:
        # kth-largest threshold via top_k, not a full V-sort — this runs
        # once per generated token
        kth = lax.top_k(scaled, top_k)[0][:, -1][:, None]
        scaled = jnp.where(scaled >= kth, scaled, -jnp.inf)
    if top_p and top_p < 1.0:
        # nucleus: keep the smallest descending-prob prefix whose mass
        # reaches top_p (the top token always survives: its own
        # exclusive-cumsum is 0 < top_p)
        sorted_l = jnp.sort(scaled, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_l, axis=-1)
        cum = jnp.cumsum(probs, axis=-1) - probs  # exclusive
        keep = cum < top_p                        # (b, V) sorted
        # threshold = smallest kept logit, mapped back to the unsorted
        # layout by value comparison
        thresh = jnp.min(jnp.where(keep, sorted_l, jnp.inf), axis=-1,
                         keepdims=True)
        scaled = jnp.where(scaled >= thresh, scaled, -jnp.inf)
    return scaled


def make_token_sampler(model: Transformer, temperature: float = 0.0,
                       top_k: int = 0, top_p: float = 0.0):
    """Per-ROW-seeded sampler for the serving engine: `sample(logits,
    seeds, positions)` -> (b,) token ids, called INSIDE shard_map.

    Greedy (temperature 0) ignores seeds/positions. Sampled rows draw with
    key = fold_in(fold_in(key(0), seed_row), position_row): the draw is a
    pure function of the REQUEST's seed and the absolute position the
    token will occupy — independent of which slot the request landed in,
    what else shares the batch, and when it was admitted, which is exactly
    the reproducibility contract continuous batching needs. (The fused
    `make_generate` keeps its own caller-key schedule; the filter and
    gather lowerings are shared.)"""
    validate_sampling(model.cfg, temperature, top_k, top_p)

    def sample(logits: jax.Array, seeds: jax.Array,
               positions: jax.Array) -> jax.Array:
        full = _full_vocab_logits(model, logits)
        if temperature == 0.0:
            idx = jnp.argmax(full, axis=-1).astype(jnp.int32)
        else:
            scaled = _filter_logits(full / temperature, top_k, top_p)

            def draw(seed, pos, row):
                key = jax.random.fold_in(
                    jax.random.fold_in(jax.random.key(0), seed), pos)
                return jax.random.categorical(key, row, axis=-1)

            idx = jax.vmap(draw)(seeds.astype(jnp.uint32),
                                 positions.astype(jnp.int32),
                                 scaled).astype(jnp.int32)
        # every tp shard computed the same choice; pmax clears the
        # varying tag so downstream carries stay tp-invariant
        return lax.pmax(idx, "tp")

    return sample


def host_sample_tokens(model: Transformer, padded_logits, seeds, positions,
                       temperature: float = 0.0, top_k: int = 0,
                       top_p: float = 0.0):
    """DEBUG-ONLY host-side sampler over materialised full-vocab logits —
    the path the engines deliberately do NOT ship (in-program sampling via
    `make_token_sampler` has been the only production path since PR 5),
    reachable behind their `debug_host_sampler` flag so the equivalence
    tests can pin that the fused sampler draws the SAME tokens, and so the
    r10 ablation can price the per-step full-vocab host transfer the fused
    design avoids.

    `padded_logits` is the host copy of the tp-concatenated (b,
    vocab_padded) logits a debug step program returns; the filter/argmax/
    fold_in(seed, position) schedule mirrors `make_token_sampler` exactly,
    so fused vs host tokens must agree bit-for-bit. Production engines
    never take this path: it moves b x vocab floats to the host every
    step where the fused path moves b int32 tokens."""
    import numpy as np

    full = jnp.asarray(padded_logits,
                       jnp.float32)[:, : model.cfg.vocab_size]
    if temperature == 0.0:
        return np.asarray(jnp.argmax(full, axis=-1).astype(jnp.int32))
    scaled = _filter_logits(full / temperature, top_k, top_p)

    def draw(seed, pos, row):
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.key(0), seed), pos)
        return jax.random.categorical(key, row, axis=-1)

    idx = jax.vmap(draw)(jnp.asarray(seeds, jnp.uint32),
                         jnp.asarray(positions, jnp.int32), scaled)
    return np.asarray(idx.astype(jnp.int32))


def make_generate(model: Transformer, mesh: Mesh, buf_len: int,
                  temperature: float = 0.0, top_k: int = 0,
                  top_p: float = 0.0):
    """Whole-generation XLA program: jitted
    (params, buf(b, buf_len), prompt_len, eos_id, max_total_len, key)
      -> (buf with generated tokens written, per-row total length (b,)).

    `prompt_len` and `max_total_len` may each be a scalar (shared) or a
    (b,) vector — mixed-length prompt batches decode in ONE dispatch, and
    each row stops at ITS total-length limit (pass
    `prompt_len + max_new` for per-prompt new-token budgets). The loop cursor is
    shared across rows ("teacher-forced catch-up"): it starts at
    min(prompt_len), and a row whose prompt extends past the cursor re-feeds
    its own prompt token (recomputing the K/V the prefill already wrote —
    per-position activations under causal attention are context-past-only,
    so the values are identical) until the cursor clears its prompt, after
    which its sampled tokens are appended like the single-row case.

    `temperature` 0 = greedy argmax (the reference's only decoding rule,
    `test.py:149`); > 0 samples from softmax(logits / temperature), with
    `top_k > 0` restricting to the k most likely tokens first and/or
    `top_p in (0, 1]` to the smallest nucleus whose probability mass
    reaches p (both filters compose: top-k prunes first, then top-p) —
    the standard sampling surface the reference lacks. Sampling keys fold
    in the cursor, so every position draws fresh randomness while staying
    a pure function of the caller's `key`. Rows that emit EOS stop
    contributing to their length and are padded with eos_id while other
    rows finish. One compile serves every prompt (prompt_len/eos/limit are
    traced; temperature/top_k/top_p are build-time constants)."""
    require_decodable(model)
    cfg = model.cfg
    dtype = resolve_dtype(cfg.compute_dtype)
    # RoPE tables cover the whole decode buffer even past the model's
    # trained maxlen (positions used to silently clip to the last table row
    # when buf_len > maxlen — ADVICE r1). Families with learned positions
    # instead hard-cap the buffer (GreedyDecoder validates).
    table_len = max(cfg.maxlen, buf_len)
    validate_sampling(cfg, temperature, top_k, top_p)

    def shard_fn(params, buf, prompt_len, eos_id, max_total_len, key):
        b, _ = buf.shape
        cos_t = sin_t = None
        if model.uses_rope:
            cos_t, sin_t = rope_tables(table_len, cfg.head_dim,
                                       cfg.rope_theta)
        if model.cp_size > 1:
            # cp-sharded ring prefill; the decode loop below stays
            # replicated over cp (outputs carry identical values, pmax
            # clears the varying tag)
            ks, vs, logits = _prefill_cp(model, params, buf, prompt_len,
                                         cos_t, sin_t, dtype)
        else:
            ks, vs, logits = _prefill(model, params, buf, prompt_len,
                                      cos_t, sin_t, dtype)

        def next_token(logits, cur):
            # gather the tp vocab shards; every shard then computes the
            # same choice (same key), and pmax clears the varying tag so
            # the buf carry stays tp-invariant
            full = _full_vocab_logits(model, logits)
            if temperature == 0.0:
                idx = jnp.argmax(full, axis=-1).astype(jnp.int32)
            else:
                scaled = _filter_logits(full / temperature, top_k, top_p)
                idx = jax.random.categorical(
                    jax.random.fold_in(key, cur), scaled, axis=-1
                ).astype(jnp.int32)
            return lax.pmax(idx, "tp")

        # per-ROW total-length cap: max_total_len may be a scalar (shared)
        # or a (b,) vector — a row finishes once prompt_len + generated
        # reaches ITS limit, so short prompts in a mixed batch don't keep
        # generating until the longest row's limit (the global cursor only
        # bounds the loop)
        row_limit = jnp.minimum(
            jnp.broadcast_to(jnp.asarray(max_total_len, jnp.int32), (b,)),
            buf_len)
        cur0 = jnp.min(prompt_len)
        nxt = next_token(logits, cur0)               # (b,) per-row first token
        done0 = ((prompt_len == cur0) & (nxt == eos_id)) | (
            prompt_len >= row_limit)
        gen0 = jnp.zeros((b,), jnp.int32)
        carry0 = (buf, ks, vs, nxt, done0, gen0, cur0)

        def cond(c):
            _, _, _, _, done, _, cur = c
            return jnp.logical_and(cur < jnp.max(row_limit), ~jnp.all(done))

        def body(c):
            buf, ck, cv, nxt, done, gen, cur = c
            in_prompt = cur < prompt_len             # (b,)
            cur_tok = lax.dynamic_slice_in_dim(buf, cur, 1, axis=1)[:, 0]
            tok = jnp.where(in_prompt, cur_tok,
                            jnp.where(done, eos_id, nxt))
            gen = gen + jnp.where(in_prompt | done, 0, 1)
            buf = lax.dynamic_update_slice(buf, tok[:, None], (0, cur))
            ck, cv, logits = _decode_one(model, params, ck, cv, tok, cur,
                                         buf_len, cos_t, sin_t, dtype)
            cand = next_token(logits, cur + 1)
            # cand is consumed at position cur+1; it counts as a GENERATED
            # token for a row only once the cursor has cleared its prompt
            starts_gen = (cur + 1) >= prompt_len
            done = done | (starts_gen & (cand == eos_id))
            done = done | (prompt_len + gen >= row_limit)
            return (buf, ck, cv, cand, done, gen, cur + 1)

        buf, _, _, _, _, gen, _ = lax.while_loop(cond, body, carry0)
        return buf, prompt_len + gen  # per-row total length

    fn = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(model.specs(), P(None, None), P(None), P(), P(), P()),
        out_specs=(P(None, None), P(None)))

    def wrapper(params, buf, prompt_len, eos_id, max_total_len, key):
        prompt_len = jnp.broadcast_to(
            jnp.asarray(prompt_len, jnp.int32), (buf.shape[0],))
        return fn(params, buf, prompt_len, eos_id, max_total_len, key)

    return jax.jit(wrapper)


class GreedyDecoder:
    """KV-cache decoder: compile the whole-generation program ONCE, reuse
    across prompts (the reference re-runs O(t^2) work per token,
    `test.py:145-152`; the no-cache jitted path in evaluate.py is
    O(buf_len^2) per token AND pays one dispatch per token).

    Greedy by default (the name survives from that contract); pass
    `temperature` / `top_k` for sampled decoding and a `seed` to
    decode_batch for reproducible draws."""

    def __init__(self, model: Transformer, mesh: Mesh, buf_len: int,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 0.0):
        require_decodable(model)
        if model.cp_size > 1:
            # Long-context decode: the PREFILL runs the same ring-attention
            # path as training (sequence sharded over 'cp'), so prompts far
            # beyond one chip's attention budget prefill across the group;
            # the per-token loop then runs on the gathered caches,
            # replicated over cp. Contiguous layout + ring only (zigzag
            # would permute the cache order; ulysses needs head headroom).
            if model.cp_impl != "ring" or model.cp_layout != "contiguous":
                raise ValueError(
                    "cp decode supports cp_impl='ring' with the contiguous "
                    f"layout (got impl={model.cp_impl!r}, "
                    f"layout={model.cp_layout!r})")
            if buf_len % model.cp_size:
                raise ValueError(f"buf_len {buf_len} must be divisible by "
                                 f"cp_size {model.cp_size} (contiguous "
                                 f"chunks)")
        cap = getattr(model, "max_decode_positions", None)
        if cap is not None and buf_len > cap:
            raise ValueError(
                f"buf_len {buf_len} exceeds the model's learned position "
                f"table ({cap}); clamp the buffer (evaluate.greedy_decode "
                f"does) or retrain with a larger maxlen")
        self.model = model
        self.mesh = mesh
        self.buf_len = buf_len
        self.generate = make_generate(model, mesh, buf_len,
                                      temperature=temperature, top_k=top_k,
                                      top_p=top_p)

    def decode(self, params, prompt_ids, eos_id: int,
               max_total_len: int, seed: int = 0) -> list:
        """Decode one prompt (ids incl. BOS); returns generated ids
        (prompt excluded), stopping at EOS or `max_total_len` total tokens.
        One device dispatch for the whole generation."""
        return self.decode_batch(params, [prompt_ids], eos_id,
                                 max_total_len, seed=seed)[0]

    def decode_batch(self, params, prompts, eos_id: int,
                     max_total_len: int, seed: int = 0) -> list:
        """Decode a LIST of prompts (mixed lengths fine) in a single
        device dispatch; returns one generated-ids list per prompt. The
        reference dispatches per prompt AND per token (`test.py:141-161`).
        `seed` matters only for sampled decoders (temperature > 0)."""
        import numpy as np

        b = len(prompts)
        for p in prompts:
            assert len(p) < self.buf_len, (
                f"prompt length {len(p)} must leave room in buf_len "
                f"{self.buf_len}")
        buf = np.full((b, self.buf_len), eos_id, dtype=np.int32)
        for i, p in enumerate(prompts):
            buf[i, : len(p)] = p
        plens = np.asarray([len(p) for p in prompts], np.int32)
        buf, flen = self.generate(params, jnp.asarray(buf),
                                  jnp.asarray(plens),
                                  jnp.asarray(eos_id, jnp.int32),
                                  jnp.asarray(max_total_len, jnp.int32),
                                  jax.random.key(seed))
        buf, flen = np.asarray(buf), np.asarray(flen)
        return [buf[i, len(prompts[i]) : int(flen[i])].tolist()
                for i in range(b)]
