"""Context-parallel causal attention over a mesh axis: ring + Ulysses.

The reference has NO long-context story: its attention materialises the full
(b, heads, t, t) score tensor on one device and sequence length is capped at
maxlen=1000 (`/root/reference/models/model.py:73-77`, SURVEY §5.7). Here the
sequence dimension shards over the mesh axis 'cp' and two TPU-native
strategies make attention work across the shards:

* **Ring attention** (`ring_attention`): each shard keeps its Q chunk and
  rotates K/V chunks around the 'cp' ring with `lax.ppermute` (one ICI hop
  per step), combining per-chunk partial results with the online-softmax
  (flash-attention) recurrence in f32. Compute for each (Q-chunk, KV-chunk)
  block is a dense MXU matmul; causal masking uses the *global* positions
  carried around the ring with K/V, so arbitrary `position_ids` work.
  Memory is O(t_local^2) per block instead of O(t^2).

* **Ulysses** (`ulysses_attention`): two `lax.all_to_all`s swap the
  head-sharding for sequence-sharding — each shard then holds the FULL
  sequence for a subset of its local heads and runs any single-device kernel
  (including the Pallas flash kernel) unchanged, then swaps back. Cheaper
  compute-wise (no duplicated softmax bookkeeping) but needs
  num_local_heads % cp == 0 and moves activations twice.

Both are differentiable with plain JAX autodiff: the transpose of `ppermute`
is the reverse permutation and the transpose of `all_to_all` is the inverse
all-to-all, so the backward pass's communication schedule is derived
automatically (the hand-written ring backward of the ring-attention paper
falls out of `lax.scan`'s transpose).

Call from inside `shard_map` code partitioned over `axis`.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .attention import causal_attention, resolve_attention_impl
from .collectives import all_to_all, ring_permute


def zigzag_perm(t: int, n: int) -> np.ndarray:
    """Token permutation for the zig-zag context-parallel layout.

    The sequence splits into 2n sub-chunks; cp shard r owns sub-chunks r and
    2n-1-r, so every shard holds an equally early+late slice of the causal
    triangle and ring work is balanced (see `ring_attention`). Returns the
    gather indices: `x[:, zigzag_perm(t, n)]` reorders a batch so a plain
    contiguous P('cp') sharding lands each shard its zig-zag pair. Static
    (numpy) — shapes are compile-time constants under jit.
    """
    if t % (2 * n):
        raise ValueError(f"zigzag layout needs sequence length {t} divisible "
                         f"by 2*cp ({2 * n})")
    c = t // (2 * n)
    idx = []
    for r in range(n):
        idx.extend(range(r * c, (r + 1) * c))
        idx.extend(range((2 * n - 1 - r) * c, (2 * n - r) * c))
    return np.asarray(idx)

_BIG_NEG = -1e30  # mask fill for f32 online softmax; exp() underflows to 0


def _block_attn_xla(q, k, v, q_pos, kv_pos, scale):
    """One (Q-chunk, KV-chunk) block, dense XLA math: returns (o, lse) with
    o normalized within the block (f32) and lse = logsumexp of the row's
    visible scores (MASKed rows emit _BIG_NEG). k/v may carry fewer
    (grouped-query) heads.

    q: (b, h, tq, d); k, v: (b, hkv, tk, d); q_pos: (b, tq); kv_pos: (b, tk).
    """
    from .attention import repeat_kv

    k, v = repeat_kv(q, k, v)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    causal = q_pos[:, None, :, None] >= kv_pos[:, None, None, :]
    s = jnp.where(causal, s, _BIG_NEG)
    m = jnp.max(s, axis=-1)                          # (b, h, tq)
    p = jnp.exp(s - m[..., None])
    # rows with no visible kv in this block: m = _BIG_NEG, p = 1 everywhere —
    # zero them so they contribute nothing.
    alive = m > _BIG_NEG / 2
    p = jnp.where(alive[..., None], p, 0.0)
    l = jnp.sum(p, axis=-1)                          # (b, h, tq)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    dead = l == 0.0
    l_safe = jnp.where(dead, 1.0, l)
    o = o / l_safe[..., None]
    lse = jnp.where(dead, _BIG_NEG, m + jnp.log(l_safe))
    return o, lse


def _block_attn(q, k, v, q_pos, kv_pos, scale, impl: str):
    """Dispatch one block to the Pallas positional kernel (MXU dots in the
    input dtype, no O(tq*tk) f32 score tensor in HBM) or the dense XLA
    path, per the RESOLVED `impl`. Both return (o f32-normalized, lse)."""
    if impl == "xla":
        return _block_attn_xla(q, k, v, q_pos, kv_pos, scale)
    from .pallas.flash_attention import block_attention

    interpret = impl == "flash_interpret"
    if interpret and getattr(jax.typeof(q), "vma", None):
        # The interpreted kernel discharges to a jaxpr that fails
        # shard_map's varying-manual-axes check (same gate as the fused
        # flash backward); compiled TPU execution never discharges.
        raise ValueError(
            "ring attention with impl='flash_interpret' cannot run inside "
            "a vma-checked shard_map: build the shard_map with "
            "check_vma=False (tests/test_ring_attention.py does), or use "
            "impl='xla'")
    o, lse = block_attention(q, k, v, q_pos, kv_pos, interpret=interpret)
    return o.astype(jnp.float32), lse


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   q_pos: jax.Array, axis: str = "cp",
                   impl: str = "auto", live=None) -> jax.Array:
    """Causal attention with the sequence dim sharded over `axis`.

    q: (b, heads_local, t_local, head_dim) — this shard's chunk; k, v may
    carry fewer (grouped-query) heads.
    q_pos:   (b, t_local) global positions of this shard's tokens (the same
             `position_ids` the model already carries; the K/V copy rides the
             ring so causal masks are exact for any position layout).
    Returns (b, heads_local, t_local, head_dim), same dtype as q.

    `impl`: 'flash' runs each (Q-half, KV-half) block through the Pallas
    positional kernel (ops/pallas/flash_attention.block_attention) —
    input-dtype MXU dots, O(t_local) block memory; 'xla' keeps the dense f32
    fallback; 'auto' picks flash on real TPU. The online-softmax combination
    carries (o, lse) either way, and both block impls differentiate through
    plain autodiff (the kernel's custom VJP takes the (do, dlse) pair).

    Work skipping is at HALF-chunk granularity: the local sequence splits
    into two sub-chunks and each ring step runs up to four
    (Q-half, KV-half) blocks, each skipped by `lax.cond` when causality
    masks it entirely (every kv position after every q position). With the
    default contiguous layout that skips ~half of all blocks but leaves the
    ring imbalanced (the last shard computes every block — ADVICE r1); with
    the zig-zag layout (`models.transformer cp_layout='zigzag'`: shard r
    owns sub-chunks r and 2n-1-r) every shard computes the same ~half, so
    the synchronous ring's per-step latency drops ~2x. Positions decide the
    masks, so BOTH layouts are exact here — the layout is purely the
    caller's input permutation.

    `live` (optional scalar bool): when provided, every block's compute is
    additionally gated on it — a False `live` runs ONLY the ring's
    ppermutes (on whatever q/k/v the caller passes, typically zeros) and
    returns the zero accumulator. This is the pipeline-bubble contract
    (models/transformer._pipeline_layers, VERDICT r3 #3): XLA lowers
    collective-permute with a global participant list, so the ring must
    execute on every pp stage each step; the per-block `lax.cond` (pure
    local math, no collectives) is where bubble FLOPs are skipped instead.
    All cp/tp/ep members of a pp stage agree on `live`, so the gated conds
    stay uniform within every collective group.
    """
    impl = resolve_attention_impl(impl)
    n = lax.axis_size(axis)
    scale = 1.0 / math.sqrt(q.shape[-1])
    t_local = q.shape[2]
    halves = 2 if t_local % 2 == 0 else 1
    th = t_local // halves
    qc = q.astype(jnp.float32) if impl == "xla" else q

    # derive the accumulators from q so they inherit its varying-axes tags
    # (fresh jnp.zeros would be mesh-invariant and trip shard_map's vma check
    # on the scan carry)
    o0 = jnp.zeros_like(q, jnp.float32)
    lse0 = q[..., 0].astype(jnp.float32) * 0.0 + _BIG_NEG

    q_halves = [qc[:, :, i * th:(i + 1) * th] for i in range(halves)]
    qp_halves = [q_pos[:, i * th:(i + 1) * th] for i in range(halves)]

    def block_into(o, lse, qh, qph, k_cur, v_cur, pos_cur):
        def compute(o, lse):
            bo, blse = _block_attn(qh, k_cur, v_cur, qph, pos_cur, scale,
                                   impl)
            lse_new = jnp.logaddexp(lse, blse)
            # combine weights; exp(_BIG_NEG - lse_new) underflows to exactly 0
            o = (o * jnp.exp(lse - lse_new)[..., None]
                 + bo * jnp.exp(blse - lse_new)[..., None])
            return o, lse_new

        skip_block = jnp.max(qph) < jnp.min(pos_cur)
        if live is not None:
            skip_block = skip_block | jnp.logical_not(live)
        return lax.cond(skip_block, lambda o, lse: (o, lse), compute,
                        o, lse)

    def accumulate_all(o, lse, k_cur, v_cur, pos_cur):
        new_o, new_lse = [], []
        for i in range(halves):
            oi = o[:, :, i * th:(i + 1) * th]
            li = lse[:, :, i * th:(i + 1) * th]
            for j in range(halves):
                kj = k_cur[:, :, j * th:(j + 1) * th]
                vj = v_cur[:, :, j * th:(j + 1) * th]
                pj = pos_cur[:, j * th:(j + 1) * th]
                oi, li = block_into(oi, li, q_halves[i], qp_halves[i],
                                    kj, vj, pj)
            new_o.append(oi)
            new_lse.append(li)
        return jnp.concatenate(new_o, axis=2), jnp.concatenate(new_lse, axis=2)

    def step(carry, _):
        o, lse, k_cur, v_cur, pos_cur = carry
        o, lse = accumulate_all(o, lse, k_cur, v_cur, pos_cur)
        # rotate KV (+ its positions) one hop around the ring
        k_nxt = ring_permute(k_cur, axis)
        v_nxt = ring_permute(v_cur, axis)
        pos_nxt = ring_permute(pos_cur, axis)
        return (o, lse, k_nxt, v_nxt, pos_nxt), None

    # n-1 rotating steps, then a final accumulate with no ppermute: the last
    # hop's rotated KV would be discarded, and XLA cannot DCE a collective
    # inside the compiled scan body. With cp=1 this is fully collective-free.
    (o, lse, k_l, v_l, pos_l), _ = lax.scan(
        step, (o0, lse0, k, v, q_pos), None, length=n - 1)
    o, _ = accumulate_all(o, lse, k_l, v_l, pos_l)
    # every query attends at least to itself, so its o is fully normalized
    return o.astype(q.dtype)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      axis: str = "cp", impl: str = "auto") -> jax.Array:
    """All-to-all sequence parallelism (DeepSpeed-Ulysses style).

    q: (b, heads_local, t_local, head_dim), sequence sharded over
    `axis` in contiguous rank-order chunks (the collate layout); k, v may
    carry fewer (grouped-query) heads. Swaps to
    (b, heads_local/cp, t_full, head_dim), runs the normal causal kernel
    (Pallas flash on TPU, GQA-routed), swaps back. Requires both head counts
    divisible by cp and contiguous equal chunks — for anything rangier use
    `ring_attention`.
    """
    n = lax.axis_size(axis)
    h, hkv = q.shape[1], k.shape[1]
    if h % n != 0 or hkv % n != 0:
        raise ValueError(
            f"ulysses needs local q heads ({h}) and kv heads ({hkv}) "
            f"divisible by cp axis size ({n})")
    # split heads (axis 1) over cp, gather sequence (axis 2)
    swap = functools.partial(all_to_all, axis=axis, split_axis=1, concat_axis=2)
    unswap = functools.partial(all_to_all, axis=axis, split_axis=2, concat_axis=1)
    o = causal_attention(swap(q), swap(k), swap(v), impl=impl)
    return unswap(o)
