"""Self-attention under a declared mask: the dense XLA path and the dispatch.

A mask is a DECLARATION (`AttnMask`): `CAUSAL`, the triangle most layers
run, `block_diffusion(B, L)`, the three-part mask of block-diffusion
training over the 2L rows `[noised ; clean]` of a sequence, and
`sliding_window(W)`, the triangle's band of the last W keys a row
(`mask_matrix` is their one dense definition). The flash kernels plan their
tiles from the same declaration (ops/pallas/flash_attention.py); the dense
path here is the CPU default and the kernels' oracle.

`causal_attention_xla` mirrors the reference's naive O(T^2) attention
(`/root/reference/models/model.py:73-77`): explicit q@k^T / sqrt(d), additive
-10000 causal mask, softmax, @v — but functionally (no in-place
`masked_fill_`) and with the softmax in f32 (torch autocast computes softmax
in f32 as well). A Pallas flash-attention kernel (`impl='flash'`) provides the
fused HBM-friendly path the reference lacks; both produce the same math.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

MASK_VALUE = -10000.0  # reference uses -10000., model.py:75


class AttnMask(NamedTuple):
    """Which (query row, key row) pairs of a sequence are live, as a static
    description both attention paths read. `causal`: key <= query.
    `block_diffusion`: the sequence is 2 * `half` rows, a NOISED copy and
    then a CLEAN copy of the same `half` positions, in blocks of `block`
    positions (`blk(i) = i // block`): noised to noised live inside one
    block (both directions), noised to clean live for EARLIER blocks,
    clean to clean live for earlier blocks and its own, clean to noised
    dead. `half * (half + block)` of the `4 * half^2` entries are live.
    `sliding_window`: key <= query and query - key < `window` (a row sees
    itself and the `window` - 1 rows before it); over t >= window rows
    `window * (2 t - window + 1) / 2` entries are live."""

    kind: str = "causal"
    block: int = 1
    half: int = 0
    window: int = 0


CAUSAL = AttnMask()


def block_diffusion(block: int, half: int) -> AttnMask:
    if block < 1 or half < 1 or half % block:
        raise ValueError(f"block_diffusion: the block length {block} must "
                         f"divide the {half} positions of a sequence")
    return AttnMask("block_diffusion", block, half)


def sliding_window(window: int) -> AttnMask:
    if window < 1:
        raise ValueError(f"sliding_window: a row sees itself at least, got "
                         f"a window of {window}")
    return AttnMask("sliding_window", window=window)


def live_entries(mask: AttnMask, t: int) -> int:
    """The live (query, key) pairs of one head over `t` rows."""
    if mask.kind == "block_diffusion":
        return mask.half * (mask.half + mask.block)
    w = min(mask.window, t) if mask.kind == "sliding_window" else t
    return w * (2 * t - w + 1) // 2


def mask_matrix(mask: AttnMask, t: int) -> jax.Array:
    """(t, t) bool, [query row, key row] live: the declaration, densely."""
    i = jnp.arange(t)
    if mask.kind == "causal":
        return i[None, :] <= i[:, None]
    if mask.kind == "sliding_window":
        back = i[:, None] - i[None, :]
        return (back >= 0) & (back < mask.window)
    L, B = mask.half, mask.block
    if t != 2 * L:
        raise ValueError(f"a block_diffusion mask over {L} positions takes "
                         f"{2 * L} rows, got {t}")
    noised = i < L
    blk = (i % L) // B
    q_noised, k_noised = noised[:, None], noised[None, :]
    q_blk, k_blk = blk[:, None], blk[None, :]
    return jnp.where(q_noised,
                     jnp.where(k_noised, k_blk == q_blk, k_blk < q_blk),
                     ~k_noised & (k_blk <= q_blk))


def repeat_kv(q: jax.Array, k: jax.Array, v: jax.Array):
    """Expand grouped-query k/v (b, kv_heads, t, d) to q's head count for
    dense consumers. Identity when the head counts already match."""
    group = q.shape[1] // k.shape[1]
    if group > 1:
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
    return k, v


def causal_attention_xla(q: jax.Array, k: jax.Array, v: jax.Array,
                         t_real: int = None) -> jax.Array:
    """q: (b, heads, t, head_dim) -> (b, heads, t, head_dim); k/v may carry
    fewer (grouped-query) heads — expanded here (the flash kernel instead
    routes blocks, ops/pallas/flash_attention.py).

    `t_real` < t marks the trailing rows as padding (sequence bucketing):
    they are sliced off before the O(t^2) score tensor forms and the output
    pads back with exact zeros — the same contract as the flash kernel's
    `t_real`, so the two impls stay interchangeable."""
    *_, t, head_dim = q.shape
    if t_real is not None and t_real < t:
        out = causal_attention_xla(q[..., :t_real, :], k[..., :t_real, :],
                                   v[..., :t_real, :])
        return jnp.pad(out, ((0, 0), (0, 0), (0, t - t_real), (0, 0)))
    k, v = repeat_kv(q, k, v)
    scale = 1.0 / math.sqrt(head_dim)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    mask = jnp.triu(jnp.ones((t, t), dtype=bool), k=1)
    scores = jnp.where(mask[None, None], jnp.asarray(MASK_VALUE, scores.dtype), scores)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def masked_attention_xla(q: jax.Array, k: jax.Array, v: jax.Array,
                         mask: AttnMask) -> jax.Array:
    """`causal_attention_xla`'s math under a declared mask (every row of
    every declared mask has a live entry, itself at least, so the additive
    mask is exact as it is there)."""
    *_, t, head_dim = q.shape
    k, v = repeat_kv(q, k, v)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(head_dim)
    scores = jnp.where(mask_matrix(mask, t)[None, None], scores,
                       jnp.asarray(MASK_VALUE, scores.dtype))
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


IMPLS = ("auto", "xla", "flash", "flash_interpret")


def resolve_attention_impl(impl: str) -> str:
    """The attention implementation `impl` runs as on this backend — what
    the entry points report, so a caller always knows which one it got.

    'auto' is the Pallas flash kernel on TPU and the XLA path elsewhere.
    'flash' is compiled by Mosaic: asked for by name off-TPU it is an
    error, never a quiet interpreter run. 'flash_interpret' is the explicit
    interpreter opt-in (the CPU tests)."""
    if impl not in IMPLS:
        raise ValueError(f"unknown attention impl {impl!r}; expected one of "
                         f"{IMPLS}")
    if impl == "auto":
        return "flash" if jax.default_backend() == "tpu" else "xla"
    if impl == "flash" and jax.default_backend() != "tpu":
        raise ValueError(
            f"attention impl 'flash' is compiled by Mosaic and needs a TPU "
            f"backend (got {jax.default_backend()!r}); use 'xla' (or 'auto') "
            f"off-TPU, or 'flash_interpret' to run the kernel under the "
            f"Pallas interpreter on purpose")
    return impl


def causal_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     impl: str = "auto", t_real: int = None) -> jax.Array:
    impl = resolve_attention_impl(impl)
    if impl == "xla":
        return causal_attention_xla(q, k, v, t_real=t_real)
    from .pallas.flash_attention import flash_attention

    return flash_attention(q, k, v, t_real=t_real,
                           interpret=impl == "flash_interpret")


def masked_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     mask: AttnMask, impl: str = "auto") -> jax.Array:
    """`causal_attention` under a declared mask that is not the triangle
    (a family says which, a kind of layer: `DecoderStack._attn_mask`)."""
    impl = resolve_attention_impl(impl)
    if impl == "xla":
        return masked_attention_xla(q, k, v, mask)
    from .pallas.flash_attention import flash_attention

    return flash_attention(q, k, v, mask=mask,
                           interpret=impl == "flash_interpret")
