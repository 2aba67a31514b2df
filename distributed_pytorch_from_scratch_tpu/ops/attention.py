"""Causal self-attention kernels.

`causal_attention_xla` mirrors the reference's naive O(T^2) attention
(`/root/reference/models/model.py:73-77`): explicit q@k^T / sqrt(d), additive
-10000 causal mask, softmax, @v — but functionally (no in-place
`masked_fill_`) and with the softmax in f32 (torch autocast computes softmax
in f32 as well). A Pallas flash-attention kernel (`impl='flash'`) provides the
fused HBM-friendly path the reference lacks; both produce the same math.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

MASK_VALUE = -10000.0  # reference uses -10000., model.py:75


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

    # block sizes come from the tuned-block table (get_block_config)
    return flash_attention(q, k, v, t_real=t_real,
                           interpret=impl == "flash_interpret")
