"""Rotary position embeddings (RoPE).

Math matches the reference's HF-style implementation
(`/root/reference/models/model.py:17-46`): half-rotation layout, frequency
tables of shape (maxlen, head_dim) built as `repeat(theta, 2)`. Two deliberate
deviations from the reference:

* tables are computed once and shared by all layers (the reference rebuilds
  identical tables per DecoderLayer — 12 copies in device memory,
  `/root/reference/models/model.py:110`, SURVEY quirk #10);
* there is no CPU-vs-GPU split of the computation (the reference split it to
  bit-match HF transformers on CUDA, `model.py:37-43`); everything is f32 and
  the cast to compute dtype happens at application time.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp


def rope_tables(maxlen: int, head_dim: int, base: float = 10000.0) -> Tuple[jax.Array, jax.Array]:
    """Precompute (cos, sin), each (maxlen, head_dim), float32."""
    assert head_dim % 2 == 0
    theta = 1.0 / (base ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    pos = jnp.arange(maxlen, dtype=jnp.float32)[:, None]  # (maxlen, 1)
    ang = pos * theta[None, :]                            # (maxlen, head_dim/2)
    ang = jnp.concatenate([ang, ang], axis=-1)            # repeat(1, 2) layout
    return jnp.cos(ang), jnp.sin(ang)


def rotate_half(x: jax.Array) -> jax.Array:
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([-x2, x1], axis=-1)


def apply_rotary(q: jax.Array, k: jax.Array, cos: jax.Array, sin: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Apply RoPE to q, k of shape (b, heads, t, head_dim).

    cos/sin: (b, t, head_dim) — already indexed by position_ids, matching
    `apply_rotary_pos_emb` (`/root/reference/models/model.py:25-31`).
    """
    cos = cos[:, None, :, :].astype(q.dtype)  # (b, 1, t, d)
    sin = sin[:, None, :, :].astype(q.dtype)
    q_rot = q * cos + rotate_half(q) * sin
    k_rot = k * cos + rotate_half(k) * sin
    return q_rot, k_rot


class YarnScaling(NamedTuple):
    """A `rope_scaling` of type `yarn` (Peng et al., arXiv:2309.00071) in
    DeepSeek-V2/V3's keys, as their published modelling code reads them."""

    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    @staticmethod
    def _mscale(factor: float, mscale: float) -> float:
        """`yarn_get_mscale`."""
        return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0

    @property
    def table_scale(self) -> float:
        """What cos and sin are multiplied by."""
        return (self._mscale(self.factor, self.mscale)
                / self._mscale(self.factor, self.mscale_all_dim))

    @property
    def softmax_scale(self) -> float:
        """What the scores' 1/sqrt(width) is multiplied by: the square of
        `yarn_get_mscale(factor, mscale_all_dim)` (1 where that key is 0)."""
        if not self.mscale_all_dim:
            return 1.0
        return self._mscale(self.factor, self.mscale_all_dim) ** 2


def yarn_inv_freq(dim: int, base: float, scaling: YarnScaling) -> jax.Array:
    """The `dim / 2` inverse frequencies under YaRN, float32: pair i's
    `base^(-2i/dim)` kept where its wavelength makes more than `beta_fast`
    turns in the original context, divided by `factor` where it makes fewer
    than `beta_slow`, and blended by a linear ramp over the pairs between
    (the two bounds rounded outward to whole pairs)."""
    def pair_of(turns: float) -> float:     # `yarn_find_correction_dim`
        return (dim * math.log(scaling.original_max_position_embeddings
                               / (turns * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(pair_of(scaling.beta_fast)), 0)
    high = min(math.ceil(pair_of(scaling.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    kept = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return kept / scaling.factor * ramp + kept * (1.0 - ramp)


def rope_angles(position_ids: jax.Array, dim: int, base: float = 10000.0,
                scaling: "YarnScaling | None" = None
                ) -> Tuple[jax.Array, jax.Array]:
    """(cos, sin), each (b, t, dim/2) float32, of pair i's angle
    `pos * base^(-2i/dim)` at `position_ids` (b, t): what
    `apply_rotary_interleaved` takes. Computed from the positions, so no
    table caps the length. Under a `scaling` the frequencies are
    `yarn_inv_freq`'s and cos and sin carry its `table_scale`."""
    assert dim % 2 == 0
    if scaling is None:
        theta = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32)
                                / dim))
    else:
        theta = yarn_inv_freq(dim, base, scaling)
    ang = position_ids.astype(jnp.float32)[..., None] * theta
    if scaling is None or scaling.table_scale == 1.0:
        return jnp.cos(ang), jnp.sin(ang)
    return (jnp.cos(ang) * scaling.table_scale,
            jnp.sin(ang) * scaling.table_scale)


def apply_rotary_interleaved(x: jax.Array, cos: jax.Array,
                             sin: jax.Array) -> jax.Array:
    """RoPE over INTERLEAVED pairs (x_2i, x_2i+1) of x (b, heads, t, dim),
    DeepSeek's `rope_interleave`: pair i turns by its angle and stays where
    it was. cos/sin: (b, t, dim/2) (`rope_angles`)."""
    *lead, dim = x.shape
    pairs = x.reshape(*lead, dim // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    cos = cos[:, None].astype(x.dtype)
    sin = sin[:, None].astype(x.dtype)
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def apply_rotary_leading(x: jax.Array, cos: jax.Array, sin: jax.Array,
                         rotary_dim: int) -> jax.Array:
    """Partial RoPE: the first `rotary_dim` dimensions of every head of x
    (b, heads, t, dim) turn, in the half-split layout (pairs `(x_i,
    x_{i + rotary_dim/2})`, `rotate_half` over the slice); the rest pass
    through untouched. cos/sin: (b, t, rotary_dim/2) (`rope_angles` of
    `rotary_dim`)."""
    half = rotary_dim // 2
    cos = cos[:, None].astype(x.dtype)
    sin = sin[:, None].astype(x.dtype)
    a, b, rest = x[..., :half], x[..., half:rotary_dim], x[..., rotary_dim:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest],
                           axis=-1)
