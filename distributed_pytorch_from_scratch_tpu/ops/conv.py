"""The causal depthwise convolution over time: the one in the tree.

Two mixers take it: Gated DeltaNet's four taps over a key head's `[q | k |
v]` channels, followed by a SiLU (`parallel/gdn.py`), and the gated short
convolution's three taps with no activation (`parallel/shortconv.py`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def causal_depthwise_conv(u: jax.Array, w: jax.Array) -> jax.Array:
    """u (b, t, *channels), w (*channels, taps) -> (b, t, *channels)
    float32: `out[t] = sum_j w[..., j] * u[t - (taps - 1) + j]` a channel,
    zeros before the sequence. Tap `taps - 1` reads the token itself, tap 0
    the one `taps - 1` back. Summed in float32 whatever u's dtype; the
    caller casts (a float32 copy of 8192 channels and its cotangent are 1.5
    GB at 16k tokens)."""
    t, taps = u.shape[1], w.shape[-1]
    u = jnp.pad(u, ((0, 0), (taps - 1, 0)) + ((0, 0),) * (u.ndim - 2))
    return sum(u[:, j:j + t].astype(jnp.float32) * w[..., j]
               for j in range(taps))
