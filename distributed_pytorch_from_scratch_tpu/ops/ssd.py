"""The Mamba-2 state-space recurrence in its chunked (SSD) form.

A head h holds a state `S` (P, N), float32, and reads the B and C of its
group (`G` groups of `H / G` heads each share one B and one C):

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        y_t = S_t C_t

with `A` a negative scalar a head and `dt_t` > 0 a scalar a head and token.
The recurrence is linear in the state, so a chunk of `chunk` tokens is three
products around float32 decay sums `cum_i = sum_{r <= i} dt_r A` (inclusive,
from the chunk's first row):

* inside the chunk, `y_i += sum_{j <= i} exp(cum_i - cum_j) (C_i . B_j) dt_j
  x_j`: the chunk's masked `C B^T` scores (a group's, shared by its heads)
  times the heads' decays, then times the chunk's `dt x`;
* the chunk's own state, `sum_j exp(cum_last - cum_j) dt_j x_j B_j^T`;
* what the state that ENTERED the chunk adds, `y_i += exp(cum_i) C_i . S_in`,
  the states passed from chunk to chunk by a scan, `S_out = exp(cum_last)
  S_in + the chunk's own`.

Every exponent is a difference of sums INSIDE one chunk and is at most 0
(the mask is applied to the exponent, before `exp`), so nothing overflows and
what underflows is a contribution that has decayed away; `decay_min` says
how far the sums reach. The decay sums, the exponentials and the states are
float32 whatever the operands' dtype; the products take their operands in
x's dtype and sum in float32. There is no triangular solve (the delta rules
of `ops/delta_rule.py` have one) and no kernel yet: XLA text.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

CHUNK = 128     # Nemotron-H's published `chunk_size`


def ssd_flops_per_token(head_dim: int, state: int, heads_a_group: int,
                        chunk: int = CHUNK) -> float:
    """The chunked form's forward FLOPs a token and HEAD: the chunk's C B^T
    scores (2 chunk N, once a group), the scores times `dt x` (2 chunk P),
    the chunk's own state and the entering state's part (2 P N each)."""
    return (2.0 * chunk * state / heads_a_group + 2.0 * chunk * head_dim
            + 4.0 * head_dim * state)


def ssd(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
        C: jax.Array, chunk: int = CHUNK, state_dtype=jnp.float32
        ) -> Tuple[jax.Array, jax.Array]:
    """x (b, t, H, P), dt (b, t, H) float32 and positive, A (H,) float32 and
    negative, B and C (b, t, G, N) with G dividing H -> (y (b, t, H, P) in
    x's dtype, `decay_min`: the smallest `dt A` summed over a chunk).
    `state_dtype` is the precision the decay sums, the decays and the
    states are kept at (float32; a test and the benchmark's control hand
    bfloat16 to show what that loses: rounded by `lax.reduce_precision`,
    which no compiler pass takes back out as a pair of converts is)."""
    b, t, H, P = x.shape
    G, N = B.shape[2:]
    R = H // G                                    # heads a group
    pad = -t % chunk
    if pad:
        # a padding row has dt = 0: it decays nothing and adds nothing
        x, dt, B, C = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (x, dt, B, C))
    c = (t + pad) // chunk
    dtype = x.dtype
    kind = jnp.finfo(state_dtype)
    kept = (lambda a: a) if kind.bits == 32 else (
        lambda a: lax.reduce_precision(a, kind.nexp, kind.nmant))
    chunks = lambda a: a.reshape(b, c, chunk, *a.shape[2:])
    # (b, c, Q, G, R, ...): a group's heads beside its B and C
    xdt = chunks((x * dt[..., None].astype(dtype)).reshape(b, -1, G, R, P))
    B, C = chunks(B), chunks(C)
    cum = kept(jnp.cumsum(chunks(kept(dt * A)), axis=2)).reshape(
        b, c, chunk, G, R)
    last = cum[:, :, -1]                          # (b, c, G, R)
    decay_min = jnp.min(lax.stop_gradient(last))

    # inside the chunk: the group's scores, masked before the exponential
    scores = jnp.einsum("bcign,bcjgn->bcgij", C, B,
                        preferred_element_type=jnp.float32)
    seen = jnp.tril(jnp.ones((chunk, chunk), bool))
    gap = (cum[:, :, :, None] - cum[:, :, None]).transpose(0, 1, 4, 5, 2, 3)
    decay = kept(jnp.exp(jnp.where(seen, kept(gap), -jnp.inf)))
    mixed = (scores[:, :, :, None] * decay).astype(dtype)  # (b,c,G,R,Q,Q)
    y = jnp.einsum("bcgrij,bcjgrp->bcigrp", mixed, xdt,
                   preferred_element_type=jnp.float32)

    # the chunk's own state, and the states handed from chunk to chunk
    to_end = kept(jnp.exp(kept(last[:, :, None] - cum))).astype(dtype)
    own = kept(jnp.einsum("bcjgrp,bcjgn->bcgrpn", xdt * to_end[..., None], B,
                          preferred_element_type=jnp.float32))

    def hand_on(S, chunk_):
        own_c, last_c = chunk_
        return kept(kept(jnp.exp(last_c))[..., None, None] * S + own_c), S

    # (zeros that vary over the mesh axes the operands vary over)
    _, entering = lax.scan(
        hand_on, own[:, 0] * 0,
        (jnp.moveaxis(own, 1, 0), jnp.moveaxis(last, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)       # (b, c, G, R, P, N)
    carried = jnp.einsum("bcign,bcgrpn->bcigrp", C, entering.astype(dtype),
                         preferred_element_type=jnp.float32)
    y = y + carried * kept(jnp.exp(cum))[..., None]
    return y.reshape(b, c * chunk, H, P)[:, :t].astype(dtype), decay_min
