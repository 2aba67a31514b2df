"""The gated delta rule (Gated DeltaNet, Yang et al. 2024): a linear-attention
layer's recurrence, per head, over a state `S` (d_k x d_v) that starts at
zero:

    S~  = alpha_t S_{t-1}                         alpha_t = exp(g_t), g_t <= 0
    S_t = S~ + k_t (beta_t (v_t - S~^T k_t))^T
    o_t = S_t^T q_t

`delta_rule_recurrent` is that, token by token under one `lax.scan`: the
definition, for tests and small shapes.

`gated_delta_rule` computes the same in CHUNKS of `chunk` tokens (64 in the
published implementations, and here), which turns all but one pass over the
chunks into batched matrix products. With `G` the running sum of `g` inside
a chunk (inclusive) and `K`, `V`, `Q` the chunk's rows:

    A  = strict_lower((beta K) K^T * exp(G_i - G_j))
    W  = (I + A)^-1 (beta K * exp(G))      U = (I + A)^-1 (beta V)
    per chunk, carrying S:
        V' = U - W S
        O  = (Q * exp(G)) S + lower_incl(Q K^T * exp(G_i - G_j)) V'
        S <- exp(G_C) S + (K * exp(G_C - G))^T V'

The two solves are one unit-lower-triangular solve of `[W | U]` a chunk
(forward substitution: stable whatever the keys, where a product of powers
of `A` is not). Everything that does not read `S` is computed for all chunks
at once, outside the scan. A decay ratio is always the exponential of a
MASKED DIFFERENCE, never a quotient of two exponentials: with the decay
parameter near its cap `exp(G)` underflows inside one chunk while the ratio
between two near rows is an ordinary number. `S`, `G`, the solve and every
sum are float32; the products' operands are `q`'s dtype (the model's compute
dtype). The backward is JAX's transpose of this text, the chunk scan
rematerialised by chunk (its residuals are the carried states, one a
chunk): a Pallas kernel is a later change's.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .collectives import copy_to

CHUNK = 64


def rule_flops_per_token(d_k: int, d_v: int, chunk: int = CHUNK) -> float:
    """The chunked rule's forward FLOPs a head and token: K K^T and Q K^T
    inside a chunk, the unit triangular solve of [W | U], three products
    with the state and the chunk's scores times its new values."""
    return (4.0 * chunk * d_k + chunk * (d_k + d_v) + 6.0 * d_k * d_v
            + 2.0 * chunk * d_v)


def delta_rule_recurrent(q: jax.Array, k: jax.Array, v: jax.Array,
                         g: jax.Array, beta: jax.Array
                         ) -> Tuple[jax.Array, jax.Array]:
    """q, k (..., t, d_k), v (..., t, d_v), g, beta (..., t) -> (o (..., t,
    d_v), the final state (..., d_k, d_v)), float32, one token at a time."""
    f32 = lambda z: z.astype(jnp.float32)
    q, k, v, g, beta = map(f32, (q, k, v, g, beta))
    lead = q.shape[:-2]
    time_first = lambda z: jnp.moveaxis(z, len(lead), 0)

    def token(S, row):
        q_t, k_t, v_t, g_t, b_t = row
        S = jnp.exp(g_t)[..., None, None] * S
        delta = b_t[..., None] * (v_t - jnp.einsum("...kv,...k->...v", S, k_t))
        S = S + k_t[..., :, None] * delta[..., None, :]
        return S, jnp.einsum("...kv,...k->...v", S, q_t)

    S0 = jnp.zeros(lead + (q.shape[-1], v.shape[-1]), jnp.float32)
    S, o = lax.scan(token, S0, tuple(map(time_first, (q, k, v, g, beta))))
    return jnp.moveaxis(o, 0, len(lead)), S


def gated_delta_rule(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                     beta: jax.Array, chunk: int = CHUNK
                     ) -> Tuple[jax.Array, jax.Array]:
    """The rule in chunks (module docstring). q, k (b, h, t, d_k), v (b, h,
    t, d_v) in the compute dtype; g, beta (b, h, t) float32. Returns (o (b,
    h, t, d_v) in v's dtype, the final state (b, h, d_k, d_v) float32). A
    length that is no multiple of `chunk` is padded with tokens that leave
    the state as it is (g = 0, beta = 0, k = 0) and cut off again.

    ONE SEQUENCE AT A TIME (`lax.map` over b, each sequence's rule under
    `jax.checkpoint`): what the rule holds between its passes (`W`, `U`,
    the chunks' matrices, the carried states, and their cotangents) is a
    sequence's and does not grow with the batch; 8192 tokens of 32 heads
    128 wide hold about 2 GB that way."""
    one = jax.checkpoint(functools.partial(_one_sequence, chunk=chunk))
    return lax.map(lambda row: one(*row), (q, k, v, g, beta))


def _one_sequence(q, k, v, g, beta, *, chunk: int):
    """`gated_delta_rule` for one sequence: q, k (h, t, d_k), v (h, t, d_v),
    g, beta (h, t)."""
    h, t, dk = q.shape
    dv = v.shape[-1]
    dtype = v.dtype
    pad = -t % chunk
    if pad:
        rows = lambda z: jnp.pad(z, ((0, 0), (0, pad))
                                 + ((0, 0),) * (z.ndim - 2))
        q, k, v, g, beta = map(rows, (q, k, v, g, beta))
    n = (t + pad) // chunk
    chunks = lambda z: z.reshape(h, n, chunk, *z.shape[2:])
    q, k, v = chunks(q), chunks(k), chunks(v)
    g = chunks(g.astype(jnp.float32))
    beta = chunks(beta.astype(jnp.float32))
    dot = lambda eq, x, y: jnp.einsum(eq, x.astype(dtype), y.astype(dtype),
                                      preferred_element_type=jnp.float32)

    G = jnp.cumsum(g, axis=-1)                              # (h, n, C)
    i = jnp.arange(chunk)
    # exp of a masked difference: G_i - G_j <= 0 wherever i >= j
    diff = G[..., :, None] - G[..., None, :]
    decay = jnp.exp(jnp.where(i[:, None] >= i[None, :], diff, -jnp.inf))
    k_beta = k.astype(jnp.float32) * beta[..., None]
    A = jnp.where(i[:, None] > i[None, :],
                  dot("hnik,hnjk->hnij", k_beta, k) * decay, 0.0)
    rhs = jnp.concatenate([k_beta * jnp.exp(G)[..., None],
                           v.astype(jnp.float32) * beta[..., None]], axis=-1)
    WU = lax.linalg.triangular_solve(
        A + jnp.eye(chunk, dtype=jnp.float32), rhs, left_side=True,
        lower=True, unit_diagonal=True)
    # what the chunk's own rows give each other, and what they hand the
    # state, for all chunks at once; kept in the products' dtype
    attn = dot("hnik,hnjk->hnij", q, k) * decay             # lower, incl.
    q_in = q.astype(jnp.float32) * jnp.exp(G)[..., None]
    G_end = G[..., -1]                                       # (h, n)
    k_out = k.astype(jnp.float32) * jnp.exp(G_end[..., None] - G)[..., None]

    @jax.checkpoint
    def one_chunk(S, c):
        W_c, U_c, attn_c, q_c, k_c, end_c = c
        v_new = U_c - dot("hik,hkv->hiv", W_c, S)
        o = dot("hik,hkv->hiv", q_c, S) + dot("hij,hjv->hiv", attn_c, v_new)
        S = (jnp.exp(end_c)[..., None, None] * S
             + dot("hik,hiv->hkv", k_c, v_new))
        return S, o.astype(dtype)

    chunk_first = lambda z: jnp.moveaxis(z, 1, 0)
    operand = lambda z: chunk_first(z.astype(dtype))
    S0 = jnp.zeros((h, dk, dv), jnp.float32)
    vma = tuple(jax.typeof(WU).vma)
    if vma:     # inside shard_map the carry varies over what its inputs do
        S0 = copy_to(S0, vma)
    S, o = lax.scan(one_chunk, S0, (
        operand(WU[..., :dk]), chunk_first(WU[..., dk:]), operand(attn),
        operand(q_in), operand(k_out), chunk_first(G_end)))
    o = jnp.moveaxis(o, 0, 1).reshape(h, t + pad, dv)
    return o[:, :t], S
