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
(`solve_unit_lower`: the inverse built by halves from diagonal blocks that
forward substitution inverts, every step bounded by the inverse itself:
stable whatever the keys, where a product of powers of `A` is not: with
collinear keys and beta near 1 the powers grow as the binomials and the
inverse has two diagonals). Everything that does not read `S` is computed
for all chunks at once, outside the walk over the chunks. A decay ratio is
always the exponential of a MASKED DIFFERENCE, never a quotient of two
exponentials: with the decay parameter near its cap `exp(G)` underflows
inside one chunk while the ratio between two near rows is an ordinary
number. `S`, `G`, the solve and every sum are float32; the products'
operands are `q`'s dtype (the model's compute dtype).

The walk over the chunks, the part that reads `S`, is two Pallas kernels on
a TPU at widths that are multiples of 128 (ops/pallas/delta_rule.py: the
state stays in VMEM from a sequence's first chunk to its last; the backward
is the reverse walk by hand, from the states the forward rule writes out,
and JAX's transpose of the chunk-parallel text) and a `lax.scan`
rematerialised by chunk everywhere else (its backward JAX's transpose of
the whole text; its residuals the carried states, one a chunk).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .collectives import copy_to
from .pallas.delta_rule import (holds as kernels_hold, walk_backward,
                                walk_forward)

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
                     beta: jax.Array, chunk: int = CHUNK,
                     interpret: bool = False
                     ) -> Tuple[jax.Array, jax.Array]:
    """The rule in chunks (module docstring). q, k (b, h, t, d_k), v (b, h,
    t, d_v) in the compute dtype; g, beta (b, h, t) float32. Returns (o (b,
    h, t, d_v) in v's dtype, the final state (b, h, d_k, d_v) float32). A
    length that is no multiple of `chunk` is padded with tokens that leave
    the state as it is (g = 0, beta = 0, k = 0) and cut off again.

    The walk over the chunks is the Pallas kernels' on a TPU at a shape
    they hold (`ops/pallas/delta_rule.holds`: widths that are multiples of
    128) and a `lax.scan` everywhere else, decided here from what the call
    sees; `interpret=True` asks for the kernels under the Pallas
    interpreter (the tests do, off the TPU).

    ONE SEQUENCE AT A TIME (`lax.map` over b): what the rule holds between
    its passes (`W`, `U`, the chunks' matrices, the carried states, and
    their cotangents) is a sequence's and does not grow with the batch;
    8192 tokens of 32 heads 128 wide hold about 2 GB that way. What the
    kernels' forward leaves for their backward (a state a chunk and
    `v_new`: 0.34 GB a sequence at that shape) is the batch's."""
    kernels = kernels_hold(q.shape[-1], v.shape[-1], chunk)
    if interpret and not kernels:
        raise ValueError(
            f"the delta rule's kernels do not hold d_k {q.shape[-1]}, d_v "
            f"{v.shape[-1]}, chunk {chunk}: widths must be multiples of 128")
    if interpret or (kernels and jax.default_backend() == "tpu"):
        one = functools.partial(_one_sequence_kernels, chunk, interpret)
    else:
        one = jax.checkpoint(functools.partial(_one_sequence, chunk=chunk))
    return lax.map(lambda row: one(*row), (q, k, v, g, beta))


def _chunk_operands(q, k, v, g, beta, *, chunk: int):
    """Everything of one sequence's rule that does not read the state, for
    all chunks at once: q, k (h, t, d_k), v (h, t, d_v), g, beta (h, t) ->
    [W | U] (h, n, C, d_k + d_v), attn (h, n, C, C), q_in and k_out (h, n,
    C, d_k), G_end (h, n), float32, the length padded to n chunks of C."""
    h, t, dk = q.shape
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
    dot = functools.partial(_dot, dtype)

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
    WU = solve_unit_lower(A, rhs)
    # what the chunk's own rows give each other, and what they hand the
    # state
    attn = dot("hnik,hnjk->hnij", q, k) * decay             # lower, incl.
    q_in = q.astype(jnp.float32) * jnp.exp(G)[..., None]
    G_end = G[..., -1]                                       # (h, n)
    k_out = k.astype(jnp.float32) * jnp.exp(G_end[..., None] - G)[..., None]
    return WU, attn, q_in, k_out, G_end


# ---- the solve: (I + A)^-1 [W | U] by block inverses ----

# a diagonal block this small is inverted row by row
SOLVE_BASE = 8


@jax.custom_vjp
def solve_unit_lower(A: jax.Array, rhs: jax.Array) -> jax.Array:
    """(I + A)^-1 rhs for A (..., C, C) of which the strictly lower part is
    read, rhs (..., C, m), float32. The inverse is formed (`_unit_lower_
    inverse`) and applied as one product; its cotangents are two more
    products with the inverse, where a triangular solve's are a solve."""
    return _solve_fwd(A, rhs)[0]


def _solve_fwd(A, rhs):
    T = _unit_lower_inverse(A)
    X = jnp.einsum("...ij,...jm->...im", T, rhs,
                   precision=lax.Precision.HIGHEST)
    return X, (T, X)


def _solve_bwd(saved, dX):
    T, X = saved
    drhs = jnp.einsum("...ji,...jm->...im", T, dX,
                      precision=lax.Precision.HIGHEST)
    dA = -jnp.einsum("...im,...jm->...ij", drhs, X,
                     precision=lax.Precision.HIGHEST)
    return jnp.tril(dA, -1), drhs


solve_unit_lower.defvjp(_solve_fwd, _solve_bwd)


def _unit_lower_inverse(A: jax.Array) -> jax.Array:
    """(I + A)^-1 for the strictly lower part of A (..., C, C), float32, by
    halves: [[P, 0], [R, Q]]^-1 = [[P^-1, 0], [-Q^-1 R P^-1, Q^-1]], from
    diagonal blocks of at most `SOLVE_BASE` rows (C halved while it is even
    and larger), which forward substitution inverts a row at a time, up to
    the whole. Every step is bounded by the inverse it builds and by A,
    which a product of powers of A is not (module docstring).

    The matrices are 64 wide and there are thousands: the work is done with
    the batch in the minor (lane) dimension and a level's blocks side by
    side, (blocks, s, s, batch), as elementwise float32 products and sums
    over whole arrays: exact float32, no matrix-unit pass over tiles an
    eighth full, and a few dozen ops to trace whatever C is."""
    *lead, C, _ = A.shape
    A = jnp.moveaxis(A.reshape(-1, C, C), 0, -1)            # (C, C, batch)
    s = C
    while s % 2 == 0 and s > SOLVE_BASE:
        s //= 2
    # blocks of s rows: every `step`-th along the diagonal, `down` below it
    blocks = lambda s, step, down: jnp.stack([
        A[(i + down) * s:(i + down + 1) * s, i * s:(i + 1) * s]
        for i in range(0, C // s, step)])
    D = blocks(s, 1, 0)
    eye = jnp.eye(s, dtype=A.dtype)[:, :, None]
    T = jnp.zeros_like(D)
    for i in range(s):  # row i: e_i - A[i, :i] T[:i]; T's later rows are 0
        T = T.at[:, i].set(eye[i] - jnp.sum(D[:, i, :, None] * T, axis=1))
    while s < C:
        P, Q = T[0::2], T[1::2]
        below = -_batch_minor_product(Q, _batch_minor_product(
            blocks(s, 2, 1), P))
        T = jnp.concatenate([
            jnp.concatenate([P, jnp.zeros_like(P)], axis=2),
            jnp.concatenate([below, Q], axis=2)], axis=1)
        s *= 2
    return jnp.moveaxis(T[0], -1, 0).reshape(*lead, C, C)


def _batch_minor_product(X, Y):
    """X (blocks, a, b, batch), Y (blocks, b, c, batch) -> X Y."""
    return jnp.sum(X[:, :, :, None] * Y[:, None], axis=2)


def _dot(dtype, eq, x, y):
    return jnp.einsum(eq, x.astype(dtype), y.astype(dtype),
                      preferred_element_type=jnp.float32)


def _one_sequence(q, k, v, g, beta, *, chunk: int):
    """`gated_delta_rule` for one sequence, the walk a `lax.scan`
    rematerialised by chunk (the backward is JAX's transpose of this text;
    its residuals are the carried states, one a chunk): q, k (h, t, d_k),
    v (h, t, d_v), g, beta (h, t)."""
    h, t, dk = q.shape
    dv = v.shape[-1]
    dtype = v.dtype
    WU, attn, q_in, k_out, G_end = _chunk_operands(q, k, v, g, beta,
                                                   chunk=chunk)
    dot = functools.partial(_dot, dtype)

    @jax.checkpoint
    def one_chunk(S, c):
        W_c, U_c, attn_c, q_c, k_c, end_c = c
        v_new = U_c - dot("hik,hkv->hiv", W_c, S)
        o = dot("hik,hkv->hiv", q_c, S) + dot("hij,hjv->hiv", attn_c, v_new)
        S = (jnp.exp(end_c)[..., None, None] * S
             + dot("hik,hiv->hkv", k_c, v_new))
        return S, o.astype(dtype)

    chunk_first = lambda z: jnp.moveaxis(z, 1, 0)
    # the products' operands are kept in the products' dtype
    operand = lambda z: chunk_first(z.astype(dtype))
    S0 = jnp.zeros((h, dk, dv), jnp.float32)
    vma = tuple(jax.typeof(WU).vma)
    if vma:     # inside shard_map the carry varies over what its inputs do
        S0 = copy_to(S0, vma)
    S, o = lax.scan(one_chunk, S0, (
        operand(WU[..., :dk]), chunk_first(WU[..., dk:]), operand(attn),
        operand(q_in), operand(k_out), chunk_first(G_end)))
    o = jnp.moveaxis(o, 0, 1).reshape(h, -1, dv)
    return o[:, :t], S


# ---- the walk as the Pallas kernels (ops/pallas/delta_rule.py) ----

def _walk_operands(q, k, v, g, beta, *, chunk: int):
    """`_chunk_operands` as the kernels take them: [W | U] float32 as the
    solve leaves it, the other products' operands in the products' dtype,
    the chunk's whole decay exp(G_end)."""
    WU, attn, q_in, k_out, G_end = _chunk_operands(q, k, v, g, beta,
                                                   chunk=chunk)
    dtype = v.dtype
    return (WU, attn.astype(dtype), q_in.astype(dtype), k_out.astype(dtype),
            jnp.exp(G_end))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _one_sequence_kernels(chunk: int, interpret: bool, q, k, v, g, beta):
    """`_one_sequence` with the walk as one kernel call. Its backward is by
    hand for the walk alone (the reverse kernel, from the states and
    `v_new` the forward rule wrote out) and JAX's for `_walk_operands`,
    which it runs again: between forward and backward a sequence keeps its
    inputs, a state a chunk and `v_new`, nothing chunk-parallel."""
    return _kernels_fwd(chunk, interpret, q, k, v, g, beta)[0]


def _kernels_fwd(chunk, interpret, q, k, v, g, beta, residuals=False):
    h, t, _ = q.shape
    o, S, *saved = walk_forward(
        *_walk_operands(q, k, v, g, beta, chunk=chunk), out_dtype=v.dtype,
        residuals=residuals, interpret=interpret)
    return (o.reshape(h, -1, o.shape[-1])[:, :t], S), (q, k, v, g, beta,
                                                       *saved)


def _kernels_bwd(chunk, interpret, saved, cotangents):
    q, k, v, g, beta, S_in, v_new = saved
    do, dS = cotangents
    operands, transpose = jax.vjp(
        functools.partial(_walk_operands, chunk=chunk), q, k, v, g, beta)
    h, n, C, _ = v_new.shape
    do = jnp.pad(do, ((0, 0), (0, n * C - do.shape[1]), (0, 0)))
    return transpose(walk_backward(
        *operands, S_in, v_new, do.reshape(v_new.shape).astype(v_new.dtype),
        dS, interpret=interpret))


_one_sequence_kernels.defvjp(
    functools.partial(_kernels_fwd, residuals=True), _kernels_bwd)
