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

Where it is made. On a TPU at widths that are multiples of 128 the rule is
two Pallas kernels (ops/pallas/delta_rule.py, `gdn_rule_fwd` and
`gdn_rule_bwd`, a call for the whole batch's heads): XLA makes `G`, `A` and
the inverse `T = (I + A)^-1` (`_unit_lower_inverse`: exact float32, nothing
differentiates through it) and hands the kernels `q`, `k`, `v`, `[G; beta]`
and `T`; the kernels make `[W | U] = T rhs`, `attn`, `q_in`, `k_out` and the
decays per head and chunk in VMEM and walk the chunks with the state
resident. The backward kernel makes a chunk's operands again from the same
inputs, walks the chunks in reverse from the states the forward wrote out,
and transposes the operands by hand, `A`'s lines among them: between
forward and backward a head keeps its inputs, `T` and a state every other
chunk, and no chunk-parallel array besides `A` and `T` ever exists in HBM.
Everywhere else (off the TPU, other widths) the rule is the XLA text below,
`_chunk_operands` and a `lax.scan` rematerialised by chunk (its backward
JAX's transpose of the whole text; its residuals the carried states, one a
chunk): the tests' oracle for the kernels.

**A decay a CHANNEL** (Kimi Delta Attention, arXiv:2510.26692):
`channel_delta_rule` is the same rule with `g_t` a vector, `S~ =
Diag(exp(g_t)) S_{t-1}`, one decay a row of the state. `G` is then (C, d_k) a
chunk and the ratio `exp(G_i - G_j)` differs by channel, so `A` and the
scores are no longer `(K K^T) * D`; they are products of `K * exp(G - G_r)`
with `K * exp(G_r - G)` about reference rows `r`: a chunk is cut in
sub-blocks of `SUB` rows, a sub-block's rows take its FIRST row as `r`, and
against them every column up to the sub-block's end. For a column of an
earlier sub-block both factors are at most 1; for a column of the sub-block
itself the second is at most `exp(-(SUB - 1) g_min)`. **That relies on a
bounded gate**: with `g >= -5` (the `kda_lower_bound` the model publishes and
`parallel/kda.py` holds `g` to) the factor is at most `exp(75)` = 3.7e32,
inside float32 and bfloat16 alike; a gate without a bound overflows it. The
entries above the diagonal are such products too and are SELECTED away, never
multiplied. Everything else is the scalar rule's text: the solve, the walk
(`_walk_chunks`, the state's rows decayed each by its own `exp(G_C)`), the
padding, the precisions.

Where that one is made (PR 60). On a TPU at widths that are multiples of
128, chunks of 64 and sub-blocks of 16 the rule is three Pallas kernels
(ops/pallas/kda_rule.py, a call for the whole batch's heads): XLA makes `G`
(a cumsum) and hands it with `beta` as one float32 tile a chunk;
`kda_rule_pairs` makes `A` from a sub-block's two factors in VMEM; XLA
inverts it (`_unit_lower_inverse`, as the scalar rule's); `kda_rule_fwd`
makes the factors again, `attn`, `[W | U] = T rhs`, `q_in`, `k_out` and
walks the chunks with the state resident; `kda_rule_bwd` makes a chunk's
operands once more, walks back from the states the forward wrote out and
transposes the operands by hand (the decay's cotangent elementwise, a
channel's). No array the size of the factors, `(h, n, B, C, d_k)`, ever
exists in HBM on that path; between forward and backward a head keeps its
inputs, `T` and one state a block of chunks. Everywhere else it is the XLA
text, one sequence at a time: the kernels' oracle. `channel_delta_rule`
decides from what the call sees and says which on the program's tracer.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..obs.trace import current_tracer
from .collectives import copy_to
from .pallas import kda_rule
from .pallas.delta_rule import (ROWS, holds as kernels_hold, rule_backward,
                                rule_forward, sequences_a_call)

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
    d_v), the final state (..., d_k, d_v)), float32, one token at a time. A
    `g` of q's shape, (..., t, d_k), is a decay a CHANNEL: row c of the
    state decays by `exp(g_t[c])`."""
    f32 = lambda z: z.astype(jnp.float32)
    q, k, v, g, beta = map(f32, (q, k, v, g, beta))
    lead = q.shape[:-2]
    time_first = lambda z: jnp.moveaxis(z, len(lead), 0)
    a_channel = g.ndim == q.ndim

    def token(S, row):
        q_t, k_t, v_t, g_t, b_t = row
        S = (jnp.exp(g_t)[..., None] if a_channel
             else jnp.exp(g_t)[..., None, None]) * S
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

    The rule is the Pallas kernels' on a TPU at a shape they hold
    (`ops/pallas/delta_rule.holds`: widths that are multiples of 128) and
    the XLA text with a `lax.scan` everywhere else, decided here from what
    the call sees; `interpret=True` asks for the kernels under the Pallas
    interpreter (the tests do, off the TPU).

    The text runs ONE SEQUENCE AT A TIME (`lax.map` over b): what it holds
    between its passes (`W`, `U`, the chunks' matrices, the carried states,
    and their cotangents) is a sequence's and does not grow with the batch;
    8192 tokens of 32 heads 128 wide hold about 2 GB that way. To the
    kernels heads are all the same: a call takes the heads of as many
    sequences as its scalar tables hold (`sequences_a_call`: the whole
    batch of 2 x 8192 tokens), so that no sequence's inputs, outputs and
    residuals are copied in and out of a loop (23 ms of a 700 ms step at
    that shape); only `A` and the inverse's blocks, 0.6 GB a sequence, are
    made a sequence at a time. What the kernels' forward leaves for their
    backward (`T` and a state every other chunk: 0.27 GB a sequence at that
    shape) is the batch's."""
    kernels = kernels_hold(q.shape[-1], v.shape[-1], chunk)
    if interpret and not kernels:
        raise ValueError(
            f"the delta rule's kernels do not hold d_k {q.shape[-1]}, d_v "
            f"{v.shape[-1]}, chunk {chunk}: widths must be multiples of 128")
    if not interpret and not (kernels and jax.default_backend() == "tpu"):
        one = jax.checkpoint(functools.partial(_one_sequence, chunk=chunk))
        return lax.map(lambda row: one(*row), (q, k, v, g, beta))
    b, h, t, _ = q.shape
    group = sequences_a_call(b, h, -(-t // chunk))
    # (calls, a call's sequences x heads, ...)
    fold = lambda z: z.reshape(b // group, group * h, *z.shape[2:])
    call = functools.partial(_heads_kernels, chunk, interpret, group)
    # the caller's fusions end here and begin again after: with no loop
    # between them and the kernels, XLA otherwise lays the caller's float32
    # intermediates out for the kernels' operands and copies them (7 ms of
    # that step; half of it is left)
    q, k, v, g, beta = lax.optimization_barrier((q, k, v, g, beta))
    o, S = lax.map(lambda rows: call(*rows), tuple(map(fold, (q, k, v, g,
                                                              beta))))
    return lax.optimization_barrier((o.reshape(b, h, *o.shape[2:]),
                                     S.reshape(b, h, *S.shape[2:])))


def _in_chunks(q, k, v, g, beta, *, chunk: int):
    """One sequence's inputs, q, k (h, t, d_k), v (h, t, d_v), g, beta (h,
    t), as n chunks of C tokens, (h, n, C, ...), g and beta float32; the
    length padded with tokens that leave the state as it is."""
    h, t, _ = q.shape
    pad = -t % chunk
    if pad:
        rows = lambda z: jnp.pad(z, ((0, 0), (0, pad))
                                 + ((0, 0),) * (z.ndim - 2))
        q, k, v, g, beta = map(rows, (q, k, v, g, beta))
    chunks = lambda z: z.reshape(h, (t + pad) // chunk, chunk, *z.shape[2:])
    return (chunks(q), chunks(k), chunks(v), chunks(g.astype(jnp.float32)),
            chunks(beta.astype(jnp.float32)))


def _chunk_matrix(k, G, beta):
    """k (h, n, C, d_k) in the products' dtype, G (the running sum of g
    inside a chunk) and beta (h, n, C) float32 -> (decay, k_beta, A): the
    chunk's decays exp(G_i - G_j) on and under the diagonal, beta k, and A
    (module docstring), float32."""
    i = jnp.arange(G.shape[-1])
    # exp of a masked difference: G_i - G_j <= 0 wherever i >= j
    diff = G[..., :, None] - G[..., None, :]
    decay = jnp.exp(jnp.where(i[:, None] >= i[None, :], diff, -jnp.inf))
    k_beta = k.astype(jnp.float32) * beta[..., None]
    A = jnp.where(i[:, None] > i[None, :],
                  _dot(k.dtype, "hnik,hnjk->hnij", k_beta, k) * decay, 0.0)
    return decay, k_beta, A


def _chunk_operands(q, k, v, g, beta, *, chunk: int):
    """Everything of one sequence's rule that does not read the state, for
    all chunks at once: q, k (h, t, d_k), v (h, t, d_v), g, beta (h, t) ->
    [W | U] (h, n, C, d_k + d_v), attn (h, n, C, C), q_in and k_out (h, n,
    C, d_k), G_end (h, n), float32, the length padded to n chunks of C."""
    q, k, v, g, beta = _in_chunks(q, k, v, g, beta, chunk=chunk)
    dot = functools.partial(_dot, v.dtype)

    G = jnp.cumsum(g, axis=-1)                              # (h, n, C)
    decay, k_beta, A = _chunk_matrix(k, G, beta)
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
    WU, attn, q_in, k_out, G_end = _chunk_operands(q, k, v, g, beta,
                                                   chunk=chunk)
    return _walk_chunks(WU, attn, q_in, k_out, G_end, q.shape[1], v.dtype)


def _walk_chunks(WU, attn, q_in, k_out, G_end, t: int, dtype):
    """The walk over one sequence's chunks from `_chunk_operands`' (or
    `_channel_chunk_operands`') arrays, a `lax.scan` rematerialised by
    chunk: -> (o (h, t, d_v) in `dtype`, the final state (h, d_k, d_v)).
    `G_end` (h, n) decays the whole state, (h, n, d_k) each of its rows."""
    h, dk = WU.shape[0], q_in.shape[-1]
    dv = WU.shape[-1] - dk
    dot = functools.partial(_dot, dtype)

    @jax.checkpoint
    def one_chunk(S, c):
        W_c, U_c, attn_c, q_c, k_c, end_c = c
        v_new = U_c - dot("hik,hkv->hiv", W_c, S)
        o = dot("hik,hkv->hiv", q_c, S) + dot("hij,hjv->hiv", attn_c, v_new)
        decay = (jnp.exp(end_c)[..., None, None] if end_c.ndim == 1
                 else jnp.exp(end_c)[..., None])
        S = decay * S + dot("hik,hiv->hkv", k_c, v_new)
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


# ---- a decay a channel (module docstring) ----

# rows of a sub-block: what shares one reference row. The bound on the gate
# (`g >= -5`) times `SUB - 1` rows must stay inside float32's exponent
SUB = 16


def channel_delta_rule(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                       beta: jax.Array, chunk: int = CHUNK, sub: int = SUB,
                       interpret: bool = False
                       ) -> Tuple[jax.Array, jax.Array]:
    """The rule with a decay a channel, in chunks (module docstring). q, k
    (b, h, t, d_k), v (b, h, t, d_v) in the compute dtype; g (b, h, t, d_k)
    float32, `-5 <= g <= 0` (the bound the sub-blocks rely on); beta (b, h,
    t) float32. Returns (o (b, h, t, d_v) in v's dtype, the final state (b,
    h, d_k, d_v) float32). A length that is no multiple of `chunk` is
    padded as the scalar rule's.

    The rule is the Pallas kernels' on a TPU at a shape they hold
    (`ops/pallas/kda_rule.holds`: widths that are multiples of 128, chunks
    of 64 in sub-blocks of 16) and the XLA text, one sequence at a time,
    everywhere else: decided here from what the call sees, and said on the
    program's tracer (the instant `kda_rule`, once a trace). `interpret=
    True` asks for the kernels under the Pallas interpreter (the tests do,
    off the TPU). To the kernels heads are all the same: one call takes the
    whole batch's."""
    if chunk % sub:
        raise ValueError(f"sub-blocks of {sub} rows do not divide a chunk "
                         f"of {chunk}")
    b, h, t, dk = q.shape
    dv = v.shape[-1]
    held = kda_rule.holds(dk, dv, chunk, sub)
    if interpret and not held:
        raise ValueError(
            f"the channel rule's kernels do not hold d_k {dk}, d_v {dv}, "
            f"chunk {chunk}, sub {sub}: widths must be multiples of 128, a "
            f"chunk 64 rows in sub-blocks of 16")
    kernels = interpret or (held and jax.default_backend() == "tpu")
    tracer = current_tracer()
    if tracer is not None:
        tracer.instant(
            "kda_rule", path="kernel" if kernels else "xla", heads=b * h,
            tokens=t, d_k=dk, d_v=dv, chunk=chunk, sub=sub,
            dtype=str(q.dtype), block=kda_rule.blocks(
                b * h, -(-t // chunk)) if kernels else None)
    if not kernels:
        one = jax.checkpoint(functools.partial(_one_sequence_channel,
                                               chunk=chunk, sub=sub))
        return lax.map(lambda row: one(*row), (q, k, v, g, beta))
    heads = lambda z: z.reshape(b * h, *z.shape[2:])
    # the caller's fusions end here and begin again after, as the scalar
    # rule's
    q, k, v, g, beta = lax.optimization_barrier((q, k, v, g, beta))
    o, S = _channel_kernels(chunk, sub, interpret, b, *map(
        heads, (q, k, v, g, beta)))
    return lax.optimization_barrier((o.reshape(b, h, t, dv),
                                     S.reshape(b, h, dk, dv)))


def _running_decay(g: jax.Array) -> jax.Array:
    """`G`: the running sum of g inside a chunk (inclusive), g (h, n, C,
    d_k) float32. Every decay ratio of a chunk is the exponential of a
    DIFFERENCE of two of its rows, so its rounding is the ratio's relative
    error: float32 (benchmark/tools/kda_control.py rounds it to bfloat16
    and the cell's check fails)."""
    return jnp.cumsum(g, axis=2)


def _channel_chunk_operands(q, k, v, g, beta, *, chunk: int, sub: int):
    """`_chunk_operands` with g (h, t, d_k): the same arrays, `G_end` (h,
    n, d_k). `A` and `attn` are made a sub-block of rows at a time about
    the sub-block's first row (module docstring)."""
    q, k, v, g, beta = _in_chunks(q, k, v, g, beta, chunk=chunk)
    h, n, C, dk = k.shape
    B = C // sub
    dot = functools.partial(_dot, v.dtype)
    f32 = lambda z: z.astype(jnp.float32)

    with jax.named_scope("operands"):
        G = _running_decay(g)                               # (h, n, C, d_k)
        blocks = lambda z: z.reshape(h, n, B, sub, dk)
        G_ref = blocks(G)[:, :, :, :1]                      # (h, n, B, 1, .)
        # a sub-block's rows about its first row: exp(G_i - G_r) <= 1
        rows_in = jnp.exp(blocks(G) - G_ref)
        # every column up to the sub-block's end about that row: at most 1
        # before the sub-block, at most exp(-(sub - 1) g_min) inside it;
        # the columns past it are masked (there the exponent has no bound)
        i = jnp.arange(C)
        seen = i[None, :] < (jnp.arange(B)[:, None] + 1) * sub  # (B, C)
        cols_out = jnp.exp(jnp.where(seen[..., None],
                                     G_ref - G[:, :, None], -jnp.inf))
        k_cols = f32(k)[:, :, None] * cols_out              # (h, n, B, C, .)
        k_beta = f32(k) * beta[..., None]
        pairs = lambda rows: dot(
            "hnbik,hnbjk->hnbij", blocks(rows) * rows_in,
            k_cols).reshape(h, n, C, C)
        A = jnp.where(i[:, None] > i[None, :], pairs(k_beta), 0.0)
        attn = jnp.where(i[:, None] >= i[None, :], pairs(f32(q)), 0.0)
        rhs = jnp.concatenate([k_beta * jnp.exp(G),
                               f32(v) * beta[..., None]], axis=-1)
        WU = solve_unit_lower(A, rhs)
        q_in = f32(q) * jnp.exp(G)
        G_end = G[:, :, -1]                                 # (h, n, d_k)
        k_out = f32(k) * jnp.exp(G_end[:, :, None] - G)
    return WU, attn, q_in, k_out, G_end


def _one_sequence_channel(q, k, v, g, beta, *, chunk: int, sub: int):
    """`channel_delta_rule` for one sequence: q, k, g (h, t, d_k), v (h, t,
    d_v), beta (h, t)."""
    WU, attn, q_in, k_out, G_end = _channel_chunk_operands(
        q, k, v, g, beta, chunk=chunk, sub=sub)
    with jax.named_scope("walk"):
        return _walk_chunks(WU, attn, q_in, k_out, G_end, q.shape[1],
                            v.dtype)


# ---- the rule as the Pallas kernels (ops/pallas/delta_rule.py) ----

def _kernel_inputs(q, k, v, g, beta, *, chunk: int):
    """q ... beta (heads, t, .) as the kernels take them: q, k, v in chunks
    and [G; beta] as the rows of one float32 tile a chunk, (heads, n,
    `ROWS`, C), G the running sum of g inside a chunk."""
    q, k, v, g, beta = _in_chunks(q, k, v, g, beta, chunk=chunk)
    gb = jnp.pad(jnp.stack([jnp.cumsum(g, axis=-1), beta], axis=2),
                 ((0, 0), (0, 0), (0, ROWS - 2), (0, 0)))
    return q, k, v, gb


def _inverses(sequences: int, k, gb):
    """T = (I + A)^-1 (heads, n, C, C) for the heads of `sequences`
    sequences, from k and [G; beta] as `_kernel_inputs` leaves them, made
    a sequence at a time (`A` and the inverse's blocks are 0.6 GB a
    sequence of 32 heads and 128 chunks)."""
    apart = lambda z: z.reshape(sequences, -1, *z.shape[1:])
    T = lax.map(lambda one: _unit_lower_inverse(_chunk_matrix(*one)[2]),
                (apart(k), apart(gb[:, :, 0]), apart(gb[:, :, 1])))
    return T.reshape(-1, *T.shape[2:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _heads_kernels(chunk: int, interpret: bool, sequences: int, q, k, v, g,
                   beta):
    """`_one_sequence` as one kernel call, for the heads of `sequences`
    sequences, (heads, t, .). Its backward is one more, by hand (the
    operands made again in VMEM, the reverse walk from the states the
    forward wrote out, the operands' transpose): between forward and
    backward a head keeps its inputs, T and a state every other chunk."""
    return _kernels_fwd(chunk, interpret, sequences, q, k, v, g, beta)[0]


def _kernels_fwd(chunk, interpret, sequences, q, k, v, g, beta,
                 residuals=False):
    h, t, _ = q.shape
    inputs = _kernel_inputs(q, k, v, g, beta, chunk=chunk)
    T = _inverses(sequences, inputs[1], inputs[3])
    o, S, *S_in = rule_forward(*inputs, T, residuals=residuals,
                               interpret=interpret)
    return (o.reshape(h, -1, o.shape[-1])[:, :t], S), (q, k, v, g, beta, T,
                                                       *S_in)


def _kernels_bwd(chunk, interpret, sequences, saved, cotangents):
    q, k, v, g, beta, T, S_in = saved
    do, dS = cotangents
    h, t, _ = q.shape
    n, C = T.shape[1:3]
    # the inputs in chunks again (a pad and a reshape; G a cumsum): what
    # the forward made of them was not kept
    inputs = _kernel_inputs(q, k, v, g, beta, chunk=chunk)
    do = jnp.pad(do, ((0, 0), (0, n * C - t), (0, 0))).reshape(
        inputs[2].shape)
    dq, dk, dv, dgb = rule_backward(*inputs, T, S_in, do.astype(v.dtype), dS,
                                    interpret=interpret)
    tokens = lambda z: z.reshape(h, n * C, *z.shape[3:])[:, :t]
    # G is the running sum of g inside a chunk: its transpose runs back
    dg = jnp.flip(jnp.cumsum(jnp.flip(dgb[:, :, 0], -1), axis=-1), -1)
    return (tokens(dq), tokens(dk), tokens(dv), tokens(dg).astype(g.dtype),
            tokens(dgb[:, :, 1]).astype(beta.dtype))


_heads_kernels.defvjp(
    functools.partial(_kernels_fwd, residuals=True), _kernels_bwd)


# ---- a decay a channel as the Pallas kernels (ops/pallas/kda_rule.py) ----

def _channel_kernel_inputs(q, k, v, g, beta, *, chunk: int):
    """q ... beta (heads, t, .) as the channel kernels take them: q, k, v in
    chunks and `Gb` (heads, n, C + `ROWS`, d_k) float32: rows 0 .. C - 1 the
    running sum of g inside a chunk, row C beta in its first C lanes."""
    q, k, v, g, beta = _in_chunks(q, k, v, g, beta, chunk=chunk)
    tile = jnp.pad(beta[:, :, None], ((0, 0), (0, 0), (0, ROWS - 1),
                                      (0, q.shape[-1] - chunk)))
    return q, k, v, jnp.concatenate([_running_decay(g), tile], axis=2)


def _channel_inverses(sequences: int, k, Gb, *, sub: int, interpret: bool):
    """T = (I + A)^-1 (heads, n, C, C) for the heads of `sequences`
    sequences: `A` from the pairs kernel (one call), its inverse XLA's, a
    sequence at a time (the inverse's blocks are 0.3 GB a sequence of 32
    heads and 64 chunks)."""
    A = kda_rule.rule_pairs(k, Gb, sub=sub, interpret=interpret)
    T = lax.map(_unit_lower_inverse,
                A.reshape(sequences, -1, *A.shape[1:]))
    return T.reshape(A.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _channel_kernels(chunk: int, sub: int, interpret: bool, sequences: int,
                     q, k, v, g, beta):
    """`_one_sequence_channel` as kernel calls for the heads of `sequences`
    sequences, (heads, t, .). Its backward is one more, by hand: between
    forward and backward a head keeps its inputs, T and one state a block of
    chunks (0.27 GB a layer at 32 heads x 4096 tokens)."""
    return _channel_fwd(chunk, sub, interpret, sequences, q, k, v, g,
                        beta)[0]


def _channel_fwd(chunk, sub, interpret, sequences, q, k, v, g, beta,
                 residuals=False):
    h, t, _ = q.shape
    inputs = _channel_kernel_inputs(q, k, v, g, beta, chunk=chunk)
    T = _channel_inverses(sequences, inputs[1], inputs[3], sub=sub,
                          interpret=interpret)
    o, St, *S_in = kda_rule.rule_forward(*inputs, T, sub=sub,
                                         residuals=residuals,
                                         interpret=interpret)
    # the kernels keep the state transposed (their docstring)
    return (o.reshape(h, -1, o.shape[-1])[:, :t], St.swapaxes(1, 2)), (
        q, k, v, g, beta, T, *S_in)


def _channel_bwd(chunk, sub, interpret, sequences, saved, cotangents):
    q, k, v, g, beta, T, S_in = saved
    do, dS = cotangents
    h, t, _ = q.shape
    n, C = T.shape[1:3]
    # the inputs in chunks again (a pad and a reshape; G a cumsum): what
    # the forward made of them was not kept
    inputs = _channel_kernel_inputs(q, k, v, g, beta, chunk=chunk)
    do = jnp.pad(do, ((0, 0), (0, n * C - t), (0, 0))).reshape(
        inputs[2].shape)
    dq, dk, dv, dG, dbeta = kda_rule.rule_backward(
        *inputs, T, S_in, do.astype(v.dtype), dS.swapaxes(1, 2), sub=sub,
        interpret=interpret)
    tokens = lambda z: z.reshape(h, n * C, *z.shape[3:])[:, :t]
    # G is the running sum of g inside a chunk: its transpose runs back
    dg = lax.cumsum(dG, axis=2, reverse=True)
    return (tokens(dq), tokens(dk), tokens(dv), tokens(dg).astype(g.dtype),
            tokens(dbeta[:, :, 0]).astype(beta.dtype))


_channel_kernels.defvjp(
    functools.partial(_channel_fwd, residuals=True), _channel_bwd)
