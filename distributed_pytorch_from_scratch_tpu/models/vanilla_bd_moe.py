"""The plain float32 reference of the `bd_moe` family (models/bd_moe.py),
beside `models/vanilla_conv_moe.py`'s: the whole model in straightforward
`jax.numpy` at precision "highest", consuming the parameter pytree
`BlockDiffusionMoETransformer.init` produces. **The 2L rows `[xt ; x0]` and
their positions `[0..L-1 ; 0..L-1]` are built in the open, and the mask is a
boolean matrix from the three rules** (`bd_mask`; scores in blocks of 512
query rows so that it fits at 2 x 8192 rows); q/k norms per head, then
half-split RoPE over the whole head at a row's POSITION; the softmax top-k
router normalised over the chosen; **the held experts applied one by one to
every row and masked by the weights**; the loss on the noised rows only,
`(1 / (b L)) sum_seq (1 / p) sum_{masked} CE`; each layer under
`jax.checkpoint`; gradients by `jax.grad`. It is HANDED the draw `(xt, m,
p)` as arrays and never makes one. No kernel, no sharding, no dispatch, no
scan: what tests/test_bd_moe.py holds the program to, leaf by leaf.
`benchmark/families/bd_moe.py` keeps a copy of its own (the yardstick does
not import the program's oracle).

Departures from the published description, each also in the benchmark
configuration's `assumed`: the block length, the noise schedule and the
objective's normalisation are the family's convention (the published
configuration gives none); no balance loss; the sum of the chosen scores
gets nothing added (the program's `SharedRoutedFFN` adds 1e-20).
"""

from __future__ import annotations

import math
from types import SimpleNamespace

import jax
import jax.numpy as jnp
from jax import lax

from ..config import ModelConfig

QUERY_BLOCK = 512
HIGHEST = lax.Precision.HIGHEST


def sizes_of(cfg: ModelConfig) -> SimpleNamespace:
    return SimpleNamespace(
        n_head=cfg.num_heads, n_kv_head=cfg.kv_heads,
        head_dim=cfg.bd_moe.head_dim, top_k=cfg.moe_top_k,
        vocab=cfg.vocab_size, block_length=cfg.bd_moe.block_length)


def vanilla_loss(cfg: ModelConfig, params, x0, position_ids, xt, m, p):
    """The loss `BlockDiffusionMoETransformer.loss_shard` computes on the
    draw `(xt, m, p)`, plainly."""
    bd = cfg.bd_moe
    return reference_loss_routed(
        params, x0, position_ids, xt, m, p, sizes=sizes_of(cfg),
        expert_offset=bd.expert_offset, rope_theta=cfg.rope_theta,
        eps=bd.rms_norm_eps)[0]


# ---- the plain reference ----

def bd_mask(q_rows, k_rows, L: int, B: int):
    """[query row, key row] live, for rows of the 2L a sequence has: row
    r < L is noised position r, row L + r is clean position r."""
    qi, kj = q_rows[:, None], k_rows[None, :]
    q_noised, k_noised = qi < L, kj < L
    q_blk, k_blk = (qi % L) // B, (kj % L) // B
    return ((q_noised & k_noised & (k_blk == q_blk))
            | (q_noised & ~k_noised & (k_blk < q_blk))
            | (~q_noised & ~k_noised & (k_blk <= q_blk)))


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _norm(p, x, eps):
    return (x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                          + eps) * p["scale"])


def _rope(x, cos, sin):
    """Half-split pairs (x_i, x_{i + dim/2}) of x (b, heads, t, dim);
    cos/sin (b, 1, t, dim/2)."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(lp, y, cos, sin, s, eps, live):
    """`live(query rows, key rows)` -> bool matrix: the mask."""
    b, t, _ = y.shape
    h = s.head_dim
    heads = lambda z, n: z.reshape(b, t, n, h).transpose(0, 2, 1, 3)
    q = heads(_mm(y, lp["wq"]["weight"]), s.n_head)
    k = heads(_mm(y, lp["wk"]["weight"]), s.n_kv_head)
    v = heads(_mm(y, lp["wv"]["weight"]), s.n_kv_head)
    q = _rope(_norm(lp["q_norm"], q, eps), cos, sin)
    k = _rope(_norm(lp["k_norm"], k, eps), cos, sin)
    group = s.n_head // s.n_kv_head         # query head h reads h // group
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scale = 1.0 / math.sqrt(h)

    @jax.checkpoint
    def rows(q_rows, first):
        n = q_rows.shape[2]
        scores = jnp.einsum("bhqd,bhkd->bhqk", q_rows, k,
                            precision=HIGHEST) * scale
        seen = live(first + jnp.arange(n), jnp.arange(t))
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", probs, v, precision=HIGHEST)

    # one block of query rows at a time, the last one shorter
    step = min(QUERY_BLOCK, t)
    whole = t - t % step
    blocks = q[:, :, :whole].reshape(b, s.n_head, whole // step, step, h)
    o = lax.map(lambda block: rows(*block),
                (jnp.moveaxis(blocks, 2, 0), jnp.arange(0, whole, step)))
    o = jnp.moveaxis(o, 0, 2).reshape(b, s.n_head, whole, h)
    if whole < t:
        o = jnp.concatenate([o, rows(q[:, :, whole:], whole)], axis=2)
    o = o.transpose(0, 2, 1, 3).reshape(b, t, s.n_head * h)
    return _mm(o, lp["wo"]["weight"])


def _expert_ffn(mp, y, s, expert_offset: int):
    """sum over the experts HELD of w_e E_e(y), each expert applied to every
    row and masked by its weight (no shared expert); and how many (row,
    choice) pairs chose each routed expert."""
    b, t, d = y.shape
    x = y.reshape(b * t, d)
    score = jax.nn.softmax(_mm(x, mp["router"]), axis=-1)     # all routed
    _, chosen = lax.top_k(score, s.top_k)
    w = jnp.take_along_axis(score, chosen, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True)

    @jax.checkpoint
    def one(acc, expert):
        e, gate, up, down = expert
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)
        out = _mm(jax.nn.silu(_mm(x, gate)) * _mm(x, up), down)
        return acc + w_e[:, None] * out, None

    held = mp["gate"].shape[0]
    out, _ = lax.scan(one, jnp.zeros_like(x),
                      (expert_offset + jnp.arange(held), mp["gate"],
                       mp["up"], mp["down"]))
    routed = jnp.zeros(score.shape[-1]).at[chosen.reshape(-1)].add(1.0)
    return out.reshape(b, t, d), routed


def reference_hidden(params, rows, positions, *, sizes, expert_offset: int,
                     rope_theta: float, eps: float, live):
    """(the last layer's output for `rows` (b, t) at `positions` under the
    mask `live`, routed (layers, routed experts)), float32."""
    s = sizes
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    theta = 1.0 / (rope_theta ** (
        jnp.arange(0, s.head_dim, 2, dtype=jnp.float32) / s.head_dim))
    ang = positions.astype(jnp.float32)[:, None, :, None] * theta
    cos, sin = jnp.cos(ang), jnp.sin(ang)

    @jax.checkpoint
    def layer(x, lp):
        x = x + _attention(lp, _norm(lp["norm1"], x, eps), cos, sin, s, eps,
                           live)
        out, routed = _expert_ffn(lp["moe"], _norm(lp["norm2"], x, eps), s,
                                  expert_offset)
        return x + out, routed

    x = params["embedding"]["weight"][rows]
    routed = []
    n_layers = jax.tree.leaves(params["layers"])[0].shape[0]
    for i in range(n_layers):
        x, chose = layer(x, jax.tree.map(lambda a: a[i], params["layers"]))
        routed.append(chose)
    return x, lax.stop_gradient(jnp.stack(routed))


def reference_loss_routed(params, x0, position_ids, xt, m, p, *, sizes,
                          expert_offset: int, rope_theta: float,
                          eps: float):
    """(the block-diffusion loss over the slice on the draw `(xt, m, p)`,
    routed (layers, routed experts): the pairs each expert was chosen for,
    over the 2L rows), float32."""
    s = sizes
    b, L = x0.shape
    rows = jnp.concatenate([xt, x0], axis=1)                 # (b, 2L)
    positions = jnp.concatenate([position_ids, position_ids], axis=1)
    x, routed = reference_hidden(
        params, rows, positions, sizes=s, expert_offset=expert_offset,
        rope_theta=rope_theta, eps=eps,
        live=lambda q, k: bd_mask(q, k, L, s.block_length))
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    logits = _mm(_norm(params["norm"], x[:, :L], eps),
                 params["lm_head"]["weight"][:, :s.vocab])
    ce = (jax.nn.logsumexp(logits, axis=-1)
          - jnp.take_along_axis(logits, x0[..., None], axis=-1)[..., 0])
    weight = m.astype(jnp.float32) / p[:, None]
    return jnp.sum(ce * weight) / (b * L), routed
