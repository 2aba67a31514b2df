"""The plain float32 reference of the `conv_moe` family (models/conv_moe.py),
beside `models/vanilla_gdn_moe.py`'s: the whole model in straightforward
`jax.numpy`, consuming the parameter pytree `ConvMoETransformer.init`
produces. The layers are LOOPED over `layer_types` (`layers_in_order` hands
out the program's stacked layers one by one; what KIND a layer is, and
whether its FFN is dense, is read from the configuration); **the
convolution as three shifted products**; full score matrices in blocks of
512 query rows; q/k norms per head, then half-split RoPE over the whole
head; the sigmoid top-k router with its selection bias; **the held experts
applied one by one to every token and masked by the weights**; the head
tied to the embedding; each layer under `jax.checkpoint`; gradients by
`jax.grad`. No kernel, no sharding, no dispatch, no scan over periods: what
tests/test_conv_moe.py holds the program to, leaf by leaf.
`benchmark/families/conv_moe.py` keeps a copy of its own (the yardstick does
not import the program's oracle).

Departures from the published description, each also in the benchmark
configuration's `assumed`: the selection bias is whatever the tree holds
(zeros from `init`; nothing updates it); no balance loss; the sum of the
chosen scores gets the published 1e-6 (the program's `SharedRoutedFFN` adds
1e-20: a weight moves by under 5e-7 relative).
"""

from __future__ import annotations

import math
from types import SimpleNamespace

import jax
import jax.numpy as jnp
from jax import lax

from ..config import IGNORE_INDEX, ModelConfig
from .conv_moe import layer_blocks, layers_in_order

QUERY_BLOCK = 512


def sizes_of(cfg: ModelConfig) -> SimpleNamespace:
    cm = cfg.conv_moe
    return SimpleNamespace(
        n_head=cfg.num_heads, n_kv_head=cfg.kv_heads, head_dim=cfg.head_dim,
        top_k=cfg.moe_top_k, vocab=cfg.vocab_size,
        layer_types=tuple(cm.layer_types), n_dense=cm.num_dense_layers,
        scaling=cm.routed_scaling_factor)


def vanilla_loss(cfg: ModelConfig, params, input_ids, target_ids,
                 position_ids):
    """The loss `ConvMoETransformer.loss_shard` computes, plainly."""
    cm = cfg.conv_moe
    return reference_loss_routed(
        params, input_ids, target_ids, position_ids, sizes=sizes_of(cfg),
        expert_offset=cm.expert_offset, rope_theta=cfg.rope_theta,
        eps=cm.norm_eps)[0]


# ---- the plain reference ----

def _norm(p, x, eps):
    return (x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                          + eps) * p["scale"])


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _short_conv(p, y):
    """[B | C | u] = y W_in; the taps over B * u as shifted products (tap
    `taps - 1` reads the token itself; zeros before the sequence); times C;
    W_out."""
    t = y.shape[1]
    B, C, u = (y @ p["w_in"][:, i] for i in range(3))
    h = B * u
    taps = p["conv"].shape[-1]
    c = sum(p["conv"][:, j]
            * jnp.pad(h, ((0, 0), (taps - 1 - j, 0), (0, 0)))[:, :t]
            for j in range(taps))
    return (C * c) @ p["w_out"]


def _rope(x, cos, sin):
    """Half-split pairs (x_i, x_{i + dim/2}) of x (b, heads, t, dim);
    cos/sin (b, 1, t, dim/2)."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(lp, y, cos, sin, s, eps):
    b, t, _ = y.shape
    h = s.head_dim
    heads = lambda z, n: z.reshape(b, t, n, h).transpose(0, 2, 1, 3)
    q = heads(y @ lp["wq"]["weight"], s.n_head)
    k = heads(y @ lp["wk"]["weight"], s.n_kv_head)
    v = heads(y @ lp["wv"]["weight"], s.n_kv_head)
    q = _rope(_norm(lp["q_norm"], q, eps), cos, sin)
    k = _rope(_norm(lp["k_norm"], k, eps), cos, sin)
    group = s.n_head // s.n_kv_head         # query head h reads h // group
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scale = 1.0 / math.sqrt(h)

    @jax.checkpoint
    def rows(q_rows, first):
        n = q_rows.shape[2]
        scores = jnp.einsum("bhqd,bhkd->bhqk", q_rows, k) * scale
        seen = (first + jnp.arange(n))[:, None] >= jnp.arange(t)[None, :]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", probs, v)

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
    return o @ lp["wo"]["weight"]


def _expert_ffn(mp, y, s, expert_offset: int):
    """sum over the experts HELD of w_e E_e(y), each expert applied to every
    token and masked by its weight (no shared expert); and how many (token,
    choice) pairs chose each routed expert."""
    b, t, d = y.shape
    x = y.reshape(b * t, d)
    score = jax.nn.sigmoid(x @ mp["router"])                  # all routed
    _, chosen = lax.top_k(score + mp["bias"], s.top_k)
    w = jnp.take_along_axis(score, chosen, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6) * s.scaling

    @jax.checkpoint
    def one(acc, expert):
        e, gate, up, down = expert
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)
        return acc + w_e[:, None] * _swiglu(x, gate, up, down), None

    held = mp["gate"].shape[0]
    out, _ = lax.scan(one, jnp.zeros_like(x),
                      (expert_offset + jnp.arange(held), mp["gate"],
                       mp["up"], mp["down"]))
    routed = jnp.zeros(score.shape[-1]).at[chosen.reshape(-1)].add(1.0)
    return out.reshape(b, t, d), routed


def reference_loss_routed(params, input_ids, target_ids, position_ids, *,
                          sizes, expert_offset: int, rope_theta: float,
                          eps: float):
    """(mean cross-entropy over the slice, routed (expert layers, routed
    experts): the pairs each expert was chosen for, a row an expert layer
    in the order the layers run), float32."""
    s = sizes
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    theta = 1.0 / (rope_theta ** (
        jnp.arange(0, s.head_dim, 2, dtype=jnp.float32) / s.head_dim))
    ang = position_ids.astype(jnp.float32)[:, None, :, None] * theta
    cos, sin = jnp.cos(ang), jnp.sin(ang)

    def layer(kind, dense):
        @jax.checkpoint
        def run(x, lp):
            y = _norm(lp["norm1"], x, eps)
            if kind == "conv":
                x = x + _short_conv(lp["conv"], y)
            else:
                x = x + _attention(lp, y, cos, sin, s, eps)
            y = _norm(lp["norm2"], x, eps)
            if dense:
                return x + _swiglu(y, lp["gate_proj"]["weight"],
                                   lp["up_proj"]["weight"],
                                   lp["down_proj"]["weight"]), None
            out, routed = _expert_ffn(lp["moe"], y, s, expert_offset)
            return x + out, routed
        return run

    x = params["embedding"]["weight"][input_ids]
    stacked = layers_in_order(params, layer_blocks(s.layer_types, s.n_dense))
    routed = []
    for i, (name, lp) in enumerate(zip(s.layer_types, stacked, strict=True)):
        x, chose = layer(name, i < s.n_dense)(x, lp)
        if chose is not None:
            routed.append(chose)
    logits = (_norm(params["norm"], x, eps)
              @ params["embedding"]["weight"][:s.vocab].T)
    valid = target_ids != IGNORE_INDEX
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, jnp.where(valid, target_ids, 0)[..., None], axis=-1)[..., 0]
    loss = (jnp.sum(jnp.where(valid, lse - picked, 0.0))
            / jnp.maximum(jnp.sum(valid), 1))
    return loss, lax.stop_gradient(jnp.stack(routed))
