"""The plain float32 reference of the `early_moe` family
(models/early_moe.py), beside `models/vanilla_swa_moe.py`'s: the whole model
in straightforward `jax.numpy`, consuming the parameter pytree
`EarlyRouterMoETransformer.init` produces. The layers are LOOPED over
`sliding_window_layout` (`models/conv_moe.layers_in_order` hands out the
program's stacked layers one by one; what KIND a layer is is read from the
configuration); **the router's product from the layer's input written in
the open** (`logits = x W_r` on the residual stream as it enters the layer,
before `norm1`), the top-k logits and a softmax over the chosen; **the mask
as a dense boolean built from `i - j`** (`0 <= i - j`, and `i - j <
sliding_window_size` in a window layer); full score matrices in blocks of
512 query rows under `jax.checkpoint`; half-split RoPE over the whole head
in a window layer and NO positions in a full layer; **the held experts
applied one by one to every token and masked by the weights, with
`jnp.maximum(., 0)`**; each layer under `jax.checkpoint`; gradients by
`jax.grad`. No kernel, no sharding, no dispatch, no scan over periods: what
tests/test_early_moe.py holds the program to, leaf by leaf, under
`jax.default_matmul_precision("highest")`. `benchmark/families/early_moe.py`
keeps a copy of its own (the yardstick does not import the program's
oracle).

Departures from the published description (`modeling_smallthinker.py`,
llama.cpp's graph for the architecture; both from memory), each also in the
benchmark configuration's `assumed`: the router reads the layer's input
itself, before `input_layernorm` (`router_input="layer_input"`; the other
reading, the NORMED input, is `router_input="normed_input"`, and what every
other family does, the post-attention normed stream, is
`"post_attention"`: the tests' counter-examples); the weights are a softmax
over the chosen logits (`moe_primary_router_apply_softmax`; `norm_topk_prob`
is then already so); the experts' activation is ReLU; no secondary experts;
no balance loss; a job that holds a share of the experts adds what its
experts give, and nothing for the absent ones.
"""

from __future__ import annotations

import math
from types import SimpleNamespace

import jax
import jax.numpy as jnp
from jax import lax

from ..config import IGNORE_INDEX, ModelConfig
from .conv_moe import layer_blocks, layers_in_order
from .early_moe import KINDS

QUERY_BLOCK = 512


def sizes_of(cfg: ModelConfig) -> SimpleNamespace:
    em = cfg.early_moe
    return SimpleNamespace(
        n_head=cfg.num_heads, n_kv_head=cfg.kv_heads, head_dim=em.head_dim,
        top_k=cfg.moe_top_k, vocab=cfg.vocab_size,
        layout=tuple(em.sliding_window_layout),
        window=em.sliding_window_size)


def vanilla_loss(cfg: ModelConfig, params, input_ids, target_ids,
                 position_ids, **variant):
    """The loss `EarlyRouterMoETransformer.loss_shard` computes, plainly."""
    em = cfg.early_moe
    return reference_loss_routed(
        params, input_ids, target_ids, position_ids, sizes=sizes_of(cfg),
        expert_offset=em.expert_offset, rope_theta=cfg.rope_theta,
        eps=em.rms_norm_eps, **variant)[0]


# ---- the plain reference ----

def _norm(p, x, eps):
    return (x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                          + eps) * p["scale"])


def _rope(x, cos, sin):
    """Half-split pairs (x_i, x_{i + dim/2}) of x (b, heads, t, dim);
    cos/sin (b, 1, t, dim/2)."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(lp, y, cos, sin, s, window):
    """`window` None: a full layer (the whole past, no positions)."""
    b, t, _ = y.shape
    h = s.head_dim
    heads = lambda z, n: z.reshape(b, t, n, h).transpose(0, 2, 1, 3)
    q = heads(y @ lp["wq"]["weight"], s.n_head)
    k = heads(y @ lp["wk"]["weight"], s.n_kv_head)
    v = heads(y @ lp["wv"]["weight"], s.n_kv_head)
    if window is not None:
        q, k = _rope(q, cos, sin), _rope(k, cos, sin)
    group = s.n_head // s.n_kv_head         # query head h reads h // group
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scale = 1.0 / math.sqrt(h)

    @jax.checkpoint
    def rows(q_rows, first):
        n = q_rows.shape[2]
        scores = jnp.einsum("bhqd,bhkd->bhqk", q_rows, k) * scale
        back = (first + jnp.arange(n))[:, None] - jnp.arange(t)[None, :]
        live = back >= 0
        if window is not None:
            live = live & (back < window)
        probs = jax.nn.softmax(jnp.where(live, scores, -jnp.inf), axis=-1)
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


def _expert_ffn(mp, router_in, m, s, expert_offset: int, activation):
    """Sum over the experts HELD of w_e E_e(m), each expert applied to
    every token and masked by its weight, the weights from `router_in`; and
    how many (token, choice) pairs chose each routed expert."""
    b, t, d = m.shape
    x = m.reshape(b * t, d)
    logits = router_in.reshape(b * t, d) @ mp["router"]       # all routed
    top, chosen = lax.top_k(logits, s.top_k)
    w = jax.nn.softmax(top, axis=-1)                 # over the chosen

    @jax.checkpoint
    def one(acc, expert):
        e, gate, up, down = expert
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)
        return acc + w_e[:, None] * (
            (activation(x @ gate) * (x @ up)) @ down), None

    held = mp["gate"].shape[0]
    out, _ = lax.scan(one, jnp.zeros_like(x),
                      (expert_offset + jnp.arange(held), mp["gate"],
                       mp["up"], mp["down"]))
    routed = jnp.zeros(logits.shape[-1]).at[chosen.reshape(-1)].add(1.0)
    return out.reshape(b, t, d), routed


def reference_loss_routed(params, input_ids, target_ids, position_ids, *,
                          sizes, expert_offset: int, rope_theta: float,
                          eps: float, router_input: str = "layer_input",
                          activation=lambda z: jnp.maximum(z, 0),
                          logits_too: bool = False):
    """(mean cross-entropy over the slice, routed (layers, routed experts):
    the pairs each expert was chosen for, a row a layer in the order the
    layers run), float32; with `logits_too` the logits third."""
    s = sizes
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    theta = 1.0 / (rope_theta ** (
        jnp.arange(0, s.head_dim, 2, dtype=jnp.float32) / s.head_dim))
    ang = position_ids.astype(jnp.float32)[:, None, :, None] * theta
    cos, sin = jnp.cos(ang), jnp.sin(ang)

    def layer(flag):
        window = s.window if KINDS[flag] == "window" else None

        @jax.checkpoint
        def run(x, lp):
            h = _norm(lp["norm1"], x, eps)
            x1 = x + _attention(lp, h, cos, sin, s, window)
            m = _norm(lp["norm2"], x1, eps)
            router_in = {"layer_input": x, "normed_input": h,
                         "post_attention": m}[router_input]
            f, routed = _expert_ffn(lp["moe"], router_in, m, s,
                                    expert_offset, activation)
            return x1 + f, routed
        return run

    x = params["embedding"]["weight"][input_ids]
    stacked = layers_in_order(params,
                              layer_blocks(s.layout, 0, KINDS, "early_moe"))
    routed = []
    for flag, lp in zip(s.layout, stacked, strict=True):
        x, chose = layer(flag)(x, lp)
        routed.append(chose)
    logits = (_norm(params["norm"], x, eps)
              @ params["lm_head"]["weight"][:, :s.vocab])
    valid = target_ids != IGNORE_INDEX
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, jnp.where(valid, target_ids, 0)[..., None], axis=-1)[..., 0]
    loss = (jnp.sum(jnp.where(valid, lse - picked, 0.0))
            / jnp.maximum(jnp.sum(valid), 1))
    routed = lax.stop_gradient(jnp.stack(routed))
    return (loss, routed, logits) if logits_too else (loss, routed)
