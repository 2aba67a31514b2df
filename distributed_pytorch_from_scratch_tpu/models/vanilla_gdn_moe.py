"""The plain float32 reference of the `gdn_moe` family (models/gdn_moe.py),
beside `models/vanilla_mla_moe.py`'s: the whole model in straightforward
`jax.numpy`, consuming the parameter pytree `GdnMoETransformer.init`
produces. The layers are LOOPED, layer `i` full attention where `(i + 1) %
interval == 0`; **the gated delta rule runs token by token** (one `lax.scan`
over positions, under `jax.checkpoint` in blocks of 64 steps); full score
matrices in blocks of query rows; half-split RoPE on the leading slice of a
head; the softmax top-k router; **the held experts applied one by one to
every token and masked by the weights**; the gated shared expert; gradients
by `jax.grad`. No kernel, no sharding, no dispatch, no chunked rule: what
tests/test_gdn_moe.py holds the program to, leaf by leaf.
`benchmark/families/gdn_moe.py` keeps a copy of its own (the yardstick does
not import the program's oracle).
"""

from __future__ import annotations

import math
from types import SimpleNamespace

import jax
import jax.numpy as jnp
from jax import lax

from ..config import IGNORE_INDEX, ModelConfig

QUERY_BLOCK = 512
RULE_BLOCK = 64


def sizes_of(cfg: ModelConfig) -> SimpleNamespace:
    gm = cfg.gdn_moe
    return SimpleNamespace(
        n_head=cfg.num_heads, n_kv_head=cfg.kv_heads, head_dim=gm.head_dim,
        rotary_dim=gm.rotary_dim, d_k=gm.linear_key_head_dim,
        d_v=gm.linear_value_head_dim, interval=gm.full_attention_interval,
        top_k=cfg.moe_top_k, vocab=cfg.vocab_size)


def vanilla_loss(cfg: ModelConfig, params, input_ids, target_ids,
                 position_ids):
    """The loss `GdnMoETransformer.loss_shard` computes, plainly."""
    gm = cfg.gdn_moe
    return reference_loss(
        params, input_ids, target_ids, position_ids, sizes=sizes_of(cfg),
        expert_offset=gm.expert_offset, rope_theta=cfg.rope_theta,
        eps=gm.rms_norm_eps)


# ---- the plain reference ----

def _rms(x, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)


def _norm(p, x, eps):
    """Zero-centred: the stored weight is the offset from one."""
    return _rms(x, eps) * (1.0 + p["scale"])


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _rope_leading(x, cos, sin, rotary_dim):
    """Half-split pairs (x_i, x_{i + rotary_dim/2}) of the first
    `rotary_dim` dimensions of x (b, heads, t, dim); cos/sin (b, 1, t,
    rotary_dim/2)."""
    half = rotary_dim // 2
    a, b = x[..., :half], x[..., half:rotary_dim]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rotary_dim:]], axis=-1)


def _delta_rule(q, k, v, g, beta):
    """The gated delta rule one token at a time: q, k (b, t, H, d_k), v (b,
    t, H, d_v), g, beta (b, t, H) -> o (b, t, H, d_v). State (b, H, d_k,
    d_v) from zero."""
    b, t, H, dk = q.shape
    dv = v.shape[-1]

    def token(S, row):
        q_t, k_t, v_t, g_t, b_t = row
        S = jnp.exp(g_t)[..., None, None] * S
        delta = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t))
        S = S + k_t[..., :, None] * delta[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    @jax.checkpoint
    def block(S, rows):
        return lax.scan(token, S, rows)

    # time first, in blocks of RULE_BLOCK steps (the last one shorter)
    rows = tuple(jnp.moveaxis(z, 1, 0) for z in (q, k, v, g, beta))
    S = jnp.zeros((b, H, dk, dv), jnp.float32)
    out = []
    whole = t - t % RULE_BLOCK
    if whole:
        blocks = tuple(z[:whole].reshape(whole // RULE_BLOCK, RULE_BLOCK,
                                         *z.shape[1:]) for z in rows)
        S, o = lax.scan(block, S, blocks)
        out.append(o.reshape(whole, *o.shape[2:]))
    if t % RULE_BLOCK:
        S, o = block(S, tuple(z[whole:] for z in rows))
        out.append(o)
    return jnp.moveaxis(jnp.concatenate(out), 0, 1)


def _gated_delta_net(p, y, s, eps):
    b, t, _ = y.shape
    dk, dv = s.d_k, s.d_v
    hk = p["w_qkvz"].shape[1]
    r = p["A_log"].shape[0] // hk
    conv_channels = 2 * dk + r * dv
    proj = jnp.einsum("btd,dhc->bthc", y, p["w_qkvz"])
    ba = jnp.einsum("btd,dhc->bthc", y, p["w_ba"])
    # causal depthwise convolution: tap j reads the token taps-1-j back
    u = proj[..., :conv_channels]
    taps = p["conv"].shape[-1]
    padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0), (0, 0)))
    mixed = jax.nn.silu(sum(padded[:, j:j + t] * p["conv"][..., j]
                            for j in range(taps)))
    z = proj[..., conv_channels:].reshape(b, t, hk * r, dv)
    l2 = lambda x: x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)
    q = jnp.repeat(l2(mixed[..., :dk]) / math.sqrt(dk), r, axis=2)
    k = jnp.repeat(l2(mixed[..., dk:2 * dk]), r, axis=2)
    v = mixed[..., 2 * dk:].reshape(b, t, hk * r, dv)
    beta = jax.nn.sigmoid(ba[..., :r]).reshape(b, t, hk * r)
    a = ba[..., r:].reshape(b, t, hk * r)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
    o = _delta_rule(q, k, v, g, beta)
    o = p["o_norm"]["scale"] * _rms(o, eps) * jax.nn.silu(z)
    return o.reshape(b, t, hk * r * dv) @ p["w_out"]


def _gated_attention(p, y, cos, sin, s, eps):
    b, t, _ = y.shape
    h = s.head_dim
    qg = jnp.einsum("btd,dhc->bhtc", y, p["wq"])
    q, gate = qg[..., :h], qg[..., h:]
    k = jnp.einsum("btd,dhc->bhtc", y, p["wk"])
    v = jnp.einsum("btd,dhc->bhtc", y, p["wv"])
    q = _rope_leading(_norm(p["q_norm"], q, eps), cos, sin, s.rotary_dim)
    k = _rope_leading(_norm(p["k_norm"], k, eps), cos, sin, s.rotary_dim)
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

    # one block of query rows at a time (`lax.map`: sixteen blocks side by
    # side are 9 GB of scores at 2 x 8192 tokens), the last one shorter
    step = min(QUERY_BLOCK, t)
    whole = t - t % step
    blocks = q[:, :, :whole].reshape(b, s.n_head, whole // step, step, h)
    o = lax.map(lambda block: rows(*block),
                (jnp.moveaxis(blocks, 2, 0), jnp.arange(0, whole, step)))
    o = jnp.moveaxis(o, 0, 2).reshape(b, s.n_head, whole, h)
    if whole < t:
        o = jnp.concatenate([o, rows(q[:, :, whole:], whole)], axis=2)
    merge = lambda z: z.transpose(0, 2, 1, 3).reshape(b, t, s.n_head * h)
    return (merge(o) * jax.nn.sigmoid(merge(gate))) @ p["wo"]


def _expert_ffn(mp, y, s, expert_offset: int):
    """sum over the experts HELD of w_e E_e(y), each expert applied to every
    token and masked by its weight, plus the gated shared expert."""
    b, t, d = y.shape
    x = y.reshape(b * t, d)
    score = jax.nn.softmax(x @ mp["router"], axis=-1)         # all routed
    _, chosen = lax.top_k(score, s.top_k)
    w = jnp.take_along_axis(score, chosen, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)

    @jax.checkpoint
    def one(acc, expert):
        e, gate, up, down = expert
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)
        return acc + w_e[:, None] * _swiglu(x, gate, up, down), None

    held = mp["gate"].shape[0]
    out, _ = lax.scan(one, jnp.zeros_like(x),
                      (expert_offset + jnp.arange(held), mp["gate"],
                       mp["up"], mp["down"]))
    sh = mp["shared"]
    out = out + (jax.nn.sigmoid(x @ sh["gate_score"])
                 * _swiglu(x, sh["gate"], sh["up"], sh["down"]))
    return out.reshape(b, t, d)


def reference_loss(params, input_ids, target_ids, position_ids, *, sizes,
                   expert_offset: int, rope_theta: float, eps: float):
    s = sizes
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    theta = 1.0 / (rope_theta ** (
        jnp.arange(0, s.rotary_dim, 2, dtype=jnp.float32) / s.rotary_dim))
    ang = position_ids.astype(jnp.float32)[:, None, :, None] * theta
    cos, sin = jnp.cos(ang), jnp.sin(ang)

    @jax.checkpoint
    def layer(x, lp):
        y = _norm(lp["norm1"], x, eps)
        if "gdn" in lp:
            x = x + _gated_delta_net(lp["gdn"], y, s, eps)
        else:
            x = x + _gated_attention(lp["attn"], y, cos, sin, s, eps)
        return x + _expert_ffn(lp["moe"], _norm(lp["norm2"], x, eps), s,
                               expert_offset)

    x = params["embedding"]["weight"][input_ids]
    periods = jax.tree.leaves(params["attn_layers"])[0].shape[0]
    at = lambda tree, p, j: jax.tree.map(lambda a: a[p, j], tree)
    for i in range(periods * s.interval):
        p, j = divmod(i, s.interval)
        x = layer(x, at(params["attn_layers"], p, 0)
                  if (i + 1) % s.interval == 0
                  else at(params["gdn_layers"], p, j))
    logits = (_norm(params["norm"], x, eps)
              @ params["lm_head"]["weight"][:, :s.vocab])
    valid = target_ids != IGNORE_INDEX
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, jnp.where(valid, target_ids, 0)[..., None], axis=-1)[..., 0]
    return (jnp.sum(jnp.where(valid, lse - picked, 0.0))
            / jnp.maximum(jnp.sum(valid), 1))
