"""The plain float32 reference of the `mhc_mla_moe` family
(models/mhc_mla_moe.py): the whole model in straightforward `jax.numpy`,
consuming the parameter pytree `HyperLatentMoETransformer.init` produces.
The residual state is an explicit `(b, t, n, C)` array; every mixer is
written out per token (the flattened streams through W, the three maps, the
Sinkhorn rounds as a Python loop over `(b, t, n, n)` matrices); YaRN's
tables come from the formula; latent attention with the full score matrix;
the held experts applied one by one to every token and masked by the
weights; the layers as a Python loop. No scan, no kernel, no remat, no
sharding; it shares no function with `parallel/hyper.py`, `ops/rope.py` or
the program's model: what tests/test_mhc_mla_moe.py holds the program to,
leaf by leaf. `benchmark/families/mhc_mla_moe.py` keeps a copy of its own
(the yardstick does not import the program's oracle).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from ..config import IGNORE_INDEX, ModelConfig


def vanilla_logits(cfg: ModelConfig, params, input_ids, position_ids):
    """The main model's logits (b, t, vocab), float32."""
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    X = _trunk(cfg, params, input_ids, position_ids)
    return _head(cfg, params, params["hc_exit"], params["norm"], X)


def vanilla_loss(cfg: ModelConfig, params, input_ids, target_ids,
                 position_ids):
    """The loss `HyperLatentMoETransformer.loss_shard` computes, plainly."""
    lm = cfg.latent_moe
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    X = _trunk(cfg, params, input_ids, position_ids)
    loss = _mean_ce(_head(cfg, params, params["hc_exit"], params["norm"], X),
                    target_ids)
    if "mtp" not in params:
        return loss
    # h_i (every stream of it, before the exit) with Emb(t_{i+1}) predicts
    # t_{i+2}
    mp = params["mtp"]
    known = target_ids != IGNORE_INDEX
    nxt = params["embedding"]["weight"][jnp.where(known, target_ids, 0)]
    e = _rms_norm(mp["enorm"], nxt, lm.rms_norm_eps)
    H = jnp.stack(
        [jnp.concatenate([_rms_norm(mp["hnorm"], X[:, :, i], lm.rms_norm_eps),
                          e], axis=-1) @ mp["eh_proj"]["weight"]
         for i in range(X.shape[2])], axis=2)
    H = _layers(cfg, H, params["mtp_layers"], position_ids)
    after = jnp.concatenate(
        [target_ids[:, 1:], jnp.full_like(target_ids[:, :1], IGNORE_INDEX)],
        axis=1)
    after = jnp.where(known, after, IGNORE_INDEX)
    mtp = _mean_ce(_head(cfg, params, mp["hc_exit"], mp["norm"], H), after)
    return loss + lm.mtp_loss_weight * mtp


# ---- the streams ----

def mixer_maps(cfg: ModelConfig, mp, X):
    """(pre (b, t, n), post (b, t, n), H (b, t, n, n)) of the streams X
    (b, t, n, C); an exit mixer (W n wide) has `pre` alone."""
    lm, hc = cfg.latent_moe, cfg.latent_moe.hyper
    b, t, n, c = X.shape
    x = X.reshape(b, t, n * c)
    m = (x @ mp["w"]) * lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + lm.rms_norm_eps)
    pre = jax.nn.sigmoid(mp["alpha"][0] * m[..., :n] + mp["b"][:n]) \
        + hc.hc_eps
    if mp["w"].shape[1] == n:
        return pre, None, None
    post = 2.0 * jax.nn.sigmoid(mp["alpha"][1] * m[..., n:2 * n]
                                + mp["b"][n:2 * n])
    h = mp["alpha"][2] * m[..., 2 * n:] + mp["b"][2 * n:]
    h = jnp.clip(h, hc.mhc_h_res_clamp_min, hc.mhc_h_res_clamp_max)
    H = jnp.exp(h.reshape(b, t, n, n))
    for _ in range(hc.hc_sinkhorn_iters):
        H = H / (jnp.sum(H, axis=-1, keepdims=True) + hc.hc_eps)     # rows
        H = H / (jnp.sum(H, axis=-2, keepdims=True) + hc.hc_eps)   # columns
    return pre, post, H


def _mixed(cfg, mp, X, sublayer):
    """X' = H X + post F(sum_i pre_i X[i])."""
    pre, post, H = mixer_maps(cfg, mp, X)
    u = jnp.einsum("bti,btic->btc", pre, X)
    y = sublayer(u)
    return jnp.einsum("btij,btjc->btic", H, X) + post[..., None] * y[:, :,
                                                                    None]


# ---- the sublayers ----

def _rms_norm(p, x, eps):
    return p["scale"] * (x * lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps))


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def yarn_tables(cfg: ModelConfig, position_ids):
    """(cos, sin) (b, 1, t, rope/2) and what the softmax scale is
    multiplied by, from DeepSeek-V2's published rule."""
    lm = cfg.latent_moe
    dim, base, ys = lm.qk_rope_head_dim, cfg.rope_theta, lm.rope_scaling
    pair = jnp.arange(0, dim, 2, dtype=jnp.float32)
    freq = 1.0 / base ** (pair / dim)
    if ys is None:
        ang = position_ids.astype(jnp.float32)[:, None, :, None] * freq
        return jnp.cos(ang), jnp.sin(ang), 1.0

    def correction(turns):
        return dim * math.log(ys.original_max_position_embeddings
                              / (turns * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction(ys.beta_fast)), 0)
    high = min(math.ceil(correction(ys.beta_slow)), dim - 1)
    span = high - low if high != low else 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / span,
                    0.0, 1.0)
    freq = freq / ys.factor * ramp + freq * (1.0 - ramp)
    get = lambda m: (1.0 if ys.factor <= 1
                     else 0.1 * m * math.log(ys.factor) + 1.0)
    ang = position_ids.astype(jnp.float32)[:, None, :, None] * freq
    table = get(ys.mscale) / get(ys.mscale_all_dim)
    softmax = get(ys.mscale_all_dim) ** 2 if ys.mscale_all_dim else 1.0
    return jnp.cos(ang) * table, jnp.sin(ang) * table, softmax


def _rope(x, cos, sin):
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _attention(cfg, lp, y, position_ids):
    lm, heads_n = cfg.latent_moe, cfg.num_heads
    eps = lm.rms_norm_eps
    b, t, _ = y.shape
    nope, rope, vd = lm.qk_nope_head_dim, lm.qk_rope_head_dim, lm.v_head_dim
    cos, sin, softmax = yarn_tables(cfg, position_ids)
    heads = lambda z, w: z.reshape(b, t, heads_n, w).transpose(0, 2, 1, 3)
    c_q = _rms_norm(lp["q_norm"], y @ lp["wq_a"]["weight"], eps)
    q = heads(c_q @ lp["wq_b"]["weight"], nope + rope)
    ckv = y @ lp["wkv_a"]["weight"]
    c_kv = _rms_norm(lp["kv_norm"], ckv[..., :lm.kv_lora_rank], eps)
    k_r = ckv[..., lm.kv_lora_rank:][:, None]
    kv = heads(c_kv @ lp["wkv_b"]["weight"], nope + vd)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], cos, sin)], -1)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(_rope(k_r, cos, sin), (b, heads_n, t, rope))], -1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (
        softmax / math.sqrt(nope + rope))
    seen = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", probs, kv[..., nope:])
    return o.transpose(0, 2, 1, 3).reshape(b, t, heads_n * vd) \
        @ lp["wo"]["weight"]


def _expert_ffn(cfg, mp, y):
    lm = cfg.latent_moe
    b, t, d = y.shape
    x = y.reshape(b * t, d)
    score = jax.nn.sigmoid(x @ mp["router"])
    _, chosen = lax.top_k(score + lax.stop_gradient(mp["bias"]),
                          cfg.moe_top_k)
    w = jnp.take_along_axis(score, chosen, axis=-1)
    w = (w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
         * lm.routed_scaling_factor)
    sh = mp["shared"]
    out = _swiglu(x, sh["gate"], sh["up"], sh["down"])
    for i in range(mp["gate"].shape[0]):             # the experts held
        w_e = jnp.sum(jnp.where(chosen == lm.expert_offset + i, w, 0.0),
                      axis=-1)
        out = out + w_e[:, None] * _swiglu(x, mp["gate"][i], mp["up"][i],
                                           mp["down"][i])
    return out.reshape(b, t, d)


def _layers(cfg, X, layers, position_ids):
    eps = cfg.latent_moe.rms_norm_eps
    for i in range(jax.tree.leaves(layers)[0].shape[0]):
        lp = jax.tree.map(lambda a: a[i], layers)
        X = _mixed(cfg, lp["hc_attn"], X, lambda u: _attention(
            cfg, lp, _rms_norm(lp["norm1"], u, eps), position_ids))
        if "moe" in lp:
            ffn = lambda u: _expert_ffn(cfg, lp["moe"],
                                        _rms_norm(lp["norm2"], u, eps))
        else:
            ffn = lambda u: _swiglu(
                _rms_norm(lp["norm2"], u, eps), lp["gate_proj"]["weight"],
                lp["up_proj"]["weight"], lp["down_proj"]["weight"])
        X = _mixed(cfg, lp["hc_ffn"], X, ffn)
    return X


def _trunk(cfg, params, input_ids, position_ids):
    n = cfg.latent_moe.hyper.hc_mult
    x = params["embedding"]["weight"][input_ids]
    X = jnp.stack([x] * n, axis=2)                   # X_0: n copies
    if "dense_layers" in params:
        X = _layers(cfg, X, params["dense_layers"], position_ids)
    return _layers(cfg, X, params["layers"], position_ids)


def _head(cfg, params, exit_params, norm_params, X):
    pre, _, _ = mixer_maps(cfg, exit_params, X)
    h = jnp.einsum("bti,btic->btc", pre, X)
    return (_rms_norm(norm_params, h, cfg.latent_moe.rms_norm_eps)
            @ params["lm_head"]["weight"][:, :cfg.vocab_size])


def _mean_ce(logits, targets):
    valid = targets != IGNORE_INDEX
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, jnp.where(valid, targets, 0)[..., None], axis=-1)[..., 0]
    return (jnp.sum(jnp.where(valid, lse - picked, 0.0))
            / jnp.maximum(jnp.sum(valid), 1))
