"""The plain float32 reference of the `mla_moe` family (models/mla_moe.py),
beside `models/vanilla.py`'s for the other two: the whole model in
straightforward `jax.numpy`, consuming the parameter pytree
`LatentMoETransformer.init` produces. Latent attention with full score
matrices (in blocks of query rows, each block and each layer under
`jax.checkpoint`), interleaved RoPE, the sigmoid top-k router, **the held
experts applied one by one to every token and masked by the weights** (no
sort, no gather, no grouped product), the shared expert, the leading dense
layers, the multi-token-prediction module and `CE_main + lambda * CE_mtp`;
gradients by `jax.grad`. No kernel, no sharding, no dispatch: what
tests/test_mla_moe.py holds the program to, leaf by leaf.
`benchmark/families/mla_moe.py` keeps a copy of its own (the yardstick does
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


def sizes_of(cfg: ModelConfig) -> SimpleNamespace:
    lm = cfg.latent_moe
    return SimpleNamespace(
        n_head=cfg.num_heads, kv_lora_rank=lm.kv_lora_rank,
        qk_nope_head_dim=lm.qk_nope_head_dim,
        qk_rope_head_dim=lm.qk_rope_head_dim, v_head_dim=lm.v_head_dim,
        top_k=cfg.moe_top_k, vocab=cfg.vocab_size)


def vanilla_loss(cfg: ModelConfig, params, input_ids, target_ids,
                 position_ids):
    """The loss `LatentMoETransformer.loss_shard` computes, plainly."""
    lm = cfg.latent_moe
    return reference_loss(
        params, input_ids, target_ids, position_ids, sizes=sizes_of(cfg),
        expert_offset=lm.expert_offset, scaling=lm.routed_scaling_factor,
        rope_theta=cfg.rope_theta, eps=lm.rms_norm_eps,
        mtp_loss_weight=lm.mtp_loss_weight)


# ---- the plain reference ----

def _rms_norm(p, x, eps):
    return p["scale"] * (x * lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps))


def _rope(x, cos, sin):
    """Interleaved pairs (x_2i, x_2i+1) of x (b, heads, t, dim) turned by
    pair i's angle; cos/sin (b, 1, t, dim/2)."""
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _attention(lp, y, cos, sin, s, eps):
    b, t, _ = y.shape
    nope, rope, vd = s.qk_nope_head_dim, s.qk_rope_head_dim, s.v_head_dim
    heads = lambda z, w: z.reshape(b, t, s.n_head, w).transpose(0, 2, 1, 3)
    c_q = _rms_norm(lp["q_norm"], y @ lp["wq_a"]["weight"], eps)
    q = heads(c_q @ lp["wq_b"]["weight"], nope + rope)
    ckv = y @ lp["wkv_a"]["weight"]
    c_kv = _rms_norm(lp["kv_norm"], ckv[..., :s.kv_lora_rank], eps)
    k_r = ckv[..., s.kv_lora_rank:][:, None]             # one head for all
    kv = heads(c_kv @ lp["wkv_b"]["weight"], nope + vd)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], cos, sin)], -1)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(_rope(k_r, cos, sin), (b, s.n_head, t, rope))], -1)
    v = kv[..., nope:]
    scale = 1.0 / math.sqrt(nope + rope)

    @jax.checkpoint
    def rows(q_rows, first):
        n = q_rows.shape[2]
        scores = jnp.einsum("bhqd,bhkd->bhqk", q_rows, k) * scale
        seen = (first + jnp.arange(n))[:, None] >= jnp.arange(t)[None, :]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", probs, v)

    step = min(QUERY_BLOCK, t)
    o = jnp.concatenate([rows(q[:, :, i:i + step], i)
                         for i in range(0, t, step)], axis=2)
    return o.transpose(0, 2, 1, 3).reshape(b, t, s.n_head * vd) \
        @ lp["wo"]["weight"]


def _expert_ffn(mp, y, s, expert_offset: int, scaling):
    """sum over the experts HELD of w_e E_e(y), each expert applied to every
    token and masked by its weight, plus the shared expert."""
    b, t, d = y.shape
    x = y.reshape(b * t, d)
    score = jax.nn.sigmoid(x @ mp["router"])                  # all routed
    _, chosen = lax.top_k(score + lax.stop_gradient(mp["bias"]), s.top_k)
    w = jnp.take_along_axis(score, chosen, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * scaling

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
    out = out + _swiglu(x, sh["gate"], sh["up"], sh["down"])
    return out.reshape(b, t, d)


def _layers(x, layers, cos, sin, s, eps, expert_offset, scaling):
    @jax.checkpoint
    def layer(x, lp):
        x = x + _attention(lp, _rms_norm(lp["norm1"], x, eps), cos, sin, s,
                           eps)
        y = _rms_norm(lp["norm2"], x, eps)
        if "moe" in lp:
            return x + _expert_ffn(lp["moe"], y, s, expert_offset,
                                   scaling), None
        return x + _swiglu(y, lp["gate_proj"]["weight"],
                           lp["up_proj"]["weight"],
                           lp["down_proj"]["weight"]), None

    x, _ = lax.scan(layer, x, layers)
    return x


def _mean_ce(logits, targets):
    valid = targets != IGNORE_INDEX
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, jnp.where(valid, targets, 0)[..., None], axis=-1)[..., 0]
    return (jnp.sum(jnp.where(valid, lse - picked, 0.0))
            / jnp.maximum(jnp.sum(valid), 1))


def reference_losses(params, input_ids, target_ids, position_ids, *,
                     sizes, expert_offset: int,
                     scaling: float, rope_theta: float, eps: float):
    """(CE of the main model, CE of the multi-token-prediction module or
    None), float32."""
    s = sizes
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    emb = params["embedding"]["weight"]
    head = params["lm_head"]["weight"][:, :s.vocab]
    theta = 1.0 / (rope_theta ** (
        jnp.arange(0, s.qk_rope_head_dim, 2, dtype=jnp.float32)
        / s.qk_rope_head_dim))
    ang = position_ids.astype(jnp.float32)[:, None, :, None] * theta
    run = lambda x, layers: _layers(x, layers, jnp.cos(ang), jnp.sin(ang), s,
                                    eps, expert_offset, scaling)

    x = emb[input_ids]
    if "dense_layers" in params:
        x = run(x, params["dense_layers"])
    x = run(x, params["layers"])
    main = _mean_ce(_rms_norm(params["norm"], x, eps) @ head, target_ids)
    if "mtp" not in params:
        return main, None
    # h_i (before the main final norm) with Emb(t_{i+1}) predicts t_{i+2}
    mp = params["mtp"]
    known = target_ids != IGNORE_INDEX
    nxt = emb[jnp.where(known, target_ids, 0)]
    h = jnp.concatenate([_rms_norm(mp["hnorm"], x, eps),
                         _rms_norm(mp["enorm"], nxt, eps)], axis=-1)
    h = run(h @ mp["eh_proj"]["weight"], params["mtp_layers"])
    after = jnp.concatenate(
        [target_ids[:, 1:], jnp.full_like(target_ids[:, :1], IGNORE_INDEX)],
        axis=1)
    after = jnp.where(known, after, IGNORE_INDEX)
    return main, _mean_ce(_rms_norm(mp["norm"], h, eps) @ head, after)


def reference_loss(params, input_ids, target_ids, position_ids, *,
                   mtp_loss_weight: float, **kw):
    main, mtp = reference_losses(params, input_ids, target_ids, position_ids,
                                 **kw)
    return main if mtp is None else main + mtp_loss_weight * mtp
