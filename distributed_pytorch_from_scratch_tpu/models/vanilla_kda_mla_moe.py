"""The plain float32 reference of the `kda_mla_moe` family
(models/kda_mla_moe.py): the whole model in straightforward `jax.numpy` under
`jax.default_matmul_precision("highest")`, consuming the parameter pytree
`KdaMlaMoETransformer.init` produces. The layers are LOOPED in Python;
**Kimi Delta Attention runs token by token** (the recurrence itself, one
`lax.scan` over positions: no chunk, no reference row, no solve); the
convolutions are four shifted sums; latent attention forms the full score
matrix; the router's groups are a reshape and a sort; **the held experts are
applied one by one to every token and masked by the weights**; gradients by
`jax.grad`. No kernel, no scan over layers, no remat, no sharding, no
dispatch: what tests/test_kda_mla_moe.py holds the program to, leaf by leaf.
It shares no function with `parallel/kda.py`, `ops/delta_rule.py`'s chunked
rule or `parallel/moe.py`'s selection. `benchmark/families/kda_mla_moe.py`
keeps a copy of its own (the yardstick does not import the program's oracle).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from ..config import IGNORE_INDEX, ModelConfig


def vanilla_loss(cfg: ModelConfig, params, input_ids, target_ids,
                 position_ids):
    """The loss `KdaMlaMoETransformer.loss_shard` computes, plainly."""
    with jax.default_matmul_precision("highest"):
        main, mtp, _ = _losses(cfg, params, input_ids, target_ids,
                               position_ids)
    weight = cfg.kda_mla_moe.mtp_loss_weight
    return main if mtp is None else main + weight * mtp


def vanilla_logits(cfg: ModelConfig, params, input_ids, position_ids):
    """The main head's logits (b, t, vocab)."""
    with jax.default_matmul_precision("highest"):
        return _losses(cfg, params, input_ids, input_ids, position_ids)[2]


# ---- the pieces ----

def _rms_norm(p, x, eps):
    return p["scale"] * x / jnp.sqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _rope_interleaved(x, cos, sin):
    """Pairs (x_2i, x_2i+1) of x (b, heads, t, dim); cos/sin (b, 1, t,
    dim/2)."""
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _conv_silu(u, w):
    """u (b, t, H, c), w (H, c, taps): tap j reads the token taps-1-j back,
    zeros before the sequence; then SiLU."""
    taps, t = w.shape[-1], u.shape[1]
    total = jnp.zeros_like(u)
    for j in range(taps):
        back = taps - 1 - j
        shifted = jnp.pad(u, ((0, 0), (back, 0), (0, 0), (0, 0)))[:, :t]
        total = total + shifted * w[..., j]
    return jax.nn.silu(total)


def _kda(p, y, lower_bound, eps):
    """Kimi Delta Attention, one token at a time. y (b, t, d)."""
    b, t, _ = y.shape
    H, dk = p["w_q"].shape[1:]
    dv = p["w_v"].shape[-1]
    project = lambda w: jnp.einsum("btd,dhc->bthc", y, w)
    q = _conv_silu(project(p["w_q"]), p["conv_q"])
    k = _conv_silu(project(p["w_k"]), p["conv_k"])
    v = _conv_silu(project(p["w_v"]), p["conv_v"])
    unit = lambda z: z / jnp.sqrt(jnp.sum(z * z, axis=-1, keepdims=True)
                                  + eps)
    q, k = unit(q) / math.sqrt(dk), unit(k)
    beta = jax.nn.sigmoid(jnp.einsum("btd,dh->bth", y, p["w_beta"]))
    a = project(p["w_f"])                                   # (b, t, H, d_k)
    g = lower_bound * jax.nn.sigmoid(
        jnp.exp(p["A_log"])[:, None] * (a + p["dt_bias"]))

    def token(S, row):
        q_t, k_t, v_t, g_t, b_t = row
        S = jnp.exp(g_t)[..., None] * S                     # a decay a row
        delta = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t))
        S = S + k_t[..., :, None] * delta[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    rows = tuple(jnp.moveaxis(z, 1, 0) for z in (q, k, v, g, beta))
    _, o = lax.scan(token, jnp.zeros((b, H, dk, dv), jnp.float32), rows)
    o = jnp.moveaxis(o, 0, 1)                               # (b, t, H, d_v)
    o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    o = p["o_norm"]["scale"] * o * jax.nn.sigmoid(project(p["w_g"]))
    return o.reshape(b, t, H * dv) @ p["w_out"]


def _mla(p, y, cos, sin, km, n_head, eps):
    """Latent attention with no q latent and a gate a head."""
    b, t, _ = y.shape
    nope, rope, vd = km.qk_nope_head_dim, km.qk_rope_head_dim, km.v_head_dim
    heads = lambda z, w: z.reshape(b, t, n_head, w).transpose(0, 2, 1, 3)
    q = heads(y @ p["wq"]["weight"], nope + rope)
    ckv = y @ p["wkv_a"]["weight"]
    c_kv = _rms_norm(p["kv_norm"], ckv[..., :km.kv_lora_rank], eps)
    k_r = ckv[..., km.kv_lora_rank:][:, None]            # one head for all
    kv = heads(c_kv @ p["wkv_b"]["weight"], nope + vd)
    q = jnp.concatenate([q[..., :nope],
                         _rope_interleaved(q[..., nope:], cos, sin)], -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(
            _rope_interleaved(k_r, cos, sin), (b, n_head, t, rope))], -1)
    v = kv[..., nope:]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(nope + rope)
    seen = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
    gate = jax.nn.sigmoid(y @ p["w_gate"]["weight"])        # (b, t, heads)
    o = o * gate.transpose(0, 2, 1)[..., None]
    return o.transpose(0, 2, 1, 3).reshape(b, t, n_head * vd) \
        @ p["wo"]["weight"]


def _choose(biased, km, top_k):
    """The group-limited selection by reshape and sort: (S, experts) -> the
    chosen experts (S, top_k)."""
    S, E = biased.shape
    if km.n_group > 1:
        grouped = biased.reshape(S, km.n_group, E // km.n_group)
        best_two = jnp.sort(grouped, axis=-1)[..., -2:].sum(-1)
        kept = jnp.argsort(-best_two, axis=-1)[:, :km.topk_group]
        keep = jnp.zeros((S, km.n_group), bool).at[
            jnp.arange(S)[:, None], kept].set(True)
        biased = jnp.where(keep[..., None], grouped, -jnp.inf).reshape(S, E)
    return jnp.argsort(-biased, axis=-1)[:, :top_k]


def _expert_ffn(mp, y, km, top_k):
    """sum over the experts HELD of w_e E_e(y), each expert applied to every
    token and masked by its weight, plus the shared expert."""
    b, t, d = y.shape
    x = y.reshape(b * t, d)
    score = jax.nn.sigmoid(x @ mp["router"])                  # all routed
    chosen = _choose(score + lax.stop_gradient(mp["bias"]), km, top_k)
    w = jnp.take_along_axis(score, chosen, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) \
        * km.routed_scaling_factor
    out = jnp.zeros_like(x)
    for i in range(mp["gate"].shape[0]):
        w_e = jnp.sum(jnp.where(chosen == km.expert_offset + i, w, 0.0), -1)
        out = out + w_e[:, None] * _swiglu(x, mp["gate"][i], mp["up"][i],
                                           mp["down"][i])
    if "shared" in mp:
        sh = mp["shared"]
        out = out + _swiglu(x, sh["gate"], sh["up"], sh["down"])
    return out.reshape(b, t, d)


def layers_in_order(params):
    """The main model's layers' parameters, one tree a layer, in the order
    they run: the first group's segments, then the periods."""
    out = []
    at = lambda tree, *i: jax.tree.map(lambda a: a[i], tree)
    for key in ("dense_layers", "lead_kda_layers", "lead_mla_layers"):
        if key in params:
            n = jax.tree.leaves(params[key])[0].shape[0]
            out += [at(params[key], i) for i in range(n)]
    if "mla_layers" in params:
        periods, a_period = jax.tree.leaves(params["kda_layers"])[0].shape[:2]
        for p in range(periods):
            out += [at(params["kda_layers"], p, j) for j in range(a_period)]
            out.append(at(params["mla_layers"], p, 0))
    return out


def _mean_ce(logits, targets):
    valid = targets != IGNORE_INDEX
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, jnp.where(valid, targets, 0)[..., None], axis=-1)[..., 0]
    return (jnp.sum(jnp.where(valid, lse - picked, 0.0))
            / jnp.maximum(jnp.sum(valid), 1))


def _losses(cfg, params, input_ids, target_ids, position_ids):
    """(CE of the main model, CE of the module or None, the main logits)."""
    km, eps = cfg.kda_mla_moe, cfg.kda_mla_moe.rms_norm_eps
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    emb = params["embedding"]["weight"]
    head = params["lm_head"]["weight"][:, :cfg.vocab_size]
    rope = km.qk_rope_head_dim
    theta = 1.0 / (cfg.rope_theta ** (
        jnp.arange(0, rope, 2, dtype=jnp.float32) / rope))
    ang = position_ids.astype(jnp.float32)[:, None, :, None] * theta
    cos, sin = jnp.cos(ang), jnp.sin(ang)

    def layer(x, lp):
        y = _rms_norm(lp["norm1"], x, eps)
        if "kda" in lp:
            x = x + _kda(lp["kda"], y, km.kda_lower_bound, eps)
        else:
            x = x + _mla(lp["mla"], y, cos, sin, km, cfg.num_heads, eps)
        y = _rms_norm(lp["norm2"], x, eps)
        if "moe" in lp:
            return x + _expert_ffn(lp["moe"], y, km, cfg.moe_top_k)
        return x + _swiglu(y, lp["gate_proj"]["weight"],
                           lp["up_proj"]["weight"], lp["down_proj"]["weight"])

    x = emb[input_ids]
    for lp in layers_in_order(params):
        x = layer(x, lp)
    logits = _rms_norm(params["norm"], x, eps) @ head
    main = _mean_ce(logits, target_ids)
    if "mtp" not in params:
        return main, None, logits
    # h_i (before the main final norm) with Emb(t_{i+1}) predicts t_{i+2}
    mp = params["mtp"]
    known = target_ids != IGNORE_INDEX
    nxt = emb[jnp.where(known, target_ids, 0)]
    h = jnp.concatenate([_rms_norm(mp["hnorm"], x, eps),
                         _rms_norm(mp["enorm"], nxt, eps)], axis=-1)
    h = layer(h @ mp["eh_proj"]["weight"],
              jax.tree.map(lambda a: a[0], params["mtp_layers"]))
    after = jnp.concatenate(
        [target_ids[:, 1:], jnp.full_like(target_ids[:, :1], IGNORE_INDEX)],
        axis=1)
    after = jnp.where(known, after, IGNORE_INDEX)
    return main, _mean_ce(_rms_norm(mp["norm"], h, eps) @ head, after), logits
