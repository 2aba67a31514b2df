"""The plain float32 reference of the `loop_llama` family
(models/loop_llama.py): the whole model in straightforward `jax.numpy`,
consuming the parameter pytree `LoopedTransformer.init` produces. A Python
loop of R passes over a Python loop of L layers, the SAME `params["layers"]`
rows in every pass; attention as a masked softmax over the full score
matrix; RoPE in the rotate-half convention over the whole head; four norms
a layer; the final norm after every pass, its output fed to the next; R
full logit tensors through the one head; the gate, `p` and the loss exactly
by the equations:

    lam_r[i] = sigmoid(w_g . h_r[i] + b_g)
    p_r = lam_r prod_{j<r} (1 - lam_j)  (r < R),  p_R = prod_{j<R} (1 - lam_j)
    loss = mean_i [ sum_r p_r[i] l_r[i] - beta H(p[i]) ],  H(p) = -sum p log p

No kernel, no scan, no remat, no sharding: what tests/test_loop_llama.py
holds the program to, leaf by leaf, under
`jax.default_matmul_precision("highest")`. `benchmark/families/loop_llama.py`
keeps a copy of its own (the yardstick does not import the program's
oracle), which computes the same in blocks.

`passes` and `norm_between` are the tests' counter-examples (a program that
ran another number of passes, or fed the next pass the un-normed state);
`detail=True` hands back the R exit losses and `p` beside the loss;
`unrolled=True` reads `params["layers"]` as R x L DISTINCT layers, pass r
taking rows r L .. (r + 1) L: set to equal values, their gradients summed
over the copies are the shared layers'.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..config import IGNORE_INDEX, ModelConfig


def _norm(p, x, eps):
    return (x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                              + eps) * p["scale"])


def _rope(x, cos, sin):
    """Rotate-half: x (b, heads, t, dim), cos/sin (b, 1, t, dim / 2)."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _layer(lp, x, cos, sin, cfg: ModelConfig, eps):
    b, t, _ = x.shape
    h = cfg.head_dim
    y = _norm(lp["norm1"], x, eps)
    heads = lambda z, n: z.reshape(b, t, n, h).transpose(0, 2, 1, 3)
    q = _rope(heads(y @ lp["wq"]["weight"], cfg.num_heads), cos, sin)
    k = _rope(heads(y @ lp["wk"]["weight"], cfg.kv_heads), cos, sin)
    v = heads(y @ lp["wv"]["weight"], cfg.kv_heads)
    group = cfg.num_heads // cfg.kv_heads
    k, v = (jnp.repeat(z, group, axis=1) for z in (k, v))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(h)
    causal = jnp.tril(jnp.ones((t, t), dtype=bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
    o = o.transpose(0, 2, 1, 3).reshape(b, t, cfg.num_heads * h)
    x = x + _norm(lp["post_attn_norm"], o @ lp["wo"]["weight"], eps)
    y = _norm(lp["norm2"], x, eps)
    ff = (jax.nn.silu(y @ lp["gate_proj"]["weight"])
          * (y @ lp["up_proj"]["weight"])) @ lp["down_proj"]["weight"]
    return x + _norm(lp["post_ffn_norm"], ff, eps)


def exit_distribution(z):
    """`p` (R, ...) from the gate's logits `z` (R, ...), by the products."""
    lam = jax.nn.sigmoid(z)
    R = z.shape[0]
    left, p = jnp.ones_like(lam[0]), []
    for r in range(R - 1):
        p.append(lam[r] * left)
        left = left * (1.0 - lam[r])
    return jnp.stack(p + [left])


def vanilla_loss(cfg: ModelConfig, params, input_ids, target_ids,
                 position_ids, *, passes: "int | None" = None,
                 norm_between: bool = True, detail: bool = False,
                 unrolled: bool = False):
    facts = cfg.loop_llama
    R = facts.loop_steps if passes is None else passes
    eps, beta = facts.rms_norm_eps, facts.exit_entropy_coef
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    half = cfg.head_dim // 2
    theta = 1.0 / (cfg.rope_theta ** (jnp.arange(half, dtype=jnp.float32)
                                      / half))
    ang = position_ids.astype(jnp.float32)[:, None, :, None] * theta
    cos, sin = jnp.cos(ang), jnp.sin(ang)

    x = params["embedding"]["weight"][input_ids]
    valid = target_ids != IGNORE_INDEX
    tgt = jnp.where(valid, target_ids, 0)
    head = params["lm_head"]["weight"][:, :cfg.vocab_size]
    gate = params["exit_gate"]
    ces, zs = [], []
    for r in range(R):
        for i in range(cfg.num_layers):
            row = r * cfg.num_layers + i if unrolled else i
            x = _layer(jax.tree.map(lambda a: a[row], params["layers"]), x,
                       cos, sin, cfg, eps)
        h = _norm(params["norm"], x, eps)
        if norm_between:
            x = h
        logits = h @ head
        ces.append(jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, tgt[..., None], axis=-1)[..., 0])
        zs.append(h @ gate["weight"] + gate["bias"])
    ces = jnp.stack(ces)                                     # (R, b, t)
    p = exit_distribution(jnp.stack(zs))
    entropy = -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)),
                                 0.0), axis=0)
    count = jnp.maximum(jnp.sum(valid), 1)
    mean = lambda a: jnp.sum(jnp.where(valid, a, 0.0), axis=(-2, -1)) / count
    loss = mean(jnp.sum(p * ces, axis=0) - beta * entropy)
    if detail:
        return loss, {"loss_exit": mean(ces), "exit_p_mean": mean(p),
                      "exit_entropy": mean(entropy), "p": p}
    return loss
