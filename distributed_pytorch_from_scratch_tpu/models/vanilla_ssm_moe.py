"""The plain float32 reference of the `ssm_moe` family (models/ssm_moe.py),
beside the other families' `vanilla_*`: the whole model in straightforward
`jax.numpy`, consuming the parameter pytree `SsmMoETransformer.init`
produces. The layers are LOOPED over the pattern's letters
(`models/conv_moe.layers_in_order` hands out the program's stacked layers one
by one), each ONE norm and ONE sublayer; **the Mamba-2 recurrence one token
at a time** (`S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T`, `y_t = S_t C_t + D
x_t`: one `lax.scan` over positions, no chunk anywhere), head `h` reading
the B and C of group `h // (H / G)`; the convolution as shifted products;
the gate BEFORE the norm over a group's channels; attention with no
positions, full score matrices in blocks of 512 query rows; the sigmoid
top-k router over the d-wide token; **the held experts applied one by one to
every token's latent and masked by the weights** (`down (relu(up l))^2`, two
matrices), their sum up the latent's second projection; the shared expert at
the model's width; the multi-token-prediction module where the
configuration keeps it; each layer under `jax.checkpoint`; gradients by
`jax.grad`. No kernel, no sharding, no dispatch, no chunked recurrence, no
scan over periods: what tests/test_ssm_moe.py holds the program to, leaf by
leaf. `benchmark/families/ssm_moe.py` keeps a copy of its own (the yardstick
does not import the program's oracle).

Departures from the published description, each also a key of the
benchmark configuration's `assumed`:

* `no_positions`: the attention layers rotate nothing (Nemotron-H,
  arXiv:2504.03624; the config's `rope_theta` and `partial_rotary_factor`
  are not read);
* `latent_experts`: the router reads the d-wide token and only the ROUTED
  experts live in the latent; the two latent projections have no bias and
  no norm;
* `mtp`: the module's joints are DeepSeek-V3's (norm both, concatenate,
  2 d -> d, the trunk's embedding and head), its loss weight a fact;
* `router`: the selection bias is whatever the tree holds (zeros from
  `init`; nothing updates it); no balance loss; the chosen scores' sum gets
  1e-20;
* `recurrence_state`: the state is float32 (the published kernels keep it
  so too).
"""

from __future__ import annotations

import math
from types import SimpleNamespace

import jax
import jax.numpy as jnp
from jax import lax

from ..config import IGNORE_INDEX, ModelConfig
from .conv_moe import layer_blocks, layers_in_order
from .ssm_moe import KINDS

QUERY_BLOCK = 512


def sizes_of(cfg: ModelConfig) -> SimpleNamespace:
    sm = cfg.ssm_moe
    return SimpleNamespace(
        n_head=cfg.num_heads, n_kv_head=cfg.kv_heads, head_dim=sm.head_dim,
        m_head=sm.mamba_num_heads, m_head_dim=sm.mamba_head_dim,
        m_group=sm.n_groups, m_state=sm.ssm_state_size,
        top_k=cfg.moe_top_k, vocab=cfg.vocab_size,
        pattern=sm.hybrid_override_pattern,
        mtp_pattern=(sm.mtp_hybrid_override_pattern
                     * sm.num_nextn_predict_layers),
        scaling=sm.routed_scaling_factor, expert_offset=sm.expert_offset,
        eps=sm.norm_eps, mtp_loss_weight=sm.mtp_loss_weight)


def vanilla_loss(cfg: ModelConfig, params, input_ids, target_ids,
                 position_ids):
    """The loss `SsmMoETransformer.loss_shard` computes, plainly."""
    return reference_loss_routed(params, input_ids, target_ids, position_ids,
                                 sizes=sizes_of(cfg))[0]


# ---- the plain reference ----

def _norm(p, x, eps):
    return (x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                          + eps) * p["scale"])


def _relu2(x, up, down):
    return jnp.square(jax.nn.relu(x @ up)) @ down


def recurrence(x, dt, A, B, C):
    """x (b, t, H, P), dt (b, t, H), A (H,), B and C (b, t, H, N), a head's
    own -> y (b, t, H, P): the state (b, H, P, N) from zero, one token at a
    time."""
    def token(S, row):
        x_t, dt_t, B_t, C_t = row
        S = (jnp.exp(dt_t * A)[..., None, None] * S
             + (dt_t[..., None] * x_t)[..., :, None] * B_t[..., None, :])
        return S, jnp.einsum("bhpn,bhn->bhp", S, C_t)

    b, _, H, Pd = x.shape
    S = jnp.zeros((b, H, Pd, B.shape[-1]), jnp.float32)
    _, y = lax.scan(token, S, tuple(jnp.moveaxis(a, 1, 0)
                                    for a in (x, dt, B, C)))
    return jnp.moveaxis(y, 0, 1)


def _mamba(p, u, s, scan=recurrence):
    b, t, _ = u.shape
    H, Pd, G, N = s.m_head, s.m_head_dim, s.m_group, s.m_state
    inner = H * Pd
    z, xBC, dt = jnp.split(u @ p["w_in"], (inner, 2 * inner + 2 * G * N), -1)
    taps = p["conv"].shape[-1]
    # tap `taps - 1` reads the token itself; zeros before the sequence
    xBC = jax.nn.silu(p["conv_bias"] + sum(
        p["conv"][:, j]
        * jnp.pad(xBC, ((0, 0), (taps - 1 - j, 0), (0, 0)))[:, :t]
        for j in range(taps)))
    x, B, C = jnp.split(xBC, (inner, inner + G * N), -1)
    x = x.reshape(b, t, H, Pd)
    # head h reads group h // (H / G)
    own = lambda a: jnp.repeat(a.reshape(b, t, G, N), H // G, axis=2)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = scan(x, dt, -jnp.exp(p["A_log"]), own(B), own(C))
    y = (y + p["D"][:, None] * x).reshape(b, t, inner)
    # the gate first, then the norm over a group's channels
    g = (y * jax.nn.silu(z)).reshape(b, t, G, inner // G)
    g = g * lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + s.eps)
    return (p["norm"] * g.reshape(b, t, inner)) @ p["w_out"]


def _attention(lp, y, s):
    b, t, _ = y.shape
    h = s.head_dim
    heads = lambda z, n: z.reshape(b, t, n, h).transpose(0, 2, 1, 3)
    q = heads(y @ lp["wq"]["weight"], s.n_head)
    k = heads(y @ lp["wk"]["weight"], s.n_kv_head)
    v = heads(y @ lp["wv"]["weight"], s.n_kv_head)
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


def _expert_ffn(mp, y, s, act=_relu2):
    """The latent's second projection of the sum over the experts HELD of
    w_e E_e(l), each expert applied to every token's latent and masked by
    its weight, plus the shared expert at the model's width; and how many
    (token, choice) pairs chose each routed expert."""
    b, t, d = y.shape
    x = y.reshape(b * t, d)
    score = jax.nn.sigmoid(x @ mp["router"])                  # all routed
    _, chosen = lax.top_k(score + lax.stop_gradient(mp["bias"]), s.top_k)
    w = jnp.take_along_axis(score, chosen, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * s.scaling
    latent = x @ mp["latent"]["down"]

    @jax.checkpoint
    def one(acc, expert):
        e, up, down = expert
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)
        return acc + w_e[:, None] * act(latent, up, down), None

    held = mp["up"].shape[0]
    out, _ = lax.scan(one, jnp.zeros_like(latent),
                      (s.expert_offset + jnp.arange(held), mp["up"],
                       mp["down"]))
    out = out @ mp["latent"]["up"] + act(x, mp["shared"]["up"],
                                         mp["shared"]["down"])
    routed = jnp.zeros(score.shape[-1]).at[chosen.reshape(-1)].add(1.0)
    return out.reshape(b, t, d), routed


def _mean_ce(logits, targets):
    valid = targets != IGNORE_INDEX
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, jnp.where(valid, targets, 0)[..., None], axis=-1)[..., 0]
    return (jnp.sum(jnp.where(valid, lse - picked, 0.0))
            / jnp.maximum(jnp.sum(valid), 1))


def reference_loss_routed(params, input_ids, target_ids, position_ids, *,
                          sizes, scan=recurrence, act=_relu2):
    """(mean cross-entropy over the slice, with the module's where the
    configuration keeps one; routed (expert layers, routed experts): the
    pairs each expert was chosen for, a row an expert layer in the order the
    layers run, the module's last), float32. `position_ids` are not read:
    no layer takes positions. `scan` is the recurrence a Mamba layer runs
    and `act` an expert (the benchmark's controls hand others)."""
    del position_ids
    s = sizes
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    emb = params["embedding"]["weight"]
    head = params["lm_head"]["weight"][:, :s.vocab]

    def layer(letter):
        @jax.checkpoint
        def run(x, lp):
            y = _norm(lp["norm1"], x, s.eps)
            if letter == "M":
                return x + _mamba(lp["mamba"], y, s, scan), None
            if letter == "*":
                return x + _attention(lp, y, s), None
            out, routed = _expert_ffn(lp["moe"], y, s, act)
            return x + out, routed
        return run

    x = emb[input_ids]
    stacked = layers_in_order(
        params, layer_blocks(tuple(s.pattern), 0, KINDS, "ssm_moe"))
    routed = []
    for letter, lp in zip(s.pattern, stacked, strict=True):
        x, chose = layer(letter)(x, lp)
        if chose is not None:
            routed.append(chose)
    loss = _mean_ce(_norm(params["norm"], x, s.eps) @ head, target_ids)
    if s.mtp_pattern:
        # h_i (before the main final norm) with Emb(t_{i+1}) predicts t_{i+2}
        mp = params["mtp"]
        known = target_ids != IGNORE_INDEX
        nxt = emb[jnp.where(known, target_ids, 0)]
        h = jnp.concatenate([_norm(mp["hnorm"], x, s.eps),
                             _norm(mp["enorm"], nxt, s.eps)], axis=-1)
        h = h @ mp["eh_proj"]["weight"]
        for letter in s.mtp_pattern:
            h, chose = layer(letter)(h, jax.tree.map(
                lambda a: a[0], params[f"mtp_{KINDS[letter]}_layers"]))
            if chose is not None:
                routed.append(chose)
        after = jnp.concatenate(
            [target_ids[:, 1:],
             jnp.full_like(target_ids[:, :1], IGNORE_INDEX)], axis=1)
        after = jnp.where(known, after, IGNORE_INDEX)
        loss = loss + s.mtp_loss_weight * _mean_ce(
            _norm(mp["norm"], h, s.eps) @ head, after)
    return loss, lax.stop_gradient(jnp.stack(routed))
