"""The plain float32 reference of the `ssm_dense` family (models/
ssm_dense.py), beside the other families' `vanilla_*`: the whole model in
straightforward `jax.numpy`, consuming the parameter pytree
`SsmDenseTransformer.init` produces. The layers are LOOPED over
`layer_types` (`models/conv_moe.layers_in_order` hands out the program's
stacked layers one by one), each a mixer and then a SwiGLU, BOTH outputs
times `residual_multiplier` before the add; **the Mamba-2 recurrence one
token at a time** (`S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T`, `y_t = S_t
C_t + D x_t`: one `lax.scan` over positions, no chunk anywhere, so nothing
of `ops/ssd.py`'s algebra is shared), every head reading the one B and C of
its group; the convolution as shifted products plus its bias; the gate
BEFORE the norm over a group's channels; plain softmax attention with no
positions over `q k^T * attention_multiplier` written out (full score
matrices in blocks of 512 query rows); the embedding's rows times
`embedding_multiplier`; the logits of the TIED head over `logits_scaling`;
each layer under `jax.checkpoint`; gradients by `jax.grad`. No kernel, no
sharding, no chunked recurrence, no scan over periods: what
tests/test_ssm_dense.py holds the program to, leaf by leaf.
`benchmark/families/ssm_dense.py` keeps a copy of its own (the yardstick
does not import the program's oracle).

Departures from the published code (HF `GraniteMoeHybrid` with
`num_local_experts` 0), each also a key of the benchmark configuration's
`assumed`:

* `fused_linears`: the SwiGLU's published `input_linear` (d -> 2 f) is two
  matrices here, `gate_proj` and `up_proj` (its two halves), and
  `output_linear` is `down_proj`; the Mamba mixer's `in_proj` is one matrix
  with the columns `[z | xBC | dt]`, as published;
* `gated_norm`: `y <- w * RMSNorm(y * silu(z))` over the ONE group's
  channels, the gate first (the published mixer's `norm_before_gate`
  false);
* `time_step_limit`: (0, inf), so `dt` is not clamped;
* `recurrence_state`: the state, the decays and their sums are float32 (the
  published kernels keep them so too);
* `initialisation`: the program's own from the seed, not the published
  weights.
"""

from __future__ import annotations

from types import SimpleNamespace

import jax
import jax.numpy as jnp
from jax import lax

from ..config import IGNORE_INDEX, ModelConfig
from .conv_moe import layer_blocks, layers_in_order
from .ssm_dense import KINDS

QUERY_BLOCK = 512


def sizes_of(cfg: ModelConfig) -> SimpleNamespace:
    sd = cfg.ssm_dense
    return SimpleNamespace(
        n_head=cfg.num_heads, n_kv_head=cfg.kv_heads, head_dim=cfg.head_dim,
        m_head=sd.mamba_n_heads, m_head_dim=sd.mamba_d_head,
        m_group=sd.mamba_n_groups, m_state=sd.mamba_d_state,
        vocab=cfg.vocab_size, layer_types=tuple(sd.layer_types),
        eps=sd.rms_norm_eps,
        embedding_multiplier=sd.embedding_multiplier,
        residual_multiplier=sd.residual_multiplier,
        attention_multiplier=(cfg.head_dim ** -0.5
                              if sd.attention_multiplier is None
                              else sd.attention_multiplier),
        logits_scaling=sd.logits_scaling)


def vanilla_loss(cfg: ModelConfig, params, input_ids, target_ids,
                 position_ids):
    """The loss `SsmDenseTransformer.loss_shard` computes, plainly."""
    return reference_loss(params, input_ids, target_ids, position_ids,
                          sizes=sizes_of(cfg))


def vanilla_logits(cfg: ModelConfig, params, input_ids):
    """The logits `SsmDenseTransformer.make_forward` computes, plainly."""
    return reference_logits(params, input_ids, sizes=sizes_of(cfg))[0]


# ---- the plain reference ----

def _norm(p, x, eps):
    return (x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                          + eps) * p["scale"])


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
    # head h reads group h // (H / G): with one group, every head the same
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

    @jax.checkpoint
    def rows(q_rows, first):
        n = q_rows.shape[2]
        scores = (jnp.einsum("bhqd,bhkd->bhqk", q_rows, k)
                  * s.attention_multiplier)
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


def _swiglu(lp, y):
    return ((jax.nn.silu(y @ lp["gate_proj"]["weight"])
             * (y @ lp["up_proj"]["weight"])) @ lp["down_proj"]["weight"])


def _mean_ce(logits, targets):
    valid = targets != IGNORE_INDEX
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, jnp.where(valid, targets, 0)[..., None], axis=-1)[..., 0]
    return (jnp.sum(jnp.where(valid, lse - picked, 0.0))
            / jnp.maximum(jnp.sum(valid), 1))


def reference_logits(params, input_ids, *, sizes, scan=recurrence):
    """(logits (b, t, vocab), the residual stream that entered the final
    norm), float32. `scan` is the recurrence a Mamba layer runs (the
    benchmark's controls hand others)."""
    s = sizes
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    emb = params["embedding"]["weight"][:s.vocab]

    def layer(kind):
        @jax.checkpoint
        def run(x, lp):
            y = _norm(lp["norm1"], x, s.eps)
            mixed = (_mamba(lp["mamba"], y, s, scan) if kind == "mamba"
                     else _attention(lp, y, s))
            h = x + s.residual_multiplier * mixed
            return h + s.residual_multiplier * _swiglu(
                lp, _norm(lp["norm2"], h, s.eps))
        return run

    x = s.embedding_multiplier * emb[input_ids]
    stacked = layers_in_order(
        params, layer_blocks(s.layer_types, 0, KINDS, "ssm_dense"))
    for name, lp in zip(s.layer_types, stacked, strict=True):
        x = layer(KINDS[name])(x, lp)
    return (_norm(params["norm"], x, s.eps) @ emb.T) / s.logits_scaling, x


def reference_loss(params, input_ids, target_ids, position_ids, *, sizes,
                   scan=recurrence):
    """The mean cross-entropy over the vocabulary held, float32.
    `position_ids` are not read: no layer takes positions."""
    del position_ids
    logits, _ = reference_logits(params, input_ids, sizes=sizes, scan=scan)
    return _mean_ce(logits, target_ids)
