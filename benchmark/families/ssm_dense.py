"""The ssm_dense family (Granite 4.0-H, `granitemoehybrid` with no experts): a
configuration file in the published keys -> the program's model
(`models/ssm_dense.SsmDenseTransformer`) and the plain reference the
benchmark checks it against.

`reference_loss` is the benchmark's own copy of the architecture in
straightforward `jax.numpy`, float32, each scalar written where the
published equations put it:

    x0 = embedding_multiplier * E[ids]
    h  = x + residual_multiplier * mixer(RMSNorm(x))
    x' = h + residual_multiplier * (silu(u W_g) * (u W_u)) W_d,  u = RMSNorm(h)
    logits = RMSNorm(x_L) E^T / logits_scaling        (the TIED table)

the layers LOOPED over `layer_types`; **the Mamba-2 recurrence token by
token** (one `lax.scan` over positions, under `jax.checkpoint` in blocks of
64 steps, so that its backward keeps 64 states of 2 MB a layer at 4096
tokens and not 4096), every head reading the ONE B and C of its group; the
convolution as shifted sums plus its bias; the gate before the norm over
the group's channels; attention with NO positions as a masked softmax over
`q k^T * attention_multiplier`, full score matrices in blocks of 512 query
rows. No kernel, no sharding, no chunked recurrence, no scan over periods.
It consumes the parameter pytree `SsmDenseTransformer.init` produces and is
given the same layers and the same vocabulary slice.

The configuration file states the cut (`reduced`) beside a `published`
group: `num_layers` of the published `layer_types` from the first (one whole
period) and `vocab_size` rows of the table.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.lib.ssm_dense_counts import SsmDenseSizes
# at import, so that a program without the family fails before any device
# is touched (run.py loads this module before the runner starts)
from distributed_pytorch_from_scratch_tpu.config import (ModelConfig,
                                                         SsmDenseConfig)
from distributed_pytorch_from_scratch_tpu.models.ssm_dense import (
    SsmDenseTransformer)

IGNORE_INDEX = -1
QUERY_BLOCK = 512
SCAN_BLOCK = 64
KEYS = {"mamba": "mamba", "attention": "attn"}


class Family(NamedTuple):
    model: object            # the program's model, built for the mesh
    sizes: SsmDenseSizes     # for benchmark/lib/ssm_dense_counts.py; data
                             # is drawn from its `vocab` (the slice held)
    reference_loss: object   # (params, ids, tgt, pos) -> float32 loss
    facts: object            # what the reference reads beside the sizes


class Facts(NamedTuple):
    embedding_multiplier: float
    residual_multiplier: float
    attention_multiplier: float
    logits_scaling: float
    eps: float


def sizes_of(config: dict) -> SsmDenseSizes:
    return SsmDenseSizes(
        d_model=config["hidden_size"], m_head=config["mamba_n_heads"],
        m_head_dim=config["mamba_d_head"], m_state=config["mamba_d_state"],
        m_group=config["mamba_n_groups"], conv=config["mamba_d_conv"],
        chunk=config["mamba_chunk_size"],
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        d_ff=config["shared_intermediate_size"],
        layer_types=tuple(config["layer_types"][:config["num_layers"]]),
        vocab=config["vocab_size"])


def build(config: dict, mesh_sizes: dict, compute_dtype: str) -> Family:
    for key, want in (
            ("hidden_act", "silu"), ("normalization_function", "rmsnorm"),
            ("position_embedding_type", "nope"), ("num_local_experts", 0),
            ("num_experts_per_tok", 0), ("mamba_conv_bias", True),
            ("mamba_proj_bias", False), ("attention_bias", False),
            ("tie_word_embeddings", True)):
        if config.get(key) != want:
            raise ValueError(f"the ssm_dense family computes {key}={want!r} "
                             f"only, the configuration says "
                             f"{config.get(key)!r}")
    s = sizes_of(config)
    if len(s.layer_types) != config["num_layers"]:
        raise ValueError("num_layers must not pass the published layer_types")
    if s.m_head * s.m_head_dim != config["mamba_expand"] * s.d_model:
        raise ValueError("the published mixer is mamba_expand x hidden_size "
                         "wide")
    facts = Facts(
        embedding_multiplier=float(config["embedding_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        attention_multiplier=float(config["attention_multiplier"]),
        logits_scaling=float(config["logits_scaling"]),
        eps=float(config["rms_norm_eps"]))
    assumed = config["assumed"]
    cfg = ModelConfig(
        attn_dim=s.d_model, ffn_dim=s.d_ff, num_heads=s.n_head,
        num_kv_heads=s.n_kv_head, num_layers=s.n_layer, vocab_size=s.vocab,
        maxlen=config["max_position_embeddings"],
        compute_dtype=compute_dtype,
        ssm_dense=SsmDenseConfig(
            layer_types=s.layer_types, mamba_n_heads=s.m_head,
            mamba_d_head=s.m_head_dim, mamba_d_state=s.m_state,
            mamba_n_groups=s.m_group, mamba_d_conv=s.conv,
            mamba_chunk_size=s.chunk, mamba_expand=config["mamba_expand"],
            mamba_conv_bias=config["mamba_conv_bias"],
            embedding_multiplier=facts.embedding_multiplier,
            residual_multiplier=facts.residual_multiplier,
            attention_multiplier=facts.attention_multiplier,
            logits_scaling=facts.logits_scaling,
            position_embedding_type=config["position_embedding_type"],
            rms_norm_eps=facts.eps,
            initializer_range=float(assumed["initializer_range"]),
            time_step_min=float(assumed["time_step_min"]),
            time_step_max=float(assumed["time_step_max"]),
            time_step_floor=float(assumed["time_step_floor"])))
    # every knob the workload does not define stays at the program's default
    model = SsmDenseTransformer(cfg, tp_size=mesh_sizes.get("tp", 1))

    def loss(params, input_ids, target_ids, position_ids):
        return reference_loss(params, input_ids, target_ids, position_ids,
                              sizes=s, facts=facts)

    return Family(model=model, sizes=s, reference_loss=loss, facts=facts)


# ---- the plain reference ----

def _rms_norm(p, x, eps):
    return p["scale"] * x * lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)


def _conv_silu(u, w, bias):
    """u (b, t, c), w (c, taps), bias (c,): tap j reads the token taps-1-j
    back; plus the bias; then SiLU."""
    taps, t = w.shape[-1], u.shape[1]
    padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    return jax.nn.silu(bias + sum(padded[:, j:j + t] * w[:, j]
                                  for j in range(taps)))


def state_space_scan(x, dt, A, B, C):
    """The recurrence one token at a time: x (b, t, H, P), dt (b, t, H), A
    (H,), B and C (b, t, G, N) with H a multiple of G, head h reading group
    `h // (H / G)` -> y (b, t, H, P). State (b, H, P, N) from zero: S <-
    exp(dt A) S + dt x B^T, y = S C."""
    b, t, H, Pd = x.shape
    reads = jnp.arange(H) // (H // B.shape[2])

    def token(S, row):
        x_t, dt_t, B_t, C_t = row
        S = (jnp.exp(dt_t * A)[..., None, None] * S
             + jnp.einsum("bhp,bhn->bhpn", dt_t[..., None] * x_t,
                          B_t[:, reads]))
        return S, jnp.einsum("bhpn,bhn->bhp", S, C_t[:, reads])

    @jax.checkpoint
    def block(S, rows):
        return lax.scan(token, S, rows)

    # time first, in blocks of SCAN_BLOCK steps (the last one shorter)
    rows = tuple(jnp.moveaxis(z, 1, 0) for z in (x, dt, B, C))
    S = jnp.zeros((b, H, Pd, B.shape[-1]), jnp.float32)
    out = []
    whole = t - t % SCAN_BLOCK
    if whole:
        blocks = tuple(z[:whole].reshape(whole // SCAN_BLOCK, SCAN_BLOCK,
                                         *z.shape[1:]) for z in rows)
        S, y = lax.scan(block, S, blocks)
        out.append(y.reshape(whole, *y.shape[2:]))
    if t % SCAN_BLOCK:
        S, y = block(S, tuple(z[whole:] for z in rows))
        out.append(y)
    return jnp.moveaxis(jnp.concatenate(out), 0, 1)


def gate_then_norm(y, z, w, groups: int, eps: float):
    """`w * RMSNorm over a group's channels (y * silu(z))`: the gate first."""
    b, t, inner = y.shape
    g = (y * jax.nn.silu(z)).reshape(b, t, groups, inner // groups)
    g = g * lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True) + eps)
    return w * g.reshape(b, t, inner)


def _mamba(p, u, s: SsmDenseSizes, f: Facts, gated_norm=gate_then_norm):
    b, t, _ = u.shape
    H, Pd, G, N = s.m_head, s.m_head_dim, s.m_group, s.m_state
    proj = u @ p["w_in"]
    z, xBC, dt = (proj[..., :s.m_inner],
                  proj[..., s.m_inner:s.m_inner + s.m_conv_channels],
                  proj[..., s.m_inner + s.m_conv_channels:])
    xBC = _conv_silu(xBC, p["conv"], p["conv_bias"])
    x = xBC[..., :s.m_inner].reshape(b, t, H, Pd)
    B = xBC[..., s.m_inner:s.m_inner + G * N].reshape(b, t, G, N)
    C = xBC[..., s.m_inner + G * N:].reshape(b, t, G, N)
    y = state_space_scan(x, jax.nn.softplus(dt + p["dt_bias"]),
                         -jnp.exp(p["A_log"]), B, C)
    y = (y + p["D"][:, None] * x).reshape(b, t, s.m_inner)
    return gated_norm(y, z, p["norm"], G, f.eps) @ p["w_out"]


def _attention(lp, y, s: SsmDenseSizes, f: Facts):
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
                  * f.attention_multiplier)
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
    return (o.transpose(0, 2, 1, 3).reshape(b, t, s.n_head * h)
            @ lp["wo"]["weight"])


def _swiglu(lp, u):
    return ((jax.nn.silu(u @ lp["gate_proj"]["weight"])
             * (u @ lp["up_proj"]["weight"])) @ lp["down_proj"]["weight"])


def layers_in_order(params, layer_types):
    """The layers' parameters, one tree a layer, in the order they run: the
    program's blocks are periods of at most two runs of one kind each, by
    run length (`models/conv_moe.layer_blocks`), keyed
    `<kind>_layers_<block>` and stacked (periods, layers a period, ...)."""
    runs = []
    for name in layer_types:
        if runs and runs[-1][0] == name:
            runs[-1][1] += 1
        else:
            runs.append([name, 1])
    out, at, block = [], 0, 0
    take = lambda tree, *i: jax.tree.map(lambda a: a[i], tree)
    while at < len(runs):
        period = runs[at:at + 2]
        repeats = 1
        while (runs[at + repeats * len(period):
                    at + (repeats + 1) * len(period)] == period):
            repeats += 1
        for p in range(repeats):
            for name, n in period:
                key = f"{KEYS[name]}_layers_{block}"
                out += [(name, take(params[key], p, j)) for j in range(n)]
        at += repeats * len(period)
        block += 1
    return out


def _mean_ce(logits, targets):
    valid = targets != IGNORE_INDEX
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, jnp.where(valid, targets, 0)[..., None], axis=-1)[..., 0]
    return (jnp.sum(jnp.where(valid, lse - picked, 0.0))
            / jnp.maximum(jnp.sum(valid), 1))


def reference_loss(params, input_ids, target_ids, position_ids, *,
                   sizes: SsmDenseSizes, facts: Facts,
                   gated_norm=gate_then_norm):
    """The mean cross-entropy over the slice, float32. No layer takes
    positions. `gated_norm` is the mixer's gate and norm (the controls hand
    another)."""
    del position_ids
    s, f = sizes, facts
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    table = params["embedding"]["weight"][:s.vocab]

    def layer(name):
        @jax.checkpoint
        def run(x, lp):
            u = _rms_norm(lp["norm1"], x, f.eps)
            mixed = (_mamba(lp["mamba"], u, s, f, gated_norm)
                     if name == "mamba" else _attention(lp, u, s, f))
            h = x + f.residual_multiplier * mixed
            return h + f.residual_multiplier * _swiglu(
                lp, _rms_norm(lp["norm2"], h, f.eps))
        return run

    x = f.embedding_multiplier * table[input_ids]
    for name, lp in layers_in_order(params, s.layer_types):
        x = layer(name)(x, lp)
    logits = (_rms_norm(params["norm"], x, f.eps) @ table.T
              ) / f.logits_scaling
    return _mean_ce(logits, target_ids)
