"""The ssm_moe family (Nemotron-H as Nemotron 3 publishes it, `nemotron_h`):
a configuration file in the published keys -> the program's model
(`models/ssm_moe.SsmMoETransformer`) and the plain reference the benchmark
checks it against.

`reference_loss` is the benchmark's own copy of the architecture in
straightforward `jax.numpy`, float32: the layers LOOPED over the pattern's
letters, each ONE norm and ONE sublayer; **the Mamba-2 recurrence token by
token** (one `lax.scan` over positions, under `jax.checkpoint` in blocks of
64 steps, so that its backward keeps 64 states of 1 MB a layer at 4096
tokens and not 4096), head `h` reading the B and C of group `h // (H / G)`;
the convolution as shifted sums; the gate before the norm over a group's
channels; attention with NO positions, full score matrices in blocks of 512
query rows; the sigmoid router over the d-wide token; **the held experts
applied one by one to every token's latent and masked by the weights** (two
matrices and a squared ReLU; no sort, no gather, no grouped product), their
sum up the latent's second projection; the shared expert at the model's
width; the multi-token-prediction module where the configuration keeps it.
No kernel, no sharding, no dispatch, no chunked recurrence. It consumes the
parameter pytree `SsmMoETransformer.init` produces and is given the same
share of heads, groups and experts and the same vocabulary slice.

The configuration file states the cut (`reduced`) beside a `published`
group; the router is sized from `published.n_routed_experts`, never from
the experts held. The heads and groups held are
`deployment_share.*_here` (one rank of `tensor_parallel`, checked against
the published counts), the layers `deployment_share.layers_here`.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.lib.ssm_moe_counts import SsmMoESizes
# at import, so that a program without the family fails before any device
# is touched (run.py loads this module before the runner starts)
from distributed_pytorch_from_scratch_tpu.config import (ModelConfig,
                                                         SsmMoEConfig)
from distributed_pytorch_from_scratch_tpu.models.ssm_moe import (
    SsmMoETransformer)

IGNORE_INDEX = -1
QUERY_BLOCK = 512
SCAN_BLOCK = 64
KEYS = {"M": "mamba", "*": "attn", "E": "moe"}


class Family(NamedTuple):
    model: object            # the program's model, built for the mesh
    sizes: SsmMoESizes       # for benchmark/lib/ssm_moe_counts.py; data is
                             # drawn from its `vocab` (the slice held)
    reference_loss: object   # (params, ids, tgt, pos) -> float32 loss
    reference_routed: object  # ... -> (loss, routed (expert layers, routed
                              # experts)), for has_aux
    facts: object            # what the reference reads beside the sizes


class Facts(NamedTuple):
    expert_offset: int
    head_offset: int
    scaling: float
    eps: float
    mtp_loss_weight: float


def sizes_of(config: dict) -> SsmMoESizes:
    share = config["deployment_share"]
    return SsmMoESizes(
        d_model=config["hidden_size"],
        m_head=int(share["mamba_heads_here"]),
        m_head_dim=config["mamba_head_dim"],
        m_state=config["ssm_state_size"],
        m_group=int(share["mamba_groups_here"]),
        conv=config["conv_kernel"], chunk=config["chunk_size"],
        n_head=int(share["attention_heads_here"]),
        n_kv_head=int(share["key_value_heads_here"]),
        head_dim=config["head_dim"], d_latent=config["moe_latent_size"],
        d_expert=config["moe_intermediate_size"],
        d_shared=config["moe_shared_expert_intermediate_size"],
        n_routed=config["published"]["n_routed_experts"],
        n_held=config["n_routed_experts"],
        top_k=config["num_experts_per_tok"],
        pattern=share["layers_here"],
        mtp_pattern=(config["mtp_hybrid_override_pattern"]
                     * config["num_nextn_predict_layers"]),
        vocab=config["vocab_size"])


def build(config: dict, mesh_sizes: dict, compute_dtype: str) -> Family:
    for key, want in (
            ("norm_topk_prob", True), ("mlp_hidden_act", "relu2"),
            ("mamba_hidden_act", "silu"), ("n_group", 1), ("topk_group", 1),
            ("n_shared_experts", 1), ("use_conv_bias", True),
            ("mamba_proj_bias", False), ("use_bias", False),
            ("mlp_bias", False), ("attention_bias", False),
            ("residual_in_fp32", False), ("sliding_window", None),
            ("tie_word_embeddings", False)):
        if config.get(key) != want:
            raise ValueError(f"the ssm_moe family computes {key}={want!r} "
                             f"only, the configuration says "
                             f"{config.get(key)!r}")
    s = sizes_of(config)
    share = config["deployment_share"]
    tp = int(share["tensor_parallel"])
    for here, key in ((s.m_head, "mamba_num_heads"), (s.m_group, "n_groups"),
                      (s.n_head, "num_attention_heads")):
        if here * tp != config[key]:
            raise ValueError(f"{here} of {key} {config[key]} held is not "
                             f"one rank of tensor_parallel {tp}")
    if s.n_kv_head != max(1, config["num_key_value_heads"] // tp):
        raise ValueError("the key-value heads held are not one rank's")
    if len(s.pattern) != config["num_layers"] or (
            s.pattern not in config["hybrid_override_pattern"]):
        raise ValueError("layers_here must be num_layers letters that stand "
                         "together in the published pattern")
    if config["mamba_num_heads"] * s.m_head_dim != (
            config["expand"] * s.d_model):
        raise ValueError("the published mixer is expand x hidden_size wide")
    rank = int(share["tensor_parallel_rank"])
    facts = Facts(
        expert_offset=int(share["expert_offset"]),
        head_offset=rank * s.m_head,
        scaling=float(config["routed_scaling_factor"]),
        eps=float(config["layer_norm_epsilon"]),
        mtp_loss_weight=SsmMoEConfig.mtp_loss_weight)
    cfg = ModelConfig(
        attn_dim=s.d_model, ffn_dim=s.d_shared, num_heads=s.n_head,
        num_kv_heads=s.n_kv_head, num_layers=s.n_layer, vocab_size=s.vocab,
        maxlen=config["max_position_embeddings"],
        compute_dtype=compute_dtype, num_experts=s.n_routed,
        moe_top_k=s.top_k,
        ssm_moe=SsmMoEConfig(
            hybrid_override_pattern=s.pattern, mamba_num_heads=s.m_head,
            mamba_head_dim=s.m_head_dim, ssm_state_size=s.m_state,
            n_groups=s.m_group, head_dim=s.head_dim,
            moe_intermediate_size=s.d_expert, moe_latent_size=s.d_latent,
            moe_shared_expert_intermediate_size=s.d_shared,
            conv_kernel=s.conv, chunk_size=s.chunk,
            mamba_head_offset=facts.head_offset,
            routed_scaling_factor=facts.scaling, n_group=config["n_group"],
            topk_group=config["topk_group"], experts_held=s.n_held,
            expert_offset=facts.expert_offset,
            num_nextn_predict_layers=config["num_nextn_predict_layers"],
            mtp_hybrid_override_pattern=config["mtp_hybrid_override_pattern"],
            mtp_loss_weight=facts.mtp_loss_weight,
            time_step_min=config["time_step_min"],
            time_step_max=config["time_step_max"],
            time_step_floor=config["time_step_floor"], norm_eps=facts.eps))
    # every knob the workload does not define stays at the program's default
    model = SsmMoETransformer(cfg, tp_size=mesh_sizes.get("tp", 1))

    def routed(params, input_ids, target_ids, position_ids):
        return reference_loss_routed(params, input_ids, target_ids,
                                     position_ids, sizes=s, facts=facts)

    return Family(model=model, sizes=s,
                  reference_loss=lambda *a: routed(*a)[0],
                  reference_routed=routed, facts=facts)


# ---- the plain reference ----

def _rms_norm(p, x, eps):
    return p["scale"] * x * lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)


def _relu2_expert(x, up, down):
    return jnp.square(jnp.maximum(x @ up, 0.0)) @ down


def _conv_silu(u, w, bias):
    """u (b, t, c), w (c, taps), bias (c,): tap j reads the token taps-1-j
    back; plus the bias; then SiLU."""
    taps, t = w.shape[-1], u.shape[1]
    padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    return jax.nn.silu(bias + sum(padded[:, j:j + t] * w[:, j]
                                  for j in range(taps)))


def state_space_scan(x, dt, A, B, C):
    """The recurrence one token at a time: x (b, t, H, P), dt (b, t, H), A
    (H,), B and C (b, t, H, N), a head's own -> y (b, t, H, P). State (b, H,
    P, N) from zero: S <- exp(dt A) S + dt x B^T, y = S C."""
    b, t, H, Pd = x.shape

    def token(S, row):
        x_t, dt_t, B_t, C_t = row
        S = (jnp.exp(dt_t * A)[..., None, None] * S
             + jnp.einsum("bhp,bhn->bhpn", dt_t[..., None] * x_t, B_t))
        return S, jnp.einsum("bhpn,bhn->bhp", S, C_t)

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


def group_of_head(heads: int, groups: int):
    """The B / C group each head reads: h // (heads / groups)."""
    return jnp.arange(heads) // (heads // groups)


def _mamba(p, u, s: SsmMoESizes, f: Facts, group_of=group_of_head):
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
    reads = group_of(H, G)
    y = state_space_scan(x, jax.nn.softplus(dt + p["dt_bias"]),
                         -jnp.exp(p["A_log"]), B[:, :, reads], C[:, :, reads])
    y = (y + p["D"][:, None] * x).reshape(b, t, s.m_inner)
    gated = (y * jax.nn.silu(z)).reshape(b, t, G, s.m_inner // G)
    gated = gated * lax.rsqrt(
        jnp.mean(jnp.square(gated), axis=-1, keepdims=True) + f.eps)
    return (p["norm"] * gated.reshape(b, t, s.m_inner)) @ p["w_out"]


def _attention(lp, y, s: SsmMoESizes):
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
    return (o.transpose(0, 2, 1, 3).reshape(b, t, s.n_head * h)
            @ lp["wo"]["weight"])


def _expert_ffn(mp, y, s: SsmMoESizes, f: Facts, expert=_relu2_expert):
    """(sum over the experts HELD of w_e E_e(l)) W_up, each expert applied
    to every token's latent l = x W_down and masked by its weight, plus the
    shared expert on x; and how many (token, choice) pairs chose each routed
    expert."""
    b, t, d = y.shape
    x = y.reshape(b * t, d)
    score = jax.nn.sigmoid(x @ mp["router"])                  # all routed
    chosen = lax.top_k(score + lax.stop_gradient(mp["bias"]), s.top_k)[1]
    w = jnp.take_along_axis(score, chosen, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * f.scaling
    latent = x @ mp["latent"]["down"]

    @jax.checkpoint
    def one(acc, held):
        e, up, down = held
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)
        return acc + w_e[:, None] * expert(latent, up, down), None

    n = mp["up"].shape[0]
    out, _ = lax.scan(one, jnp.zeros_like(latent),
                      (f.expert_offset + jnp.arange(n), mp["up"],
                       mp["down"]))
    out = (out @ mp["latent"]["up"]
           + expert(x, mp["shared"]["up"], mp["shared"]["down"]))
    routed = jnp.zeros(score.shape[-1]).at[chosen.reshape(-1)].add(1.0)
    return out.reshape(b, t, d), routed


def layers_in_order(params, pattern: str):
    """The main model's layers' parameters, one tree a layer, in the order
    they run: the program's blocks are periods of at most two runs of one
    kind each, by run length (`models/conv_moe.layer_blocks`), keyed
    `<kind>_layers_<block>` and stacked (periods, layers a period, ...)."""
    runs = []
    for letter in pattern:
        if runs and runs[-1][0] == letter:
            runs[-1][1] += 1
        else:
            runs.append([letter, 1])
    out, at, block = [], 0, 0
    take = lambda tree, *i: jax.tree.map(lambda a: a[i], tree)
    while at < len(runs):
        period = runs[at:at + 2]
        repeats = 1
        while (runs[at + repeats * len(period):
                    at + (repeats + 1) * len(period)] == period):
            repeats += 1
        for p in range(repeats):
            for letter, n in period:
                key = f"{KEYS[letter]}_layers_{block}"
                out += [(letter, take(params[key], p, j)) for j in range(n)]
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


def reference_loss_routed(params, input_ids, target_ids, position_ids, *,
                          sizes: SsmMoESizes, facts: Facts,
                          group_of=group_of_head, expert=_relu2_expert):
    """(mean cross-entropy over the slice, with the module's where the
    configuration keeps one; routed (expert layers, routed experts): the
    pairs each expert was chosen for, a row an expert layer in the order the
    layers run, the module's last), float32. No layer takes positions.
    `group_of` says which group a head reads and `expert` is an expert's
    function (the controls hand others)."""
    del position_ids
    s, f = sizes, facts
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    emb = params["embedding"]["weight"]
    head = params["lm_head"]["weight"][:, :s.vocab]

    def layer(letter):
        @jax.checkpoint
        def run(x, lp):
            y = _rms_norm(lp["norm1"], x, f.eps)
            if letter == "M":
                return x + _mamba(lp["mamba"], y, s, f, group_of), None
            if letter == "*":
                return x + _attention(lp, y, s), None
            out, routed = _expert_ffn(lp["moe"], y, s, f, expert)
            return x + out, routed
        return run

    x = emb[input_ids]
    routed = []
    for letter, lp in layers_in_order(params, s.pattern):
        x, chose = layer(letter)(x, lp)
        if chose is not None:
            routed.append(chose)
    loss = _mean_ce(_rms_norm(params["norm"], x, f.eps) @ head, target_ids)
    if s.mtp_pattern:
        # h_i (before the main final norm) with Emb(t_{i+1}) predicts t_{i+2}
        mp = params["mtp"]
        known = target_ids != IGNORE_INDEX
        nxt = emb[jnp.where(known, target_ids, 0)]
        h = jnp.concatenate([_rms_norm(mp["hnorm"], x, f.eps),
                             _rms_norm(mp["enorm"], nxt, f.eps)], axis=-1)
        h = h @ mp["eh_proj"]["weight"]
        for letter in s.mtp_pattern:
            h, chose = layer(letter)(h, jax.tree.map(
                lambda a: a[0], params[f"mtp_{KEYS[letter]}_layers"]))
            if chose is not None:
                routed.append(chose)
        after = jnp.concatenate(
            [target_ids[:, 1:],
             jnp.full_like(target_ids[:, :1], IGNORE_INDEX)], axis=1)
        after = jnp.where(known, after, IGNORE_INDEX)
        loss = loss + f.mtp_loss_weight * _mean_ce(
            _rms_norm(mp["norm"], h, f.eps) @ head, after)
    return loss, lax.stop_gradient(jnp.stack(routed))
