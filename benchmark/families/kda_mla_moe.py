"""The kda_mla_moe family (Ling-3.0's architecture, `bailing_hybrid`): a
configuration file in the published keys -> the program's model
(`models/kda_mla_moe.KdaMlaMoETransformer`) and the plain reference the
benchmark checks it against.

`reference_loss` is the benchmark's own copy of the architecture in
straightforward `jax.numpy`, float32: the layers LOOPED, layer `i` latent
attention where `(i + 1) % layer_group_size == 0` and Kimi Delta Attention
elsewhere; **the delta rule with a decay a channel token by token** (one
`lax.scan` over positions, under `jax.checkpoint` in blocks of 64 steps, so
that its backward keeps 64 states of 2 MB a layer at 4096 tokens and not
4096); the convolutions as shifted sums; latent attention with NO q latent,
interleaved RoPE on the rotary part, full score matrices in blocks of 512
query rows, a sigmoid gate a head before `wo`; the sigmoid router with its
selection LIMITED TO GROUPS (a group's score the sum of its two largest
biased scores, by reshape and `top_k`); **the held experts applied one by one
to every token and masked by the weights** (no sort, no gather, no grouped
product); the shared expert; the multi-token-prediction module where the
configuration keeps it. No kernel, no sharding, no dispatch, no chunked rule.
It consumes the parameter pytree `KdaMlaMoETransformer.init` produces and is
given the same share of experts and the same vocabulary slice.

The configuration file states the cut (`reduced`) beside a `published`
group; the router is sized from `published.num_experts`, never from the
experts held. The layers kept are `deployment_share.dense_layers_here`
leading dense layers (the published `first_k_dense_replace` counted once
where the cut says so) and the rest of `num_layers` (whole groups).
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.lib.kda_mla_moe_counts import KdaMlaMoESizes
# at import, so that a program without the family fails before any device
# is touched (run.py loads this module before the runner starts)
from distributed_pytorch_from_scratch_tpu.config import (KdaMlaMoEConfig,
                                                         ModelConfig)
from distributed_pytorch_from_scratch_tpu.models.kda_mla_moe import (
    KdaMlaMoETransformer)

IGNORE_INDEX = -1
QUERY_BLOCK = 512
RULE_BLOCK = 64


class Family(NamedTuple):
    model: object            # the program's model, built for the mesh
    sizes: KdaMlaMoESizes    # for benchmark/lib/kda_mla_moe_counts.py; data
                             # is drawn from its `vocab` (the slice held)
    reference_loss: object   # (params, ids, tgt, pos) -> float32 loss
    reference_routed: object  # ... -> (loss, routed (expert layers, routed
                              # experts)), for has_aux
    facts: object            # what the reference reads beside the sizes


class Facts(NamedTuple):
    expert_offset: int
    scaling: float
    lower_bound: float
    rope_theta: float
    eps: float
    mtp_loss_weight: float


def sizes_of(config: dict) -> KdaMlaMoESizes:
    group = config["layer_group_size"]
    layers = config["num_layers"]
    dense = int(config["deployment_share"]["dense_layers_here"])
    return KdaMlaMoESizes(
        d_model=config["hidden_size"], n_head=config["num_attention_heads"],
        d_k=config["head_dim"], d_v=config["head_dim"],
        conv=config["short_conv_kernel_size"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"], d_ff=config["intermediate_size"],
        d_expert=config["moe_intermediate_size"],
        n_routed=config["published"]["num_experts"],
        n_held=config["num_experts"],
        n_shared=config["num_shared_experts"],
        top_k=config["num_experts_per_tok"], n_group=config["n_group"],
        topk_group=config["topk_group"], group=group, n_dense_layer=dense,
        n_kda_expert_layer=layers - layers // group - dense,
        n_mla_expert_layer=layers // group,
        n_mtp=config["num_nextn_predict_layers"],
        vocab=config["vocab_size"])


def build(config: dict, mesh_sizes: dict, compute_dtype: str) -> Family:
    for key, want in (
            ("norm_topk_prob", True), ("hidden_act", "silu"),
            ("score_function", "sigmoid"), ("topk_method", "noaux_tc"),
            ("moe_router_enable_expert_bias", True),
            ("scale_router_input", False), ("q_lora_rank", None),
            ("rope_scaling", None), ("rope_interleave", True),
            ("use_mla_nope", False), ("mtp_use_kda", False),
            ("gated_attention_proj_granularity_type", "head_wise"),
            ("linear_silu", True), ("kda_safe_gate", True),
            ("no_kda_lora", True), ("use_kda_lora", False),
            ("num_kv_heads_for_linear_attn", 0), ("group_norm_size", 1),
            ("use_qk_norm", True), ("use_bias", False),
            ("use_qkv_bias", False), ("use_nGPT", False),
            ("value_norm", False), ("up_proj_norm", False),
            ("tie_word_embeddings", False)):
        if config.get(key) != want:
            raise ValueError(f"the kda_mla_moe family computes {key}="
                             f"{want!r} only, the configuration says "
                             f"{config.get(key)!r}")
    if config["rotary_dim"] != config["qk_rope_head_dim"] or (
            config["qk_head_dim"] != config["qk_nope_head_dim"]
            + config["qk_rope_head_dim"]):
        raise ValueError("rotary_dim must be qk_rope_head_dim and "
                         "qk_head_dim the sum of its two parts")
    s = sizes_of(config)
    if (config["moe_shared_expert_intermediate_size"]
            != s.n_shared * s.d_expert):
        raise ValueError("the shared expert's width must be num_shared_"
                         "experts routed experts'")
    share = config["deployment_share"]
    # the published SwiGLU limits of the layers KEPT must be 0: no clamp is
    # written (they are 0 in layers 0 - 34 and not in the last seven)
    kept = s.n_layer + config["first_k_dense_replace"] - s.n_dense_layer
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        if any(config[key][:kept]):
            raise ValueError(f"a kept layer's {key} is not 0: the clamp it "
                             f"asks for is not written")
    facts = Facts(
        expert_offset=int(share["expert_offset"]),
        scaling=float(config["routed_scaling_factor"]),
        lower_bound=float(config["kda_lower_bound"]),
        rope_theta=float(config["rope_theta"]),
        eps=float(config["rms_norm_eps"]),
        mtp_loss_weight=float(config["mtp_loss_scaling_factor"]))
    cfg = ModelConfig(
        attn_dim=s.d_model, ffn_dim=s.d_ff, num_heads=s.n_head,
        num_layers=s.n_layer, vocab_size=s.vocab,
        maxlen=config["max_position_embeddings"],
        rope_theta=facts.rope_theta, compute_dtype=compute_dtype,
        num_experts=s.n_routed, moe_top_k=s.top_k,
        kda_mla_moe=KdaMlaMoEConfig(
            head_dim=s.d_k, kv_lora_rank=s.kv_lora_rank,
            qk_nope_head_dim=s.qk_nope_head_dim,
            qk_rope_head_dim=s.qk_rope_head_dim, v_head_dim=s.v_head_dim,
            moe_intermediate_size=s.d_expert, q_lora_rank=None,
            layer_group_size=s.group, first_k_dense_replace=s.n_dense_layer,
            short_conv_kernel_size=s.conv,
            kda_lower_bound=facts.lower_bound, n_shared_experts=s.n_shared,
            n_group=s.n_group, topk_group=s.topk_group,
            routed_scaling_factor=facts.scaling, experts_held=s.n_held,
            expert_offset=facts.expert_offset,
            num_nextn_predict_layers=s.n_mtp,
            mtp_loss_weight=facts.mtp_loss_weight, rms_norm_eps=facts.eps))
    # every knob the workload does not define stays at the program's default
    model = KdaMlaMoETransformer(cfg, tp_size=mesh_sizes.get("tp", 1))

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


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _rope(x, cos, sin):
    """Interleaved pairs (x_2i, x_2i+1) of x (b, heads, t, dim); cos/sin
    (b, 1, t, dim/2)."""
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _conv_silu(u, w):
    """u (b, t, H, c), w (H, c, taps): tap j reads the token taps-1-j
    back; then SiLU."""
    taps, t = w.shape[-1], u.shape[1]
    padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0), (0, 0)))
    return jax.nn.silu(sum(padded[:, j:j + t] * w[..., j]
                           for j in range(taps)))


def channel_decay(p, y, lower_bound):
    """The decay a channel, (b, t, H, d_k): the bounded gate."""
    a = jnp.einsum("btd,dhc->bthc", y, p["w_f"])
    return lower_bound * jax.nn.sigmoid(
        jnp.exp(p["A_log"])[:, None] * (a + p["dt_bias"]))


def _delta_rule(q, k, v, g, beta):
    """The rule one token at a time: q, k, g (b, t, H, d_k), v (b, t, H,
    d_v), beta (b, t, H) -> o (b, t, H, d_v). State (b, H, d_k, d_v) from
    zero; row c of the state decays by exp(g[c])."""
    b, t, H, dk = q.shape
    dv = v.shape[-1]

    def token(S, row):
        q_t, k_t, v_t, g_t, b_t = row
        S = jnp.exp(g_t)[..., None] * S
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


def _kda(p, y, f: Facts, decay=channel_decay):
    b, t, _ = y.shape
    H, dk = p["w_q"].shape[1:]
    dv = p["w_v"].shape[-1]
    project = lambda w: jnp.einsum("btd,dhc->bthc", y, w)
    q = _conv_silu(project(p["w_q"]), p["conv_q"])
    k = _conv_silu(project(p["w_k"]), p["conv_k"])
    v = _conv_silu(project(p["w_v"]), p["conv_v"])
    l2 = lambda x: x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                                + f.eps)
    beta = jax.nn.sigmoid(jnp.einsum("btd,dh->bth", y, p["w_beta"]))
    o = _delta_rule(l2(q) / math.sqrt(dk), l2(k), v,
                    decay(p, y, f.lower_bound), beta)
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + f.eps)
    o = p["o_norm"]["scale"] * o * jax.nn.sigmoid(project(p["w_g"]))
    return o.reshape(b, t, H * dv) @ p["w_out"]


def _mla(p, y, cos, sin, s: KdaMlaMoESizes, f: Facts):
    b, t, _ = y.shape
    nope, rope, vd = s.qk_nope_head_dim, s.qk_rope_head_dim, s.v_head_dim
    heads = lambda z, w: z.reshape(b, t, s.n_head, w).transpose(0, 2, 1, 3)
    q = heads(y @ p["wq"]["weight"], nope + rope)
    ckv = y @ p["wkv_a"]["weight"]
    c_kv = _rms_norm(p["kv_norm"], ckv[..., :s.kv_lora_rank], f.eps)
    k_r = ckv[..., s.kv_lora_rank:][:, None]             # one head for all
    kv = heads(c_kv @ p["wkv_b"]["weight"], nope + vd)
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

    # one block of query rows at a time, the last one shorter
    step = min(QUERY_BLOCK, t)
    whole = t - t % step
    blocks = q[:, :, :whole].reshape(b, s.n_head, whole // step, step,
                                     nope + rope)
    o = lax.map(lambda block: rows(*block),
                (jnp.moveaxis(blocks, 2, 0), jnp.arange(0, whole, step)))
    o = jnp.moveaxis(o, 0, 2).reshape(b, s.n_head, whole, vd)
    if whole < t:
        o = jnp.concatenate([o, rows(q[:, :, whole:], whole)], axis=2)
    gate = jax.nn.sigmoid(y @ p["w_gate"]["weight"])        # (b, t, heads)
    o = o * gate.transpose(0, 2, 1)[..., None]
    return o.transpose(0, 2, 1, 3).reshape(b, t, s.n_head * vd) \
        @ p["wo"]["weight"]


def choose(biased, s: KdaMlaMoESizes):
    """The group-limited selection: (S, routed) biased scores -> the chosen
    experts (S, top_k)."""
    S, E = biased.shape
    if s.n_group > 1:
        grouped = biased.reshape(S, s.n_group, E // s.n_group)
        of_group = lax.top_k(grouped, 2)[0].sum(-1)
        kept = lax.top_k(of_group, s.topk_group)[1]
        keep = jnp.zeros((S, s.n_group), bool).at[
            jnp.arange(S)[:, None], kept].set(True)
        biased = jnp.where(keep[..., None], grouped, -jnp.inf).reshape(S, E)
    return lax.top_k(biased, s.top_k)[1]


def _expert_ffn(mp, y, s: KdaMlaMoESizes, f: Facts):
    """sum over the experts HELD of w_e E_e(y), each expert applied to every
    token and masked by its weight, plus the shared expert; and how many
    (token, choice) pairs chose each routed expert."""
    b, t, d = y.shape
    x = y.reshape(b * t, d)
    score = jax.nn.sigmoid(x @ mp["router"])                  # all routed
    chosen = choose(score + lax.stop_gradient(mp["bias"]), s)
    w = jnp.take_along_axis(score, chosen, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * f.scaling

    @jax.checkpoint
    def one(acc, expert):
        e, gate, up, down = expert
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)
        return acc + w_e[:, None] * _swiglu(x, gate, up, down), None

    held = mp["gate"].shape[0]
    out, _ = lax.scan(one, jnp.zeros_like(x),
                      (f.expert_offset + jnp.arange(held), mp["gate"],
                       mp["up"], mp["down"]))
    sh = mp["shared"]
    out = out + _swiglu(x, sh["gate"], sh["up"], sh["down"])
    routed = jnp.zeros(score.shape[-1]).at[chosen.reshape(-1)].add(1.0)
    return out.reshape(b, t, d), routed


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


def reference_loss_routed(params, input_ids, target_ids, position_ids, *,
                          sizes: KdaMlaMoESizes, facts: Facts,
                          decay=channel_decay):
    """(mean cross-entropy over the slice, with the module's where the
    configuration keeps one; routed (expert layers, routed experts): the
    pairs each expert was chosen for, a row an expert layer in the order the
    layers run, the module's last), float32. `decay` is the gate a delta
    layer's decay is made by (the controls hand another)."""
    s, f = sizes, facts
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    emb = params["embedding"]["weight"]
    head = params["lm_head"]["weight"][:, :s.vocab]
    rope = s.qk_rope_head_dim
    theta = 1.0 / (f.rope_theta ** (
        jnp.arange(0, rope, 2, dtype=jnp.float32) / rope))
    ang = position_ids.astype(jnp.float32)[:, None, :, None] * theta
    cos, sin = jnp.cos(ang), jnp.sin(ang)

    @jax.checkpoint
    def layer(x, lp):
        y = _rms_norm(lp["norm1"], x, f.eps)
        if "kda" in lp:
            x = x + _kda(lp["kda"], y, f, decay)
        else:
            x = x + _mla(lp["mla"], y, cos, sin, s, f)
        y = _rms_norm(lp["norm2"], x, f.eps)
        if "moe" in lp:
            out, routed = _expert_ffn(lp["moe"], y, s, f)
            return x + out, routed
        return x + _swiglu(y, lp["gate_proj"]["weight"],
                           lp["up_proj"]["weight"],
                           lp["down_proj"]["weight"]), None

    x = emb[input_ids]
    routed = []
    for lp in layers_in_order(params):
        x, chose = layer(x, lp)
        if chose is not None:
            routed.append(chose)
    loss = _mean_ce(_rms_norm(params["norm"], x, f.eps) @ head, target_ids)
    if "mtp" in params:
        # h_i (before the main final norm) with Emb(t_{i+1}) predicts t_{i+2}
        mp = params["mtp"]
        known = target_ids != IGNORE_INDEX
        nxt = emb[jnp.where(known, target_ids, 0)]
        h = jnp.concatenate([_rms_norm(mp["hnorm"], x, f.eps),
                             _rms_norm(mp["enorm"], nxt, f.eps)], axis=-1)
        h, chose = layer(h @ mp["eh_proj"]["weight"],
                         jax.tree.map(lambda a: a[0], params["mtp_layers"]))
        routed.append(chose)
        after = jnp.concatenate(
            [target_ids[:, 1:],
             jnp.full_like(target_ids[:, :1], IGNORE_INDEX)], axis=1)
        after = jnp.where(known, after, IGNORE_INDEX)
        loss = loss + f.mtp_loss_weight * _mean_ce(
            _rms_norm(mp["norm"], h, f.eps) @ head, after)
    return loss, lax.stop_gradient(jnp.stack(routed))
