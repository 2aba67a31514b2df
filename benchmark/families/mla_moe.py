"""The mla_moe family (DeepSeek-V3's architecture; JoyAI-LLM-Flash's
`config.json` has its keys): a configuration file in the published keys ->
the program's model (`models/mla_moe.LatentMoETransformer`) and the plain
reference the benchmark checks it against.

`reference_loss` is the benchmark's own copy of the architecture in
straightforward `jax.numpy`, float32: latent attention with full score
matrices (computed in blocks of query rows, each block and each layer under
`jax.checkpoint`, so that 2 x 4096 tokens of 32 heads keep half a gigabyte
of scores alive and not four), interleaved RoPE, the sigmoid top-k router,
**the held experts applied one by one to every token and masked by the
weights** (no sort, no gather, no grouped product), the shared expert, the
leading dense layer, the multi-token-prediction module and
`CE_main + lambda * CE_mtp`. No kernel, no sharding, no dispatch. It
consumes the parameter pytree `LatentMoETransformer.init` produces and is
given the same share of experts and the same vocabulary slice.

The configuration file states the cut (`reduced`) beside a `published`
group; the router is sized from `published.n_routed_experts`, never from
the experts held.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.lib.mla_moe_counts import LatentMoESizes
# at import, so that a program without the family fails before any device
# is touched (run.py loads this module before the runner starts)
from distributed_pytorch_from_scratch_tpu.config import (LatentMoEConfig,
                                                         ModelConfig)
from distributed_pytorch_from_scratch_tpu.models.mla_moe import (
    LatentMoETransformer)

IGNORE_INDEX = -1
QUERY_BLOCK = 512


class Family(NamedTuple):
    model: object            # the program's model, built for the mesh
    sizes: LatentMoESizes    # for benchmark/lib/mla_moe_counts.py; data is
                             # drawn from its `vocab` (the slice held)
    reference_loss: object   # (params, ids, tgt, pos) -> float32 loss
    reference_routed: object  # ... -> (loss, routed (expert layers, routed
                              # experts): the pairs each expert was chosen
                              # for, the module's layer last), for has_aux


def sizes_of(config: dict) -> LatentMoESizes:
    first = config["first_k_dense_replace"]
    return LatentMoESizes(
        d_model=config["hidden_size"], n_head=config["num_attention_heads"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"], d_ff=config["intermediate_size"],
        d_expert=config["moe_intermediate_size"],
        n_routed=config["published"]["n_routed_experts"],
        n_held=config["n_routed_experts"],
        n_shared=config["n_shared_experts"],
        top_k=config["num_experts_per_tok"],
        n_dense_layer=first, n_expert_layer=config["num_layers"] - first,
        n_mtp=config["num_nextn_predict_layers"],
        vocab=config["vocab_size"])


def build(config: dict, mesh_sizes: dict, compute_dtype: str) -> Family:
    for key, want in (("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"),
                      ("norm_topk_prob", True), ("n_group", 1),
                      ("topk_group", 1), ("hidden_act", "silu"),
                      ("rope_interleave", True), ("rope_scaling", None),
                      ("attention_bias", False),
                      ("tie_word_embeddings", False)):
        if config.get(key) != want:
            raise ValueError(f"the mla_moe family computes {key}={want!r} "
                             f"only, the configuration says "
                             f"{config.get(key)!r}")
    s = sizes_of(config)
    lam = float(config["assumed"]["mtp_loss_weight"]["value"])
    cfg = ModelConfig(
        attn_dim=s.d_model, ffn_dim=s.d_ff, num_heads=s.n_head,
        num_layers=s.n_dense_layer + s.n_expert_layer, vocab_size=s.vocab,
        maxlen=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]), compute_dtype=compute_dtype,
        num_experts=s.n_routed, moe_top_k=s.top_k,
        latent_moe=LatentMoEConfig(
            q_lora_rank=s.q_lora_rank, kv_lora_rank=s.kv_lora_rank,
            qk_nope_head_dim=s.qk_nope_head_dim,
            qk_rope_head_dim=s.qk_rope_head_dim, v_head_dim=s.v_head_dim,
            moe_intermediate_size=s.d_expert, n_shared_experts=s.n_shared,
            first_k_dense_replace=s.n_dense_layer,
            routed_scaling_factor=float(config["routed_scaling_factor"]),
            experts_held=s.n_held,
            expert_offset=int(config["deployment_share"]["expert_offset"]),
            num_nextn_predict_layers=s.n_mtp, mtp_loss_weight=lam,
            rms_norm_eps=float(config["rms_norm_eps"])))
    # every knob the workload does not define stays at the program's default
    model = LatentMoETransformer(cfg, tp_size=mesh_sizes.get("tp", 1))

    def routed(params, input_ids, target_ids, position_ids):
        return reference_loss_routed(
            params, input_ids, target_ids, position_ids, sizes=s,
            expert_offset=cfg.latent_moe.expert_offset,
            scaling=cfg.latent_moe.routed_scaling_factor,
            rope_theta=cfg.rope_theta, eps=cfg.latent_moe.rms_norm_eps,
            mtp_loss_weight=lam)

    return Family(model=model, sizes=s,
                  reference_loss=lambda *a: routed(*a)[0],
                  reference_routed=routed)


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


def _attention(lp, y, cos, sin, s: LatentMoESizes, eps):
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


def _expert_ffn(mp, y, s: LatentMoESizes, expert_offset: int, scaling):
    """sum over the experts HELD of w_e E_e(y), each expert applied to every
    token and masked by its weight, plus the shared expert; and how many
    (token, choice) pairs chose each routed expert."""
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
    routed = jnp.zeros(score.shape[-1]).at[chosen.reshape(-1)].add(1.0)
    return out.reshape(b, t, d), routed


def _layers(x, layers, cos, sin, s, eps, expert_offset, scaling):
    @jax.checkpoint
    def layer(x, lp):
        x = x + _attention(lp, _rms_norm(lp["norm1"], x, eps), cos, sin, s,
                           eps)
        y = _rms_norm(lp["norm2"], x, eps)
        if "moe" in lp:
            out, routed = _expert_ffn(lp["moe"], y, s, expert_offset, scaling)
            return x + out, routed
        return x + _swiglu(y, lp["gate_proj"]["weight"],
                           lp["up_proj"]["weight"],
                           lp["down_proj"]["weight"]), None

    return lax.scan(layer, x, layers)     # (x, routed a layer or None)


def _mean_ce(logits, targets):
    valid = targets != IGNORE_INDEX
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, jnp.where(valid, targets, 0)[..., None], axis=-1)[..., 0]
    return (jnp.sum(jnp.where(valid, lse - picked, 0.0))
            / jnp.maximum(jnp.sum(valid), 1))


def reference_losses(params, input_ids, target_ids, position_ids, *,
                     sizes: LatentMoESizes, expert_offset: int,
                     scaling: float, rope_theta: float, eps: float):
    """(CE of the main model, CE of the multi-token-prediction module or
    None, routed (expert layers, routed experts), the module's layer
    last), float32."""
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
        x, _ = run(x, params["dense_layers"])
    x, routed = run(x, params["layers"])
    main = _mean_ce(_rms_norm(params["norm"], x, eps) @ head, target_ids)
    if "mtp" not in params:
        return main, None, routed
    # h_i (before the main final norm) with Emb(t_{i+1}) predicts t_{i+2}
    mp = params["mtp"]
    known = target_ids != IGNORE_INDEX
    nxt = emb[jnp.where(known, target_ids, 0)]
    h = jnp.concatenate([_rms_norm(mp["hnorm"], x, eps),
                         _rms_norm(mp["enorm"], nxt, eps)], axis=-1)
    h, routed_mtp = run(h @ mp["eh_proj"]["weight"], params["mtp_layers"])
    after = jnp.concatenate(
        [target_ids[:, 1:], jnp.full_like(target_ids[:, :1], IGNORE_INDEX)],
        axis=1)
    after = jnp.where(known, after, IGNORE_INDEX)
    return (main, _mean_ce(_rms_norm(mp["norm"], h, eps) @ head, after),
            jnp.concatenate([routed, routed_mtp]))


def reference_loss_routed(params, input_ids, target_ids, position_ids, *,
                          mtp_loss_weight: float, **kw):
    main, mtp, routed = reference_losses(params, input_ids, target_ids,
                                         position_ids, **kw)
    loss = main if mtp is None else main + mtp_loss_weight * mtp
    return loss, lax.stop_gradient(routed)
