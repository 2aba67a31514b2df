"""The mhc_mla_moe family (Xing4.0-29B-A4B's `config.json`, `model_type`
`xing4_0`: DeepSeek-V3's keys plus `hc_mult`, `hc_sinkhorn_iters`, `hc_eps`,
`mhc_h_res_clamp_min/max`, and `rope_scaling` of type `yarn`): a
configuration file in the published keys -> the program's model
(`models/mhc_mla_moe.HyperLatentMoETransformer`) and the plain reference the
benchmark checks it against.

`reference_loss` is the benchmark's own copy of the architecture in
straightforward `jax.numpy`, float32: the residual state an explicit
`(b, t, n, C)` array, every mixer written out per token (the flattened
streams through W, the three maps, the Sinkhorn rounds a Python loop over
`(b, t, n, n)` matrices), YaRN's tables from the formula, and `mla_moe`'s
sublayers as `benchmark/families/mla_moe.py` writes them (latent attention
with full score matrices in blocks of query rows, each block and each layer
under `jax.checkpoint`; **the held experts applied one by one to every
token and masked by the weights**; the shared expert; the leading dense
layer), the exit mixer, `CE_main` and, where the module runs,
`+ lambda * CE_mtp` over streams. No kernel, no sharding, no dispatch. It
consumes the parameter pytree `HyperLatentMoETransformer.init` produces and
is given the same share of experts and the same vocabulary slice. The
sublayers' small functions (`_rms_norm`, `_rope`, `_swiglu`, `_expert_ffn`,
`_mean_ce`) are that file's own, imported: they are the benchmark's, not the
program's; the attention is written again here, because its softmax scale
carries YaRN's mscale^2.

The configuration file states the cut (`reduced`) beside a `published`
group; the router is sized from `published.n_routed_experts`, never from
the experts held, and the leading dense layers held here from
`deployment_share.dense_layers_here` (`first_k_dense_replace` stands as
published).
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.families.mla_moe import (IGNORE_INDEX, QUERY_BLOCK,
                                        _expert_ffn, _mean_ce, _rms_norm,
                                        _rope, _swiglu)
from benchmark.lib.mhc_mla_moe_counts import HyperLatentMoESizes
# at import, so that a program without the family fails before any device
# is touched (run.py loads this module before the runner starts)
from distributed_pytorch_from_scratch_tpu.config import (
    HyperConnectionConfig, LatentMoEConfig, ModelConfig)
from distributed_pytorch_from_scratch_tpu.models.mhc_mla_moe import (
    HyperLatentMoETransformer)
from distributed_pytorch_from_scratch_tpu.ops.rope import YarnScaling


class Family(NamedTuple):
    model: object            # the program's model, built for the mesh
    sizes: HyperLatentMoESizes
    reference_loss: object   # (params, ids, tgt, pos) -> float32 loss
    reference_routed: object  # ... -> (loss, routed (expert layers, routed
                              # experts)), for has_aux


class Hyper(NamedTuple):
    """What the reference reads beside `sizes`."""

    eps: float               # rms_norm_eps
    hc_eps: float
    clamp: tuple
    rope_theta: float
    yarn: "dict | None"
    expert_offset: int
    scaling: float
    mtp_loss_weight: float


def sizes_of(config: dict) -> HyperLatentMoESizes:
    dense = int(config["deployment_share"]["dense_layers_here"])
    return HyperLatentMoESizes(
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
        n_dense_layer=dense, n_expert_layer=config["num_layers"] - dense,
        n_mtp=config["num_nextn_predict_layers"],
        vocab=config["vocab_size"], hc_mult=config["hc_mult"],
        sinkhorn_iters=config["hc_sinkhorn_iters"])


def build(config: dict, mesh_sizes: dict, compute_dtype: str) -> Family:
    for key, want in (("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"),
                      ("norm_topk_prob", True), ("n_group", 1),
                      ("topk_group", 1), ("hidden_act", "silu"),
                      ("attention_bias", False),
                      ("tie_word_embeddings", False)):
        if config.get(key) != want:
            raise ValueError(f"the mhc_mla_moe family computes "
                             f"{key}={want!r} only, the configuration says "
                             f"{config.get(key)!r}")
    yarn = config["rope_scaling"]
    if yarn is not None and yarn.get("type") != "yarn":
        raise ValueError(f"rope_scaling of type {yarn.get('type')!r}: the "
                         f"family computes yarn or none")
    if not 1 <= sizes_of(config).n_dense_layer <= config[
            "first_k_dense_replace"]:
        raise ValueError("deployment_share.dense_layers_here must hold 1 "
                         "to first_k_dense_replace leading dense layers")
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
            rms_norm_eps=float(config["rms_norm_eps"]),
            rope_scaling=yarn and YarnScaling(
                factor=float(yarn["factor"]),
                original_max_position_embeddings=int(
                    yarn["original_max_position_embeddings"]),
                beta_fast=float(yarn["beta_fast"]),
                beta_slow=float(yarn["beta_slow"]),
                mscale=float(yarn["mscale"]),
                mscale_all_dim=float(yarn["mscale_all_dim"])),
            hyper=HyperConnectionConfig(
                hc_mult=s.hc_mult, hc_sinkhorn_iters=s.sinkhorn_iters,
                hc_eps=float(config["hc_eps"]),
                mhc_h_res_clamp_min=float(config["mhc_h_res_clamp_min"]),
                mhc_h_res_clamp_max=float(config["mhc_h_res_clamp_max"]))))
    # every knob the workload does not define stays at the program's default
    model = HyperLatentMoETransformer(cfg, tp_size=mesh_sizes.get("tp", 1))
    hyper = Hyper(
        eps=cfg.latent_moe.rms_norm_eps, hc_eps=float(config["hc_eps"]),
        clamp=(float(config["mhc_h_res_clamp_min"]),
               float(config["mhc_h_res_clamp_max"])),
        rope_theta=cfg.rope_theta, yarn=yarn,
        expert_offset=cfg.latent_moe.expert_offset,
        scaling=cfg.latent_moe.routed_scaling_factor, mtp_loss_weight=lam)

    def routed(params, input_ids, target_ids, position_ids):
        return reference_loss_routed(params, input_ids, target_ids,
                                     position_ids, sizes=s, hyper=hyper)

    return Family(model=model, sizes=s,
                  reference_loss=lambda *a: routed(*a)[0],
                  reference_routed=routed)


# ---- the plain reference ----

def yarn_tables(hyper: Hyper, dim: int, position_ids):
    """(cos, sin) (b, 1, t, dim/2) and what the softmax scale is multiplied
    by: DeepSeek-V2's published rule, written out."""
    base, y = hyper.rope_theta, hyper.yarn
    freq = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    pos = position_ids.astype(jnp.float32)[:, None, :, None]
    if y is None:
        return jnp.cos(pos * freq), jnp.sin(pos * freq), 1.0

    def correction(turns):
        return dim * math.log(y["original_max_position_embeddings"]
                              / (turns * 2 * math.pi)) / (2 * math.log(base))

    def get_mscale(scale, mscale):
        return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0

    low = max(math.floor(correction(y["beta_fast"])), 0)
    high = min(math.ceil(correction(y["beta_slow"])), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / ((high - low) or 0.001), 0.0, 1.0)
    freq = freq / y["factor"] * ramp + freq * (1.0 - ramp)
    table = (get_mscale(y["factor"], y["mscale"])
             / get_mscale(y["factor"], y["mscale_all_dim"]))
    softmax = (get_mscale(y["factor"], y["mscale_all_dim"]) ** 2
               if y["mscale_all_dim"] else 1.0)
    return jnp.cos(pos * freq) * table, jnp.sin(pos * freq) * table, softmax


def mixer_maps(mp, X, hyper: Hyper, rounds: int):
    """(pre (b, t, n), post (b, t, n), H (b, t, n, n)) of the streams X
    (b, t, n, C); an exit mixer (W n wide) has `pre` alone."""
    b, t, n, c = X.shape
    x = X.reshape(b, t, n * c)
    m = (x @ mp["w"]) * lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + hyper.eps)
    pre = jax.nn.sigmoid(mp["alpha"][0] * m[..., :n] + mp["b"][:n]) \
        + hyper.hc_eps
    if mp["w"].shape[1] == n:
        return pre, None, None
    post = 2.0 * jax.nn.sigmoid(mp["alpha"][1] * m[..., n:2 * n]
                                + mp["b"][n:2 * n])
    h = jnp.clip(mp["alpha"][2] * m[..., 2 * n:] + mp["b"][2 * n:],
                 *hyper.clamp)
    H = jnp.exp(h.reshape(b, t, n, n))
    for _ in range(rounds):
        H = H / (jnp.sum(H, axis=-1, keepdims=True) + hyper.hc_eps)
        H = H / (jnp.sum(H, axis=-2, keepdims=True) + hyper.hc_eps)
    return pre, post, H


def _attention(lp, y, tables, s: HyperLatentMoESizes, eps):
    cos, sin, softmax = tables
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
    scale = softmax / math.sqrt(nope + rope)

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


def _layers(X, layers, tables, s: HyperLatentMoESizes, hyper: Hyper):
    eps = hyper.eps

    def mixed(mp, X, sublayer):
        pre, post, H = mixer_maps(mp, X, hyper, s.sinkhorn_iters)
        out = sublayer(jnp.einsum("bti,btic->btc", pre, X))
        if isinstance(out, tuple):
            y, routed = out
        else:
            y, routed = out, None
        return (jnp.einsum("btij,btjc->btic", H, X)
                + post[..., None] * y[:, :, None]), routed

    @jax.checkpoint
    def layer(X, lp):
        X, _ = mixed(lp["hc_attn"], X, lambda u: _attention(
            lp, _rms_norm(lp["norm1"], u, eps), tables, s, eps))
        if "moe" in lp:
            return mixed(lp["hc_ffn"], X, lambda u: _expert_ffn(
                lp["moe"], _rms_norm(lp["norm2"], u, eps), s,
                hyper.expert_offset, hyper.scaling))
        return mixed(lp["hc_ffn"], X, lambda u: _swiglu(
            _rms_norm(lp["norm2"], u, eps), lp["gate_proj"]["weight"],
            lp["up_proj"]["weight"], lp["down_proj"]["weight"]))

    return lax.scan(layer, X, layers)     # (X, routed a layer or None)


def reference_losses(params, input_ids, target_ids, position_ids, *,
                     sizes: HyperLatentMoESizes, hyper: Hyper):
    """(CE of the main model, CE of the multi-token-prediction module or
    None, routed (expert layers, routed experts), the module's layer
    last), float32."""
    s = sizes
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    emb = params["embedding"]["weight"]
    head = params["lm_head"]["weight"][:, :s.vocab]
    tables = yarn_tables(hyper, s.qk_rope_head_dim, position_ids)
    run = lambda X, layers: _layers(X, layers, tables, s, hyper)

    def leave(mixer, norm, X):
        pre, _, _ = mixer_maps(mixer, X, hyper, s.sinkhorn_iters)
        return _rms_norm(norm, jnp.einsum("bti,btic->btc", pre, X),
                         hyper.eps) @ head

    x = emb[input_ids]
    X = jnp.stack([x] * s.hc_mult, axis=2)           # X_0: n copies
    X, _ = run(X, params["dense_layers"])
    X, routed = run(X, params["layers"])
    main = _mean_ce(leave(params["hc_exit"], params["norm"], X), target_ids)
    if "mtp" not in params:
        return main, None, routed
    mp = params["mtp"]
    known = target_ids != IGNORE_INDEX
    nxt = _rms_norm(mp["enorm"], emb[jnp.where(known, target_ids, 0)],
                    hyper.eps)
    H = jnp.stack(
        [jnp.concatenate([_rms_norm(mp["hnorm"], X[:, :, i], hyper.eps),
                          nxt], axis=-1) @ mp["eh_proj"]["weight"]
         for i in range(s.hc_mult)], axis=2)
    H, routed_mtp = run(H, params["mtp_layers"])
    after = jnp.concatenate(
        [target_ids[:, 1:], jnp.full_like(target_ids[:, :1], IGNORE_INDEX)],
        axis=1)
    after = jnp.where(known, after, IGNORE_INDEX)
    return (main, _mean_ce(leave(mp["hc_exit"], mp["norm"], H), after),
            jnp.concatenate([routed, routed_mtp]))


def reference_loss_routed(params, input_ids, target_ids, position_ids, *,
                          sizes: HyperLatentMoESizes, hyper: Hyper):
    main, mtp, routed = reference_losses(params, input_ids, target_ids,
                                         position_ids, sizes=sizes,
                                         hyper=hyper)
    loss = main if mtp is None else main + hyper.mtp_loss_weight * mtp
    return loss, lax.stop_gradient(routed)
