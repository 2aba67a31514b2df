"""The swa_moe family (Trinity's architecture, `afmoe`): a configuration
file in the published keys -> the program's model (`models/swa_moe.
SlidingWindowMoETransformer`) and the plain reference the benchmark checks
it against.

`reference_loss_routed` is the benchmark's own copy of the architecture in
straightforward `jax.numpy`, float32: the layers LOOPED over `layer_types`
(layer `i` dense where `i < num_dense_layers`); the embedding's rows times
`sqrt(hidden_size)` (`mup_enabled`); **the mask as a dense boolean built
from `i - j`** (`0 <= i - j`, and `i - j < sliding_window` in a
`sliding_attention` layer); full score matrices in blocks of 512 query rows
(2 x 32 x 512 x 8192 float32 = 1 GB), each block and each layer under
`jax.checkpoint`; RMSNorm on q and k per head; half-split RoPE over the
whole head in a window layer and NO positions in a full layer; the heads'
outputs times the sigmoid of the gate's projection; a norm after each
sublayer (`x + N2(attn(N1 x))`, `x + N4(ffn(N3 x))`); the sigmoid top-k
router with its selection bias, the weights normalised over ALL chosen
experts with 1e-20 and times `route_scale`; **the held experts applied one
by one to every token and masked by the weights** (no sort, no gather, no
grouped product), the shared expert beside them; an untied head; **the bias
rule as three `jnp` lines** (`bias_rule`). No kernel, no sharding, no
dispatch, no scan over periods. It consumes the parameter pytree
`SlidingWindowMoETransformer.init` produces (`layers_in_order` hands out
the program's stacked layers one by one: the tree's layout is the program's
fact, what each layer computes is read from the configuration) and is given
the same share of experts and the same vocabulary slice.

Departures from the published description (the configuration file's
`assumed`): no balance loss (the bias is the balancing); the multiplier on
the embedding only; a share adds what its experts and the shared expert
give; the rule reads the step's own counts over the whole batch.

The configuration file states the cut (`reduced`) beside a `published`
group; the router is sized from `published.num_experts`, never from the
experts held.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.lib.swa_moe_counts import WINDOW, SwaMoESizes
# at import, so that a program without the family fails before any device
# is touched (run.py loads this module before the runner starts)
from distributed_pytorch_from_scratch_tpu.config import (ModelConfig,
                                                         SwaMoEConfig)
from distributed_pytorch_from_scratch_tpu.models.conv_moe import (
    layer_blocks, layers_in_order)
from distributed_pytorch_from_scratch_tpu.models.swa_moe import (
    KINDS, SlidingWindowMoETransformer)

IGNORE_INDEX = -1
QUERY_BLOCK = 512


class Family(NamedTuple):
    model: object            # the program's model, built for the mesh
    sizes: SwaMoESizes       # for benchmark/lib/swa_moe_counts.py; data is
                             # drawn from its `vocab` (the slice held)
    reference_loss: object   # (params, ids, tgt, pos) -> float32 loss
    reference_routed: object  # ... -> (loss, routed (expert layers, routed
                              # experts)), for has_aux
    bias_speed: float        # the published `load_balance_coeff`
    bias_in_order: object    # params -> the expert layers' selection bias,
                             # (expert layers, routed experts), a row a
                             # layer in the order the layers run
    bias_rule: object        # (bias, routed, speed) -> the bias after a step


def sizes_of(config: dict) -> SwaMoESizes:
    if len(config["layer_types"]) != config["num_layers"]:
        raise ValueError(f"layer_types names {len(config['layer_types'])} "
                         f"layers, num_layers is {config['num_layers']}")
    return SwaMoESizes(
        d_model=config["hidden_size"],
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"],
        head_dim=config["head_dim"], window=config["sliding_window"],
        layer_types=tuple(config["layer_types"]),
        n_dense=config["num_dense_layers"],
        d_dense=config["intermediate_size"],
        d_expert=config["moe_intermediate_size"],
        n_shared=config["num_shared_experts"],
        n_routed=config["published"]["num_experts"],
        n_held=config["num_experts"], top_k=config["num_experts_per_tok"],
        vocab=config["vocab_size"])


def build(config: dict, mesh_sizes: dict, compute_dtype: str) -> Family:
    for key, want in (("score_func", "sigmoid"), ("route_norm", True),
                      ("mup_enabled", True), ("tie_word_embeddings", False),
                      ("n_group", 1), ("topk_group", 1)):
        if config.get(key) != want:
            raise ValueError(f"the swa_moe family computes {key}={want!r} "
                             f"only, the configuration says "
                             f"{config.get(key)!r}")
    s = sizes_of(config)
    cfg = ModelConfig(
        attn_dim=s.d_model, ffn_dim=s.d_dense, num_heads=s.n_head,
        num_kv_heads=s.n_kv_head, num_layers=s.n_layer, vocab_size=s.vocab,
        maxlen=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]), compute_dtype=compute_dtype,
        num_experts=s.n_routed, moe_top_k=s.top_k,
        swa_moe=SwaMoEConfig(
            layer_types=s.layer_types, head_dim=s.head_dim,
            moe_intermediate_size=s.d_expert, sliding_window=s.window,
            num_dense_layers=s.n_dense, num_shared_experts=s.n_shared,
            route_scale=float(config["route_scale"]),
            load_balance_coeff=float(config["load_balance_coeff"]),
            mup_enabled=True, experts_held=s.n_held,
            expert_offset=int(config["deployment_share"]["expert_offset"]),
            rms_norm_eps=float(config["rms_norm_eps"])))
    # every knob the workload does not define stays at the program's default
    model = SlidingWindowMoETransformer(cfg, tp_size=mesh_sizes.get("tp", 1))

    def routed(params, input_ids, target_ids, position_ids):
        return reference_loss_routed(
            params, input_ids, target_ids, position_ids, sizes=s,
            expert_offset=cfg.swa_moe.expert_offset,
            rope_theta=cfg.rope_theta, eps=cfg.swa_moe.rms_norm_eps,
            scaling=cfg.swa_moe.route_scale)

    return Family(model=model, sizes=s,
                  reference_loss=lambda *a: routed(*a)[0],
                  reference_routed=routed,
                  bias_speed=cfg.swa_moe.load_balance_coeff,
                  bias_in_order=lambda params: jnp.stack([
                      lp["moe"]["bias"] for lp in layers_in_order(
                          params, layer_blocks(s.layer_types, s.n_dense,
                                               KINDS, "swa_moe"))
                      if "moe" in lp]),
                  bias_rule=bias_rule)


def bias_rule(bias, routed, speed: float):
    """The selection bias after a step whose layer counted `routed` (...,
    routed experts) pairs an expert: the three lines, float32."""
    delta = speed * jnp.sign(jnp.mean(routed, -1, keepdims=True) - routed)
    delta = delta - jnp.mean(delta, -1, keepdims=True)
    return bias + delta


# ---- the plain reference ----

def _norm(p, x, eps):
    return (x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                          + eps) * p["scale"])


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _rope(x, cos, sin):
    """Half-split pairs (x_i, x_{i + dim/2}) of x (b, heads, t, dim);
    cos/sin (b, 1, t, dim/2)."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(lp, y, cos, sin, s, eps, window):
    """`window` None: a full layer (the whole past, no positions)."""
    b, t, _ = y.shape
    h = s.head_dim
    heads = lambda z, n: z.reshape(b, t, n, h).transpose(0, 2, 1, 3)
    q = _norm(lp["q_norm"], heads(y @ lp["wq"]["weight"], s.n_head), eps)
    k = _norm(lp["k_norm"], heads(y @ lp["wk"]["weight"], s.n_kv_head), eps)
    v = heads(y @ lp["wv"]["weight"], s.n_kv_head)
    gate = jax.nn.sigmoid(y @ lp["wg"]["weight"])
    if window is not None:
        q, k = _rope(q, cos, sin), _rope(k, cos, sin)
    group = s.n_head // s.n_kv_head         # query head h reads h // group
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scale = 1.0 / math.sqrt(h)

    @jax.checkpoint
    def rows(q_rows, first):
        n = q_rows.shape[2]
        scores = jnp.einsum("bhqd,bhkd->bhqk", q_rows, k) * scale
        back = (first + jnp.arange(n))[:, None] - jnp.arange(t)[None, :]
        live = back >= 0
        if window is not None:
            live = live & (back < window)
        probs = jax.nn.softmax(jnp.where(live, scores, -jnp.inf), axis=-1)
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
    return (o * gate) @ lp["wo"]["weight"]


def _expert_ffn(mp, y, s, expert_offset: int, scaling: float):
    """Shared(y) + sum over the experts HELD of w_e E_e(y), each expert
    applied to every token and masked by its weight; and how many (token,
    choice) pairs chose each routed expert."""
    b, t, d = y.shape
    x = y.reshape(b * t, d)
    score = jax.nn.sigmoid(x @ mp["router"])                  # all routed
    _, chosen = lax.top_k(score + mp["bias"], s.top_k)
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
    sp = mp["shared"]
    out = out + _swiglu(x, sp["gate"], sp["up"], sp["down"])
    routed = jnp.zeros(score.shape[-1]).at[chosen.reshape(-1)].add(1.0)
    return out.reshape(b, t, d), routed


def reference_loss_routed(params, input_ids, target_ids, position_ids, *,
                          sizes: SwaMoESizes, expert_offset: int,
                          rope_theta: float, eps: float, scaling: float):
    """(mean cross-entropy over the slice, routed (expert layers, routed
    experts): the pairs each expert was chosen for, a row an expert layer
    in the order the layers run), float32."""
    s = sizes
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    theta = 1.0 / (rope_theta ** (
        jnp.arange(0, s.head_dim, 2, dtype=jnp.float32) / s.head_dim))
    ang = position_ids.astype(jnp.float32)[:, None, :, None] * theta
    cos, sin = jnp.cos(ang), jnp.sin(ang)

    def layer(name, dense):
        window = s.window if name == WINDOW else None

        @jax.checkpoint
        def run(x, lp):
            a = _attention(lp, _norm(lp["norm1"], x, eps), cos, sin, s, eps,
                           window)
            x = x + _norm(lp["norm2"], a, eps)
            y = _norm(lp["norm3"], x, eps)
            if dense:
                f, routed = _swiglu(y, lp["gate_proj"]["weight"],
                                    lp["up_proj"]["weight"],
                                    lp["down_proj"]["weight"]), None
            else:
                f, routed = _expert_ffn(lp["moe"], y, s, expert_offset,
                                        scaling)
            return x + _norm(lp["norm4"], f, eps), routed
        return run

    x = params["embedding"]["weight"][input_ids] * math.sqrt(s.d_model)
    stacked = layers_in_order(
        params, layer_blocks(s.layer_types, s.n_dense, KINDS, "swa_moe"))
    routed = []
    for i, (name, lp) in enumerate(zip(s.layer_types, stacked, strict=True)):
        x, chose = layer(name, i < s.n_dense)(x, lp)
        if chose is not None:
            routed.append(chose)
    logits = (_norm(params["norm"], x, eps)
              @ params["lm_head"]["weight"][:, :s.vocab])
    valid = target_ids != IGNORE_INDEX
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, jnp.where(valid, target_ids, 0)[..., None], axis=-1)[..., 0]
    loss = (jnp.sum(jnp.where(valid, lse - picked, 0.0))
            / jnp.maximum(jnp.sum(valid), 1))
    return loss, lax.stop_gradient(jnp.stack(routed))
