"""The early_moe family (SmallThinker's architecture, `smallthinker`): a
configuration file in the published keys -> the program's model
(`models/early_moe.EarlyRouterMoETransformer`) and the plain reference the
benchmark checks it against.

`reference_loss_routed` is the benchmark's own copy of the architecture in
straightforward `jax.numpy`, float32: the layers LOOPED over
`sliding_window_layout`; **the router's product from the layer's input
written in the open** (`logits = x W_r` on the residual stream as it enters
the layer, before `input_layernorm`), the top-k logits, a softmax over the
chosen; **the mask as a dense boolean built from `i - j`** (`0 <= i - j`,
and `i - j < sliding_window_size` in a layer whose layout says 1); full
score matrices in blocks of 512 query rows (28 x 512 x 16384 float32 = 0.94
GB), each block and each layer under `jax.checkpoint`; half-split RoPE over
the whole head in a window layer and NO positions in a full layer; no q/k
norm, no gate, two norms a layer; **the held experts applied one by one to
every token and masked by the weights, with `jnp.maximum(., 0)`** (no sort,
no gather, no grouped product), no shared expert; an untied head whose
logits and loss are made a block of 4096 rows at a time under
`jax.checkpoint` (16384 x 37984 float32 logits are 2.5 GB, and their
cotangent as much again). No kernel, no sharding, no dispatch, no scan over
periods. It consumes the parameter pytree `EarlyRouterMoETransformer.init`
produces (`layers_in_order` hands out the program's stacked layers one by
one: the tree's layout is the program's fact, what each layer computes is
read from the configuration) and is given the same share of experts and the
same vocabulary slice.

Departures from the published description (the configuration file's
`assumed`): the router reads the layer's input itself, before the norm; the
experts' activation is ReLU; no secondary experts; no balance loss; a share
adds what its experts give.

The configuration file states the cut (`reduced`) beside a `published`
group; the router is sized from `published.moe_num_primary_experts`, never
from the experts held.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.lib.early_moe_counts import EarlyMoESizes
# at import, so that a program without the family fails before any device
# is touched (run.py loads this module before the runner starts)
from distributed_pytorch_from_scratch_tpu.config import (EarlyMoEConfig,
                                                         ModelConfig)
from distributed_pytorch_from_scratch_tpu.models.conv_moe import (
    layer_blocks, layers_in_order)
from distributed_pytorch_from_scratch_tpu.models.early_moe import (
    KINDS, EarlyRouterMoETransformer)

IGNORE_INDEX = -1
QUERY_BLOCK = 512
HEAD_BLOCK = 4096


class Family(NamedTuple):
    model: object            # the program's model, built for the mesh
    sizes: EarlyMoESizes     # for benchmark/lib/early_moe_counts.py; data
                             # is drawn from its `vocab` (the slice held)
    reference_loss: object   # (params, ids, tgt, pos) -> float32 loss
    reference_routed: object  # ... -> (loss, routed (layers, routed
                              # experts)), for has_aux


def sizes_of(config: dict) -> EarlyMoESizes:
    layout = tuple(config["sliding_window_layout"])
    if len(layout) != config["num_layers"]:
        raise ValueError(f"sliding_window_layout names {len(layout)} "
                         f"layers, num_layers is {config['num_layers']}")
    if tuple(config["rope_layout"]) != layout:
        raise ValueError("the early_moe family rotates q and k in its "
                         "window layers and in no other: rope_layout must "
                         "equal sliding_window_layout")
    if (config["moe_num_active_primary_experts"]
            != config["num_experts_per_tok"]):
        raise ValueError("num_experts_per_tok is the harness's name for "
                         "moe_num_active_primary_experts: the two differ")
    return EarlyMoESizes(
        d_model=config["hidden_size"],
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"],
        head_dim=config["head_dim"], window=config["sliding_window_size"],
        layout=layout, d_expert=config["moe_ffn_hidden_size"],
        n_routed=config["published"]["moe_num_primary_experts"],
        n_held=config["moe_num_primary_experts"],
        top_k=config["moe_num_active_primary_experts"],
        vocab=config["vocab_size"])


def build(config: dict, mesh_sizes: dict, compute_dtype: str) -> Family:
    for key, want in (("moe_primary_router_apply_softmax", True),
                      ("norm_topk_prob", True),
                      ("tie_word_embeddings", False),
                      ("rope_scaling", None)):
        if config.get(key) != want:
            raise ValueError(f"the early_moe family computes {key}={want!r} "
                             f"only, the configuration says "
                             f"{config.get(key)!r}")
    s = sizes_of(config)
    cfg = ModelConfig(
        attn_dim=s.d_model, ffn_dim=0, num_heads=s.n_head,
        num_kv_heads=s.n_kv_head, num_layers=s.n_layer, vocab_size=s.vocab,
        maxlen=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]), compute_dtype=compute_dtype,
        num_experts=s.n_routed, moe_top_k=s.top_k,
        early_moe=EarlyMoEConfig(
            sliding_window_layout=s.layout, rope_layout=s.layout,
            head_dim=s.head_dim, moe_ffn_hidden_size=s.d_expert,
            sliding_window_size=s.window, experts_held=s.n_held,
            expert_offset=int(config["deployment_share"]["expert_offset"]),
            rms_norm_eps=float(config["rms_norm_eps"])))
    # every knob the workload does not define stays at the program's default
    model = EarlyRouterMoETransformer(cfg, tp_size=mesh_sizes.get("tp", 1))

    def routed(params, input_ids, target_ids, position_ids):
        return reference_loss_routed(
            params, input_ids, target_ids, position_ids, sizes=s,
            expert_offset=cfg.early_moe.expert_offset,
            rope_theta=cfg.rope_theta, eps=cfg.early_moe.rms_norm_eps)

    return Family(model=model, sizes=s,
                  reference_loss=lambda *a: routed(*a)[0],
                  reference_routed=routed)


# ---- the plain reference ----

def _norm(p, x, eps):
    return (x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                          + eps) * p["scale"])


def _rope(x, cos, sin):
    """Half-split pairs (x_i, x_{i + dim/2}) of x (b, heads, t, dim);
    cos/sin (b, 1, t, dim/2)."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(lp, y, cos, sin, s, window):
    """`window` None: a full layer (the whole past, no positions)."""
    b, t, _ = y.shape
    h = s.head_dim
    heads = lambda z, n: z.reshape(b, t, n, h).transpose(0, 2, 1, 3)
    q = heads(y @ lp["wq"]["weight"], s.n_head)
    k = heads(y @ lp["wk"]["weight"], s.n_kv_head)
    v = heads(y @ lp["wv"]["weight"], s.n_kv_head)
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
    return o @ lp["wo"]["weight"]


def _expert_ffn(mp, router_in, m, s, expert_offset: int):
    """Sum over the experts HELD of w_e E_e(m), each expert applied to
    every token and masked by its weight, the weights from `router_in`; and
    how many (token, choice) pairs chose each routed expert."""
    b, t, d = m.shape
    x = m.reshape(b * t, d)
    logits = router_in.reshape(b * t, d) @ mp["router"]       # all routed
    top, chosen = lax.top_k(logits, s.top_k)
    w = jax.nn.softmax(top, axis=-1)                 # over the chosen

    @jax.checkpoint
    def one(acc, expert):
        e, gate, up, down = expert
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)
        hidden = jnp.maximum(x @ gate, 0) * (x @ up)
        return acc + w_e[:, None] * (hidden @ down), None

    held = mp["gate"].shape[0]
    out, _ = lax.scan(one, jnp.zeros_like(x),
                      (expert_offset + jnp.arange(held), mp["gate"],
                       mp["up"], mp["down"]))
    routed = jnp.zeros(logits.shape[-1]).at[chosen.reshape(-1)].add(1.0)
    return out.reshape(b, t, d), routed


def _head_loss(x, head, target_ids):
    """Summed cross-entropy of the rows `x` (n, d) whose target is not
    ignored, and how many those are, a block of rows at a time."""
    @jax.checkpoint
    def block(x, tgt):
        logits = x @ head
        valid = tgt != IGNORE_INDEX
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(
            logits, jnp.where(valid, tgt, 0)[:, None], axis=-1)[:, 0]
        return jnp.sum(jnp.where(valid, lse - picked, 0.0)), jnp.sum(valid)

    total = count = 0
    for lo in range(0, x.shape[0], HEAD_BLOCK):
        a, n = block(x[lo:lo + HEAD_BLOCK], target_ids[lo:lo + HEAD_BLOCK])
        total, count = total + a, count + n
    return total, count


def reference_loss_routed(params, input_ids, target_ids, position_ids, *,
                          sizes: EarlyMoESizes, expert_offset: int,
                          rope_theta: float, eps: float):
    """(mean cross-entropy over the slice, routed (layers, routed experts):
    the pairs each expert was chosen for, a row a layer in the order the
    layers run), float32."""
    s = sizes
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    theta = 1.0 / (rope_theta ** (
        jnp.arange(0, s.head_dim, 2, dtype=jnp.float32) / s.head_dim))
    ang = position_ids.astype(jnp.float32)[:, None, :, None] * theta
    cos, sin = jnp.cos(ang), jnp.sin(ang)

    def layer(flag):
        window = s.window if flag else None

        @jax.checkpoint
        def run(x, lp):
            # the router reads x, the layer's input, before anything else
            x1 = x + _attention(lp, _norm(lp["norm1"], x, eps), cos, sin, s,
                                window)
            f, routed = _expert_ffn(lp["moe"], x, _norm(lp["norm2"], x1, eps),
                                    s, expert_offset)
            return x1 + f, routed
        return run

    x = params["embedding"]["weight"][input_ids]
    stacked = layers_in_order(params,
                              layer_blocks(s.layout, 0, KINDS, "early_moe"))
    routed = []
    for flag, lp in zip(s.layout, stacked, strict=True):
        x, chose = layer(flag)(x, lp)
        routed.append(chose)
    x = _norm(params["norm"], x, eps)
    total, count = _head_loss(x.reshape(-1, x.shape[-1]),
                              params["lm_head"]["weight"][:, :s.vocab],
                              target_ids.reshape(-1))
    return total / jnp.maximum(count, 1), lax.stop_gradient(jnp.stack(routed))
