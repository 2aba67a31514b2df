"""The loop_llama family (Ouro's architecture, `ouro`): a configuration file
in the published keys -> the program's model
(`models/loop_llama.LoopedTransformer`) and the plain reference the benchmark
checks it against.

`reference_detail` is the benchmark's own copy of the architecture in
straightforward `jax.numpy`, float32: **a Python loop of `total_ut_steps`
passes over the SAME stacked layers** (so autodiff sums a weight's R uses by
plain adds: no scan of passes whose transpose would do it), the layers of a
pass looped by a `lax.scan` whose body is a `jax.checkpoint` (the one
departure from the program's oracle, `models/vanilla_loop_llama.py`, which
loops them in Python: 32 unrolled float32 layers at the published widths
take minutes to compile; benchmark/tests/test_loop_llama_counts.py pins the
two to each other); four norms a layer; attention as a masked softmax over
full score matrices in blocks of 512 query rows (16 x 512 x 4096 float32 =
134 MB) under `jax.checkpoint`; RoPE in the rotate-half convention over the
whole head; the final norm after EVERY pass, its output fed to the next; an
exit a pass through the one untied head, its logits and CE under
`jax.checkpoint` (4096 x 49152 float32 logits are 0.81 GB, and their
cotangent as much again: one exit's at a time); the gate, `p` and the loss
exactly by the equations:

    lam_r[i] = sigmoid(w_g . h_r[i] + b_g)
    p_r = lam_r prod_{j<r} (1 - lam_j)  (r < R),  p_R = prod_{j<R} (1 - lam_j)
    loss = mean_i [ sum_r p_r[i] l_r[i] - beta H(p[i]) ],  H(p) = -sum p log p

No kernel, no sharding, no scan of passes. It consumes the parameter pytree
`LoopedTransformer.init` produces.

What `config.json` does not pin is the configuration file's `assumed`: the
final norm after every pass and its output fed on; the gate a `Linear(d, 1)`
with bias on the normed state; beta; no bias in any projection; rotate-half
RoPE over the whole head; Stage I's objective.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.lib.loop_llama_counts import LoopLlamaSizes
# at import, so that a program without the family fails before any device
# is touched (run.py loads this module before the runner starts)
from distributed_pytorch_from_scratch_tpu.config import (LoopLlamaConfig,
                                                         ModelConfig)
from distributed_pytorch_from_scratch_tpu.models.loop_llama import (
    LoopedTransformer)

IGNORE_INDEX = -1
QUERY_BLOCK = 512


class Family(NamedTuple):
    model: object            # the program's model, built for the mesh
    sizes: LoopLlamaSizes    # for benchmark/lib/loop_llama_counts.py; data
                             # is drawn from its `vocab`
    reference_loss: object   # (params, ids, tgt, pos) -> float32 loss
    reference_detail: object  # ... -> (loss, the R exits' mean CEs), for
                              # has_aux


def sizes_of(config: dict) -> LoopLlamaSizes:
    if config["hidden_size"] != (config["num_attention_heads"]
                                 * config["head_dim"]):
        raise ValueError("the loop_llama family's heads split the model's "
                         "width: hidden_size must be heads x head_dim")
    return LoopLlamaSizes(
        d_model=config["hidden_size"], n_layer=config["num_layers"],
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"],
        head_dim=config["head_dim"], d_ff=config["intermediate_size"],
        vocab=config["vocab_size"], passes=config["total_ut_steps"])


def build(config: dict, mesh_sizes: dict, compute_dtype: str) -> Family:
    for key, want in (("hidden_act", "silu"), ("tie_word_embeddings", False),
                      ("rope_scaling", None), ("use_sliding_window", False)):
        if config.get(key) != want:
            raise ValueError(f"the loop_llama family computes {key}={want!r} "
                             f"only, the configuration says "
                             f"{config.get(key)!r}")
    s = sizes_of(config)
    cfg = ModelConfig(
        attn_dim=s.d_model, ffn_dim=s.d_ff, num_heads=s.n_head,
        num_kv_heads=s.n_kv_head, num_layers=s.n_layer, vocab_size=s.vocab,
        maxlen=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]), compute_dtype=compute_dtype,
        loop_llama=LoopLlamaConfig(
            loop_steps=s.passes,
            exit_entropy_coef=float(config["assumed"]["exit_entropy_coef"]),
            rms_norm_eps=float(config["rms_norm_eps"])))
    # every knob the workload does not define stays at the program's default
    model = LoopedTransformer(cfg, tp_size=mesh_sizes.get("tp", 1))

    def detail(params, input_ids, target_ids, position_ids):
        return reference_loss_detail(
            params, input_ids, target_ids, position_ids, sizes=s,
            rope_theta=cfg.rope_theta, eps=cfg.loop_llama.rms_norm_eps,
            beta=cfg.loop_llama.exit_entropy_coef)

    return Family(model=model, sizes=s,
                  reference_loss=lambda *a: detail(*a)[0],
                  reference_detail=detail)


# ---- the plain reference ----

def _norm(p, x, eps):
    return (x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                          + eps) * p["scale"])


def _rope(x, cos, sin):
    """Rotate-half: x (b, heads, t, dim), cos/sin (b, 1, t, dim / 2)."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(lp, y, cos, sin, s):
    b, t, _ = y.shape
    h = s.head_dim
    heads = lambda z, n: z.reshape(b, t, n, h).transpose(0, 2, 1, 3)
    q = _rope(heads(y @ lp["wq"]["weight"], s.n_head), cos, sin)
    k = _rope(heads(y @ lp["wk"]["weight"], s.n_kv_head), cos, sin)
    v = heads(y @ lp["wv"]["weight"], s.n_kv_head)
    group = s.n_head // s.n_kv_head
    k, v = (jnp.repeat(z, group, axis=1) for z in (k, v))
    block = min(QUERY_BLOCK, t)
    assert t % block == 0, (t, block)
    col = jnp.arange(t)

    @jax.checkpoint
    def rows(q_rows, first):
        scores = jnp.einsum("bhqd,bhkd->bhqk", q_rows, k) / math.sqrt(h)
        seen = col[None, :] <= (first + jnp.arange(block))[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", probs, v)

    o = jnp.concatenate([rows(q[:, :, i:i + block], i)
                         for i in range(0, t, block)], axis=2)
    return (o.transpose(0, 2, 1, 3).reshape(b, t, s.n_head * h)
            @ lp["wo"]["weight"])


def _layer(lp, x, cos, sin, s, eps):
    x = x + _norm(lp["post_attn_norm"],
                  _attention(lp, _norm(lp["norm1"], x, eps), cos, sin, s),
                  eps)
    y = _norm(lp["norm2"], x, eps)
    ff = (jax.nn.silu(y @ lp["gate_proj"]["weight"])
          * (y @ lp["up_proj"]["weight"])) @ lp["down_proj"]["weight"]
    return x + _norm(lp["post_ffn_norm"], ff, eps)


def exit_distribution(z):
    """`p` (R, ...) from the gate's logits `z` (R, ...), by the products."""
    lam = jax.nn.sigmoid(z)
    left, p = jnp.ones_like(lam[0]), []
    for r in range(z.shape[0] - 1):
        p.append(lam[r] * left)
        left = left * (1.0 - lam[r])
    return jnp.stack(p + [left])


def reference_loss_detail(params, input_ids, target_ids, position_ids, *,
                          sizes: LoopLlamaSizes, rope_theta: float,
                          eps: float, beta: float):
    s = sizes
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    half = s.head_dim // 2
    theta = 1.0 / (rope_theta ** (jnp.arange(half, dtype=jnp.float32)
                                  / half))
    ang = position_ids.astype(jnp.float32)[:, None, :, None] * theta
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    valid = target_ids != IGNORE_INDEX
    tgt = jnp.where(valid, target_ids, 0)
    head = params["lm_head"]["weight"][:, :s.vocab]
    gate = params["exit_gate"]

    @jax.checkpoint
    def layer(x, lp):
        return _layer(lp, x, cos, sin, s, eps), None

    @jax.checkpoint
    def exit_ce(h):
        logits = h @ head
        return jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, tgt[..., None], axis=-1)[..., 0]

    x = params["embedding"]["weight"][input_ids]
    ces, zs = [], []
    for _ in range(s.passes):
        x, _ = lax.scan(layer, x, params["layers"])
        x = _norm(params["norm"], x, eps)       # what the next pass reads
        ces.append(exit_ce(x))
        zs.append(jnp.sum(x * gate["weight"], axis=-1) + gate["bias"])
    ces = jnp.stack(ces)                                     # (R, b, t)
    p = exit_distribution(jnp.stack(zs))
    entropy = -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)),
                                 0.0), axis=0)
    count = jnp.maximum(jnp.sum(valid), 1)
    mean = lambda a: jnp.sum(jnp.where(valid, a, 0.0), axis=(-2, -1)) / count
    return (mean(jnp.sum(p * ces, axis=0) - beta * entropy),
            {"loss_exit": mean(ces), "exit_p_mean": mean(p)})
