"""The GPT-2 family: a configuration file in the published `config.json`
keys -> the program's model (`models/gpt2.GPT2Transformer`) and the plain
reference the benchmark checks it against.

`reference_loss` is the benchmark's own copy of the architecture in
straightforward `jax.numpy`: LayerNorm, multi-head causal attention with a
full score matrix, GELU(tanh) MLP, learned positions, tied head, mean
cross-entropy in float32. No kernel, no sharding, no cache. It consumes the
parameter pytree `GPT2Transformer.init` produces. Copied from the program's
`models/vanilla.VanillaGPT2` (PERF.md, Open questions) with one addition:
each layer is wrapped in `jax.checkpoint`, so that the gradient of a 36-layer
model at 2 x 1024 tokens keeps one layer's score matrices alive and not
thirty-six (same mathematics, less memory).
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.lib.flops import DecoderSizes


class Family(NamedTuple):
    model: object            # the program's model, built for the mesh
    sizes: DecoderSizes      # for benchmark/lib/flops.py; data is drawn
                             # from its published vocabulary
    reference_loss: object   # (params, ids, tgt, pos) -> float32 loss


def sizes_of(config: dict) -> DecoderSizes:
    d, heads = config["n_embd"], config["n_head"]
    return DecoderSizes(
        d_model=d, n_layer=config["n_layer"], n_head=heads,
        head_dim=d // heads, d_ff=config.get("n_inner") or 4 * d,
        vocab=config["vocab_size"], n_positions=config["n_positions"],
        mlp_matmuls=2, tied_head=True, learned_positions=True, biases=True,
        norm_params_per_layer=4 * d)


def build(config: dict, mesh_sizes: dict, compute_dtype: str) -> Family:
    from distributed_pytorch_from_scratch_tpu.config import ModelConfig
    from distributed_pytorch_from_scratch_tpu.models.gpt2 import (
        GPT2Transformer)

    # The three published dropout keys are not read: the program has no
    # dropout, and neither has the reference (the file's `assumed` says so).
    if not config.get("tie_word_embeddings", True):
        raise ValueError("the gpt2 family ties the head to the embedding")
    if config.get("activation_function", "gelu_new") != "gelu_new":
        raise ValueError("the gpt2 family computes gelu_new (tanh) only")
    s = sizes_of(config)
    cfg = ModelConfig(attn_dim=s.d_model, ffn_dim=s.d_ff, num_heads=s.n_head,
                      num_layers=s.n_layer, vocab_size=s.vocab,
                      maxlen=s.n_positions, compute_dtype=compute_dtype)
    # every knob the workload does not define stays at the program's default
    model = GPT2Transformer(cfg, tp_size=mesh_sizes.get("tp", 1))
    eps = config.get("layer_norm_epsilon", 1e-5)

    def reference(params, input_ids, target_ids, position_ids):
        return reference_loss(params, input_ids, target_ids, position_ids,
                              n_head=s.n_head, vocab=s.vocab, eps=eps)

    return Family(model=model, sizes=s, reference_loss=reference)


# ---- the plain reference ----

def _layer_norm(p, x, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return p["scale"] * ((x - mean) * lax.rsqrt(var + eps)) + p["bias"]


def _linear(p, x):
    return x @ p["weight"] + p["bias"]


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def reference_logits(params, input_ids, position_ids, *, n_head: int,
                     vocab: int, eps: float = 1e-5):
    emb = params["embedding"]["weight"].astype(jnp.float32)
    x = emb[input_ids] + params["pos_embedding"]["weight"][position_ids]
    b, t, d = x.shape
    hd = d // n_head
    causal = jnp.tril(jnp.ones((t, t), dtype=bool))

    @jax.checkpoint
    def layer(x, lp):
        y = _layer_norm(lp["ln1"], x, eps)
        heads = lambda z: z.reshape(b, t, n_head, hd).transpose(0, 2, 1, 3)
        q, k, v = (heads(_linear(lp[n], y)) for n in ("wq", "wk", "wv"))
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(hd)
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
        x = x + _linear(lp["wo"], o.transpose(0, 2, 1, 3).reshape(b, t, d))
        y = _layer_norm(lp["ln2"], x, eps)
        x = x + _linear(lp["proj"], _gelu_new(_linear(lp["fc"], y)))
        return x, None

    x, _ = lax.scan(layer, x, params["layers"])
    x = _layer_norm(params["norm"], x, eps)
    # tied head over the published vocabulary only: rows a tensor-parallel
    # layout padded on are not part of the model
    return x @ emb[:vocab].T


def reference_loss(params, input_ids, target_ids, position_ids, *,
                   n_head: int, vocab: int, eps: float = 1e-5):
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    logits = reference_logits(params, input_ids, position_ids, n_head=n_head,
                              vocab=vocab, eps=eps)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, target_ids[..., None], axis=-1)
    return jnp.mean(lse - picked[..., 0])
