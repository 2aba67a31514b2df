"""The plain float32 reference of the `sambay` family (models/sambay.py),
beside the other families' `vanilla_*`: the whole model in straightforward
`jax.numpy`, consuming the parameter pytree `SambaYTransformer.init`
produces. The layers are LOOPED in their published order
(`models/conv_moe.layers_in_order` hands out the program's stacked layers
one by one), each a mixer and then a SwiGLU between two LayerNorms; **the
Mamba-1 recurrence one token at a time** (`h_t = exp(dt_t A) h_{t-1} + dt_t
B_t u_t`, `y_t = h_t C_t + D u_t`: one `lax.scan` over positions, no chunk
anywhere, so nothing of `ops/selective_scan.py` is shared); differential
attention as TWO whole masked softmaxes a head, `(A_1 - lambda A_2) v`, then
the head's RMSNorm times `1 - lambda_init`; **the memory `M` (layer N / 2's
scan output before its gate) and layer N / 2 + 1's keys and values are
plain Python values handed down the loop** to the gated memory units and the
cross-attentions; loss and gradients by `jax.grad`. No kernel, no sharding,
no chunked scan, no scan over periods: what tests/test_sambay.py holds the
program to, leaf by leaf. `benchmark/families/sambay.py` keeps a copy of its
own (the yardstick does not import the program's oracle).

It takes the cut as the program does: `cfg.sambay.layers_here` (each layer
keeps its PUBLISHED index, so its kind and its `lambda_init`) and the
vocabulary's slice.

Departures from the published code (HF `Phi4FlashForCausalLM`), each also a
key of the benchmark configuration's `assumed`:

* `fused_linears`: the published `Wqkv` (d -> 2560 + 1280 + 1280) is three
  matrices here, `wq`, `wk`, `wv`, with its bias cut the same way; the
  SwiGLU's `gate_up_proj` is `gate_proj` and `up_proj`;
* `head_pairing`: how the 40 + 20 head columns pair up is a permutation of
  columns; here query head `(2 j + i) g + r` is map i of differential head
  `j g + r`, key head `2 j + i` key i of pair j, value j the pair's (`g` =
  heads a pair: `parallel/diff_attention.py`);
* `scan_state`: the state, the decays and their products are float32;
* `dropouts`: 0, as published for training from this checkpoint;
* `initialisation`: the program's own from the seed, not the published
  weights.
"""

from __future__ import annotations

import math
from types import SimpleNamespace

import jax
import jax.numpy as jnp
from jax import lax

from ..config import IGNORE_INDEX, ModelConfig
from .conv_moe import layers_in_order
from .sambay import blocks_of, layers_held


def sizes_of(cfg: ModelConfig) -> SimpleNamespace:
    sy = cfg.sambay
    return SimpleNamespace(
        heads=cfg.num_heads // 2, pairs=cfg.kv_heads // 2,
        head_dim=cfg.head_dim, state=sy.mamba_d_state,
        rank=sy.mamba_dt_rank or -(-cfg.attn_dim // 16),
        window=sy.sliding_window, eps=sy.layer_norm_eps,
        vocab=cfg.vocab_size, layers=layers_held(cfg),
        half=sy.num_hidden_layers // 2,
        blocks=blocks_of(cfg)[0])


def vanilla_loss(cfg: ModelConfig, params, input_ids, target_ids,
                 position_ids):
    """The loss `SambaYTransformer.loss_shard` computes, plainly."""
    return reference_loss(params, input_ids, target_ids, position_ids,
                          sizes=sizes_of(cfg))


def vanilla_logits(cfg: ModelConfig, params, input_ids):
    """The logits `SambaYTransformer.make_forward` computes, plainly."""
    return reference_logits(params, input_ids, sizes=sizes_of(cfg))[0]


# ---- the plain reference ----

def _layer_norm(p, x, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return p["scale"] * (x - mean) * lax.rsqrt(var + eps) + p["bias"]


def recurrence(u, dt, A, B, C):
    """u, dt (b, t, c), A (c, N), B and C (b, t, N) -> y (b, t, c): the state
    (b, c, N) from zero, one token at a time."""
    def token(h, row):
        u_t, dt_t, B_t, C_t = row
        h = (jnp.exp(dt_t[..., None] * A) * h
             + (dt_t * u_t)[..., None] * B_t[:, None, :])
        return h, jnp.einsum("bcn,bn->bc", h, C_t)

    h = jnp.zeros((*u.shape[::2], A.shape[1]), jnp.float32)
    _, y = lax.scan(token, h, tuple(jnp.moveaxis(a, 1, 0)
                                    for a in (u, dt, B, C)))
    return jnp.moveaxis(y, 0, 1)


def _scan(p, n, s, scan=recurrence):
    """(the scan's output with `D u`, before the gate; the gate's logits)."""
    t = n.shape[1]
    u, z = jnp.split(n @ p["w_in"], 2, -1)
    taps = p["conv"].shape[-1]
    # tap `taps - 1` reads the token itself; zeros before the sequence
    u = jax.nn.silu(p["conv_bias"] + sum(
        p["conv"][:, j]
        * jnp.pad(u, ((0, 0), (taps - 1 - j, 0), (0, 0)))[:, :t]
        for j in range(taps)))
    dt_r, B, C = jnp.split(u @ p["w_x"], (s.rank, s.rank + s.state), -1)
    dt = jax.nn.softplus(dt_r @ p["w_dt"] + p["dt_bias"])
    return scan(u, dt, -jnp.exp(p["A_log"]), B, C) + p["D"] * u, z


def lambda_init_of(index: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * index)


def _diff_attention(p, n, k, v, s, index: int, window: "int | None"):
    """Differential attention over keys k (b, t, 2 J h) and values v (b, t,
    J 2 h), the layer's own or another's; `window` None is causal and
    full."""
    b, t, _ = n.shape
    h, H, J = s.head_dim, s.heads, s.pairs
    g = H // J
    lin = lambda q, x: x @ q["weight"] + q.get("bias", 0.0)
    # query head (2 j + i) g + r -> [pair j, map i, head r of the pair]
    q = lin(p["wq"], n).reshape(b, t, J, 2, g, h)
    k = k.reshape(b, t, J, 2, h)
    v = v.reshape(b, t, J, 2 * h)
    back = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    seen = (back >= 0) if window is None else (back >= 0) & (back < window)
    scores = jnp.einsum("bqjigh,bkjih->bjigqk", q, k) / math.sqrt(h)
    maps = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    lam_0 = lambda_init_of(index)
    lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
           - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + lam_0)
    o = jnp.einsum("bjgqk,bkjw->bqjgw", maps[:, :, 0] - lam * maps[:, :, 1],
                   v)
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + s.eps)
    o = p["subln"] * o * (1.0 - lam_0)
    # differential head m = j g + r
    return lin(p["wo"], o.reshape(b, t, H * 2 * h))


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


def reference_logits(params, input_ids, *, sizes, scan=recurrence,
                     probes=None):
    """(logits (b, t, vocab), the residual stream that entered the final
    norm), float32. `scan` is the recurrence a Mamba layer runs. `probes`
    ({"memory", "k", "v"}: arrays of the values' shapes, or None) are ADDED
    to the three shared values as they are made, before any layer reads
    them, the maker too: the gradient at a probe of zeros is the value's
    summed cotangent."""
    s = sizes
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    emb = params["embedding"]["weight"][:s.vocab]
    probe = lambda name, a: a if probes is None else a + probes[name]
    x = emb[input_ids]
    memory = keys = values = None
    for (index, kind), lp in zip(s.layers, layers_in_order(params, s.blocks),
                                 strict=True):
        n = _layer_norm(lp["norm1"], x, s.eps)
        if kind == "mamba":
            y, z = _scan(lp["mamba"], n, s, scan)
            if index == s.half:
                # the layer that leaves the memory: its scan's output
                # BEFORE the gate, which its own gate reads too
                memory = y = probe("memory", y)
            mixed = (y * jax.nn.silu(z)) @ lp["mamba"]["w_out"]
        elif kind == "gmu":
            mixed = ((memory * jax.nn.silu(n @ lp["gmu"]["w_in"]))
                     @ lp["gmu"]["w_out"])
        elif kind == "cross":
            mixed = _diff_attention(lp["cross"], n, keys, values, s, index,
                                    None)
        else:
            p = lp["attn"]
            k = n @ p["wk"]["weight"] + p["wk"].get("bias", 0.0)
            v = n @ p["wv"]["weight"] + p["wv"].get("bias", 0.0)
            if kind == "full":      # the layer that leaves its keys, values
                keys, values = k, v = probe("k", k), probe("v", v)
            mixed = _diff_attention(p, n, k, v, s, index,
                                    s.window if kind == "swa" else None)
        h = x + mixed
        x = h + _swiglu(lp, _layer_norm(lp["norm2"], h, s.eps))
    return _layer_norm(params["norm"], x, s.eps) @ emb.T, x


def reference_loss(params, input_ids, target_ids, position_ids, *, sizes,
                   scan=recurrence, probes=None):
    """The mean cross-entropy over the vocabulary held, float32.
    `position_ids` are not read: no layer takes positions."""
    del position_ids
    logits, _ = reference_logits(params, input_ids, sizes=sizes, scan=scan,
                                 probes=probes)
    return _mean_ce(logits, target_ids)
