"""The sambay family (Phi-4-mini-flash-reasoning, `phi4flash`: SambaY,
arXiv:2507.06607, with differential attention, arXiv:2410.05258): a
configuration file in the published keys -> the program's model
(`models/sambay.SambaYTransformer`) and the plain reference the benchmark
checks it against.

`reference_loss` is the benchmark's own copy of the architecture in
straightforward `jax.numpy`, float32, as the published equations put it (n =
LN1(x), LayerNorm with weight and bias, no positions anywhere):

    h  = x + mixer(LN1(x))          x' = h + (silu(g) * u) W_down
    [g | u] = LN2(h) W_gate_up      logits = LN_f(x_L) E^T   (the TIED table)

    mamba   [u | z] = n W_in; u <- silu(conv4(u) + b); [dt_r | B | C] = u W_x
            dt = softplus(dt_r W_dt + b_dt); A = -exp(A_log)
            h_t = exp(dt_t A) h_{t-1} + dt_t B_t u_t;  y_t = h_t C_t + D u_t
            out = (y * silu(z)) W_out.   LAYER N / 2 LEAVES M = y
    swa / full   q, k, v = n W + b; A_i = softmax(q_i k_i^T / sqrt(h) + mask)
            o = (A_1 - lambda A_2) v; lambda = exp(lq1 . lk1) - exp(lq2 . lk2)
            + lambda_init(l); o <- w RMSNorm(o) (1 - lambda_init(l));
            out = o W_o + b_o.   LAYER N / 2 + 1 LEAVES ITS k, v
    cross   q = n W_q + b only, over layer N / 2 + 1's k, v, causal
    gmu     out = (M * silu(n W_1)) W_2

the layers LOOPED in their published order, `M`, `k`, `v` plain Python
values handed down the loop; **the Mamba-1 recurrence token by token** (one
`lax.scan` over positions, under `jax.checkpoint` in blocks of 64 steps, so
that its backward keeps 64 states of 327 KB a layer at 16,384 tokens and not
16,384); the convolution as shifted sums plus its bias; differential
attention as two whole masked softmaxes a head, full score matrices in
blocks of 512 query rows; each layer under `jax.checkpoint`. No kernel, no
sharding, no chunked scan, no scan over periods. It consumes the parameter
pytree `SambaYTransformer.init` produces and is given the same layers
(`layers_here`, each at its PUBLISHED index) and the same vocabulary slice.

The configuration file states the cut (`reduced`) beside a `published`
group: `num_layers` layers, `layers_here`, and `vocab_size` rows of the
table. How the 40 + 20 head columns pair up is a permutation of columns,
stated once (`assumed.head_pairing`) and the same here as in the program.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.lib.sambay_counts import SambaYSizes
# at import, so that a program without the family fails before any device
# is touched (run.py loads this module before the runner starts)
from distributed_pytorch_from_scratch_tpu.config import (ModelConfig,
                                                         SambaYConfig)
from distributed_pytorch_from_scratch_tpu.models.sambay import (
    SambaYTransformer)

IGNORE_INDEX = -1
QUERY_BLOCK = 512
SCAN_BLOCK = 64


class Family(NamedTuple):
    model: object            # the program's model, built for the mesh
    sizes: SambaYSizes       # for benchmark/lib/sambay_counts.py; data is
                             # drawn from its `vocab` (the slice held)
    reference_loss: object   # (params, ids, tgt, pos) -> float32 loss
    facts: object            # what the reference reads beside the sizes


class Facts(NamedTuple):
    half: int                # the published N / 2: the layer that leaves M
    eps: float


def layer_kinds(num_hidden_layers: int, mb_per_layer: int):
    """The published rule (`Phi4FlashDecoderLayer`), the yardstick's own
    copy: a scan every `mb_per_layer`-th layer; below N / 2 Mamba-1 or window
    attention, N / 2 the Mamba-1 layer that leaves the memory, N / 2 + 1 the
    full attention that leaves its keys and values, above a gated memory
    unit or a cross-attention."""
    N, half = num_hidden_layers, num_hidden_layers // 2
    scan = lambda i: i % mb_per_layer == 0
    return tuple(
        ("mamba" if scan(i) else "swa") if i < half
        else "mamba" if i == half else "full" if i == half + 1
        else "gmu" if scan(i) else "cross" for i in range(N))


def sizes_of(config: dict) -> SambaYSizes:
    assumed = config["assumed"]
    d = config["hidden_size"]
    kinds = layer_kinds(config["num_hidden_layers"], config["mb_per_layer"])
    here = config.get("layers_here") or list(range(len(kinds)))
    return SambaYSizes(
        d_model=d, d_ff=config["intermediate_size"],
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"],
        head_dim=d // config["num_attention_heads"],
        swa_window=config["sliding_window"],
        m_inner=assumed["mamba_expand"] * d, m_state=assumed["mamba_d_state"],
        m_rank=assumed["mamba_dt_rank"], conv=assumed["mamba_d_conv"],
        layers=tuple((i, kinds[i]) for i in here), vocab=config["vocab_size"],
        bias=bool(assumed["attention_bias"]))


def build(config: dict, mesh_sizes: dict, compute_dtype: str) -> Family:
    for key, want in (("hidden_act", "silu"), ("mlp_bias", False),
                      ("lm_head_bias", False), ("tie_word_embeddings", True),
                      ("embd_pdrop", 0), ("resid_pdrop", 0)):
        if config.get(key) != want:
            raise ValueError(f"the sambay family computes {key}={want!r} "
                             f"only, the configuration says "
                             f"{config.get(key)!r}")
    s = sizes_of(config)
    if len(s.layers) != config["num_layers"]:
        raise ValueError("num_layers must be the length of layers_here")
    assumed = config["assumed"]
    if s.m_rank != -(-s.d_model // 16):
        raise ValueError("the published dt rank is ceil(hidden_size / 16)")
    facts = Facts(half=config["num_hidden_layers"] // 2,
                  eps=float(config["layer_norm_eps"]))
    cfg = ModelConfig(
        attn_dim=s.d_model, ffn_dim=s.d_ff, num_heads=s.n_head,
        num_kv_heads=s.n_kv_head, num_layers=s.n_layer, vocab_size=s.vocab,
        maxlen=config["max_position_embeddings"],
        compute_dtype=compute_dtype,
        sambay=SambaYConfig(
            num_hidden_layers=config["num_hidden_layers"],
            layers_here=tuple(i for i, _ in s.layers),
            mb_per_layer=config["mb_per_layer"],
            sliding_window=s.swa_window, layer_norm_eps=facts.eps,
            mamba_d_state=s.m_state, mamba_d_conv=s.conv,
            mamba_expand=assumed["mamba_expand"], mamba_dt_rank=s.m_rank,
            attention_bias=s.bias,
            initializer_range=float(assumed["initializer_range"]),
            lambda_std=float(assumed["lambda_std"]),
            time_step_min=float(assumed["time_step_min"]),
            time_step_max=float(assumed["time_step_max"]),
            time_step_floor=float(assumed["time_step_floor"])))
    # every knob the workload does not define stays at the program's default
    model = SambaYTransformer(cfg, tp_size=mesh_sizes.get("tp", 1))

    def loss(params, input_ids, target_ids, position_ids):
        return reference_loss(params, input_ids, target_ids, position_ids,
                              sizes=s, facts=facts)

    return Family(model=model, sizes=s, reference_loss=loss, facts=facts)


# ---- the plain reference ----

def _layer_norm(p, x, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return p["scale"] * (x - mean) * lax.rsqrt(var + eps) + p["bias"]


def _linear(p, x):
    return x @ p["weight"] + p.get("bias", 0.0)


def _conv_silu(u, w, bias):
    """u (b, t, c), w (c, taps), bias (c,): tap j reads the token taps-1-j
    back; plus the bias; then SiLU."""
    taps, t = w.shape[-1], u.shape[1]
    padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    return jax.nn.silu(bias + sum(padded[:, j:j + t] * w[:, j]
                                  for j in range(taps)))


def selective_scan(u, dt, A, B, C):
    """The recurrence one token at a time: u, dt (b, t, c), A (c, N), B and C
    (b, t, N) -> y (b, t, c). State (b, c, N) from zero: h <- exp(dt A) h +
    dt u B^T, y = h C."""
    b, t, c = u.shape

    def token(h, row):
        u_t, dt_t, B_t, C_t = row
        h = (jnp.exp(dt_t[..., None] * A) * h
             + (dt_t * u_t)[..., None] * B_t[:, None, :])
        return h, jnp.einsum("bcn,bn->bc", h, C_t)

    @jax.checkpoint
    def block(h, rows):
        return lax.scan(token, h, rows)

    # time first, in blocks of SCAN_BLOCK steps (the last one shorter)
    rows = tuple(jnp.moveaxis(z, 1, 0) for z in (u, dt, B, C))
    h = jnp.zeros((b, c, A.shape[1]), jnp.float32)
    out = []
    whole = t - t % SCAN_BLOCK
    if whole:
        blocks = tuple(z[:whole].reshape(whole // SCAN_BLOCK, SCAN_BLOCK,
                                         *z.shape[1:]) for z in rows)
        h, y = lax.scan(block, h, blocks)
        out.append(y.reshape(whole, *y.shape[2:]))
    if t % SCAN_BLOCK:
        h, y = block(h, tuple(z[whole:] for z in rows))
        out.append(y)
    return jnp.moveaxis(jnp.concatenate(out), 0, 1)


def scan_output(p, n, s: SambaYSizes, scan=selective_scan):
    """(the scan's output with `D u`, BEFORE the gate; the gate's logits)."""
    u, z = jnp.split(n @ p["w_in"], 2, -1)
    u = _conv_silu(u, p["conv"], p["conv_bias"])
    proj = u @ p["w_x"]
    dt_r, B, C = (proj[..., :s.m_rank],
                  proj[..., s.m_rank:s.m_rank + s.m_state],
                  proj[..., s.m_rank + s.m_state:])
    dt = jax.nn.softplus(dt_r @ p["w_dt"] + p["dt_bias"])
    return scan(u, dt, -jnp.exp(p["A_log"]), B, C) + p["D"] * u, z


def lambda_init(index: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * index)


def lambda_of(p, index: int):
    return (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
            - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"]))
            + lambda_init(index))


def diff_attention(p, n, k, v, s: SambaYSizes, f: Facts, index: int,
                   window, lam=lambda_of, out_scale=None):
    """Differential attention over keys k (b, t, 2 J h) and values v (b, t,
    J 2 h), the layer's own or another's; `window` None is causal and full.
    `lam` and `out_scale` are the controls' (the published rule and `1 -
    lambda_init` where None)."""
    b, t, _ = n.shape
    h, J = s.head_dim, s.n_kv_head // 2
    H = s.n_head // 2
    g = H // J
    # query head (2 j + i) g + r -> [pair j, map i, head r of the pair]
    q = _linear(p["wq"], n).reshape(b, t, J, 2, g, h)
    k = k.reshape(b, t, J, 2, h)
    v = v.reshape(b, t, J, 2 * h)
    weight = lam(p, index)

    @jax.checkpoint
    def rows(q_rows, first):
        m = q_rows.shape[1]
        back = (first + jnp.arange(m))[:, None] - jnp.arange(t)[None, :]
        seen = (back >= 0) if window is None else (
            (back >= 0) & (back < window))
        scores = jnp.einsum("bqjigh,bkjih->bjigqk", q_rows, k) / math.sqrt(h)
        maps = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bjgqk,bkjw->bqjgw",
                          maps[:, :, 0] - weight * maps[:, :, 1], v)

    # one block of query rows at a time, the last one shorter
    step = min(QUERY_BLOCK, t)
    whole = t - t % step
    blocks = q[:, :whole].reshape(b, whole // step, step, J, 2, g, h)
    o = lax.map(lambda block: rows(*block),
                (jnp.moveaxis(blocks, 1, 0), jnp.arange(0, whole, step)))
    o = jnp.moveaxis(o, 0, 1).reshape(b, whole, J, g, 2 * h)
    if whole < t:
        o = jnp.concatenate([o, rows(q[:, whole:], whole)], axis=1)
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + f.eps)
    scale = 1.0 - lambda_init(index) if out_scale is None else out_scale
    # differential head m = j g + r
    return _linear(p["wo"], (p["subln"] * o * scale).reshape(b, t, -1))


def _swiglu(lp, u):
    return ((jax.nn.silu(u @ lp["gate_proj"]["weight"])
             * (u @ lp["up_proj"]["weight"])) @ lp["down_proj"]["weight"])


def _by_run_length(params, kinds):
    """The layers of one half, a tree a layer, in the order they run: the
    program cuts a half's kinds into periods of at most two runs of one kind
    each (`models/conv_moe.layer_blocks`), keyed `<kind>_layers_<block>` and
    stacked (periods, layers a period, ...)."""
    take = lambda tree, *i: jax.tree.map(lambda a: a[i], tree)
    runs = []
    for name in kinds:
        if runs and runs[-1][0] == name:
            runs[-1][1] += 1
        else:
            runs.append([name, 1])
    out, at, block = [], 0, 0
    while at < len(runs):
        period = runs[at:at + 2]
        repeats = 1
        while (runs[at + repeats * len(period):
                    at + (repeats + 1) * len(period)] == period):
            repeats += 1
        for p in range(repeats):
            for name, n in period:
                out += [take(params[f"{name}_layers_{block}"], p, j)
                        for j in range(n)]
        at += repeats * len(period)
        block += 1
    return out


def layers_in_order(params, s: SambaYSizes, f: Facts):
    """The layers' parameters, one tree a layer, in the order they run: the
    lower half's periods, the two makers (a segment of one layer each,
    `memory_layers` and `full_layers`), the upper half's periods."""
    first = lambda key: jax.tree.map(lambda a: a[0], params[key])
    held = [i for i, _ in s.layers]
    return (_by_run_length(params, [k for i, k in s.layers if i < f.half])
            + [first("memory_layers")] * (f.half in held)
            + [first("full_layers")] * (f.half + 1 in held)
            + _by_run_length(params,
                             [k for i, k in s.layers if i > f.half + 1]))


def _mean_ce(logits, targets):
    valid = targets != IGNORE_INDEX
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, jnp.where(valid, targets, 0)[..., None], axis=-1)[..., 0]
    return (jnp.sum(jnp.where(valid, lse - picked, 0.0))
            / jnp.maximum(jnp.sum(valid), 1))


def reference_loss(params, input_ids, target_ids, position_ids, *,
                   sizes: SambaYSizes, facts: Facts, scan=selective_scan,
                   attention=diff_attention, memory_of=None, cross_keys=None,
                   window=None):
    """The mean cross-entropy over the slice, float32. No layer takes
    positions. The keyword arguments past `facts` are the controls': the
    recurrence a Mamba layer runs, the attention, what layer N / 2 leaves of
    (y, z) (None: y, the scan's output before the gate), the keys a cross
    layer reads given (the full layer's, its own normed input) (None: the
    full layer's), the `swa` layers' window (None: the configuration's)."""
    del position_ids
    s, f = sizes, facts
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    table = params["embedding"]["weight"][:s.vocab]
    width = s.swa_window if window is None else window

    def layer(index, kind):
        @jax.checkpoint
        def run(x, lp, shared):
            n = _layer_norm(lp["norm1"], x, f.eps)
            left = shared
            if kind == "mamba":
                y, z = scan_output(lp["mamba"], n, s, scan)
                if index == f.half:
                    left = {**shared, "memory": y if memory_of is None
                            else memory_of(y, z)}
                mixed = (y * jax.nn.silu(z)) @ lp["mamba"]["w_out"]
            elif kind == "gmu":
                mixed = ((shared["memory"]
                          * jax.nn.silu(n @ lp["gmu"]["w_in"]))
                         @ lp["gmu"]["w_out"])
            elif kind == "cross":
                keys = (shared["k"] if cross_keys is None
                        else cross_keys(shared["k"], n))
                mixed = attention(lp["cross"], n, keys, shared["v"], s, f,
                                  index, None)
            else:
                p = lp["attn"]
                k, v = _linear(p["wk"], n), _linear(p["wv"], n)
                if kind == "full":
                    left = {**shared, "k": k, "v": v}
                mixed = attention(p, n, k, v, s, f, index,
                                  width if kind == "swa" else None)
            h = x + mixed
            return h + _swiglu(lp, _layer_norm(lp["norm2"], h, f.eps)), left
        return run

    x, shared = table[input_ids], {}
    for (index, kind), lp in zip(s.layers, layers_in_order(params, s, f),
                                 strict=True):
        x, shared = layer(index, kind)(x, lp, shared)
    return _mean_ce(_layer_norm(params["norm"], x, f.eps) @ table.T,
                    target_ids)
