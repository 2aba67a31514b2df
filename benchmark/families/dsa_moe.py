"""The dsa_moe family (the Keye-VL-2.0 language model, `KeyeVL2`: a
grouped-query expert decoder whose every layer chooses its keys): a
configuration file in the published keys -> the program's model
(`models/dsa_moe.SelectedAttentionMoETransformer`) and the plain reference
the benchmark checks it against.

`reference_loss_parts` is the benchmark's own copy of the architecture in
straightforward `jax.numpy`, float32 (precision "highest" is the caller's):
**the index scores a whole matrix a block of 256 query rows (16 x 256 x
16384 float32 = 0.27 GB), `I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`
written as that sum, the top-k by `lax.top_k` on the row with the keys after
it at -inf, the set a boolean matrix (scores above the k-th largest, and of
the keys that equal it the first few by a running count), the attention a
dense softmax under that matrix (32 x 256 x 16384 float32 = 0.5 GB a
block), the indexer's loss written as its definition** (the heads' mean
probabilities under `stop_gradient`, the KL from the softmax of the scores
over the set, the rows' mean, summed over the layers); q/k norms per head,
then half-split RoPE over the whole head (the indexer's over all of its
own); the indexer reads a `stop_gradient` of the normed input; the softmax
top-k router normalised over the chosen; **the held experts applied one by
one to every row and masked by the weights**; an untied head whose logits
and loss are made a block of 4096 rows at a time; each block and each layer
under `jax.checkpoint`. No kernel, no sharding, no dispatch, no scan. It
consumes the parameter pytree `SelectedAttentionMoETransformer.init`
produces and is given the same share of experts and the same vocabulary
slice. It can be HANDED a selection (`given`, layers x (b, t, t)): it then
attends over that one (the check compares the program's gradients on the
program's own choice, where a key at the margin changes no side) and counts
how much of it is its own. `models/vanilla_dsa_moe.py` is the program's
oracle; this is a copy, held to it by
benchmark/tests/test_dsa_moe_counts.py.

Departures from the published description (the configuration file's
`assumed`): the q/k head norms, the indexer's inputs, norm, scale and
positions, the objective and its weight 1, the tie rule; no balance loss; a
share adds what its experts give.

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

from benchmark.lib.dsa_moe_counts import DsaMoESizes
# at import, so that a program without the family fails before any device
# is touched (run.py loads this module before the runner starts)
from distributed_pytorch_from_scratch_tpu.config import (DsaMoEConfig,
                                                         ModelConfig)
from distributed_pytorch_from_scratch_tpu.models.dsa_moe import (
    SelectedAttentionMoETransformer)

IGNORE_INDEX = -1
QUERY_BLOCK = 256
HEAD_BLOCK = 4096
PROBE_ROWS = 512
HIGHEST = lax.Precision.HIGHEST


class Family(NamedTuple):
    model: object            # the program's model, built for the mesh
    sizes: DsaMoESizes       # for benchmark/lib/dsa_moe_counts.py; data is
                             # drawn from its `vocab` (the slice held)
    reference_loss: object   # (params, ids, tgt, pos) -> float32 loss
    reference_parts: object  # (params, ids, tgt, pos, given=None) ->
                             # (loss, parts), for has_aux:
                             # `reference_loss_parts`


def sizes_of(config: dict) -> DsaMoESizes:
    sa = config["sa_config"]
    if sa["indexer_num_kv_heads"] != 1:
        raise ValueError("the dsa_moe family has ONE index key head, the "
                         f"configuration says {sa['indexer_num_kv_heads']}")
    return DsaMoESizes(
        d_model=config["hidden_size"], n_layer=config["num_layers"],
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        d_expert=config["moe_intermediate_size"],
        n_routed=config["published"]["num_experts"],
        n_held=config["num_experts"], top_k=config["num_experts_per_tok"],
        vocab=config["vocab_size"], index_heads=sa["indexer_num_heads"],
        index_dim=sa["indexer_head_dim"], index_topk=sa["topk"])


def build(config: dict, mesh_sizes: dict, compute_dtype: str) -> Family:
    for key, want in (("norm_topk_prob", True), ("attention_bias", False),
                      ("tie_word_embeddings", False),
                      ("use_sliding_window", False),
                      ("decoder_sparse_step", 1), ("mlp_only_layers", [])):
        if config.get(key) != want:
            raise ValueError(f"the dsa_moe family computes {key}={want!r} "
                             f"only, the configuration says "
                             f"{config.get(key)!r}")
    s = sizes_of(config)
    cfg = ModelConfig(
        attn_dim=s.d_model, ffn_dim=0, num_heads=s.n_head,
        num_kv_heads=s.n_kv_head, num_layers=s.n_layer, vocab_size=s.vocab,
        maxlen=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]), compute_dtype=compute_dtype,
        num_experts=s.n_routed, moe_top_k=s.top_k,
        dsa_moe=DsaMoEConfig(
            head_dim=s.head_dim, moe_intermediate_size=s.d_expert,
            indexer_num_heads=s.index_heads, indexer_head_dim=s.index_dim,
            topk=s.index_topk, experts_held=s.n_held,
            expert_offset=int(config["deployment_share"]["expert_offset"]),
            rms_norm_eps=float(config["rms_norm_eps"])))
    # every knob the workload does not define stays at the program's default
    model = SelectedAttentionMoETransformer(
        cfg, tp_size=mesh_sizes.get("tp", 1))
    dm = cfg.dsa_moe

    def parts(params, input_ids, target_ids, position_ids, given=None):
        return reference_loss_parts(
            params, input_ids, target_ids, position_ids, sizes=s,
            expert_offset=dm.expert_offset, rope_theta=cfg.rope_theta,
            eps=dm.rms_norm_eps, given=given)

    return Family(model=model, sizes=s,
                  reference_loss=lambda *a: parts(*a)[0],
                  reference_parts=parts)


# ---- the plain reference ----

def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _norm(p, x, eps):
    return (x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                          + eps) * p["scale"])


def _layer_norm(p, x, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _rope(x, pos, theta: float):
    """Half-split pairs (x_i, x_{i + dim/2}) of x (b, heads, t, dim) at the
    positions `pos` (b, t)."""
    dim = x.shape[-1]
    freq = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = pos.astype(jnp.float32)[:, None, :, None] * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def top_keys(score, first, k: int):
    """((n, t) bool a sequence: row i (the sequence's row `first + i`) keeps
    the `k` keys at or before it of largest `score` (b, n, t), of equal
    scores the earlier; all of them where it sees fewer; (n,) bool: did
    the tie rule decide the row, keys that equal its k-th largest lying on
    both sides of the budget)."""
    n, t = score.shape[-2:]
    causal = jnp.arange(t)[None, :] <= first + jnp.arange(n)[:, None]
    seen = jnp.where(causal, score, -jnp.inf)
    kth = lax.top_k(seen, min(k, t))[0][..., -1:]
    above, equal = seen > kth, seen == kth
    need = k - jnp.sum(above, axis=-1, keepdims=True)
    keep = causal & (above | (equal & (jnp.cumsum(equal, axis=-1) <= need)))
    tied = (jnp.sum(equal & causal, axis=-1, keepdims=True) > need)[..., 0]
    return keep, tied


def _attention(lp, y, pos, s, theta: float, eps: float, given):
    """(the sublayer's output (b, t, d), the sum of the rows' KL, counts:
    the pairs the reference chose, the pairs given, the pairs in both and
    the rows the tie rule decided, and the scores of the last `PROBE_ROWS` rows)."""
    b, t, _ = y.shape
    h, J, c = s.head_dim, s.index_heads, s.index_dim
    heads = lambda z, n, w: z.reshape(b, t, n, w).transpose(0, 2, 1, 3)
    q = heads(_mm(y, lp["wq"]), s.n_head, h)
    k = heads(_mm(y, lp["wk"]), s.n_kv_head, h)
    v = heads(_mm(y, lp["wv"]), s.n_kv_head, h)
    q = _rope(_norm(lp["q_norm"], q, eps), pos, theta)
    k = _rope(_norm(lp["k_norm"], k, eps), pos, theta)
    # query head g of key-value head n is head `n * group + g`
    group = s.n_head // s.n_kv_head
    q = q.reshape(b, s.n_kv_head, group, t, h)
    # the indexer reads the layer's normed input and hands it no gradient
    ip, yi = lp["indexer"], lax.stop_gradient(y)
    qi = _rope(heads(_mm(yi, ip["wq"]), J, c), pos, theta)
    ki = _rope(_layer_norm(ip["k_norm"], _mm(yi, ip["wk"]), eps)[:, None],
               pos, theta)[:, 0]
    w = _mm(yi, ip["w_proj"]) / math.sqrt(J * c)          # (b, t, J)

    @jax.checkpoint
    def rows(q_rows, qi_rows, w_rows, handed, first):
        z = jnp.einsum("bjnc,bsc->bjns", qi_rows, ki, precision=HIGHEST)
        score = jnp.sum(jnp.moveaxis(w_rows, 2, 1)[..., None]
                        * jnp.maximum(z, 0.0), axis=1)        # (b, n, t)
        score = jnp.where(score == 0.0, 0.0, score)
        own, tied = top_keys(lax.stop_gradient(score), first, s.index_topk)
        keep = own if handed is None else handed
        scores = jnp.einsum("bngqd,bnkd->bngqk", q_rows, k,
                            precision=HIGHEST) / math.sqrt(h)
        probs = jax.nn.softmax(
            jnp.where(keep[:, None, None], scores, -jnp.inf), axis=-1)
        o = jnp.einsum("bngqk,bnkd->bngqd", probs, v, precision=HIGHEST)
        # the indexer's loss: the heads' mean probabilities are its target
        target = lax.stop_gradient(jnp.mean(probs, axis=(1, 2)))
        log_pi = jax.nn.log_softmax(jnp.where(keep, score, -jnp.inf),
                                    axis=-1)
        kl = jnp.sum(jnp.where(
            target > 0.0, target * (jnp.log(jnp.maximum(target, 1e-37))
                                    - jnp.where(keep, log_pi, 0.0)), 0.0))
        pairs = jnp.stack([jnp.sum(own), *(
            (jnp.sum(handed), jnp.sum(own & handed))
            if handed is not None else (0, 0)),
            jnp.sum(tied)]).astype(jnp.float32)
        return o, kl, pairs, lax.stop_gradient(score)

    # one block of query rows at a time (the tests' sequences are one)
    step = min(QUERY_BLOCK, t)
    assert t % step == 0, (t, step)
    cut = lambda z, axis: jnp.moveaxis(
        z.reshape(*z.shape[:axis], t // step, step, *z.shape[axis + 1:]),
        axis, 0)
    blocks = (cut(q, 3), cut(qi, 2), cut(w, 1),
              None if given is None else cut(given.astype(bool), 1),
              jnp.arange(0, t, step))
    o, kl, pairs, score = lax.map(lambda block: rows(*block), blocks)
    o = jnp.moveaxis(o, 0, 3).reshape(b, s.n_head, t, h)
    o = o.transpose(0, 2, 1, 3).reshape(b, t, s.n_head * h)
    last = jnp.moveaxis(score, 0, 1).reshape(b, t, t)[:, -min(PROBE_ROWS, t):]
    return _mm(o, lp["wo"]), jnp.sum(kl), jnp.sum(pairs, axis=0), last


def _expert_ffn(mp, y, s, expert_offset: int):
    """sum over the experts HELD of w_e E_e(y), each expert applied to every
    row and masked by its weight (no shared expert); and how many (row,
    choice) pairs chose each routed expert."""
    b, t, d = y.shape
    x = y.reshape(b * t, d)
    score = jax.nn.softmax(_mm(x, mp["router"]), axis=-1)     # all routed
    _, chosen = lax.top_k(score, s.top_k)
    w = jnp.take_along_axis(score, chosen, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True)

    @jax.checkpoint
    def one(acc, expert):
        e, gate, up, down = expert
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)
        out = _mm(jax.nn.silu(_mm(x, gate)) * _mm(x, up), down)
        return acc + w_e[:, None] * out, None

    held = mp["gate"].shape[0]
    out, _ = lax.scan(one, jnp.zeros_like(x),
                      (expert_offset + jnp.arange(held), mp["gate"],
                       mp["up"], mp["down"]))
    routed = jnp.zeros(score.shape[-1]).at[chosen.reshape(-1)].add(1.0)
    return out.reshape(b, t, d), routed


def reference_loss_parts(params, input_ids, target_ids, position_ids, *,
                         sizes, expert_offset: int, rope_theta: float,
                         eps: float, given=None):
    """(the loss: the mean CE over the slice + the sum over the layers of
    the rows' mean KL; parts: `ce`, `index_kl` (layers,), `routed` (layers,
    routed experts), `pairs` (layers, 4): the (row, key) pairs the
    reference chose, the pairs `given` (layers, b, t, t) holds, the pairs
    in both and the ROWS its tie rule decided, `score_rows` (layers, b, rows, t): the index scores of the last
    rows), float32. With `given` every layer attends
    over the set it is handed, and its own choice is only counted."""
    s = sizes
    b, t = input_ids.shape
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)

    @jax.checkpoint
    def layer(x, lp, handed):
        a, kl, pairs, last = _attention(
            lp["attn"], _norm(lp["norm1"], x, eps), position_ids, s,
            rope_theta, eps, handed)
        x = x + a
        out, routed = _expert_ffn(lp["moe"], _norm(lp["norm2"], x, eps), s,
                                  expert_offset)
        return x + out, (kl / (b * t), routed, pairs, last)

    x = params["embedding"]["weight"][input_ids]
    counted = []
    n_layers = jax.tree.leaves(params["layers"])[0].shape[0]
    for i in range(n_layers):
        x, more = layer(x, jax.tree.map(lambda a: a[i], params["layers"]),
                        None if given is None else given[i])
        counted.append(more)
    kl, routed, pairs, last = (jnp.stack(z) for z in zip(*counted))

    @jax.checkpoint
    def head(x_rows, targets):
        logits = _mm(_norm(params["norm"], x_rows, eps),
                     params["lm_head"]["weight"][:, :s.vocab])
        valid = targets != IGNORE_INDEX
        ce = (jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, jnp.where(valid, targets, 0)[..., None], axis=-1)[..., 0])
        return jnp.sum(jnp.where(valid, ce, 0.0)), jnp.sum(valid)

    step = min(HEAD_BLOCK, t)
    assert t % step == 0, (t, step)
    sums = [head(x[:, at:at + step], target_ids[:, at:at + step])
            for at in range(0, t, step)]
    ce = sum(a for a, _ in sums) / jnp.maximum(sum(n for _, n in sums), 1)
    parts = {"ce": ce, "index_kl": kl, "routed": routed, "pairs": pairs,
             "score_rows": last}
    return ce + jnp.sum(kl), jax.tree.map(lax.stop_gradient, parts)
