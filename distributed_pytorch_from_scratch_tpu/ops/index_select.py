"""Attention over keys a layer CHOOSES: the lightning indexer's score, the
exact top-k a row, the attention over the chosen keys and the indexer's own
loss (DeepSeek-V3.2's sparse attention and its sparse training stage).

For one sequence, per row t and key s <= t, with `J` index heads of width
`c`, `qI` (b, J, t, c), ONE index key head `kI` (b, t, c) and the rows'
head weights `w` (b, t, J) float32:

    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])            float32
    S_t     = the `top_k` keys s <= t of largest I[t, s], ties to the
              earlier key; all t + 1 of them where t < top_k
    o[t, h] = sum_{s in S_t} softmax_{s in S_t}(q[t, h] . k[s, g(h)]
                                                / sqrt(head_dim)) v[s, g(h)]
    P[t, s] = (1 / H) sum_h (that softmax)      the indexer's target
    KL_t    = sum_{s in S_t} P[t, s] (log P[t, s] - log softmax_S(I)[t, s])

A row's set is two numbers (`select`): `tau`, its `top_k`-th largest
score, and `cut`, the last key index kept among the keys whose score EQUALS
tau; `live` is the set as a boolean. **No gradient passes through the
choice, P is a constant of the KL, and the attention's gradient reaches no
index tensor**: `selected_attention` returns `(o, sums)` where the sum of
the rows' KL in `sums["dsa_index_kl"]` is differentiable in (qI, kI, w)
alone and o in (q, k, v) alone. The caller feeds the indexer from a
`stop_gradient` of the layer's input and adds the KL to its loss, which
splits the parameters between the two losses exactly.

`selected_attention` is the dispatch (`ops/attention.IMPLS`): `xla` is the
text below, whole (t, t) matrices, the CPU default and the kernels' oracle;
`flash` / `flash_interpret` are `ops/pallas/dsa_attention.py`'s five
kernels, which never hold the score: the selection makes it a tile at a
time and writes the set as a bit a pair, the three attention walks read
the bits, and the loss walk makes the tile once more for its numbers.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..obs.trace import current_tracer
from .attention import repeat_kv, resolve_attention_impl

# what `selected_attention` counts beside the output, each a SUM over the
# rows of the call (the caller divides, after its mesh has added them up)
SUMS = ("dsa_index_kl", "dsa_index_entropy", "dsa_kept", "dsa_causal",
        "dsa_tau_ties", "dsa_rows")


def index_scores(q_idx: jax.Array, k_idx: jax.Array, w: jax.Array):
    """I (b, t, t) float32, every pair (the caller masks s > t)."""
    z = jnp.einsum("bjtc,bsc->bjts", q_idx, k_idx,
                   preferred_element_type=jnp.float32)
    score = jnp.einsum("btj,bjts->bts", w.astype(jnp.float32),
                       jax.nn.relu(z))
    return jnp.where(score == 0.0, 0.0, score)          # no -0.0


def select(score: jax.Array, top_k: int):
    """(tau (b, t) float32, cut (b, t) int32, tied (b, t) bool) of `score`
    (b, t, t): `live` below is then the row's `top_k` causal keys of
    largest score, the earlier key first among equals; `tied`: keys that
    score tau lie on both sides of the row's budget."""
    t = score.shape[-1]
    rows, cols = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    seen = jnp.where(cols <= rows, score, -jnp.inf)
    if top_k >= t:
        return (jnp.full(score.shape[:-1], -jnp.inf, jnp.float32),
                jnp.full(score.shape[:-1], t, jnp.int32),
                jnp.zeros(score.shape[:-1], bool))
    tau = lax.top_k(seen, top_k)[0][..., -1]    # -inf where t + 1 < top_k
    short = jnp.isneginf(tau)
    equal = seen == tau[..., None]
    # the first `need` of the keys that score tau, by index
    need = top_k - jnp.sum(seen > tau[..., None], axis=-1)
    last = jnp.cumsum(equal, axis=-1) == need[..., None]
    cut = jnp.argmax(equal & last, axis=-1).astype(jnp.int32)
    tied = (jnp.sum(equal, axis=-1) > need) & ~short
    return tau, jnp.where(short, t, cut), tied


def live(score: jax.Array, tau: jax.Array, cut: jax.Array) -> jax.Array:
    """(b, t, t) bool: is key s in row t's set."""
    t = score.shape[-1]
    rows, cols = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    tau, cut = tau[..., None], cut[..., None]
    return (cols <= rows) & ((score > tau)
                             | ((score == tau) & (cols <= cut)))


def _sums(kl, entropy, kept, tied, b: int, t: int) -> Dict[str, jax.Array]:
    f32 = jnp.float32
    return {"dsa_index_kl": jnp.sum(kl), "dsa_index_entropy":
            jnp.sum(entropy), "dsa_kept": jnp.sum(kept.astype(f32)),
            "dsa_causal": jnp.asarray(b * (t * (t + 1) // 2), f32),
            "dsa_tau_ties": jnp.sum(tied.astype(f32)),
            "dsa_rows": jnp.asarray(b * t, f32)}


def selected_attention_xla(q, k, v, q_idx, k_idx, w, top_k: int):
    """The definition, densely."""
    b, H, t, h = q.shape
    with jax.named_scope("dsa_index"):
        score = index_scores(q_idx, k_idx, w)
    with jax.named_scope("dsa_select"):
        held = lax.stop_gradient(score)
        tau, cut, tied = select(held, top_k)
        keep = live(held, tau, cut)
    with jax.named_scope("dsa_attend"):
        kx, vx = repeat_kv(q, k, v)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kx,
                       preferred_element_type=jnp.float32) / math.sqrt(h)
        probs = jax.nn.softmax(jnp.where(keep[:, None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("bhqk,bhkd->bhqd", probs.astype(q.dtype), vx)
    with jax.named_scope("dsa_index_loss"):
        target = lax.stop_gradient(jnp.mean(probs, axis=1))
        log_pi = jax.nn.log_softmax(jnp.where(keep, score, -jnp.inf), axis=-1)
        log_pi = jnp.where(keep, log_pi, 0.0)
        kl = jnp.sum(jnp.where(
            target > 0.0,
            target * (jnp.log(jnp.maximum(target, 1e-37)) - log_pi), 0.0),
            axis=-1)
        pi = jnp.where(keep, jnp.exp(log_pi), 0.0)
        entropy = lax.stop_gradient(-jnp.sum(pi * log_pi, axis=-1))
    return o, _sums(kl, entropy, jnp.sum(keep, axis=-1), tied, b, t)


# ---- the kernels, under one custom_vjp ----

@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _selected_flash(q, k, v, q_idx, k_idx, w, top_k, bq, bk, interpret):
    return _selected_flash_fwd(q, k, v, q_idx, k_idx, w, top_k, bq, bk,
                               interpret)[0]


def _selected_flash_fwd(q, k, v, q_idx, k_idx, w, top_k, bq, bk, interpret):
    from .pallas import dsa_attention as kernels
    b, _, t, _ = q.shape
    blocks = dict(bq=bq, bk=bk, interpret=interpret)
    w4 = _rows_last(w.astype(jnp.float32))
    with jax.named_scope("dsa_select"):
        tau, cut, tied, bits, lse_i, kept = kernels.select_call(
            q_idx, k_idx, w4, top_k, **blocks)
    tracer = current_tracer()
    if tracer is not None:
        # what this call built: the kernels that make the index tile are
        # the selection and the loss walk, the set is `bits`, and a head's
        # score tile of the forward walk is (keys, rows)
        tracer.instant("dsa_walk", mask="bits", planes=bits.shape[1],
                       bits_bytes=bits.size * bits.dtype.itemsize,
                       index_tiles_a_layer=2, blocks=[bq, bk], t=t,
                       fwd_tile="keys_by_rows", fwd_tile_shape=[bk, bq])
    # A rung that keeps the kernels' outputs keeps the choice they were
    # made under: the backward never attends over a re-made selection. The
    # choice is a bit a (row, key) pair, t / 8 bytes a row: the backward
    # walks read nothing else of the indexer's.
    bits = checkpoint_name(bits, "flash_lse")
    with jax.named_scope("dsa_attend"):
        o, lse = kernels.fwd_call(q, k, v, bits, **blocks)
    o = checkpoint_name(o, "flash_out")
    # (the forward leaves it lane-dense, (b, H, t): a (.., t, 1) column is
    # a tile of 128 lanes a row on the chip, 128 times its size)
    lse = checkpoint_name(lse, "flash_lse")
    with jax.named_scope("dsa_index_loss"):
        kl, entropy, d_qi, d_w, d_ki = kernels.loss_call(
            q, k, lse[..., None], q_idx, k_idx, w4, tau, cut, lse_i, **blocks)
        # (with the flash outputs, so that a rung that keeps those does
        # not walk the triangle again for these)
        d_qi = checkpoint_name(d_qi, "flash_out")
        d_ki = checkpoint_name(d_ki.astype(k_idx.dtype), "flash_out")
        d_w = checkpoint_name(
            jnp.swapaxes(d_w[..., 0], 1, 2).astype(w.dtype), "flash_out")
    sums = _sums(kl, entropy, kept, tied, b, t)
    return (o, sums), (q, k, v, bits, o, lse, d_qi, d_ki, d_w)


def _rows_last(w):
    """(b, t, J) -> (b, J, t, 1): the head weights as the kernels' blocks
    take them, a column a head."""
    return jnp.swapaxes(w, 1, 2)[..., None]


def _selected_flash_bwd(top_k, bq, bk, interpret, kept_back, cts):
    from .pallas import dsa_attention as kernels
    q, k, v, bits, o, lse, d_qi, d_ki, d_w = kept_back
    do, d_sums = cts
    with jax.named_scope("dsa_attend"):
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1, keepdims=True)
        dq, dk, dv = kernels.bwd_calls(
            q, k, v, bits, do, lse[..., None], delta, bq=bq, bk=bk,
            interpret=interpret)
    with jax.named_scope("dsa_index_loss"):
        g = d_sums["dsa_index_kl"].astype(jnp.float32)
        scale = lambda a: (g * a.astype(jnp.float32)).astype(a.dtype)
    return dq, dk, dv, scale(d_qi), scale(d_ki), scale(d_w)


_selected_flash.defvjp(_selected_flash_fwd, _selected_flash_bwd)


def flash_blocks(t: int) -> Tuple[int, int]:
    """(query block, key block) of the kernels' walks over `t` rows: the
    tuned pair where it divides the sequence, else the largest power of two
    that does (the tests' sizes)."""
    from .pallas.dsa_attention import BLOCK_K, BLOCK_Q

    def fit(block):
        while t % block:
            block //= 2
        return block
    return fit(min(BLOCK_Q, t)), fit(min(BLOCK_K, t))


# rows of a sequence, its last, whose scores `selection_probe` hands out
PROBE_ROWS = 512


def selection_probe(q_idx, k_idx, w, top_k: int, impl: str = "auto"):
    """What `selected_attention` chose, written out for a check (in no
    step: the sets are (t, t) a sequence): (the index scores of a
    sequence's last `PROBE_ROWS` rows (b, rows, t) float32, is key s in row
    t's set (b, t, t) int8), from the implementation `impl` runs."""
    impl = resolve_attention_impl(impl)
    t = q_idx.shape[2]
    if impl == "xla":
        score = index_scores(q_idx, k_idx, w)
        tau, cut, _ = select(score, top_k)
        chosen = live(score, tau, cut).astype(jnp.int8)
    else:
        from .pallas import dsa_attention as kernels
        kernels.require_tpu(impl == "flash_interpret")
        bq, bk = flash_blocks(t)
        blocks = dict(bq=bq, bk=bk, interpret=impl == "flash_interpret")
        w4 = _rows_last(w.astype(jnp.float32))
        _, _, _, bits, _, _ = kernels.select_call(q_idx, k_idx, w4, top_k,
                                                  **blocks)
        score, chosen = kernels.probe_call(q_idx, k_idx, w4, bits, **blocks)
    return score[:, -min(PROBE_ROWS, t):], chosen


def selected_attention(q, k, v, q_idx, k_idx, w, top_k: int,
                       impl: str = "auto"):
    """(o (b, H, t, h), `SUMS`) of the attention over each row's `top_k`
    chosen keys (module docstring): q (b, H, t, h); k, v (b, Hkv, t, h);
    q_idx (b, J, t, c); k_idx (b, t, c); w (b, t, J) float32."""
    impl = resolve_attention_impl(impl)
    if impl == "xla":
        return selected_attention_xla(q, k, v, q_idx, k_idx, w, top_k)
    from .pallas.dsa_attention import require_tpu
    bq, bk = flash_blocks(q.shape[2])
    require_tpu(impl == "flash_interpret", bq)
    return _selected_flash(q, k, v, q_idx, k_idx, w, top_k, bq, bk,
                           impl == "flash_interpret")
