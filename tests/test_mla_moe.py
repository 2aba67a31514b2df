"""The `mla_moe` family (models/mla_moe.py): latent attention, the sigmoid
router over held experts with a shared expert, the layer pattern, the
multi-token-prediction module. CPU, tiny sizes, float32.

* the program against the plain reference (models/vanilla_mla_moe.py): loss
  and EVERY gradient leaf, at tp 1 and tp 2, on a job that holds a slice of
  the experts; no top-k choice sits on a tie (the margin is asserted);
* the flash kernel at unequal q/k and v widths against the XLA path,
  forward and backward, one tile and a multi-block grid;
* the share test: the routed parts of all the shares of a layer plus the
  shared expert once add up to the uncut layer;
* a router forced onto the same experts drops nothing;
* the grouped products' groups cover the held rows and nothing more, and no
  row past them is read: the layer with NaN in every such row (what the
  chip's kernel may leave there) equals the plain run, every gradient too;
* what the family does not run is refused with a message;
* the counts: parameters at the published widths (680.4M in all).
"""

import dataclasses
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from family_recipe import (Recipe, apply_moe, hold_leaves, hold_loss,
                           mesh_of, token_file)

from distributed_pytorch_from_scratch_tpu.config import (
    IGNORE_INDEX, LatentMoEConfig, ModelConfig, OptimizerConfig)
from distributed_pytorch_from_scratch_tpu.models import build_model
from distributed_pytorch_from_scratch_tpu.models.mla_moe import (
    LatentMoETransformer)
from distributed_pytorch_from_scratch_tpu.models.vanilla_mla_moe import (
    vanilla_loss)
from distributed_pytorch_from_scratch_tpu.ops.attention import (
    causal_attention_xla)
from distributed_pytorch_from_scratch_tpu.ops.pallas.flash_attention import (
    flash_attention)
from distributed_pytorch_from_scratch_tpu.ops.rope import (
    apply_rotary_interleaved, rope_angles)
from distributed_pytorch_from_scratch_tpu.parallel.moe import SharedRoutedFFN
from distributed_pytorch_from_scratch_tpu.training.metrics import (
    model_flops_per_step, moe_counters_summary)
from distributed_pytorch_from_scratch_tpu.training.train_step import (
    build_train_step)


# the family's own: its reference, sequences of 128 from id 0 up, and an
# ignored target in the middle
R = Recipe("mla_moe", vanilla_loss, t=128, low=0, ignore=((0, 5),))
tiny, batch, on_mesh = R.tiny, R.batch, R.on_mesh


# ---- the program against the plain reference ----

@pytest.mark.parametrize("tp,impl", [(1, "xla"), (2, "xla"),
                                     (1, "flash_interpret")])
def test_loss_and_every_gradient_leaf_equal_the_reference(tp, impl):
    """A job that holds experts 2..5 of 8: what the absent ones would add
    is left out by program and reference alike."""
    cfg = tiny(experts_held=4, expert_offset=2)
    assert batch(cfg)[1][0, 5] == IGNORE_INDEX
    # (the parameters and the reference are one for the three layouts)
    _, (want, want_g) = R.reference(cfg)
    got, got_g = R.program(cfg, tp=tp, attn_impl=impl)
    hold_loss(want, got)
    assert len(hold_leaves(want_g, got_g, 1e-5)[0]) > 40
    # the selection bias is read by top-k alone: no gradient reaches it
    assert not np.any(np.asarray(got_g["layers"]["moe"]["bias"]))


def test_no_top_k_choice_sits_on_a_tie():
    """The comparison above means something only if float32 rounding cannot
    flip a choice: the k-th and (k+1)-th scores are apart at every token of
    the first expert layer's input (seeded weights, the test's batch)."""
    cfg = tiny()
    moe = SharedRoutedFFN(cfg.attn_dim, 32, cfg.num_experts, cfg.moe_top_k)
    p = moe.init(jax.random.key(3))
    x = jax.random.normal(jax.random.key(4), (256, cfg.attn_dim))
    with jax.default_matmul_precision("highest"):
        s = np.sort(np.asarray(jax.nn.sigmoid(x @ p["router"])), axis=-1)
    margin = s[:, -cfg.moe_top_k] - s[:, -cfg.moe_top_k - 1]
    assert margin.min() > 1e-5


def test_interleaved_rope_turns_pairs():
    """Pair (x_2i, x_2i+1) times e^{i pos theta_i}, as complex numbers."""
    x = jax.random.normal(jax.random.key(0), (2, 3, 8, 6))
    pos = jnp.tile(jnp.arange(8)[None], (2, 1))
    cos, sin = rope_angles(pos, 6, 100.0)
    got = np.asarray(apply_rotary_interleaved(x, cos, sin))
    z = np.asarray(x[..., 0::2]) + 1j * np.asarray(x[..., 1::2])
    theta = 100.0 ** (-np.arange(0, 6, 2) / 6)
    w = z * np.exp(1j * np.arange(8)[None, None, :, None] * theta)
    np.testing.assert_allclose(got[..., 0::2], w.real, atol=1e-5)
    np.testing.assert_allclose(got[..., 1::2], w.imag, atol=1e-5)


# ---- the flash kernel at two widths ----

@pytest.mark.parametrize("t,block,kv_heads", [(128, None, 2), (384, 128, 2),
                                              (256, 128, 1)])
def test_flash_at_unequal_widths_equals_the_xla_path(t, block, kv_heads):
    """q/k 48 wide against v of 32: one tile (the fused backward) and a
    multi-block grid (scratch across key blocks, the split backward), with
    and without grouped kv heads."""
    key = jax.random.key(0)
    q = jax.random.normal(jax.random.fold_in(key, 1), (1, 2, t, 48))
    k = jax.random.normal(jax.random.fold_in(key, 2), (1, kv_heads, t, 48))
    v = jax.random.normal(jax.random.fold_in(key, 3), (1, kv_heads, t, 32))
    blocks = dict.fromkeys(
        ("block_q", "block_k", "bwd_block_q", "bwd_block_k"), block)
    flash = lambda q, k, v: jnp.sum(jnp.sin(flash_attention(
        q, k, v, interpret=True, **(blocks if block else {}))))
    plain = lambda q, k, v: jnp.sum(jnp.sin(causal_attention_xla(q, k, v)))
    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(flash, (0, 1, 2))(q, k, v)
        want = jax.value_and_grad(plain, (0, 1, 2))(q, k, v)
    assert flash_attention(q, k, v, interpret=True).shape == (1, 2, t, 32)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_flash_refuses_q_and_k_of_different_widths():
    x = jnp.zeros((1, 1, 128, 16))
    with pytest.raises(ValueError, match="q and k widths differ"):
        flash_attention(x, jnp.zeros((1, 1, 128, 8)), x, interpret=True)


# ---- the expert layer: shares, and no drop ----

def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Four jobs hold two experts each of one layer's eight. Their routed
    parts, plus the shared expert once, are the layer a job holding all
    eight computes: the weights are normalised over all chosen experts,
    held or not, so the parts are parts of one sum."""
    d, f, E = 32, 16, 8
    whole = SharedRoutedFFN(d, f, E, top_k=3, scaling=2.5)
    p = whole.init(jax.random.key(1))
    x = jax.random.normal(jax.random.key(2), (2, 64, d))
    with jax.default_matmul_precision("highest"):
        want, counters = apply_moe(whole, p, x)
        xf, sh = x.reshape(-1, d), p["shared"]
        shared_only = ((jax.nn.silu(xf @ sh["gate"]) * (xf @ sh["up"]))
                       @ sh["down"]).reshape(x.shape)
        total, rows = shared_only, 0.0
        for lo in range(0, E, 2):
            share = dataclasses.replace(whole, held=2, offset=lo)
            ps = {**p, **{n: p[n][lo:lo + 2] for n in ("gate", "up", "down")}}
            y, c = apply_moe(share, ps, x)
            total = total + (y - shared_only)
            rows += float(c["rows_here"])
            np.testing.assert_array_equal(c["routed"], counters["routed"])
    np.testing.assert_allclose(total, want, atol=2e-5)
    assert rows == float(counters["rows_here"]) == 2 * 64 * 3


def test_a_router_forced_onto_the_same_experts_drops_nothing():
    """The selection bias sends EVERY token to experts 0..2, all held and
    far over any mean share: each (token, choice) pair is computed, in
    several chunks of the sorted pairs, and the layer equals the dense
    sum over those experts."""
    d, f, E, k = 32, 16, 64, 3
    moe = SharedRoutedFFN(d, f, E, top_k=k, held=4)
    p = moe.init(jax.random.key(1))
    p["bias"] = jnp.where(jnp.arange(E) < k, 10.0, 0.0)
    x = jax.random.normal(jax.random.key(2), (4, 214, d))
    # 2568 pairs, a chunk of 4/64 of them rounded up to the kernel's
    # 512-row tile: six live chunks, the last of which runs past the pairs
    assert moe.chunk_rows(4 * 214 * k) == 512
    with jax.default_matmul_precision("highest"):
        got, counters = apply_moe(moe, p, x)
        xf = x.reshape(-1, d)
        s = jax.nn.sigmoid(xf @ p["router"])[:, :k]
        w = s / jnp.sum(s, axis=-1, keepdims=True)
        ffn = lambda g, u, dn: (jax.nn.silu(xf @ g) * (xf @ u)) @ dn
        want = sum(w[:, e:e + 1] * ffn(p["gate"][e], p["up"][e], p["down"][e])
                   for e in range(k))
        sh = p["shared"]
        want = want + ffn(sh["gate"], sh["up"], sh["down"])
    assert float(counters["rows_here"]) == 4 * 214 * k     # 2568 pairs
    assert float(counters["rows_computed"]) == 4 * 214 * k
    assert float(counters["rows_walked"]) == 6 * 512       # every chunk live
    np.testing.assert_array_equal(
        counters["routed"], np.where(np.arange(E) < k, 4 * 214, 0))
    np.testing.assert_allclose(got.reshape(-1, d), want, atol=2e-5)


# ---- the dispatch's row movers: `take_held` in, `sum_held` back ----

def add_held(y, r, tok, valid):
    """A chunk's rows back onto the sums as the layer moved them until PR
    65, kept HERE as `sum_held`'s oracle: the row scatter-add
    `y.at[tok].add(r)` over the chunk's held rows, a padding row aimed past
    `y`'s last row, where a scatter drops it."""
    at = jnp.where(valid[:, 0], tok, y.shape[0])
    return y.at[at].add(r, mode="drop")


SUM_HELD_CASES = [
    # case, S, M, d, held rows, the tokens they fall on, windows a block
    ("random rows, a chunk of as many rows as tokens", 1024, 1024, 128, 1024,
     1024, 1.0),
    ("padding rows past the held ones", 1024, 1024, 128, 700, 1024, 1.0),
    ("every row on a few tokens: a block of many windows", 512, 2048, 128,
     2048, 3, 2.5),
    ("a block's rows run past a window's end", 512, 1536, 128, 1536, 512,
     2.0),
    ("a chunk with no held row", 512, 512, 128, 0, 512, 1.0),
    ("tokens that fill no whole block", 300, 600, 16, 450, 300, None),
    ("fewer tokens and rows than a block and a window", 64, 96, 16, 50, 64,
     1.0),
]


# the Mosaic kernel (under the interpreter) takes whole blocks and windows
# in whole lanes; XLA's text runs the other shapes on every backend
SUM_HELD_FORMS = [(*c, form) for c in SUM_HELD_CASES
                  for form in ("xla", "xla-tp2", "kernel", "kernel-tp2")
                  if "kernel" not in form or c[3] % 128 == 0]


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-6), ("bfloat16", 2.0 ** -7)])
@pytest.mark.parametrize("case,S,M,d,n,spread,per_block,form", SUM_HELD_FORMS,
                         ids=[f"{c[0]}-{c[-1]}" for c in SUM_HELD_FORMS])
def test_sum_held_is_the_row_scatter_add_summed_in_float32(
        case, S, M, d, n, spread, per_block, form, dtype, tol):
    """`sum_held` (token-sorted rows summed by block one-hot products: the
    XLA text and the Mosaic kernel under the interpreter, each alone and
    under `shard_map` with rows that vary over tp) against the row scatter-add
    it replaced, run in float32: float32 rows to 1e-6 of the largest sum,
    bf16 rows to one rounding of the float32 sum (the scatter-add rounded
    at every row it added). NaN in every padding row; tokens that repeat
    (a token of several held experts); a block of tokens that owns more
    rows than a window holds takes as many windows as it needs, and the
    mover says how many it took."""
    from jax.sharding import PartitionSpec as P
    from distributed_pytorch_from_scratch_tpu.ops.collectives import copy_to
    from distributed_pytorch_from_scratch_tpu.ops.pallas import (
        sum_held as kernel)
    from distributed_pytorch_from_scratch_tpu.parallel import moe as moe_mod

    interpret = form.startswith("kernel")
    assert not interpret or kernel.fits(
        S, M, d, min(moe_mod.SUM_BLOCK, S), min(moe_mod.SUM_WINDOW, M))
    keys = jax.random.split(jax.random.key(3), 3)
    tok = jax.random.randint(keys[0], (M,), 0, spread)
    valid = (jnp.arange(M) < n)[:, None]
    y32 = jax.random.normal(keys[1], (S, d))
    r32 = jnp.where(valid, jax.random.normal(keys[2], (M, d)), jnp.nan)
    y, r = y32.astype(dtype), r32.astype(dtype)
    want = add_held(y.astype(jnp.float32), jnp.where(
        valid, r, 0).astype(jnp.float32), tok, valid)
    with jax.default_matmul_precision("highest"):
        if form.endswith("tp2"):
            mesh = mesh_of(2)

            def shard(y, r):
                rank = 1.0 + jax.lax.axis_index("tp")
                vary = lambda a: (copy_to(a, "tp") * rank).astype(a.dtype)
                got, took = moe_mod.sum_held(vary(y), vary(r), tok, valid,
                                             interpret=interpret)
                return (got / rank.astype(got.dtype))[None], took[None]
            got, took = jax.jit(jax.shard_map(
                shard, mesh=mesh, in_specs=P(),
                out_specs=(P("tp"), P("tp"))))(y, r)
            if dtype == "float32":      # bf16 rows times 2 round the same
                np.testing.assert_array_equal(got[0], got[1])
            got, took = got[0], took[0]
        else:
            got, took = jax.jit(lambda *a: moe_mod.sum_held(
                *a, interpret=interpret))(y, r, tok, valid)
    assert got.shape == y.shape and got.dtype == y.dtype
    assert np.all(np.isfinite(np.asarray(got, np.float32)))
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
    assert err <= tol * float(jnp.max(jnp.abs(want))), err
    blocks = -(-S // min(moe_mod.SUM_BLOCK, S))
    if per_block is not None:
        assert int(took) == per_block * blocks
    assert int(took) >= blocks
    if n == 0:
        np.testing.assert_array_equal(got, y)


@pytest.mark.parametrize("tp", [1, 2])
@pytest.mark.parametrize("dtype,tol", [("float32", 1e-6), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("case,E,H,k,forced,chunks", [
    ("a quarter held, four chunks of which the routing reaches some", 8, 2, 2,
     False, 4),
    ("an eighth held, six live chunks", 24, 3, 3, True, 6),
    ("a sixteenth held, six live chunks", 64, 4, 3, True, 6),
    ("chunks the routing does not reach", 64, 4, 3, False, 6),
])
def test_the_layer_equals_the_scatter_form_in_value_and_every_gradient(
        monkeypatch, case, E, H, k, forced, chunks, dtype, tol, tp):
    """`SharedRoutedFFN.apply` walking its chunks (one arm whatever share
    of the experts is held: the walk's text holds a `while` and no `cond`,
    and the step says what it walked), against itself with the plain row
    scatter-add in `sum_held`'s place, forward and in the walk's transpose
    (the form the layer had, kept HERE as the oracle): a scalar of the
    output and the gradient of every leaf and of the input, float32 to
    1e-6 of a leaf's largest entry, bfloat16 (whose scatter-add sums in
    bf16 where `sum_held` sums in float32) within the family tests'
    tolerance."""
    from jax.sharding import PartitionSpec as P
    from distributed_pytorch_from_scratch_tpu.parallel import moe as moe_mod

    d, f = 32, 16
    moe = SharedRoutedFFN(d, f, E, top_k=k, held=H, tp_size=tp)
    p = moe.init(jax.random.key(1))
    if forced:
        p["bias"] = jnp.where(jnp.arange(E) < k, 10.0, 0.0)
    x = jax.random.normal(jax.random.key(2), (4, 214, d))
    pairs = 4 * 214 * k
    M = moe.chunk_rows(pairs)
    assert -(-pairs // M) == chunks
    mesh = mesh_of(tp=tp)

    def value_and_grads():
        def loss(p, x):
            y, c = jax.shard_map(
                lambda p, x: moe.apply(p, x, jnp.dtype(dtype)), mesh=mesh,
                in_specs=(moe.specs(), P()), out_specs=(P(), P()))(p, x)
            return jnp.sum(jnp.sin(y.astype(jnp.float32))), c
        with jax.default_matmul_precision("highest"):
            step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1),
                                              has_aux=True))
            text = str(jax.make_jaxpr(step)(p, x))
            assert " while[" in text and " cond[" not in text
            return step(p, x)

    (got, c), got_g = value_and_grads()
    held = int(c["rows_here"])
    assert int(c["rows_walked"]) == M * -(-held // M) > 0
    assert (held == pairs) == bool(forced)
    assert float(c["sum_blocks"]) == -(-held // M) * -(-4 * 214
                                                       // moe_mod.SUM_BLOCK)
    monkeypatch.setattr(moe_mod, "sum_held", lambda y, r, tok, valid: (
        add_held(y, r, tok, valid), jnp.int32(0)))
    (want, _), want_g = value_and_grads()
    assert abs(float(got) - float(want)) <= tol * max(abs(float(want)), 1.0)
    assert len(hold_leaves(want_g, got_g, tol)[0]) == 9


def garbage_past_the_groups(seen, garbage=jnp.nan):
    """`lax.ragged_dot` as the chip runs it: a row no group holds comes
    back as `garbage` (NaN, or inf), from the product (`out`) and from its
    operand's cotangent (`d_rows`; the CPU lowering zero-fills both).
    `seen` gets (sum of the sizes, rows) of every product the forward pass
    runs."""
    real = jax.lax.ragged_dot

    def poison(rows, sizes):
        inside = jnp.arange(rows.shape[0]) < jnp.sum(sizes)
        return jnp.where(inside[:, None], rows, garbage)

    @jax.custom_vjp
    def ragged_dot(lhs, rhs, sizes):
        jax.debug.callback(
            lambda n, m=lhs.shape[0]: seen.append((int(n), m)),
            jnp.sum(sizes))
        return poison(real(lhs, rhs, sizes), sizes)

    def bwd(res, g):
        lhs, rhs, sizes = res
        d_lhs, d_rhs = jax.vjp(lambda l, r: real(l, r, sizes), lhs, rhs)[1](g)
        return poison(d_lhs, sizes), d_rhs, None

    ragged_dot.defvjp(
        lambda lhs, rhs, sizes: (ragged_dot(lhs, rhs, sizes),
                                 (lhs, rhs, sizes)), bwd)
    return ragged_dot


def _layer_value_and_grads(moe, p, x):
    def loss(p, x):
        y, c = apply_moe(moe, p, x)
        return jnp.sum(jnp.sin(y)), (y, c)
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1),
                                          has_aux=True))(p, x)


@functools.lru_cache(maxsize=None)
def _plain_run(E, H, k, S, forced):
    """(the layer, its parameters, its input, `_layer_value_and_grads` of
    them under jax's own `ragged_dot`)."""
    moe = SharedRoutedFFN(32, 16, E, top_k=k, held=H)
    p = moe.init(jax.random.key(1))
    if forced:
        p["bias"] = jnp.zeros(E).at[jnp.array(forced)].set(10.0)
    x = jax.random.normal(jax.random.key(2), (2, S // 2, 32))
    return moe, p, x, _layer_value_and_grads(moe, p, x)


@pytest.mark.parametrize("garbage", [jnp.nan, jnp.inf], ids=["nan", "inf"])
@pytest.mark.parametrize("case,E,H,k,S,forced,chunks,rows", [
    ("a quarter held, chunks the routing does not reach", 8, 2, 2, 600, (), 3,
     None),
    ("the walk, an eighth held, every chunk live", 24, 3, 3, 856,
     (0, 1, 2), 6, 2568),
    ("the walk, a sixteenth held, every chunk live", 64, 4, 3, 856,
     (0, 1, 2), 6, 2568),
    ("the walk, chunks the routing does not reach", 64, 4, 3, 856, (), 6,
     None),
    ("no held row, a quarter held", 8, 2, 2, 600, (4, 5), 3, 0),
    ("no held row, every chunk skipped", 64, 4, 3, 856, (8, 9, 10), 6, 0),
    ("the held rows end on the first chunk's last row, a quarter held", 8, 2,
     2, 512, (0, 4), 2, 512),
    ("the held rows end on a chunk's last row", 64, 4, 2, 1024, (0, 8), 4,
     1024),
    ("every pair held: one chunk of two rows a token", 4, 4, 2, 600, (), 1,
     1200),
    ("a chunk of three rows a token, blocks of two windows", 192, 24, 24,
     512, tuple(range(24)), 8, 12288),
])
def test_no_row_past_the_groups_is_read_anywhere(
        monkeypatch, case, E, H, k, S, forced, chunks, rows, garbage):
    """The groups the two products are handed cover the chunk's HELD rows
    and nothing more (their sizes are read where the products are called),
    and the layer reads no other row of what the products and their
    transposes return: with NaN (or inf) in every row past the groups ON
    BOTH SIDES, the products' `out` and their transposes' `d_rows`, as the
    chip may leave there (PR 33), the output and the gradient of every
    leaf and of the input are finite and are the plain run's. `sum_held`
    MULTIPLIES the rows it is handed (its one-hot products), so its
    select of the padding rows, which rides its row gather, is what
    stands between that garbage and the sums, forward (`y`) and in the
    walk's transpose (`d_x`)."""
    from distributed_pytorch_from_scratch_tpu.parallel import moe as moe_mod

    # (the plain run is one for a case's NaN and its inf)
    moe, p, x, ((_, (want, _)), want_g) = _plain_run(E, H, k, S, forced)
    M = moe.chunk_rows(S * k)
    assert -(-S * k // M) == chunks
    value_and_grads = lambda: _layer_value_and_grads(moe, p, x)
    seen = []
    monkeypatch.setattr(jax.lax, "ragged_dot",
                        garbage_past_the_groups(seen, garbage))
    with jax.default_matmul_precision("highest"):
        got, c = apply_moe(moe, p, x)
        jax.effects_barrier()
    forward = list(seen)
    (_, (again, _)), got_g = value_and_grads()

    held = float(c["rows_here"])
    assert held == float(c["rows_computed"])
    # the movers and the passes walked whole chunks up to the last held row
    live = -(-int(held) // M)
    assert float(c["rows_walked"]) == M * live
    if rows is not None:
        assert held == rows
    else:
        assert 0 < held < S * k and live < chunks
    # two products a live chunk, none for a chunk the walk stops before;
    # `sum_held` walked every token block of every live chunk, a window
    # each unless a block owns more rows than one holds
    blocks = live * -(-S // moe_mod.SUM_BLOCK)
    assert float(c["sum_blocks"]) == blocks
    assert float(c["sum_windows"]) == (2 if "two windows" in case
                                       else 1) * blocks
    assert len(forward) == 2 * live
    assert all(n <= m == M for n, m in forward)
    assert sum(n for n, _ in forward) == 2 * held
    np.testing.assert_array_equal(got, again)
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, want, atol=1e-6)
    # (a NaN or an inf in a leaf is an error past any tolerance)
    assert len(hold_leaves(want_g, got_g, 1e-6)[0]) == 9


@pytest.mark.parametrize("cell,E,H,k,chunk,chunks,parents", [
    ("joyai-llm-flash.train-ep16share-b4-t4096", 256, 16, 8, 8192, 16,
     "4b7872808d71b7c8"),
    ("qwen3-next-80b-a3b.train-ep16share-b2-t8192", 512, 32, 10, 10240, 16,
     "30a262a132862dc1"),
    ("lfm2-8b-a1b.train-ep4share-b2-t8192", 32, 8, 4, 16384, 4, None),
    ("sdar-30b-a3b.train-ep8share-b2-t4096", 128, 16, 8, 16384, 8,
     "c47b1a7127cde798"),
    ("trinity-mini.train-epshare-b2-t8192", 128, 16, 8, 16384, 8,
     "c47b1a7127cde798"),
    ("smallthinker-21b-a3b.train-ep4share-b1-t16384", 64, 16, 6, 24576, 4,
     None),
])
def test_the_gradient_s_text_scatters_no_row_at_any_cell_s_share(
        cell, E, H, k, chunk, chunks, parents):
    """The chunk rule at each expert cell's routing (16,384 tokens of 2048
    in bf16, the cell's experts, held share and k): a chunk is ONE mean
    share of the pairs whatever share of the experts is held (a quarter in
    cells 7 and 10, whose one chunk was ALL the pairs until PR 71), walked
    by a loop up to the last held row with no `cond` anywhere. The lowered
    gradient holds NO scatter at all: a chunk's rows come back by
    `sum_held`'s sort, gather and products (until PR 65 by one row
    scatter-add of the chunk's M rows, forward and in the walk's
    transpose); a scalar scatter is nowhere. Where under a sixth is held
    the text is what PR 71's PARENT lowered here (its digest, locations
    stripped: making the walk the one arm moved nothing in those cells);
    cells 7 and 10's is the walk's since."""
    import hashlib
    import re
    from jax.sharding import PartitionSpec as P
    from distributed_pytorch_from_scratch_tpu.parallel import moe as moe_mod

    d = 2048
    moe = SharedRoutedFFN(d, 128, E, top_k=k, held=H, n_shared=0)
    pairs = 16384 * k
    assert moe.chunk_rows(pairs) == chunk and chunk % 512 == 0
    assert -(-pairs // chunk) == chunks
    assert chunk == moe_mod.CHUNK_SHARES * pairs * H // E
    mesh = mesh_of()
    params = jax.eval_shape(moe.init, jax.random.key(0))
    x = jax.ShapeDtypeStruct((2, 8192, d), jnp.bfloat16)

    def loss(p, x):
        y, _ = jax.shard_map(
            lambda p, x: moe.apply(p, x, jnp.bfloat16), mesh=mesh,
            in_specs=(moe.specs(), P()), out_specs=(P(), P()))(p, x)
        return jnp.sum(y.astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(params, x).as_text()
    updates = [sig.split(", ")[-1] for sig in re.findall(
        r"stablehlo\.scatter.*?\}\) : \((.*?)\) ->", text, re.S)]
    assert updates == [] and "stablehlo.scatter" not in text
    # no `cond`: the chunks are a loop that STOPS at the last held row
    assert "stablehlo.case" not in text and "stablehlo.if" not in text
    assert "stablehlo.while" in text
    if parents:
        bare = re.sub(r"loc\(.*?\)|#loc.*|metadata=\{[^}]*\}", "", text)
        assert hashlib.sha256(bare.encode()).hexdigest()[:16] == parents


# ---- chunks of a share or less: the walk stops where the held rows do ----

def one_expert_at_a_time(moe, params, x):
    """The layer's routed part as a sum over the held experts, each a
    dense FFN over EVERY token times the token's weight for it (zero where
    it was not chosen): no sort, no group, no chunk (`scripts/
    tpu_checks.py` holds the layer to the same form on the chip)."""
    xf = x.reshape(-1, x.shape[-1])
    chosen, w = moe.route(params, xf)
    y = 0.0
    for e in range(moe.num_held):
        w_e = jnp.sum(jnp.where(chosen == moe.offset + e, w, 0), axis=-1)
        h = jax.nn.silu(xf @ params["gate"][e]) * (xf @ params["up"][e])
        y = y + w_e[:, None] * (h @ params["down"][e])
    return y.reshape(x.shape)


@pytest.mark.parametrize("case,E,H,forced,S,M,rows,live", [
    ("every token on two held experts: every chunk live", 64, 4, (0, 1), 1024,
     512, 2048, 4),
    ("no token on a held expert: no chunk live", 64, 4, (8, 9), 1024, 512, 0,
     0),
    ("the held rows end exactly on a chunk's edge", 64, 4, (0, 8), 1024, 512,
     1024, 2),
    ("one row past a chunk's edge", 64, 4, (0, 8), 1025, 512, 1025, 3),
    ("an untrained router: the chunks past the held rows skipped", 64, 4, (),
     1024, 512, None, None),
    ("a quarter held: the held rows end in the first chunk of a share", 8, 2,
     (0, 4), 500, 512, 500, 1),
    ("a quarter held: the held rows end in the second chunk", 8, 2, (0, 4),
     1000, 512, 1000, 2),
    ("a quarter held: the held rows end in the last chunk", 8, 2, (0, 1),
     1024, 512, 2048, 4),
    ("a quarter held: no held row", 8, 2, (4, 5), 1024, 512, 0, 0),
    ("a quarter held, an untrained router", 8, 2, (), 1024, 512, None, None),
    ("a half held: the held rows fill the first of two chunks", 8, 4, (0, 4),
     1024, 1024, 1024, 1),
    ("a half held: both chunks live", 8, 4, (0, 1), 1024, 1024, 2048, 2),
    ("every expert held: one chunk of all the pairs", 4, 4, (), 1024, 2048,
     2048, 1),
])
def test_fine_chunks_equal_one_expert_at_a_time_in_value_and_every_gradient(
        case, E, H, forced, S, M, rows, live):
    """Chunks of one mean share of the pairs walked up to the last held row,
    at every held share (a sixteenth, so chunks of 512 rows, the grain's
    floor at this size; a quarter and a half, whose one chunk was all the
    pairs until PR 71; every expert held, whose one chunk still is): the
    output and the gradient
    of every leaf and of the input are the dense sum's over the held
    experts whichever chunks are live, every pair that exists is computed
    (`rows_computed == rows_here`), and the movers and passes walked whole
    chunks up to the last held row and no further (`rows_walked == M *
    ceil(rows_here / M)`)."""
    d, f, k = 32, 16, 2
    moe = SharedRoutedFFN(d, f, E, top_k=k, held=H, n_shared=0)
    p = moe.init(jax.random.key(1))
    if forced:
        p["bias"] = jnp.zeros(E).at[jnp.array(forced)].set(10.0)
    x = jax.random.normal(jax.random.key(2), (1, S, d))
    assert M == moe.chunk_rows(S * k)
    assert (M == S * k) == (H == E)

    def value_and_grads(layer):
        def loss(p, x):
            y, c = layer(p, x)
            return jnp.sum(jnp.sin(y)), (y, c)
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(
                loss, argnums=(0, 1), has_aux=True))(p, x)

    (_, (got, c)), got_g = value_and_grads(lambda p, x: apply_moe(moe, p, x))
    (_, (want, _)), want_g = value_and_grads(
        lambda p, x: (one_expert_at_a_time(moe, p, x), None))
    held = int(c["rows_here"])
    assert held == int(c["rows_computed"])
    if rows is None:
        live = -(-held // M)
        assert 0 < live < -(-S * k // M)
    else:
        assert held == rows
    assert int(c["rows_walked"]) == M * live == M * -(-held // M)
    assert int(c["sum_blocks"]) == live * -(-S // 256)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert len(hold_leaves(want_g, got_g, 2e-5, floor=1.0)[0]) == 6


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("case,forced,per_block", [
    ("every token on all 24 held experts: three rows a token a chunk",
     tuple(range(24)), 2.0),
    ("every token on 12 held experts and 12 absent ones", tuple(range(12, 36)),
     2.0),
    ("an untrained router: whatever it routes", (), None),
])
def test_blocks_of_several_windows_equal_one_expert_at_a_time(
        case, forced, per_block, dtype, tol):
    """An eighth of 192 experts held and top-24, so a chunk is THREE rows a
    token: where the router sends every token to held experts a block of
    256 tokens owns 768 rows of a live chunk, more than `sum_held`'s window
    of 512, and takes two (`sum_windows` over `sum_blocks`); every pair
    that exists is still summed, forward and in the walk's transpose: the
    output and the gradient of every leaf and of the input are the float32
    dense sum's over the held experts, at the limits the cases above use
    (bf16 rows within the family tests' tolerance)."""
    d, f, E, H, k, S = 32, 16, 192, 24, 24, 512
    moe = SharedRoutedFFN(d, f, E, top_k=k, held=H, n_shared=0)
    p = moe.init(jax.random.key(1))
    if forced:
        p["bias"] = jnp.zeros(E).at[jnp.array(forced)].set(10.0)
    x = jax.random.normal(jax.random.key(2), (1, S, d))
    M = moe.chunk_rows(S * k)
    assert M == 1536 == 3 * S and M > 512

    def value_and_grads(layer):
        def loss(p, x):
            y, c = layer(p, x)
            return jnp.sum(jnp.sin(y.astype(jnp.float32))), (y, c)
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(
                loss, argnums=(0, 1), has_aux=True))(p, x)

    mesh = mesh_of()
    from jax.sharding import PartitionSpec as P
    mine = jax.jit(jax.shard_map(
        lambda p, x: moe.apply(p, x, jnp.dtype(dtype)), mesh=mesh,
        in_specs=(moe.specs(), P()), out_specs=(P(), P())))
    (_, (got, c)), got_g = value_and_grads(mine)
    (_, (want, _)), want_g = value_and_grads(
        lambda p, x: (one_expert_at_a_time(moe, p, x), None))
    held = int(c["rows_here"])
    assert held == int(c["rows_computed"])
    if forced:
        assert held == S * sum(e < H for e in forced)
    live = -(-held // M)
    assert int(c["rows_walked"]) == M * live
    assert float(c["sum_blocks"]) == live * S // 256
    assert float(c["sum_windows"]) == (per_block or 1.0) * float(
        c["sum_blocks"]) or (per_block is None and float(c["sum_windows"])
                             > float(c["sum_blocks"]) > 0)
    scale = max(float(jnp.max(jnp.abs(want))), 1.0)
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) \
        <= tol * scale
    assert len(hold_leaves(want_g, got_g, tol, floor=1.0)[0]) == 6


@pytest.mark.parametrize("dp,tp,E,H", [
    (2, 1, 64, 4), (1, 2, 64, 4), (2, 2, 64, 4),
    (2, 1, 8, 2), (1, 2, 8, 2), (2, 2, 8, 4), (1, 2, 4, 4), (2, 1, 4, 4)],
    ids=lambda v: str(v))
def test_the_walk_stops_at_each_data_shards_own_last_held_row(dp, tp, E, H):
    """Under a mesh the loop's length is a data shard's own (`rows_here`
    differs between them, so no collective may run inside it: the float
    operands are cast to one set of mesh axes before the walk and the
    sums over them happen once, outside): batch rows over dp, the experts'
    width over tp, against one expert at a time on one device, in value
    and every gradient; the shards' counters add up. At a sixteenth, a
    quarter and a half of the experts held, and with every expert held
    (one chunk of all a shard's pairs, which every shard walks)."""
    from jax.sharding import PartitionSpec as P
    d, f, k, S = 32, 16, 2, 1024
    moe = SharedRoutedFFN(d, f, E, top_k=k, held=H, n_shared=0, tp_size=tp)
    whole = SharedRoutedFFN(d, f, E, top_k=k, held=H, n_shared=0)
    p = whole.init(jax.random.key(1))
    # the first sequence's tokens favour a held expert, the second's none
    x = jax.random.normal(jax.random.key(2), (2, S, d))
    p["router"] = p["router"].at[:, 0].set(0.0)
    x = x.at[0, :, 0].set(3.0).at[1, :, 0].set(-3.0)
    p["router"] = p["router"].at[0, 0].set(4.0)
    mesh = mesh_of(tp, dp)

    def layer(p, x):
        def shard(p, x):
            y, c = moe.apply(p, x)
            return y, jax.tree.map(lambda a: jax.lax.psum(a, "dp"), c)
        return jax.shard_map(shard, mesh=mesh, in_specs=(moe.specs(), P("dp")),
                             out_specs=(P("dp"), P()))(p, x)

    def value_and_grads(fn):
        def loss(p, x):
            y, c = fn(p, x)
            return jnp.sum(jnp.sin(y)), (y, c)
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(loss, argnums=(0, 1),
                                              has_aux=True))(p, x)

    (_, (got, c)), got_g = value_and_grads(layer)
    (_, (want, _)), want_g = value_and_grads(
        lambda p, x: (one_expert_at_a_time(whole, p, x), None))
    per_shard = [int(jnp.sum(whole.route(p, xs.reshape(-1, d))[0] < H))
                 for xs in x.reshape(dp, -1, S, d)]
    if H < E:
        assert len(set(per_shard)) == dp        # the shards' loops differ
    M = moe.chunk_rows(2 * S * k // dp)
    assert M == max(512, 2 * S * k // dp * H // E)
    assert int(c["rows_here"]) == int(c["rows_computed"]) == sum(per_shard)
    assert int(c["rows_walked"]) == sum(M * -(-n // M) for n in per_shard)
    np.testing.assert_allclose(got, want, atol=2e-5)
    hold_leaves(want_g, got_g, 2e-5, floor=1.0)


@pytest.mark.parametrize("E,H,k,pairs,chunk", [
    (256, 16, 8, 131072, 8192), (512, 32, 10, 163840, 10240),
    (128, 16, 8, 131072, 16384), (64, 4, 3, 2568, 512), (24, 3, 3, 2568, 512),
    (7, 1, 2, 8192, 1536), (6, 1, 2, 8192, 1536), (32, 8, 4, 65536, 16384),
    (64, 16, 6, 98304, 24576), (8, 4, 2, 4096, 2048), (8, 8, 2, 4096, 4096),
    (4, 1, 2, 256, 256),
])
def test_a_chunk_is_a_mean_share_of_the_pairs_at_every_held_share(
        E, H, k, pairs, chunk):
    """The grain of the dispatch: ONE of the job's mean shares of the pairs
    up to the grouped kernel's 512-row tile, whatever share of the experts
    is held (the expert cells 5 to 13: a quarter held in cells 7 and 10, a
    sixteenth in cell 5; a half; the tests' tiny shapes at the tile's
    floor), walked by a loop that stops at the last held row; ALL the pairs
    in one chunk where every expert is held or the pairs are under a tile.
    The text has a `while` and no `cond` at every share."""
    moe = SharedRoutedFFN(32, 16, E, top_k=k, held=H, n_shared=0)
    assert moe.chunk_rows(pairs) == chunk
    assert moe.chunk_share == H / E
    assert (chunk == pairs) == (H == E or pairs <= 512)
    x = jax.ShapeDtypeStruct((1, pairs // k, 32), jnp.float32)
    text = str(jax.make_jaxpr(lambda p, x: apply_moe(moe, p, x))(
        jax.eval_shape(moe.init, jax.random.key(0)), x))
    assert " cond[" not in text and " while[" in text


# ---- the dispatch's index work: no scalar gather, no scalar scatter ----

def plain_route(moe, params, xf):
    """`SharedRoutedFFN.route` with the chosen scores taken by
    `take_along_axis`: the plain form, the oracle."""
    logits = jnp.dot(xf.astype(jnp.float32), params["router"],
                     precision=jax.lax.Precision.HIGHEST)
    if moe.score == "softmax":
        s = jax.nn.softmax(logits, axis=-1)
        _, chosen = jax.lax.top_k(s, moe.top_k)
    else:
        s = jax.nn.sigmoid(logits)
        _, chosen = jax.lax.top_k(
            s + jax.lax.stop_gradient(params["bias"]), moe.top_k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * moe.scaling
    return chosen, w


def plain_index(moe, chosen, w):
    """`SharedRoutedFFN.index` by `argsort`, `bincount`, `w[order]` and a
    second sort."""
    H = moe.num_held
    local = chosen - moe.offset
    key = jnp.where((local >= 0) & (local < H), local, H).reshape(-1)
    order = jnp.argsort(key, stable=True)
    ends = jnp.cumsum(jnp.bincount(key, length=H + 1)[:H])
    routed = jnp.bincount(chosen.reshape(-1), length=moe.num_experts)
    return order, w.reshape(-1)[order], ends, routed


INDEX_CASES = [
    # case, score, experts, held, offset, k, experts the bias forces
    ("cell 5's router: sigmoid + bias top-8 of 256, held 16",
     "sigmoid", 256, 16, 32, 8, None),
    ("cell 6's router: softmax top-10 of 512, held 32",
     "softmax", 512, 32, 480, 10, None),
    ("cell 7's router: sigmoid top-4 of 32, held 8",
     "sigmoid", 32, 8, 8, 4, None),
    ("cell 8's router: softmax top-8 of 128, held 16",
     "softmax", 128, 16, 0, 8, None),
    ("every token on one held expert", "sigmoid", 8, 2, 4, 2, (1, 5)),
    ("no pair held", "sigmoid", 8, 2, 4, 2, (0, 7)),
]


@pytest.mark.parametrize("case,score,E,H,offset,k,forced", INDEX_CASES,
                         ids=[c[0] for c in INDEX_CASES])
def test_the_index_work_equals_the_plain_gathers_and_counts(
        case, score, E, H, offset, k, forced):
    """`route` and `index` against `take_along_axis`, `bincount`,
    `argsort` and `w[order]`: every selection and every integer EXACTLY
    (`chosen`, `w`, `order`, `w_sorted`, `ends`, `routed`), and the
    cotangents of the router's weights and of the tokens through `w` and
    through `w_sorted`, and of the scores through the pick alone, to 1e-6
    of autodiff's of the plain forms, in float32."""
    from distributed_pytorch_from_scratch_tpu.parallel.moe import pick_scores

    S, d = 96, 16
    moe = SharedRoutedFFN(d, 8, E, top_k=k, held=H, offset=offset,
                          score=score, n_shared=0, scaling=2.5)
    p = moe.init(jax.random.key(3))
    if forced is not None:
        p["bias"] = jnp.zeros((E,)).at[jnp.array(forced)].set(10.0)
    xf = jax.random.normal(jax.random.key(4), (S, d))

    # op by op: a fused sigmoid rounds otherwise beside one form than
    # beside the other, by an ulp, and the pick is to be read alone
    chosen, w = moe.route(p, xf)
    want_chosen, want_w = plain_route(moe, p, xf)
    np.testing.assert_array_equal(chosen, want_chosen)
    np.testing.assert_array_equal(w, want_w)
    got = jax.jit(moe.index)(chosen, w)
    want = jax.jit(lambda c, w: plain_index(moe, c, w))(chosen, w)
    assert len(got) == len(want) == 4
    for name, a, b in zip(("order", "w_sorted", "ends", "routed"), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        np.testing.assert_array_equal(a, b, err_msg=name)
    held = int(got[2][-1])
    if forced is not None:
        assert sorted(np.unique(chosen).tolist()) == sorted(forced)
        assert held == (S if case.startswith("every token") else 0)
    else:
        assert 0 < held < S * k and int(np.max(got[3])) < S

    # cotangents through w (unsorted) and through w_sorted
    cw, cs = (jax.random.normal(kk, (S * k,))
              for kk in jax.random.split(jax.random.key(5)))

    def loss(route, index):
        def f(router, xf):
            chosen, w = route({**p, "router": router}, xf)
            w_sorted = index(chosen, w)[1]
            return jnp.sum(w.reshape(-1) * cw) + jnp.sum(jnp.sin(w_sorted) * cs)
        return jax.jit(jax.grad(f, argnums=(0, 1)))(p["router"], xf)

    got_g = loss(moe.route, moe.index)
    want_g = loss(lambda p, x: plain_route(moe, p, x),
                  lambda c, w: plain_index(moe, c, w))
    for a, b in zip(got_g, want_g):
        assert np.max(np.abs(a - b)) <= 1e-6 * max(np.max(np.abs(b)), 1e-6)
    assert np.max(np.abs(want_g[0])) > 0
    # the scores' cotangent through the pick alone
    s = jax.random.uniform(jax.random.key(6), (S, E))
    g = jax.random.normal(jax.random.key(7), (S, k))
    ds = jax.grad(lambda s: jnp.sum(pick_scores(s, chosen) * g))(s)
    want_ds = jax.grad(lambda s: jnp.sum(
        jnp.take_along_axis(s, chosen, axis=-1) * g))(s)
    np.testing.assert_array_equal(ds, want_ds)


@pytest.mark.parametrize("share,E,H,k", [("a quarter held", 8, 2, 2),
                                         ("a sixteenth held", 64, 4, 3)])
def test_no_scalar_gather_or_scatter_is_left_in_the_layer(share, E, H, k):
    """The jaxpr of the value-and-gradient of `apply` under
    `jax.checkpoint` (forward, recompute and backward), at a held share
    that kept one chunk of all the pairs until PR 71 and at one that has
    walked chunks of a share since PR 50: no `scatter` / `scatter-add`
    whose update is a scalar, no `gather` of one-element slices. What is
    left are the movers' row gathers (`x[tok]`, `sum_held`'s rows into
    token order, the sums' cotangent at a chunk's tokens); since PR 65
    `sum_held` brings a chunk's rows back by a sort, a row gather and
    products, and NO scatter of any kind is left at either share."""
    from jax.sharding import PartitionSpec as P

    d = 32
    moe = SharedRoutedFFN(d, 16, E, top_k=k, held=H)
    pairs = 4 * 214 * k
    assert -(-pairs // moe.chunk_rows(pairs)) >= 4
    p = moe.init(jax.random.key(1))
    x = jax.random.normal(jax.random.key(2), (4, 214, d))

    mesh = mesh_of()

    def layer(p, x):
        y, _ = jax.shard_map(
            jax.checkpoint(moe.apply), mesh=mesh,
            in_specs=(moe.specs(), P()), out_specs=(P(), P()))(p, x)
        return jnp.sum(jnp.sin(y))

    found = {"gather": [], "scatter": [], "scatter-add": []}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in found:
                found[eqn.primitive.name].append(eqn)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(jax.value_and_grad(layer, argnums=(0, 1)))(p, x).jaxpr)
    for eqn in found["gather"]:
        assert eqn.params["slice_sizes"] == (1, d), eqn
    for eqn in found["scatter"] + found["scatter-add"]:
        assert eqn.invars[2].aval.shape[-1] == d, eqn
    assert not found["scatter"] and not found["scatter-add"]
    assert len(found["gather"]) >= 3


# ---- the step, its counters, the entry point ----

def test_the_train_step_returns_counters_when_asked_and_the_loss_falls():
    cfg = tiny()
    losses, (_, gnorm, c), (mesh, model, params, opt, (ids, tgt, pos)) = (
        R.train(cfg))
    assert losses[-1] < losses[0] and np.isfinite(float(gnorm))
    assert c["routed"].shape == (3, 8) and c["rows_here"].shape == (3,)
    # every token takes top_k experts in each of the 2 + 1 expert layers
    np.testing.assert_array_equal(c["routed"].sum(-1), [2 * 64 * 2] * 3)
    assert abs(float(c["loss_main"] + 0.3 * c["loss_mtp"]) - losses[-1]) \
        < 1e-5
    summary = moe_counters_summary(jax.device_get(c), cfg, 2 * 64)
    assert summary["rows_here_per_token"] == 2.0    # all experts held
    assert summary["rows_computed_per_token"] == 2.0
    assert summary["rows_walked_per_token"] == 2.0  # one chunk of all pairs
    # ... of two rows a token: a block of 128 tokens owns 256 of them, in
    # one window or two by where its first row falls among the lanes
    assert 1.0 <= summary["sum_windows_per_block"] <= 2.0
    assert summary["load_max_over_mean"] >= 1.0
    # off by default: the step's output is what it has always been
    plain = build_train_step(model, mesh, OptimizerConfig(),
                             with_grad_norm=True)
    assert len(plain(params, opt, ids, tgt, pos)[2]) == 2


def test_train_cli_runs_the_family(tmp_path, capsys):
    from distributed_pytorch_from_scratch_tpu import train as train_mod
    tokens = token_file(tmp_path)
    train_mod.main([
        "--family", "mla_moe", "--model", "tiny-mla-moe", "--tp_size", "2",
        "--data_path", str(tokens), "--save_dir", str(tmp_path / "ckpt"),
        "--batch_size", "4", "--maxlen", "64", "--max_steps", "4",
        "--log_interval", "2", "--save_interval", "100",
        "--warmup_steps", "2"])
    out = capsys.readouterr().out
    assert "model[mla_moe]" in out and "rows_here_per_token" in out
    assert "rows_computed_per_token" in out
    assert "rows_walked_per_token" in out and "sum_windows_per_block" in out
    events = [json.loads(line) for line in
              open(tmp_path / "ckpt" / "logs" / "metrics.jsonl")]
    assert any(e.get("tag") == "moe_counters" for e in events)
    with pytest.raises(SystemExit, match="reads the config field"):
        train_mod.main(["--family", "llama", "--model", "tiny-mla-moe",
                        "--data_path", str(tokens),
                        "--save_dir", str(tmp_path / "x")])


# ---- what is refused ----

@pytest.mark.parametrize("kw,message", [
    (dict(pp_size=2), "pp_size > 1"),
    (dict(cp_size=2), "cp_size > 1"),
    (dict(ep_size=2), "ep_size > 1"),
    (dict(sequence_parallel=True), "sequence_parallel=True"),
    (dict(tp_size=2, tp_overlap="ring"), "does not compose with MoE"),
    (dict(attn_t_real=32), "attn_t_real"),
    (dict(zero3_axis="dp"), "ZeRO stage 3"),
])
def test_the_model_refuses_what_it_does_not_run(kw, message):
    with pytest.raises(ValueError, match=message):
        build_model("mla_moe", tiny(), **kw)


def test_a_family_needs_its_own_facts():
    with pytest.raises(ValueError, match="needs cfg.latent_moe"):
        build_model("mla_moe", ModelConfig(num_experts=8))


# ---- the counts at the published widths ----

def published(held=16, vocab=16160, layers=5):
    return ModelConfig(
        attn_dim=2048, ffn_dim=7168, num_heads=32, num_layers=layers,
        vocab_size=vocab, maxlen=4096, rope_theta=3.2e7, num_experts=256,
        moe_top_k=8, latent_moe=LatentMoEConfig(
            q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
            qk_rope_head_dim=64, v_head_dim=128, moe_intermediate_size=768,
            routed_scaling_factor=2.5, experts_held=held,
            num_nextn_predict_layers=1))


def test_parameter_counts_at_the_published_widths():
    """One chip's share (16 of 256 experts, an eighth of the vocabulary, 1
    + 4 layers, the module): 680.4M, as `init` makes them."""
    cfg = published()
    parts = LatentMoETransformer.param_counts(cfg)
    assert round(parts["dense_layers"] / 1e6, 1) == 70.4
    assert round(parts["expert_layers"] / 4e6, 1) == 107.1
    assert round(parts["mtp"] / 1e6, 1) == 115.5
    assert round(parts["embedding_and_head"] / 1e6, 1) == 66.2
    assert cfg.num_params() == 680_441_088          # 680.4M
    model = build_model("mla_moe", cfg)
    made = jax.eval_shape(model.init, jax.random.key(0))
    assert sum(x.size for x in jax.tree.leaves(made)) == cfg.num_params()
    # uncut, an expert layer is 1,239.6M
    uncut = LatentMoETransformer.param_counts(published(held=None))
    assert round(uncut["expert_layers"] / 4e6, 1) == 1239.6
    # the step's FLOPs count the held experts at a token's mean share of
    # them (8 x 16/256 = 0.5 an expert layer), not all sixteen
    flops = model_flops_per_step(cfg, 4, 4096, cfg.num_params())
    assert 3.2e9 < flops / (4 * 4096) < 3.7e9
