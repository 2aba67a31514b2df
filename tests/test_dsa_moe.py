"""The `dsa_moe` family (models/dsa_moe.py): a grouped-query expert decoder
whose every layer CHOOSES its keys (an indexer scores each earlier token, a
top-k keeps them, attention runs over the set) and trains its indexer on a
loss of its own. CPU, tiny sizes, float32, top-k smaller than the sequence
so the choice is live, some rows with fewer keys than the budget.

* the program against the plain reference (models/vanilla_dsa_moe.py, whose
  set is a boolean matrix from `lax.top_k`): loss and EVERY gradient leaf,
  on the XLA text and on the kernels under the interpreter, on a job that
  holds a slice of the experts;
* the choice itself: what the program chose (`make_probe`) IS the
  reference's set on float32, pair for pair, and its scores the reference's;
* the two stop-gradients, exactly: the attention's output has a ZERO
  gradient at every index tensor and indexer leaf, the indexer's loss a
  ZERO gradient at q, k, v, the layer's input and every other leaf;
* the kernels (the interpreter) against the XLA text at several shapes:
  several tiles each way, groups of 1 to 3, two sequences, ties at the
  threshold (the ReLU's zeros) that the budget cuts by index; the set the
  selection WRITES, a bit a pair, is the text's for every pair, at one
  plane of words, at two and at a second plane part full, and the walk's
  instant says what was built at the cell's shape;
* with top-k >= T the layer is cell 8's attention under `CAUSAL`;
* the eight shares of the expert layer add up to the uncut reference's;
* what the family does not run is refused with a message;
* the step trains, counts what it kept, and the CLI logs it;
* the counts at the published widths (659,190,016 in the cut).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from family_recipe import (Recipe, hold_leaves, hold_loss, mesh_of, token_file)
from jax.sharding import PartitionSpec as P

from distributed_pytorch_from_scratch_tpu.config import (
    DsaMoEConfig, ModelConfig, OptimizerConfig, model_preset)
from distributed_pytorch_from_scratch_tpu.models import build_model
from distributed_pytorch_from_scratch_tpu.models import vanilla_dsa_moe
from distributed_pytorch_from_scratch_tpu.models.dsa_moe import (
    SelectedAttentionMoETransformer, attention_of, kept_pairs)
from distributed_pytorch_from_scratch_tpu.models.vanilla_dsa_moe import (
    sizes_of, vanilla_parts)
from distributed_pytorch_from_scratch_tpu.ops import index_select
from distributed_pytorch_from_scratch_tpu.ops.attention import (
    causal_attention_xla, repeat_kv)
from distributed_pytorch_from_scratch_tpu.ops.pallas import dsa_attention
from distributed_pytorch_from_scratch_tpu.ops.rope import rope_angles
from distributed_pytorch_from_scratch_tpu.parallel.moe import SharedRoutedFFN
from distributed_pytorch_from_scratch_tpu.training.metrics import (
    dsa_counters_summary, model_flops_per_step, moe_counters_summary)
from distributed_pytorch_from_scratch_tpu.training.optim import (
    init_adam_state)
from distributed_pytorch_from_scratch_tpu.training.train_step import (
    build_train_step)

IMPLS = ("xla", "flash_interpret")


# the family's own: its reference's loss comes with its parts (the CE and a
# layer's KL), and the program's side of them is its counters
R = Recipe("dsa_moe", vanilla_parts)
tiny, batch, on_mesh = R.tiny, R.batch, R.on_mesh


@pytest.fixture
def small_blocks(monkeypatch):
    """Several tiles each way at the tests' 64 rows."""
    monkeypatch.setattr(dsa_attention, "BLOCK_Q", 16)
    monkeypatch.setattr(dsa_attention, "BLOCK_K", 32)
    monkeypatch.setattr(dsa_attention, "COUNT_CHUNK", 32)


# ---- the program against the plain reference ----

@pytest.mark.parametrize("impl", IMPLS)
def test_loss_and_every_gradient_leaf_equal_the_reference(impl, small_blocks):
    """On a job that holds experts 2..5 of 8. Leaves to 1e-5 of their
    largest entry; the loss is the CE and both layers' KL."""
    cfg = tiny(experts_held=4, expert_offset=2)
    # (the parameters and the reference are one for both; the program is
    # built under this case's `small_blocks`, so it is not kept)
    params, ((want, parts), want_g) = R.reference(cfg, has_aux=True)
    (got, c), got_g = R.program(cfg, with_counters=True, cached=False,
                                attn_impl=impl)
    hold_loss(want, got)
    assert float(want) == pytest.approx(
        float(parts["ce"]) + float(parts["index_kl"].sum()), rel=1e-6)
    assert float(c["loss_main"]) == pytest.approx(float(parts["ce"]),
                                                  rel=1e-5)
    np.testing.assert_allclose(c["dsa_index_kl"] / c["dsa_rows"],
                               parts["index_kl"], rtol=1e-4)
    assert float(parts["index_kl"].min()) > 0.01        # the term is live
    names, moved = hold_leaves(want_g, got_g, 1e-5)
    assert len(names) == 20 and moved == names
    # a row keeps 16 of up to 64 keys; 15 rows a sequence see fewer
    np.testing.assert_array_equal(c["dsa_kept"], [2 * kept_pairs(64, 16)] * 2)
    assert kept_pairs(64, 16) == 136 + 48 * 16 < 64 * 65 // 2
    attn = params["layers"]["attn"]
    assert attn["wq"].shape == (2, 64, 128) and attn["wk"].shape == (2, 64, 64)
    assert attn["indexer"]["wq"].shape == (2, 64, 32)
    assert attn["indexer"]["wk"].shape == (2, 64, 16)
    assert attn["indexer"]["w_proj"].shape == (2, 64, 2)
    assert set(attn["indexer"]["k_norm"]) == {"scale", "bias"}
    assert "wo" not in params["layers"] and "wo" in attn


@pytest.mark.parametrize("impl", IMPLS)
def test_the_program_chooses_the_references_keys(impl, small_blocks):
    """What every layer chose, read out of the implementation the step
    runs, against the reference's `lax.top_k`: every pair, and the scores
    of a sequence's last rows; handed that choice the reference says it is
    its own."""
    cfg = tiny()
    mesh, model = on_mesh(cfg, attn_impl=impl)
    params = model.init(jax.random.key(5))
    ids, tgt, pos = batch(cfg, seed=1)
    rows, chosen = model.make_probe(mesh)(params, ids, pos)
    assert rows.shape == chosen.shape == (2, 2, 64, 64)
    assert chosen.dtype == jnp.int8
    with jax.default_matmul_precision("highest"):
        _, parts = jax.jit(lambda pr, given: vanilla_parts(
            cfg, pr, ids, tgt, pos, given))(params, chosen)
    own, given, both, tied = np.asarray(parts["pairs"]).T
    np.testing.assert_array_equal(own, [2 * kept_pairs(64, 16)] * 2)
    # the rows the tie rule decided are the program's count of them
    loss = model.make_loss(mesh, with_counters=True)
    np.testing.assert_array_equal(
        loss(params, ids, tgt, pos)[1]["dsa_tau_ties"], tied)
    assert tied.min() > 0
    np.testing.assert_array_equal(given, own)
    np.testing.assert_array_equal(both, own)
    causal = np.tril(np.ones((64, 64), bool))
    np.testing.assert_allclose(np.asarray(rows) * causal,
                               np.asarray(parts["score_rows"]) * causal,
                               atol=2e-6)
    # every row keeps itself at most its budget, and nothing after itself
    kept = np.asarray(chosen).astype(bool)
    assert not (kept & ~causal).any()
    np.testing.assert_array_equal(
        kept.sum(-1)[0, 0], np.minimum(np.arange(64) + 1, 16))


# ---- the two stop-gradients ----

def attention_inputs(cfg, b=2, t=64, seed=0):
    attn = attention_of(cfg)
    keys = jax.random.split(jax.random.key(seed), 2)
    params = attn.init(keys[0])
    x = jax.random.normal(keys[1], (b, t, cfg.attn_dim))
    ids = jnp.tile(jnp.arange(t), (b, 1))
    pos = (*rope_angles(ids, attn.head_dim, cfg.rope_theta),
           *rope_angles(ids, attn.indexer.head_dim, cfg.rope_theta))
    return attn, params, x, pos


@pytest.mark.parametrize("impl", IMPLS)
def test_each_loss_reaches_its_own_leaves_and_no_other_exactly(
        impl, small_blocks):
    cfg = tiny()
    attn, params, x, pos = attention_inputs(cfg)
    apply = lambda p, x: attn.apply(p, x, pos, jnp.float32, impl=impl)
    ce_side = jax.jit(jax.grad(
        lambda p, x: jnp.sum(jnp.sin(apply(p, x)[0])), argnums=(0, 1)))
    kl_side = jax.jit(jax.grad(
        lambda p, x: apply(p, x)[1]["dsa_index_kl"], argnums=(0, 1)))
    from_y, _ = ce_side(params, x)
    from_kl, into_x = kl_side(params, x)
    index_y, index_kl = from_y.pop("indexer"), from_kl.pop("indexer")
    for leaf in jax.tree.leaves(index_y):
        assert not np.any(np.asarray(leaf))         # exactly zero
    for leaf in jax.tree.leaves(from_kl) + [into_x]:
        assert not np.any(np.asarray(leaf))
    for leaf in jax.tree.leaves(from_y) + jax.tree.leaves(index_kl):
        assert np.any(np.asarray(leaf))


# ---- the kernels against the text ----

def unpacked(bits, bk):
    """(b, t, t) bool of `bits` (b, planes, t, bk) int32: the pair (row, s)
    is bit `(s // bk) mod 32` of word `[s // (32 bk), row, s mod bk]`
    (ops/pallas/dsa_attention.py's docstring, written out in numpy)."""
    bits = np.asarray(bits)
    t = bits.shape[2]
    rows, cols = np.arange(t)[:, None], np.arange(t)[None, :]
    words = bits[:, cols // (32 * bk), rows, cols % bk]
    return ((words >> ((cols // bk) % 32)) & 1).astype(bool)


@pytest.mark.parametrize("b,H,Hkv,t,J,top_k,bq,bk", [
    (2, 4, 2, 64, 2, 8, 16, 32),        # ties: two index heads' zeros
    (1, 6, 2, 96, 3, 40, 32, 32),       # a group of 3, square tiles
    (1, 2, 2, 64, 4, 64, 64, 16),       # top-k = T: nothing is dropped
    (1, 4, 1, 128, 1, 5, 8, 128),       # one index head, one key tile
    (1, 2, 1, 512, 2, 24, 32, 8),       # 64 key tiles: two planes of bits
    (1, 2, 2, 384, 1, 40, 64, 8),       # 48: the second plane part full
    (1, 8, 1, 256, 2, 8, 128, 64),      # the cell's group and query block
])
def test_the_kernels_equal_the_text(b, H, Hkv, t, J, top_k, bq, bk,
                                    monkeypatch):
    monkeypatch.setattr(dsa_attention, "COUNT_CHUNK", 32)
    h, c = 16, 8
    ks = jax.random.split(jax.random.key(t + J), 6)
    args = (jax.random.normal(ks[0], (b, H, t, h)),
            jax.random.normal(ks[1], (b, Hkv, t, h)),
            jax.random.normal(ks[2], (b, Hkv, t, h)),
            jax.random.normal(ks[3], (b, J, t, c)),
            jax.random.normal(ks[4], (b, t, c)),
            jax.random.normal(ks[5], (b, t, J)) * 0.3)
    lanes = jnp.cos(jnp.arange(h))

    def both(impl):
        def value(*a):
            o, s = (index_select.selected_attention_xla(*a, top_k)
                    if impl == "xla" else index_select._selected_flash(
                        *a, top_k, bq, bk, True))
            return jnp.sum(o * lanes) + 0.7 * s["dsa_index_kl"], (o, s)
        return jax.jit(jax.value_and_grad(value, argnums=tuple(range(6)),
                                          has_aux=True))(*args)

    (_, (o_x, s_x)), g_x = both("xla")
    (_, (o_k, s_k)), g_k = both("kernels")
    np.testing.assert_allclose(o_k, o_x, atol=3e-6)
    for name in index_select.SUMS:
        np.testing.assert_allclose(s_k[name], s_x[name], rtol=2e-6,
                                   err_msg=name)
    assert float(s_x["dsa_kept"]) == b * kept_pairs(t, top_k)
    if J <= 2 and top_k < t:        # the tie rule decided some rows
        assert float(s_x["dsa_tau_ties"]) > 0
    for a, k in zip(g_x, g_k):
        np.testing.assert_allclose(k, a, atol=1e-5 * max(
            float(jnp.abs(a).max()), 1e-3))
    # the set the selection writes, a bit a pair, IS the text's, every
    # pair; it is what the loss walk's rule makes of the kernels' own score
    # under the kernel's (tau, cut), and what the walks read out of it; and
    # the two numbers the selection takes over the set are the text's
    q_idx, k_idx, w = args[3:]
    blocks = dict(bq=bq, bk=bk, interpret=True)
    w4 = index_select._rows_last(w)
    tau, cut, _, bits, lse_i, kept = dsa_attention.select_call(
        q_idx, k_idx, w4, top_k, **blocks)
    planes = dsa_attention.bit_planes(t, bk)
    assert planes == -(-(t // bk) // 32) and bits.shape == (b, planes, t, bk)
    score = index_select.index_scores(q_idx, k_idx, w)
    keep = index_select.live(score, *index_select.select(score, top_k)[:2])
    np.testing.assert_array_equal(unpacked(bits, bk), keep)
    made, read = dsa_attention.probe_call(q_idx, k_idx, w4, bits, **blocks)
    np.testing.assert_array_equal(read.astype(bool), keep)
    np.testing.assert_array_equal(
        index_select.live(made, tau[..., 0], cut[..., 0]), keep)
    np.testing.assert_array_equal(kept[..., 0], keep.sum(-1))
    np.testing.assert_allclose(
        lse_i[..., 0], jax.nn.logsumexp(jnp.where(keep, score, -jnp.inf),
                                        axis=-1), rtol=2e-6)
    # the forward walk's lse is the text's logsumexp over each row's set,
    # and leaves the kernel lane-dense, a row of the sequence a head
    q, k, v = args[:3]
    _, lse = dsa_attention.fwd_call(q, k, v, bits, **blocks)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, repeat_kv(q, k, v)[0],
                        precision="highest") / np.sqrt(h)
    assert lse.shape == (b, H, t)
    np.testing.assert_allclose(lse, jax.nn.logsumexp(
        jnp.where(keep[:, None], logits, -jnp.inf), axis=-1), rtol=2e-6)
    if bq == 128:
        # some rows hold NO key of the first tile they cross: their running
        # maximum stays at the mask's value there (the walk's exact zeros)
        assert not keep[..., :bk].any(-1).all()


def test_equal_scores_are_cut_by_index_and_counted():
    """A whole row of EQUAL scores (an indexer that outputs zeros): the
    budget keeps the earliest keys, in the text and in the kernels, and
    every row past the budget counts as tied."""
    b, H, t, h, J, c, top_k = 1, 2, 32, 8, 2, 4, 5
    q = jax.random.normal(jax.random.key(0), (b, H, t, h))
    zeros = (jnp.zeros((b, J, t, c)), jnp.zeros((b, t, c)),
             jnp.zeros((b, t, J)))
    rows, chosen = index_select.selection_probe(*zeros, top_k, impl="xla")
    want = np.tril(np.ones((t, t), np.int8)) * (np.arange(t)[None] < top_k)
    np.testing.assert_array_equal(chosen[0], want)
    _, s_x = index_select.selected_attention_xla(q, q, q, *zeros, top_k)
    _, s_k = index_select._selected_flash(q, q, q, *zeros, top_k, 8, 16,
                                          True)
    # the bits of a row whose ties straddle the budget: the earlier keys
    _, _, _, bits, _, _ = dsa_attention.select_call(
        zeros[0], zeros[1], index_select._rows_last(zeros[2]), top_k, bq=8,
        bk=16, interpret=True)
    np.testing.assert_array_equal(unpacked(bits, 16)[0], want.astype(bool))
    for s in (s_x, s_k):
        assert float(s["dsa_tau_ties"]) == t - top_k
        assert float(s["dsa_kept"]) == kept_pairs(t, top_k)
        # a uniform softmax over the set: its entropy is the log of its size
        sizes = np.minimum(np.arange(t) + 1, top_k)
        assert float(s["dsa_index_entropy"]) == pytest.approx(
            np.log(sizes).sum(), rel=1e-5)


def test_the_walk_says_on_the_programs_tracer_what_it_built(tmp_path):
    """At trace time `_selected_flash_fwd` leaves ONE instant `dsa_walk`
    (as `flash_attention._bwd_call`'s `flash_bwd_walk`): the mask the
    walks read, its planes and bytes a layer, and how many kernels of a
    layer still make the index tile, and which way up the forward walk's
    score tile is. At the cell's shape: one plane, 32 MiB, the selection and
    the loss walk, a head's 512 keys down the sublanes by 128 rows along
    the lanes."""
    import json

    from distributed_pytorch_from_scratch_tpu.obs.trace import SpanTracer
    b, H, Hkv, t, h, J, c = 1, 32, 4, 16384, 128, 16, 64
    arg = lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype)
    tracer = SpanTracer(str(tmp_path))
    try:
        o, sums = jax.eval_shape(
            lambda *a: index_select.selected_attention(
                *a, 2048, impl="flash_interpret"),
            arg(b, H, t, h), arg(b, Hkv, t, h), arg(b, Hkv, t, h),
            arg(b, J, t, c), arg(b, t, c), arg(b, t, J, dtype=jnp.float32))
    finally:
        tracer.close()
    assert o.shape == (b, H, t, h) and set(sums) == set(index_select.SUMS)
    events = [json.loads(line)["args"] for line in
              open(tmp_path / "trace.jsonl")
              if json.loads(line)["name"] == "dsa_walk"]
    assert events == [{"mask": "bits", "planes": 1,
                       "bits_bytes": 33_554_432, "index_tiles_a_layer": 2,
                       "blocks": [128, 512], "t": 16384,
                       "fwd_tile": "keys_by_rows",
                       "fwd_tile_shape": [512, 128]}]


# ---- nothing dropped is the causal layer ----

@pytest.mark.parametrize("impl", IMPLS)
def test_a_budget_of_the_whole_sequence_is_cell_8s_causal_attention(impl):
    cfg = tiny(topk=64)
    attn, params, x, pos = attention_inputs(cfg, seed=2)
    y, sums = jax.jit(lambda p, x: attn.apply(
        p, x, pos, jnp.float32, impl=impl))(params, x)
    q, k, v = attn.qkv(params, x, pos[0], pos[1], jnp.float32)
    want = attn.project(params, causal_attention_xla(q, k, v), jnp.float32)
    np.testing.assert_allclose(y, want, atol=2e-6)
    assert float(sums["dsa_kept"]) == float(sums["dsa_causal"]) \
        == 2 * 64 * 65 // 2
    assert float(sums["dsa_tau_ties"]) == 0


# ---- the share test ----

def test_the_eight_shares_of_a_layer_add_up_to_the_uncut_references_layer():
    """Eight jobs hold four experts each of one layer's 32. Their routed
    parts are what the REFERENCE's expert layer computes holding all 32:
    the softmax weights are normalised over all chosen experts, held or
    not."""
    d, f, E, k = 32, 16, 32, 4
    kw = dict(n_shared=0, score="softmax")
    p = SharedRoutedFFN(d, f, E, k, **kw).init(jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2, 64, d))
    mesh = mesh_of()
    sizes = type("Sizes", (), {"top_k": k})         # (all that is read)
    with jax.default_matmul_precision("highest"):
        want, routed = vanilla_dsa_moe._expert_ffn(p, x, sizes, 0)
        parts = []
        for share in range(8):
            lo = 4 * share
            held = SharedRoutedFFN(d, f, E, k, held=4, offset=lo, **kw)
            ps = {**p, **{n: p[n][lo:lo + 4] for n in ("gate", "up", "down")}}
            out, c = jax.jit(jax.shard_map(
                lambda pr, x: held.apply(pr, x), mesh=mesh,
                in_specs=(held.specs(), P()), out_specs=(P(), P())))(ps, x)
            np.testing.assert_array_equal(c["routed"], routed)
            parts.append(out)
    np.testing.assert_allclose(sum(parts), want, atol=1e-5)


# ---- what the family does not run ----

@pytest.mark.parametrize("kw,message", [
    (dict(tp_size=2), "tp_size > 1"),
    (dict(pp_size=2), "pp_size > 1"),
    (dict(cp_size=2), "cp_size > 1"),
    (dict(ep_size=2), "ep_size > 1"),
    (dict(sequence_parallel=True), "sequence_parallel=True"),
    (dict(attn_t_real=100), "attn_t_real"),
    (dict(zero3_axis="dp"), "ZeRO stage 3"),
])
def test_what_the_family_does_not_run_is_refused_where_it_is_built(
        kw, message):
    with pytest.raises(ValueError, match=message):
        build_model("dsa_moe", tiny(), **kw)


def test_decode_and_the_zero_builders_refuse_the_family():
    from distributed_pytorch_from_scratch_tpu.models.decode import (
        GreedyDecoder)
    mesh, model = on_mesh(tiny())
    assert not model.decodable and not model.hand_reduced_grads
    with pytest.raises(ValueError, match="cannot be decoded or served"):
        GreedyDecoder(model, mesh, 32)
    with pytest.raises(ValueError, match="ZeRO stage 2 is not made to work"):
        build_train_step(model, mesh, OptimizerConfig(), zero=2)
    with pytest.raises(ValueError, match="needs cfg.dsa_moe"):
        build_model("dsa_moe", model_preset("tiny"))
    with pytest.raises(ValueError, match="keeps itself"):
        build_model("dsa_moe", tiny(topk=0)).init(jax.random.key(0))


# ---- the step: counters, the CLI ----

def test_the_train_step_trains_both_losses_and_counts_what_it_kept():
    cfg = tiny(experts_held=4, expert_offset=2)
    mesh, model = on_mesh(cfg, attn_impl="xla")
    params = model.init(jax.random.key(0))
    opt = init_adam_state(params)
    step = build_train_step(model, mesh, OptimizerConfig(lr=3e-3,
                                                         warmup_steps=2),
                            with_grad_norm=True, with_counters=True)
    ids, tgt, pos = batch(cfg, b=4)
    fresh = jax.tree.map(np.asarray, params["layers"]["attn"]["indexer"])
    main, kl = [], []
    for _ in range(8):
        params, opt, (loss, norm, c) = step(params, opt, ids, tgt, pos)
        main.append(float(c["loss_main"]))
        kl.append(float(np.sum(c["dsa_index_kl"] / c["dsa_rows"])))
        assert float(loss) == pytest.approx(main[-1] + kl[-1], rel=1e-5)
    assert np.isfinite(main).all() and main[-1] < main[0]
    # the indexer trains (on its own loss alone: the test above), after a
    # target that moves as the heads learn
    assert np.isfinite(kl).all() and min(kl) > 0
    for was, now in zip(jax.tree.leaves(fresh), jax.tree.leaves(
            params["layers"]["attn"]["indexer"])):
        assert np.any(np.asarray(was) != np.asarray(now))
    said = dsa_counters_summary(c)
    assert said["kept_share"] == pytest.approx(
        kept_pairs(64, 16) / (64 * 65 // 2))
    assert said["index_kl"] == pytest.approx(kl[-1], rel=1e-5)
    assert 0 < said["index_entropy"] <= np.log(16)
    assert 0 <= said["tau_ties"] < 1
    assert c["routed"].shape == (2, 8)
    assert moe_counters_summary(c, cfg, 4 * 64)["rows_here_per_token"] > 0
    # the mathematics' count: kept pairs and the indexer's triangle
    n = model.num_params(cfg)
    dense = model_flops_per_step(
        tiny(experts_held=4, expert_offset=2, topk=64), 4, 64, n)
    assert 0 < model_flops_per_step(cfg, 4, 64, n) < dense


def test_train_cli_runs_the_family(tmp_path, capsys):
    import json
    from distributed_pytorch_from_scratch_tpu import train as train_mod
    tokens = token_file(tmp_path)
    train_mod.main([
        "--family", "dsa_moe", "--model", "tiny-dsa-moe",
        "--data_path", str(tokens), "--save_dir", str(tmp_path / "ckpt"),
        "--batch_size", "4", "--maxlen", "64", "--max_steps", "4",
        "--log_interval", "2", "--save_interval", "100",
        "--warmup_steps", "2"])
    out = capsys.readouterr().out
    assert "model[dsa_moe]" in out and "kept_share" in out
    assert "index_kl" in out and "rows_here_per_token" in out
    events = [json.loads(line) for line in
              open(tmp_path / "ckpt" / "logs" / "metrics.jsonl")]
    assert any(e.get("tag") == "moe_counters" for e in events)
    dsa = [e for e in events if e.get("tag") == "dsa_counters"]
    assert dsa and 0.0 < dsa[-1]["kept_share"] < 1.0
    assert dsa[-1]["index_kl"] > 0 and "tau_ties" in dsa[-1]
    with pytest.raises(SystemExit, match="reads the config field"):
        train_mod.main(["--family", "dsa_moe", "--model", "tiny-bd-moe",
                        "--data_path", str(tokens),
                        "--save_dir", str(tmp_path / "x")])


# ---- the counts at the published widths ----

def test_the_cut_at_the_published_widths_counts_659_190_016():
    cfg = ModelConfig(
        attn_dim=2048, ffn_dim=0, num_heads=32, num_kv_heads=4,
        num_layers=6, vocab_size=18992, num_experts=128, moe_top_k=8,
        rope_theta=1e7, dsa_moe=DsaMoEConfig(
            head_dim=128, moe_intermediate_size=768, indexer_num_heads=16,
            indexer_head_dim=64, topk=2048, experts_held=16))
    parts = SelectedAttentionMoETransformer.param_counts(cfg)
    indexer = 2048 * (16 * 64 + 64 + 16) + 2 * 64
    assert attention_of(cfg).indexer.num_params() == indexer == 2_261_120
    # cell 8's layer and one indexer
    assert parts["layers"] == 6 * (94_638_336 + indexer)
    assert parts["embedding_and_head"] == 77_791_232
    assert cfg.num_params() == sum(parts.values()) == 659_190_016
    assert sizes_of(cfg).index_topk == 2048
    # a row keeps 2048 of up to 16384 keys: 23.4% of the triangle
    assert kept_pairs(16384, 2048) == 31_458_304
    assert kept_pairs(16384, 2048) / (16384 * 16385 // 2) == pytest.approx(
        0.2344, abs=1e-4)
    uncut = dataclasses.replace(
        cfg, num_layers=48, vocab_size=151936,
        dsa_moe=dataclasses.replace(cfg.dsa_moe, experts_held=None))
    assert 30.5e9 < uncut.num_params() < 30.7e9      # the published 30B
