"""The `kda_mla_moe` family (models/kda_mla_moe.py): Kimi Delta Attention
layers (a delta rule whose decay is a channel's, under a bounded gate), a
head-gated latent-attention layer closing every group, leading dense layers,
a sigmoid router whose selection is limited to groups of experts, a shared
expert, a multi-token-prediction module. CPU, tiny sizes.

* the program against the plain reference (models/vanilla_kda_mla_moe.py,
  which LOOPS its layers and runs the rule token by token): loss, logits and
  EVERY gradient leaf, with and without the module, at tp 1 and tp 2, on a
  job that holds a slice of the experts; in bfloat16 to bfloat16's rounding;
* the chunked rule with a decay a channel against the token-by-token rule:
  outputs, final state and all five gradients, at the gate's bound on every
  channel for whole chunks (what the sub-blocks rely on), near 0, a ragged
  length; with every channel's decay equal it is the scalar rule; as the XLA
  text and as the Pallas kernels (ops/pallas/kda_rule.py) under the
  interpreter, at the widths they hold;
* what a delta layer makes again is its remat rung's to say (PR 64: the
  mixer keeps no checkpoint of its own): the forward products of `w_q`,
  `w_k`, `w_v`, `w_f` counted in the train step's jaxpr at `true`, `flash`
  and `dots`, and every rung's loss and gradients against `remat=False`'s;
* the group-limited selection against a sort-based one, and today's at one
  group; `LatentAttention` with no q latent and a gate a head;
* the shares of an expert layer add up to the uncut layer;
* parameters round-trip through the canonical form and a checkpoint;
* what the family does not run is refused with a message;
* the counts at the published widths;
* **what must not move**: the ninth standing family's lowered digest and
  remat pick (tests/test_mhc_mla_moe.py holds the other eight's).
"""

import collections
import dataclasses
import functools
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from family_recipe import (Recipe, apply_moe, hold_leaves, hold_loss,
                           lowered_text, mesh_of, on_one_device,
                           outputs_and_grads, picked_rung, token_file)
from jax.extend import core as jex_core
from jax.sharding import PartitionSpec as P

from distributed_pytorch_from_scratch_tpu.training.checkpoint import (
    load_checkpoint, save_checkpoint)
from distributed_pytorch_from_scratch_tpu.config import (
    KdaMlaMoEConfig, ModelConfig, OptimizerConfig, model_preset)
from distributed_pytorch_from_scratch_tpu.models import (FAMILIES,
                                                         build_model)
from distributed_pytorch_from_scratch_tpu.models.kda_mla_moe import (
    KdaMlaMoETransformer, layer_counts)
from distributed_pytorch_from_scratch_tpu.models.vanilla_kda_mla_moe import (
    layers_in_order, vanilla_logits, vanilla_loss)
from distributed_pytorch_from_scratch_tpu.ops.delta_rule import (
    SUB, channel_delta_rule, delta_rule_recurrent, gated_delta_rule)
from distributed_pytorch_from_scratch_tpu.ops.pallas import kda_rule
from distributed_pytorch_from_scratch_tpu.ops.rope import rope_angles
from distributed_pytorch_from_scratch_tpu.parallel.kda import (
    KimiDeltaAttention)
from distributed_pytorch_from_scratch_tpu.parallel.mla import LatentAttention
from distributed_pytorch_from_scratch_tpu.parallel.moe import SharedRoutedFFN
from distributed_pytorch_from_scratch_tpu.training import memory
from distributed_pytorch_from_scratch_tpu.training.metrics import (
    model_flops_per_step, moe_counters_summary)
from distributed_pytorch_from_scratch_tpu.training.optim import (
    init_adam_state)
from distributed_pytorch_from_scratch_tpu.training.train_step import (
    build_train_step)

FAMILY = "kda_mla_moe"
# the family's own: its reference, and sequences of 96 from id 0 up
R = Recipe(FAMILY, vanilla_loss, vanilla_logits, t=96, low=0)
tiny, batch, on_mesh = R.tiny, R.batch, R.on_mesh


# ---- the program against the plain reference ----

@pytest.mark.parametrize("tp,impl,mtp", [
    (1, "xla", 1), (2, "xla", 1), (1, "xla", 0), (2, "xla", 0),
    (1, "flash_interpret", 1)])
def test_loss_and_every_gradient_leaf_equal_the_reference(tp, impl, mtp):
    """Segments and a period SCANNED (the program) against six layers
    LOOPED (the reference), the chunked rule against the token-by-token
    one, the top_k inside kept groups against a sort, on a job that holds
    experts 4..11 of 16 (the second and third groups of four). Leaves to
    5e-5 of their largest entry, as the third family's rule."""
    cfg = tiny(experts_held=8, expert_offset=4,
               num_nextn_predict_layers=mtp)
    # (the parameters and the reference are one per `mtp`)
    params, (want, want_g) = R.reference(cfg)
    assert len(layers_in_order(params)) == cfg.num_layers == 6
    got, got_g = R.program(cfg, tp=tp, attn_impl=impl)
    hold_loss(want, got)
    names, moved = hold_leaves(want_g, got_g, 5e-5)
    # every leaf but the selection biases has a gradient (A_log, dt_bias,
    # the convolutions and the head gate among them)
    biases = sum("bias" in name for name in names if "dt_bias" not in name)
    assert len(moved) == len(names) - biases
    assert ("mtp" in params) == bool(mtp)
    assert params["kda_layers"]["kda"]["w_f"].shape[:2] == (1, 2)
    assert params["mla_layers"]["mla"]["w_gate"]["weight"].shape == (1, 1,
                                                                     64, 4)
    assert "wq_a" not in params["mla_layers"]["mla"]


def test_the_logits_equal_the_reference():
    cfg = tiny(num_nextn_predict_layers=0)
    mesh, model = on_mesh(cfg, 2)
    ids, _, pos = batch(cfg, t=80)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(model.make_forward(mesh))(
            jax.device_put(R.params(cfg, 5), model.shardings(mesh)), ids, pos)
    np.testing.assert_allclose(got[..., :cfg.vocab_size],
                               R.reference_logits(cfg, t=80, seed=5),
                               atol=3e-5)


def test_in_bfloat16_the_loss_is_the_references_to_bfloat16s_rounding():
    cfg = tiny("bfloat16", experts_held=8, expert_offset=4)
    mesh, model = on_mesh(cfg, 1)
    params = R.params(cfg)
    ids, tgt, pos = batch(cfg)
    want = jax.jit(lambda p: vanilla_loss(cfg, p, ids, tgt, pos))(params)
    got = jax.jit(model.make_loss(mesh))(params, ids, tgt, pos)
    hold_loss(want, got, 2e-2)


# ---- what a delta layer makes again is the rung's to say ----

# a weight stays the leaf it is through these on its way into a product
_SAME_LEAF = {"convert_element_type", "squeeze", "reshape", "slice",
              "dynamic_slice", "copy", "pvary"}


def _count_products(jaxpr, leaf_of, counts):
    """`counts[leaf, the axes of it a product contracts]` over `jaxpr` and
    every jaxpr its equations hold (scan and checkpoint bodies, the
    shard_map, custom derivatives), each body once: a scanned layer's
    products count once a layer. `leaf_of`: variable -> the name of the
    parameter leaf it is (a cast, a cut or one scanned layer of)."""
    for eqn in jaxpr.eqns:
        leaves = [None if isinstance(v, jex_core.Literal) else leaf_of.get(v)
                  for v in eqn.invars]
        if eqn.primitive.name == "dot_general":
            contracted, _ = eqn.params["dimension_numbers"]
            for leaf, axes in zip(leaves, contracted):
                if leaf:
                    counts[leaf, tuple(axes)] += 1
        elif eqn.primitive.name in _SAME_LEAF and leaves[0]:
            leaf_of[eqn.outvars[0]] = leaves[0]
        for body in jax.core.jaxprs_in_params(eqn.params):
            skipped = len(eqn.invars) - len(body.invars)  # a cond's index
            if skipped >= 0:
                _count_products(body, {
                    v: leaf for v, leaf in zip(body.invars, leaves[skipped:])
                    if leaf}, counts)


@pytest.mark.parametrize("rung,forwards", [
    (True, (2, 2, 2, 2)), ("flash", (2, 2, 2, 2)), ("dots", (1, 1, 1, 2))])
def test_a_delta_layer_makes_its_rules_inputs_as_often_as_the_rung_says(
        rung, forwards):
    """The train step's jaxpr at the tiny preset, every delta segment: a
    product that contracts a projection's first axis (d) is a FORWARD of it
    (the transposes contract its heads, or do not hold it). Once in the
    forward and once in the layer's recompute under `true` and `flash`;
    under `dots` the recompute starts from the named q, k and v and only
    the decay's projection, which carries no name, runs again. With a
    checkpoint of the mixer's own (before PR 64) every count was 3, at
    every rung: a name under an inner checkpoint is not the layer's policy's
    to keep."""
    mesh, model = on_mesh(tiny(), 1, remat=rung)
    params = jax.eval_shape(model.init, jax.random.key(0))
    opt = jax.eval_shape(init_adam_state, params)
    ids = jax.ShapeDtypeStruct((2, 128), np.int32)
    step = build_train_step(model, mesh, OptimizerConfig())
    args = (params, opt, ids, ids, ids)
    closed = jax.make_jaxpr(step)(*args)
    leaf_of = {}
    for var, (path, _) in zip(closed.jaxpr.invars,
                              jax.tree_util.tree_flatten_with_path(args)[0]):
        if path[0].idx == 0:
            leaf_of[var] = jax.tree_util.keystr(path[1:])
    counts = collections.Counter()
    _count_products(closed.jaxpr, leaf_of, counts)
    for segment in ("dense_layers", "lead_kda_layers", "kda_layers"):
        leaf = lambda name: f"['{segment}']['kda']['{name}']"
        assert tuple(counts[leaf(n), (0,)] for n in (
            "w_q", "w_k", "w_v", "w_f")) == forwards, (segment, rung)
        # the output gate and `W_out` sit outside the rule's inputs: twice
        # at every rung; and every projection is transposed once
        assert counts[leaf("w_g"), (0,)] == counts[leaf("w_out"), (0,)] == 2
        assert all(counts[leaf(n), (1, 2)] == 1 for n in (
            "w_q", "w_k", "w_v", "w_f", "w_g"))


@pytest.mark.parametrize("rung", [True, "flash", "dots"])
def test_every_rung_is_the_unrematerialised_programs_numbers(rung):
    """What a rung keeps changes when a tensor is made, never what it is:
    the loss and every gradient leaf against `remat=False`'s, to the last
    bit or to float32's rounding of a reordered sum (leaves to 1e-5 of
    their largest entry; read: 2e-6 at `true`; the reference of
    `test_loss_and_every_gradient_leaf_equal_the_reference` stands beside
    it at the family's default)."""
    cfg = tiny(experts_held=8, expert_offset=4)
    want, want_g = R.program(cfg, remat=False)      # once for the three
    got, got_g = R.program(cfg, remat=rung)
    hold_loss(want, got, 1e-6)
    hold_leaves(want_g, got_g, 1e-5)


def test_no_top_k_choice_sits_on_a_tie():
    """The comparison above is meaningful only if no token's last kept
    group or last chosen expert is within rounding of the next."""
    cfg = tiny()
    moe = build_model(FAMILY, cfg)._mods["moe"]
    p = moe.init(jax.random.key(1))
    x = jax.random.normal(jax.random.key(2), (192, cfg.attn_dim))
    s = np.asarray(jax.nn.sigmoid(x @ p["router"]))
    groups = np.sort(s.reshape(192, 4, 4), -1)[..., -2:].sum(-1)
    ranked = np.sort(groups, -1)
    assert np.min(ranked[:, -2] - ranked[:, -3]) > 1e-5


# ---- the chunked rule with a decay a channel ----

def rule_inputs(seed, t, g="drawn", b=2, h=2, dk=16, dv=8):
    ks = jax.random.split(jax.random.key(seed), 5)
    unit = lambda z: z / jnp.linalg.norm(z, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, h, t, dk))) / 4.0
    k = unit(jax.random.normal(ks[1], (b, h, t, dk)))
    v = jax.random.normal(ks[2], (b, h, t, dv))
    beta = jax.nn.sigmoid(2.0 * jax.random.normal(ks[3], (b, h, t)))
    if g == "drawn":       # anywhere between the bound and 0
        gate = -5.0 * jax.nn.sigmoid(3.0 * jax.random.normal(
            ks[4], (b, h, t, dk)))
    elif g == "bound":     # the bound on EVERY channel for whole chunks
        gate = jnp.full((b, h, t, dk), -5.0).at[:, :, 64:70].set(-0.01)
    else:                  # near 0: the state forgets nothing
        gate = -1e-3 * jax.random.uniform(ks[4], (b, h, t, dk))
    return q, k, v, gate, beta


# the rule as the Pallas kernels under the interpreter, at the widths they
# hold: two heads a grid step for the four heads of two sequences (one call
# takes both), their chains traced side by side, two chunks a grid step
KERNELS = dict(dk=128, dv=128)


@functools.lru_cache(maxsize=None)
def _rule(interpret, **kw):
    """One function a (path, chunk): the sweep's cases that differ in their
    data alone run one compiled program (`outputs_and_grads` keeps it)."""
    return lambda *a: channel_delta_rule(*a, interpret=interpret, **kw)


def channel_rule(impl, monkeypatch, **kw):
    if impl == "kernels":       # (every caller steers the same two)
        monkeypatch.setattr(kda_rule, "HEAD_BLOCK", 2)
        monkeypatch.setattr(kda_rule, "HEADS_IN_TURN", 2)
    return _rule(impl == "kernels", **kw)


def _sines(o, S):
    return jnp.sum(jnp.sin(o)) + jnp.sum(S * S)


@pytest.mark.parametrize("g", ["drawn", "bound", "near_zero"])
@pytest.mark.parametrize("t,chunk,impl", [
    (192, 64, "text"), (150, 64, "text"), (96, 32, "text"),
    # three chunks (one a grid step), a ragged length, a chunk and a half
    (192, 64, "kernels"), (150, 64, "kernels"), (96, 64, "kernels")])
def test_the_chunked_channel_rule_equals_the_token_by_token_rule(
        g, t, chunk, impl, monkeypatch):
    """Outputs, the final state and all five gradients (`g`'s a channel).
    At the bound a sub-block's factor about its first row reaches exp(75):
    the products stay finite and the entries above the diagonal are
    selected away."""
    args = rule_inputs(7, t, g, **(KERNELS if impl == "kernels" else {}))
    rule = channel_rule(impl, monkeypatch, chunk=chunk)
    # (a rule's outputs and its five gradients are one compiled program,
    # and one for the cases of a (length, chunk, path): `g` is data)
    (o, S), grads = outputs_and_grads(rule, _sines, *args)
    (o_ref, S_ref), grads_ref = outputs_and_grads(delta_rule_recurrent,
                                                  _sines, *args)
    scale = float(jnp.max(jnp.abs(o_ref)))
    assert float(jnp.max(jnp.abs(o - o_ref))) <= 2e-6 * max(scale, 1.0)
    assert float(jnp.max(jnp.abs(S - S_ref))) <= 1e-5 * max(
        float(jnp.max(jnp.abs(S_ref))), 1.0)
    assert grads[3].shape == args[3].shape      # a channel's
    for a, b in zip(grads, grads_ref):
        assert bool(jnp.all(jnp.isfinite(a)))
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-4 * max(
            float(jnp.max(jnp.abs(b))), 1e-6)


@pytest.mark.parametrize("impl", ["text", "kernels"])
def test_with_every_channels_decay_equal_it_is_the_scalar_rule(
        impl, monkeypatch):
    q, k, v, g, beta = rule_inputs(
        11, 160, **(KERNELS if impl == "kernels" else {}))
    one = g[..., 0]
    with jax.default_matmul_precision("highest"):
        o, S = channel_rule(impl, monkeypatch)(q, k, v, jnp.broadcast_to(
            one[..., None], g.shape), beta)
        o_s, S_s = gated_delta_rule(q, k, v, one, beta)
    np.testing.assert_allclose(o, o_s, atol=2e-6)
    np.testing.assert_allclose(S, S_s, atol=2e-6)


def test_the_kernels_take_a_batchs_heads_in_one_call_and_a_ragged_length(
        monkeypatch):
    """Two sequences of three heads, 100 tokens: one call a kernel for the
    six heads, four a grid step (the last block hangs over), to the text's
    value, state and gradients."""
    args = rule_inputs(3, 100, h=3, **KERNELS)
    monkeypatch.setattr(kda_rule, "HEAD_BLOCK", 4)
    monkeypatch.setattr(kda_rule, "HEADS_IN_TURN", 2)
    kernels = lambda *a: channel_delta_rule(*a, interpret=True)
    loss = lambda o, S: jnp.sum(o * jnp.cos(o)) + jnp.sum(S * S)
    flat = lambda out, grads: (*out, *grads)
    got = flat(*outputs_and_grads(kernels, loss, *args))
    want = flat(*outputs_and_grads(channel_delta_rule, loss, *args))
    assert got[0].shape == (2, 3, 100, 128) and got[1].shape == (2, 3, 128,
                                                                 128)
    top = max(float(jnp.max(jnp.abs(b))) for b in want[2:])
    for a, b in zip(got[:2], want[:2]):
        assert float(jnp.max(jnp.abs(a - b))) <= 2e-6 * max(
            float(jnp.max(jnp.abs(b))), 1.0)
    # the same sums in another order: by the largest gradient's scale
    for a, b in zip(got[2:], want[2:]):
        assert float(jnp.max(jnp.abs(a - b))) <= 2e-6 * max(top, 1.0)


def test_the_sub_blocks_rely_on_the_bound_and_the_mixer_says_so():
    with pytest.raises(ValueError, match="sub-blocks rely on it"):
        KimiDeltaAttention(64, 4, 16, 16, lower_bound=-6.0)
    with pytest.raises(ValueError, match="sub-blocks rely on it"):
        KimiDeltaAttention(64, 4, 16, 16, lower_bound=0.5)
    assert (SUB - 1) * 5.0 < 87.0
    with pytest.raises(ValueError, match="do not divide a chunk"):
        channel_delta_rule(*rule_inputs(0, 64), chunk=40)


def test_the_mixers_gate_stays_over_its_bound_and_differs_by_channel():
    kda = KimiDeltaAttention(64, 4, 16, 16)
    p = kda.init(jax.random.key(0))
    # a gate driven hard: the decay's projection a hundred times its size
    p = {**p, "w_f": 100.0 * p["w_f"]}
    x = jax.random.normal(jax.random.key(1), (2, 70, 64))
    y, c = on_one_device(lambda p, x: kda.apply(p, x), (kda.specs(), P()),
                         (P(), P()))(p, x)
    assert bool(jnp.all(jnp.isfinite(y)))
    assert -5.0 <= float(c["kda_g_min"]) < -4.9
    assert float(c["kda_g_spread"]) > 0.5


# ---- the selection, and latent attention's two facts ----

def test_the_group_limited_selection_equals_a_sort_and_one_group_is_todays():
    d, E = 32, 32
    grouped = SharedRoutedFFN(d, 16, E, top_k=4, n_group=4, topk_group=2)
    p = grouped.init(jax.random.key(1))
    p["bias"] = 0.2 * jax.random.normal(jax.random.key(5), (E,))
    x = jax.random.normal(jax.random.key(2), (256, d))
    chosen, w = grouped.route(p, x)
    s = np.asarray(jax.nn.sigmoid(x @ p["router"]))
    biased = s + np.asarray(p["bias"])
    of_group = np.sort(biased.reshape(256, 4, 8), -1)[..., -2:].sum(-1)
    kept = np.argsort(-of_group, -1)[:, :2]
    for row in range(256):
        allowed = [e for e in range(E) if e // 8 in kept[row]]
        want = sorted(allowed, key=lambda e: -biased[row, e])[:4]
        assert sorted(np.asarray(chosen[row])) == sorted(want)
        picked = s[row, np.asarray(chosen[row])]
        np.testing.assert_allclose(w[row], picked / picked.sum(), rtol=1e-5)
    # a token's choices hit at most topk_group groups
    assert int(jnp.max(jnp.sum(jnp.any(
        (chosen // 8)[..., None] == jnp.arange(4), axis=1), -1))) <= 2
    hit = grouped.groups_hit(chosen)
    assert hit.shape == (4,) and 256 <= float(hit.sum()) <= 512
    # one group: the largest of them all, the selection as it always was
    plain = dataclasses.replace(grouped, n_group=1, topk_group=1)
    chosen_1, _ = plain.route(p, x)
    _, want_1 = jax.lax.top_k(jnp.asarray(biased), 4)
    np.testing.assert_array_equal(chosen_1, want_1)
    assert float(jnp.mean((jnp.sort(chosen_1) != jnp.sort(chosen))
                          .astype(jnp.float32))) > 0.05


@pytest.mark.parametrize("kw,message", [
    (dict(n_group=3, topk_group=1), "groups must divide"),
    (dict(n_group=4, topk_group=5), "groups must divide"),
    (dict(n_group=32, topk_group=8), "two experts each"),
    (dict(n_group=4, topk_group=1, top_k=12), "two experts each"),
    (dict(n_group=4, topk_group=2, score="softmax"), "sigmoid scores"),
])
def test_a_selection_its_groups_cannot_hold_is_refused(kw, message):
    with pytest.raises(ValueError, match=message):
        SharedRoutedFFN(32, 16, 32, **{"top_k": 4, **kw})


def test_latent_attention_with_no_q_latent_and_a_gate_a_head():
    attn = LatentAttention(64, 4, None, 16, 16, 8, 16, head_gate=True)
    assert set(attn.modules()) == {"wq", "w_gate", "wkv_a", "kv_norm",
                                   "wkv_b", "wo"}
    assert attn.num_params() == (64 * 4 * 24 + 64 * 4 + 64 * 24 + 16
                                 + 16 * 4 * 32 + 64 * 64)
    # (with a latent and no gate: the third family's modules)
    assert set(LatentAttention(64, 4, 32, 16, 16, 8, 16).modules()) == {
        "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo"}
    p = attn.init(jax.random.key(0))
    y = jax.random.normal(jax.random.key(1), (2, 48, 64))
    pos = jnp.tile(jnp.arange(48), (2, 1))
    cos, sin = rope_angles(pos, 8, 10000.0)
    fn = jax.shard_map(
        lambda p, y, cos, sin: attn.apply(p, y, cos, sin, jnp.float32,
                                          attn_impl="xla"),
        mesh=mesh_of(tp=2), in_specs=(attn.specs(), P(), P(), P()),
        out_specs=P())
    with jax.default_matmul_precision("highest"):
        got = jax.jit(fn)(p, y, cos, sin)
        # plainly
        heads = lambda z, w: z.reshape(2, 48, 4, w).transpose(0, 2, 1, 3)
        rot = lambda z: jnp.stack(
            [z[..., 0::2] * cos[:, None] - z[..., 1::2] * sin[:, None],
             z[..., 1::2] * cos[:, None] + z[..., 0::2] * sin[:, None]],
            -1).reshape(z.shape)
        q = heads(y @ p["wq"]["weight"], 24)
        ckv = y @ p["wkv_a"]["weight"]
        c = ckv[..., :16]
        c = p["kv_norm"]["scale"] * c / jnp.sqrt(
            jnp.mean(c * c, -1, keepdims=True) + 1e-6)
        kv = heads(c @ p["wkv_b"]["weight"], 32)
        q = jnp.concatenate([q[..., :16], rot(q[..., 16:])], -1)
        k = jnp.concatenate([kv[..., :16], jnp.broadcast_to(
            rot(ckv[..., 16:][:, None]), (2, 4, 48, 8))], -1)
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(24.0)
        scores = jnp.where(jnp.tril(jnp.ones((48, 48), bool)), scores,
                           -jnp.inf)
        o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1),
                       kv[..., 16:])
        gate = jax.nn.sigmoid(y @ p["w_gate"]["weight"])
        o = o * gate.transpose(0, 2, 1)[..., None]
        want = o.transpose(0, 2, 1, 3).reshape(2, 48, 64) @ p["wo"]["weight"]
    np.testing.assert_allclose(got, want, atol=2e-5)


# ---- the shares add up ----

def test_the_four_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Four jobs hold four experts each of one layer's 16 in 2 groups (a
    group on two shares). Their routed parts, plus the shared expert once,
    are the layer a job holding all 16 computes: the router, its groups and
    the weights' normalisation see all the experts on every share."""
    d, f, E = 32, 16, 16
    whole = SharedRoutedFFN(d, f, E, top_k=3, scaling=2.5, n_group=2,
                            topk_group=1)
    p = whole.init(jax.random.key(1))
    x = jax.random.normal(jax.random.key(2), (2, 64, d))
    shared = lambda p, x: ((jax.nn.silu(x @ p["shared"]["gate"])
                            * (x @ p["shared"]["up"]))
                           @ p["shared"]["down"])
    with jax.default_matmul_precision("highest"):
        want, counters = apply_moe(whole, p, x)
        shared_only = shared(p, x)
        total, rows = shared_only, 0.0
        for lo in range(0, E, 4):
            share = dataclasses.replace(whole, held=4, offset=lo)
            ps = {**p, **{n: p[n][lo:lo + 4] for n in ("gate", "up", "down")}}
            y, c = apply_moe(share, ps, x)
            total = total + (y - shared_only)
            rows += float(c["rows_here"])
            np.testing.assert_array_equal(c["routed"], counters["routed"])
            np.testing.assert_array_equal(c["groups_hit"],
                                          counters["groups_hit"])
    np.testing.assert_allclose(total, want, atol=2e-5)
    assert rows == float(counters["rows_here"]) == 2 * 64 * 3
    # one group a token: every token hits exactly one
    assert float(counters["groups_hit"].sum()) == 2 * 64


def test_the_shares_of_the_models_expert_layer_equal_the_uncut_references():
    """The same through the model: a one-group model cut in four shares of
    four experts; the losses' routed parts differ, so compare the LAYER: the
    mixer, the router and the shared expert counted once, the four shares'
    expert parts summed, against the uncut reference's layer."""
    from distributed_pytorch_from_scratch_tpu.models import (
        vanilla_kda_mla_moe as ref)
    cfg = tiny(num_nextn_predict_layers=0)
    km = cfg.kda_mla_moe
    model = build_model(FAMILY, cfg)
    params = R.params(cfg, 2)
    lp = jax.tree.map(lambda a: a[0], params["lead_kda_layers"])
    y = jax.random.normal(jax.random.key(4), (2, 64, cfg.attn_dim))
    with jax.default_matmul_precision("highest"):
        want = ref._expert_ffn(lp["moe"], y, km, cfg.moe_top_k)
        sh = lp["moe"]["shared"]
        shared_only = ref._swiglu(y.reshape(-1, 64), sh["gate"], sh["up"],
                                  sh["down"]).reshape(y.shape)
        total = shared_only
        for lo in range(0, 16, 4):
            moe = dataclasses.replace(model._mods["moe"], held=4, offset=lo)
            ps = {**lp["moe"], **{n: lp["moe"][n][lo:lo + 4]
                                  for n in ("gate", "up", "down")}}
            total = total + (apply_moe(moe, ps, y)[0] - shared_only)
    np.testing.assert_allclose(total, want, atol=3e-5)


# ---- the parameters' other forms ----

def test_parameters_round_trip_through_the_canonical_form_and_a_checkpoint(
        tmp_path):
    cfg = tiny()
    mesh, model = on_mesh(cfg, 2)
    params = R.params(cfg, 1)
    n = layer_counts(cfg)
    assert n == {"dense_layers": 1, "lead_kda_layers": 1,
                 "lead_mla_layers": 1, "kda_layers": 2, "mla_layers": 1,
                 "mtp_layers": 1}
    assert params["kda_layers"]["kda"]["dt_bias"].shape == (1, 2, 4, 16)
    assert params["dense_layers"]["gate_proj"]["weight"].shape == (1, 64,
                                                                   128)
    assert "moe" not in params["dense_layers"]
    # two layers' decays start apart
    a = params["kda_layers"]["kda"]["A_log"]
    assert float(jnp.abs(a[0, 0] - a[0, 1]).max()) > 1e-3
    canonical = model.to_canonical(params)
    back = model.from_canonical(canonical)
    jax.tree.map(np.testing.assert_array_equal, back, params)
    save_checkpoint(str(tmp_path), 3, 1.0, canonical,
                    model.canonical_specs(), 1)
    fresh = R.params(cfg, 9)
    restored, _, at = load_checkpoint(str(tmp_path), 3, fresh,
                                      model.canonical_specs())
    assert at == 3
    jax.tree.map(np.testing.assert_array_equal, restored, params)


# ---- the step, its counters, the entry point ----

def test_the_train_step_returns_the_decays_and_the_groups_rows_and_the_loss_falls():
    cfg = tiny()
    losses, (_, gnorm, c), _ = R.train(cfg)
    assert losses[-1] < losses[0] and np.isfinite(float(gnorm))
    # a row a delta layer (4 of the 6), a row an expert layer (5 and the
    # module's), in the order the layers run
    assert c["kda_g_min"].shape == c["kda_g_spread"].shape == (4,)
    assert c["routed"].shape == (6, 16) and c["groups_hit"].shape == (6, 4)
    assert float(jnp.min(c["kda_g_min"])) >= -5.0
    assert float(jnp.min(c["kda_g_spread"])) > 0.0
    np.testing.assert_array_equal(c["routed"].sum(-1), [2 * 64 * 2] * 6)
    assert float(c["groups_hit"].sum(-1).max()) <= 2 * 64 * 2
    summary = moe_counters_summary(jax.device_get(c), cfg, 2 * 64)
    assert summary["rows_here_per_token"] == 2.0    # all experts held
    assert -5.0 <= summary["kda_g_min"] < 0.0 < summary["kda_g_spread"]
    assert summary["groups_hit_max"] >= 1.0


def test_train_cli_runs_the_family(tmp_path, capsys):
    from distributed_pytorch_from_scratch_tpu import train as train_mod
    tokens = token_file(tmp_path)
    train_mod.main([
        "--family", FAMILY, "--model", "tiny-kda-mla-moe", "--tp_size", "2",
        "--data_path", str(tokens), "--save_dir", str(tmp_path / "ckpt"),
        "--batch_size", "4", "--maxlen", "64", "--max_steps", "4",
        "--log_interval", "2", "--save_interval", "100",
        "--warmup_steps", "2"])
    out = capsys.readouterr().out
    assert f"model[{FAMILY}]" in out and "kda_g_min" in out
    events = [json.loads(line) for line in
              open(tmp_path / "ckpt" / "logs" / "metrics.jsonl")]
    assert any(e.get("tag") == "moe_counters" for e in events)
    with pytest.raises(SystemExit, match="reads the config field"):
        train_mod.main(["--family", "gdn_moe", "--model", "tiny-kda-mla-moe",
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
    (dict(tp_size=8), "not divisible by tp"),
])
def test_the_model_refuses_what_it_does_not_run(kw, message):
    with pytest.raises(ValueError, match=message):
        build_model(FAMILY, tiny(), **kw)


def test_decoding_and_the_hand_reduced_gradients_are_refused():
    from distributed_pytorch_from_scratch_tpu.models.decode import (
        require_decodable)
    mesh, model = on_mesh(tiny(), 1)
    assert not model.decodable and not model.hand_reduced_grads
    with pytest.raises(ValueError, match="cannot be decoded or served"):
        require_decodable(model)
    for kw, what in ((dict(zero=2), "ZeRO stage 2"),
                     (dict(zero=3), "ZeRO stage 3"),
                     (dict(dp_reduce_bucket_mb=25.0), "bucketed")):
        with pytest.raises(ValueError, match=what):
            build_train_step(model, mesh, OptimizerConfig(), **kw)


@pytest.mark.parametrize("cfg,message", [
    (ModelConfig(num_experts=8), "needs cfg.kda_mla_moe"),
    (dataclasses.replace(model_preset("tiny-kda-mla-moe"), num_layers=5),
     "whole groups"),
    (tiny(first_k_dense_replace=3), "latent layer its experts"),
    (tiny(num_nextn_predict_layers=2), "depth 0 or 1"),
    (tiny(kda_lower_bound=-8.0), "sub-blocks rely on it"),
])
def test_a_family_needs_its_own_facts_and_whole_groups(cfg, message):
    with pytest.raises(ValueError, match=message):
        build_model(FAMILY, cfg)


def test_with_no_dense_layer_every_group_is_a_period():
    cfg = tiny(first_k_dense_replace=0, num_nextn_predict_layers=0)
    model = build_model(FAMILY, cfg)
    assert model._pattern == ((("kda_layers", 2), ("mla_layers", 1)),)
    assert build_model(FAMILY, tiny())._pattern == (
        "dense_layers", "lead_kda_layers", "lead_mla_layers",
        (("kda_layers", 2), ("mla_layers", 1)))
    made = jax.eval_shape(model.init, jax.random.key(0))
    assert made["kda_layers"]["kda"]["w_q"].shape[:2] == (2, 2)
    assert sum(x.size for x in jax.tree.leaves(made)) == cfg.num_params()


# ---- the counts at the published widths ----

def published(held=8, vocab=19648, layers=6, dense=1, mtp=0):
    return ModelConfig(
        attn_dim=2560, ffn_dim=6144, num_heads=32, num_layers=layers,
        vocab_size=vocab, maxlen=4096, rope_theta=6e6, num_experts=512,
        moe_top_k=8, kda_mla_moe=KdaMlaMoEConfig(
            head_dim=128, kv_lora_rank=512, qk_nope_head_dim=128,
            qk_rope_head_dim=64, v_head_dim=128, moe_intermediate_size=768,
            first_k_dense_replace=dense, experts_held=held,
            num_nextn_predict_layers=mtp))


def test_parameter_counts_at_the_published_widths():
    """One chip's share (8 of 512 experts, an eighth of the vocabulary, the
    first group with its dense layers counted once: 1 dense delta layer, 4
    delta expert layers, 1 latent expert layer), as `init` makes them."""
    cfg = published()
    model = build_model(FAMILY, cfg)
    kda, mla = model._mods["kda"], model._mods["mla"]
    # q, k, v, the decay's and the output gate 5 x 2560 x 4096, W_o, beta,
    # three convolutions of 4 taps, A_log, dt_bias, the norm
    assert kda.num_params() == (6 * 2560 * 4096 + 2560 * 32
                                + 3 * 4096 * 4 + 32 + 4096 + 128)
    assert kda.num_params() == 63_049_888
    assert mla.num_params() == (2560 * 32 * 192 + 2560 * 32 + 2560 * 576
                                + 512 + 512 * 8192 + 4096 * 2560)
    assert mla.num_params() == 31_965_696
    parts = KdaMlaMoETransformer.param_counts(cfg)
    ffn = 2560 * 512 + 512 + 9 * 3 * 2560 * 768
    assert parts["dense_layers"] == 63_049_888 + 5120 + 3 * 2560 * 6144
    assert parts["kda_expert_layers"] == 4 * (63_049_888 + 5120 + ffn)
    assert parts["mla_expert_layers"] == 31_965_696 + 5120 + ffn
    assert parts["embedding_and_head"] == 2 * 19648 * 2560
    assert cfg.num_params() == 767_009_056
    made = jax.eval_shape(model.init, jax.random.key(0))
    assert sum(x.size for x in jax.tree.leaves(made)) == cfg.num_params()
    # x 16 bytes (weights, gradients, two Adam moments): 12.27 GB
    assert 11.8e9 < cfg.num_params() * 16 < 12.8e9
    # the module is one more latent expert layer and the 5120 -> 2560
    # projection: 1.59 GB more
    with_module = published(mtp=1).num_params() - cfg.num_params()
    assert with_module == 31_965_696 + 5120 + ffn + 2 * 2560 * 2560 + 3 * 2560
    # forward FLOPs a token by the program's convention (the scores' full
    # square): 6 N_active + the latent layer's scores + five layers' rules
    flops = model_flops_per_step(cfg, 1, 4096, cfg.num_params())
    assert 1.0e9 < flops / 4096 / 3 < 1.25e9


def test_remat_auto_sizes_the_benchmarks_cell(capsys):
    """`remat="auto"` at the cell's shapes on a v5e's 15.75 GiB walks the
    ladder with the delta layer's own count (`layer_extra_elems_per_token`:
    a float32 decay 4096 wide a token among it, less what the chip's
    reading takes back off: PR 64) and, with no reserve held beside 8.57
    GiB of state, keeps q, k and v of the five delta layers (fifteen stacks
    of 33.5 MB, at the heads' whole width 32 x 128) on top of the one
    latent layer's flash outputs and the dense layer's gate and up: 13.70
    GiB where the chip counts 13.40 (12.937 at `true`, 12.940 at `flash`:
    my chip runs, PR 64)."""
    cfg = dataclasses.replace(published(), compute_dtype="bfloat16")
    model = build_model(FAMILY, cfg, remat_budget_gib=15.748)
    assert model.layer_extra_elems_per_token == pytest.approx(
        86944 - 31.87 * 2560)
    tagged = model.tagged_layers
    assert tagged["flash_out"] == 1 == tagged["ffn_gate"]
    # (a latent layer's q, k, v carry no name: the five are the delta ones)
    assert tagged["q_proj"] == tagged["k_proj"] == tagged["v_proj"] == 5
    assert model.head_dim == 128 and model.kv_dim == 4096
    layer_params = cfg.num_params() - 2 * 19648 * 2560 - 2560
    memory.select_remat_traced.cache_clear()
    rung = memory.select_remat_traced(model, cfg.num_params(), layer_params,
                                      1, 4096)
    said = capsys.readouterr().err
    assert rung == "dots" and "reserve_held=False" in said, said
    estimate = {name: float(said.split(f"{name}=")[1].split("GiB")[0])
                for name in ("true", "flash", "dots")}
    for name, chip in (("true", 12.937), ("flash", 12.940), ("dots", 13.400)):
        assert 0.99 * chip < estimate[name] < 1.05 * chip, said
    # the names are priced at their logical size, fifteen stacks of 4096 x
    # 4096 in bfloat16 (0.469 GiB): the chip's `dots` is `flash` + 0.460
    assert estimate["dots"] - estimate["flash"] == pytest.approx(
        15 * 4096 * 4096 * 2 / memory.GIB, abs=0.011)


# ---- what must not move ----

# the ninth standing family's train step at its tiny preset, as
# tests/test_mhc_mla_moe.py holds the other eight's (its `STANDING`): the
# StableHLO's digest at the commit before `parallel/mla.py`,
# `parallel/moe.py` and `ops/delta_rule.py` were edited for this family (PR
# 58's tree), and what `select_remat_traced` picks there since PR 62, which
# meant to move it (tests/test_mhc_mla_moe.py's `STANDING` says how).
NINTH = ("mhc_mla_moe", "tiny-mhc-mla-moe", "6060959548dcf1c3", "ffn",
         0.013556059449911118)


def test_the_ninth_standing_family_lowers_to_the_text_it_lowered_to():
    family, preset, digest, _, _ = NINTH
    text = lowered_text(family, model_preset(preset))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest
    assert "kda" not in text and "groups_hit" not in text


def test_the_ninth_standing_family_is_picked_the_rung_it_was(monkeypatch):
    family, preset, _, rung, estimate = NINTH
    _, got, fields = picked_rung(
        monkeypatch, family, model_preset(preset, compute_dtype="bfloat16"),
        0.02)
    assert (got, fields["estimate_gib"]) == (rung, estimate)


def test_the_new_familys_step_names_its_scopes():
    """The named scopes a device trace splits the step by are the name
    stacks of the lowered text's debug info."""
    text = lowered_text(FAMILY, tiny(), shape=(2, 128), debug_info=True)
    # (a name stack is cut where a function is called: the rule's inner
    # scopes stand under its `checkpoint`, the call under `kda_rule`; a
    # device trace's op_name has them joined)
    for scope in ("kda/", "kda/gate", "kda_rule/", "checkpoint/operands/",
                  "checkpoint/walk/", "mla/", "mla/gate", "moe_route/",
                  "groups/", "moe_experts", "moe_shared", "dense_ffn",
                  "mtp/"):
        assert scope in text, scope
    assert FAMILY in FAMILIES and len(FAMILIES) >= 10
