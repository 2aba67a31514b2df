"""The `gdn_moe` family (models/gdn_moe.py): Gated DeltaNet layers, a gated
grouped-query full-attention layer closing every period, a softmax router
over held experts with a gated shared expert, a scan over periods. CPU, tiny
sizes, float32.

* the program against the plain reference (models/vanilla_gdn_moe.py, which
  LOOPS its eight layers and runs the rule token by token): loss and EVERY
  gradient leaf, at tp 1 and tp 2, on a job that holds a slice of the
  experts; no top-k choice sits on a tie (the margin is asserted);
* the chunked rule against the token-by-token rule: outputs, final state and
  all five gradients, at two chunk sizes, a ragged length, the decay's and
  the write strength's extremes;
* guards that the rule's kernels engaged and are read right, the scalar
  rule's (cell 6) and beside them the rule with a decay a channel's
  (`channel_delta_rule`, ops/pallas/kda_rule.py, cell 12);
* the flash kernel at width 256 and a group of 8 against the XLA path,
  forward and backward, at a multi-block length;
* partial RoPE; the two norms;
* the share test and the forced router for the softmax router with a gated
  shared expert;
* what the family does not run is refused with a message;
* the counts at the published widths (625,667,136 in all).
"""

import dataclasses
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from family_recipe import (Recipe, apply_moe, hold_leaves, hold_loss,
                           mesh_of, outputs_and_grads, token_file)

from distributed_pytorch_from_scratch_tpu.config import (
    GdnMoEConfig, ModelConfig, model_preset)
from distributed_pytorch_from_scratch_tpu.models import build_model
from distributed_pytorch_from_scratch_tpu.models.gdn_moe import (
    GdnMoETransformer)
from distributed_pytorch_from_scratch_tpu.models.vanilla_gdn_moe import (
    vanilla_loss)
from distributed_pytorch_from_scratch_tpu.ops.attention import (
    causal_attention_xla)
from distributed_pytorch_from_scratch_tpu.obs import trace as obs_trace
from distributed_pytorch_from_scratch_tpu.ops.delta_rule import (
    SUB, channel_delta_rule, delta_rule_recurrent, gated_delta_rule)
from distributed_pytorch_from_scratch_tpu.ops.pallas import (
    delta_rule as rule_kernels)
from distributed_pytorch_from_scratch_tpu.parallel.kda import (
    KimiDeltaAttention)
from distributed_pytorch_from_scratch_tpu.ops.pallas.flash_attention import (
    flash_attention)
from distributed_pytorch_from_scratch_tpu.ops.ssd import ssd
from distributed_pytorch_from_scratch_tpu.ops.rope import (
    apply_rotary_leading, rope_angles)
from distributed_pytorch_from_scratch_tpu.parallel.moe import SharedRoutedFFN
from distributed_pytorch_from_scratch_tpu.parallel.norm import (
    GatedRMSNorm, ZeroCenteredRMSNorm)
from distributed_pytorch_from_scratch_tpu.training.metrics import (
    model_flops_per_step, moe_counters_summary)


# the family's own: its reference, and sequences of 128 from id 0 up
R = Recipe("gdn_moe", vanilla_loss, t=128, low=0)
tiny, batch, on_mesh = R.tiny, R.batch, R.on_mesh


# ---- the program against the plain reference ----

@pytest.mark.parametrize("tp,impl", [(1, "xla"), (2, "xla"),
                                     (1, "flash_interpret")])
def test_loss_and_every_gradient_leaf_equal_the_reference(tp, impl):
    """Two periods SCANNED (the program) against eight layers LOOPED (the
    reference), the chunked rule against the token-by-token one, on a job
    that holds experts 2..5 of 8. Leaves to 5e-5 of their largest entry:
    the chunked rule sums a chunk's decays in another order than the
    recurrence does, in float32 (1.8e-5 at the worst leaf)."""
    cfg = tiny(experts_held=4, expert_offset=2)
    _, model = on_mesh(cfg, tp, attn_impl=impl)
    assert model.periods == 2 and cfg.num_layers == 8
    # (the parameters and the reference are one for the three layouts)
    params, (want, want_g) = R.reference(cfg)
    got, got_g = R.program(cfg, tp=tp, attn_impl=impl)
    hold_loss(want, got)
    assert len(hold_leaves(want_g, got_g, 5e-5)[0]) == 36
    # a softmax router has no selection bias
    assert "bias" not in params["gdn_layers"]["moe"]
    assert params["gdn_layers"]["gdn"]["w_qkvz"].shape[:2] == (2, 3)
    assert params["attn_layers"]["attn"]["wq"].shape[:2] == (2, 1)


def test_no_top_k_choice_sits_on_a_tie():
    cfg = tiny()
    moe = SharedRoutedFFN(cfg.attn_dim, 32, cfg.num_experts, cfg.moe_top_k,
                          score="softmax", shared_gate=True)
    p = moe.init(jax.random.key(3))
    x = jax.random.normal(jax.random.key(4), (256, cfg.attn_dim))
    with jax.default_matmul_precision("highest"):
        s = np.sort(np.asarray(jax.nn.softmax(x @ p["router"])), axis=-1)
    margin = s[:, -cfg.moe_top_k] - s[:, -cfg.moe_top_k - 1]
    assert margin.min() > 1e-5


# ---- the chunked rule against the token-by-token rule ----

def rule_inputs(t, decay=1.0, beta_shift=0.0, seed=0, widths=(16, 8),
                keys="random"):
    ks = jax.random.split(jax.random.key(seed), 5)
    b, h = 2, 3
    dk, dv = widths
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, h, t, dk))) / 4
    k = unit(jax.random.normal(ks[1], (b, h, t, dk)))
    if keys == "collinear":     # one direction a head, either sign
        k = k[:, :, :1] * jnp.where(jnp.arange(t) % 3 == 2, -1.0, 1.0)[:, None]
    v = jax.random.normal(ks[2], (b, h, t, dv))
    g = -decay * jax.nn.softplus(jax.random.normal(ks[3], (b, h, t)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, h, t)) + beta_shift)
    return q, k, v, g, beta


# the rule as the Pallas kernels (a chunk's operands made in the kernel, the
# walk, and the backward's transpose by hand), under the interpreter, at the
# widths they hold: four heads a grid step for the six heads of two sequences
# (one call takes both; the last block hangs over), their chains traced two
# at a time, two chunks a grid step, the state carried over several grid
# steps
KERNELS = dict(widths=(128, 128), kernels=True)


@functools.lru_cache(maxsize=None)
def _rule(chunk, interpret):
    """One function a (chunk, path): the sweep's cases that differ in their
    data alone run one compiled program (`outputs_and_grads` keeps it; every
    case steers the kernels' two block sizes alike)."""
    return lambda *a: gated_delta_rule(*a, chunk=chunk, interpret=interpret)


def _cosines(o, S):
    return jnp.sum(o * jnp.cos(o)) + jnp.sum(S * S)


@pytest.mark.parametrize("t,chunk,decay,beta_shift,how", [
    (128, 16, 1.0, 0.0, {}), (128, 64, 1.0, 0.0, {}),
    (100, 64, 1.0, 0.0, {}),        # a length that is no multiple of chunk
    (128, 64, 16.0, 0.0, {}),       # alpha near 0: exp(G) underflows
    (128, 16, 1e-4, 6.0, {}),       # alpha near 1, beta near 1
    # the solve: every key of a head the same direction, beta -> 1, hardly
    # a decay: A is all ones under the diagonal, its powers grow as the
    # binomials (A^32 passes 1e17) and (I + A)^-1 has two diagonals
    (128, 64, 1e-4, 12.0, dict(keys="collinear")),
    (200, 64, 1.0, 0.0, KERNELS),   # ragged; four chunks, two grid steps
    (192, 64, 1.0, 0.0, KERNELS),   # three chunks: one a grid step
    (256, 64, 16.0, 0.0, KERNELS),
    (128, 16, 1e-4, 6.0, KERNELS),
    (128, 64, 1e-4, 12.0, dict(KERNELS, keys="collinear")),
    (128, 64, 16.0, 12.0, KERNELS),  # the decay at its cap, beta near 1
    (128, 64, 1.0, 0.0, dict(KERNELS, widths=(128, 256))),  # d_k != d_v
])
def test_the_chunked_rule_equals_the_token_by_token_rule(
        t, chunk, decay, beta_shift, how, monkeypatch):
    how = dict(how)
    kernels = how.pop("kernels", False)
    monkeypatch.setattr(rule_kernels, "HEAD_BLOCK", 4)
    monkeypatch.setattr(rule_kernels, "HEADS_IN_TURN", 2)
    args = rule_inputs(t, decay, beta_shift, **how)
    dk, dv = args[0].shape[-1], args[2].shape[-1]
    text, chunked = _rule(chunk, False), _rule(chunk, kernels)
    # (a rule's outputs and its five gradients are one compiled program,
    # and one for the cases of a shape and chunk: the rest is data)
    (o, S), got = outputs_and_grads(chunked, _cosines, *args)
    (o_want, S_want), want = outputs_and_grads(delta_rule_recurrent,
                                               _cosines, *args)
    all_five = got
    rel = lambda a, b: float(jnp.max(jnp.abs(a - b))
                             / jnp.maximum(jnp.max(jnp.abs(b)), 1e-9))
    assert o.shape == (2, 3, t, dv) and S.shape == (2, 3, dk, dv)
    assert np.all(np.isfinite(o)) and rel(o, o_want) < 1e-5
    assert rel(S, S_want) < 1e-5
    # with the decay at its cap a chunk's running sum of g reaches a
    # thousand, whose float32 spacing is what is left of g's gradient
    tol = 1e-4 if decay > 4 else 1e-5
    if how.get("keys") == "collinear":
        # as beta -> 1 a write replaces all the state holds along the one
        # key, so the decay's gradient vanishes (6e-5 at its largest where
        # the others reach 5 to 35): what is left of it is the rounding of
        # sums that size, and is held by their scale
        top = max(float(jnp.max(jnp.abs(b))) for b in want)
        assert float(jnp.max(jnp.abs(got[3] - want[3]))) < tol * top
        got, want = got[:3] + got[4:], want[:3] + want[4:]
    for a, b in zip(got, want):
        assert np.all(np.isfinite(a)) and rel(a, b) < tol
    if kernels:     # and the XLA text with its `lax.scan` that they replace
        (o_text, S_text), g_text = outputs_and_grads(text, _cosines, *args)
        # the kernels multiply T rhs in bfloat16 pieces, six passes, as
        # `HIGHEST` does on the chip; the text's product here is float32's
        assert rel(o, o_text) < 2e-6 and rel(S, S_text) < 2e-6
        # the same sums in another order: by the largest gradient's scale
        top = max(float(jnp.max(jnp.abs(b))) for b in g_text)
        for a, b in zip(all_five, g_text):
            assert float(jnp.max(jnp.abs(a - b))) <= 2e-6 * max(top, 1.0)


def eqns_outside_kernels(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it (a
    `custom_vjp_call`'s among them), a `pallas_call`'s body left out."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for inner in jax.tree.leaves(
                list(eqn.params.values()),
                is_leaf=lambda x: hasattr(x, "eqns") or hasattr(x, "jaxpr")):
            inner = getattr(inner, "jaxpr", inner)
            if hasattr(inner, "eqns"):
                yield from eqns_outside_kernels(inner)


def pallas_calls(jaxpr):
    """(name, operands) of every `pallas_call` in a jaxpr, inner ones
    too."""
    return [(eqn.params["name"], len(eqn.invars))
            for eqn in eqns_outside_kernels(jaxpr)
            if eqn.primitive.name == "pallas_call"]


def rule_calls(*args, grad=False):
    """The Mosaic calls of the rule (or its gradient) as it traces NOW: a
    fresh function a call, because a trace is cached by its function."""
    return pallas_calls(rule_jaxpr(*args, grad=grad))


def rule_jaxpr(*args, grad=False):
    rule = lambda *a: gated_delta_rule(*a)
    if grad:
        rule = jax.grad(lambda *a: jnp.sum(gated_delta_rule(*a)[0]),
                        (0, 1, 2, 3, 4))
    return jax.make_jaxpr(rule)(*args).jaxpr


def test_off_the_tpu_or_at_other_widths_the_rule_is_the_xla_text(
        monkeypatch):
    """The kernels engage from what the call sees: a TPU and widths that
    are multiples of 128. Anything else lowers with no Mosaic call."""
    wide, narrow = rule_inputs(128, widths=(128, 128)), rule_inputs(128)
    assert jax.default_backend() != "tpu"
    assert not rule_calls(*wide)
    text = jax.jit(lambda *a: gated_delta_rule(*a)).lower(*wide).as_text()
    assert "tpu_custom_call" not in text and "while" in text
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert not rule_calls(*narrow)
    assert len(rule_calls(*wide)) == 1
    with pytest.raises(ValueError, match="multiples of 128"):
        gated_delta_rule(*narrow, interpret=True)


def test_the_rules_kernels_are_not_read_as_flash_calls(monkeypatch):
    """benchmark/lib/kernels.py reads a Mosaic call named `flash_*`, or
    with 3 or 6 operands, as a flash kernel's: the rule's are neither."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    args = rule_inputs(128, widths=(128, 128))
    assert rule_calls(*args) == [("gdn_rule_fwd", 7)]
    calls = rule_calls(*args, grad=True)
    assert sorted(calls) == [("gdn_rule_bwd", 10), ("gdn_rule_fwd", 7)]
    for name, operands in calls:
        assert not name.startswith("flash_") and operands not in (3, 6)


def test_on_the_kernel_path_no_chunk_parallel_product_is_left_to_xla(
        monkeypatch):
    """The mechanism engaged: `[W | U]` is made in the kernels and the XLA
    text of it is gone, not run beside them. The gradient's jaxpr on the
    kernel path has no `dot_general` outside a `pallas_call` over an (h, n,
    C, d_k + d_v) array, and none at `HIGHEST` (the solve's product and its
    two cotangents were); what XLA still multiplies is `A`'s k k^T. The
    XLA text has all three: the guard reads what it should."""
    h, C, (dk, dv) = 3, 64, (128, 256)
    args = rule_inputs(128, widths=(dk, dv))
    wide = lambda eqn: [v.aval.shape for v in eqn.invars + eqn.outvars
                        if v.aval.shape[-2:] == (C, dk + dv)]
    highest = lambda eqn: "HIGHEST" in str(eqn.params["precision"])
    dots = lambda jaxpr: [eqn for eqn in eqns_outside_kernels(jaxpr)
                          if eqn.primitive.name == "dot_general"]
    text = dots(rule_jaxpr(*args, grad=True))
    assert sum(map(bool, map(wide, text))) >= 3 <= sum(map(highest, text))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    kernels = dots(rule_jaxpr(*args, grad=True))
    assert kernels and not any(map(wide, kernels))
    assert not any(map(highest, kernels))
    # a sequence at a time (the kernels take both sequences' heads at once)
    assert {eqn.outvars[0].aval.shape for eqn in kernels} == {(h, 2, C, C)}
    assert {eqn.invars[2].aval.shape for eqn in eqns_outside_kernels(
        rule_jaxpr(*args)) if eqn.primitive.name == "pallas_call"} == {
            (2 * h, 2, C, dk)}


@pytest.mark.parametrize("right,dims,passes", [
    (jnp.float32, "NN", 6), (jnp.float32, "NT", 6), (jnp.bfloat16, "NN", 3)])
def test_the_kernels_float32_products_reach_float32s_accuracy(
        right, dims, passes, monkeypatch):
    """`_dot32` multiplies bfloat16 pieces (what the matrix unit takes):
    six stacked passes of two float32 sides, three where the right side is
    bfloat16 and so exact, either way within a few float32 roundings of
    the sum of magnitudes (one bfloat16 pass is at 4e-3, three of two
    float32 sides at 1e-5); the sizes span many binades, as the inverse's
    entries do against a decayed key."""
    ks = jax.random.split(jax.random.key(0), 4)
    a = jax.random.normal(ks[0], (64, 64)) * jnp.exp(
        6 * jax.random.normal(ks[1], (64, 64)))
    b = (jax.random.normal(ks[2], (64, 256)) * jnp.exp(
        6 * jax.random.normal(ks[3], (64, 1)))).astype(right)
    if dims == "NT":
        a, b = jax.random.normal(ks[0], (64, 256)), b.astype(jnp.float32)
    calls = []
    dot = rule_kernels._dot
    monkeypatch.setattr(rule_kernels, "_dot", lambda x, y, d: (
        calls.append((x.shape, x.dtype, y.dtype)), dot(x, y, d))[1])
    got = rule_kernels._dot32(a, b, getattr(rule_kernels, "_" + dims))
    exact = np.asarray(a, np.float64) @ (
        np.asarray(b.astype(jnp.float32), np.float64).T if dims == "NT"
        else np.asarray(b.astype(jnp.float32), np.float64))
    scale = np.abs(np.asarray(a, np.float64)) @ np.abs(
        np.asarray(b.astype(jnp.float32), np.float64).T if dims == "NT"
        else np.asarray(b.astype(jnp.float32), np.float64))
    assert np.max(np.abs(np.asarray(got, np.float64) - exact) / scale) < 1e-6
    # every pass bfloat16 x bfloat16, the left side's pieces stacked
    assert all(x == y == jnp.bfloat16 for _, x, y in calls)
    assert sum(shape[0] // 64 for shape, _, _ in calls) == passes
    assert len(calls) == (1 if right == jnp.bfloat16 else 3)


def test_a_batch_past_one_calls_tables_is_walked_a_call_at_a_time(
        monkeypatch):
    """A call takes the heads of as many sequences as its two scalar tables
    hold (`sequences_a_call`); past that the batch goes a call at a time,
    to the same value and gradients."""
    args = rule_inputs(128, widths=(128, 128))
    assert rule_kernels.sequences_a_call(2, 32, 128) == 2
    assert rule_kernels.sequences_a_call(8, 32, 128) == 4
    assert rule_kernels.sequences_a_call(6, 32, 128) == 3
    assert rule_kernels.sequences_a_call(5, 32, 128) == 1
    assert rule_kernels.sequences_a_call(2, 32, 1024) == 1
    rule = lambda *a: gated_delta_rule(*a, interpret=True)
    loss = lambda *a: (lambda o, S: jnp.sum(o * jnp.cos(o))
                       + jnp.sum(S * S))(*rule(*a))
    run = lambda: (rule(*args), jax.grad(loss, argnums=(0, 1, 2, 3, 4))(
        *args), [c for c in pallas_calls(jax.make_jaxpr(rule)(*args).jaxpr)])
    (o, S), grads, calls = run()
    monkeypatch.setattr(rule_kernels, "TABLE_SCALARS", 3 * 2)  # one sequence
    (o1, S1), grads1, calls1 = run()
    assert calls == calls1 == [("gdn_rule_fwd", 7)]     # there inside a loop
    for a, b in zip((o, S, *grads), (o1, S1, *grads1)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


# ---- the rule with a decay a channel: that its kernels engaged ----

def channel_inputs(t=128, widths=(128, 128)):
    """`rule_inputs` with a decay a channel under the bounded gate."""
    q, k, v, g, beta = rule_inputs(t, widths=widths)
    gate = -5.0 * jax.nn.sigmoid(jax.random.normal(
        jax.random.key(9), q.shape))
    return q, k, v, gate, beta


def channel_jaxpr(*args, grad=False):
    rule = lambda *a: channel_delta_rule(*a)
    if grad:
        rule = jax.grad(lambda *a: jnp.sum(channel_delta_rule(*a)[0]),
                        (0, 1, 2, 3, 4))
    return jax.make_jaxpr(rule)(*args).jaxpr


class _Said:
    """The program's tracer for a test: keeps the instants. Once a test of
    the same worker has run `train()`, `runtime/compile_cache.py`'s
    listener is registered for good and writes a compile span into
    whatever tracer is current (which files share a worker changes from
    run to run): its two calls are taken and dropped."""

    def __init__(self):
        self.instants = []

    def instant(self, name, **fields):
        self.instants.append((name, fields))

    def now(self):
        return 0.0

    def complete_span(self, name, start, end, **fields):
        pass


def test_off_the_tpu_or_at_other_widths_the_channel_rule_is_the_xla_text(
        monkeypatch):
    """The channel rule's kernels engage from what the call sees, as the
    scalar rule's: a TPU, widths that are multiples of 128, chunks of 64 in
    sub-blocks of 16. Anything else lowers with no Mosaic call, and the
    instant on the program's tracer says which path a trace took."""
    wide, narrow = channel_inputs(), channel_inputs(widths=(16, 8))
    said = _Said()
    monkeypatch.setattr(obs_trace, "_current", said)
    assert jax.default_backend() != "tpu"
    assert not pallas_calls(channel_jaxpr(*wide))
    text = jax.jit(lambda *a: channel_delta_rule(*a)).lower(*wide).as_text()
    assert "tpu_custom_call" not in text and "while" in text
    assert [f["path"] for _, f in said.instants] == ["xla", "xla"]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert not pallas_calls(channel_jaxpr(*narrow))
    assert not pallas_calls(jax.make_jaxpr(lambda *a: channel_delta_rule(
        *a, chunk=32))(*wide).jaxpr)
    assert len(pallas_calls(channel_jaxpr(*wide))) == 2
    assert [f["path"] for _, f in said.instants[2:]] == ["xla", "xla",
                                                         "kernel"]
    name, fields = said.instants[-1]
    assert name == "kda_rule" and fields == dict(
        path="kernel", heads=6, tokens=128, d_k=128, d_v=128, chunk=64,
        sub=SUB, dtype="float32", block=(6, 2))
    with pytest.raises(ValueError, match="multiples of 128"):
        channel_delta_rule(*narrow, interpret=True)


def test_the_channel_rules_kernels_are_not_read_as_flash_calls(monkeypatch):
    """Every Mosaic call of the channel rule and of its gradient is named
    `kda_rule_*` and has neither 3 nor 6 operands (benchmark/lib/kernels.py
    would read it as a flash call)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    args = channel_inputs()
    assert pallas_calls(channel_jaxpr(*args)) == [
        ("kda_rule_pairs", 2), ("kda_rule_fwd", 5)]
    calls = pallas_calls(channel_jaxpr(*args, grad=True))
    assert sorted(calls) == [("kda_rule_bwd", 8), ("kda_rule_fwd", 5),
                             ("kda_rule_pairs", 2)]
    for name, operands in calls:
        assert name.startswith("kda_rule_") and operands not in (3, 6)


def test_on_the_channel_kernel_path_no_sub_blocks_factor_is_left_to_xla(
        monkeypatch):
    """The mechanism engaged: on the kernel path (forward and gradient) no
    array outside a `pallas_call` has the decay factors' shape, a (B, C,
    d_k) or (B, sub, d_k) tail (four times the keys, the 65 ms of cell 12's
    step), and no product is at `HIGHEST` (the solve's were). The XLA text
    has both: the guard reads what it should."""
    C, dk = 64, 128
    args = channel_inputs()
    tails = {(C // SUB, C, dk), (C // SUB, SUB, dk)}
    shapes = lambda eqn: {v.aval.shape[-3:] for v in eqn.invars + eqn.outvars
                          if hasattr(v.aval, "shape")}
    factors = lambda jaxpr: [eqn for eqn in eqns_outside_kernels(jaxpr)
                             if shapes(eqn) & tails]
    highest = lambda jaxpr: [
        eqn for eqn in eqns_outside_kernels(jaxpr)
        if eqn.primitive.name == "dot_general"
        and "HIGHEST" in str(eqn.params["precision"])]
    for grad in (False, True):
        text = channel_jaxpr(*args, grad=grad)
        assert factors(text) and highest(text)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for grad in (False, True):
        kernels = channel_jaxpr(*args, grad=grad)
        assert pallas_calls(kernels)
        assert not factors(kernels) and not highest(kernels)


# ---- the state-space recurrence's kernels (ops/pallas/ssd.py) ----

def ssd_inputs(t=256, H=4, G=2, widths=(64, 128)):
    """x (1, t, H, P), dt, A, B and C (1, t, G, N) of the Mamba-2
    recurrence, P and N `widths`."""
    k = jax.random.split(jax.random.key(3), 4)
    return (jax.random.normal(k[0], (1, t, H, widths[0])),
            jax.nn.softplus(jax.random.normal(k[1], (1, t, H)) - 2.0),
            -(1.0 + jnp.arange(H, dtype=jnp.float32)),
            jax.random.normal(k[2], (1, t, G, widths[1])),
            jax.random.normal(k[3], (1, t, G, widths[1])))


def ssd_jaxpr(*args, grad=False, **kw):
    rule = lambda *a: ssd(*a, **kw)
    if grad:
        rule = jax.grad(lambda *a: jnp.sum(ssd(*a, **kw)[0]), (0, 1, 2, 3, 4))
    return jax.make_jaxpr(rule)(*args).jaxpr


def test_off_the_tpu_or_at_other_shapes_the_recurrence_is_the_xla_text(
        monkeypatch):
    """The recurrence's kernels engage from what the call sees, as the delta
    rules': a TPU, heads of 64 in pairs of a group, a state and a chunk in
    multiples of 128, float32 sums. Anything else lowers with no Mosaic
    call, and the instant `ssd` on the program's tracer says which path a
    trace took and at what shape."""
    held, narrow = ssd_inputs(), ssd_inputs(widths=(8, 4))
    said = _Said()
    monkeypatch.setattr(obs_trace, "_current", said)
    assert jax.default_backend() != "tpu"
    assert not pallas_calls(ssd_jaxpr(*held, chunk=128))
    text = jax.jit(lambda *a: ssd(*a, chunk=128)).lower(*held).as_text()
    assert "tpu_custom_call" not in text and "while" in text
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for args, kw in ((narrow, dict(chunk=128)), (held, dict(chunk=64)),
                     (ssd_inputs(H=3, G=3), dict(chunk=128)),
                     (held, dict(chunk=128, state_dtype=jnp.bfloat16))):
        assert not pallas_calls(ssd_jaxpr(*args, **kw))
    assert [f["path"] for _, f in said.instants] == ["xla"] * 6
    name, fields = said.instants[0]
    assert name == "ssd" and fields == dict(
        path="xla", heads=4, groups=2, tokens=256, chunk=128, head_dim=64,
        state=128, dtype="float32", block=None)
    assert len(pallas_calls(ssd_jaxpr(*held, chunk=128))) == 1
    assert pallas_calls(ssd_jaxpr(*ssd_inputs(H=32, G=2), chunk=256))
    assert [(name, f["path"], f["heads"], f["chunk"], f["block"])
            for name, f in said.instants[6:]] == [
        ("ssd", "kernel", 4, 128, 2), ("ssd", "kernel", 32, 256, 8)]


def test_the_recurrences_kernels_are_not_read_as_flash_calls(monkeypatch):
    """The Mosaic calls of the recurrence and of its gradient are named
    `ssd_fwd` / `ssd_bwd` and have neither 3 nor 6 operands
    (benchmark/lib/kernels.py would read either as a flash call)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    args = ssd_inputs()
    assert pallas_calls(ssd_jaxpr(*args, chunk=128)) == [("ssd_fwd", 5)]
    assert sorted(pallas_calls(ssd_jaxpr(*args, chunk=128, grad=True))) == [
        ("ssd_bwd", 7), ("ssd_fwd", 5)]


def test_on_the_recurrences_kernel_path_no_decay_of_a_chunk_is_left_to_xla(
        monkeypatch):
    """The mechanism engaged: on the kernel path (forward and gradient) no
    array outside a `pallas_call` ends in two `chunk` dimensions (the
    text's `gap`, `decay` and `mixed`, (b, c, G, R, Q, Q): 268 MB each in
    float32 a layer of cell 15), and what the forward keeps for the
    backward beside its inputs is the state each chunk ENTERED with, (b, c,
    N, H P) float32. The XLA text has the decays: the guard reads what it
    should."""
    Q, t, H, G = 128, 512, 4, 2
    args = ssd_inputs(t, H, G)
    shapes = lambda eqn: [v.aval.shape for v in eqn.invars + eqn.outvars
                          if hasattr(v.aval, "shape")]
    decays = lambda jaxpr: [eqn for eqn in eqns_outside_kernels(jaxpr)
                            if any(shape[-2:] == (Q, Q)
                                   for shape in shapes(eqn))]
    for grad in (False, True):
        assert decays(ssd_jaxpr(*args, chunk=Q, grad=grad))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for grad in (False, True):
        kernels = ssd_jaxpr(*args, chunk=Q, grad=grad)
        assert pallas_calls(kernels) and not decays(kernels)
    forward, = [eqn for eqn in eqns_outside_kernels(kernels)
                if eqn.primitive.name == "pallas_call"
                and eqn.params["name"] == "ssd_fwd"]
    assert [(v.aval.shape, str(v.aval.dtype)) for v in forward.outvars] == [
        ((1, t, H * 64), "float32"), ((1, t // Q, 128, H * 64), "float32")]



def test_the_delta_mixer_takes_the_kernel_path_inside_shard_map(monkeypatch):
    """The call sits inside `shard_map`, per-shard heads: traced for two
    shards at the published head widths, the mixer's rule is the kernels'
    and their outputs carry the shards' varying axes."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    kda = KimiDeltaAttention(64, 4, 128, 128, tp_size=2)
    params = jax.eval_shape(kda.init, jax.random.key(0))
    x = jax.ShapeDtypeStruct((2, 128, 64), jnp.float32)
    mesh = mesh_of(2)
    fn = jax.shard_map(lambda p, x: kda.apply(p, x)[0], mesh=mesh,
                       in_specs=(kda.specs(), jax.sharding.PartitionSpec()),
                       out_specs=jax.sharding.PartitionSpec())
    loss = lambda p, x: jnp.sum(fn(p, x))
    calls = pallas_calls(jax.make_jaxpr(jax.grad(loss))(params, x).jaxpr)
    assert sorted(set(calls)) == [("kda_rule_bwd", 8), ("kda_rule_fwd", 5),
                                  ("kda_rule_pairs", 2)]


# ---- the flash kernel at width 256 and a group of 8 ----

def test_flash_at_width_256_and_a_group_of_8_equals_the_xla_path():
    """16 query heads over 2 key-value heads, 256 wide, 384 tokens in
    blocks of 128: a multi-block grid (the split backward, dk/dv summed
    over the group of 8)."""
    key = jax.random.key(0)
    t = 384
    q = jax.random.normal(jax.random.fold_in(key, 1), (1, 16, t, 256))
    k = jax.random.normal(jax.random.fold_in(key, 2), (1, 2, t, 256))
    v = jax.random.normal(jax.random.fold_in(key, 3), (1, 2, t, 256))
    blocks = dict.fromkeys(
        ("block_q", "block_k", "bwd_block_q", "bwd_block_k"), 128)
    flash = lambda q, k, v: jnp.sum(jnp.sin(flash_attention(
        q, k, v, interpret=True, **blocks)))
    plain = lambda q, k, v: jnp.sum(jnp.sin(causal_attention_xla(q, k, v)))
    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(flash, (0, 1, 2))(q, k, v)
        want = jax.value_and_grad(plain, (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(a, b, atol=5e-5)


# ---- partial RoPE and the norms ----

def test_partial_rope_turns_the_leading_slice_and_leaves_the_rest():
    x = jax.random.normal(jax.random.key(0), (2, 3, 8, 16))
    pos = jnp.tile(jnp.arange(8)[None], (2, 1))
    cos, sin = rope_angles(pos, 4, 100.0)
    got = np.asarray(apply_rotary_leading(x, cos, sin, 4))
    np.testing.assert_array_equal(got[..., 4:], np.asarray(x[..., 4:]))
    # half-split pairs (x_i, x_{i+2}) of the first four, as complex numbers
    z = np.asarray(x[..., 0:2]) + 1j * np.asarray(x[..., 2:4])
    theta = 100.0 ** (-np.arange(0, 4, 2) / 4)
    w = z * np.exp(1j * np.arange(8)[None, None, :, None] * theta)
    np.testing.assert_allclose(got[..., 0:2], w.real, atol=1e-5)
    np.testing.assert_allclose(got[..., 2:4], w.imag, atol=1e-5)
    np.testing.assert_array_equal(got[:, :, 0], np.asarray(x[:, :, 0]))


def test_the_zero_centred_and_the_gated_norm():
    x = jax.random.normal(jax.random.key(0), (4, 16)) * 3
    z = jax.random.normal(jax.random.key(1), (4, 16))
    rms = np.sqrt(np.mean(np.square(x), -1, keepdims=True) + 1e-6)
    zc = ZeroCenteredRMSNorm(16)
    p = zc.init(jax.random.key(2))
    assert not np.any(p["scale"])                   # from zeros: (1 + w) = 1
    np.testing.assert_allclose(zc.apply(p, x), x / rms, rtol=1e-5)
    np.testing.assert_allclose(zc.apply({"scale": p["scale"] + 0.5}, x),
                               1.5 * x / rms, rtol=1e-5)
    gated = GatedRMSNorm(16)
    w = gated.init(jax.random.key(2))
    assert np.all(np.asarray(w["scale"]) == 1.0)
    np.testing.assert_allclose(gated.apply(w, x, z),
                               x / rms * jax.nn.silu(z), rtol=1e-5)


# ---- the expert layer: shares, and no drop ----

def gated_shared(p, x):
    xf, sh = x.reshape(-1, x.shape[-1]), p["shared"]
    out = ((jax.nn.silu(xf @ sh["gate"]) * (xf @ sh["up"])) @ sh["down"]
           * jax.nn.sigmoid(xf @ sh["gate_score"]))
    return out.reshape(x.shape)


def test_the_sixteen_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Sixteen jobs hold two experts each of one layer's 32. Their routed
    parts, plus the GATED shared expert once, are the layer a job holding
    all 32 computes: the softmax weights are normalised over all chosen
    experts, held or not."""
    d, f, E = 32, 16, 32
    whole = SharedRoutedFFN(d, f, E, top_k=5, score="softmax",
                            shared_gate=True)
    p = whole.init(jax.random.key(1))
    assert "bias" not in p and p["shared"]["gate_score"].shape == (d, 1)
    x = jax.random.normal(jax.random.key(2), (2, 64, d))
    with jax.default_matmul_precision("highest"):
        want, counters = apply_moe(whole, p, x)
        shared_only = gated_shared(p, x)
        total, rows = shared_only, 0.0
        for lo in range(0, E, 2):
            share = dataclasses.replace(whole, held=2, offset=lo)
            ps = {**p, **{n: p[n][lo:lo + 2] for n in ("gate", "up", "down")}}
            y, c = apply_moe(share, ps, x)
            total = total + (y - shared_only)
            rows += float(c["rows_here"])
            np.testing.assert_array_equal(c["routed"], counters["routed"])
    np.testing.assert_allclose(total, want, atol=2e-5)
    assert rows == float(counters["rows_here"]) == 2 * 64 * 5


def test_a_softmax_router_forced_onto_the_same_experts_drops_nothing():
    """Router columns that send EVERY token to experts 0..2, all held and
    far over any mean share: each (token, choice) pair is computed, in
    several chunks, and the layer equals the dense sum over those experts
    with softmax weights renormalised over the three."""
    d, f, E, k = 32, 16, 64, 3
    moe = SharedRoutedFFN(d, f, E, top_k=k, held=4, score="softmax",
                          shared_gate=True)
    p = moe.init(jax.random.key(1))
    x = jnp.abs(jax.random.normal(jax.random.key(2), (4, 214, d))) + 0.1
    # positive tokens, and the first k columns large and positive
    p["router"] = p["router"].at[:, :k].add(5.0)
    assert moe.chunk_rows(4 * 214 * k) == 512     # six chunks, every one live
    with jax.default_matmul_precision("highest"):
        got, counters = apply_moe(moe, p, x)
        xf = x.reshape(-1, d)
        s = jax.nn.softmax(xf @ p["router"], axis=-1)[:, :k]
        w = s / jnp.sum(s, axis=-1, keepdims=True)
        ffn = lambda g, u, dn: (jax.nn.silu(xf @ g) * (xf @ u)) @ dn
        want = sum(w[:, e:e + 1] * ffn(p["gate"][e], p["up"][e], p["down"][e])
                   for e in range(k))
        want = want + gated_shared(p, x).reshape(-1, d)
    assert float(counters["rows_here"]) == 4 * 214 * k
    assert float(counters["rows_walked"]) == 6 * 512
    np.testing.assert_array_equal(
        counters["routed"], np.where(np.arange(E) < k, 4 * 214, 0))
    np.testing.assert_allclose(got.reshape(-1, d), want, atol=2e-5)


# ---- the step, its counters, the entry point ----

def test_the_train_step_returns_a_row_of_counters_a_layer_and_the_loss_falls():
    cfg = tiny()
    losses, (_, gnorm, c), _ = R.train(cfg)
    assert losses[-1] < losses[0] and np.isfinite(float(gnorm))
    # one row a layer, in the order the layers run (two periods of four)
    assert c["routed"].shape == (8, 8) and c["rows_here"].shape == (8,)
    np.testing.assert_array_equal(c["routed"].sum(-1), [2 * 64 * 2] * 8)
    assert abs(float(c["loss_main"]) - losses[-1]) < 1e-5
    summary = moe_counters_summary(jax.device_get(c), cfg, 2 * 64)
    assert summary["rows_here_per_token"] == 2.0    # all experts held
    assert summary["rows_computed_per_token"] == 2.0
    assert summary["rows_walked_per_token"] == 2.0
    assert summary["load_max_over_mean"] >= 1.0


def test_train_cli_runs_the_family(tmp_path, capsys):
    from distributed_pytorch_from_scratch_tpu import train as train_mod
    tokens = token_file(tmp_path)
    train_mod.main([
        "--family", "gdn_moe", "--model", "tiny-gdn-moe", "--tp_size", "2",
        "--data_path", str(tokens), "--save_dir", str(tmp_path / "ckpt"),
        "--batch_size", "4", "--maxlen", "64", "--max_steps", "4",
        "--log_interval", "2", "--save_interval", "100",
        "--warmup_steps", "2"])
    out = capsys.readouterr().out
    assert "model[gdn_moe]" in out and "rows_here_per_token" in out
    assert "rows_computed_per_token" in out
    assert "rows_walked_per_token" in out
    events = [json.loads(line) for line in
              open(tmp_path / "ckpt" / "logs" / "metrics.jsonl")]
    assert any(e.get("tag") == "moe_counters" for e in events)
    with pytest.raises(SystemExit, match="reads the config field"):
        train_mod.main(["--family", "mla_moe", "--model", "tiny-gdn-moe",
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
    (dict(tp_size=4), "not divisible by tp_size"),   # 2 key-value heads
])
def test_the_model_refuses_what_it_does_not_run(kw, message):
    with pytest.raises(ValueError, match=message):
        build_model("gdn_moe", tiny(), **kw)


@pytest.mark.parametrize("cfg,message", [
    (ModelConfig(num_experts=8), "needs cfg.gdn_moe"),
    (dataclasses.replace(model_preset("tiny-gdn-moe"), num_layers=6),
     "whole periods"),
])
def test_a_family_needs_its_own_facts_and_whole_periods(cfg, message):
    with pytest.raises(ValueError, match=message):
        build_model("gdn_moe", cfg)


# ---- the counts at the published widths ----

def published(held=32, vocab=18992, layers=4):
    return ModelConfig(
        attn_dim=2048, ffn_dim=512, num_heads=16, num_kv_heads=2,
        num_layers=layers, vocab_size=vocab, maxlen=8192, rope_theta=1e7,
        num_experts=512, moe_top_k=10, gdn_moe=GdnMoEConfig(
            head_dim=256, linear_num_key_heads=16, linear_num_value_heads=32,
            linear_key_head_dim=128, linear_value_head_dim=128,
            moe_intermediate_size=512, shared_expert_intermediate_size=512,
            experts_held=held))


def test_parameter_counts_at_the_published_widths():
    """One chip's share (32 of 512 experts, an eighth of the vocabulary, one
    period of three linear layers and one full layer): 625,667,136, as
    `init` makes them."""
    cfg = published()
    parts = GdnMoETransformer.param_counts(cfg)
    assert parts["gdn_layers"] == 3 * 138_582_208
    assert parts["attn_layers"] == 132_127_232
    assert parts["embedding_and_head"] == 77_791_232
    assert cfg.num_params() == 625_667_136
    model = build_model("gdn_moe", cfg)
    assert model._mods["gdn"].num_params() == 33_718_464
    assert model._mods["attn"].num_params() == 27_263_488
    made = jax.eval_shape(model.init, jax.random.key(0))
    assert sum(x.size for x in jax.tree.leaves(made)) == cfg.num_params()
    # uncut, a layer's FFN is 1,614,809,088
    uncut = GdnMoETransformer.param_counts(published(held=None))
    assert (uncut["attn_layers"] - 27_263_488 - 4096) == 1_614_809_088
    # the step's FLOPs count the held experts at a token's mean share of
    # them (10 x 32/512 = 0.625 a layer): 469 MFLOP a token forward with
    # the scores counted causally, 536 with the full square the program's
    # convention counts (4 x 16 heads x 8192 x 256 = 134 M, not 67)
    flops = model_flops_per_step(cfg, 2, 8192, cfg.num_params())
    assert abs(flops / (2 * 8192) / 3 / 536e6 - 1) < 0.01


def test_remat_auto_keeps_the_flash_outputs_in_the_benchmarks_cell(capsys):
    """`remat="auto"` at the cell's shapes on a v5e's 15.75 GiB, since PR
    62: rung 'flash' (the one attention layer's kernel outputs are kept;
    the state is 7 GiB, no snapshot of it fits beside even the floor, so
    no reserve is held), from an estimate of 14.42 GiB where the chip
    counts 14.11 at 'true' and at 'flash' alike (in use + reserved; the
    family's count is set from that reading: it read 12.9, under the chip,
    until then); 'dots', 14.70, is over the margin's 14.65."""
    from distributed_pytorch_from_scratch_tpu.training import memory
    cfg = dataclasses.replace(published(), compute_dtype="bfloat16")
    model = build_model("gdn_moe", cfg, remat_budget_gib=15.748)
    layer_params = cfg.num_params() - 77_791_232 - 2048
    memory.select_remat_traced.cache_clear()
    assert memory.select_remat_traced(model, cfg.num_params(), layer_params,
                                      2, 8192) == "flash"
    said = capsys.readouterr().err
    assert "reserve_held=False" in said
    estimate = float(said.split("flash=")[1].split("GiB")[0])
    assert 0.99 * 14.11 < estimate < 1.05 * 14.11, said
