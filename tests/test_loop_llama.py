"""The `loop_llama` family (models/loop_llama.py): the llama block with four
norms a layer whose stack a step passes R times over the SAME weights, an
exit after every pass and a learned exit gate that weighs the exits' losses.
CPU, tiny sizes, R = 3 (a pass count of 1 or 2 cannot agree by accident)
and R = 1.

* the program against the plain reference (models/vanilla_loop_llama.py: a
  Python loop of passes over a Python loop of layers, R full logit tensors,
  the loss by the equations): the loss, the R exit losses, `p`, and EVERY
  gradient leaf, at tp 1, tp 2 (sequence parallelism over the rings), dp 2
  and under the flash kernels (the interpreter);
* the weight gradient against an UNROLLED reference of R x L distinct
  layers set to equal values, its gradients summed over the copies;
* `sum_r p_r = 1`, the last step takes the remainder; R = 1 is the llama
  block with the two post-norms passed once (p = 1, H = 0, the gate gets no
  gradient);
* a reference that ran R - 1 passes, or fed a pass the un-normed state, is
  another model by far more than the tolerance;
* every rung of the remat ladder and `remat=False` give one loss and
  gradient; the exits' logits are made again in the backward, not kept;
* what the family does not run is refused with its reason; the step, the
  counters, the CLI's `loop_counters` event; the counts (612,438,017 at the
  cell's cut, a step's FLOPs R-fold); the memory estimate's passes; the pin
  of `benchmark/families/loop_llama.py` to the vanilla file.
"""

import dataclasses
import functools
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from family_recipe import (Recipe, lowered_step, lowered_text, mesh_of,
                           token_file, worst_leaf)

from distributed_pytorch_from_scratch_tpu.config import (
    LoopLlamaConfig, ModelConfig, OptimizerConfig, model_preset)
from distributed_pytorch_from_scratch_tpu.models import build_model
from distributed_pytorch_from_scratch_tpu.models.loop_llama import (
    LoopedTransformer)
from distributed_pytorch_from_scratch_tpu.models.stack import REMAT_RUNGS
from distributed_pytorch_from_scratch_tpu.models.vanilla_loop_llama import (
    exit_distribution, vanilla_loss)
from distributed_pytorch_from_scratch_tpu.obs import schema
from distributed_pytorch_from_scratch_tpu.obs.attribution import (
    analytic_phases)
from distributed_pytorch_from_scratch_tpu.training import memory
from distributed_pytorch_from_scratch_tpu.training.metrics import (
    loop_counters_summary, model_flops_per_step)
from distributed_pytorch_from_scratch_tpu.training.train_step import (
    build_train_step)

ROOT = pathlib.Path(__file__).resolve().parent.parent


# the family's own: its reference hands back the exits' detail beside the
# loss, and the program's side of it is its counters
R = Recipe("loop_llama", vanilla_loss)
tiny, batch, on_mesh = R.tiny, R.batch, R.on_mesh
reference = functools.partial(R.reference, has_aux=True, detail=True)
program = functools.partial(R.program, with_counters=True)


# ---- the program against the plain reference ----

@pytest.mark.parametrize("tp,dp,impl,t", [
    (1, 1, "xla", 64), (2, 1, "xla", 64), (1, 2, "xla", 64),
    (1, 1, "flash_interpret", 128)])
def test_loss_exits_p_and_every_gradient_leaf_equal_the_reference(
        tp, dp, impl, t):
    """Two layers SCANNED inside a scan of three passes (the program)
    against three Python passes over two Python layers (the reference). At
    tp 2 the model picks sequence parallelism over the ring matmuls, so a
    shard weighs its own rows of the exits. Leaves to 1e-4 of their largest
    entry (the gate's bias is one number: a sum of 128 terms of both
    signs)."""
    cfg = tiny()
    params, ((want, detail), want_g) = reference(cfg, t)
    (got, counters), got_g = program(cfg, tp, dp, t, attn_impl=impl)
    assert abs(float(got) - float(want)) <= 1e-5 * abs(float(want))
    assert len(jax.tree.leaves(got_g)) == 16
    err, at = worst_leaf(want_g, got_g)
    assert err <= 1e-4, at
    counters = jax.device_get(counters)
    assert counters["loss_exit"].shape == (3,)
    np.testing.assert_allclose(counters["loss_exit"], detail["loss_exit"],
                               rtol=1e-5)
    np.testing.assert_allclose(counters["exit_p_mean"],
                               detail["exit_p_mean"], rtol=1e-5)
    np.testing.assert_allclose(counters["exit_entropy"],
                               detail["exit_entropy"], rtol=1e-5)
    assert float(counters["loss_main"]) == float(got)
    if tp == 2:
        _, model = on_mesh(cfg, tp)
        assert model.tp_layout(t) == (True, "ring")


def test_the_shared_gradient_is_the_sum_over_unrolled_copies():
    """R x L = 6 DISTINCT layers set to the 2 shared layers' values, three
    times over: the unrolled model's gradients, summed over a layer's three
    copies, are the shared layers' gradients as the program makes them (a
    gradient taken from one pass is a third of the work and another
    number)."""
    cfg = tiny()
    params, _ = reference(cfg)
    (_, _), got_g = program(cfg)
    ids, tgt, pos = batch(cfg)
    R, L = 3, cfg.num_layers
    unrolled = {**params, "layers": jax.tree.map(
        lambda a: jnp.tile(a, (R,) + (1,) * (a.ndim - 1)), params["layers"])}
    with jax.default_matmul_precision("highest"):
        grads = jax.jit(jax.grad(lambda p: vanilla_loss(
            cfg, p, ids, tgt, pos, unrolled=True)))(unrolled)
    copies = jax.tree.map(lambda a: a.reshape(R, L, *a.shape[1:]),
                          grads["layers"])
    summed = jax.tree.map(lambda a: a.sum(0), copies)
    err, at = worst_leaf(summed, got_g["layers"])
    assert err <= 1e-4, at
    # one pass's share alone is far off: the sum is load-bearing
    err, _ = worst_leaf(jax.tree.map(lambda a: a[-1], copies),
                           got_g["layers"])
    assert err > 0.2
    others = lambda g: {k: v for k, v in g.items() if k != "layers"}
    err, at = worst_leaf(others(grads), others(got_g))
    assert err <= 1e-4, at


def test_p_sums_to_one_and_the_last_step_takes_the_remainder():
    z = jax.random.normal(jax.random.key(0), (4, 5, 7)) * 3.0
    p = np.asarray(exit_distribution(z), np.float64)
    np.testing.assert_allclose(p.sum(0), 1.0, atol=1e-6)
    lam = 1.0 / (1.0 + np.exp(-np.asarray(z, np.float64)))
    np.testing.assert_allclose(p[0], lam[0], rtol=1e-5)
    np.testing.assert_allclose(p[1], lam[1] * (1 - lam[0]), rtol=1e-4)
    np.testing.assert_allclose(p[3], np.prod(1 - lam[:3], axis=0),
                               rtol=1e-4, atol=1e-9)
    # the program's p (from log-sigmoids) is the same distribution
    cfg = tiny()
    params, ((_, detail), _) = reference(cfg)
    (_, counters), _ = program(cfg)
    assert float(np.sum(counters["exit_p_mean"])) == pytest.approx(1.0,
                                                                   abs=1e-6)
    np.testing.assert_allclose(np.asarray(detail["p"]).sum(0), 1.0,
                               atol=1e-6)
    # a gate that says 1/2 everywhere: (1/2, 1/4, 1/8, 1/8), step 1.875
    half = np.asarray(exit_distribution(jnp.zeros((4, 2))))
    np.testing.assert_allclose(half[:, 0], [0.5, 0.25, 0.125, 0.125])
    summary = loop_counters_summary({
        "loss_main": 1.0, "exit_entropy": 1.2, "loss_exit": half[:, 0],
        "exit_p_mean": half[:, 0]})
    assert summary["exit_step_mean"] == pytest.approx(1.875)
    assert set(schema.EVENT_REQUIRED["loop_counters"]) <= set(summary)


class PassedOnce(LoopedTransformer):
    """The family's block (four norms, no bias) on the stack's ordinary
    path: the pattern once, the final norm and the head once, the plain
    mean CE. What R = 1 must equal."""
    loop_steps = None


def test_one_pass_is_the_llama_block_with_the_two_post_norms():
    cfg = tiny(loop_steps=1)
    params, ((want, _), want_g) = reference(cfg)
    (got, counters), got_g = program(cfg)
    assert abs(float(got) - float(want)) <= 1e-5 * abs(float(want))
    np.testing.assert_array_equal(counters["exit_p_mean"], [1.0])   # p = 1
    assert float(counters["exit_entropy"]) == 0.0                   # H = 0
    assert not np.any(np.asarray(got_g["exit_gate"]["weight"]))
    assert float(got_g["exit_gate"]["bias"]) == 0.0
    mesh = mesh_of()
    once = PassedOnce(cfg, attn_impl="xla")
    assert once.post_attn_norm_key and once.post_ffn_norm_key
    plain = {k: v for k, v in params.items() if k != "exit_gate"}
    assert jax.tree.structure(plain) == jax.tree.structure(
        jax.eval_shape(once.init, jax.random.key(0)))
    ids, tgt, pos = batch(cfg)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(once.make_loss(mesh)))(
            plain, ids, tgt, pos)
    assert abs(float(got) - float(loss)) <= 1e-6 * abs(float(loss))
    err, at = worst_leaf(grads, {k: got_g[k] for k in plain})
    assert err <= 1e-5, at


@pytest.mark.parametrize("variant", [dict(passes=2),
                                     dict(norm_between=False)])
def test_fewer_passes_or_no_norm_between_them_is_another_model(variant):
    cfg = tiny()
    params, ((want, detail), want_g) = reference(cfg)
    _, ((other, other_detail), other_g) = reference(cfg, **variant)
    if "passes" in variant:
        assert other_detail["loss_exit"].shape == (2,)
    else:
        # the first exit reads the same state; the later passes do not
        np.testing.assert_allclose(other_detail["loss_exit"][0],
                                   detail["loss_exit"][0], rtol=1e-6)
        assert abs(float(other_detail["loss_exit"][2])
                   - float(detail["loss_exit"][2])) > 1e-3
    assert worst_leaf(want_g["layers"], other_g["layers"])[0] > 0.05
    (got, _), got_g = program(cfg)
    assert worst_leaf(want_g["layers"], got_g["layers"])[0] <= 1e-4


def test_bfloat16_stays_in_its_band():
    cfg = tiny("bfloat16")
    params, ((want, _), _) = reference(tiny())
    (got, counters), _ = program(cfg, params_of=tiny())
    assert abs(float(got) - float(want)) <= 2e-2 * abs(float(want))
    assert float(np.sum(counters["exit_p_mean"])) == pytest.approx(
        1.0, abs=1e-5)


# ---- the remat ladder, and the exits' logits ----

@pytest.mark.parametrize("remat", [False, *REMAT_RUNGS])
def test_every_remat_rung_gives_the_same_loss_and_gradients(remat):
    cfg = tiny()
    params, ((want, _), want_g) = reference(cfg)
    tp = 2 if remat in ("attn_proj", "dots") else 1
    (got, _), got_g = program(cfg, tp, attn_impl="xla", remat=remat)
    assert abs(float(got) - float(want)) <= 1e-5 * abs(float(want))
    err, at = worst_leaf(want_g, got_g)
    assert err <= 1e-4, at


def test_an_exits_logits_are_made_again_in_the_backward_not_kept():
    """No (R, b, t, vocabulary) tensor anywhere in the gradient's jaxpr:
    the exits are one scan whose body is a checkpoint, so what the scan
    keeps a pass is the normed state."""
    cfg = tiny()
    mesh, model = on_mesh(cfg)
    ids, tgt, pos = batch(cfg)
    params = jax.eval_shape(model.init, jax.random.key(0))
    text = str(jax.make_jaxpr(jax.grad(model.make_loss(mesh)))(
        params, ids, tgt, pos))
    assert f"[3,2,64,{cfg.vocab_size}]" not in text
    assert f"[2,64,{cfg.vocab_size}]" in text       # one exit's, in a body
    assert "[3,2,64,64]" in text                    # the R states


def _eqns(jaxpr):
    """Every equation of `jaxpr` and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def test_the_layers_gradient_is_one_stack_in_the_backward_walks_carry():
    """The gradient's jaxpr: ONE reverse scan whose carry holds a float32
    stack of every layer leaf's shape (the sums a layer application adds its
    slice into), no `add` of two whole stacks anywhere (the transpose of a
    scan of passes around a scan of layers made one a pass), and no R x L
    copies of a weight among what the walk keeps of its steps."""
    cfg = tiny()
    mesh, model = on_mesh(cfg)
    ids, tgt, pos = batch(cfg, t=96)    # (no activation is a weight's shape)
    params = jax.eval_shape(model.init, jax.random.key(0))
    jaxpr = jax.make_jaxpr(jax.grad(model.make_loss(mesh)))(
        params, ids, tgt, pos).jaxpr
    stacks = sorted(leaf.shape for leaf in jax.tree.leaves(params["layers"]))
    L, R = cfg.num_layers, cfg.loop_llama.loop_steps
    carried = []
    for eqn in _eqns(jaxpr):
        for var in eqn.outvars:
            shape = var.aval.shape
            assert not (eqn.primitive.name in ("add", "add_any")
                        and len(shape) == 3 and shape in stacks), eqn
            assert not (len(shape) >= 3 and shape[0] in (R, R * L) and any(
                shape[-2:] == stack[-2:] for stack in stacks
                if len(stack) == 3)), eqn
        if eqn.primitive.name == "scan":
            n = eqn.params["num_consts"], eqn.params["num_carry"]
            carry = sorted(v.aval.shape for v in eqn.invars[n[0]:sum(n)]
                           if v.aval.shape in stacks
                           and v.aval.dtype == jnp.float32)
            if carry:
                assert carry == stacks and eqn.params["reverse"]
                assert eqn.params["length"] == R * L
                carried.append(eqn)
    assert len(carried) == 1


# ---- refusals ----

@pytest.mark.parametrize("kw,message", [
    (dict(pp_size=2), "pp_size > 1 .a pipeline whose stages"),
    (dict(cp_size=2), "cp_size > 1"),
    (dict(attn_t_real=50), "attn_t_real"),
    (dict(zero3_axis="dp"), "ZeRO stage 3"),
])
def test_what_the_family_does_not_run_is_refused_where_it_is_built(
        kw, message):
    with pytest.raises(ValueError, match=message):
        build_model("loop_llama", tiny(), **kw)


@pytest.mark.parametrize("cfg,message", [
    (model_preset("tiny"), "needs cfg.loop_llama"),
    (dataclasses.replace(tiny(), num_experts=4), "layers are dense"),
    (tiny(loop_steps=0), "at least once"),
])
def test_a_family_needs_its_own_facts(cfg, message):
    with pytest.raises(ValueError, match=message):
        build_model("loop_llama", cfg)


def test_decode_and_the_hand_reduced_gradients_are_refused():
    from distributed_pytorch_from_scratch_tpu.models.decode import (
        GreedyDecoder)
    mesh, model = on_mesh(tiny())
    assert not model.decodable and not model.hand_reduced_grads
    with pytest.raises(ValueError, match="cannot be decoded or served"):
        GreedyDecoder(model, mesh, 32)
    with pytest.raises(ValueError, match="ZeRO stage 2 is not made to work"):
        build_train_step(model, mesh, OptimizerConfig(), zero=2)


def test_the_forward_hands_back_the_last_exits_logits():
    cfg = tiny()
    mesh, model = on_mesh(cfg, attn_impl="xla")
    params, _ = reference(cfg)
    ids, tgt, pos = batch(cfg)
    with jax.default_matmul_precision("highest"):
        logits = model.make_forward(mesh)(params, ids, pos)
    assert logits.shape == (2, 64, cfg.vocab_size)
    ce = (jax.nn.logsumexp(logits, -1)
          - jnp.take_along_axis(logits, tgt[..., None], -1)[..., 0])
    _, ((_, detail), _) = reference(cfg)
    assert float(ce.mean()) == pytest.approx(
        float(detail["loss_exit"][-1]), rel=1e-5)


# ---- the step, the counters, the CLI ----

def test_the_train_step_trains_and_counts_its_exits():
    cfg = tiny()
    losses, (_, _, c), (mesh, model, *_) = R.train(
        cfg, tp=1, steps=8, b=4, max_steps=20000, attn_impl="xla")
    assert np.isfinite(losses).all() and min(losses[-3:]) < losses[0]
    c = jax.device_get(c)
    assert set(c) == {"loss_main", "loss_exit", "exit_p_mean",
                      "exit_entropy"}
    assert c["loss_exit"].shape == c["exit_p_mean"].shape == (3,)
    # a step over one ZeRO-1 state runs too (llama's)
    build_train_step(model, mesh, OptimizerConfig(), zero=1)


def test_train_cli_runs_the_family(tmp_path, capsys):
    from distributed_pytorch_from_scratch_tpu import train as train_mod
    tokens = token_file(tmp_path)
    train_mod.main([
        "--family", "loop_llama", "--model", "tiny-loop-llama",
        "--tp_size", "2", "--data_path", str(tokens),
        "--save_dir", str(tmp_path / "ckpt"), "--batch_size", "4",
        "--maxlen", "64", "--max_steps", "4", "--log_interval", "2",
        "--save_interval", "100", "--warmup_steps", "2"])
    out = capsys.readouterr().out
    assert "model[loop_llama]" in out and "exit_step_mean" in out
    assert "loss_exit_3" in out and "exit_p_3" in out
    with pytest.raises(SystemExit, match="reads the config field"):
        train_mod.main(["--family", "llama", "--model", "tiny-loop-llama",
                        "--data_path", str(tokens),
                        "--save_dir", str(tmp_path / "x")])


# ---- the counts and the memory estimate ----

def published(num_layers=48, **facts):
    return ModelConfig(
        attn_dim=2048, ffn_dim=5632, num_heads=16, num_kv_heads=16,
        num_layers=num_layers, vocab_size=49152, maxlen=65536,
        rope_theta=1e6, compute_dtype="bfloat16",
        loop_llama=LoopLlamaConfig(loop_steps=4, **facts))


def test_the_cut_at_the_published_widths_counts_612_438_017():
    assert LoopedTransformer.num_params(published()) == 2_667_974_657
    cut = published(8)
    counts = LoopedTransformer.param_counts(cut)
    assert counts["layers"] == 8 * 51_388_416
    assert counts["embedding_and_head"] == 201_326_592
    assert (counts["final_norm"], counts["exit_gate"]) == (2048, 2049)
    assert sum(counts.values()) == cut.num_params() == 612_438_017
    model = build_model("loop_llama", cut)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 612_438_017
    assert shapes["exit_gate"]["weight"].dtype == jnp.float32
    assert "bias" not in shapes["layers"]["wq"]
    assert "bias" not in shapes["lm_head"]


def test_a_steps_flops_are_r_times_what_the_parameters_say():
    cut = published(8)
    n = cut.num_params()
    flops = model_flops_per_step(cut, 1, 4096, n)
    matmul = 8 * (4 * 2048 ** 2 + 3 * 2048 * 5632) + 49152 * 2048
    assert matmul == 511_705_088
    attention = 12 * 8 * 16 * 128 * 4096 * 4096
    assert flops == 4 * (6.0 * matmul * 4096 + attention)
    once = dataclasses.replace(cut, loop_llama=LoopLlamaConfig(loop_steps=1))
    assert flops == 4 * model_flops_per_step(once, 1, 4096, n)
    # obs/attribution's phases run the layers R x L times, the head R times
    by_name = lambda cfg: {p.name: p.flops for p in analytic_phases(
        cfg, 1, 4096, "true", family="loop_llama")}
    four, one = by_name(cut), by_name(once)
    for name in ("qkv_proj", "attention", "wo_proj", "ffn", "lm_head",
                 "ce_loss"):
        assert four[name] == 4 * one[name], name
    assert four["adam"] == one["adam"] and four["embed"] == one["embed"]
    assert four["lm_head"] + four["qkv_proj"] + four["wo_proj"] \
        + four["ffn"] == 4 * 2 * matmul * 4096


def test_the_programs_count_and_the_benchmarks_count_are_one_number():
    """`flops_per_step` (what a run's MFU is made of), `obs/attribution`'s
    phases and `benchmark/lib/loop_llama_counts.py` (the numerator of the
    cell's `train_step.mfu_pct`) agree at the cell's shape."""
    import sys
    sys.path.insert(0, str(ROOT))
    from benchmark.lib import loop_llama_counts as counts
    cut = published(8)
    sizes = counts.LoopLlamaSizes(
        d_model=2048, n_layer=8, n_head=16, n_kv_head=16, head_dim=128,
        d_ff=5632, vocab=49152, passes=4)
    assert counts.param_counts(sizes)["total"] == cut.num_params()
    per_token = counts.train_flops_per_token(sizes, 4096)
    assert model_flops_per_step(cut, 1, 4096, cut.num_params()) \
        == 4096 * per_token
    phases = {p.name: p.flops for p in analytic_phases(
        cut, 1, 4096, "true", family="loop_llama")}
    matmuls = sum(phases[n] for n in ("qkv_proj", "wo_proj", "ffn",
                                      "lm_head"))
    assert 3 * matmuls == 4096 * 6.0 * 4 * counts.matmul_params_per_pass(
        sizes)


def test_the_memory_estimate_reads_the_passes():
    """R x L kept layer inputs and named stacks, the R states, one exit's
    logits, and the layers' gradient ONCE, as a stack that is passed once
    counts it (the backward walk adds into the one stack)."""
    cut = published(8)
    model = build_model("loop_llama", cut)
    n, layers = cut.num_params(), 8 * 51_388_416
    four = memory.traced_step_bytes(model, n, layers, 1, 4096)
    once_model = build_model("loop_llama", dataclasses.replace(
        cut, loop_llama=LoopLlamaConfig(loop_steps=1)))
    once = memory.traced_step_bytes(once_model, n, layers, 1, 4096)
    wide = 4096 * 2048 * 2
    for rung in ("true", "ffn", "dots"):
        a, b = four(rung), once(rung)
        assert a["head"] == b["head"] == 4096 * 49152 * 6
        assert a["stacks"] - 16 * wide == 4 * (b["stacks"] - 4 * wide)
        assert a["grads"] == b["grads"] == n * 4
        assert a["resident"] == b["resident"] == n * 12
    # (32 kept layer inputs; a pass's norm input, its output, and the
    # output once more in float32 for the gate: 4 widths of bfloat16 a pass)
    assert four("true")["stacks"] == (32 + 16) * wide
    assert four("true")["total"] / memory.GIB == pytest.approx(10.884,
                                                               abs=0.005)
    assert (four("ffn")["stacks"] - four("true")["stacks"]
            == 32 * 2 * 4096 * 5632 * 2)
    assert model.stacked_layers == 8 and model.loop_steps == 4


# ---- the standing families' programs, and this one's scopes ----

# The two standing families tests/test_ssm_moe.py does not pin (its STANDING
# holds the other nine): the eleventh itself and the reference's own block,
# StableHLO's digest as there (locations stripped; sha256, first 16 digits),
# taken from the PARENT of the PR that gave the stack its loop: with
# `loop_steps` None the stack's text is what it was.
# This family's own step joins them (PR 68: its digest on PR 67's tree), so
# that all twelve families standing before the stack's three scalars
# (`residual_scale`, `softmax_scale`, `logit_scale`) are held to their text.
# (PR 69 meant to change the eleventh's text: its mixer writes `D x` on (b, t,
# H P) as it lies, the same values; the digest is that tree's.)
STANDING = {"ssm_moe": ("tiny-ssm-moe", "17ff3dd5ace75c97"),
            "llama": ("tiny", "14bb75356a403459"),
            "loop_llama": ("tiny-loop-llama", "93ef26ecbe667867")}


@pytest.mark.parametrize("family", sorted(STANDING))
def test_a_standing_family_lowers_to_the_text_the_parent_lowered_it_to(
        family):
    import hashlib
    preset, digest = STANDING[family]
    text = lowered_text(family, model_preset(preset))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


def test_the_new_familys_step_names_its_scopes():
    """The named scopes a device trace splits the step by are the name
    stacks of the lowered text's debug info; one traced copy of the layer
    body (one `dense_ffn` scope's worth of SwiGLU products in the forward,
    not three)."""
    from distributed_pytorch_from_scratch_tpu.models import FAMILIES
    text = lowered_text("loop_llama", tiny(), shape=(2, 128),
                        debug_info=True)
    for scope in ("loop_pass/", "dense_ffn", "head_loss/", "exit_gate",
                  "optimizer", "grad_norm"):
        assert scope in text, scope
    assert "loop_llama" in FAMILIES and len(FAMILIES) >= 12
    # The COMPILED step's ops by `benchmark/lib/loop_scopes.py`'s rule (the
    # scope named LAST in an op's `op_name`, between slashes), both
    # directions: the hand-written backward walk opens `loop_pass` and
    # `head_loss` itself, and the layer's forward is a call of its own under
    # `jax.vjp`, so no op is named `jvp(dense_ffn)`, which the rule would
    # not find.
    import re
    import sys
    sys.path.insert(0, str(ROOT))
    from benchmark.lib.loop_scopes import SCOPES
    rule = re.compile(r"(?:^|/)(" + "|".join(SCOPES) + r")(?=/|$)")
    wrapped = re.compile(r"\((?:" + "|".join(SCOPES) + r")[/)]")
    text = lowered_step("loop_llama", tiny(), shape=(2, 128)).compile(
        ).as_text()
    found = {"forward": set(), "backward": set()}
    for name in re.findall(r'op_name="([^"]*)"', text):
        assert not wrapped.search(name), name
        side = ("backward" if "transpose(" in name
                else "forward" if "jvp(" in name else None)
        if side and rule.findall(name):
            found[side].add(rule.findall(name)[-1])
    for side in found:
        assert {"loop_pass", "dense_ffn", "head_loss"} <= found[side], (
            side, found[side])


# ---- the benchmark's copy of the reference ----

def test_the_benchmarks_family_file_is_pinned_to_the_vanilla_file():
    """`benchmark/families/loop_llama.py` (blocks, a scan of layers inside
    a Python loop of passes) and `models/vanilla_loop_llama.py` (Python
    loops, full tensors) compute one loss, one set of exit losses and one
    gradient on the cell's rehearsal shape."""
    import sys
    sys.path.insert(0, str(ROOT))
    from benchmark.lib.cells import load_cell
    from benchmark.lib.files import load_module
    workload, config = load_cell("ouro-2.6b.train-loop4-b1-t4096",
                                 rehearse=True)
    assert config["total_ut_steps"] == 3
    built = load_module("families", "loop_llama").build(
        config, workload["mesh"], "float32")
    cfg = built.model.cfg
    params = built.model.init(jax.random.key(1))
    ids, tgt, pos = batch(cfg, t=workload["seqlen"])
    with jax.default_matmul_precision("highest"):
        (ours, more), grads = jax.jit(jax.value_and_grad(
            built.reference_detail, has_aux=True))(params, ids, tgt, pos)
        (theirs, detail), their_grads = jax.jit(jax.value_and_grad(
            lambda p: vanilla_loss(cfg, p, ids, tgt, pos, detail=True),
            has_aux=True))(params)
    assert float(ours) == pytest.approx(float(theirs), rel=1e-6)
    np.testing.assert_allclose(more["loss_exit"], detail["loss_exit"],
                               rtol=1e-6)
    np.testing.assert_allclose(more["exit_p_mean"], detail["exit_p_mean"],
                               rtol=1e-5)
    err, at = worst_leaf(their_grads, grads)
    assert err <= 1e-4, at
    published_file = json.loads(
        (ROOT / "benchmark" / "configs" / "ouro-2.6b.json").read_text())
    assert published_file["reduced"] == ["num_layers"]
    assert "612,438,017" in published_file["deployment"]
