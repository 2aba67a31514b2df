"""The `sambay` family (models/sambay.py: Phi-4-mini-flash's
decoder-hybrid-decoder, Mamba-1 scans and window differential attention
below, ONE layer's scan output and ONE layer's keys and values read by every
layer above) against its plain float32 reference (models/vanilla_sambay.py),
on the CPU at small sizes with seeded weights:

* **the program against the reference**: logits, loss and every leaf's
  gradient, periods SCANNED against layers LOOPED, the chunked scan against
  the token-by-token one, the shared values' SUMMED COTANGENTS as leaves of
  their own (a probe added where each is made);
* the selective scan, text and kernels under the interpreter, against the
  token-by-token recurrence at lengths that are and are not whole chunks;
* **what no tolerance may hide**: the memory taken after the gate and the
  window one key short FAIL the same comparison (a cross layer on keys of
  its own input, `lambda` held at `lambda_init` and the rest are the
  benchmark's controls, rehearsed in benchmark/tests/test_sambay_counts.py);
* the pattern, the counts at the published widths, the refusals, the
  counters, the entry point and the named scopes.
"""

import dataclasses
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from family_recipe import (Recipe, hold_leaves, hold_loss, leaf_errors,
                           mesh_of, outputs_and_grads, token_file)
from jax.sharding import PartitionSpec as P

from distributed_pytorch_from_scratch_tpu.config import (ModelConfig,
                                                         OptimizerConfig,
                                                         SambaYConfig)
from distributed_pytorch_from_scratch_tpu.models import (FAMILIES,
                                                         build_model)
from distributed_pytorch_from_scratch_tpu.models import vanilla_sambay as ref
from distributed_pytorch_from_scratch_tpu.models.sambay import (
    SambaYTransformer, blocks_of, layer_counts, layer_kinds)
from distributed_pytorch_from_scratch_tpu.ops.attention import (
    sliding_window)
from distributed_pytorch_from_scratch_tpu.ops.selective_scan import (
    selective_scan)
from distributed_pytorch_from_scratch_tpu.training.metrics import (
    mixer_counters_summary, model_flops_per_step)
from distributed_pytorch_from_scratch_tpu.training.optim import (
    init_adam_state)
from distributed_pytorch_from_scratch_tpu.training.train_step import (
    build_train_step)

FAMILY = "sambay"
# Float32 against float32 with matmul precision "highest" on both sides: the
# two texts differ in the ORDER of float32 sums alone (a scan of periods
# against a loop of layers, one attention call over 2 H maps against two
# softmaxes a head), so a leaf agrees to 5e-5 of its largest entry and the
# loss to 1e-5, as every family's does; the smallest departure this file
# tests for is held to a hundred times the leaves' tolerance.
LOSS_RTOL, LEAF_RTOL = 1e-5, 5e-5

# the family's own: its reference, and sequences of 48 (three windows of
# 16) from id 0 up
R = Recipe(FAMILY, ref.vanilla_loss,
           lambda cfg, p, ids, pos: ref.vanilla_logits(cfg, p, ids),
           t=48, low=0)
tiny, batch = R.tiny, R.batch


def floor(name):
    """A softmax does not see a bias on its keys (every key of a row moves
    by the same `q . b`): `wk`'s bias has NO gradient but rounding's, 1e-9
    where the layer's other leaves are 1e-2, and is held to zero at their
    size."""
    return 1e-3 if "['wk']['bias']" in name else 1e-6


# every leaf of the tiny preset: six stacked keys (a Mamba layer 9 + 4 + 3,
# an attention layer 13 + 7, a cross layer 9 + 7, a memory unit 2 + 7), the
# table, the final norm's two
LEAVES = 2 * 16 + 2 * 20 + 16 + 9 + 3


def published(layers_here=None, vocab=200_064):
    """Phi-4-mini-flash-reasoning's `config.json` (all 32 layers, or the
    published layers `layers_here`) as the program's facts."""
    return ModelConfig(
        attn_dim=2560, ffn_dim=10240, num_heads=40, num_kv_heads=20,
        num_layers=len(layers_here or range(32)), vocab_size=vocab,
        maxlen=262144, sambay=SambaYConfig(
            num_hidden_layers=32, layers_here=layers_here, mb_per_layer=2,
            sliding_window=512))


CUT = (0, 1, 16, 17, 18, 19)


# ---- the program against the plain reference ----

@pytest.mark.parametrize("dp", [1, 2])
def test_loss_and_every_gradient_leaf_equal_the_reference(dp):
    """Periods SCANNED, the makers a segment each, the readers handed what
    the makers left (the program) against eight layers LOOPED with the
    memory and the keys and values as plain Python values (the reference);
    the chunked scan against the token-by-token one; one attention call
    over 2 H maps against two softmaxes a head. Every leaf has a gradient:
    the makers' (`memory_layers`' mixer and `full_layers`' `wk`, `wv`: the
    summed cotangents land there) among them. (One compiled program a
    layout: dp 1 with the logits behind it, dp 2 with the counters.)"""
    cfg = tiny()
    _, (want, want_g) = R.reference(cfg)
    if dp == 1:
        got, got_g, _ = R.program(cfg, logits=True)
    else:
        (got, _), got_g = R.program(cfg, dp=2, with_counters=True)
    hold_loss(want, got, LOSS_RTOL)
    names, moved = hold_leaves(want_g, got_g, LEAF_RTOL, floor)
    assert len(names) == LEAVES and moved == names


def test_the_forward_hands_back_the_references_logits():
    cfg = tiny()
    want = R.reference_logits(cfg)
    *_, got = R.program(cfg, logits=True)
    assert got.shape == (2, 48, cfg.vocab_size)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_twelve_layers_carry_the_shared_values_through_a_scan_of_periods():
    """N = 12: two periods above the makers, so the memory and the keys and
    values are constants of a scan of TWO periods and their cotangents the
    scan's sums over both. Through the flash kernels under the interpreter
    (keys 16 and values 32 wide, a group of 2, a window of 16 in 128 rows
    and the triangle), which plan a window over whole blocks of 128."""
    cfg = dataclasses.replace(tiny(num_hidden_layers=12), num_layers=12)
    assert layer_counts(cfg) == {"mamba": 4, "swa": 3, "full": 1, "gmu": 2,
                                 "cross": 2}
    _, (want, want_g) = R.reference(cfg, t=128)
    got, got_g = R.program(cfg, t=128, attn_impl="flash_interpret")
    hold_loss(want, got, LOSS_RTOL)
    hold_leaves(want_g, got_g, LEAF_RTOL, floor)


def test_the_set_the_benchmarks_cell_keeps_is_the_floors_function():
    """`remat="auto"` passes over the MLP's stacks in the benchmark's cell
    and keeps the flash kernel's outputs and q, k, v (`true+flash+dots`,
    tests/test_remat_topology.py): the same loss and gradients as the floor
    (what `auto` is on this backend), through the interpreted kernels, in
    the makers' segments of ONE layer (the policy under its CSE barrier),
    the scanned periods and the cross layers whose keys are another's."""
    cfg = dataclasses.replace(tiny(num_hidden_layers=12), num_layers=12)
    want, want_g = R.program(cfg, t=128, attn_impl="flash_interpret")
    got, got_g = R.program(cfg, t=128, attn_impl="flash_interpret",
                           remat="true+flash+dots")
    hold_loss(want, got, 1e-6)
    hold_leaves(want_g, got_g, 1e-5, floor)


# ---- the shared values' summed cotangents ----

@dataclasses.dataclass(frozen=True)
class Probe:
    """Zeros of the shapes a maker's values have, as a layer's module: a
    parameter that is ADDED to a value where it is made, so that its
    gradient is the value's summed cotangent."""
    shapes: tuple

    def init(self, key):
        return {name: jnp.zeros(shape, jnp.float32)
                for name, shape in self.shapes}

    def specs(self):
        return {name: P() for name, _ in self.shapes}


@dataclasses.dataclass(frozen=True)
class Probed(SambaYTransformer):
    """The family with a probe on each of its three shared values (for a
    batch of 2 x 48)."""

    @property
    def _segments(self):
        return tuple((key, n, (*names, "probe") if key in (
            "memory_layers", "full_layers") else names)
            for key, n, names in super()._segments)

    @functools.cached_property
    def _mods(self):
        sy, d = self.cfg.sambay, self.d
        wide = sy.mamba_expand * d
        return {**super()._mods, "probe": Probe((
            ("memory", (2, 48, wide)), ("k", (2, 48, self.cfg.kv_dim)),
            ("v", (2, 48, self.cfg.kv_dim))))}

    def _made(self, lp, values):
        return {name: a + lp["probe"][name].astype(a.dtype)
                for name, a in values.items()}


def test_the_shared_values_summed_cotangents_are_the_references():
    """The gradient at a probe of zeros added where a value is made, before
    ANY layer reads it (its maker too): the memory's is the sum of its own
    layer's gate's and the memory unit's, the keys' and values' of the full
    layer's and the cross layer's. Leaves of their own, against the
    reference's probes."""
    cfg = tiny()
    params = R.params(cfg)
    ids, tgt, pos = batch(cfg)
    model = Probed(cfg)
    probed = model.init(jax.random.key(3))
    mesh = mesh_of()
    for key, layers in params.items():          # the recipe's own weights
        probed[key] = ({**layers, "probe": probed[key]["probe"]}
                       if "probe" in probed[key] else layers)
    zeros = {"memory": jnp.zeros((2, 48, 128)), "k": jnp.zeros((2, 48, 32)),
             "v": jnp.zeros((2, 48, 32))}
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.grad(lambda q: ref.reference_loss(
            params, ids, tgt, pos, sizes=ref.sizes_of(cfg), probes=q)))(zeros)
        got_g = jax.jit(jax.grad(model.make_loss(mesh)))(probed, ids, tgt,
                                                         pos)
    got = {"memory": got_g["memory_layers"]["probe"]["memory"][0],
           "k": got_g["full_layers"]["probe"]["k"][0],
           "v": got_g["full_layers"]["probe"]["v"][0]}
    for name, a in want.items():
        assert float(jnp.max(jnp.abs(a))) > 0, name
        assert got_g["full_layers"]["probe"]["memory"].any() == 0
    hold_leaves(want, got, LEAF_RTOL)


# ---- what no tolerance may hide ----

def _departs(monkeypatch, patch):
    """The worst leaf of the program with `patch` applied against the sound
    reference."""
    cfg = tiny()
    patch(monkeypatch)
    _, (_, want_g) = R.reference(cfg)
    _, got_g = R.program(cfg, cached=False)
    return max(leaf_errors(want_g, got_g))


def _memory_after_the_gate(mp):
    from distributed_pytorch_from_scratch_tpu.parallel.mamba1 import gate
    sound = SambaYTransformer._mix_sharing

    def mix(self, lp, y, dtype, kind, told, shared):
        out, counted, left = sound(self, lp, y, dtype, kind, told, shared)
        if kind == "memory":
            _, z, _ = self._mods["mamba"].scan(lp["mamba"], y, dtype)
            left = {"memory": gate(left["memory"], z)}
        return out, counted, left
    mp.setattr(SambaYTransformer, "_mix_sharing", mix)


def _window_one_short(mp):
    mp.setattr(SambaYTransformer, "_attn_mask",
               lambda self, t, kind=None: sliding_window(
                   self.cfg.sambay.sliding_window - 1)
               if kind == "swa" else None)


@pytest.mark.parametrize("patch", [_memory_after_the_gate,
                                   _window_one_short])
def test_a_departure_fails_the_comparison(monkeypatch, patch):
    error, leaf, _ = _departs(monkeypatch, patch)
    assert error > 100 * LEAF_RTOL, (patch.__name__, error, leaf)


# ---- the selective scan ----

def _scan_case(t, c=128, N=8, seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    return (jax.random.normal(ks[0], (2, t, c)),
            jax.nn.softplus(jax.random.normal(ks[1], (2, t, c)) - 2.0),
            -jnp.exp(jax.random.normal(ks[2], (c, N))),
            jax.random.normal(ks[3], (2, t, N)),
            jax.random.normal(ks[4], (2, t, N)))


def _token_by_token(u, dt, A, B, C):
    return (ref.recurrence(u, dt, A, B, C),)


def _text(u, dt, A, B, C):
    return (selective_scan(u, dt, A, B, C, chunk=32)[0],)


def _kernels(u, dt, A, B, C):
    return (selective_scan(u, dt, A, B, C, interpret=True)[0],)


def _weighted(y):
    return jnp.sum(y * jnp.cos(jnp.arange(y.size).reshape(y.shape)))


@pytest.mark.parametrize("path", [_text, _kernels])
@pytest.mark.parametrize("t", [64, 200])
def test_the_selective_scan_is_the_token_by_token_recurrence(path, t):
    """Values and all five gradients, at a length that is whole chunks (64:
    two of the text's 32; the kernels pad it to their 128) and one that is
    not (200): the text's checkpointed chunks and the kernels' walk (the
    forward's states a chunk, the backward's recomputed states and its sums
    over channels) against one scan over the tokens."""
    args = _scan_case(t)
    (want,), want_g = outputs_and_grads(_token_by_token, _weighted, *args)
    (got,), got_g = outputs_and_grads(path, _weighted, *args)
    np.testing.assert_allclose(got, want, atol=2e-5)
    for name, a, b in zip("u dt A B C".split(), got_g, want_g):
        np.testing.assert_allclose(
            a, b, atol=5e-5 * float(jnp.max(jnp.abs(b))), err_msg=name)


def test_the_interpreter_is_asked_for_by_name_at_shapes_the_kernels_hold():
    u, dt, A, B, C = _scan_case(16, c=96)
    with pytest.raises(ValueError, match="do not hold 96 channels"):
        selective_scan(u, dt, A, B, C, interpret=True)
    with pytest.raises(ValueError, match="in bfloat16"):
        selective_scan(*_scan_case(16), state_dtype=jnp.bfloat16,
                       interpret=True)


def test_a_bfloat16_state_is_not_the_float32_one():
    args = _scan_case(64)
    want = ref.recurrence(*args)
    got = selective_scan(*args, state_dtype=jnp.bfloat16)[0]
    sound = selective_scan(*args)[0]
    err = lambda a: float(jnp.max(jnp.abs(a - want)) / jnp.max(jnp.abs(want)))
    assert err(sound) < 1e-5 < 1e-3 < err(got)


# ---- the pattern and the counts ----

def test_the_published_rule_gives_the_five_kinds():
    kinds = layer_kinds(32, 2)
    assert {k: kinds.count(k) for k in set(kinds)} == {
        "mamba": 9, "swa": 8, "full": 1, "gmu": 7, "cross": 7}
    assert kinds[16] == "mamba" and kinds[17] == "full"
    assert kinds[:4] == ("mamba", "swa") * 2
    assert kinds[18:22] == ("gmu", "cross") * 2
    assert [kinds[i] for i in CUT] == ["mamba", "swa", "mamba", "full",
                                       "gmu", "cross"]
    assert layer_kinds(8) == ("mamba", "swa", "mamba", "swa", "mamba",
                              "full", "gmu", "cross")
    for depth, message in ((6, "multiple of 4"), (2, "multiple of 4")):
        with pytest.raises(ValueError, match=message):
            layer_kinds(depth)
    with pytest.raises(ValueError, match="must be a scan"):
        layer_kinds(8, 3)


def test_the_pattern_is_two_periods_around_two_makers():
    blocks, indices = blocks_of(published())
    assert [(r, tuple(k for k, *_ in parts)) for r, parts in blocks] == [
        (8, ("mamba_layers_0", "swa_layers_0")), (None, ("memory_layers",)),
        (None, ("full_layers",)), (7, ("gmu_layers_0", "cross_layers_0"))]
    assert indices["memory_layers"] == [16]
    assert indices["cross_layers_0"] == list(range(19, 32, 2))
    model = build_model(FAMILY, published(CUT, 25_008))
    assert model._pattern == (
        (("mamba_layers_0", 1), ("swa_layers_0", 1)), "memory_layers",
        "full_layers", (("gmu_layers_0", 1), ("cross_layers_0", 1)))
    # a layer keeps its PUBLISHED index: the cut's cross layer is layer 19
    told = model._told("cross_layers_0", {"w": jnp.zeros((1, 1, 3))})
    assert float(told["told"]["lambda_init"][0, 0]) == pytest.approx(
        0.8 - 0.6 * np.exp(-0.3 * 19))


def test_parameter_counts_at_the_published_widths():
    """The published model is 3,852,562,944 parameters (its "3.8B") and the
    cut the benchmark runs 697,094,272, by the five kinds' layers."""
    whole, cut = published(), published(CUT, 25_008)
    assert whole.num_params() == 3_852_562_944
    assert cut.num_params() == 697_094_272
    assert SambaYTransformer.param_counts(whole) == {
        "embedding": 512_163_840, "final_norm": 5120,
        "mamba_layers": 9 * 119_895_040, "swa_layers": 8 * 98_322_304,
        "full_layers": 98_322_304, "gmu_layers": 7 * 104_867_840,
        "cross_layers": 7 * 91_766_144}
    for cfg in (whole, cut):
        shapes = jax.eval_shape(build_model(FAMILY, cfg).init,
                                jax.random.key(0))
        assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(
            shapes)) == cfg.num_params()
    assert shapes["memory_layers"]["mamba"]["w_x"].shape == (1, 5120, 192)
    assert shapes["cross_layers_0"]["cross"]["wq"]["weight"].shape == (
        1, 1, 2560, 2560)
    # 6 N a token; two maps a differential head, 64 wide against keys and
    # 128 against values, a window's band and two triangles; two scans
    t = 16_384
    band, triangle = 512 * (2 * t - 511) // 2, t * (t + 1) // 2
    assert model_flops_per_step(cut, 1, t, cut.num_params()) == (
        pytest.approx(6 * 697_094_272 * t
                      + 3 * 40 * (band + 2 * triangle) * 2 * 192
                      + 3 * 2 * 7 * 5120 * 16 * t))


# ---- counters, the entry point, the scopes ----

def test_the_step_counts_what_the_mixers_count():
    """(the loss's counters on two data shards: a minimum, a layer's one
    value, a mean of squares and a count each join as they should)"""
    cfg = tiny()
    (_, c), _ = R.program(cfg, dp=2, with_counters=True)
    # a row a Mamba layer (3), a row an attention layer (4)
    assert c["sscan_decay_min"].shape == (3,)
    assert float(jnp.max(c["sscan_decay_min"])) < 0.0
    assert c["diff_lambda"].shape == (4,)
    # fresh lambdas: exp(small) - exp(small) + lambda_init
    want = [0.8 - 0.6 * np.exp(-0.3 * i) for i in (1, 3, 5, 7)]
    np.testing.assert_allclose(c["diff_lambda"], want, atol=0.15)
    assert float(c["shared_kv_readers"]) == 2.0
    assert float(c["memory_rms"]) > 0 and float(c["resid_rms_last"]) > 0
    summary = mixer_counters_summary(jax.device_get(c))
    from distributed_pytorch_from_scratch_tpu.obs import schema
    assert set(schema.EVENT_REQUIRED["mixer_counters"]) <= set(summary) == {
        "loss_main", "sscan_decay_min", "diff_lambda", "memory_rms",
        "shared_kv_readers", "resid_rms_last"}


def test_train_cli_runs_the_family(tmp_path, capsys):
    from distributed_pytorch_from_scratch_tpu import train as train_mod
    tokens = token_file(tmp_path)
    train_mod.main([
        "--family", FAMILY, "--model", "tiny-sambay",
        "--data_path", str(tokens), "--save_dir", str(tmp_path / "ckpt"),
        "--batch_size", "4", "--maxlen", "64", "--max_steps", "4",
        "--log_interval", "2", "--save_interval", "100",
        "--warmup_steps", "2"])
    out = capsys.readouterr().out
    assert f"model[{FAMILY}]" in out and "memory_rms" in out
    events = [json.loads(line) for line in
              open(tmp_path / "ckpt" / "logs" / "metrics.jsonl")]
    assert any(e.get("tag") == "mixer_counters" for e in events)


def test_the_new_familys_step_names_its_scopes():
    mesh, model = R.on_mesh(tiny())
    params = jax.eval_shape(model.init, jax.random.key(0))
    opt = jax.eval_shape(init_adam_state, params)
    ids = jax.ShapeDtypeStruct((2, 128), np.int32)
    step = build_train_step(model, mesh, OptimizerConfig(),
                            with_grad_norm=True, with_counters=True)
    text = step.lower(params, opt, ids, ids, ids).as_text(debug_info=True)
    for scope in ("mamba1/in_proj", "mamba1/conv", "mamba1/x_proj",
                  "mamba1/dt_proj", "mamba1/sscan", "mamba1/gate",
                  "mamba1/out_proj", "diff_attn", "cross_attn", "gmu",
                  "dense_ffn", "head_loss", "optimizer", "grad_norm"):
        assert scope in text, scope
    assert "moe_" not in text
    assert FAMILY in FAMILIES and len(FAMILIES) >= 15


# ---- what is refused ----

@pytest.mark.parametrize("kw,message", [
    (dict(tp_size=2), "tp_size > 1 .*no reduce of a counted mixer"),
    (dict(pp_size=2), "pp_size > 1 .*between a maker and a reader"),
    (dict(cp_size=2), "cp_size > 1 .*the convolution's taps"),
    (dict(ep_size=2), "ep_size > 1 requires cfg.num_experts > 0"),
    (dict(sequence_parallel=True), "sequence_parallel=True .*whole seq"),
    (dict(attn_t_real=32), "attn_t_real .*pad tokens"),
    (dict(zero3_axis="dp"), "ZeRO stage 3"),
])
def test_the_model_refuses_what_it_does_not_run(kw, message):
    with pytest.raises(ValueError, match=message):
        build_model(FAMILY, tiny(), **kw)


def test_decoding_and_the_hand_reduced_gradients_are_refused():
    from distributed_pytorch_from_scratch_tpu.models.decode import (
        require_decodable)
    _, model = R.on_mesh(tiny())
    assert not model.decodable and not model.hand_reduced_grads
    with pytest.raises(ValueError, match="cannot be decoded or served"):
        require_decodable(model)
    with pytest.raises(ValueError, match="ZeRO stage 2 is not made to work"):
        build_train_step(model, mesh_of(1, 2), OptimizerConfig(), zero=2)


@pytest.mark.parametrize("cfg,message", [
    (lambda: dataclasses.replace(tiny(), sambay=None), "needs cfg.sambay"),
    (lambda: dataclasses.replace(tiny(), num_layers=6), "names 8 layers"),
    (lambda: dataclasses.replace(tiny(), num_experts=4), "layers are dense"),
    (lambda: tiny(num_hidden_layers=10), "multiple of 4"),
    (lambda: dataclasses.replace(tiny(layers_here=(0, 1, 6, 7)),
                                 num_layers=4),
     "not layer 4, which makes what they read"),
    (lambda: dataclasses.replace(tiny(layers_here=(1, 0)), num_layers=2),
     "ascending"),
])
def test_a_family_needs_its_own_facts_and_a_cut_it_can_run(cfg, message):
    with pytest.raises(ValueError, match=message):
        build_model(FAMILY, cfg())


# ---- the standing families' text ----

# The two standing families no file pinned yet (the other twelve:
# tests/test_ssm_moe.py and tests/test_loop_llama.py): sha256[:16] of the
# tiny step's lowered text on the PARENT of the PR that gave the stack its
# shared values. With `shares_values` False the stack's text is what it was.
LOWERED_BEFORE = {"ssm_dense": ("tiny-ssm-dense", "9f9c5657caf7a2b9"),
                  "dsa_moe": ("tiny-dsa-moe", "67d6cd817e026d4d")}


@pytest.mark.parametrize("family", sorted(LOWERED_BEFORE))
def test_a_standing_family_lowers_to_the_text_the_parent_lowered_it_to(
        family):
    import hashlib
    from family_recipe import lowered_text
    from distributed_pytorch_from_scratch_tpu.config import model_preset
    preset, digest = LOWERED_BEFORE[family]
    text = lowered_text(family, model_preset(preset))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest
