"""The `ssm_dense` family (models/ssm_dense.py: the Granite 4.0-H
architecture, a Mamba-2 mixer or an attention and then a SwiGLU in every
layer, under four published scalars and a tied head) against its plain
float32 reference (models/vanilla_ssm_dense.py), on the CPU at small sizes
with seeded weights:

* **the program against the reference**: logits, loss and every leaf's
  gradient, periods SCANNED against layers LOOPED, the chunked recurrence
  against the token-by-token one at chunks that do and do not divide the
  sequence, in float32 and in bfloat16;
* **what no tolerance may hide**: the program built with any ONE of the four
  scalars at its neutral value, with the norm before the gate, or with the
  decays in bfloat16, FAILS the same comparison;
* the counts at the published widths, the refusals, the counters, the
  entry point, the named scopes, and the benchmark's copy of the reference.
"""

import dataclasses
import functools
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from family_recipe import (
    Recipe, hold_leaves, hold_loss, leaf_errors, mesh_of, token_file)

from distributed_pytorch_from_scratch_tpu.config import (
    ModelConfig, OptimizerConfig, SsmDenseConfig)
from distributed_pytorch_from_scratch_tpu.models import (FAMILIES,
                                                         build_model)
from distributed_pytorch_from_scratch_tpu.models import (
    vanilla_ssm_dense as ref)
from distributed_pytorch_from_scratch_tpu.models.ssm_dense import (
    SsmDenseTransformer, layer_counts)
from distributed_pytorch_from_scratch_tpu.parallel import mamba
from distributed_pytorch_from_scratch_tpu.training import memory
from distributed_pytorch_from_scratch_tpu.training.checkpoint import (
    load_checkpoint, save_checkpoint)
from distributed_pytorch_from_scratch_tpu.training.metrics import (
    mixer_counters_summary, model_flops_per_step)
from distributed_pytorch_from_scratch_tpu.training.optim import (
    init_adam_state)
from distributed_pytorch_from_scratch_tpu.training.train_step import (
    build_train_step)

FAMILY = "ssm_dense"
ROOT = pathlib.Path(__file__).resolve().parent.parent
# Float32 against float32 with matmul precision "highest" on both sides: the
# two texts differ in the ORDER of float32 sums alone (a chunk's products
# against a token at a time, a scan of periods against a loop of layers), so
# a leaf agrees to 5e-5 of its largest entry and the loss to 1e-5, as every
# family's does; the smallest departure this file tests for (the softmax at
# 1 / sqrt(16) where the facts say 1 / 8) moves the loss by 4e-4.
LOSS_RTOL, LEAF_RTOL = 1e-5, 5e-5


# the family's own: its reference, and sequences of 80 from id 0 up
R = Recipe(FAMILY, ref.vanilla_loss, t=80, low=0)
batch = R.batch


def tiny(dtype="float32", **facts):
    """(its own: the pattern's length is the model's depth)"""
    cfg = R.tiny(dtype, **facts)
    if "layer_types" in facts:
        cfg = dataclasses.replace(cfg, num_layers=len(facts["layer_types"]))
    return cfg


def published(layers=40, vocab=100_352):
    """Granite 4.0-H Micro's `config.json` (all 40 layers, or the first
    `layers` of them) as the program's facts."""
    types = (("mamba",) * 5 + ("attention",) + (("mamba",) * 9
             + ("attention",)) * 3 + ("mamba",) * 4)[:layers]
    return ModelConfig(
        attn_dim=2048, ffn_dim=8192, num_heads=32, num_kv_heads=8,
        num_layers=layers, vocab_size=vocab, maxlen=131072,
        ssm_dense=SsmDenseConfig(
            layer_types=types, mamba_n_heads=64, mamba_d_head=64,
            mamba_d_state=128, mamba_n_groups=1, mamba_d_conv=4,
            mamba_chunk_size=256, mamba_expand=2,
            embedding_multiplier=12.0, residual_multiplier=0.22,
            attention_multiplier=0.015625, logits_scaling=8.0))


def on_mesh(cfg, dp=1, **kw):
    """(its own: the family refuses `tp`, so a file's second axis is dp)"""
    return R.on_mesh(cfg, dp=dp, **kw)


def compare(cfg, dp=1, cached=True, **kw):
    """The program built from `cfg` against the reference of the published
    facts, `tiny()` (the token-by-token reference reads no chunk size, so
    one reference and one set of parameters serve every case of the file's
    grids): (the loss's relative error, the worst leaf's error over its
    largest entry, the leaves compared, those with a gradient).
    `cached=False` where a test has patched either side."""
    _, (want, want_g) = R.reference(tiny(), cached=cached)
    got, got_g = R.program(cfg, dp=dp, params_of=tiny(), cached=cached, **kw)
    errors = leaf_errors(want_g, got_g)
    return (abs(float(got) - float(want)) / abs(float(want)),
            max(errors)[0], len(errors), sum(m for _, _, m in errors))


# ---- the program against the plain reference ----

@pytest.mark.parametrize("dp,impl,chunk", [
    (1, "xla", 32), (2, "xla", 32), (1, "flash_interpret", 32),
    (1, "xla", 16), (1, "xla", 80), (1, "xla", 256)])
def test_loss_and_every_gradient_leaf_equal_the_reference(dp, impl, chunk):
    """Periods SCANNED (the program: (Mamba-2 x 2, attention) twice in one
    scan, then a Mamba-2 layer) against seven layers LOOPED (the
    reference); the chunked recurrence over 80 tokens in chunks that divide
    them (16, 80), that do not (32: the last one padded) and that hold them
    all with room (256, the published chunk) against the token-by-token
    one; all four scalars off their neutral values. `LOSS_RTOL`,
    `LEAF_RTOL` above, with their reason; every leaf has a gradient (the
    recurrence's `A_log` and `dt_bias`, whose gradients exist only through
    the decays, among them)."""
    loss, leaf, leaves, moved = compare(tiny(mamba_chunk_size=chunk), dp=dp,
                                        attn_impl=impl)
    assert loss <= LOSS_RTOL and leaf <= LEAF_RTOL, (loss, leaf)
    # 3 stacked keys: 2 of Mamba layers (13 leaves each), 1 of attention
    # layers (9); the table and the final norm
    assert leaves == 2 * 13 + 9 + 2 and moved == leaves


def test_the_forward_hands_back_the_references_logits():
    cfg = tiny()
    mesh, model = on_mesh(cfg)
    params = R.params(cfg, 5)
    ids, _, pos = batch(cfg, t=48)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p: ref.vanilla_logits(cfg, p, ids))(params)
        got = model.make_forward(mesh)(params, ids, pos)
    assert got.shape == (2, 48, cfg.vocab_size)
    # (logits of size 1 - 3: 2e-5 absolute is the leaves' 5e-5 relative)
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("neutral", [
    dict(embedding_multiplier=1.0), dict(residual_multiplier=1.0),
    dict(attention_multiplier=None), dict(logits_scaling=1.0)])
def test_a_scalar_at_its_neutral_value_fails_the_comparison(neutral):
    """The program built with ONE of the four published scalars at its
    neutral value (1, or `1 / sqrt(head_dim)` for the softmax) against the
    reference with all four: the worst leaf's gradient is out by its own
    size or more (1.0 - 6.5 where the tolerance is 5e-5), so none of the
    four can be dropped inside a tolerance. (A fresh model's LOSS hardly
    sees them: 2e-6 for the softmax's, which is why the comparison holds
    every leaf.)"""
    loss, leaf, _, _ = compare(tiny(**neutral))
    assert leaf > 1000 * LEAF_RTOL, (loss, leaf)


def test_the_norm_before_the_gate_fails_the_comparison(monkeypatch):
    """The mixer norms `y * silu(z)`: the gate first. A reference that
    norms y and gates after (the published option `norm_before_gate` true,
    which this model does not set) is another model."""
    def norm_first(p, u, s, scan=ref.recurrence):
        b, t, _ = u.shape
        H, G, N = s.m_head, s.m_group, s.m_state
        inner = H * s.m_head_dim
        z, xBC, dt = jnp.split(u @ p["w_in"],
                               (inner, 2 * inner + 2 * G * N), -1)
        taps = p["conv"].shape[-1]
        xBC = jax.nn.silu(p["conv_bias"] + sum(
            p["conv"][:, j]
            * jnp.pad(xBC, ((0, 0), (taps - 1 - j, 0), (0, 0)))[:, :t]
            for j in range(taps)))
        x, B, C = jnp.split(xBC, (inner, inner + G * N), -1)
        x = x.reshape(b, t, H, -1)
        own = lambda a: jnp.repeat(a.reshape(b, t, G, N), H // G, axis=2)
        y = scan(x, jax.nn.softplus(dt + p["dt_bias"]),
                 -jnp.exp(p["A_log"]), own(B), own(C))
        y = (y + p["D"][:, None] * x).reshape(b, t, inner)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                              + s.eps)
        return (p["norm"] * y * jax.nn.silu(z)) @ p["w_out"]

    monkeypatch.setattr(ref, "_mamba", norm_first)
    loss, leaf, _, _ = compare(tiny(), cached=False)
    assert leaf > 1000 * LEAF_RTOL, (loss, leaf)


def test_bfloat16_decays_fail_the_comparison(monkeypatch):
    """The decay sums, the decays and the states rounded to bfloat16
    (`ops/ssd.ssd(state_dtype=...)`) where the facts say float32: a chunk's
    sums reach -17 on these weights, where bfloat16's step is 1/16."""
    monkeypatch.setattr(mamba, "ssd", functools.partial(
        mamba.ssd, state_dtype=jnp.bfloat16))
    loss, leaf, _, _ = compare(tiny(), cached=False)
    assert leaf > 100 * LEAF_RTOL, (loss, leaf)     # 0.034 read


def test_in_bfloat16_loss_and_gradients_are_the_references_to_its_rounding():
    """bfloat16 compute over float32 parameters: the loss to 2e-3 (the
    operands' 2^-9 through fourteen sublayers), every leaf to 0.06 in
    relative L2 (no router: no choice flips)."""
    cfg = tiny("bfloat16")
    mesh, model = on_mesh(cfg)
    params, (want, want_g) = R.reference(cfg, t=96, seed=0)
    ids, tgt, pos = batch(cfg, t=96)
    # (the program at the backend's own products: not `R.program`'s)
    got, got_g = jax.jit(jax.value_and_grad(model.make_loss(mesh)))(
        params, ids, tgt, pos)
    hold_loss(want, got, 2e-3)
    l2 = lambda b, a: (np.linalg.norm(np.float64(a) - np.float64(b))
                       / np.linalg.norm(np.float64(a)))
    hold_leaves(want_g, got_g, 0.06, err=l2)


# ---- the pattern, the parameters' other forms ----

def test_the_published_pattern_is_cut_into_blocks_the_stack_scans():
    """40 layers: (mamba x 5, attention), (mamba x 9, attention) three
    times in ONE scan, mamba x 4; the benchmark's first ten are the first
    block and the last."""
    _, whole = on_mesh(published())
    assert whole._pattern == (
        (("mamba_layers_0", 5), ("attn_layers_0", 1)),
        (("mamba_layers_1", 9), ("attn_layers_1", 1)),
        (("mamba_layers_2", 4),))
    assert [n for _, n, _ in whole._segments] == [5, 1, 27, 3, 4]
    _, cut = on_mesh(published(10, 12_544))
    assert cut._pattern == ((("mamba_layers_0", 5), ("attn_layers_0", 1)),
                            (("mamba_layers_1", 4),))
    assert layer_counts(cut.cfg) == {"mamba": 9, "attn": 1}
    # every layer tags the SwiGLU's names, the one attention layer the rest
    assert cut.tagged_layers["ffn_gate"] == 10 == cut.stacked_layers
    assert cut.tagged_layers["flash_out"] == 1 == cut.tagged_layers["q_proj"]


def test_parameter_counts_at_the_published_widths():
    """The cut the benchmark runs holds 772,160,448 parameters and the
    published model 3,191,396,096: a Mamba layer 25,847,232 + the SwiGLU's
    50,331,648 + two norms, an attention layer 10,485,760 + the same, the
    tied table once, the final norm."""
    cut, whole = published(10, 12_544), published()
    assert cut.num_params() == 772_160_448
    assert whole.num_params() == 3_191_396_096
    parts = SsmDenseTransformer.param_counts(cut)
    assert parts == {"embedding": 12_544 * 2048, "final_norm": 2048,
                     "mamba_layers": 9 * 76_182_976,
                     "attn_layers": 60_821_504}
    shapes = jax.eval_shape(build_model(FAMILY, cut).init, jax.random.key(0))
    assert sum(int(np.prod(x.shape))
               for x in jax.tree.leaves(shapes)) == 772_160_448
    assert shapes["mamba_layers_0"]["mamba"]["w_in"].shape == (
        1, 5, 2048, 8512)
    # 6 N a token, attention at the full T^2 in ONE layer, the recurrence
    # at chunk 256 with one group in nine
    flops = model_flops_per_step(cut, 1, 4096, cut.num_params())
    scan = 2 * 256 * 128 + 64 * (2 * 256 * 64 + 4 * 64 * 128)
    assert flops == pytest.approx(
        4096 * (6 * 772_160_448 + 12 * 32 * 4096 * 64 + 3 * 9 * scan))


def test_parameters_round_trip_through_the_canonical_form_and_a_checkpoint(
        tmp_path):
    cfg = tiny()
    mesh, model = on_mesh(cfg, 2)
    params = R.params(cfg, 1)
    assert model._pattern == ((("mamba_layers_0", 2), ("attn_layers_0", 1)),
                              (("mamba_layers_1", 1),))
    assert params["mamba_layers_0"]["mamba"]["w_in"].shape == (
        2, 2, 64, 128 + 128 + 16 + 8)
    assert sorted(params["attn_layers_0"]) == [
        "down_proj", "gate_proj", "norm1", "norm2", "up_proj", "wk", "wo",
        "wq", "wv"]
    assert "lm_head" not in params      # the head is the table
    np.testing.assert_allclose(
        params["mamba_layers_1"]["mamba"]["A_log"][0, 0],
        np.log(np.arange(1.0, 9.0)), rtol=1e-6)
    assert float(jnp.std(params["embedding"]["weight"])) == pytest.approx(
        cfg.ssm_dense.initializer_range, rel=0.02)
    canonical = model.to_canonical(params)
    save_checkpoint(str(tmp_path), 3, 1.0, canonical,
                    model.canonical_specs(), 1)
    restored, _, at = load_checkpoint(str(tmp_path), 3, R.params(cfg, 9),
                                      model.canonical_specs())
    assert at == 3
    jax.tree.map(np.testing.assert_array_equal, restored, params)


# ---- the step, its counters, the entry point ----

def test_the_train_step_counts_its_decays_and_the_residuals_rms():
    cfg = tiny()
    mesh, model = on_mesh(cfg, 2)
    params = jax.device_put(R.params(cfg, 0), model.shardings(mesh))
    opt = init_adam_state(params)
    ocfg = OptimizerConfig(lr=3e-3, warmup_steps=2, max_steps=20)
    step = build_train_step(model, mesh, ocfg, with_grad_norm=True,
                            with_counters=True)
    ids, tgt, pos = batch(cfg, t=64)
    with jax.default_matmul_precision("highest"):
        _, x = jax.jit(lambda p: ref.reference_logits(
            p, ids, sizes=ref.sizes_of(cfg)))(params)
    losses = []
    for i in range(6):
        params, opt, (loss, gnorm, c) = step(params, opt, ids, tgt, pos)
        losses.append(float(loss))
        first = c if i == 0 else first
    assert losses[-1] < losses[0] and np.isfinite(float(gnorm))
    # a row a Mamba layer (5), none of the two attention layers
    assert c["ssm_decay_min"].shape == (5,)
    assert float(jnp.max(c["ssm_decay_min"])) < 0.0
    # the RMS over the width of what entered the final norm, a mean a token
    want = float(jnp.mean(jnp.sqrt(jnp.mean(jnp.square(x), axis=-1))))
    assert float(first["resid_rms_last"]) == pytest.approx(want, rel=1e-5)
    summary = mixer_counters_summary(jax.device_get(c))
    assert summary["ssm_decay_min"] == float(jnp.min(c["ssm_decay_min"]))
    from distributed_pytorch_from_scratch_tpu.obs import schema
    assert set(schema.EVENT_REQUIRED["mixer_counters"]) <= set(summary) == {
        "loss_main", "ssm_decay_min", "resid_rms_last"}


def test_the_multipliers_hold_the_residuals_rms():
    """`resid_rms_last` is the first number to move where a multiplier is
    lost: 1.2 on fresh weights (12 x the table's 0.1, and seven layers'
    branches at 0.22), 0.1 - 0.3 without the embedding's, 1.77 with the
    residual's at 1."""
    def rms(**facts):
        cfg = tiny(**facts)
        mesh, model = on_mesh(cfg)
        _, c = model.make_loss(mesh, with_counters=True)(
            model.init(jax.random.key(0)), *batch(cfg, t=64))
        return float(c["resid_rms_last"])

    assert 1.1 < rms() < 1.5
    assert rms(embedding_multiplier=1.0) < 0.5
    assert rms(residual_multiplier=1.0) > 1.6


def test_train_cli_runs_the_family(tmp_path, capsys):
    from distributed_pytorch_from_scratch_tpu import train as train_mod
    tokens = token_file(tmp_path)
    train_mod.main([
        "--family", FAMILY, "--model", "tiny-ssm-dense",
        "--data_path", str(tokens), "--save_dir", str(tmp_path / "ckpt"),
        "--batch_size", "4", "--maxlen", "64", "--max_steps", "4",
        "--log_interval", "2", "--save_interval", "100",
        "--warmup_steps", "2"])
    out = capsys.readouterr().out
    assert f"model[{FAMILY}]" in out and "resid_rms_last" in out
    events = [json.loads(line) for line in
              open(tmp_path / "ckpt" / "logs" / "metrics.jsonl")]
    assert any(e.get("tag") == "mixer_counters" for e in events)


# ---- what is refused ----

@pytest.mark.parametrize("kw,message", [
    (dict(tp_size=2), "tp_size > 1 .*no reduce of a counted mixer"),
    (dict(pp_size=2), "pp_size > 1 .*two kinds of mixer"),
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
    _, model = on_mesh(tiny())
    assert not model.decodable and not model.hand_reduced_grads
    with pytest.raises(ValueError, match="cannot be decoded or served"):
        require_decodable(model)
    mesh = mesh_of(1, 2)
    with pytest.raises(ValueError, match="ZeRO stage 2 is not made to work"):
        build_train_step(model, mesh, OptimizerConfig(), zero=2)


@pytest.mark.parametrize("cfg,message", [
    (lambda: dataclasses.replace(tiny(), ssm_dense=None),
     "needs cfg.ssm_dense"),
    (lambda: dataclasses.replace(tiny(), num_layers=6), "names 7 layers"),
    (lambda: dataclasses.replace(tiny(), num_experts=4),
     "layers are dense"),
    (lambda: tiny(layer_types=("mamba", "conv")), "holds 'conv'"),
    (lambda: tiny(position_embedding_type="rope"), "takes no positions"),
    (lambda: tiny(mamba_conv_bias=False), "convolution has a bias"),
    (lambda: tiny(mamba_n_heads=4), "is not mamba_expand 2 x"),
    (lambda: tiny(mamba_n_groups=3), "whole groups"),
])
def test_a_family_needs_its_own_facts_and_a_pattern_it_can_run(cfg, message):
    with pytest.raises(ValueError, match=message):
        build_model(FAMILY, cfg())


def test_a_scaled_residual_takes_one_stream():
    """`residual_scale` multiplies what `x + y` adds; the stream mixers
    join a sublayer's output by maps of their own."""
    from distributed_pytorch_from_scratch_tpu.parallel.hyper import (
        StreamMixer)

    @dataclasses.dataclass(frozen=True)
    class Streams(SsmDenseTransformer):
        stream_mixer = StreamMixer(64, 2, sinkhorn_iters=1)

    with pytest.raises(ValueError, match="scales its sublayers' outputs"):
        Streams(tiny())


# ---- memory ----

def test_remat_auto_sizes_the_benchmarks_cell(capsys):
    """`remat="auto"` at the cell's shapes on a v5e's 15.75 GiB, beside
    8.63 GiB of weights and moments: no snapshot's reserve can be held, and
    the estimate at the picked rung is what PERF.md section 5 sets beside
    the chip's reading."""
    cfg = dataclasses.replace(published(10, 12_544),
                              compute_dtype="bfloat16")
    model = build_model(FAMILY, cfg, remat_budget_gib=15.748)
    layer_params = cfg.num_params() - 12_544 * 2048 - 2048
    memory.select_remat_traced.cache_clear()
    rung = memory.select_remat_traced(model, cfg.num_params(), layer_params,
                                      1, 4096)
    said = capsys.readouterr().err
    assert rung == AUTO_RUNG and "reserve_held=False" in said, said
    estimate = float(said.split(f"{rung}=")[1].split("GiB")[0])
    assert ESTIMATE_GIB[0] < estimate < ESTIMATE_GIB[1], said


AUTO_RUNG = "dots"
ESTIMATE_GIB = (13.445, 13.445 * 1.05)     # the chip counts 13.445


# ---- the scopes a device trace splits the step by ----

def test_the_new_familys_step_names_its_scopes():
    """The named scopes a device trace splits the step by are the name
    stacks of the lowered text's debug info; the SwiGLU of a Mamba layer
    and of an attention layer both run under `dense_ffn`."""
    import re
    mesh, model = on_mesh(tiny())
    params = jax.eval_shape(model.init, jax.random.key(0))
    opt = jax.eval_shape(init_adam_state, params)
    ids = jax.ShapeDtypeStruct((2, 128), np.int32)
    step = build_train_step(model, mesh, OptimizerConfig(),
                            with_grad_norm=True, with_counters=True)
    text = step.lower(params, opt, ids, ids, ids).as_text(debug_info=True)
    for scope in ("mamba/in_proj", "mamba/conv", "mamba/ssd",
                  "mamba/gate_norm", "mamba/out_proj", "gqa_attn",
                  "dense_ffn", "head_loss", "optimizer", "grad_norm"):
        assert scope in text, scope
    assert "moe_" not in text
    assert len(re.findall(r"dense_ffn", text)) > 2
    assert FAMILY in FAMILIES and len(FAMILIES) >= 13


# ---- the benchmark's copy of the reference ----

def test_the_benchmarks_family_file_is_pinned_to_the_vanilla_file():
    """`benchmark/families/ssm_dense.py` (the recurrence under a checkpoint
    in blocks of 64 steps, so that it fits the chip beside the state) and
    `models/vanilla_ssm_dense.py` (one scan) compute one loss and one
    gradient on the cell's rehearsal shape."""
    import sys
    sys.path.insert(0, str(ROOT))
    from benchmark.lib.cells import load_cell
    from benchmark.lib.files import load_module
    workload, config = load_cell(
        "granite-4.0-h-micro.train-pp4stage-b1-t4096", rehearse=True)
    built = load_module("families", "ssm_dense").build(
        config, workload["mesh"], "float32")
    cfg = built.model.cfg
    assert cfg.ssm_dense.mamba_n_groups == 1
    params = built.model.init(jax.random.key(1))
    ids, tgt, pos = batch(cfg, t=workload["seqlen"])
    with jax.default_matmul_precision("highest"):
        ours, grads = jax.jit(jax.value_and_grad(built.reference_loss))(
            params, ids, tgt, pos)
        theirs, their_grads = jax.jit(jax.value_and_grad(
            lambda p: ref.vanilla_loss(cfg, p, ids, tgt, pos)))(params)
    assert float(ours) == pytest.approx(float(theirs), rel=1e-6)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(their_grads)):
        np.testing.assert_allclose(
            a, b, atol=2e-5 * float(np.max(np.abs(b))),
            err_msg=jax.tree_util.keystr(path))
