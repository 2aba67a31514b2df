"""The ladder of named layer residuals (models/transformer.REMAT_LADDER).

Every rung computes what rung 0 computes: the policy only decides which
tensors the backward finds saved and which it rebuilds. So loss and every
gradient leaf must match rung 0's, in both families, with and without
tensor parallelism, and with the flash kernel's tagged outputs in play. The
text of the compiled program says what a rung buys under tp: the recomputed
forward's all-reduce is gone from the rung that keeps the attention
projection's output past its reduce. And `remat="auto"` with a budget that
fits nothing is the program `remat=True` has always been. What `auto`
resolves to is a SET of the ladder's groups (one climb that passes over a
group that does not fit), spelt as a rung where it is one: the climb is
held on hand-made sizes, and a set that is no prefix to rung 0's numbers.
"""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_from_scratch_tpu.config import MeshConfig, ModelConfig
from distributed_pytorch_from_scratch_tpu.models.gpt2 import GPT2Transformer
from distributed_pytorch_from_scratch_tpu.models.transformer import (
    REMAT_LADDER, REMAT_RUNGS, Transformer, remat_groups)
from distributed_pytorch_from_scratch_tpu.runtime.mesh import make_mesh

CFG = ModelConfig(attn_dim=32, ffn_dim=64, num_heads=4, num_layers=2,
                  vocab_size=96, maxlen=32)
FAMILIES = {"gpt2": GPT2Transformer, "llama": Transformer}
UPPER_RUNGS = REMAT_RUNGS[1:]
# a set of the ladder's groups that is no rung's prefix: what `auto` gets
# where the MLP's stacks do not fit and what stands behind them does
PASSED_OVER = "true+flash+dots"


def batch(t=16, b=4):
    ids = jax.random.randint(jax.random.key(3), (b, t + 1), 0,
                             CFG.vocab_size)
    pos = jnp.tile(jnp.arange(t, dtype=jnp.int32), (b, 1))
    return ids[:, :-1], ids[:, 1:], pos


@functools.lru_cache(maxsize=None)
def loss_and_grads(family, tp, remat, attn_impl="xla", budget=None):
    mesh = make_mesh(MeshConfig(dp=1, tp=tp), devices=jax.devices()[:tp])
    model = FAMILIES[family](CFG, tp_size=tp, remat=remat,
                             attn_impl=attn_impl, remat_budget_gib=budget)
    params = jax.device_put(model.init(jax.random.key(0)),
                            model.shardings(mesh))
    loss, grads = jax.jit(jax.value_and_grad(model.make_loss(mesh)))(
        params, *batch())
    return float(loss), jax.tree.map(np.asarray, grads)


def test_ladder_names_are_ordered_and_unique():
    names = [n for _, ns in REMAT_LADDER for n in ns]
    assert len(names) == len(set(names))
    assert REMAT_RUNGS[0] == "true" and REMAT_RUNGS[-1] == "dots"
    assert remat_groups(True) == remat_groups("true") == ()
    assert remat_groups("dots") == REMAT_RUNGS[1:]      # a rung: its prefix
    assert remat_groups("flash") == ("attn_proj", "ffn", "flash")
    assert remat_groups(PASSED_OVER) == ("flash", "dots")   # a set: itself
    assert remat_groups("true+flash") == ("flash",)
    for bad in (1, "sometimes", None, "flash+dots", "true+dots+flash",
                "true+", "true+true", "true+flash+flash", "true+sometimes"):
        with pytest.raises(ValueError, match="remat must be"):
            Transformer(CFG, remat=bad)
    assert Transformer(CFG, remat=PASSED_OVER).remat == PASSED_OVER
    assert Transformer(CFG).remat == "auto" == GPT2Transformer(CFG).remat


@pytest.mark.parametrize("rung", UPPER_RUNGS + (PASSED_OVER,))
@pytest.mark.parametrize("tp", [1, 2])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_every_rung_gives_rung_zero_gradients(family, tp, rung):
    want_loss, want = loss_and_grads(family, tp, True)
    loss, grads = loss_and_grads(family, tp, rung)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("rung", UPPER_RUNGS)
def test_every_rung_with_the_flash_kernels_tagged_outputs(rung):
    """`flash_out` / `flash_lse` exist only on the kernel path: under the
    interpreter the rungs that keep them must still give rung 0's numbers."""
    want_loss, want = loss_and_grads("gpt2", 1, True, "flash_interpret")
    loss, grads = loss_and_grads("gpt2", 1, rung, "flash_interpret")
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


def grad_program(family, remat, mesh_cfg, budget=None, **layout):
    mesh = make_mesh(mesh_cfg, devices=jax.devices()[:mesh_cfg.world_size])
    model = FAMILIES[family](CFG, tp_size=mesh_cfg.tp, remat=remat,
                             remat_budget_gib=budget, **layout)
    params = jax.eval_shape(model.init, jax.random.key(0))
    return jax.jit(jax.value_and_grad(model.make_loss(mesh))).lower(
        params, *batch())


def rematted(hlo_text: str, collective: str) -> int:
    return sum(1 for line in hlo_text.splitlines()
               if re.search(rf"= .*\b{collective}(-start)?\(", line)
               and "rematted_computation" in line)


@pytest.mark.parametrize("layout", ["replicated", "default"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_recomputed_all_reduce_goes_with_the_projection_output(family, layout):
    """dp2 x tp2 on four virtual devices. The layers are one scan, so the
    compiled gradient holds one layer body. In the replicated layout its
    recomputed forward has, at rung 0 (and on every rung that recomputes
    the attention projection), exactly one tensor-parallel all-reduce, the
    attention projection's (the MLP projection's is dead code: a layer's
    output is not a residual); on the rung that keeps `attn_proj`, named
    past the reduce, there is none. In the layout a model picks for itself
    at tp 2 (sequence parallelism over the ring matmuls) the recomputed
    forward's collectives are ring hops: none is an all-reduce, keeping
    `attn_proj` (named past the reduce-scatter ring) takes hops away, and
    on the top rung nothing is left to recompute over the wire."""
    mesh_cfg = MeshConfig(dp=2, tp=2)
    kw = dict(sequence_parallel=False) if layout == "replicated" else {}
    texts = {r: grad_program(family, r, mesh_cfg, **kw).compile().as_text()
             for r in REMAT_RUNGS}
    reduces = {r: rematted(t, "all-reduce") for r, t in texts.items()}
    keeps = next(i for i, (_, names) in enumerate(REMAT_LADDER)
                 if "attn_proj" in names)
    if layout == "replicated":
        assert reduces == {r: (1 if i < keeps else 0)
                           for i, r in enumerate(REMAT_RUNGS)}, reduces
        return
    hops = [rematted(texts[r], "collective-permute") for r in REMAT_RUNGS]
    assert not any(reduces.values()), reduces
    assert hops[keeps] < hops[keeps - 1] and hops[-1] == 0, hops
    assert hops == sorted(hops, reverse=True), hops


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_auto_with_a_budget_that_fits_nothing_is_todays_program(family):
    """The control of the mechanism: `remat="auto"` (the default) sized
    against a budget nothing fits lowers to the text of `remat=True`; on
    this backend, which reports no memory_stats, so does auto with no
    budget at all; and a budget everything fits gives the top rung's."""
    mesh_cfg = MeshConfig(dp=1, tp=2)
    floor = grad_program(family, True, mesh_cfg).as_text()
    assert grad_program(family, "auto", mesh_cfg, 1e-9).as_text() == floor
    assert grad_program(family, "auto", mesh_cfg).as_text() == floor
    top = grad_program(family, "dots", mesh_cfg).as_text()
    assert top != floor
    assert grad_program(family, "auto", mesh_cfg, 1e3).as_text() == top


def test_auto_records_its_choice_on_the_tracer(tmp_path, capsys):
    """One line on stderr and one instant event on the program's tracer:
    rung, its estimate, every estimate tried, budget and reserve."""
    import json

    from distributed_pytorch_from_scratch_tpu.obs.trace import SpanTracer
    from distributed_pytorch_from_scratch_tpu.training import memory
    memory.select_remat_traced.cache_clear()
    tracer = SpanTracer(str(tmp_path))
    try:
        grad_program("gpt2", "auto", MeshConfig(dp=1, tp=1), 1e3)
        grad_program("gpt2", "auto", MeshConfig(dp=1, tp=1), 1e3)
    finally:
        tracer.close()
    events = [json.loads(line) for line in open(tmp_path / "trace.jsonl")]
    chosen = [e for e in events if e["name"] == "remat_auto"]
    assert len(chosen) == 1                      # once per (model, shapes)
    args = chosen[0]["args"]
    assert args["rung"] == "dots" and args["budget_gib"] == 1e3
    # (nothing tags `attn_proj` at tp 1: not kept, and not passed over)
    assert args["kept"] == ["ffn", "flash", "dots"]
    assert args["passed_over"] == {}
    assert args["estimate_gib"] == args["estimate_gib.dots"] > 0
    assert args["reserve_gib"] > 0 and args["usable_gib"] < 1e3
    err = capsys.readouterr().err
    assert err.count("remat auto: picked 'dots'") == 1
    assert dataclasses.replace(GPT2Transformer(CFG), remat="flash").remat \
        == "flash"


GIB = 1024 ** 3


def hand_made_parts(floor, false=99.0, resident=1.0, **groups):
    """A `parts` for `_pick` with no model behind it: the floor's GiB and
    what each group of the ladder adds to it (0: no layer tags its names)."""
    def parts(value):
        total = false if value == "false" else floor + sum(
            groups.get(group, 0.0) for group in remat_groups(value))
        return {"total": total * GIB, "resident": resident * GIB,
                "grads": 0.5 * GIB}
    return parts


# the case: (parts, the GiB usable, _pick's reserve and allow_false, (what
# comes back, kept, passed over with the estimate that refused it,
# reserve_held))
CLIMBS = {
    # a group too large in the middle is passed over, a later one is kept
    "passes_over_the_middle": (
        hand_made_parts(10.0, ffn=4.0, flash=0.5, dots=0.4), 13.0, 0.0,
        False, (PASSED_OVER, ["flash", "dots"], {"ffn": 14.0}, True)),
    "the_last_kept_alone": (
        hand_made_parts(10.0, ffn=4.0, flash=0.5, dots=3.0), 13.0, 0.0,
        False, ("true+flash", ["flash"], {"ffn": 14.0, "dots": 13.5}, True)),
    # a kept set that is a rung's prefix comes back under the rung's name,
    # the lowest that keeps the same (`dots` adds nothing here)
    "a_prefix_is_its_rung": (
        hand_made_parts(10.0, attn_proj=0.2, ffn=1.0, flash=0.5), 13.0, 0.0,
        False, ("flash", ["attn_proj", "ffn", "flash"], {}, True)),
    # a group that adds no bytes is neither kept nor passed over, and the
    # rung's name stands for the set it keeps beside such groups
    "no_bytes_is_neither": (
        hand_made_parts(10.0, flash=0.5, dots=5.0), 13.0, 0.0, False,
        ("flash", ["flash"], {"dots": 15.5}, True)),
    "every_group_fits": (
        hand_made_parts(10.0, ffn=1.0, flash=0.5, dots=0.4), 13.0, 0.0,
        False, ("dots", ["ffn", "flash", "dots"], {}, True)),
    # nothing fits: the floor, and no reserve is held where the floor does
    # not fit beside it
    "nothing_fits": (
        hand_made_parts(20.0, ffn=1.0, flash=0.5), 13.0, None, False,
        ("true", [], {"ffn": 21.0, "flash": 20.5}, False)),
    # the reserve is held where the floor fits beside it, and what is kept
    # fits beside it too
    "beside_the_reserve": (
        hand_made_parts(9.0, ffn=4.5, flash=0.5, dots=4.0), 13.0, None,
        False, ("true+flash", ["flash"], {"ffn": 13.5, "dots": 13.5}, True)),
    # 'false' stands above the ladder and is asked first
    "false_is_asked_first": (
        hand_made_parts(10.0, false=11.0, ffn=1.0), 13.0, 0.0, True,
        ("false", [], {}, True)),
    "false_does_not_fit": (
        hand_made_parts(10.0, false=15.0, ffn=1.0), 13.0, 0.0, True,
        ("ffn", ["ffn"], {}, True)),
}


@pytest.mark.parametrize("case", sorted(CLIMBS))
def test_the_climb_keeps_what_fits_and_passes_over_what_does_not(
        case, tmp_path, capsys):
    """`training/memory._pick` on hand-made sizes: one walk of the ladder
    in its order from the floor, each group sized on top of those kept."""
    import json

    from distributed_pytorch_from_scratch_tpu.obs.trace import SpanTracer
    from distributed_pytorch_from_scratch_tpu.training import memory
    parts, usable, reserve, allow_false, want = CLIMBS[case]
    # (a reserve that is not named is the resident state's 1 GiB, where
    # the floor fits beside it)
    beside = reserve if reserve is not None else 1.0 * want[3]
    tracer = SpanTracer(str(tmp_path))
    try:
        picked = memory._pick(parts, usable / memory.MARGIN + beside,
                              reserve, allow_false=allow_false, verbose=True)
    finally:
        tracer.close()
    said, = (json.loads(line)["args"] for line in open(
        tmp_path / "trace.jsonl") if '"remat_auto"' in line)
    assert (picked, said["kept"], said["passed_over"],
            said["reserve_held"]) == want
    assert said["rung"] == picked
    assert said["estimate_gib"] == said[f"estimate_gib.{picked}"]
    assert "estimate_gib.true" in said          # the floor is always said
    err = capsys.readouterr().err
    assert f"remat auto: picked '{picked}'" in err
    assert ("passing over " + ", ".join(want[2]) in err) == bool(want[2])
    if picked != "false":
        # what comes back keeps exactly the groups that were kept, beside
        # groups that add nothing
        assert [g for g in remat_groups(picked)
                if parts(f"true+{g}")["total"] > parts("true")["total"]
                ] == said["kept"]


def test_a_joined_sets_stacks_are_the_floors_and_its_groups_names():
    """`step_bytes` of a set that is no prefix: the floor's stacks plus the
    kept groups' names, each over the layers that tag it, and nothing of
    the group between."""
    from distributed_pytorch_from_scratch_tpu.training.memory import (
        step_bytes)
    b, t, heads, hd, vhd, kd, layers = 2, 512, 8, 16, 32, 64, 6
    tagged = {"flash_out": 3, "flash_lse": 3, "q_proj": 3, "k_proj": 2,
              "v_proj": 2, "ffn_gate": 6, "ffn_up": 6, "ffn_fc": 0}
    sized = functools.partial(
        step_bytes, param_count=1e6, layer_param_count=9e5, b=b, t=t, d=128,
        kd=kd, f=512, heads=heads, head_dim=hd, layers=layers, vocab=1000,
        tagged_layers=tagged, v_head_dim=vhd)
    tok = b * t
    flash = 3 * tok * heads * (vhd * 2 + 4)
    dots = tok * 2 * (3 * heads * hd + 2 * kd + 2 * kd)
    floor = sized("true")["stacks"]
    assert sized("true+flash")["stacks"] == floor + flash
    assert sized(PASSED_OVER)["stacks"] == floor + flash + dots
    assert sized("true+dots")["stacks"] == floor + dots
    ffn = 2 * 6 * tok * 512 * 2
    assert sized("dots")["stacks"] == floor + ffn + flash + dots
    # a prefix spelt as a set is the rung
    assert sized("true+attn_proj+ffn") == sized("ffn")
    for part in ("resident", "grads", "cast", "head", "layer"):
        assert sized(PASSED_OVER)[part] == sized("true")[part]


def test_attn_proj_is_named_only_past_a_reduce():
    """With tp = 1 nothing tags `attn_proj`, so its rung is rung 0's
    program with the policy's wrapper around it: the same residuals, and
    the estimate charges it nothing."""
    from distributed_pytorch_from_scratch_tpu.training.memory import (
        estimate_step_gib)
    kw = dict(batch=4, seqlen=16)
    assert estimate_step_gib(CFG, remat="attn_proj", **kw) \
        == estimate_step_gib(CFG, remat="true", **kw)
    assert estimate_step_gib(CFG, remat="attn_proj", tp=2, world=2, **kw) \
        > estimate_step_gib(CFG, remat="true", tp=2, world=2, **kw)
    saved = lambda tp, r: str(jax.make_jaxpr(jax.grad(
        FAMILIES["gpt2"](CFG, tp_size=tp, remat=r).make_loss(
            make_mesh(MeshConfig(dp=1, tp=tp), devices=jax.devices()[:tp]))))(
        jax.eval_shape(FAMILIES["gpt2"](CFG, tp_size=tp).init,
                       jax.random.key(0)), *batch())).count("name=attn_proj")
    assert saved(1, "attn_proj") == 0 and saved(2, "attn_proj") > 0


# ---- the flash kernel's kept outputs (PR 62) ----

def _masks():
    from distributed_pytorch_from_scratch_tpu.ops.attention import (
        CAUSAL, block_diffusion, sliding_window)
    return {"causal": CAUSAL, "window": sliding_window(128),
            "block_diagonal": block_diffusion(4, 128)}


@pytest.mark.parametrize("mask,d,dv", [
    ("causal", 64, 64), ("causal", 128, 128), ("window", 128, 128),
    ("block_diagonal", 128, 128),
    ("causal", 192, 128)])          # latent attention's two widths
def test_the_kept_lse_is_lane_dense(mask, d, dv):
    """Under the `flash` rung's names the residual called `flash_lse` is
    (b h, t) float32, t on the lanes: tok x h x 4 bytes, where the kernels'
    (b h, t, 1) pads its one lane to 128 in HBM. The kernel's output is
    kept beside it as it is."""
    from jax._src.ad_checkpoint import saved_residuals

    from distributed_pytorch_from_scratch_tpu.ops.pallas.flash_attention \
        import flash_attention
    b, h, hkv, t = 2, 4, 2, 256
    attend = jax.checkpoint(
        lambda q, k, v: flash_attention(
            q, k, v, interpret=True, mask=_masks()[mask]).astype(
                jnp.float32).sum(),
        prevent_cse=False,
        policy=jax.checkpoint_policies.save_only_these_names(
            "flash_out", "flash_lse"))
    arg = lambda heads, w: jax.ShapeDtypeStruct((b, heads, t, w),
                                                jnp.bfloat16)
    saved = saved_residuals(attend, arg(h, d), arg(hkv, d), arg(hkv, dv))
    lse, = (aval for aval, why in saved if "named 'flash_lse'" in why)
    assert lse.shape == (b * h, t) and lse.dtype == jnp.float32
    assert lse.size * lse.dtype.itemsize == b * t * h * 4
    # what is kept beside it: q, k, v (the arguments) and the output
    kept = sorted(aval.shape for aval, why in saved if "argument" not in why)
    assert kept == [(b * h, t), (b * h, t, dv)], saved


def test_the_flash_rung_runs_the_forward_kernel_once_a_layer():
    """The gradient's jaxpr holds one layer body forward and one backward
    (the layers are a scan): at rung 0 the backward's recompute calls the
    flash forward again, two calls in all; from the `flash` rung up the
    backward finds `flash_out` / `flash_lse` saved and one call is left,
    with the same backward kernels either way."""
    calls = {}
    for rung in REMAT_RUNGS + ("true+flash",):
        mesh = make_mesh(MeshConfig(dp=1, tp=1), devices=jax.devices()[:1])
        model = GPT2Transformer(CFG, remat=rung,
                                attn_impl="flash_interpret")
        text = str(jax.make_jaxpr(jax.grad(model.make_loss(mesh)))(
            jax.eval_shape(model.init, jax.random.key(0)), *batch()))
        calls[rung] = (len(re.findall(r"\bname=flash_fwd\b", text)),
                       len(re.findall(r"\bname=flash_bwd\w*\b", text)))
    keeps = REMAT_RUNGS.index("flash")
    backward = calls["true"][1]     # (the interpreter's split pair here)
    assert backward >= 1
    # (and with the `flash` group kept WITHOUT the MLP's `ffn_fc` below it)
    assert calls == {"true+flash": (1, backward),
                     **{r: (2 if i < keeps else 1, backward)
                        for i, r in enumerate(REMAT_RUNGS)}}, calls


@functools.lru_cache(maxsize=None)
def drawn_loss_and_grads(remat):
    """A drawn family with a layer of each sort: conv_moe's dense
    convolution layer (tags the MLP's names only), its attention layer
    (the flash names and q, k, v) and an expert convolution layer (none),
    the kernels under the interpreter."""
    from distributed_pytorch_from_scratch_tpu.config import model_preset
    from distributed_pytorch_from_scratch_tpu.models import build_model
    cfg = model_preset("tiny-conv-moe")
    cfg = dataclasses.replace(
        cfg, num_layers=3, conv_moe=dataclasses.replace(
            cfg.conv_moe, layer_types=("conv", "full_attention", "conv"),
            num_dense_layers=1))
    mesh = make_mesh(MeshConfig(dp=1, tp=1), devices=jax.devices()[:1])
    model = build_model("conv_moe", cfg, remat=remat,
                        attn_impl="flash_interpret")
    assert model.tagged_layers == {
        "ffn_fc": 0, "ffn_gate": 1, "ffn_up": 1, "flash_out": 1,
        "flash_lse": 1, "q_proj": 1, "k_proj": 1, "v_proj": 1}
    ids = jax.random.randint(jax.random.key(3), (2, 129), 0, cfg.vocab_size)
    pos = jnp.tile(jnp.arange(128, dtype=jnp.int32), (2, 1))
    loss, grads = jax.jit(jax.value_and_grad(model.make_loss(mesh)))(
        model.init(jax.random.key(0)), ids[:, :-1], ids[:, 1:], pos)
    return float(loss), jax.tree.map(np.asarray, grads)


@pytest.mark.parametrize("rung", UPPER_RUNGS + (PASSED_OVER,))
def test_every_rung_gives_rung_zero_gradients_in_a_drawn_family(rung):
    want_loss, want = drawn_loss_and_grads(True)
    loss, grads = drawn_loss_and_grads(rung)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
