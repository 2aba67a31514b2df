"""The ZeRO ladder (training/zero.py): identical math, sharded memory.

No reference counterpart (plain per-rank Adam, `/root/reference/train.py:83`;
SURVEY §2.4 "ZeRO ❌"). Invariants pinned here, on the virtual 8-device mesh:

* stage 1 — training with zero stage 1 produces bit-comparable
  params/losses to the plain path (it is a layout change, not an algorithm
  change); the moments actually live dp-sharded on device; checkpoints
  round-trip the dp-sharded state.
* stage 2 — the bucketed REDUCE-SCATTER grad path is value-parity with the
  whole-tree transpose-derived reducer at dp4 (f32 at the exact-bound
  tolerances; int8 within the PR 8 quant bound — and measurably different
  from f32, proving the quantized ring actually ran), the grads really come
  back dp-sharded, and the full train step matches plain Adam step for step.
* stage 3 — params rest dp-sharded (measured bytes/device shrink ~1/dp),
  the gather-on-demand train step's loss trajectory matches the ZeRO-1 run
  at dp2 x tp2 + SP, and the stage trains a budget the ZeRO-1 memory
  estimate refuses (the ISSUE 9 acceptance pair).
* scope — stages 2/3 refuse MoE / pp>1 / tp>1-without-SP loudly; stage 3
  refuses remat=False and a compressed --dp_reduce_dtype; --zero 2 + int8
  routes through the quantized reduce-scatter rather than silently
  falling back.
* checkpoints — dp-sharded stage-2/3 state saves through
  training/checkpoint.py + validate_checkpoint and resumes BIT-IDENTICAL
  at dp2 (feeds ROADMAP item 5's resharding story).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_from_scratch_tpu.config import (
    MeshConfig, ModelConfig, OptimizerConfig)
from distributed_pytorch_from_scratch_tpu.models.transformer import Transformer
from distributed_pytorch_from_scratch_tpu.runtime.mesh import make_mesh
from distributed_pytorch_from_scratch_tpu.training.checkpoint import (
    load_checkpoint, save_checkpoint, validate_checkpoint)
from distributed_pytorch_from_scratch_tpu.training.optim import (
    AdamState, init_adam_state)
from distributed_pytorch_from_scratch_tpu.training.train_step import (
    build_train_step)
from distributed_pytorch_from_scratch_tpu.training.zero import (
    build_bucketed_grad_fn, build_zero3_grad_fn, zero1_moment_shardings,
    zero1_specs, zero3_shardings)

CFG = ModelConfig(attn_dim=32, ffn_dim=64, num_heads=8, num_layers=2,
                  vocab_size=96, maxlen=32)
OCFG = OptimizerConfig(lr=1e-3, warmup_steps=5, max_steps=50)
MOE_CFG = ModelConfig(attn_dim=32, ffn_dim=64, num_heads=8, num_layers=2,
                      vocab_size=96, maxlen=64, num_experts=4)


def make_batch(key, batch=8, t=16, vocab=96):
    k1, k2 = jax.random.split(key)
    ids = jax.random.randint(k1, (batch, t), 0, vocab)
    tgt = jax.random.randint(k2, (batch, t), 0, vocab)
    pos = jnp.tile(jnp.arange(t)[None, :], (batch, 1))
    return ids, tgt, pos


def put_opt(opt, mesh, moment_sh):
    scalar = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    return jax.device_put(opt, AdamState(step=scalar, mu=moment_sh,
                                         nu=moment_sh))


def tree_bytes_per_device(tree) -> float:
    """Measured resident bytes per mesh device (sums addressable shards
    over the devices that hold them)."""
    leaves = jax.tree.leaves(tree)
    total = sum(sum(s.data.nbytes for s in leaf.addressable_shards)
                for leaf in leaves)
    devices = {s.device for leaf in leaves for s in leaf.addressable_shards}
    return total / max(len(devices), 1)


# ---------------------------------------------------------------- stage 1 --

@pytest.mark.parametrize("dp,tp", [(4, 2), (8, 1), (2, 4)])
def test_zero1_matches_plain_adam(dp, tp):
    mesh = make_mesh(MeshConfig(dp=dp, tp=tp))
    model = Transformer(CFG, tp_size=tp)
    key = jax.random.key(0)
    params_a = jax.device_put(model.init(key), model.shardings(mesh))
    params_b = jax.tree.map(jnp.copy, params_a)

    step_plain = build_train_step(model, mesh, OCFG)
    step_zero = build_train_step(model, mesh, OCFG, zero1=True)
    opt_a = put_opt(init_adam_state(params_a), mesh, model.shardings(mesh))
    opt_b = put_opt(init_adam_state(params_b), mesh,
                    zero1_moment_shardings(model, mesh))

    for s in range(10):
        ids, tgt, pos = make_batch(jax.random.fold_in(key, s))
        params_a, opt_a, loss_a = step_plain(params_a, opt_a, ids, tgt, pos)
        params_b, opt_b, loss_b = step_zero(params_b, opt_b, ids, tgt, pos)
        np.testing.assert_allclose(float(loss_a), float(loss_b),
                                   rtol=1e-6, atol=1e-7)

    for a, b in zip(jax.tree.flatten(params_a)[0], jax.tree.flatten(params_b)[0]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_moments_are_dp_sharded():
    dp, tp = 4, 2
    mesh = make_mesh(MeshConfig(dp=dp, tp=tp))
    model = Transformer(CFG, tp_size=tp)
    params = jax.device_put(model.init(jax.random.key(0)),
                            model.shardings(mesh))
    opt = put_opt(init_adam_state(params), mesh,
                  zero1_moment_shardings(model, mesh))
    step = build_train_step(model, mesh, OCFG, zero1=True)
    ids, tgt, pos = make_batch(jax.random.key(1))
    params, opt, _ = step(params, opt, ids, tgt, pos)

    # the big moment leaves must be dp-sharded on device after the step
    big = opt.mu["layers"]["wq"]["weight"]          # (L, d, d/tp)
    local = big.addressable_shards[0].data.size
    assert local * dp * tp == big.size, (
        f"wq moment not dp-sharded: local={local}, global={big.size}")
    # and params stay replicated over dp (sharded only over tp)
    pw = params["layers"]["wq"]["weight"]
    assert pw.addressable_shards[0].data.size * tp == pw.size


def test_zero1_specs_fallback_replicated():
    """Leaves with no free dp-divisible dim keep their param spec."""
    mesh = make_mesh(MeshConfig(dp=8, tp=1))
    import jax.sharding as shd
    P = shd.PartitionSpec
    specs = {"w": P(None, None)}
    shapes = {"w": jax.ShapeDtypeStruct((3, 5), jnp.float32)}  # nothing divides by 8
    out = zero1_specs(specs, shapes, mesh)
    assert out["w"] == P(None, None)


def test_zero1_checkpoint_roundtrip(tmp_path):
    dp, tp = 2, 2
    mesh = make_mesh(MeshConfig(dp=dp, tp=tp))
    model = Transformer(CFG, tp_size=tp)
    params = jax.device_put(model.init(jax.random.key(0)),
                            model.shardings(mesh))
    opt = put_opt(init_adam_state(params), mesh,
                  zero1_moment_shardings(model, mesh))
    step = build_train_step(model, mesh, OCFG, zero1=True)
    ids, tgt, pos = make_batch(jax.random.key(2))
    for s in range(3):
        params, opt, _ = step(params, opt, ids, tgt, pos)

    save_checkpoint(str(tmp_path), 3, 1.0, params, model.specs(), tp,
                    opt_state=opt)
    p2, opt2, it = load_checkpoint(str(tmp_path), 3, params, model.specs(),
                                   with_opt=True)
    assert it == 3
    for a, b in zip(jax.tree.flatten(opt.mu)[0], jax.tree.flatten(opt2.mu)[0]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-7)


# ---------------------------------------------------------------- stage 2 --

# the tp layouts ZeRO's hand-reduced grads are pinned on: sequence
# parallelism asked for by name (the monolithic gather/reduce-scatter), and
# what a model picks for itself at tp > 1 (the same over the ring matmuls)
TP_LAYOUTS = [pytest.param(dict(sequence_parallel=True), id="sp"),
              pytest.param({}, id="default")]


@pytest.mark.parametrize("layout", TP_LAYOUTS)
def test_zero2_grads_match_whole_tree_reducer(layout):
    """ISSUE 9 acceptance: the bucketed reduce-scatter grad path at dp4 is
    value-parity with the whole-tree transpose-derived reducer (f32, exact
    bound — same tolerances as the stage-1 bucketed parity pin), AND the
    grads really come back dp-sharded (half the wire would be no win if
    every rank still materialised the full tree)."""
    dp, tp = 4, 2
    mesh = make_mesh(MeshConfig(dp=dp, tp=tp))
    model = Transformer(CFG, tp_size=tp, **layout)
    assert model.tp_layout(32)[0]
    params = model.init(jax.random.key(0))
    ids, tgt, pos = make_batch(jax.random.key(2), t=32)
    l0, g0 = jax.jit(jax.value_and_grad(
        model.make_loss(mesh)))(params, ids, tgt, pos)
    # tiny buckets force many reduce-scatters: the schedule is exercised
    l2, g2 = jax.jit(build_bucketed_grad_fn(
        model, mesh, bucket_mb=0.001, zero_stage=2))(params, ids, tgt, pos)
    np.testing.assert_allclose(float(l2), float(l0), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g2), jax.tree.leaves(g0)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    # sharding: a big grad leaf holds only 1/(dp*tp) locally
    big = g2["layers"]["wq"]["weight"]
    assert big.addressable_shards[0].data.size * dp * tp == big.size, (
        "zero-2 grads must be dp-sharded, not replicated")


def test_zero2_int8_wire_within_quant_bound():
    """--zero 2 --dp_reduce_dtype int8: the bucket routes through the
    quantized reduce-scatter (PR 8's ring stopped at its RS half). Pinned
    BOTH ways: within the PR 8 bound of the f32 reduction, and NOT
    bit-identical to it — a silent f32 fallback would pass a pure
    closeness check."""
    dp = 4
    mesh = make_mesh(MeshConfig(dp=dp, tp=1))
    model = Transformer(CFG)
    params = model.init(jax.random.key(0))
    ids, tgt, pos = make_batch(jax.random.key(2), t=32)
    _, g32 = jax.jit(build_bucketed_grad_fn(
        model, mesh, bucket_mb=0.001, zero_stage=2))(params, ids, tgt, pos)
    _, g8 = jax.jit(build_bucketed_grad_fn(
        model, mesh, bucket_mb=0.001, reduce_dtype=jnp.int8,
        zero_stage=2))(params, ids, tgt, pos)
    worst, bitwise_same = 0.0, True
    for a, b in zip(jax.tree.leaves(g8), jax.tree.leaves(g32)):
        assert a.dtype == jnp.float32  # wire-only compression
        scale = max(float(jnp.max(jnp.abs(b))), 1e-8)
        err = float(jnp.max(jnp.abs(a - b))) / scale
        worst = max(worst, err)
        bitwise_same &= bool(jnp.array_equal(a, b))
    assert worst < 2.0 ** -4, f"int8 RS wire error {worst} out of bounds"
    assert not bitwise_same, (
        "int8 grads bit-identical to f32: the quantized reduce-scatter "
        "silently did not run")


def test_zero2_matches_plain_adam():
    """Full stage-2 train step (reduce-scattered grads + dp-sharded
    moments + param all-gather) is step-for-step parity with plain Adam."""
    dp, tp = 4, 2
    mesh = make_mesh(MeshConfig(dp=dp, tp=tp))
    model = Transformer(CFG, tp_size=tp, sequence_parallel=True)
    key = jax.random.key(0)
    params_a = jax.device_put(model.init(key), model.shardings(mesh))
    params_b = jax.tree.map(jnp.copy, params_a)
    step_plain = build_train_step(model, mesh, OCFG)
    step_z2 = build_train_step(model, mesh, OCFG, zero=2)
    opt_a = put_opt(init_adam_state(params_a), mesh, model.shardings(mesh))
    opt_b = put_opt(init_adam_state(params_b), mesh,
                    zero1_moment_shardings(model, mesh))
    for s in range(6):
        ids, tgt, pos = make_batch(jax.random.fold_in(key, s), t=32)
        params_a, opt_a, loss_a = step_plain(params_a, opt_a, ids, tgt, pos)
        params_b, opt_b, loss_b = step_z2(params_b, opt_b, ids, tgt, pos)
        np.testing.assert_allclose(float(loss_a), float(loss_b),
                                   rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree.leaves(params_a), jax.tree.leaves(params_b)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------- stage 3 --

def test_zero3_param_bytes_shrink():
    """ZeRO-3's memory claim, MEASURED: params device_put at
    zero3_shardings occupy ~1/dp the per-device bytes of the replicated
    layout (slack for the few indivisible leaves)."""
    dp = 4
    mesh = make_mesh(MeshConfig(dp=dp, tp=2))
    model = Transformer(CFG, tp_size=2, sequence_parallel=True,
                        remat="dots")
    params = model.init(jax.random.key(0))
    full = tree_bytes_per_device(
        jax.device_put(params, model.shardings(mesh)))
    shard = tree_bytes_per_device(
        jax.device_put(params, zero3_shardings(model, mesh)))
    assert shard <= full / dp * 1.35, (
        f"zero-3 params not ~1/dp per device: {shard} vs full {full}")


def test_zero3_loss_trajectory_matches_zero1():
    """ISSUE 9 acceptance: 3-step loss trajectory of the gather-on-demand
    ZeRO-3 step within tolerance of the ZeRO-1 run at dp2 x tp2 + SP
    (different float summation orders — the ring gathers and the scattered
    update — so allclose, not bitwise)."""
    mesh = make_mesh(MeshConfig(dp=2, tp=2))
    model = Transformer(CFG, tp_size=2, sequence_parallel=True,
                        remat="dots")
    key = jax.random.key(0)
    init = model.init(key)
    params_1 = jax.device_put(init, model.shardings(mesh))
    params_3 = jax.device_put(init, zero3_shardings(model, mesh))
    step_1 = build_train_step(model, mesh, OCFG, zero=1)
    step_3 = build_train_step(model, mesh, OCFG, zero=3)
    opt_1 = put_opt(init_adam_state(init), mesh,
                    zero1_moment_shardings(model, mesh))
    opt_3 = put_opt(init_adam_state(init), mesh, zero3_shardings(model, mesh))
    for s in range(3):
        ids, tgt, pos = make_batch(jax.random.fold_in(key, s), t=32)
        params_1, opt_1, loss_1 = step_1(params_1, opt_1, ids, tgt, pos)
        params_3, opt_3, loss_3 = step_3(params_3, opt_3, ids, tgt, pos)
        np.testing.assert_allclose(float(loss_3), float(loss_1),
                                   rtol=1e-4, atol=1e-5)
    # params stay dp-sharded at rest after the donated step
    big = params_3["layers"]["wq"]["weight"]
    assert big.addressable_shards[0].data.size * 2 * 2 == big.size


@pytest.mark.parametrize("layout", TP_LAYOUTS)
@pytest.mark.parametrize("family", ["llama", "gpt2"])
def test_zero3_grads_match_whole_tree_reducer(family, layout):
    """The gather-transpose grad path (no explicit dp reduction at all)
    equals the whole-tree reducer on every leaf — the stage-3 sibling of
    the stage-2 parity pin, dp2 x tp2 + SP, BOTH families (the per-layer
    gather hook lives in each family's _layer_body)."""
    from distributed_pytorch_from_scratch_tpu.models.gpt2 import (
        GPT2Transformer)
    cls = GPT2Transformer if family == "gpt2" else Transformer
    mesh = make_mesh(MeshConfig(dp=2, tp=2))
    model = cls(CFG, tp_size=2, remat="dots", **layout)
    params = model.init(jax.random.key(0))
    ids, tgt, pos = make_batch(jax.random.key(2), t=32)
    l0, g0 = jax.jit(jax.value_and_grad(
        model.make_loss(mesh)))(params, ids, tgt, pos)
    p3 = jax.device_put(params, zero3_shardings(model, mesh))
    l3, g3 = jax.jit(build_zero3_grad_fn(
        model, mesh, bucket_mb=0.001))(p3, ids, tgt, pos)
    np.testing.assert_allclose(float(l3), float(l0), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(g3), jax.tree.leaves(g0)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-5)


def test_zero3_trains_past_zero1_budget():
    """The unlock, pinned with the estimator's own numbers: a budget that
    REFUSES the flagship shape under ZeRO-1 (even full remat exceeds it)
    fits comfortably under ZeRO-3 at dp8 — the config class item 4 exists
    for (params bigger than HBM x tp). The trajectory-parity half of the
    criterion is test_zero3_loss_trajectory_matches_zero1."""
    from distributed_pytorch_from_scratch_tpu.config import model_preset
    from distributed_pytorch_from_scratch_tpu.training.memory import (
        estimate_step_gib)
    cfg = model_preset("gpt2-355m")
    kw = dict(batch=8, seqlen=1024, tp=1, world=8, dp=8)
    z1_best = min(estimate_step_gib(cfg, remat=r, zero_stage=1, **kw)
                  for r in ("false", "dots", "true"))
    z3_dots = estimate_step_gib(cfg, remat="dots", zero_stage=3, **kw)
    budget = z1_best * 0.9  # a chip ZeRO-1 cannot fit even at full remat
    assert z1_best > budget
    assert z3_dots < budget, (
        f"zero-3 estimate {z3_dots:.2f} GiB must fit the {budget:.2f} GiB "
        f"budget zero-1 refuses (zero-1 best {z1_best:.2f})")
    # and the estimator ladder is monotone at fixed remat
    stages = [estimate_step_gib(cfg, remat="dots", zero_stage=z, **kw)
              for z in (0, 1, 2, 3)]
    assert stages == sorted(stages, reverse=True), stages


# ------------------------------------------------ scope refusals + resume --

def test_zero_scope_refusals():
    """Stages 2/3 refuse the configurations whose cotangent bookkeeping
    the static spec cannot express — loudly, at build time."""
    mesh_ep = make_mesh(MeshConfig(dp=2, ep=2, tp=2))
    with pytest.raises(ValueError, match="MoE"):
        build_bucketed_grad_fn(Transformer(MOE_CFG, tp_size=2, ep_size=2),
                               mesh_ep, zero_stage=2)
    with pytest.raises(ValueError, match="MoE"):
        build_zero3_grad_fn(Transformer(MOE_CFG, tp_size=2, ep_size=2),
                            mesh_ep)
    mesh_pp = make_mesh(MeshConfig(pp=2, tp=2))
    with pytest.raises(ValueError, match="pp_size"):
        build_zero3_grad_fn(
            Transformer(CFG, tp_size=2, pp_size=2, sequence_parallel=True),
            mesh_pp)
    mesh = make_mesh(MeshConfig(dp=2, tp=2))
    with pytest.raises(ValueError, match="sequence_parallel"):
        build_zero3_grad_fn(
            Transformer(CFG, tp_size=2, sequence_parallel=False), mesh)
    # stage 3 without remat would re-materialise the full replica as
    # backward residuals — refused, not silently absorbed
    with pytest.raises(ValueError, match="remat"):
        build_zero3_grad_fn(
            Transformer(CFG, tp_size=2, sequence_parallel=True, remat=False),
            mesh)
    # and build_bucketed_grad_fn only speaks stages 1/2
    with pytest.raises(ValueError, match="zero_stage"):
        build_bucketed_grad_fn(Transformer(CFG), mesh, zero_stage=3)


def test_zero_cli_refusals():
    """bench.py's argparse mirrors the loud scope refusals (the staged r12
    sweep parses through the same code): zero 3 never silently degrades
    the wire or drops remat, zero 2 + int8 is accepted WITHOUT an explicit
    bucket (stage 2 implies the bucketed reducer)."""
    import bench
    with pytest.raises(SystemExit) as e:
        bench.parse_args(["--zero", "3", "--dp_reduce_dtype", "int8",
                          "--dp_reduce_bucket_mb", "25"])
    assert e.value.code != 0
    with pytest.raises(SystemExit) as e:
        bench.parse_args(["--zero", "3", "--remat", "false"])
    assert e.value.code != 0
    with pytest.raises(SystemExit) as e:
        bench.parse_args(["--zero", "2", "--model", "45m-moe8"])
    assert e.value.code != 0
    # accepted: int8 wire under zero 2 with the implied default bucket
    args = bench.parse_args(["--zero", "2", "--dp_reduce_dtype", "int8",
                             "--dp", "2"])
    assert args.zero == 2 and args.dp_reduce_dtype == "int8"
    # zero 3 defaults remat to dots (never 'false')
    assert bench.parse_args(["--zero", "3", "--dp", "2"]).remat == "dots"


@pytest.mark.parametrize("stage", [2, 3])
def test_zero_checkpoint_bit_identical_resume(stage, tmp_path):
    """Save -> validate -> load -> resume is BIT-identical to the
    uninterrupted run at dp2, for dp-sharded stage-2 moments and stage-3
    params+moments alike: the checkpoint stores global arrays (no
    host-side full-tree gather — leaves stream one at a time), so
    device_put back onto the ZeRO layouts reconstructs the exact state."""
    dp, tp = 2, 2
    mesh = make_mesh(MeshConfig(dp=dp, tp=tp))
    model = Transformer(CFG, tp_size=tp, sequence_parallel=True,
                        remat="dots")
    key = jax.random.key(0)
    init = model.init(key)
    param_sh = (zero3_shardings(model, mesh) if stage == 3
                else model.shardings(mesh))
    moment_sh = (param_sh if stage == 3
                 else zero1_moment_shardings(model, mesh))
    step = build_train_step(model, mesh, OCFG, zero=stage)

    def run(params, opt, lo, hi):
        for s in range(lo, hi):
            ids, tgt, pos = make_batch(jax.random.fold_in(key, s), t=32)
            params, opt, _ = step(params, opt, ids, tgt, pos)
        return params, opt

    params = jax.device_put(init, param_sh)
    opt = put_opt(init_adam_state(init), mesh, moment_sh)
    params, opt = run(params, opt, 0, 2)
    save_checkpoint(str(tmp_path), 2, 1.0, params, model.specs(), tp,
                    opt_state=opt, zero_stage=stage)
    # uninterrupted continuation
    params_a, _ = run(jax.tree.map(jnp.copy, params),
                      jax.tree.map(jnp.copy, opt), 2, 4)
    # resumed continuation: validate -> load -> device_put at ZeRO layouts
    tp_found, _ = validate_checkpoint(str(tmp_path), 2)
    assert tp_found == tp
    p2, opt2, it = load_checkpoint(str(tmp_path), 2, init, model.specs(),
                                   with_opt=True)
    assert it == 2
    p2 = jax.device_put(p2, param_sh)
    opt2 = put_opt(AdamState(step=jnp.asarray(opt2.step), mu=opt2.mu,
                             nu=opt2.nu), mesh, moment_sh)
    params_b, _ = run(p2, opt2, 2, 4)
    for a, b in zip(jax.tree.leaves(params_a), jax.tree.leaves(params_b)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
