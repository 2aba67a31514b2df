"""ZeRO 1/2/3: shard the weight-update state over the data-parallel axis.

Absent from the reference (plain per-rank `optim.Adam`,
`/root/reference/train.py:83` — every rank keeps full moments; SURVEY §2.4
"ZeRO ❌"). The ladder, following "Automatic Cross-Replica Sharding of
Weight Update in Data-Parallel Training" (PAPERS.md):

* **Stage 1** — Adam moments get a PartitionSpec that additionally shards
  their first free, dp-divisible dimension over 'dp', and `jit`'s
  out_shardings pin them there. XLA's SPMD partitioner then computes each
  moment update (and the parameter delta) on the dp shard that owns it and
  all-gathers the updated parameters — the reduce-scatter/update/all-gather
  schedule, derived by the compiler instead of hand-written NCCL (the
  scaling-book recipe). Optimizer memory: 2/dp x param bytes.

* **Stage 2** — gradients too: `build_bucketed_grad_fn(zero_stage=2)` swaps
  each bucket's all-reduce for a RE­DUCE-SCATTER (`ops/overlap.
  bucketed_reduce_scatter` — same bucket boundaries, HALF the wire bytes),
  so every dp rank receives only the 1/dp grad shard it updates; the int8
  wire reuses PR 8's quantized ring stopped after its reduce-scatter half
  (`quantized_reduce_scatter`). The optimizer update is then fully local
  per shard and ONE parameter all-gather per step (XLA inserts it to meet
  the replicated param out_sharding) replaces the grads' gather half.
  Grad + optimizer memory: (1 + 2)/dp x param bytes.

* **Stage 3** — the parameters themselves: `zero3_specs` extends the param
  specs with a 'dp' dim (skipping the stacked layer axis so the scan still
  slices per layer), `build_zero3_grad_fn` runs the loss with params
  ENTERING shard_map dp-sharded, and the model's layer scan ring-all-
  gathers each layer's leaves on entry (`zero3_layer_gather`, called from
  `_layer_body` under the `zero3_axis` field — INSIDE the remat boundary,
  so gathered weights are recomputed rather than saved and peak param HBM
  is full/dp + one gathered layer). The backward derives the grad
  reduce-scatter for free: `ring_all_gather`'s transpose is the conjugate
  ppermute ring, handing each rank the dp-summed cotangent of exactly its
  own shard. Param + grad + optimizer memory: 4/dp x param bytes per
  device — the unlock for configs whose full replica exceeds HBM x tp.

Scope (stages 2/3): dense models, pp=1, and sequence_parallel whenever
tp > 1 — the same per-leaf cotangent bookkeeping scope as the bucketed
reducer; the refusals below are loud.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.overlap import (bucketed_psum, bucketed_reduce_scatter,
                           ring_all_gather)

DP_AXIS = "dp"


def _zero_dim(spec: P, shaped, dp: int, start: int = 0) -> int:
    """Index of the first dimension of `shaped` at or after `start` that
    `spec` leaves unsharded and whose size divides by `dp`; -1 when none
    qualifies (the leaf stays replicated over dp). The ONE dim-selection
    rule shared by the stage-1 moment specs, the stage-2 grad scatter and
    the stage-3 param specs/per-layer gather — they must never disagree,
    or a grad shard would land on a layout its moment doesn't own."""
    if dp == 1:
        return -1
    spec_t = tuple(spec) + (None,) * (shaped.ndim - len(tuple(spec)))
    for i, (s, d) in enumerate(zip(spec_t, shaped.shape)):
        if i >= start and s is None and d % dp == 0 and d > 0:
            return i
    return -1


def _extend_spec(spec: P, shaped, dim: int, dp_axis: str) -> P:
    if dim < 0:
        return spec
    spec_t = tuple(spec) + (None,) * (shaped.ndim - len(tuple(spec)))
    return P(*spec_t[:dim], dp_axis, *spec_t[dim + 1:])


def zero1_specs(specs: Any, shapes: Any, mesh: Mesh,
                dp_axis: str = DP_AXIS) -> Any:
    """Moment PartitionSpecs: each param spec extended with `dp_axis` on the
    first unsharded dimension whose size divides by the dp axis size.

    Leaves where no dimension qualifies (e.g. tiny norm gains with every dim
    taken or indivisible) stay on their param spec — replicated over dp, like
    plain Adam. `shapes` is any pytree with `.shape`/`.ndim` leaves matching
    `specs` (e.g. from `jax.eval_shape`).
    """
    dp = mesh.shape[dp_axis]

    def one(spec: P, shaped) -> P:
        return _extend_spec(spec, shaped, _zero_dim(spec, shaped, dp),
                            dp_axis)

    return jax.tree.map(one, specs, shapes,
                        is_leaf=lambda x: isinstance(x, P))


@functools.lru_cache(maxsize=32)
def _eval_shapes(model) -> Any:
    """Abstract param-tree shapes for `model`. Cached: both model families
    are frozen, value-hashable dataclasses, and `jax.eval_shape` of the
    full init — pure host work, but a whole trace — would otherwise rerun
    on every trace of the ZeRO-3 layer body (fwd + checkpoint fwd + bwd
    replay) and on every specs/shardings call."""
    return jax.eval_shape(model.init, jax.random.key(0))


def zero1_moment_shardings(model, mesh: Mesh) -> Any:
    """NamedSharding pytree for the Adam mu/nu trees of `model` on `mesh`."""
    specs = zero1_specs(model.specs(), _eval_shapes(model), mesh)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


# ----------------------------------------------------------- ZeRO-3 layout --

@functools.lru_cache(maxsize=32)
def zero3_dims(model, dp: int) -> Any:
    """Per-leaf ZeRO-3 partition dims for `model`'s param tree (STACKED
    layout): -1 = replicated over dp, else the dim index `dp_axis` shards.

    The layers subtree skips dim 0 — that's the stacked num_layers axis the
    forward scan slices per layer, so sharding it would hand each dp rank a
    DIFFERENT model; each layer leaf shards within-layer instead (its
    in-scan gather dim is this value minus 1). Non-layer leaves (embedding,
    final norm, lm_head/pos tables) use the plain stage-1 rule.

    Cached per (model, dp) — the result is a static int tree consulted on
    every layer-body trace; treat it as read-only."""
    specs = model.specs()
    shapes = _eval_shapes(model)
    out = {}
    for key, sub in specs.items():
        start = 1 if key == "layers" else 0
        out[key] = jax.tree.map(
            lambda s, sh: _zero_dim(s, sh, dp, start=start),
            sub, shapes[key], is_leaf=lambda x: isinstance(x, P))
    return out


def zero3_specs(model, mesh: Mesh, dp_axis: str = DP_AXIS) -> Any:
    """PartitionSpec tree for ZeRO-3 params (and their grads/moments —
    all three live on the same layout, so the Adam update is fully local)."""
    specs = model.specs()
    shapes = _eval_shapes(model)
    dims = zero3_dims(model, mesh.shape[dp_axis])
    return jax.tree.map(
        lambda s, sh, d: _extend_spec(s, sh, d, dp_axis),
        specs, shapes, dims, is_leaf=lambda x: isinstance(x, P))


def zero3_shardings(model, mesh: Mesh, dp_axis: str = DP_AXIS) -> Any:
    """NamedSharding pytree for ZeRO-3 params/grads/moments on `mesh`."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        zero3_specs(model, mesh, dp_axis),
                        is_leaf=lambda x: isinstance(x, P))


def zero3_layer_gather(model, layer_params: Any,
                       axis: str = DP_AXIS) -> Any:
    """Gather ONE layer's dp-sharded leaves back to their tp-local shapes
    (ring all-gather per leaf; `ops/overlap.ring_all_gather`).

    Called from the model's `_layer_body` when `model.zero3_axis` is set —
    i.e. inside the layer scan AND inside the remat boundary, which is what
    bounds gathered-weight liveness to one layer: the scan structurally
    frees the gather before the next iteration, and remat replays (rather
    than saves) it for the backward. The transpose of each gather is the
    conjugate ring reduce-scatter, so the backward also produces each
    rank's dp-SUMMED grad shard without an explicit all-reduce."""
    from jax import lax
    dp = lax.axis_size(axis)  # static: mesh shape is trace-time known
    if dp == 1:
        return layer_params
    dims = zero3_dims(model, dp)["layers"]
    return jax.tree.map(
        lambda a, d: a if d < 0 else ring_all_gather(a, axis, d - 1),
        layer_params, dims)


def _check_bucketed_scope(model, what: str) -> None:
    """The shared stage>=2 / bucketed-reducer scope refusals."""
    if model.is_moe:
        raise ValueError(
            f"{what} does not compose with MoE: expert grads are "
            f"ep-sharded, not batch-replicated — use the default reducer")
    if model.pp_size > 1:
        raise ValueError(
            f"{what} requires pp_size == 1: non-layer params are "
            f"pp-replicated and their reduction axes depend on the "
            f"pipeline head layout — use the default reducer")
    if model.sequence_parallel is False:
        _require_sp(model, 0, what)     # said by name: no sequence decides


def _require_sp(model, t_local: int, what: str) -> bool:
    """The model's sequence parallelism as a batch of cp-local sequence
    length `t_local` is traced, refused where the hand-reduced grad paths
    cannot do without it: an explicit False at build time, an 'auto' that
    resolves off (a sequence the tp ranks cannot split) at trace time."""
    sp = model.tp_layout(t_local)[0]
    if model.tp_size > 1 and not sp:
        raise ValueError(
            f"{what} with tp > 1 requires sequence_parallel: the non-SP "
            f"path all-reduces inside every row-parallel layer, so "
            f"per-shard cotangent bookkeeping is depth-dependent — use "
            f"the default reducer (or turn SP on)")
    return sp


# ------------------------------------------------- bucketed grad reduction --

# Why the two grad builders below say check_vma=False. They take jax.grad
# INSIDE the shard body, on purpose: the per-shard cotangents must come out
# UN-reduced so the reduction can be issued by hand — in buckets, as
# reduce-scatters, over int8 rings. Under shard_map's varying-axes typing
# that is not expressible: a gradient with respect to a replicated
# parameter is typed replicated, so autodiff inserts the whole-tree psum
# this module exists to replace, and the hand-rolled ppermute rings
# (ops/overlap.py) produce values whose replication the checker cannot
# infer at the out_specs. With the check off jax transposes `psum` to
# `psum` (not to a varying cast): every cotangent that crosses a psum on
# its way back fans out by that psum's axis size — see _psum_fanout.


def _psum_fanout(mesh: Mesh, batch_axes) -> int:
    """The factor every per-shard cotangent carries under check_vma=False,
    where psum transposes to psum. In scope (dense, pp=1, SP whenever
    tp > 1) each leaf's cotangent crosses exactly two psums: the loss's
    batch-axis psum and the vocab-parallel CE's tp psum; every other
    collective on the path (all_gather / psum_scatter / ppermute)
    transposes value-correctly. Static: axis sizes are mesh facts. The
    parity tests (tests/test_zero.py, tests/test_overlap.py) compare every
    grad leaf with the whole-tree reducer and catch any drift here."""
    return math.prod(mesh.shape[a] for a in (*batch_axes, "tp"))


def _spec_axes(spec: P) -> set:
    """Mesh axes a PartitionSpec shards over (entries may be axis names or
    tuples of them)."""
    out = set()
    for entry in tuple(spec):
        if entry is None:
            continue
        for a in (entry if isinstance(entry, tuple) else (entry,)):
            out.add(a)
    return out


def build_bucketed_grad_fn(model, mesh: Mesh, loss_mode: str = "vocab_parallel",
                           bucket_mb: float = 25.0, reduce_dtype=None,
                           zero_stage: int = 1):
    """(params, ids, tgt, pos) -> (loss, grads) with the data-parallel
    gradient reduction issued in size-bounded BUCKETS instead of the
    shard_map transpose's end-of-step whole-tree blob.

    How: the loss AND its gradient are taken per-shard (jax.value_and_grad
    INSIDE one shard_map), so no automatic boundary reduction happens for
    the grads; the batch-axis sums the transpose would have inserted are
    issued explicitly by `ops.overlap.bucketed_psum` — one flattened psum
    per <= bucket_mb bucket, each depending only on its own cotangents, so
    XLA can launch it as soon as the backward produces them and hide the
    wire under the remaining backward compute. `reduce_dtype` compresses
    the wire only; grads return to f32 before the optimizer's master
    accumulate (EQuARX-style, no stochastic rounding): jnp.bfloat16
    casts around the psum (bound pinned in tests/test_overlap.py),
    jnp.int8 routes each bucket through the block-scaled quantized ring
    (`ops/overlap.quantized_allreduce`; bound pinned in
    tests/test_quant.py).

    `zero_stage=2` swaps each dp bucket's all-reduce for a REDUCE-SCATTER
    (`ops/overlap.bucketed_reduce_scatter` — identical buckets, HALF the
    wire bytes): every leaf with a free dp-divisible dim (the `zero1_specs`
    rule, so the grad shard lands exactly on its moment's layout) comes
    back as this rank's 1/dp shard, declared dp-sharded in the out_specs;
    the int8 wire routes through `quantized_reduce_scatter`, PR 8's ring
    stopped after its reduce-scatter half. Residual axes (cp, and 'tp' for
    SP-replicated leaves) are summed AFTER the scatter on the 1/dp shard;
    leaves with no qualifying dim fall back to the stage-1 psum. The
    optimizer then updates only owned shards and XLA's all-gather of the
    fresh params (to meet the replicated out_sharding) replaces the grads'
    gather half — the ZeRO-2 schedule.

    Which axes each leaf reduces over: the batch axes (dp/ep/cp — params
    are replicated over them, data varies), plus 'tp' for tp-REPLICATED
    leaves when sequence parallelism is on (norm gains / row-linear biases
    then see only t/tp tokens per shard, so their local grads are partial
    sums; without SP those grads are tp-invariant — identical on every
    shard — and summing them would scale by tp). Value-parity with the
    transpose's reduction is pinned in tests/test_overlap.py (stage 1)
    and tests/test_zero.py (stage 2).

    The shard_map runs with check_vma=False, where psum transposes to
    psum: per-shard cotangents come back multiplied by `_psum_fanout`,
    which is divided out (the module comment above it says why the check
    cannot stay on here). Parity with the whole-tree reducer on every leaf
    is pinned in tests/test_overlap.py.

    Scope: dense models on pp=1 meshes, with sequence_parallel on
    whenever tp > 1. MoE routes through ep-sharded expert params, pp
    shards the layer stack, and the non-SP tp path crosses a psum per
    row-linear (depth-dependent inflation) — all need per-leaf variance
    bookkeeping the static spec cannot express; the default whole-tree
    path handles them.
    """
    _check_bucketed_scope(model, "bucketed DP grad reduction")
    if zero_stage not in (1, 2):
        raise ValueError(f"build_bucketed_grad_fn handles zero_stage 1 "
                         f"(all-reduce) or 2 (reduce-scatter), got "
                         f"{zero_stage}; stage 3 is build_zero3_grad_fn")
    specs = model.specs()
    batch_axes = ("dp", "ep", "cp")
    leaf_specs = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    dp = mesh.shape[DP_AXIS]
    if zero_stage >= 2:
        shapes = _eval_shapes(model)
        leaf_shapes = jax.tree.leaves(shapes)
        scatter_dims = [_zero_dim(s, sh, dp)
                        for s, sh in zip(leaf_specs, leaf_shapes)]
        grad_specs = zero1_specs(specs, shapes, mesh)
    else:
        scatter_dims = [-1] * len(leaf_specs)
        grad_specs = specs

    psum_fanout = _psum_fanout(mesh, batch_axes)

    def shard_fn(params, input_ids, target_ids, position_ids):
        sp = _require_sp(model, input_ids.shape[1],
                         "bucketed DP grad reduction")
        loss, grads = jax.value_and_grad(
            lambda p: model.loss_shard(p, input_ids, target_ids,
                                       position_ids, mode=loss_mode))(params)
        grads = jax.tree.map(lambda g: g / psum_fanout, grads)
        flat, treedef = jax.tree.flatten(grads)
        assert len(flat) == len(leaf_specs)
        groups: "dict[tuple, list[int]]" = {}
        for i, spec in enumerate(leaf_specs):
            axes = batch_axes
            if sp and "tp" not in _spec_axes(spec):
                axes = batch_axes + ("tp",)
            groups.setdefault(axes, []).append(i)
        out = list(flat)
        for axes, idxs in groups.items():
            if zero_stage >= 2:
                scat = [i for i in idxs if scatter_dims[i] >= 0]
                idxs = [i for i in idxs if scatter_dims[i] < 0]
                if scat:
                    shards = bucketed_reduce_scatter(
                        [flat[i] for i in scat],
                        [scatter_dims[i] for i in scat], DP_AXIS,
                        other_axes=tuple(a for a in axes if a != DP_AXIS),
                        bucket_mb=bucket_mb, reduce_dtype=reduce_dtype)
                    for i, r in zip(scat, shards):
                        out[i] = r
            if idxs:
                reduced = bucketed_psum([flat[i] for i in idxs], axes,
                                        bucket_mb=bucket_mb,
                                        reduce_dtype=reduce_dtype)
                for i, r in zip(idxs, reduced):
                    out[i] = r
        return loss, jax.tree.unflatten(treedef, out)

    batch_spec = P(("dp", "ep"), "cp")
    fn = jax.shard_map(shard_fn, mesh=mesh,
                       in_specs=(specs, batch_spec, batch_spec, batch_spec),
                       out_specs=(P(), grad_specs),
                       # per-shard grads, reduced by hand: see "Why" above
                       check_vma=False)
    if not model._zigzag:
        return fn

    from ..ops.ring_attention import zigzag_perm

    def zz(params, input_ids, target_ids, position_ids):
        # masked token-mean CE is permutation-invariant (make_loss's rule)
        perm = zigzag_perm(input_ids.shape[1], model.cp_size)
        return fn(params, input_ids[:, perm], target_ids[:, perm],
                  position_ids[:, perm])

    return zz


# ---------------------------------------------- ZeRO-3 gather-on-demand fn --

def build_zero3_grad_fn(model, mesh: Mesh, loss_mode: str = "vocab_parallel",
                        bucket_mb: float = 25.0, dp_axis: str = DP_AXIS):
    """(params, ids, tgt, pos) -> (loss, grads) with params AND grads
    dp-sharded end to end — the ZeRO-3 schedule.

    Params enter shard_map on `zero3_specs` layouts (each leaf's free
    dp-divisible dim sharded; the stacked layer axis deliberately skipped).
    Per-shard, the non-layer leaves (embedding, final norm, head/position
    tables) ring-all-gather once at their use sites; the LAYER leaves stay
    sharded and gather per layer inside the model's scan body (the
    `zero3_axis` hook, inside the remat boundary), so peak gathered-param
    HBM is one layer plus the head/embedding — `full/dp + one layer` for
    the dominant stack. The backward needs no explicit dp grad reduction
    at all: every gather's transpose is the conjugate ring reduce-scatter,
    handing this rank the dp-SUMMED cotangent of exactly its own shard —
    ZeRO-2's halved wire, derived by autodiff. Residual reductions (cp,
    'tp' for SP-replicated leaves, and dp for the few leaves too small to
    shard) go through `bucketed_psum` on the already-scattered shards.

    Requires a remat'ing model (remat True or 'dots'): without remat,
    autodiff would SAVE each layer's gathered weights as backward
    residuals and the full replica would rematerialise in HBM. Scope
    otherwise matches the bucketed reducer: dense, pp=1, SP whenever
    tp > 1. The psum fan-out is divided out exactly as in
    `build_bucketed_grad_fn` (ppermute rings transpose value-correctly, so
    the gathers add none of their own).
    """
    _check_bucketed_scope(model, "ZeRO-3 (gather-on-demand params)")
    if model.remat is False:
        raise ValueError(
            "ZeRO-3 requires a rematerialising model (remat=True or "
            "'dots'): without remat, autodiff saves every layer's GATHERED "
            "weights as backward residuals, recreating the full param "
            "replica the stage exists to eliminate")
    dp = mesh.shape[dp_axis]
    zmodel = dataclasses.replace(model, zero3_axis=dp_axis)
    specs = model.specs()
    pspecs = zero3_specs(model, mesh, dp_axis)
    dims = zero3_dims(model, dp)
    batch_axes = ("dp", "ep", "cp")
    leaf_specs = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    leaf_dims = jax.tree.leaves(dims)
    psum_fanout = _psum_fanout(mesh, batch_axes)

    def shard_fn(params, input_ids, target_ids, position_ids):
        sp = _require_sp(model, input_ids.shape[1],
                         "ZeRO-3 (gather-on-demand params)")
        def loss_of(p):
            full = {}
            for key, sub in p.items():
                if key == "layers":
                    full[key] = sub  # gathered per layer inside the scan
                else:
                    full[key] = jax.tree.map(
                        lambda a, d: a if d < 0 else
                        ring_all_gather(a, dp_axis, d),
                        sub, dims[key])
            return zmodel.loss_shard(full, input_ids, target_ids,
                                     position_ids, mode=loss_mode)

        loss, grads = jax.value_and_grad(loss_of)(params)
        # only the loss psum and the CE tp psum fan the cotangents out (see
        # _psum_fanout); the gather rings (ppermute + slice updates)
        # transpose value-correctly
        grads = jax.tree.map(lambda g: g / psum_fanout, grads)
        flat, treedef = jax.tree.flatten(grads)
        assert len(flat) == len(leaf_specs)
        groups: "dict[tuple, list[int]]" = {}
        for i, (spec, d) in enumerate(zip(leaf_specs, leaf_dims)):
            # dp-sharded leaves: the gather transpose already dp-summed
            # this shard; only the residual axes remain
            axes = tuple(a for a in batch_axes if d < 0 or a != dp_axis)
            if sp and "tp" not in _spec_axes(spec):
                axes = axes + ("tp",)
            if axes:
                groups.setdefault(axes, []).append(i)
        out = list(flat)
        for axes, idxs in groups.items():
            reduced = bucketed_psum([flat[i] for i in idxs], axes,
                                    bucket_mb=bucket_mb)
            for i, r in zip(idxs, reduced):
                out[i] = r
        return loss, jax.tree.unflatten(treedef, out)

    batch_spec = P(("dp", "ep"), "cp")
    fn = jax.shard_map(shard_fn, mesh=mesh,
                       in_specs=(pspecs, batch_spec, batch_spec, batch_spec),
                       out_specs=(P(), pspecs),
                       # per-shard grads, reduced by hand: see "Why" above
                       check_vma=False)
    if not model._zigzag:
        return fn

    from ..ops.ring_attention import zigzag_perm

    def zz(params, input_ids, target_ids, position_ids):
        perm = zigzag_perm(input_ids.shape[1], model.cp_size)
        return fn(params, input_ids[:, perm], target_ids[:, perm],
                  position_ids[:, perm])

    return zz
