"""The `train_ssm_moe` runner: the `train_scopes` runner's recipe (benchmark/
runners/train_scopes.py: its docstring is this runner's too, phase by phase)
for the ssm_moe family, whose layers are ONE sublayer each: Mamba-2 mixers,
an attention layer with no positions, expert FFNs whose experts live in a
latent. What differs:

* **the scope split** is `benchmark/lib/ssm_scopes.py`'s (`mamba`,
  `gqa_attn`, `moe_latent`, `moe_route`, `moe_experts`, `moe_shared`, `mtp`,
  `head_loss`, `optimizer`, `grad_norm`, and `flash`, `rest`,
  `unattributed`, `other_programs`), so `measured.scopes` carries the
  mixer's scope beside the names the standing readers read
  (`model.gqa_attn_ms`, `model.moe_*`), and `measured.mamba_parts` the
  mixer's time by inner scope (`mamba/ssd` is `model.ssd_ms`'s; the
  breakdown's `mamba_parts_ms_per_step`);
* **the counters** carry the decay's rows too: the window's worst
  `ssm_decay_min` (`ssm.decay_min`), by layer in the `window` log line;
* **the counts** are `benchmark/lib/ssm_moe_counts.py`'s: the active FLOPs
  with the recurrence's, what a layer's recurrence must compute and move
  (`measured.ssd_cost`, for `model.ssd_roofline`) and what each expert
  layer's grouped products must, at two matrices a latent wide
  (`measured.latent_expert_costs`, for `model.latent_experts_roofline`);
* **the check batch is ONE sequence** (the cell's batch is 1: the state
  fills the chip), where `train`'s is two;
* **the check holds one reading more, `ssm_grad`** (below);
* `_moe_named` is `train_scopes`'s over this family's parameter keys
  (blocks of periods and the module's two keys), the latent projections
  among the leaves it names;
* `memory_peak_bytes` is what the chip held at ONE time
  (`train_swa_moe._held_at_once`).

`measured` carries every field `train_scopes`'s does, so the readers
written for it work here unchanged (`sizes` has the names they read:
`n_head`, `n_kv_head`, `head_dim`, `d_model`, `d_expert`, `n_held`,
`expert_layers`).

The helpers are `train`'s, `train_scopes`'s and `train_swa_moe`'s own,
imported, not copied (the comparison and ITS TOLERANCES, the expert leaves'
sampling, the memory readings, the log); the recipe itself is the tenth
copy: ROADMAP D14.
"""

from __future__ import annotations

import json
import math
import os
import tempfile
import time
from types import SimpleNamespace

import numpy as np

from benchmark.lib import peaks, program_trace, ssm_scopes, timing, trace
from benchmark.lib.files import load_module
from benchmark.lib.job import Job, Outcome, data_seed, init_seed
from benchmark.lib.ssm_moe_counts import (latent_expert_products_cost,
                                          ssd_cost, train_flops_per_token)
from benchmark.runners.train import (WARMUP_STEPS, _compare, _mean, _memory,
                                     _no_times, compared, log)
from benchmark.runners.train_scopes import GRAD_STRIDE, _compare_moe, _sample
from benchmark.runners.train_swa_moe import _held_at_once

CHECK_SEQUENCES = 1

# What this runner's check holds beside `train`'s two scalars and
# `train_scopes`'s three readings (`RTOL`, `MOE_RTOL`, whose limits stand:
# this cell's readings are in PERF.md section 2). Loss and gradient norm of
# a freshly initialised model hardly see HOW a state-space layer remembers.
# So one reading more of the step's own outputs on the check batch
# (benchmark/tools/ssm_control.py, the weights from the seed too; PERF.md
# section 2 has the table and the seeds):
#
# * `ssm_grad`: over the Mamba leaves (`w_in`, the convolution and its
#   bias, `A_log`, `D`, `dt_bias`, the gated norm's weight, `w_out`; each
#   Mamba layer apart) the relative L2 error of the step's gradient against
#   `jax.grad` of the reference (whose recurrence runs token by token in
#   float32); the worst leaf and layer. It is the guard of the chunked
#   recurrence and of its backward: the gradients of `A_log` and `dt_bias`
#   exist only through the decays, a head at a time, and a head's B and C
#   are its group's.
#
#   Sound 0.017 - 0.036 over eleven runs (seven data seeds on the file's
#   weights 0.017 - 0.036, four seeds of weights and data 0.019 - 0.029;
#   0.022 on the batch the file pins); the decay sums, the decays and the
#   states in bfloat16 (`bf16_state`) 0.102, 0.135: a chunk's sums reach
#   -350 to -580 on fresh weights (`ssm.decay_min`), where bfloat16's step
#   is 2; every head on group 0's B and C (`one_group`) 0.857, 1.40.
#   Limit 0.06: 1.7 times the sound runs' largest and 0.59 of the controls'
#   smallest, 2.7 times the reading of the replay the cell runs.
#   `relu_experts` (ReLU, not its square) reads moe_grad 1.29, 1.30 against
#   `MOE_RTOL`'s 0.25 and fails every other limit but the loss's too; sound
#   `routed_moved` at 22 choices a token 0.0035 - 0.0040 under its 0.009,
#   so that limit stands (my chip runs, PR 63).
#
# On every GRAD_STRIDE-th element of a large leaf (the input projection is
# 19M numbers a layer, which the reference's phase has no room to hand out
# whole); the small leaves whole. The step returns no gradient; after its
# first call Adam's first moment is (1 - beta1) times it, exactly.
SSM_LEAVES = ("w_in", "conv", "conv_bias", "A_log", "D", "dt_bias", "norm",
              "w_out")
# read in bfloat16, the only compute dtype a cell of this runner states
SSM_RTOL = {"bfloat16": {"ssm_grad": 0.06}}


def run(job: Job) -> Outcome:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from distributed_pytorch_from_scratch_tpu.config import (
        MeshConfig, OptimizerConfig)
    from distributed_pytorch_from_scratch_tpu.runtime.compile_cache import (
        compile_cache_stats, enable_compile_cache)
    from distributed_pytorch_from_scratch_tpu.runtime.mesh import (
        batch_feeder, make_mesh)
    from distributed_pytorch_from_scratch_tpu.training.optim import (
        AdamState, init_adam_state)
    from distributed_pytorch_from_scratch_tpu.training.train_step import (
        build_train_step)

    w = job.workload
    cache_dir = enable_compile_cache()
    devices = jax.devices()
    chips = int(w["chips"])
    platform, kind = devices[0].platform, devices[0].device_kind
    if not job.rehearse and platform != "tpu":
        raise SystemExit(f"benchmark: backend is {platform!r}, not a TPU; "
                         f"nothing is measured off the chip")
    if len(devices) < chips:
        raise SystemExit(f"benchmark: {job.name} needs {chips} chip(s), "
                         f"JAX sees {len(devices)}")
    peak = None if job.rehearse else peaks.peak_for(kind)
    marks = [("reach_chip", time.time())]

    def mark(phase, *ready):
        jax.block_until_ready(ready)
        marks.append((phase, time.time()))

    mesh_sizes = dict(w["mesh"])
    if math.prod(mesh_sizes.values()) != chips:
        raise SystemExit(f"benchmark: mesh {mesh_sizes} is not {chips} chips")
    mesh = make_mesh(MeshConfig(**mesh_sizes), devices=devices[:chips])
    family = job.family.build(job.config, mesh_sizes, w["dtype"])
    model, sizes = family.model, family.sizes
    batch, seqlen = int(w["batch"]), int(w["seqlen"])

    param_sh = model.shardings(mesh)
    params = jax.jit(model.init, out_shardings=param_sh)(
        jax.random.key(init_seed(job)))
    feed = batch_feeder(mesh)
    mark("weights", params)

    batches = load_module("data", w["data"]["kind"]).TokenBatches
    ids, tgt, check_pos = batches(w["data"], sizes.vocab, CHECK_SEQUENCES,
                                  seqlen, data_seed(job) + 1).next()
    want, want_routed, want_moe_grads, want_ssm_grads = _reference(
        family, mesh, params, ids, tgt, check_pos)
    mark("reference")
    memory_reference = _memory(devices[:chips])

    scalar = NamedSharding(mesh, P())
    opt_state = jax.jit(init_adam_state, out_shardings=AdamState(
        step=scalar, mu=param_sh, nu=param_sh))(params)
    optimizer = OptimizerConfig()
    step_fn = build_train_step(model, mesh, optimizer,
                               with_grad_norm=True, with_counters=True)
    mark("adam_state", opt_state)

    stream = batches(w["data"], sizes.vocab, batch, seqlen,
                     data_seed(job))
    pos = feed(stream.next()[2])
    annotate = jax.profiler.TraceAnnotation

    def next_batch():
        with annotate("bench.data"):
            ids, tgt, _ = stream.next()
            return feed(ids), feed(tgt)

    if batch % CHECK_SEQUENCES:
        raise SystemExit(f"benchmark: batch {batch} is not a multiple of "
                         f"the check's {CHECK_SEQUENCES} sequences")
    check_batch = [feed(np.tile(x, (batch // CHECK_SEQUENCES, 1)))
                   for x in (ids, tgt)]

    t0 = time.time()
    step = step_fn.lower(params, opt_state, *check_batch, pos).compile()
    step_temp_bytes = step.memory_analysis().temp_size_in_bytes
    step_hlo = step.as_text() if job.trace else None

    def dispatch():
        nonlocal params, opt_state
        ids, tgt = next_batch()
        with annotate("bench.dispatch"):
            params, opt_state, out = step(params, opt_state, ids, tgt, pos)
        return out      # (loss, gradient norm, counters)

    def wait(out):
        with annotate("bench.wait"):
            out[0].block_until_ready()

    # the step's first call is the check, as in `train`
    params, opt_state, first = step(params, opt_state, *check_batch, pos)
    wait(first)
    compile_s = time.time() - t0
    first_counters = jax.device_get(first[2])
    check = _compare([float(x) for x in first[:2]], want, w["dtype"])
    moe_grads, ssm_grads = _first_gradients(optimizer, opt_state)
    check = _compare_moe(
        check, w["dtype"],
        first_counters["routed"] / (batch // CHECK_SEQUENCES), want_routed,
        moe_grads, want_moe_grads)
    # (the limit is read at the published widths; at the rehearsal shape,
    # where a head's leaf has four elements, the reading is logged and not
    # held)
    check = _compare_ssm(check, w["dtype"], ssm_grads, want_ssm_grads,
                         held=not job.rehearse)
    del want_moe_grads, moe_grads, want_ssm_grads, ssm_grads
    log(event="check", **check,
        loss_main=float(first_counters["loss_main"]),
        loss_mtp=float(first_counters.get("loss_mtp", float("nan"))),
        ssm_decay_min=first_counters["ssm_decay_min"].tolist())
    mark("step_compile_or_load_and_check")
    for _ in range(WARMUP_STEPS):
        wait(dispatch())
    mark("warm_up")
    cache_setup = dict(compile_cache_stats())
    memory_setup = _memory(devices[:chips])

    wall_offset = time.time() - time.perf_counter()
    window = timing.run_window(dispatch, wait, job.seconds)
    setup_s = window.stamps[0] + wall_offset - job.t_process_start
    cache_window = dict(compile_cache_stats())
    losses = [float(out[0]) for out in window.results]
    counters = jax.device_get([out[2] for out in window.results])

    captured = None
    if job.trace:
        with tempfile.TemporaryDirectory() as tmp:
            # the benchmark's own capture, the only one in this process
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(  # graftcheck: disable=profiler-discipline
                tmp, profiler_options=opts)
            try:
                timing.run_window(dispatch, wait, float("inf"),
                                  max_steps=int(w["trace_steps"]))
            finally:
                jax.profiler.stop_trace()  # graftcheck: disable=profiler-discipline
            captured = trace.load_xplane(trace.find_xplane(tmp))
        if job.dump_dir:
            os.makedirs(job.dump_dir, exist_ok=True)
            with open(os.path.join(job.dump_dir, job.name + ".trace.json"),
                      "w") as f:
                json.dump(trace.to_plain(captured), f)
            with open(os.path.join(job.dump_dir, job.name + ".op_names.json"),
                      "w") as f:
                json.dump(program_trace.op_names(step_hlo), f)
    memory = _memory(devices[:chips])
    peak_bytes = memory and _held_at_once(memory_reference, memory)

    intervals = window.step_intervals_ms
    slowest = intervals.index(max(intervals))
    tokens_per_step = batch * seqlen
    tokens_per_s = window.steps * tokens_per_step / window.seconds
    finite = [math.isfinite(x) for x in losses]
    first10, last10 = _mean(losses[:10]), _mean(losses[-10:])
    falling = len(losses) >= 20 and last10 < first10
    correct = bool(check["ok"] and all(finite) and falling)
    end_to_end = {
        "tokens_per_s_per_chip": tokens_per_s / chips,
        "step_ms_p90": timing.quantile(intervals, 0.9),
        "setup_s": setup_s,
    }

    # the window's counters: per expert layer the router's, per Mamba layer
    # the decay's; means over the steps (the decay's minimum: the worst)
    rows = np.mean([c["rows_here"] for c in counters], axis=0)     # (L,)
    routed = np.stack([c["routed"] for c in counters])             # (n, L, E)
    lo = int(job.config["deployment_share"]["expert_offset"])
    held = routed[..., lo:lo + sizes.n_held]
    balance = float(np.mean(held.max(-1) / np.maximum(held.mean(-1), 1e-9)))
    rows_per_token = float(rows.sum()) / tokens_per_step
    decay_min = np.min([c["ssm_decay_min"] for c in counters], axis=0)
    lines = [
        dict(event="window", steps=window.steps, seconds=window.seconds,
             step_ms_median=timing.quantile(intervals, 0.5),
             step_ms_p90=end_to_end["step_ms_p90"],
             step_ms_max=max(intervals), interval_samples=len(intervals),
             around_slowest_ms=intervals[max(slowest - 2, 0):slowest + 4],
             loss_first10=first10, loss_last10=last10,
             losses_finite=all(finite), loss_fell=falling,
             loss_main_last=float(counters[-1]["loss_main"]),
             rows_here_mean=[int(r) for r in rows],
             rows_here_min_max=[
                 int(min(c["rows_here"].min() for c in counters)),
                 int(max(c["rows_here"].max() for c in counters))],
             load_max_over_mean=balance,
             ssm_decay_min_by_layer=decay_min.tolist()),
        dict(event="setup", setup_s=setup_s,
             init_seed=init_seed(job), data_seed=data_seed(job),
             phases_s={phase: t - t_before for (phase, t), t_before in zip(
                 marks, [job.t_process_start] + [t for _, t in marks])},
             compile_cache={"dir": cache_dir, **cache_setup},
             compile_cache_after_window=cache_window,
             step_temp_bytes=step_temp_bytes,
             memory_after_reference=memory_reference,
             memory_after_setup=memory_setup,
             memory_after_window=memory, memory_peak_bytes=peak_bytes)]
    for fields in lines:
        log(**(_no_times(fields) if job.rehearse else fields))

    devs = trace.device_traces(captured) if captured else []
    device = {"platform": platform, "kind": kind,
              "count": jax.device_count(), "memory_peak_bytes": peak_bytes,
              "peak_bytes_in_use": memory and memory["peak_bytes_in_use"],
              "peak_bytes_reserved": memory and memory["peak_bytes_reserved"]}
    breakdown = parts = mamba_parts = None
    if job.trace and devs:
        device["busy_s"] = sum(d.busy_ns() for d in devs) / len(devs) / 1e9
        device["window_s"] = sum(d.window_ns for d in devs) / len(devs) / 1e9
        spans = trace.host_spans(captured, "bench.")
        names = program_trace.op_names(step_hlo)
        runs = program_trace.step_runs(captured, devs[0])
        parts = ssm_scopes.scope_ns(devs[0], runs, names)
        mamba_parts = ssm_scopes.mamba_parts_ns(devs[0], runs, names)
        per_step = lambda ns: {k: v / devs[0].steps / 1e6
                               for k, v in ns.items()}
        breakdown = {"device_ops": trace.top_ops(devs[0]),
                     "idle_gaps": trace.top_gaps(devs[0], spans),
                     "scopes_ms_per_step": per_step(parts),
                     "mamba_parts_ms_per_step": per_step(mamba_parts),
                     "unattributed_ops": program_trace.top_unattributed(
                         devs[0], runs, names)}
    if job.rehearse:
        device.update(busy_s=None, window_s=None)

    import jax.numpy as jnp
    itemsize = jnp.dtype(w["dtype"]).itemsize
    measured = SimpleNamespace(
        workload=w, sizes=sizes, mesh=mesh_sizes, chips=chips,
        window=window, intervals_ms=intervals,
        tokens_per_s=tokens_per_s, setup_s=setup_s, compile_s=compile_s,
        cache_setup=cache_setup, cache_window=cache_window,
        flops_per_token=None, peak=peak, peak_bytes=peak_bytes, devices=devs,
        # what `train_scopes` adds
        scopes=parts, rows_here_per_layer=[float(r) for r in rows],
        rows_here_per_token=rows_per_token / sizes.expert_layers,
        load_max_over_mean=balance,
        active_flops_per_token=train_flops_per_token(sizes, seqlen,
                                                     rows_per_token),
        # what this runner adds
        mamba_parts=mamba_parts, ssm_decay_min=float(decay_min.min()),
        ssd_cost=ssd_cost(batch // mesh_sizes.get("dp", 1), seqlen, sizes,
                          itemsize),
        latent_expert_costs=[
            latent_expert_products_cost(float(r), sizes, itemsize)
            for r in rows])
    return Outcome(correct=correct, attempted=window.steps,
                   failed=finite.count(False), end_to_end=end_to_end,
                   measured=measured, device=device, breakdown=breakdown,
                   compared=compared(check, first10, last10,
                                     finite.count(False)))


def _by_layer(tree: dict, kind: str) -> dict:
    """key -> the subtree of every key that holds layers of `kind`
    ("mamba", "moe"; the multi-token-prediction module's among them) with
    its layers leading, (layers, ...): a block's (periods, layers a period,
    ...) flattened in the order the layers run, the module's as they are."""
    import jax
    flat = lambda a: a.reshape(a.shape[0] * a.shape[1], *a.shape[2:])
    return {key: (tree[key] if key.startswith("mtp_")
                  else jax.tree.map(flat, tree[key]))
            for key in sorted(tree)
            if key.startswith((f"{kind}_layers_", f"mtp_{kind}_layers"))}


def _moe_named(tree: dict) -> dict:
    """`train_scopes._moe_named` over this family's keys: name -> (kind,
    leaf) of the leaves under `moe` a gradient reaches. The two latent
    projections are sampled as the shared expert's leaves are (one block a
    layer: no expert axis) and read under `moe_grad` (no name of theirs
    holds `/shared/`: every choice reaches them)."""
    import jax
    kind = lambda name: {"router": "router", "shared": "shared",
                         "latent": "shared"}.get(name, "expert")
    return {"/".join([key] + [k.key for k in path]): (kind(path[0].key), leaf)
            for key, layers in _by_layer(tree, "moe").items()
            for path, leaf in jax.tree_util.tree_leaves_with_path(
                {k: v for k, v in layers["moe"].items() if k != "bias"})}


def _ssm_named(tree: dict) -> dict:
    """name -> a Mamba leaf of every layer of its key as (layers,
    elements): every GRAD_STRIDE-th element of a large leaf, a small one
    whole."""
    out = {}
    for key, layers in _by_layer(tree, "mamba").items():
        for name in SSM_LEAVES:
            leaf = layers["mamba"][name]
            flat = leaf.reshape(leaf.shape[0], -1)
            out[f"{key}/{name}"] = (flat[:, ::GRAD_STRIDE]
                                    if flat.shape[1] > 1 << 20 else flat)
    return out


def _reference(family, mesh, params, ids, tgt, pos):
    """`train_scopes._reference` (float32, matmul precision "highest", a
    copy of the parameters on one device: loss, gradient norm, the
    reference's `routed` counts and its sampled gradient leaves under `moe`)
    with the Mamba leaves' gradients, sampled, from the same pass."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    def detail(p, i, t, q):
        (loss, routed), grads = jax.value_and_grad(
            family.reference_routed, has_aux=True)(p, i, t, q)
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                            for g in jax.tree.leaves(grads)))
        return (loss, norm, routed,
                {name: _sample(*leaf) for name, leaf
                 in _moe_named(grads).items()}, _ssm_named(grads))

    one = SingleDeviceSharding(mesh.devices.flat[0])
    held = jax.device_put(params, one)
    with jax.default_matmul_precision("highest"):
        loss, norm, routed, leaves, ssm = jax.jit(detail)(
            held, *(jax.device_put(x, one) for x in (ids, tgt, pos)))
    return ([float(loss), float(norm)], np.asarray(routed),
            jax.device_get(leaves), jax.device_get(ssm))


def _first_gradients(optimizer, opt_state):
    """(the sampled gradient leaves under `moe`, the Mamba leaves sampled)
    of the step's FIRST call, from Adam's first moment
    (`train_scopes._first_gradients` says why that is exact)."""
    import jax
    import jax.numpy as jnp
    from distributed_pytorch_from_scratch_tpu.training.optim import (
        schedule_lr)
    beta1 = float(schedule_lr(optimizer, jnp.zeros((), jnp.int32))[1])
    sample = jax.jit(_sample, static_argnums=0)
    moe = {name: np.asarray(sample(*leaf)) / (1.0 - beta1)
           for name, leaf in _moe_named(opt_state.mu).items()}
    ssm = {name: np.asarray(leaf) / (1.0 - beta1)
           for name, leaf in jax.jit(_ssm_named)(opt_state.mu).items()}
    return moe, ssm


def _compare_ssm(check: dict, dtype: str, grads: dict, want_grads: dict,
                 held: bool = True) -> dict:
    """`train_scopes._compare_moe`'s record with `ssm_grad` added (SSM_RTOL,
    above); with `held` off it is recorded and decides nothing."""
    by_leaf = {}
    for name, want in want_grads.items():
        diff = np.square(grads[name].astype(np.float64) - want).sum(-1)
        norm = np.square(want, dtype=np.float64).sum(-1)      # (layers,)
        by_leaf[name] = np.sqrt(diff / np.where(norm > 0, norm, 1.0)).tolist()
    # numpy's max: a NaN anywhere is the reading, and is over any limit
    err = {"ssm_grad": float(np.max(np.concatenate(
        [np.asarray(v) for v in by_leaf.values()])))}
    rtol = SSM_RTOL[dtype]
    ok = not held or all(math.isfinite(v) and v <= rtol[k]
                         for k, v in err.items())
    return {**check, "ok": bool(check["ok"] and ok),
            "rel_err": {**check["rel_err"], **err},
            "rtol": {**check["rtol"], **rtol},
            "ssm_grad_by_leaf": by_leaf}
