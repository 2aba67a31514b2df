"""The `train_scopes` runner: the `train` runner's recipe (benchmark/runners/
train.py: its docstring is this runner's too, phase by phase), handing its
per-layer readers two things more that `train` cannot:

* **the step's `op_name` map** (`program_trace.op_names(step.as_text())`)
  and the step's runs on chip 0, reduced to `measured.scopes`: device
  nanoseconds by the program's named scope (benchmark/lib/scopes.py), for
  the `model.<scope>_ms` readers;
* **the step's counters**: the step is built `with_counters=True`, so its
  third output says per expert layer how many (token, choice) pairs each
  routed expert got and how many rows were computed here, and the main and
  multi-token-prediction losses apart. The window's means feed
  `moe.load_max_over_mean`, `moe.rows_here_per_token`,
  `train_step.active_mfu_pct` and `model.moe_experts_roofline`.

`measured` carries every field the `train` runner's does, so the readers
written for it work here (`entry.*`, `device.*`, `model.xla_ops_ms`,
`kernels.flash_ms`, `train_step.step_ms_median`). What `train` computes from
a dense `DecoderSizes` (`flops_per_token`) is left None: the metrics that
read it (`train_step.mfu_pct`, `kernels.flash_roofline`) do not list this
runner's cells.

`memory_peak_bytes` is `benchmark/lib/memory.phase_peak_bytes`: the
quantity `train` reports, read by phase, because here the float32 reference
reserves more than the step and the process's two peak counters no longer
belong to one moment (that module's docstring; in `train`'s cells the two
readings are equal to the byte). The counters themselves stand in the last
line's `device` and on the `setup` log line.

The helpers are `train`'s own, imported, not copied: the float32 reference
on the check batch, the comparison and ITS TOLERANCES, the memory readings,
the log. This is the runner ROADMAP D14 wants `train` to become.
"""

from __future__ import annotations

import json
import math
import os
import tempfile
import time
from types import SimpleNamespace

import numpy as np

from benchmark.lib import peaks, program_trace, scopes, timing, trace
from benchmark.lib.files import load_module
from benchmark.lib.job import Job, Outcome, data_seed, init_seed
from benchmark.lib.memory import phase_peak_bytes
from benchmark.lib.mla_moe_counts import train_flops_per_token
from benchmark.runners.train import (CHECK_SEQUENCES, WARMUP_STEPS,
                                     _compare, _mean, _memory, _no_times,
                                     compared, log)

# What this runner's check holds beside `train`'s two scalars (whose
# tolerances stand: `train.RTOL`). Two scalars of a freshly initialised model
# see neither the sixteen experts held nor the router: with the experts' or
# the router's inputs rounded to float8_e4m3, loss and gradient norm read as
# the sound program's. So three more readings of the step's own outputs on
# the check batch are held to the reference. Each limit stands between the
# sound program's largest reading (22 seeds) and the smallest of a control
# (5 seeds), the program with one input in the precision below the cell's
# (benchmark/tools/moe_control.py; my chip runs, PR 33; PERF.md section 2):
#
# * `routed_moved`: per expert layer, the share of (token, choice) pairs
#   that the step's `routed` counter has at another expert than the
#   reference's top-k has them (half the summed absolute difference of the
#   two count vectors over the pairs); the worst layer. Not zero in a sound
#   run: the step's router reads a hidden state computed in bfloat16, and a
#   score within that rounding of the ninth flips. Sound 0.0032 - 0.0060;
#   the router's input in float8 0.0137 - 0.0216.
# * `moe_grad`: of the gradient leaves of the router and of the experts held
#   (gate, up, down; each expert layer apart) the relative L2 error against
#   `jax.grad` of the reference, expert by expert (a routed expert's slice,
#   its column of the router), and of those the median; the worst leaf and
#   layer. Sound 0.112 - 0.178, and that much because the choices that flip
#   move one or two rows in a hundred between experts, and a fresh model's
#   rows pull an expert's gradient every way: the error is the root of the
#   share moved. It is the coarse guard of the dispatch and of the grouped
#   products' transposes. The router's input in float8 0.335 - 0.376 (the
#   median over experts scatters less than the whole leaf: a router's read
#   0.11 - 0.25 in the sound runs).
# * `shared_grad`: the same error of the shared expert's three leaves, which
#   no choice reaches: bfloat16's own rounding, 0.0086 - 0.0099 in sound
#   runs; whatever the routed experts hand on in lower precision shows here
#   in the layers before them: the experts' inputs in float8 0.0345 -
#   0.0593 (the first expert layer's).
#
# On every GRAD_STRIDE-th element of an expert's slice (the whole leaves are
# 1.5 GB a side, which the reference's phase has no room to hand out). The
# step returns no gradient; after its first call Adam's first moment is
# (1 - beta1) times it, exactly.
GRAD_STRIDE = 7     # divides no size of a leaf: every row and column is met
# read in bfloat16, the only compute dtype a cell of this runner states
MOE_RTOL = {"bfloat16": {"routed_moved": 0.009, "moe_grad": 0.25,
                         "shared_grad": 0.018}}


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
    want, want_routed, want_moe_grads = _reference(family, mesh, params, ids,
                                                   tgt, check_pos)
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
    check = _compare_moe(
        check, w["dtype"],
        first_counters["routed"] / (batch // CHECK_SEQUENCES), want_routed,
        _first_gradients(optimizer, opt_state), want_moe_grads)
    del want_moe_grads
    log(event="check", **check,
        loss_main=float(first_counters["loss_main"]),
        loss_mtp=float(first_counters.get("loss_mtp", float("nan"))))
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
    peak_bytes = memory and phase_peak_bytes(memory_reference, memory)

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

    # the window's counters: per expert layer (the module's last), means
    # over the steps
    rows = np.mean([c["rows_here"] for c in counters], axis=0)     # (L,)
    routed = np.stack([c["routed"] for c in counters])             # (n, L, E)
    lo = int(job.config["deployment_share"]["expert_offset"])
    held = routed[..., lo:lo + sizes.n_held]
    balance = float(np.mean(held.max(-1) / np.maximum(held.mean(-1), 1e-9)))
    rows_per_token = float(rows.sum()) / tokens_per_step
    lines = [
        dict(event="window", steps=window.steps, seconds=window.seconds,
             step_ms_median=timing.quantile(intervals, 0.5),
             step_ms_p90=end_to_end["step_ms_p90"],
             step_ms_max=max(intervals), interval_samples=len(intervals),
             around_slowest_ms=intervals[max(slowest - 2, 0):slowest + 4],
             loss_first10=first10, loss_last10=last10,
             losses_finite=all(finite), loss_fell=falling,
             loss_main_last=float(counters[-1]["loss_main"]),
             loss_mtp_last=float(counters[-1].get("loss_mtp",
                                                  float("nan"))),
             rows_here_mean=[int(r) for r in rows],
             rows_here_min_max=[
                 int(min(c["rows_here"].min() for c in counters)),
                 int(max(c["rows_here"].max() for c in counters))],
             load_max_over_mean=balance),
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
    breakdown = parts = None
    if job.trace and devs:
        device["busy_s"] = sum(d.busy_ns() for d in devs) / len(devs) / 1e9
        device["window_s"] = sum(d.window_ns for d in devs) / len(devs) / 1e9
        spans = trace.host_spans(captured, "bench.")
        names = program_trace.op_names(step_hlo)
        runs = program_trace.step_runs(captured, devs[0])
        parts = scopes.scope_ns(devs[0], runs, names)
        breakdown = {"device_ops": trace.top_ops(devs[0]),
                     "idle_gaps": trace.top_gaps(devs[0], spans),
                     "scopes_ms_per_step": {
                         k: v / devs[0].steps / 1e6 for k, v in parts.items()},
                     "unattributed_ops": program_trace.top_unattributed(
                         devs[0], runs, names)}
    if job.rehearse:
        device.update(busy_s=None, window_s=None)

    measured = SimpleNamespace(
        workload=w, sizes=sizes, mesh=mesh_sizes, chips=chips,
        window=window, intervals_ms=intervals,
        tokens_per_s=tokens_per_s, setup_s=setup_s, compile_s=compile_s,
        cache_setup=cache_setup, cache_window=cache_window,
        flops_per_token=None, peak=peak, peak_bytes=peak_bytes, devices=devs,
        # what this runner adds
        scopes=parts, rows_here_per_layer=[float(r) for r in rows],
        rows_here_per_token=rows_per_token / sizes.expert_layers,
        load_max_over_mean=balance,
        active_flops_per_token=train_flops_per_token(sizes, seqlen,
                                                     rows_per_token))
    return Outcome(correct=correct, attempted=window.steps,
                   failed=finite.count(False), end_to_end=end_to_end,
                   measured=measured, device=device, breakdown=breakdown,
                   compared=compared(check, first10, last10,
                                     finite.count(False)))


def _moe_named(tree: dict) -> dict:
    """name -> (kind, leaf) of the leaves under `moe` a gradient reaches, of
    the expert layers and of the multi-token-prediction module's; kind is
    `router`, `shared` or `expert`."""
    import jax
    return {"/".join([seg] + [k.key for k in path]):
            (path[0].key if path[0].key in ("router", "shared") else "expert",
             leaf)
            for seg in ("layers", "mtp_layers") if seg in tree
            for path, leaf in jax.tree_util.tree_leaves_with_path(
                {k: v for k, v in tree[seg]["moe"].items() if k != "bias"})}


def _sample(kind: str, leaf):
    """A leaf stacked over its layers as (layers, experts, every
    GRAD_STRIDE-th element of an expert's slice): the router by column, the
    shared expert as one."""
    if kind == "shared":
        leaf = leaf[:, None]
    elif kind == "router":
        leaf = leaf.swapaxes(1, 2)
    return leaf.reshape(*leaf.shape[:2], -1)[..., ::GRAD_STRIDE]


def _reference(family, mesh, params, ids, tgt, pos):
    """`train._reference` (float32, matmul precision "highest", a copy of
    the parameters on one device) with two things more from the same pass:
    the reference's `routed` counts and its gradient leaves under `moe`,
    which leave the device at once: the step has no room beside them."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    def detail(p, i, t, q):
        (loss, routed), grads = jax.value_and_grad(
            family.reference_routed, has_aux=True)(p, i, t, q)
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                            for g in jax.tree.leaves(grads)))
        return loss, norm, routed, {name: _sample(*leaf) for name, leaf
                                    in _moe_named(grads).items()}

    one = SingleDeviceSharding(mesh.devices.flat[0])
    held = jax.device_put(params, one)
    with jax.default_matmul_precision("highest"):
        loss, norm, routed, leaves = jax.jit(detail)(
            held, *(jax.device_put(x, one) for x in (ids, tgt, pos)))
    return ([float(loss), float(norm)], np.asarray(routed),
            jax.device_get(leaves))


def _first_gradients(optimizer, opt_state) -> dict:
    """The gradient leaves under `moe` of the step's FIRST call, from what
    the step returned: Adam's first moment starts at zero, so after one
    update it is (1 - beta1) g, with the schedule's beta1 of step 0 (the
    default `OptimizerConfig` neither clips nor decays). A leaf at a time:
    all of them sampled at once are 0.2 GiB more on the device than the
    step's own peak."""
    import jax
    import jax.numpy as jnp
    from distributed_pytorch_from_scratch_tpu.training.optim import (
        schedule_lr)
    beta1 = float(schedule_lr(optimizer, jnp.zeros((), jnp.int32))[1])
    sample = jax.jit(_sample, static_argnums=0)
    return {name: np.asarray(sample(*leaf)) / (1.0 - beta1)
            for name, leaf in _moe_named(opt_state.mu).items()}


def _compare_moe(check: dict, dtype: str, routed, want_routed, grads,
                 want_grads) -> dict:
    """`train._compare`'s record with this runner's three readings added
    (MOE_RTOL, above)."""
    moved = (np.abs(routed - want_routed).sum(-1) / 2
             / want_routed.sum(-1))                        # a layer
    by_leaf, whole = {}, {}
    for name, want in want_grads.items():
        diff = np.square(grads[name].astype(np.float64) - want).sum(-1)
        norm = np.square(want, dtype=np.float64).sum(-1)      # (L, experts)
        # an expert no row reached has no gradient in either
        by_leaf[name] = np.median(
            np.sqrt(diff / np.where(norm > 0, norm, 1.0)), axis=-1).tolist()
        whole[name] = np.sqrt(diff.sum(-1) / norm.sum(-1)).tolist()
    worst = lambda shared: max(max(v) for k, v in by_leaf.items()
                               if ("/shared/" in k) == shared)
    err = {"routed_moved": float(moved.max()), "moe_grad": worst(False),
           "shared_grad": worst(True)}
    rtol = MOE_RTOL[dtype]
    ok = all(math.isfinite(v) and v <= rtol[k] for k, v in err.items())
    return {**check, "ok": bool(check["ok"] and ok),
            "rel_err": {**check["rel_err"], **err},
            "rtol": {**check["rtol"], **rtol},
            "routed_moved_by_layer": moved.tolist(),
            "moe_grad_by_leaf": by_leaf, "moe_grad_whole_leaf": whole}
