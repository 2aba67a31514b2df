"""The `train_early_moe` runner: the `train` runner's recipe (benchmark/
runners/train.py: its docstring is this runner's too, phase by phase) for
the early_moe family, handing its per-layer readers what `train_swa_moe`
hands its own, by this family's names:

* **the step's `op_name` map** and the step's runs on chip 0, reduced to
  `measured.scopes`: device nanoseconds by the program's named scope
  (benchmark/lib/early_scopes.py: `gqa_attn`, `moe_route`, `moe_experts`,
  `head_loss`, `optimizer`, `grad_norm`, and `flash`, `rest`,
  `unattributed`, `other_programs`), and to `measured.route_early_ns`: the
  part of `moe_route` that runs from the layer's input (the inner scope
  `moe_route/early` and the step's sorts), for `model.moe_route_early_ms`;
* **the step's counters** (`with_counters=True`): per layer the pairs each
  routed expert got and the rows computed here. The window's means feed
  `moe.load_max_over_mean`, `moe.rows_here_per_token`,
  `train_step.active_mfu_pct` and `model.moe_experts_roofline`;
* **`window_flash_plan`**: what the kernels' static plans compute under the
  window layers' mask at the cell's shape, forward and backward
  (`obs/attribution.flash_tile_stats`), for
  `window.flash_computed_over_live`.

`measured` carries every field `train_swa_moe`'s does but the selection
bias's (this router has none), so `entry.*`, `device.*`,
`model.xla_ops_ms`, `kernels.flash_ms`, `kernels.window_flash_ms`,
`kernels.window_flash_roofline`, `kernels.full_flash_roofline` (its `sizes`
carry the fields `lib/swa_scopes.flash_roofline_pct` reads: `n_head`,
`n_kv_head`, `head_dim`, `window`), `model.gqa_attn_ms`,
`train_step.step_ms_median`, `model.moe_*` and `moe.*` read it unchanged,
with `scopes` keyed by this family's names and `active_flops_per_token`
from benchmark/lib/early_moe_counts.py. `flops_per_token` is None:
`train_step.mfu_pct`, `kernels.flash_roofline` and
`kernels.gqa_flash_roofline` (one causal count for every call) do not list
this runner's cells.

`memory_peak_bytes` is what the chip held at ONE time
(`train_swa_moe._held_at_once`).

The helpers are `train`'s, `train_hybrid`'s and `train_swa_moe`'s own,
imported, not copied (the comparison and ITS TOLERANCES, the memory
readings, the log, the gradient samples' stride); the recipe itself is the
seventh copy, and the sixth that reads scopes: ROADMAP D14.
"""

from __future__ import annotations

import json
import math
import os
import tempfile
import time
from types import SimpleNamespace

import numpy as np

from benchmark.lib import early_scopes, peaks, program_trace, timing, trace
from benchmark.lib.early_moe_counts import train_flops_per_token
from benchmark.lib.files import load_module
from benchmark.lib.job import Job, Outcome, data_seed, init_seed
from benchmark.runners.train import (WARMUP_STEPS, _compare, _mean,
                                     _memory, _no_times, compared, log)
from benchmark.runners.train_hybrid import GRAD_STRIDE
from benchmark.runners.train_swa_moe import _held_at_once

# What this runner's check holds beside `train`'s two scalars (whose
# tolerances stand: `train.RTOL`; loss 6e-7 - 7.7e-6 and gradient norm
# 4e-6 - 2.0e-4 here, a sixtieth and a twenty-fifth of the limits). A
# freshly initialised model's loss is ln(vocabulary) and hardly sees which
# tensor a router read, an activation or a mask: every control below passes
# `train`'s two limits. So four more readings of the step's own outputs on
# the check batch are held to the reference, so that the check SEES what
# this family adds. Each limit stands between the sound program's largest
# reading and the smallest of the control it is there for, the program with
# one thing wrong (benchmark/tools/early_control.py; my chip runs, PR 51:
# the sound program over 14 distinct draws, ten data seeds at the file's
# `init_seed` 0, candidates 1 and 2 and two seeds with the weights from the
# seed too; each control at seeds 2147483659 and 3000000019, the two float8
# ones at 5100000029 too; PERF.md section 2 has the table):
#
# * `routed_moved`: per layer, the share of (token, choice) pairs that the
#   step's `routed` counter has at another expert than the reference's
#   top-6 has them (half the summed absolute difference of the two count
#   vectors over the pairs); the MEAN over the four layers, as in cell 9 and
#   for its reason: ISSUE 51 named the worst layer, which swings 0.0013 -
#   0.0076 over the sound draws (one layer whose sixth and seventh logits
#   lie close under a seed's tokens) against 0.0145 - 0.0157 for the
#   router's input in float8: a factor of 1.9; the mean reads 0.00085 -
#   0.00267 sound against 0.0101 - 0.0137 for that control (0.031 - 0.038
#   for a router that reads the post-attention stream): a factor of 3.8, so
#   0.005 has twice its reading of room on either side. Not zero in a sound
#   run: the first layer's router reads the embedding's rows, which the step
#   rounds to bfloat16 (0.0003 - 0.0008 there), a later layer's a residual
#   stream computed in bfloat16.
# * `router_grad`: of the ROUTER's gradient leaf, each layer apart, the
#   relative L2 error against `jax.grad` of the reference, expert by expert
#   (its column of the router), and of those the median; the worst layer.
#   The leaf whose gradient arrives from another place in this family: the
#   cotangent of `router_x`, past the attention half. Sound 0.058 - 0.086
#   (for cell 5's reason: the flips move a row in a few hundred between
#   experts and a fresh model's rows pull a column every way); the router's
#   input in float8 0.222 - 0.242, the post-attention router 0.277 - 0.334,
#   SiLU 0.45 - 0.50; limit 0.15 (1.7 times the sound runs' largest, two
#   thirds of the float8 control's smallest).
# * `expert_grad`: the same error of the held experts' three matrices
#   (`gate`, `up`, `down`), a routed expert's slice at a time, the median
#   over the experts; the worst leaf and layer. The guard of the activation
#   (ReLU's gradient is a step where SiLU's is smooth), of the one chunk's
#   movers and of the grouped products' transposes, and of the precision of
#   what the experts read. Sound 0.062 - 0.077 (`gate` the highest, `up` and
#   `down` under 0.062); the experts' input in float8 0.131 - 0.138, the
#   router's input in float8 0.148 - 0.164, the post-attention router 0.18 -
#   0.21, SiLU 0.465 - 0.470; limit 0.105 (1.36 times the sound runs'
#   largest, 1.25 under the float8 control's smallest: the least room of
#   the four, both ways; at the file's `init_seed`, which is what the
#   driver runs, the sound readings are 0.062 - 0.069).
# * `attn_grad`: of the attention leaves (`wq`, `wk`, `wv`, `wo`), each
#   layer apart, the relative L2 error of the whole leaf; the worst leaf
#   and layer. The guard of the window in the kernels' gridded forward and
#   split backward (the left-edge tile, the skipped tiles, the clamped
#   index maps), of the positions a window layer takes and a full layer
#   does not, and of the grouping (7 query heads a key-value head). Sound
#   0.0090 - 0.0104 (`wq` and `wk`; `wo` and `wv` under 0.0046); the window
#   layers under the causal mask 0.29 - 0.31, RoPE on the full layer too
#   0.78; limit 0.025 (2.4 times the sound runs' largest, a twelfth of the
#   causal control's smallest).
#
# Every one of the fourteen control runs is not ok under these limits, by
# one limit and not by each: the experts' input in float8 by `expert_grad` alone, the causal
# mask and the misplaced RoPE by `attn_grad` (their `router_grad` 0.09 -
# 0.13 stays under its limit), SiLU by `router_grad`, `expert_grad` and
# `attn_grad`, the router's input in float8 and the post-attention router by
# `routed_moved`, `router_grad` and `expert_grad`.
#
# On every GRAD_STRIDE-th element of an expert's slice and of `wq` and `wo`
# (the leaves are 94 MB a layer and matrix and 37 MB a layer); `wk`, `wv`
# and the router whole. The step returns no gradient; after its first call
# Adam's first moment is (1 - beta1) times it, exactly.
ATTN_LEAVES = ("wq", "wk", "wv", "wo")
EXPERT_LEAVES = ("gate", "up", "down")
# read in bfloat16, the only compute dtype a cell of this runner states
EARLY_RTOL = {"bfloat16": {"routed_moved": 0.005, "router_grad": 0.15,
                           "expert_grad": 0.105, "attn_grad": 0.025}}


def run(job: Job) -> Outcome:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from distributed_pytorch_from_scratch_tpu.config import (
        MeshConfig, OptimizerConfig)
    from distributed_pytorch_from_scratch_tpu.obs.attribution import (
        flash_tile_stats)
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
    # the check batch is the TIMED batch's size (one sequence of 16,384 in
    # the cell): what the float32 reference holds beside the weights with
    # its scores in blocks, and nothing is repeated to fill the step's batch
    ids, tgt, check_pos = batches(w["data"], sizes.vocab, batch, seqlen,
                                  data_seed(job) + 1).next()
    want, want_routed, want_grads = _reference(family, mesh, params, ids,
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

    check_batch = [feed(x) for x in (ids, tgt)]

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
    # (the four relative limits are read at the published widths in
    # bfloat16; the rehearsal runs its tiny shape in float32, where
    # `train`'s own two limits are tight: there the four readings are
    # logged and not held)
    check = _compare_early(
        check, w["dtype"],
        first_counters["routed"], want_routed,
        _first_gradients(optimizer, opt_state), want_grads,
        held=not job.rehearse)
    del want_grads
    log(event="check", **check)
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

    # the window's counters: a row a layer, means over the steps
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
             rows_here_mean=[int(r) for r in rows],
             rows_here_min_max=[
                 int(min(c["rows_here"].min() for c in counters)),
                 int(max(c["rows_here"].max() for c in counters))],
             load_max_over_mean=balance,
             load_max_over_mean_first10=float(np.mean(
                 held[:10].max(-1) / np.maximum(held[:10].mean(-1), 1e-9))),
             load_max_over_mean_last10=float(np.mean(
                 held[-10:].max(-1) / np.maximum(held[-10:].mean(-1), 1e-9)))),
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
    breakdown = parts = early_ns = None
    if job.trace and devs:
        device["busy_s"] = sum(d.busy_ns() for d in devs) / len(devs) / 1e9
        device["window_s"] = sum(d.window_ns for d in devs) / len(devs) / 1e9
        spans = trace.host_spans(captured, "bench.")
        names = program_trace.op_names(step_hlo)
        runs = program_trace.step_runs(captured, devs[0])
        parts = early_scopes.scope_ns(devs[0], runs, names)
        early_ns = early_scopes.early_route_ns(devs[0], runs, names)
        breakdown = {"device_ops": trace.top_ops(devs[0]),
                     "idle_gaps": trace.top_gaps(devs[0], spans),
                     "scopes_ms_per_step": {
                         k: v / devs[0].steps / 1e6 for k, v in parts.items()},
                     "moe_route_early_ms_per_step":
                         early_ns and early_ns / devs[0].steps / 1e6,
                     "unattributed_ops": program_trace.top_unattributed(
                         devs[0], runs, names)}
    if job.rehearse:
        device.update(busy_s=None, window_s=None)

    # what the kernels' static plans compute under the window layers' mask,
    # a head and sequence; nothing where the window covers the sequence or
    # the kernels cannot plan the shape (the XLA path runs it then)
    mask = model._attn_mask(seqlen, "window")
    try:
        window_flash_plan = mask and {
            name: flash_tile_stats(seqlen, head_dim=sizes.head_dim,
                                   dtype=w["dtype"], mask=mask,
                                   backward=name == "backward")
            for name in ("forward", "backward")}
    except ValueError:
        window_flash_plan = None

    measured = SimpleNamespace(
        workload=w, sizes=sizes, mesh=mesh_sizes, chips=chips,
        window=window, intervals_ms=intervals,
        tokens_per_s=tokens_per_s, setup_s=setup_s, compile_s=compile_s,
        cache_setup=cache_setup, cache_window=cache_window,
        flops_per_token=None, peak=peak, peak_bytes=peak_bytes, devices=devs,
        # what the scope-reading runners add
        scopes=parts, rows_here_per_layer=[float(r) for r in rows],
        rows_here_per_token=rows_per_token / sizes.expert_layers,
        load_max_over_mean=balance,
        active_flops_per_token=train_flops_per_token(sizes, seqlen,
                                                     rows_per_token),
        route_early_ns=early_ns, window_flash_plan=window_flash_plan)
    return Outcome(correct=correct, attempted=window.steps,
                   failed=finite.count(False), end_to_end=end_to_end,
                   measured=measured, device=device, breakdown=breakdown,
                   compared=compared(check, first10, last10,
                                     finite.count(False)))


def _sampled(tree: dict) -> dict:
    """name -> a gradient leaf of every layer as one (layers, groups,
    elements) array, sampled: the layers of the tree's keys in sorted order
    (the reference's gradient and Adam's first moment are the same tree, so
    the rows pair up). A period's leaf is stacked (periods, layers a period,
    ...). An attention leaf is one group; an expert matrix a group an
    expert held (every GRAD_STRIDE-th element of its slice); the router a
    group a routed expert (its column, whole)."""
    import jax.numpy as jnp
    keys = sorted(key for key, layers in tree.items()
                  if isinstance(layers, dict) and "wq" in layers)

    def thin(flat):
        # every GRAD_STRIDE-th element of a large slice, a small one whole
        return (flat[..., ::GRAD_STRIDE] if flat.shape[-1] > 1 << 20
                else flat)

    def attention(leaf):            # (periods, layers, in, out)
        return thin(leaf.reshape(math.prod(leaf.shape[:-2]), 1, -1))

    def expert(leaf):               # (periods, layers, held, in, out)
        return thin(leaf.reshape(math.prod(leaf.shape[:-3]), leaf.shape[-3],
                                 -1))

    def router(leaf):               # (periods, layers, d, routed)
        return jnp.swapaxes(leaf.reshape(-1, *leaf.shape[-2:]), 1, 2)

    out = {name: jnp.concatenate([attention(tree[key][name]["weight"])
                                  for key in keys]) for name in ATTN_LEAVES}
    out.update({name: jnp.concatenate([expert(tree[key]["moe"][name])
                                       for key in keys])
                for name in EXPERT_LEAVES})
    out["router"] = jnp.concatenate([router(tree[key]["moe"]["router"])
                                     for key in keys])
    return out


def _reference(family, mesh, params, ids, tgt, pos):
    """`train._reference` (float32, matmul precision "highest", a copy of
    the parameters on one device) with two things more from the same pass:
    the reference's `routed` counts and its gradients of the attention
    leaves, the held experts' and the router's, sampled, which leave the
    device at once."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    def detail(p, i, t, q):
        (loss, routed), grads = jax.value_and_grad(
            family.reference_routed, has_aux=True)(p, i, t, q)
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                            for g in jax.tree.leaves(grads)))
        return loss, norm, routed, _sampled(grads)

    one = SingleDeviceSharding(mesh.devices.flat[0])
    held = jax.device_put(params, one)
    with jax.default_matmul_precision("highest"):
        loss, norm, routed, leaves = jax.jit(detail)(
            held, *(jax.device_put(x, one) for x in (ids, tgt, pos)))
    return ([float(loss), float(norm)], np.asarray(routed),
            jax.device_get(leaves))


def _first_gradients(optimizer, opt_state) -> dict:
    """The sampled gradient leaves of the step's FIRST call, from what the
    step returned: Adam's first moment starts at zero, so after one update
    it is (1 - beta1) g, with the schedule's beta1 of step 0 (the default
    `OptimizerConfig` neither clips nor decays)."""
    import jax
    import jax.numpy as jnp
    from distributed_pytorch_from_scratch_tpu.training.optim import (
        schedule_lr)
    beta1 = float(schedule_lr(optimizer, jnp.zeros((), jnp.int32))[1])
    return {name: np.asarray(leaf) / (1.0 - beta1)
            for name, leaf in jax.jit(_sampled)(opt_state.mu).items()}


def _compare_early(check: dict, dtype: str, routed, want_routed, grads,
                   want_grads, held: bool = True) -> dict:
    """`train._compare`'s record with this runner's four readings added
    (EARLY_RTOL, above); with `held` off they are recorded and decide
    nothing."""
    moved = (np.abs(routed - want_routed).sum(-1) / 2
             / want_routed.sum(-1))                        # a layer
    by_leaf = {}
    for name, want in want_grads.items():
        diff = np.square(grads[name].astype(np.float64) - want).sum(-1)
        norm = np.square(want, dtype=np.float64).sum(-1)   # (layers, groups)
        # an expert no row reached has no gradient in either; the median
        # over a leaf's groups (one group: the leaf itself)
        by_leaf[name] = np.median(
            np.sqrt(diff / np.where(norm > 0, norm, 1.0)), axis=-1).tolist()
    # numpy's max: a NaN anywhere is the reading
    worst = lambda names: float(np.max([by_leaf[n] for n in names]))
    err = {"routed_moved": float(moved.mean()),
           "router_grad": worst(["router"]),
           "expert_grad": worst(EXPERT_LEAVES),
           "attn_grad": worst(ATTN_LEAVES)}
    rtol = EARLY_RTOL[dtype] if held else {}
    ok = all(math.isfinite(err[k]) and err[k] <= limit
             for k, limit in rtol.items())
    return {**check, "ok": bool(check["ok"] and ok),
            "rel_err": {**check["rel_err"], **err},
            "rtol": {**check["rtol"], **rtol},
            "routed_moved_by_layer": moved.tolist(),
            "grad_by_leaf": by_leaf}
