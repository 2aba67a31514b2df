"""The `train_bd_moe` runner: the `train` runner's recipe (benchmark/
runners/train.py: its docstring is this runner's too, phase by phase) for
the bd_moe family, a model trained by BLOCK DIFFUSION, handing its per-layer
readers what `train_conv_moe` hands its own, by this family's names.

What the objective changes in the recipe:

* **tokens are DATA tokens.** The host hands the step (b, L) batches like
  every cell; inside the step a sequence becomes 2L rows `[noised ; clean]`.
  `tokens_per_s_per_chip`, `tokens_per_s` and `active_flops_per_token` count
  b x L. `moe.rows_here_per_token` is handed per ROW and layer (the pairs
  held over the 2 b L rows a layer sees), so that it reads against `top_k x
  held / routed` = 1.0 like the other cells'; per data token it is twice
  that;
* **the step draws its noise from the optimizer's step count and the
  batch, so from `--seed`** (`models/bd_moe.block_diffusion_noise` folds a
  checksum of the batch into its key). The model's `noise_seed` is ONE
  value for every `--seed`: it is a constant of the compiled step, and a
  seed of its own made every new `--seed` compile a step of its own (69 s
  of set-up against 40 loaded; PERF.md section 6, PR 41). The check is the
  step's first call, at count 0, on the check batch: the runner makes the
  same draw with the program's function and HANDS the float32 reference
  `(xt, m, p)` as arrays. The check batch is the timed batch's size
  (`CHECK_SEQUENCES`): a repeated sequence would draw other noise;
* **the loss of a step carries its draw's factor** (`sum of the weights /
  positions`, 1 in expectation, a few percent either way a step): "the loss
  fell" is read on the weighted MEAN CE of the masked positions, the step's
  loss over that factor (`weight_sum` and `positions` of its counters).

`measured` carries every field `train_conv_moe`'s does (so `entry.*`,
`device.*`, `model.xla_ops_ms`, `kernels.flash_ms`, `model.gqa_attn_ms`,
`train_step.step_ms_median`, `model.moe_*` and `moe.*` read it unchanged),
with `scopes` keyed by this family's names (benchmark/lib/bd_scopes.py:
`bd_noise`, `gqa_attn`, `moe_route`, `moe_experts`, `head_loss`,
`optimizer`, `grad_norm`, and `flash`, `rest`, `unattributed`,
`other_programs`), `active_flops_per_token` from
benchmark/lib/bd_moe_counts.py, and `flash_plan`: what the kernels' static
plans compute under the family's mask at the cell's shape, forward and
backward (`obs/attribution.flash_tile_stats`), for
`bd.flash_computed_over_live`. `flops_per_token` is None:
`train_step.mfu_pct`, `kernels.flash_roofline` and
`kernels.gqa_flash_roofline` (a causal triangle over the workload's
`seqlen`) do not list this runner's cells; `kernels.bd_flash_roofline` is
theirs.

The helpers are `train`'s, `train_hybrid`'s and `train_conv_moe`'s own,
imported, not copied (the comparison and ITS TOLERANCES, the memory
readings, the log, the gradient samples' stride); the recipe itself is the
fifth copy, and the fourth that reads scopes: ROADMAP D14.
"""

from __future__ import annotations

import json
import math
import os
import tempfile
import time
from types import SimpleNamespace

import numpy as np

from benchmark.lib import bd_scopes, peaks, program_trace, timing, trace
from benchmark.lib.bd_moe_counts import train_flops_per_token
from benchmark.lib.files import load_module
from benchmark.lib.job import Job, Outcome, data_seed, init_seed
from benchmark.lib.memory import phase_peak_bytes
from benchmark.runners.train import (CHECK_SEQUENCES, WARMUP_STEPS,
                                     _compare, _mean, _memory, _no_times,
                                     compared, log)
from benchmark.runners.train_hybrid import GRAD_STRIDE

# What this runner's check holds beside `train`'s two scalars (whose
# tolerances stand: `train.RTOL`). The loss of a freshly initialised model
# hardly sees who attends to whom: with the mask wrong by a block, or with
# the attention's inputs in float8_e4m3, loss and gradient norm read inside
# `train`'s limits (1.7e-5 - 6.7e-5 and 3e-4 - 2.8e-3; PERF.md section 2, PR
# 41). So two more readings of the step's own outputs on the check batch
# are held to the reference. Each limit stands between the sound program's
# largest reading over 40 seeds and the smallest of the controls
# (benchmark/tools/bd_control.py; my chip runs, PR 41; PERF.md section 2 has
# the distributions):
#
# * `attn_grad`: of the attention leaves (`wq`, `wk`, `wv`, `wo`), each
#   layer apart, the relative L2 error against `jax.grad` of the reference
#   (whose mask is a boolean matrix from the three rules); the worst leaf
#   and layer. Sound 0.0066 - 0.0101; the mask wrong by one block 0.033 -
#   0.052, q, k, v in float8 0.032 - 0.041, a causal mask over the 2L rows
#   2.0 - 4.6. It is the guard of the mask in the kernels' forward and
#   backward, of the positions that repeat and of the grouping.
# * `routed_moved`: per layer, the share of (row, choice) pairs that the
#   step's `routed` counter has at another expert than the reference's
#   top-k has them (half the summed absolute difference of the two count
#   vectors over the pairs); the worst layer. Not zero in a sound run: the
#   step's router reads a hidden state computed in bfloat16, and a score
#   within that rounding of the ninth flips. Sound 0.0022 - 0.0068, with a
#   tail of this objective's own: every MASKED noised row enters the first
#   layer as the one mask token's embedding, so where that token's eighth
#   and ninth scores lie close under a seed's weights, all of them sit
#   near the tie together (the first layer read 0.0059 and 0.0068 at
#   masked shares of 0.64 and 0.85 and 0.0011 - 0.0032 otherwise; half of
#   the noised rows flipping one pair of eight would read 0.03). A causal
#   mask over the rows 0.155 - 0.52 (the later routers read other hidden
#   states); the two subtler controls read as sound.
#
# On every GRAD_STRIDE-th element of `wq` and `wo` (the leaves are 34 MB a
# layer); `wk` and `wv` whole. The step returns no gradient; after its
# first call Adam's first moment is (1 - beta1) times it, exactly.
ATTN_LEAVES = ("wq", "wk", "wv", "wo")
# read in bfloat16, the only compute dtype a cell of this runner states
BD_RTOL = {"bfloat16": {"routed_moved": 0.04, "attn_grad": 0.018}}


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
    if batch != CHECK_SEQUENCES:
        raise SystemExit(
            f"benchmark: the step draws its noise a sequence, so the check "
            f"batch is the timed batch: batch {batch} is not the check's "
            f"{CHECK_SEQUENCES} sequences")

    param_sh = model.shardings(mesh)
    params = jax.jit(model.init, out_shardings=param_sh)(
        jax.random.key(init_seed(job)))
    feed = batch_feeder(mesh)
    mark("weights", params)

    batches = load_module("data", w["data"]["kind"]).TokenBatches
    ids, tgt, check_pos = batches(w["data"], sizes.vocab, CHECK_SEQUENCES,
                                  seqlen, data_seed(job) + 1).next()
    # the step's own draw at its first call (step count 0), as arrays
    draw = jax.device_get(jax.jit(family.noise)(0, ids))
    want, want_routed, want_attn_grads = _reference(
        family, mesh, params, ids, check_pos, draw)
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
    # (the two limits are read at the published widths in bfloat16; the
    # rehearsal runs its tiny shape in float32, where `train`'s own two
    # limits are tight: there the two readings are logged and not held)
    check = _compare_bd(
        check, w["dtype"], first_counters["routed"], want_routed,
        _first_gradients(optimizer, opt_state), want_attn_grads,
        held=not job.rehearse)
    check["masked_share"] = float(first_counters["masked"]
                                  / first_counters["positions"])
    del want_attn_grads
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
    counters = jax.device_get([out[2] for out in window.results])
    raw_losses = [float(out[0]) for out in window.results]
    # the weighted mean CE of the masked positions: the loss over its
    # draw's factor (module docstring)
    losses = [loss * float(c["positions"]) / max(float(c["weight_sum"]), 1e-9)
              for loss, c in zip(raw_losses, counters)]

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
    tokens_per_step = batch * seqlen            # DATA tokens
    tokens_per_s = window.steps * tokens_per_step / window.seconds
    finite = [math.isfinite(x) for x in raw_losses]
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
    rows_per_token = float(rows.sum()) / tokens_per_step    # a DATA token
    positions = sum(float(c["positions"]) for c in counters)
    lines = [
        dict(event="window", steps=window.steps, seconds=window.seconds,
             step_ms_median=timing.quantile(intervals, 0.5),
             step_ms_p90=end_to_end["step_ms_p90"],
             step_ms_max=max(intervals), interval_samples=len(intervals),
             around_slowest_ms=intervals[max(slowest - 2, 0):slowest + 4],
             loss_first10=first10, loss_last10=last10,
             loss_raw_first10=_mean(raw_losses[:10]),
             loss_raw_last10=_mean(raw_losses[-10:]),
             losses_finite=all(finite), loss_fell=falling,
             masked_share=sum(float(c["masked"]) for c in counters)
             / positions,
             p_mean=sum(float(c["p_sum"]) for c in counters) / positions,
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
        parts = bd_scopes.scope_ns(devs[0], runs, names)
        breakdown = {"device_ops": trace.top_ops(devs[0]),
                     "idle_gaps": trace.top_gaps(devs[0], spans),
                     "scopes_ms_per_step": {
                         k: v / devs[0].steps / 1e6 for k, v in parts.items()},
                     "unattributed_ops": program_trace.top_unattributed(
                         devs[0], runs, names)}
    if job.rehearse:
        device.update(busy_s=None, window_s=None)

    # what the kernels' static plans compute under the family's mask, a
    # head and sequence; nothing where the kernels cannot plan the shape
    # (the XLA path runs it then)
    mask = model._attn_mask(2 * seqlen)
    try:
        flash_plan = {
            name: flash_tile_stats(2 * seqlen, head_dim=sizes.head_dim,
                                   dtype=w["dtype"], mask=mask,
                                   backward=name == "backward")
            for name in ("forward", "backward")}
    except ValueError:
        flash_plan = None

    measured = SimpleNamespace(
        workload=w, sizes=sizes, mesh=mesh_sizes, chips=chips,
        window=window, intervals_ms=intervals,
        tokens_per_s=tokens_per_s, setup_s=setup_s, compile_s=compile_s,
        cache_setup=cache_setup, cache_window=cache_window,
        flops_per_token=None, peak=peak, peak_bytes=peak_bytes, devices=devs,
        # what the scope-reading runners add
        scopes=parts, rows_here_per_layer=[float(r) for r in rows],
        # per ROW and layer: a layer sees two rows a data token
        rows_here_per_token=rows_per_token / 2 / sizes.expert_layers,
        load_max_over_mean=balance,
        active_flops_per_token=train_flops_per_token(sizes, seqlen,
                                                     rows_per_token),
        flash_plan=flash_plan)
    return Outcome(correct=correct, attempted=window.steps,
                   failed=finite.count(False), end_to_end=end_to_end,
                   measured=measured, device=device, breakdown=breakdown,
                   compared=compared(check, first10, last10,
                                     finite.count(False)))


def _attn_named(tree: dict) -> dict:
    """name -> the attention leaf of every layer as one (layers, elements)
    array, sampled: every GRAD_STRIDE-th element of a large leaf, a small
    one whole. The reference's gradient and Adam's first moment are the
    same tree, so the rows pair up."""
    def rows(leaf):
        flat = leaf.reshape(leaf.shape[0], -1)
        return flat[:, ::GRAD_STRIDE] if flat.shape[1] > 1 << 20 else flat

    return {name: rows(tree["layers"][name]["weight"])
            for name in ATTN_LEAVES}


def _reference(family, mesh, params, ids, pos, draw):
    """`train._reference` (float32, matmul precision "highest", a copy of
    the parameters on one device) on the step's own draw `(xt, m, p)`, with
    two things more from the same pass: the reference's `routed` counts and
    its gradients of the attention leaves, sampled, which leave the device
    at once."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    def detail(p, *batch):
        (loss, routed), grads = jax.value_and_grad(
            family.reference_routed, has_aux=True)(p, *batch)
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                            for g in jax.tree.leaves(grads)))
        return loss, norm, routed, _attn_named(grads)

    one = SingleDeviceSharding(mesh.devices.flat[0])
    held = jax.device_put(params, one)
    with jax.default_matmul_precision("highest"):
        loss, norm, routed, leaves = jax.jit(detail)(
            held, *(jax.device_put(x, one) for x in (ids, pos, *draw)))
    return ([float(loss), float(norm)], np.asarray(routed),
            jax.device_get(leaves))


def _first_gradients(optimizer, opt_state) -> dict:
    """The attention gradient leaves of the step's FIRST call, from what
    the step returned: Adam's first moment starts at zero, so after one
    update it is (1 - beta1) g, with the schedule's beta1 of step 0 (the
    default `OptimizerConfig` neither clips nor decays)."""
    import jax
    import jax.numpy as jnp
    from distributed_pytorch_from_scratch_tpu.training.optim import (
        schedule_lr)
    beta1 = float(schedule_lr(optimizer, jnp.zeros((), jnp.int32))[1])
    return {name: np.asarray(leaf) / (1.0 - beta1)
            for name, leaf in jax.jit(_attn_named)(opt_state.mu).items()}


def _compare_bd(check: dict, dtype: str, routed, want_routed, grads,
                want_grads, held: bool = True) -> dict:
    """`train._compare`'s record with this runner's two readings added
    (BD_RTOL, above); with `held` off they are recorded and decide
    nothing."""
    moved = (np.abs(routed - want_routed).sum(-1) / 2
             / want_routed.sum(-1))                        # a layer
    by_leaf = {}
    for name, want in want_grads.items():
        diff = np.square(grads[name].astype(np.float64) - want).sum(-1)
        norm = np.square(want, dtype=np.float64).sum(-1)   # (layers,)
        by_leaf[name] = np.sqrt(diff / np.where(norm > 0, norm, 1.0)).tolist()
    err = {"routed_moved": float(moved.max()),
           # numpy's max: a NaN anywhere is the reading
           "attn_grad": float(np.max(list(by_leaf.values())))}
    rtol = BD_RTOL[dtype] if held else {}
    ok = not held or all(math.isfinite(v) and v <= rtol[k]
                         for k, v in err.items())
    return {**check, "ok": bool(check["ok"] and ok),
            "rel_err": {**check["rel_err"], **err},
            "rtol": {**check["rtol"], **rtol},
            "routed_moved_by_layer": moved.tolist(),
            "attn_grad_by_leaf": by_leaf}
