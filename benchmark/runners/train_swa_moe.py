"""The `train_swa_moe` runner: the `train` runner's recipe (benchmark/
runners/train.py: its docstring is this runner's too, phase by phase) for
the swa_moe family, handing its per-layer readers what `train_conv_moe`
hands its own, by this family's names:

* **the step's `op_name` map** and the step's runs on chip 0, reduced to
  `measured.scopes`: device nanoseconds by the program's named scope
  (benchmark/lib/swa_scopes.py: `gqa_attn`, `dense_ffn`, `moe_route`,
  `moe_experts`, `moe_shared`, `head_loss`, `router_bias`, `optimizer`,
  `grad_norm`, and `flash`, `rest`, `unattributed`, `other_programs`);
* **the step's counters** (`with_counters=True`): per expert layer the pairs
  each routed expert got and the rows computed here, and the mean size of
  a selection-bias entry's step. The window's means feed
  `moe.load_max_over_mean`, `moe.rows_here_per_token`,
  `moe.bias_step_abs_mean`, `train_step.active_mfu_pct` and
  `model.moe_experts_roofline`;
* **`window_flash_plan`**: what the kernels' static plans compute under the
  window layers' mask at the cell's shape, forward and backward
  (`obs/attribution.flash_tile_stats`), for
  `window.flash_computed_over_live`.

`measured` carries every field `train_conv_moe`'s does (so `entry.*`,
`device.*`, `model.xla_ops_ms`, `kernels.flash_ms`, `model.gqa_attn_ms`,
`model.dense_ffn_ms`, `train_step.step_ms_median`, `model.moe_*` and `moe.*`
read it unchanged), with `scopes` keyed by this family's names and
`active_flops_per_token` from benchmark/lib/swa_moe_counts.py.
`flops_per_token` is None: `train_step.mfu_pct`, `kernels.flash_roofline`
and `kernels.gqa_flash_roofline` (one causal count for every call) do not
list this runner's cells; `kernels.window_flash_roofline` and
`kernels.full_flash_roofline` are theirs.

**The step moves the selection bias, so the check reads it.** The check is
the step's first call on the check batch, as in `train`; beside the
readings below it holds the bias leaves the step RETURNED to the three
lines of the benchmark's own copy of the rule (`families/swa_moe.bias_rule`)
applied to the counts the same step returned: equal but for the order in
which two programs sum 128 float32 steps (`BIAS_ATOL`).

`memory_peak_bytes` is what the chip held at ONE time (`_held_at_once`:
`benchmark/lib/memory.phase_peak_bytes` read for a step that fills the
chip, where the buffers' peak and the step's reservation are two moments).

The helpers are `train`'s and `train_hybrid`'s own, imported, not copied
(the comparison and ITS TOLERANCES, the memory readings, the log, the
gradient samples' stride); the recipe itself is the sixth copy, and the
fifth that reads scopes: ROADMAP D14.
"""

from __future__ import annotations

import json
import math
import os
import tempfile
import time
from types import SimpleNamespace

import numpy as np

from benchmark.lib import peaks, program_trace, swa_scopes, timing, trace
from benchmark.lib.swa_moe_counts import train_flops_per_token
from benchmark.lib.files import load_module
from benchmark.lib.job import Job, Outcome, data_seed, init_seed
from benchmark.runners.train import (CHECK_SEQUENCES, WARMUP_STEPS,
                                     _compare, _mean, _memory, _no_times,
                                     compared, log)
from benchmark.runners.train_hybrid import GRAD_STRIDE

# What this runner's check holds beside `train`'s two scalars (whose
# tolerances stand: `train.RTOL`). Four norms a layer renormalise the
# attention half, so unlike the other drawn cells the MASK is no small term
# of a freshly initialised model's loss here; still the loss hardly sees a
# router or an attention input in the precision below the cell's. So three
# more readings of the step's own outputs on the check batch are held to the
# reference. Each limit stands between the sound program's largest reading
# over its seeds and the smallest of a control, the program with one thing
# wrong (benchmark/tools/swa_control.py; my chip runs, PR 46; PERF.md
# section 2 has the readings):
#
# * `attn_grad`: of the attention leaves (`wq`, `wk`, `wv`, `wg`, `wo`),
#   each layer apart, the relative L2 error against `jax.grad` of the
#   reference (whose mask is a boolean matrix from `i - j`); the worst leaf
#   and layer. It is the guard of the window in the kernels' forward and
#   backward (the left-edge tile, the skipped tiles), of the positions a
#   window layer takes and a full layer does not, of the gate and of the
#   grouping.
# * `routed_moved`: per expert layer, the share of (token, choice) pairs
#   that the step's `routed` counter has at another expert than the
#   reference's top-k has them (half the summed absolute difference of the
#   two count vectors over the pairs); the MEAN over the expert layers,
#   which is steadier over seeds than the worst of four (sound 0.0016 -
#   0.0028 over ten seeds where the worst layer read 0.0020 - 0.0046) and
#   so leaves room on both sides of the limit: the router's input rounded
#   to float8 reads 0.0093 - 0.0109. Not zero in a sound run: the router
#   reads a hidden state computed in bfloat16, and a score within that
#   rounding of the ninth flips (its product in ONE bfloat16 pass adds a
#   sixth to that, inside the seeds' spread: no limit tells it apart).
# * `bias_rule`: the largest absolute difference between a selection-bias
#   entry the step returned and the rule's three lines applied to the counts
#   the same step returned (`BIAS_ATOL`, below).
#
# On every GRAD_STRIDE-th element of `wq`, `wg` and `wo` (the leaves are 34
# MB a layer); `wk` and `wv` whole. The step returns no gradient; after its
# first call Adam's first moment is (1 - beta1) times it, exactly.
ATTN_LEAVES = ("wq", "wk", "wv", "wg", "wo")
# `bias_rule`'s limit, absolute, in every compute dtype (the rule is float32
# whatever the products are). The counts and their mean are whole numbers
# and exact; `mean(delta)` is a float32 sum of 128 entries of +-0.001, and
# the step's program and the copy's sum them in orders of their compilers'
# choosing, so the two means can differ in their last bits and a bias entry
# (0.0005 - 0.002 after the first step) then in its last one: 0.0 in the
# eleven sound runs of the builder's first session (ten seeds) and 5.8e-11,
# one ulp of such an entry, at seed 357092872 (my chip run, PR 46; the
# driver's run at that seed was called incorrect). The first limit was 0.0:
# wrong, a limit has no room at a reading's own value. By arithmetic the
# rounding of a 128-term float32 sum of such entries stays under 1e-6 in
# any order, under 8e-9 in their mean. A rule that did not run, or ran at
# 1e-30 of its speed, reads 0.00142 (measured); one at a speed 1% off would
# read 1.4e-5 (arithmetic). So 1e-7: 1,700 times over the largest sound
# reading, 14,000 under the control's smallest.
BIAS_ATOL = 1e-7
# read in bfloat16, the only compute dtype a cell of this runner states
SWA_RTOL = {"bfloat16": {"routed_moved": 0.005, "attn_grad": 0.025,
                         "bias_rule": BIAS_ATOL}}


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
    ids, tgt, check_pos = batches(w["data"], sizes.vocab, CHECK_SEQUENCES,
                                  seqlen, data_seed(job) + 1).next()
    want, want_routed, want_attn_grads = _reference(family, mesh, params,
                                                    ids, tgt, check_pos)
    bias_before = jax.device_get(family.bias_in_order(params))
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
    # (the two relative limits are read at the published widths in
    # bfloat16; the rehearsal runs its tiny shape in float32, where
    # `train`'s own two limits are tight: there the two readings are logged
    # and not held; the bias rule's is float32 everywhere and always held)
    bias_want = family.bias_rule(bias_before, first_counters["routed"],
                                 family.bias_speed)
    check = _compare_swa(
        check, w["dtype"],
        first_counters["routed"] / (batch // CHECK_SEQUENCES), want_routed,
        _first_gradients(optimizer, opt_state), want_attn_grads,
        jax.device_get(family.bias_in_order(params)),
        jax.device_get(bias_want), held=not job.rehearse)
    # (0 where the step ran no rule: a program without it, or a control)
    check["router_bias_step"] = float(
        first_counters.get("router_bias_step", 0.0))
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
    bias_step = float(np.mean([c.get("router_bias_step", 0.0)
                               for c in counters]))
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
                 held[-10:].max(-1) / np.maximum(held[-10:].mean(-1), 1e-9))),
             bias_step_abs_mean=bias_step),
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
        parts = swa_scopes.scope_ns(devs[0], runs, names)
        breakdown = {"device_ops": trace.top_ops(devs[0]),
                     "idle_gaps": trace.top_gaps(devs[0], spans),
                     "scopes_ms_per_step": {
                         k: v / devs[0].steps / 1e6 for k, v in parts.items()},
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
        bias_step_abs_mean=bias_step, window_flash_plan=window_flash_plan)
    return Outcome(correct=correct, attempted=window.steps,
                   failed=finite.count(False), end_to_end=end_to_end,
                   measured=measured, device=device, breakdown=breakdown,
                   compared=compared(check, first10, last10,
                                     finite.count(False)))


def _held_at_once(after_reference: dict, after_window: dict) -> int:
    """`lib/memory.phase_peak_bytes` for a step that fills the chip: the most
    the chip held at ONE time. That helper's second term adds the buffers'
    PEAK to the reservation standing when the window ends; here the buffers
    peak before the step is loaded (10.16 GiB while the check's readings are
    made, against 7.99 GiB of weights and Adam state beside the step's 7.75
    GiB of reserved temporaries: 15.74 of the chip's 15.75), and the sum of
    two moments, 17.91 GiB, is more than the chip has (my chip run, PR 46).
    So the second term is what is held when the window ends, buffers and
    reservation, and the buffers' own peak stands as a third."""
    return max(after_reference["peak_bytes_in_use"]
               + after_reference["peak_bytes_reserved"],
               after_window["bytes_in_use"] + after_window["bytes_reserved"],
               after_window["peak_bytes_in_use"])


def _attn_named(tree: dict) -> dict:
    """name -> the attention leaf of every layer as one (layers, elements)
    array, sampled: the layers of the tree's keys in sorted order (the
    reference's gradient and Adam's first moment are the same tree, so the
    rows pair up). A segment's leaf is stacked (layers, ...), a period's
    (periods, layers a period, ...); a leaf's own dims are its last two."""
    import jax.numpy as jnp
    keys = sorted(key for key, layers in tree.items()
                  if isinstance(layers, dict) and "wq" in layers)

    def rows(leaf):
        flat = leaf.reshape(math.prod(leaf.shape[:-2]), -1)
        # every GRAD_STRIDE-th element of a large leaf, a small one whole
        return flat[:, ::GRAD_STRIDE] if flat.shape[1] > 1 << 20 else flat

    return {name: jnp.concatenate([rows(tree[key][name]["weight"])
                                   for key in keys])
            for name in ATTN_LEAVES}


def _reference(family, mesh, params, ids, tgt, pos):
    """`train._reference` (float32, matmul precision "highest", a copy of
    the parameters on one device) with two things more from the same pass:
    the reference's `routed` counts and its gradients of the attention
    leaves, sampled, which leave the device at once."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    def detail(p, i, t, q):
        (loss, routed), grads = jax.value_and_grad(
            family.reference_routed, has_aux=True)(p, i, t, q)
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                            for g in jax.tree.leaves(grads)))
        return loss, norm, routed, _attn_named(grads)

    one = SingleDeviceSharding(mesh.devices.flat[0])
    held = jax.device_put(params, one)
    with jax.default_matmul_precision("highest"):
        loss, norm, routed, leaves = jax.jit(detail)(
            held, *(jax.device_put(x, one) for x in (ids, tgt, pos)))
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


def _compare_swa(check: dict, dtype: str, routed, want_routed, grads,
                 want_grads, bias, want_bias, held: bool = True) -> dict:
    """`train._compare`'s record with this runner's three readings added
    (SWA_RTOL, above); with `held` off the two relative ones are recorded
    and decide nothing, the bias rule's is held always."""
    moved = (np.abs(routed - want_routed).sum(-1) / 2
             / want_routed.sum(-1))                        # an expert layer
    by_leaf = {}
    for name, want in want_grads.items():
        diff = np.square(grads[name].astype(np.float64) - want).sum(-1)
        norm = np.square(want, dtype=np.float64).sum(-1)   # (layers,)
        by_leaf[name] = np.sqrt(diff / np.where(norm > 0, norm, 1.0)).tolist()
    err = {"routed_moved": float(moved.mean()),
           # numpy's max: a NaN anywhere is the reading
           "attn_grad": float(np.max(list(by_leaf.values()))),
           "bias_rule": float(np.max(np.abs(bias - want_bias)))}
    rtol = SWA_RTOL[dtype] if held else {"bias_rule": BIAS_ATOL}
    ok = all(math.isfinite(err[k]) and err[k] <= limit
             for k, limit in rtol.items())
    return {**check, "ok": bool(check["ok"] and ok),
            "rel_err": {**check["rel_err"], **err},
            "rtol": {**check["rtol"], **rtol},
            "routed_moved_by_layer": moved.tolist(),
            "attn_grad_by_leaf": by_leaf,
            "bias_abs_max": float(np.max(np.abs(bias)))}
