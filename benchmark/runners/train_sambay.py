"""The `train_sambay` runner: the `train` runner's recipe (benchmark/
runners/train.py: its docstring is this runner's too, phase by phase) for
the sambay family, a dense decoder-hybrid-decoder: Mamba-1 scans and window
differential attention below, ONE layer's scan output and ONE layer's keys
and values read by every layer above through gated memory units and
cross-attentions. What differs, as `train_ssm_dense` differs:

* **the step is built `with_counters`** and its counters are the family's:
  the window's worst `sscan_decay_min` (`ssm1.decay_min`) and mean
  `diff_lambda` (`diff.lambda_mean`), by layer in the `window` log line with
  `memory_rms`, `shared_kv_readers` and `resid_rms_last`;
* **the scope split** is `benchmark/lib/sambay_scopes.py`'s (`mamba1`,
  `diff_attn`, `cross_attn`, `gmu`, `dense_ffn`, `head_loss`, `optimizer`,
  `grad_norm`, and `flash`, `rest`, `unattributed`, `other_programs`) in
  `measured.scopes`, and the mixer's time by inner scope in
  `measured.mamba1_parts` (`mamba1/sscan` is `model.sscan_ms`'s);
* **the counts** are `benchmark/lib/sambay_counts.py`'s:
  `measured.flops_per_token` is 6 x the matmul parameters (the tied table
  once), attention at two maps a differential head in three layers of six
  (one of them a window's band) and the scans' own operations
  (`train_step.mfu_pct` reads it), and `measured.sscan_cost` what a layer's
  scan must compute and move (`model.sscan_roofline`);
* **the check batch is ONE sequence** at the timed length (the cell's batch
  is 1: the state fills the chip);
* **the check holds five readings more** (below);
* `memory_peak_bytes` is what the chip held at ONE time
  (`train_swa_moe._held_at_once`).

`measured` carries every field `train`'s does, so the readers written for
it work here unchanged (`sizes` has the names they read: `n_head`,
`n_kv_head`, `head_dim`, `n_mamba_layer`).

The helpers are `train`'s, `train_swa_moe`'s and `train_ssm_dense`'s own,
imported, not copied (the comparison of the two scalars and ITS TOLERANCES,
the memory readings, the log, the sampled leaves and their relative error);
the recipe itself is the fourteenth copy: ROADMAP D14.
"""

from __future__ import annotations

import json
import math
import os
import tempfile
import time
from types import SimpleNamespace

import numpy as np

from benchmark.lib import (peaks, program_trace, sambay_scopes, timing,
                           trace)
from benchmark.lib.files import load_module
from benchmark.lib.job import Job, Outcome, data_seed, init_seed
from benchmark.lib.sambay_counts import sscan_cost, train_flops_per_token
from benchmark.runners.train import (WARMUP_STEPS, _compare, _mean, _memory,
                                     _no_times, compared, log)
from benchmark.runners.train_ssm_dense import _rel_l2, _sampled
from benchmark.runners.train_swa_moe import _held_at_once

CHECK_SEQUENCES = 1

# What this runner's check holds beside `train`'s two scalars (`RTOL`, whose
# limits stand: this cell's readings are in PERF.md section 2). The loss and
# the gradient norm of a freshly initialised model hardly see HOW a scan
# remembers, what lambda is, or WHICH layer's keys a cross layer reads: the
# norm is the table's and the SwiGLUs' before it is the mixers'. So five
# readings more of the step's own outputs on the check batch, against
# `jax.grad` of the reference (float32, "highest", the recurrence token by
# token, `M`, `k`, `v` plain values handed down a loop of layers);
# benchmark/tools/sambay_control.py reads each for a wrong program, and
# PERF.md section 2 has the table and the seeds. Each is the relative L2
# error of the step's gradient over a group of leaves, the worst leaf and
# layer of the group:
#
# * `scan_grad`: every Mamba-1 leaf (`w_in`, the convolution and its bias,
#   `w_x`, `w_dt`, `dt_bias`, `A_log`, `D`, `w_out`), each Mamba layer
#   apart. The guard of the scan's kernels, forward and backward: the
#   gradients of `A_log`, `w_dt` and `dt_bias` exist only through the decays.
# * `diff_grad`: every leaf of the `swa`, `full` and `cross` layers'
#   attention (the projections and their biases, the four lambda vectors,
#   the heads' norm weight). The guard of the two maps, lambda, `1 -
#   lambda_init`, the window and the grouping. `wk`'s BIAS is left out: a
#   softmax does not see a bias on its keys, so its gradient is rounding's
#   and has no relative error. A layer's four LAMBDA VECTORS are ONE leaf,
#   and not over their own norm: they carry ONE scalar's gradient, `dL /
#   dlambda`, a sum over every row and head that CANCELS, to nothing on
#   some seeds (over its own norm the sound program read 0.015 - 0.59 over
#   its seeds and one traced run came out not correct: no reading); their
#   error is taken over the norm of the reference's four vectors PLUS the
#   norm of the reference's gradient at the heads'
#   norm weight, the layer's other scale a head, a sum over the same rows
#   and heads that does not cancel (`_cancelling_errors`, which reads a
#   Mamba-1 layer's `w_x` so too, beside `D`).
# * `shared_grad`: layer 17's `wk` and `wv` (the matrices; `wv`'s bias) and
#   every leaf of layer 16's mixer: the leaves the SUMMED cotangents of the
#   keys and values and of the memory land in. The guard of the stack's
#   shared values: a reader's cotangent dropped moves these and no other.
# * `gmu_grad`: the gated memory units' two matrices.
# * `sampled_grads`: every GRAD_STRIDE-th entry of every OTHER leaf (the
#   SwiGLUs' matrices and the LayerNorms of every layer, the tied table, the
#   final norm), relative L2 over all of them.
#
# On every GRAD_STRIDE-th element of a large leaf; the small leaves whole.
# The step returns no gradient; after its first call Adam's first moment is
# (1 - beta1) times it, exactly.
#
# Limits (bfloat16, the only compute dtype a cell of this runner states; my
# chip runs, PR 76: thirty-two sound runs on their own seeds of weights and
# data (calls 6, 7 and 10; the last ten with the measures as they stand),
# one run a control on seed 2147483801 (calls 3, 6 and 10); PERF.md section
# 2 has every reading), each between the sound runs' largest reading and
# the smallest of the wrong programs the reading is there to refuse, with
# the more room above the sound ones (fresh seeds read higher):
#   scan_grad      sound 0.011 - 0.044; bf16_state 11.2, memory_reader_
#                  dropped 0.60, kv_reader_dropped 0.31              -> 0.15
#   diff_grad      sound 0.015 - 0.059 (the lambdas' leaf 0.0001 - 0.059,
#                  every other leaf under 0.019); kv_reader_dropped 0.88,
#                  lambda_at_init 1.39, window_unbounded 1.44,
#                  no_out_scale 1.46, cross_own_keys 2.11            -> 0.25
#   shared_grad    sound 0.014 - 0.044; memory_reader_dropped 0.60,
#                  kv_reader_dropped 0.88, bf16_state 2.2            -> 0.15
#   gmu_grad       sound 0.0075 - 0.0099; memory_after_gate 0.96,
#                  lambda_at_init 0.30                               -> 0.04
#   sampled_grads  sound 0.0076 - 0.0098; memory_after_gate 0.10,
#                  kv_reader_dropped 0.28, no_out_scale 0.69         -> 0.03
# `train`'s two stand with room: loss sound 3.6e-6 - 1.5e-4 under 5e-4
# (no_out_scale 8.4e-3, window_unbounded 1.6e-3), gradient norm sound 3.8e-6
# - 1.0e-3 under 5e-3 (lambda_at_init 0.013, kv_reader_dropped 0.051,
# window_unbounded 0.155). bf16_state, memory_after_gate, cross_own_keys and
# memory_reader_dropped read as the sound program on BOTH of those: each is
# refused by a leaf reading alone, which is what the leaf readings are for.
# The window ONE key short (511 of 512) reads as the sound program on every
# reading: a key in 512 is under bfloat16's rounding, and its guard is the
# float32 test (tests/test_sambay.py).
SCAN_LEAVES = ("w_in", "conv", "conv_bias", "w_x", "w_dt", "dt_bias",
               "A_log", "D", "w_out")
GRAD_RTOL = {"bfloat16": {"scan_grad": 0.15, "diff_grad": 0.25,
                          "shared_grad": 0.15, "gmu_grad": 0.04,
                          "sampled_grads": 0.03},
             # (the rehearsal's dtype: the two texts agree to rounding)
             "float32": {"scan_grad": 1e-3, "diff_grad": 1e-3,
                         "shared_grad": 1e-3, "gmu_grad": 1e-3,
                         "sampled_grads": 1e-3}}


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
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    feed = batch_feeder(mesh)
    mark("weights", params)

    batches = load_module("data", w["data"]["kind"]).TokenBatches
    ids, tgt, check_pos = batches(w["data"], sizes.vocab, CHECK_SEQUENCES,
                                  seqlen, data_seed(job) + 1).next()
    want, want_grads = _reference(family, mesh, params, ids, tgt, check_pos)
    mark("reference")
    memory_reference = _memory(devices[:chips])

    scalar = NamedSharding(mesh, P())
    opt_state = jax.jit(init_adam_state, out_shardings=AdamState(
        step=scalar, mu=param_sh, nu=param_sh))(params)
    optimizer = OptimizerConfig()
    step_fn = build_train_step(model, mesh, optimizer,
                               with_grad_norm=True, with_counters=True)
    mark("adam_state", opt_state)

    stream = batches(w["data"], sizes.vocab, batch, seqlen, data_seed(job))
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
    # (the limits are read at the published widths; at the rehearsal shape
    # they are float32's)
    check = _compare_grads(
        check, w["dtype"],
        _first_gradients(optimizer, opt_state), want_grads)
    del want_grads
    log(event="check", **check, parameters=n_params,
        loss_main=float(first_counters["loss_main"]),
        resid_rms_last=float(first_counters["resid_rms_last"]),
        memory_rms=float(first_counters["memory_rms"]),
        shared_kv_readers=float(first_counters["shared_kv_readers"]),
        diff_lambda=first_counters["diff_lambda"].tolist(),
        sscan_decay_min=first_counters["sscan_decay_min"].tolist())
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

    # the window's counters: per Mamba layer the decay's minimum (the
    # worst over the steps), per attention layer lambda's mean, and the
    # mean RMS of the memory and of what enters the final norm
    decay_min = np.min([c["sscan_decay_min"] for c in counters], axis=0)
    lambdas = np.mean([c["diff_lambda"] for c in counters], axis=0)
    memory_rms = float(np.mean([c["memory_rms"] for c in counters]))
    resid_rms = float(np.mean([c["resid_rms_last"] for c in counters]))
    slowest = intervals.index(max(intervals))
    lines = [
        dict(event="window", steps=window.steps, seconds=window.seconds,
             step_ms_median=timing.quantile(intervals, 0.5),
             step_ms_p90=end_to_end["step_ms_p90"],
             step_ms_max=max(intervals), interval_samples=len(intervals),
             around_slowest_ms=intervals[max(slowest - 2, 0):slowest + 4],
             loss_first10=first10, loss_last10=last10,
             losses_finite=all(finite), loss_fell=falling,
             sscan_decay_min_by_layer=decay_min.tolist(),
             diff_lambda_by_layer=lambdas.tolist(),
             memory_rms_mean=memory_rms,
             shared_kv_readers=float(counters[-1]["shared_kv_readers"]),
             resid_rms_last_mean_first_last=[
                 resid_rms, float(counters[0]["resid_rms_last"]),
                 float(counters[-1]["resid_rms_last"])]),
        dict(event="setup", setup_s=setup_s, parameters=n_params,
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
    breakdown = parts = mamba1_parts = None
    if job.trace and devs:
        device["busy_s"] = sum(d.busy_ns() for d in devs) / len(devs) / 1e9
        device["window_s"] = sum(d.window_ns for d in devs) / len(devs) / 1e9
        spans = trace.host_spans(captured, "bench.")
        names = program_trace.op_names(step_hlo)
        runs = program_trace.step_runs(captured, devs[0])
        parts = sambay_scopes.scope_ns(devs[0], runs, names)
        mamba1_parts = sambay_scopes.mamba1_parts_ns(devs[0], runs, names)
        per_step = lambda ns: {k: v / devs[0].steps / 1e6
                               for k, v in ns.items()}
        breakdown = {"device_ops": trace.top_ops(devs[0]),
                     "idle_gaps": trace.top_gaps(devs[0], spans),
                     "scopes_ms_per_step": per_step(parts),
                     "mamba1_parts_ms_per_step": per_step(mamba1_parts),
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
        flops_per_token=train_flops_per_token(sizes, seqlen),
        peak=peak, peak_bytes=peak_bytes, devices=devs,
        # what this runner adds
        scopes=parts, mamba1_parts=mamba1_parts,
        sscan_decay_min=float(decay_min.min()),
        diff_lambda_mean=float(lambdas.mean()), resid_rms_last=resid_rms,
        sscan_cost=sscan_cost(batch // mesh_sizes.get("dp", 1), seqlen,
                              sizes, itemsize))
    return Outcome(correct=correct, attempted=window.steps,
                   failed=finite.count(False), end_to_end=end_to_end,
                   measured=measured, device=device, breakdown=breakdown,
                   compared=compared(check, first10, last10,
                                     finite.count(False)))


def _stacked(tree: dict) -> dict:
    """key -> the subtree of every key that holds layers, its layers leading,
    (layers, ...): a period's (periods, layers a period, ...) flattened in
    the order the layers run, a maker's segment as it is."""
    import jax
    flat = lambda a: a.reshape(a.shape[0] * a.shape[1], *a.shape[2:])
    return {key: (layers if key in ("memory_layers", "full_layers")
                  else jax.tree.map(flat, layers))
            for key, layers in sorted(tree.items()) if "_layers" in key}


def _paths(tree) -> dict:
    import jax
    return {"/".join(k.key for k in path): leaf for path, leaf
            in jax.tree_util.tree_leaves_with_path(tree)}


def _grads_named(tree: dict) -> dict:
    """name -> (rows, entries) of every leaf of a gradient tree (or of
    Adam's first moment, the same tree), sampled, a row a layer: a Mamba-1
    leaf under `scan/<key>/<leaf>`, an attention leaf under
    `diff/<key>/<path>` (`wk`'s bias under `nogradient/`: compared with
    nothing), a memory unit's under `gmu/`; the leaves the shared values'
    summed cotangents land in AGAIN under `shared/`; every other leaf (the
    SwiGLUs', the norms', the table's) under `rest/...`."""
    out = {}
    for key, layers in _stacked(tree).items():
        for mixer, group in (("mamba", "scan"), ("attn", "diff"),
                             ("cross", "diff"), ("gmu", "gmu")):
            for path, leaf in _paths(layers.get(mixer, {})).items():
                group_of = ("nogradient" if path == "wk/bias" else group)
                out[f"{group_of}/{key}/{path}"] = _sampled(leaf)
        rest = {k: v for k, v in layers.items()
                if k not in ("mamba", "attn", "cross", "gmu")}
        for path, leaf in _paths(rest).items():
            out[f"rest/{key}/{path}"] = _sampled(leaf)
    for name in SCAN_LEAVES:
        out[f"shared/memory_layers/{name}"] = out[
            f"scan/memory_layers/{name}"]
    for path in ("wk/weight", "wv/weight", "wv/bias"):
        if f"diff/full_layers/{path}" in out:
            out[f"shared/full_layers/{path}"] = out[
                f"diff/full_layers/{path}"]
    out["rest/embedding"] = _sampled(tree["embedding"]["weight"][None])
    out["rest/norm/scale"] = tree["norm"]["scale"][None]
    out["rest/norm/bias"] = tree["norm"]["bias"][None]
    return out


def _reference(family, mesh, params, ids, tgt, pos):
    """`train._reference` (float32, matmul precision "highest", a copy of
    the parameters on one device: loss, gradient norm) with the reference's
    gradient leaves, sampled (`_grads_named`), from the same pass."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    def detail(p, i, t, q):
        loss, grads = jax.value_and_grad(family.reference_loss)(p, i, t, q)
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                            for g in jax.tree.leaves(grads)))
        return loss, norm, _grads_named(grads)

    one = SingleDeviceSharding(mesh.devices.flat[0])
    held = jax.device_put(params, one)
    with jax.default_matmul_precision("highest"):
        loss, norm, leaves = jax.jit(detail)(
            held, *(jax.device_put(x, one) for x in (ids, tgt, pos)))
    return [float(loss), float(norm)], jax.device_get(leaves)


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
            for name, leaf in jax.jit(_grads_named)(opt_state.mu).items()}


GROUPS = {"scan_grad": "scan/", "diff_grad": "diff/",
          "shared_grad": "shared/", "gmu_grad": "gmu/"}


LAMBDAS = ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")
# The leaves whose gradient is a sum that CANCELS, to a tenth of its usual
# size or to nothing on some seeds, while bfloat16's error in it does not
# shrink with it: each is read over its own norm PLUS the norm of the
# reference's gradient at a leaf of the same module that is a sum over the
# same rows and does not cancel (module comment).
#   * a layer's four lambda vectors, ONE leaf (`<key>/lambdas`): they carry
#     one scalar's gradient, `dL / dlambda`; beside the heads' norm weight;
#   * a Mamba-1 layer's `w_x`: it carries B's and C's gradients, sums over
#     5120 channels (0.0036 on seed 2147484067 where 0.062 on seed
#     2147484037, with the same error of 5e-4: my chip run, PR 76, call 9);
#     beside `D`, the scan output's cotangent against u.
BESIDE = {"w_x": "D"}


def _norm(a) -> np.ndarray:
    return np.sqrt(np.square(np.asarray(a, np.float64)).sum(-1))


def _cancelling_errors(grads: dict, want_grads: dict) -> dict:
    """name -> a row a layer, for the leaves of `LAMBDAS` and `BESIDE`."""
    out = {}
    for key in sorted({name.rsplit("/", 1)[0] for name in want_grads
                       if name.endswith("/lambda_q1")}):
        four = lambda tree: np.concatenate(
            [np.asarray(tree[f"{key}/{v}"], np.float64) for v in LAMBDAS],
            axis=-1)
        want = four(want_grads)
        scale = _norm(want) + _norm(want_grads[f"{key}/subln"])
        out[f"{key}/lambdas"] = (_norm(four(grads) - want)
                                 / np.where(scale > 0, scale, 1.0)).tolist()
    for name, want in want_grads.items():
        key, leaf = name.rsplit("/", 1)
        if leaf in BESIDE and not name.startswith("rest/"):
            scale = _norm(want) + _norm(want_grads[f"{key}/{BESIDE[leaf]}"])
            out[name] = (_norm(np.asarray(grads[name], np.float64) - want)
                         / np.where(scale > 0, scale, 1.0)).tolist()
    return out


def _compare_grads(check: dict, dtype: str, grads: dict,
                   want_grads: dict) -> dict:
    """`train._compare`'s record with this runner's five readings added
    (GRAD_RTOL, above)."""
    alone = lambda name: not (name.startswith("nogradient/")
                              or name.rsplit("/", 1)[-1] in LAMBDAS)
    by_leaf = {name: _rel_l2(grads[name], want).tolist()
               for name, want in want_grads.items() if alone(name)}
    by_leaf.update(_cancelling_errors(grads, want_grads))
    # numpy's max: a NaN anywhere is the reading, and is over any limit
    worst = lambda prefix: float(np.max(np.concatenate(
        [np.asarray(v) for name, v in by_leaf.items()
         if name.startswith(prefix)])))
    rest = [name for name in want_grads if name.startswith("rest/")]
    err = {**{reading: worst(prefix) for reading, prefix in GROUPS.items()},
           "sampled_grads": float(_rel_l2(
               np.concatenate([grads[n].ravel() for n in rest]),
               np.concatenate([want_grads[n].ravel() for n in rest])))}
    rtol = GRAD_RTOL[dtype]
    ok = all(math.isfinite(v) and v <= rtol[k] for k, v in err.items())
    return {**check, "ok": bool(check["ok"] and ok),
            "rel_err": {**check["rel_err"], **err},
            "rtol": {**check["rtol"], **rtol},
            "grad_by_leaf": {name: v for name, v in by_leaf.items()
                             if not name.startswith("rest/")}}
