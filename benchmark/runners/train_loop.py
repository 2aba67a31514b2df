"""The `train_loop` runner: the `train` runner's recipe (benchmark/runners/
train.py: its docstring is this runner's too, phase by phase) for the
loop_llama family, whose stack a step passes R times over the same weights
with an exit after every pass and an exit gate that weighs the exits'
losses. What differs:

* **the step is built `with_counters`** and its counters are the family's:
  the window's mean `exit_p_mean` makes `measured.exit_step_mean`
  (`loop.exit_step_mean`); the exits' losses and the entropy go on the
  `window` log line;
* **the scope split** is `benchmark/lib/loop_scopes.py`'s (`loop_pass`,
  `dense_ffn`, `head_loss`, `exit_gate`, `optimizer`, `grad_norm`, and
  `flash`, `rest`, `unattributed`, `other_programs`), in `measured.scopes`;
* **the counts** are `benchmark/lib/loop_llama_counts.py`'s:
  `measured.flops_per_token` is 6 x R x the matmul parameters a pass plus
  attention R x L times, NOT 6 N (`train_step.mfu_pct` reads it);
* **the check batch is ONE sequence** (the cell's batch is 1), where
  `train`'s is two;
* **the check holds four readings more** (below);
* `memory_peak_bytes` is what the chip held at ONE time
  (`train_swa_moe._held_at_once`).

`measured` carries every field `train`'s does, so the readers written for
it work here unchanged (`sizes` has the names `kernels.flash_roofline`
reads: `n_head`, `head_dim`).

The helpers are `train`'s and `train_swa_moe`'s own, imported, not copied
(the comparison of the two scalars and ITS TOLERANCES, the memory readings,
the log); the recipe itself is the eleventh copy: ROADMAP D14.
"""

from __future__ import annotations

import json
import math
import os
import tempfile
import time
from types import SimpleNamespace

import numpy as np

from benchmark.lib import loop_scopes, peaks, program_trace, timing, trace
from benchmark.lib.files import load_module
from benchmark.lib.job import Job, Outcome, data_seed, init_seed
from benchmark.lib.loop_llama_counts import train_flops_per_token
from benchmark.runners.train import (WARMUP_STEPS, _compare, _mean, _memory,
                                     _no_times, compared, log)
from benchmark.runners.train_swa_moe import _held_at_once

CHECK_SEQUENCES = 1
GRAD_STRIDE = 7     # divides no size of a leaf: every row and column is met

# What this runner's check holds beside `train`'s two scalars (`RTOL`, whose
# limits stand: this cell's readings are in PERF.md section 2). The loss and
# the gradient norm of a freshly initialised model hardly see WHICH pass an
# exit reads, what the gate's gradient is made of, or whether a weight's
# gradient is the sum of its R uses: the norm is the head's and the
# embedding's before it is the layers'. So four readings more of the step's
# own outputs on the check batch, against `jax.grad` of the reference
# (float32, "highest"); benchmark/tools/loop_control.py reads each for a
# wrong program, and PERF.md section 2 has the table and the seeds.
#
# * `exit_losses`: the R per-exit mean CEs the step counted (`loss_exit`)
#   against the reference's; the worst exit's relative error. A step that
#   counted another number of exits reads inf. Refuses a program that ran
#   R - 1 passes, or fed a pass the un-normed state.
# * `gate_grad`: the gate's d + 1 gradient entries, relative L2. Its
#   gradient exists only through `p`: the weighted sum's and the entropy's.
#   Refuses a dropped entropy term, a `stop_gradient` on `p`, a last step
#   that does not take the remainder.
# * `shared_grad`: every leaf of the shared layers, each layer apart (every
#   GRAD_STRIDE-th entry of a matrix, a norm's weight whole), relative L2;
#   the worst leaf and layer. Refuses a gradient taken from one pass and not
#   the sum of R, and the layers' inputs in the precision below the cell's.
#   (The R-fold sum ACCUMULATED in bfloat16 reads as the sound program, 0.0333
#   against 0.0332: four terms' rounding is under the gradients' own.)
# * `sampled_grads`: every GRAD_STRIDE-th entry of every OTHER leaf (the
#   embedding, the head, the final norm), relative L2 over all of them.
#
# The step returns no gradient; after its first call Adam's first moment is
# (1 - beta1) times it, exactly.
#
# Limits (bfloat16, the only compute dtype a cell of this runner states; my
# chip runs, PR 66, calls 90 - 91: eight sound runs on eight seeds of
# weights and data; PERF.md section 2 has every reading), each between the
# sound runs' largest reading and the smallest of the wrong programs the
# reading is there to refuse:
#   exit_losses    sound 4.5e-5 - 2.8e-4; no_norm_between 3.9e-3, 8.5e-3,
#                  one_pass_short inf                            -> 1e-3
#   gate_grad      sound 0.010 - 0.016 in seven, 0.050 in one; no_entropy
#                  0.276, one_pass_short 0.270, p_detached 1.00  -> 0.13
#   shared_grad    sound 0.021 - 0.036; one_pass_short 0.47,
#                  fp8_ffn_inputs 1.00 (p_detached 0.12, 0.21)    -> 0.08
#   sampled_grads  sound 0.0076 - 0.0122; fp8_ffn_inputs 0.052, 0.061,
#                  one_pass_short 0.185                          -> 0.025
LOOP_RTOL = {"bfloat16": {"exit_losses": 1e-3, "gate_grad": 0.13,
                          "shared_grad": 0.08, "sampled_grads": 0.025},
             # (the rehearsal's dtype: the two texts agree to rounding)
             "float32": {"exit_losses": 1e-5, "gate_grad": 1e-4,
                         "shared_grad": 1e-4, "sampled_grads": 1e-4}}


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
    want, want_exits, want_grads = _reference(family, mesh, params, ids, tgt,
                                              check_pos)
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
    check = _compare_loop(check, w["dtype"], first_counters["loss_exit"],
                          want_exits, _first_gradients(optimizer, opt_state),
                          want_grads)
    del want_grads
    log(event="check", **check, parameters=n_params,
        loss_main=float(first_counters["loss_main"]),
        loss_exit=first_counters["loss_exit"].tolist(),
        exit_p_mean=first_counters["exit_p_mean"].tolist(),
        exit_entropy=float(first_counters["exit_entropy"]))
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

    # the window's counters: the exit distribution's mean a pass, and the
    # mean exit step it makes
    exit_p = np.mean([c["exit_p_mean"] for c in counters], axis=0)
    exit_step = float(np.sum(exit_p * np.arange(1, len(exit_p) + 1)))
    slowest = intervals.index(max(intervals))
    lines = [
        dict(event="window", steps=window.steps, seconds=window.seconds,
             step_ms_median=timing.quantile(intervals, 0.5),
             step_ms_p90=end_to_end["step_ms_p90"],
             step_ms_max=max(intervals), interval_samples=len(intervals),
             around_slowest_ms=intervals[max(slowest - 2, 0):slowest + 4],
             loss_first10=first10, loss_last10=last10,
             losses_finite=all(finite), loss_fell=falling,
             loss_exit_last=counters[-1]["loss_exit"].tolist(),
             exit_p_mean=exit_p.tolist(), exit_step_mean=exit_step,
             exit_entropy_last=float(counters[-1]["exit_entropy"])),
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
    breakdown = parts = None
    if job.trace and devs:
        device["busy_s"] = sum(d.busy_ns() for d in devs) / len(devs) / 1e9
        device["window_s"] = sum(d.window_ns for d in devs) / len(devs) / 1e9
        spans = trace.host_spans(captured, "bench.")
        names = program_trace.op_names(step_hlo)
        runs = program_trace.step_runs(captured, devs[0])
        parts = loop_scopes.scope_ns(devs[0], runs, names)
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
        flops_per_token=train_flops_per_token(sizes, seqlen),
        peak=peak, peak_bytes=peak_bytes, devices=devs,
        # what this runner adds
        scopes=parts, exit_step_mean=exit_step)
    return Outcome(correct=correct, attempted=window.steps,
                   failed=finite.count(False), end_to_end=end_to_end,
                   measured=measured, device=device, breakdown=breakdown,
                   compared=compared(check, first10, last10,
                                     finite.count(False)))


def _sampled(leaf, rows: int):
    """A leaf as (`rows`, entries): every GRAD_STRIDE-th entry of a matrix,
    a vector's whole; `rows` is the layers of a stacked leaf, else 1."""
    flat = leaf.reshape(rows, -1)
    return flat[:, ::GRAD_STRIDE] if flat.shape[1] > 1 << 16 else flat


def _grads_named(tree: dict) -> dict:
    """name -> (rows, entries) of every leaf of a gradient tree (or of
    Adam's first moment, the same tree): the shared layers' leaves a row a
    layer under `layers/...`, the gate's two as one row under `exit_gate`,
    every other leaf one row under its own name."""
    import jax
    import jax.numpy as jnp
    out = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        keys = [k.key for k in path]
        if keys[0] == "exit_gate":
            continue
        out["/".join(keys)] = _sampled(
            leaf, leaf.shape[0] if keys[0] == "layers" else 1)
    gate = tree["exit_gate"]
    out["exit_gate"] = jnp.concatenate(
        [gate["weight"].reshape(-1), gate["bias"].reshape(-1)])[None]
    return out


def _reference(family, mesh, params, ids, tgt, pos):
    """`train._reference` (float32, matmul precision "highest", a copy of
    the parameters on one device: loss, gradient norm) with the reference's
    R exit losses and its gradient leaves, sampled (`_grads_named`), from
    the same pass."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    def detail(p, i, t, q):
        (loss, more), grads = jax.value_and_grad(
            family.reference_detail, has_aux=True)(p, i, t, q)
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                            for g in jax.tree.leaves(grads)))
        return loss, norm, more["loss_exit"], _grads_named(grads)

    one = SingleDeviceSharding(mesh.devices.flat[0])
    held = jax.device_put(params, one)
    with jax.default_matmul_precision("highest"):
        loss, norm, exits, leaves = jax.jit(detail)(
            held, *(jax.device_put(x, one) for x in (ids, tgt, pos)))
    return ([float(loss), float(norm)], np.asarray(exits),
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
            for name, leaf in jax.jit(_grads_named)(opt_state.mu).items()}


def _rel_l2(got, want) -> np.ndarray:
    """Relative L2 error a row."""
    diff = np.square(got.astype(np.float64) - want).sum(-1)
    norm = np.square(want, dtype=np.float64).sum(-1)
    return np.sqrt(diff / np.where(norm > 0, norm, 1.0))


def _compare_loop(check: dict, dtype: str, exits, want_exits, grads: dict,
                  want_grads: dict) -> dict:
    """`train._compare`'s record with this runner's four readings added
    (LOOP_RTOL, above)."""
    exits = np.asarray(exits, np.float64)
    by_leaf = {name: _rel_l2(grads[name], want).tolist()
               for name, want in want_grads.items()}
    layers = [v for name, v in by_leaf.items() if name.startswith("layers/")]
    others = [name for name in want_grads
              if not name.startswith("layers/") and name != "exit_gate"]
    err = {
        # numpy's max: a NaN anywhere is the reading, and is over any limit
        "exit_losses": (float(np.max(np.abs(exits - want_exits)
                                     / np.abs(want_exits)))
                        if exits.shape == want_exits.shape else math.inf),
        "gate_grad": float(by_leaf["exit_gate"][0]),
        "shared_grad": float(np.max(np.concatenate(
            [np.asarray(v) for v in layers]))),
        "sampled_grads": float(_rel_l2(
            np.concatenate([grads[n].ravel() for n in others]),
            np.concatenate([want_grads[n].ravel() for n in others]))),
    }
    rtol = LOOP_RTOL[dtype]
    ok = all(math.isfinite(v) and v <= rtol[k] for k, v in err.items())
    return {**check, "ok": bool(check["ok"] and ok),
            "rel_err": {**check["rel_err"], **err},
            "rtol": {**check["rtol"], **rtol},
            "loss_exit_reference": want_exits.tolist(),
            "grad_by_leaf": by_leaf}
