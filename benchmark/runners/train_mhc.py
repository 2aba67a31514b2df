"""The `train_mhc` runner: the `train_scopes` runner's recipe (benchmark/
runners/train_scopes.py: its docstring is this runner's too, phase by phase)
for the mhc_mla_moe family, whose residual state is hyper-connection
streams. What differs:

* **the scope split** is `benchmark/lib/mhc_scopes.py`'s (`lib/scopes.py`'s
  list with `mhc`), so `measured.scopes` carries `mhc` beside the names the
  `train_scopes` readers read (`model.mla_ms`, `model.moe_*`), and the
  breakdown carries the mixers' time by part (`mhc_parts_ms_per_step`);
* **the counters** carry the mixers' rows too: the window's worst
  `hc_sinkhorn_err` (`mhc.sinkhorn_err`) and the mean `hc_res_offdiag`;
* **the counts** are `benchmark/lib/mhc_mla_moe_counts.py`'s: the active
  FLOPs with the mixers', and the bytes the mixers must move
  (`measured.mhc_cost`, for `model.mhc_roofline`);
* **the check batch is ONE sequence** (the cell's batch is 1: the state
  fills the chip), where `train`'s is two;
* **the check holds two readings more, `hc_grad` and `hc_colsum`**
  (below);
* `memory_peak_bytes` is what the chip held at ONE time
  (`train_swa_moe._held_at_once`: this step fills the chip too).

`measured` carries every field `train_scopes`'s does, so the readers
written for it work here unchanged.

The helpers are `train`'s, `train_scopes`'s and `train_swa_moe`'s own,
imported, not copied (the comparison and ITS TOLERANCES, the expert
leaves' sampling, Adam's first moment as the step's gradient, the memory
readings, the log); the recipe itself is the eighth copy: ROADMAP D14.
"""

from __future__ import annotations

import json
import math
import os
import tempfile
import time
from types import SimpleNamespace

import numpy as np

from benchmark.lib import mhc_scopes, peaks, program_trace, timing, trace
from benchmark.lib.files import load_module
from benchmark.lib.job import Job, Outcome, data_seed, init_seed
from benchmark.lib.mhc_mla_moe_counts import (mixers_step_cost,
                                              train_flops_per_token)
from benchmark.runners.train import (WARMUP_STEPS, _compare, _mean, _memory,
                                     _no_times, compared, log)
from benchmark.runners.train_scopes import (_compare_moe,
                                            _moe_named, _sample)
from benchmark.runners.train_swa_moe import _held_at_once

CHECK_SEQUENCES = 1

# What this runner's check holds beside `train`'s two scalars and
# `train_scopes`'s three readings (`MOE_RTOL`, whose limits stand: the
# sublayers are that family's; this cell's readings are in PERF.md section
# 2). Loss and gradient norm of a freshly initialised model hardly see HOW
# the streams are mixed or where the positions turn: with every mixer's maps
# in bfloat16, or with plain RoPE's tables, both read inside `train`'s
# limits. So two readings more of the step's own outputs on the check batch
# (my chip runs, PR 57: the sound program over 11 draws, each control at
# seeds 2147483659 and 3000000019 with the weights from the seed too;
# benchmark/tools/mhc_control.py; PERF.md section 2 has the table):
#
# * `hc_grad`: over the W leaves of the layers' mixers (`hc_attn` and
#   `hc_ffn`, each layer apart) the relative L2 error of the step's gradient
#   against `jax.grad` of the reference; the worst leaf and layer. A mixer's
#   gradient passes through the Sinkhorn rounds' backward and two sigmoids
#   and sums over every token, and the attention half's mixers see what the
#   attention computed: sound 0.017 - 0.047 over 11 draws (0.021 on the
#   file's weights and batch; `hc_ffn` the higher), plain RoPE tables where
#   the configuration says YaRN 0.115 - 0.161 (`plain_rope`, which passes
#   `shared_grad`'s limit too: 0.027 - 0.029 against 0.018); limit 0.07,
#   1.5 times the sound runs' largest and 0.6 of
#   the control's smallest. ISSUE 57 asked for the worst of W, alpha and b
#   with the exit's: they are logged (`hc_grad_by_leaf`, a small leaf held
#   to the median norm of its kind) and NOT held to the limit, because they
#   are sums that cancel: a pre-norm sublayer's cotangent is orthogonal to
#   what it read, so `d pre_i = <du, X[i]>` is a small rest of streams that
#   are still nearly parallel, and alpha (3 numbers) and b (24) read 0.002 -
#   0.083 in sound runs, the exit's leaves 0.005 - 0.166, as much as under
#   either control.
# * `hc_colsum`: the worst `hc_colsum_err` of the step's first call (its
#   counter): the largest |column sum - 1| of H_res over the check batch's
#   tokens, layers and mixers. The columns are normalised LAST, so whatever
#   the rounds left undone this is `hc_eps` over a column's sum plus the
#   arithmetic's own rounding: 1.1e-6 - 1.4e-6 in every sound run; with the
#   maps (m, the sigmoids, exp, the rounds) in bfloat16 4.9e-3, bfloat16's
#   step at one (`bf16_maps`); limit 1e-4, eighty times the sound reading
#   and a fiftieth of the control's. It is the reading that holds the maps
#   to float32: `hc_grad` does NOT see that control (0.009 - 0.029 against
#   the sound 0.006 - 0.027), because bfloat16's rounding of a map is no
#   larger than the rounding of the bfloat16 streams it mixes; its gradient
#   norm is 0.3% low (inside `train`'s 0.5%) and `shared_grad` 0.013 (inside
#   0.018).
#
# The step returns no gradient; after its first call Adam's first moment is
# (1 - beta1) times it, exactly (`train_scopes._first_gradients`).
HC_RTOL = {"bfloat16": {"hc_grad": 0.07, "hc_colsum": 1e-4}}


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
    want, want_routed, want_moe_grads, want_hc_grads = _reference(
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
    moe_grads, hc_grads = _first_gradients(optimizer, opt_state)
    check = _compare_moe(
        check, w["dtype"],
        first_counters["routed"] / (batch // CHECK_SEQUENCES), want_routed,
        moe_grads, want_moe_grads)
    check = _compare_hc(check, w["dtype"], hc_grads, want_hc_grads,
                        float(first_counters["hc_colsum_err"].max()))
    del want_moe_grads, moe_grads
    log(event="check", **check,
        loss_main=float(first_counters["loss_main"]),
        loss_mtp=float(first_counters.get("loss_mtp", float("nan"))),
        **{k: first_counters[k].tolist() for k in (
            "hc_sinkhorn_err", "hc_colsum_err", "hc_res_offdiag")})
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

    # the window's counters: per expert layer the router's, per layer the
    # mixers'; means over the steps (the Sinkhorn error: the worst)
    rows = np.mean([c["rows_here"] for c in counters], axis=0)     # (L,)
    routed = np.stack([c["routed"] for c in counters])             # (n, L, E)
    lo = int(job.config["deployment_share"]["expert_offset"])
    held = routed[..., lo:lo + sizes.n_held]
    balance = float(np.mean(held.max(-1) / np.maximum(held.mean(-1), 1e-9)))
    rows_per_token = float(rows.sum()) / tokens_per_step
    sinkhorn_err = float(max(c["hc_sinkhorn_err"].max() for c in counters))
    offdiag = np.mean([c["hc_res_offdiag"] for c in counters], axis=0)
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
             hc_sinkhorn_err_max=sinkhorn_err,
             hc_res_offdiag_mean_by_layer=offdiag.tolist()),
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
        parts = mhc_scopes.scope_ns(devs[0], runs, names)
        per_step = lambda ns: {k: v / devs[0].steps / 1e6
                               for k, v in ns.items()}
        breakdown = {"device_ops": trace.top_ops(devs[0]),
                     "idle_gaps": trace.top_gaps(devs[0], spans),
                     "scopes_ms_per_step": per_step(parts),
                     "mhc_parts_ms_per_step": per_step(
                         mhc_scopes.mhc_parts_ns(devs[0], runs, names)),
                     "unattributed_ops": program_trace.top_unattributed(
                         devs[0], runs, names)}
    if job.rehearse:
        device.update(busy_s=None, window_s=None)

    import jax.numpy as jnp
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
        sinkhorn_err=sinkhorn_err,
        mhc_cost=mixers_step_cost(sizes, tokens_per_step // chips,
                                  jnp.dtype(w["dtype"]).itemsize))
    return Outcome(correct=correct, attempted=window.steps,
                   failed=finite.count(False), end_to_end=end_to_end,
                   measured=measured, device=device, breakdown=breakdown,
                   compared=compared(check, first10, last10,
                                     finite.count(False)))


def _hc_named(tree: dict) -> dict:
    """name -> the mixers' leaves of a parameter-shaped tree: `hc_attn` and
    `hc_ffn` of every stacked segment (layers leading) and the exit mixers
    (one layer)."""
    import jax
    out = {f"{seg}/{mixer}/{leaf}": a
           for seg, layers in tree.items() if isinstance(layers, dict)
           for mixer in ("hc_attn", "hc_ffn")
           for leaf, a in layers.get(mixer, {}).items()}
    exits = {"hc_exit": tree["hc_exit"]}
    if "mtp" in tree:
        exits["mtp/hc_exit"] = tree["mtp"]["hc_exit"]
    out.update({f"{name}/{leaf}": a[None] for name, mixer in exits.items()
                for leaf, a in mixer.items()})
    return jax.tree.map(lambda a: a.reshape(a.shape[0], -1), out)


def _reference(family, mesh, params, ids, tgt, pos):
    """`train_scopes._reference` (float32, matmul precision "highest", a
    copy of the parameters on one device: loss, gradient norm, the
    reference's `routed` counts and its sampled gradient leaves under `moe`)
    with the mixers' gradient leaves, whole, from the same pass."""
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
                 in _moe_named(grads).items()}, _hc_named(grads))

    one = SingleDeviceSharding(mesh.devices.flat[0])
    held = jax.device_put(params, one)
    with jax.default_matmul_precision("highest"):
        loss, norm, routed, leaves, mixers = jax.jit(detail)(
            held, *(jax.device_put(x, one) for x in (ids, tgt, pos)))
    return ([float(loss), float(norm)], np.asarray(routed),
            jax.device_get(leaves), jax.device_get(mixers))


def _first_gradients(optimizer, opt_state):
    """(the sampled gradient leaves under `moe`, the mixers' leaves whole)
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
    hc = {name: np.asarray(leaf) / (1.0 - beta1)
          for name, leaf in jax.jit(_hc_named)(opt_state.mu).items()}
    return moe, hc


def _compare_hc(check: dict, dtype: str, grads: dict, want_grads: dict,
                colsum_err: float) -> dict:
    """`train_scopes._compare_moe`'s record with `hc_grad` and `hc_colsum`
    added (HC_RTOL, above). Every mixer leaf's error is logged, a layer at a
    time; the layers' W leaves are held to the limit."""
    norms = {name: np.sqrt(np.square(want, dtype=np.float64).sum(-1))
             for name, want in want_grads.items()}            # (layers,)
    # a leaf of one to a few dozen numbers (alpha, b) has a gradient near
    # zero by chance in some draws, and an error relative to nothing
    # measures nothing: a leaf is held to the median norm of its kind over
    # all the mixers where its own is smaller
    floor = {kind: float(np.median(np.concatenate(
        [n for name, n in norms.items() if name.endswith("/" + kind)])))
        for kind in ("w", "alpha", "b")}
    by_leaf = {}
    for name, want in want_grads.items():
        diff = np.square(grads[name].astype(np.float64) - want).sum(-1)
        by_leaf[name] = (np.sqrt(diff) / np.maximum(
            norms[name], floor[name.rsplit("/", 1)[1]])).tolist()
    held = [v for name, v in by_leaf.items()
            if name.endswith("/w") and "hc_exit" not in name]
    # (numpy's max: a NaN anywhere is the reading, and is over any limit)
    err = {"hc_grad": float(np.max(np.concatenate(held))),
           "hc_colsum": colsum_err}
    rtol = HC_RTOL[dtype]
    ok = all(math.isfinite(v) and v <= rtol[k] for k, v in err.items())
    return {**check, "ok": bool(check["ok"] and ok),
            "rel_err": {**check["rel_err"], **err},
            "rtol": {**check["rtol"], **rtol},
            "hc_grad_by_leaf": by_leaf}
