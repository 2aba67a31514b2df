"""The `train_ssm_dense` runner: the `train` runner's recipe (benchmark/
runners/train.py: its docstring is this runner's too, phase by phase) for
the ssm_dense family, a DENSE hybrid whose every layer is a Mamba-2 mixer or
an attention with no positions and then a SwiGLU, under four published
scalars and a tied head. What differs:

* **the step is built `with_counters`** and its counters are the family's:
  the window's worst `ssm_decay_min` (`ssm.decay_min`), by layer in the
  `window` log line, and the window's mean `resid_rms_last`
  (`resid.rms_last`);
* **the scope split** is `benchmark/lib/ssm_dense_scopes.py`'s (`mamba`,
  `gqa_attn`, `dense_ffn`, `head_loss`, `optimizer`, `grad_norm`, and
  `flash`, `rest`, `unattributed`, `other_programs`) in `measured.scopes`,
  and the mixer's time by inner scope in `measured.mamba_parts`
  (`benchmark/lib/ssm_scopes.mamba_parts_ns`: `mamba/ssd` is
  `model.ssd_ms`'s, the others this family's three readers'; the
  breakdown's `mamba_parts_ms_per_step`);
* **the counts** are `benchmark/lib/ssm_dense_counts.py`'s:
  `measured.flops_per_token` is 6 x the matmul parameters (the tied table
  once), attention in ONE layer of ten and the recurrence's own products at
  chunk 256 with one group (`train_step.mfu_pct` reads it), and
  `measured.ssd_cost` what a layer's recurrence must compute and move
  (`model.ssd_roofline`);
* **the check batch is ONE sequence** (the cell's batch is 1: the state
  fills the chip), where `train`'s is two;
* **the check holds three readings more** (below);
* `memory_peak_bytes` is what the chip held at ONE time
  (`train_swa_moe._held_at_once`).

`measured` carries every field `train`'s does, so the readers written for
it work here unchanged (`sizes` has the names they read: `n_head`,
`n_kv_head`, `head_dim`, `n_mamba_layer`).

The helpers are `train`'s and `train_swa_moe`'s own, imported, not copied
(the comparison of the two scalars and ITS TOLERANCES, the memory readings,
the log); the recipe itself is the twelfth copy: ROADMAP D14.
"""

from __future__ import annotations

import json
import math
import os
import tempfile
import time
from types import SimpleNamespace

import numpy as np

from benchmark.lib import (peaks, program_trace, ssm_dense_scopes,
                           ssm_scopes, timing, trace)
from benchmark.lib.files import load_module
from benchmark.lib.job import Job, Outcome, data_seed, init_seed
from benchmark.lib.ssm_dense_counts import ssd_cost, train_flops_per_token
from benchmark.runners.train import (WARMUP_STEPS, _compare, _mean, _memory,
                                     _no_times, compared, log)
from benchmark.runners.train_swa_moe import _held_at_once

CHECK_SEQUENCES = 1
GRAD_STRIDE = 7     # divides no size of a leaf: every row and column is met

# What this runner's check holds beside `train`'s two scalars (`RTOL`, whose
# limits stand: this cell's readings are in PERF.md section 2). The loss and
# the gradient norm of a freshly initialised model hardly see HOW a
# state-space layer remembers, or what the softmax is scaled by: the norm is
# the table's and the SwiGLUs' before it is the mixers'. So three readings
# more of the step's own outputs on the check batch, against `jax.grad` of
# the reference (float32, "highest", the recurrence token by token);
# benchmark/tools/ssm_dense_control.py reads each for a wrong program, and
# PERF.md section 2 has the table and the seeds.
#
# * `ssm_grad`: over the Mamba leaves (`w_in`, the convolution and its
#   bias, `A_log`, `D`, `dt_bias`, the gated norm's weight, `w_out`; each of
#   the nine Mamba layers apart) the relative L2 error of the step's
#   gradient; the worst leaf and layer. The guard of the chunked recurrence
#   at chunk 256 and one group, of its backward and of the gate's order: the
#   gradients of `A_log` and `dt_bias` exist only through the decays, a head
#   at a time.
# * `attn_grad`: the same over the attention layer's four matrices. `wq`'s
#   and `wk`'s gradients carry the softmax's scale twice over (the scores'
#   and the probabilities' slope): the guard of `attention_multiplier`.
# * `sampled_grads`: every GRAD_STRIDE-th entry of every OTHER leaf (the
#   SwiGLUs' matrices and the norms of every layer, the tied table, the
#   final norm), relative L2 over all of them: the guard of the three
#   scalars outside the mixers.
#
# On every GRAD_STRIDE-th element of a large leaf (the input projection is
# 17M numbers a layer); the small leaves whole. The step returns no
# gradient; after its first call Adam's first moment is (1 - beta1) times
# it, exactly.
#
# Limits (bfloat16, the only compute dtype a cell of this runner states; my
# chip runs, PR 68, calls 98 - 103: eighteen sound runs on eighteen seeds of
# weights and data, one run a control on seed 2147483801; PERF.md section 2
# has every reading), each between the sound runs' largest reading and the
# smallest of the wrong programs the reading is there to refuse:
#   ssm_grad       sound 0.018 - 0.034; bf16_state 0.755, norm_before_gate
#                  0.897 (softmax_default 0.031: not its)          -> 0.08
#   attn_grad      sound 0.0109 - 0.0128; softmax_default 7.39,
#                  norm_before_gate 0.58, embed_unscaled 4.7       -> 0.04
#   sampled_grads  sound 0.00240 - 0.00250; norm_before_gate 0.099,
#                  residual_one 0.97, embed_unscaled 1.07,
#                  logits_unscaled 7.0                             -> 0.01
# `train`'s two stand with room: loss sound 3.3e-6 - 1.8e-4 under 5e-4
# (logits_unscaled 7.0, embed_unscaled 0.60, residual_one 0.41,
# norm_before_gate 0.044), gradient norm sound 7.1e-4 - 8.5e-4 under 5e-3
# (norm_before_gate 0.25 and up). bf16_state and softmax_default read as
# the sound program on BOTH of those: each is refused by one leaf reading
# alone, which is what the leaf readings are for.
SSM_LEAVES = ("w_in", "conv", "conv_bias", "A_log", "D", "dt_bias", "norm",
              "w_out")
ATTN_LEAVES = ("wq", "wk", "wv", "wo")
GRAD_RTOL = {"bfloat16": {"ssm_grad": 0.08, "attn_grad": 0.04,
                          "sampled_grads": 0.01},
             # (the rehearsal's dtype: the two texts agree to rounding)
             "float32": {"ssm_grad": 1e-3, "attn_grad": 1e-3,
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
    check = _compare_grads(check, w["dtype"],
                           _first_gradients(optimizer, opt_state), want_grads)
    del want_grads
    log(event="check", **check, parameters=n_params,
        loss_main=float(first_counters["loss_main"]),
        resid_rms_last=float(first_counters["resid_rms_last"]),
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
    # worst over the steps), and the mean RMS of what enters the final norm
    decay_min = np.min([c["ssm_decay_min"] for c in counters], axis=0)
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
             ssm_decay_min_by_layer=decay_min.tolist(),
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
    breakdown = parts = mamba_parts = None
    if job.trace and devs:
        device["busy_s"] = sum(d.busy_ns() for d in devs) / len(devs) / 1e9
        device["window_s"] = sum(d.window_ns for d in devs) / len(devs) / 1e9
        spans = trace.host_spans(captured, "bench.")
        names = program_trace.op_names(step_hlo)
        runs = program_trace.step_runs(captured, devs[0])
        parts = ssm_dense_scopes.scope_ns(devs[0], runs, names)
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
        flops_per_token=train_flops_per_token(sizes, seqlen),
        peak=peak, peak_bytes=peak_bytes, devices=devs,
        # what this runner adds
        scopes=parts, mamba_parts=mamba_parts,
        ssm_decay_min=float(decay_min.min()), resid_rms_last=resid_rms,
        ssd_cost=ssd_cost(batch // mesh_sizes.get("dp", 1), seqlen, sizes,
                          itemsize))
    return Outcome(correct=correct, attempted=window.steps,
                   failed=finite.count(False), end_to_end=end_to_end,
                   measured=measured, device=device, breakdown=breakdown,
                   compared=compared(check, first10, last10,
                                     finite.count(False)))


def _by_layer(tree: dict, kind: str) -> dict:
    """key -> the subtree of every key that holds layers whose mixer is
    `kind` ("mamba", "attn") with its layers leading, (layers, ...): a
    block's (periods, layers a period, ...) flattened in the order the
    layers run."""
    import jax
    flat = lambda a: a.reshape(a.shape[0] * a.shape[1], *a.shape[2:])
    return {key: jax.tree.map(flat, tree[key]) for key in sorted(tree)
            if key.startswith(f"{kind}_layers_")}


def _sampled(leaf):
    """A stacked leaf as (layers, entries): every GRAD_STRIDE-th entry of a
    large one, a small one whole."""
    flat = leaf.reshape(leaf.shape[0], -1)
    return flat[:, ::GRAD_STRIDE] if flat.shape[1] > 1 << 20 else flat


def _grads_named(tree: dict) -> dict:
    """name -> (rows, entries) of every leaf of a gradient tree (or of
    Adam's first moment, the same tree), sampled: a Mamba leaf under
    `ssm/<key>/<leaf>` and an attention matrix under `attn/<key>/<leaf>`, a
    row a layer; every other leaf (the SwiGLUs', the norms', the table's)
    under `rest/...`."""
    import jax
    out = {}
    for key, layers in _by_layer(tree, "mamba").items():
        for name in SSM_LEAVES:
            out[f"ssm/{key}/{name}"] = _sampled(layers["mamba"][name])
    for key, layers in _by_layer(tree, "attn").items():
        for name in ATTN_LEAVES:
            out[f"attn/{key}/{name}"] = _sampled(layers[name]["weight"])
    for kind in ("mamba", "attn"):
        for key, layers in _by_layer(tree, kind).items():
            rest = {k: v for k, v in layers.items()
                    if k != "mamba" and k not in ATTN_LEAVES}
            for path, leaf in jax.tree_util.tree_leaves_with_path(rest):
                out["/".join(["rest", key] + [k.key for k in path])] = (
                    _sampled(leaf))
    out["rest/embedding"] = _sampled(tree["embedding"]["weight"][None])
    out["rest/norm"] = tree["norm"]["scale"][None]
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


def _rel_l2(got, want) -> np.ndarray:
    """Relative L2 error a row."""
    diff = np.square(got.astype(np.float64) - want).sum(-1)
    norm = np.square(want, dtype=np.float64).sum(-1)
    return np.sqrt(diff / np.where(norm > 0, norm, 1.0))


def _compare_grads(check: dict, dtype: str, grads: dict,
                   want_grads: dict) -> dict:
    """`train._compare`'s record with this runner's three readings added
    (GRAD_RTOL, above)."""
    by_leaf = {name: _rel_l2(grads[name], want).tolist()
               for name, want in want_grads.items()}
    # numpy's max: a NaN anywhere is the reading, and is over any limit
    worst = lambda prefix: float(np.max(np.concatenate(
        [np.asarray(v) for name, v in by_leaf.items()
         if name.startswith(prefix)])))
    rest = [name for name in want_grads if name.startswith("rest/")]
    err = {
        "ssm_grad": worst("ssm/"), "attn_grad": worst("attn/"),
        "sampled_grads": float(_rel_l2(
            np.concatenate([grads[n].ravel() for n in rest]),
            np.concatenate([want_grads[n].ravel() for n in rest]))),
    }
    rtol = GRAD_RTOL[dtype]
    ok = all(math.isfinite(v) and v <= rtol[k] for k, v in err.items())
    return {**check, "ok": bool(check["ok"] and ok),
            "rel_err": {**check["rel_err"], **err},
            "rtol": {**check["rtol"], **rtol},
            "grad_by_leaf": {name: v for name, v in by_leaf.items()
                             if not name.startswith("rest/")}}
