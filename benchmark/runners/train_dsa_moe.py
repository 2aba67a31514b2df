"""The `train_dsa_moe` runner: the `train` runner's recipe (benchmark/
runners/train.py: its docstring is this runner's too, phase by phase) for
the dsa_moe family, whose every layer CHOOSES ITS KEYS, handing its
per-layer readers what `train_early_moe` hands its own, by this family's
names:

* **the step's `op_name` map** and the step's runs on chip 0, reduced to
  `measured.scopes`: device nanoseconds by the program's named scope and
  kernel (benchmark/lib/dsa_scopes.py: `gqa_attn`, `dsa_index`,
  `dsa_select`, `dsa_attend`, `dsa_index_loss`, `dsa_flash`, `moe_route`,
  `moe_experts`, `head_loss`, `optimizer`, `grad_norm`, and `flash`,
  `rest`, `unattributed`, `other_programs`);
* **the step's counters** (`with_counters=True`): per layer the pairs each
  routed expert got, the rows computed here, and what the selection
  counted (`ops/index_select.SUMS`). The window's means feed
  `moe.load_max_over_mean`, `moe.rows_here_per_token`,
  `train_step.active_mfu_pct`, `model.moe_experts_roofline` and
  `dsa.kept_share`;
* **`dsa_walk`**: the pairs the attention kernels' walks compute at the
  cell's shape beside the pairs the rows keep, from the program's block
  sizes, for `dsa.flash_computed_over_live`;
* **`dsa_select_overlap`**: the check's reading (below).

**The check.** A choice is discontinuous: in bfloat16 a few keys at a row's
margin change sides against a float32 reference, and an attention over
another set has other gradients, which says nothing about whether the step
is right. So the program's choice is read OUT (`model.make_probe`: the
kernels' own score and thresholds on the check batch, every layer, as a (t,
t) int8 a sequence) and the comparison has two halves: **the choice against
the reference's** (the score itself on the last 512 rows, and the share of
the program's pairs the reference chose too), and **everything else
against the reference run ON THE PROGRAM'S CHOICE** (loss, gradient norm,
the routers' counts and the sampled gradient leaves, the indexer's own a
layer at a time), where a sound step differs by rounding alone; once more
the loss against the reference on ITS OWN choice, under a limit read over
seeds. `DSA_RTOL` has each limit and its reason.

`measured` carries every field `train_early_moe`'s does but the window
layers' (this family has none), so `entry.*`, `device.*`,
`model.xla_ops_ms`, `model.gqa_attn_ms`, `train_step.step_ms_median`,
`model.moe_*` and `moe.*` read it unchanged, with `scopes` keyed by this
family's names and `active_flops_per_token` from
benchmark/lib/dsa_moe_counts.py (the MATHEMATICS: the kept pairs and the
indexer's triangle, not what a masked walk computes). `flops_per_token` is
None: `train_step.mfu_pct`, `kernels.flash_roofline` and
`kernels.gqa_flash_roofline` (one causal count for every call) do not list
this runner's cells; nor do `kernels.flash_ms` and
`kernels.flash_fwd_per_bwd`: the step runs none of the static-mask flash
kernels, and its own carry other names ON PURPOSE
(`kernels.dsa_flash_roofline` is theirs).

`memory_peak_bytes` is what the chip held at ONE time (`_held_at_once`: the
reference's phase holds the program's choice beside the weights, then the
reference's own temporaries; the window holds the state and the step's).

The helpers are `train`'s, `train_hybrid`'s and `train_swa_moe`'s own,
imported, not copied (the comparison and ITS TOLERANCES, the memory
readings, the log, the gradient samples' stride); the recipe itself is a
further copy: ROADMAP D14.
"""

from __future__ import annotations

import json
import math
import os
import tempfile
import time
from types import SimpleNamespace

import numpy as np

from benchmark.lib import dsa_scopes, peaks, program_trace, timing, trace
from benchmark.lib.dsa_moe_counts import (kept_pairs, train_flops_per_token,
                                        triangle_pairs)
from benchmark.lib.files import load_module
from benchmark.lib.job import Job, Outcome, data_seed, init_seed
from benchmark.runners.train import (WARMUP_STEPS, _compare, _mean,
                                     _memory, _no_times, compared, log)
from benchmark.runners.train_hybrid import GRAD_STRIDE

# What this runner's check holds beside `train`'s two scalars (whose
# tolerances stand: `train.RTOL`, against the reference run on the
# PROGRAM'S choice; loss 2.0e-6 - 8.6e-6 and gradient norm 2.9e-6 - 1.1e-4
# here). Each reading is of the step's own outputs on the check batch; each
# limit stands between the sound program's largest reading and the smallest
# of the control it is there for, the program with one thing wrong
# (benchmark/tools/dsa_control.py; my chip runs, PR 72: the sound program
# over six data seeds at the file's `init_seed` 0 and three seeds with the
# weights from the seed too, the controls at seeds 2147483659 and
# 3000000019; PERF.md section 2 has the table):
#
# * `index_score`: of the index score I on a sequence's last 512 rows, every
#   causal key, each layer apart, the relative L2 error of the program's
#   (the kernels' tile, bfloat16 operands, float32 sums) against the
#   reference's float32; the worst layer (the last: 0.0064 in the first
#   layer, whose input is the embedding's rows, to 0.0118 in the sixth, whose
#   input six bfloat16 layers made). The guard of the indexer's
#   projections, LayerNorm, RoPE over all of its head, the head weights'
#   scale and the ReLU's sum, before any choice is made of it. Sound 0.0108 -
#   0.0118; a choice that is another sequence's 1.009; limit 0.04.
# * `select_miss`: the share of the (row, key) pairs the program chose
#   that the float32 reference did NOT choose, the worst layer (1 less
#   `dsa.select_overlap`). Over 0 in a sound run (the margin: 0.0033 in
#   the first layer to 0.0059 in the sixth; 0.0039 at 2 x 8192). The guard of
#   the threshold search, the causal edge and the per-sequence selection.
#   Another sequence's choice 0.232; limit 0.03.
# * `select_count`: how far the NUMBER of pairs the program chose is from the
#   reference's, relative, the worst layer. Zero in a sound run, exactly
#   (both keep `min(t + 1, 2048)` a row whatever they score): a budget one
#   short, which chooses a subset of the right keys and misses none, reads
#   4.6e-4 here and nowhere else; limit 1e-4.
# * `tie_rows`: the share of rows whose threshold is TIED (keys that score
#   the same lie on both sides of the budget, and the index decided), the
#   program's own count (`dsa_tau_ties`) against the reference's, the worst
#   layer. Both are a row or two in ten thousand in float32 (the ReLU's exact
#   zeros): sound 1.2e-4 - 2.4e-4. A score rounded to bfloat16 makes buckets
#   of equal scores and ties 72% of the rows (0.716, 0.719), which moves the
#   choice by too little for `select_miss` to tell (0.0060 for 0.0059) and
#   reads nowhere else; limit 0.01.
# * `own_loss`: the step's loss against the reference's on ITS OWN choice,
#   relative: what the margin's keys are worth to the objective. Sound
#   2.1e-6 - 1.1e-5 (no more than against the reference on the program's
#   choice: a fresh model's heads are near uniform over 2048 keys); limit
#   1e-4, read over the seeds and held by no control.
# * `routed_moved`, `moe_grad`, `attn_grad`: `train_early_moe`'s readings of
#   the routers' counts (the mean over the layers), the held experts' three
#   matrices and the attention leaves, against the reference on the
#   program's choice. Sound 0.0014 - 0.0017 (limit 0.005, cell 10's),
#   0.059 - 0.085 (limit 0.105, cell 10's) and 0.0085 - 0.0096 at the rung
#   `auto` picks (0.0176 at `dots`, where q, k and v are kept in bfloat16 and
#   not re-made; limit 0.05). An indexer that reads the layer's input
#   itself, whose loss's gradient then runs on upstream, reads `attn_grad`
#   0.144 - 0.159 (and `moe_grad` 0.123 - 0.129, gradient norm 8e-4).
# * `index_grad`: of every indexer leaf (`wq`, `wk`, `w_proj`, the
#   LayerNorm's scale and bias), each layer apart, the relative L2 error of
#   the whole leaf; the worst leaf and layer. The only gradient these
#   leaves get is the KL's. Sound 0.011 - 0.015; another sequence's choice
#   0.637; limit 0.06.
#
# Every one of the seven control runs is not ok under these limits, by one
# limit and not by each: the bfloat16 score by `tie_rows` alone, the budget
# one short by `select_count` alone, the leak by `attn_grad` (and `moe_grad`),
# the shared choice by `index_score`, `select_miss`, `index_grad` and the
# loss.
#
# On every GRAD_STRIDE-th element of an expert's slice and of `wq` and `wo`;
# the other leaves whole. The step returns no gradient; after its first call
# Adam's first moment is (1 - beta1) times it, exactly.
ATTN_LEAVES = ("wq", "wk", "wv", "wo")
EXPERT_LEAVES = ("gate", "up", "down")
INDEX_LEAVES = ("index_wq", "index_wk", "index_w", "index_norm_scale",
                "index_norm_bias")
# read in bfloat16, the only compute dtype a cell of this runner states
DSA_RTOL = {"bfloat16": {"index_score": 0.04, "select_miss": 0.03,
                         "select_count": 1e-4, "tie_rows": 0.01,
                         "own_loss": 1e-4, "routed_moved": 0.005,
                         "moe_grad": 0.105, "attn_grad": 0.05,
                         "index_grad": 0.06}}


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
    # the check batch is the TIMED batch's size (one sequence of 16,384 in
    # the cell): what the float32 reference holds beside the weights with
    # its scores in blocks, and nothing is repeated to fill the step's batch
    ids, tgt, check_pos = batches(w["data"], sizes.vocab, batch, seqlen,
                                  data_seed(job) + 1).next()
    # what every layer of the program chose on the check batch, from the
    # kernels the step runs; the reference then attends over THAT choice
    probe_rows, chosen = model.make_probe(mesh)(params, feed(ids),
                                                feed(check_pos))
    mark("probe", chosen)
    memory_probe = _memory(devices[:chips])
    probe_rows = np.asarray(probe_rows)
    # (a layer's choice an array: the reference takes them one by one)
    chosen = [chosen[i] for i in range(chosen.shape[0])]
    want = _reference(family, mesh, params, ids, tgt, check_pos, chosen)
    want["probe_rows"] = probe_rows
    del chosen
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
    check = _compare([float(x) for x in first[:2]], want["scalars"],
                     w["dtype"])
    # (this runner's limits are read at the published widths in bfloat16;
    # the rehearsal runs its tiny shape in float32, where `train`'s own
    # two limits are tight: there the further readings are logged and not
    # held)
    check = _compare_dsa(
        check, w["dtype"], float(first[0]), first_counters,
        _first_gradients(optimizer, opt_state), want,
        held=not job.rehearse)
    overlap = 1.0 - check["rel_err"]["select_miss"]
    del want
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
    peak_bytes = memory and _held_at_once(memory_probe, memory_reference,
                                          memory)

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
             memory_after_probe=memory_probe,
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
        parts = dsa_scopes.scope_ns(devs[0], runs, names)
        breakdown = {"device_ops": trace.top_ops(devs[0]),
                     "idle_gaps": trace.top_gaps(devs[0], spans),
                     "scopes_ms_per_step": {
                         k: v / devs[0].steps / 1e6 for k, v in parts.items()},
                     "unattributed_ops": program_trace.top_unattributed(
                         devs[0], runs, names)}
    if job.rehearse:
        device.update(busy_s=None, window_s=None)

    # the step's own count of the pairs its rows kept, over the window
    kept_share = float(np.sum([c["dsa_kept"] for c in counters])
                       / np.sum([c["dsa_causal"] for c in counters]))
    log(event="selection", kept_share=kept_share,
        index_kl_by_layer=np.mean(
            [c["dsa_index_kl"] / c["dsa_rows"] for c in counters],
            axis=0).tolist(),
        index_entropy=float(np.mean(
            [c["dsa_index_entropy"] / c["dsa_rows"] for c in counters])),
        tau_ties=float(np.mean(
            [c["dsa_tau_ties"] / c["dsa_rows"] for c in counters])))

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
        dsa_kept_share=kept_share, dsa_select_overlap=overlap,
        dsa_walk=_walk_pairs(seqlen, sizes.index_topk))
    return Outcome(correct=correct, attempted=window.steps,
                   failed=finite.count(False), end_to_end=end_to_end,
                   measured=measured, device=device, breakdown=breakdown,
                   compared=compared(check, first10, last10,
                                     finite.count(False)))


def _held_at_once(after_probe: dict, after_reference: dict,
                  after_window: dict) -> int:
    """The most the chip held at ONE time (`train_swa_moe._held_at_once`'s
    reasoning, with this runner's phases): while the reference ran, what the
    probe left on the chip (the weights and every layer's choice, 1.5 GiB)
    beside the reference's reserved temporaries; when the window ends,
    buffers and reservation; and the buffers' own peak. The reference
    phase's two PEAKS summed would be two moments, and more than the chip
    has."""
    return max(after_probe["bytes_in_use"]
               + after_reference["peak_bytes_reserved"],
               after_window["bytes_in_use"] + after_window["bytes_reserved"],
               after_window["peak_bytes_in_use"])


def _walk_pairs(seqlen: int, topk: int) -> "dict | None":
    """Pairs one attention walk computes a head and sequence (every tile
    that holds a key at or before one of its rows, whole) beside the pairs
    the rows keep, from the program's block sizes."""
    from distributed_pytorch_from_scratch_tpu.ops.index_select import (
        flash_blocks)
    bq, bk = flash_blocks(seqlen)
    tiles = sum((i * bq + bq - 1) // bk + 1 for i in range(seqlen // bq))
    return {"computed": tiles * bq * bk, "kept": kept_pairs(seqlen, topk),
            "triangle": triangle_pairs(seqlen), "blocks": [bq, bk]}


def _sampled(tree: dict) -> dict:
    """name -> a gradient leaf of every layer as one (layers, groups,
    elements) array, sampled. An attention or indexer leaf is one group; an
    expert matrix a group an expert held (every GRAD_STRIDE-th element of
    its slice); the router a group a routed expert (its column, whole)."""
    import jax.numpy as jnp
    layers = tree["layers"]

    def thin(flat):
        # every GRAD_STRIDE-th element of a large slice, a small one whole
        return (flat[..., ::GRAD_STRIDE] if flat.shape[-1] > 1 << 20
                else flat)

    whole = lambda leaf: thin(leaf.reshape(leaf.shape[0], 1, -1))
    attn, index = layers["attn"], layers["attn"]["indexer"]
    out = {name: whole(attn[name]) for name in ATTN_LEAVES}
    out.update({name: thin(layers["moe"][name].reshape(
        *layers["moe"][name].shape[:2], -1)) for name in EXPERT_LEAVES})
    out["router"] = jnp.swapaxes(layers["moe"]["router"], 1, 2)
    out.update(index_wq=whole(index["wq"]), index_wk=whole(index["wk"]),
               index_w=whole(index["w_proj"]),
               index_norm_scale=whole(index["k_norm"]["scale"]),
               index_norm_bias=whole(index["k_norm"]["bias"]))
    return out


def _reference(family, mesh, params, ids, tgt, pos, chosen) -> dict:
    """`train._reference` (float32, matmul precision "highest", a copy of
    the parameters on one device) run ON THE CHOICE `chosen` (layers, b, t,
    t), with more from the same pass: the reference's `routed` counts, how
    much of `chosen` is its own choice, its index scores of the last rows,
    and its gradients of the sampled leaves, which leave the device at
    once; then its loss on ITS OWN choice, forward only."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    def detail(p, i, t, q, given):
        (loss, parts), grads = jax.value_and_grad(
            family.reference_parts, has_aux=True)(p, i, t, q, given)
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                            for g in jax.tree.leaves(grads)))
        return loss, norm, parts, _sampled(grads)

    one = SingleDeviceSharding(mesh.devices.flat[0])
    held = jax.device_put(params, one)
    batch = [jax.device_put(x, one) for x in (ids, tgt, pos)]
    with jax.default_matmul_precision("highest"):
        loss, norm, parts, leaves = jax.jit(detail)(
            held, *batch, jax.device_put(chosen, one))
        own = jax.jit(lambda p, i, t, q: family.reference_parts(
            p, i, t, q)[0])(held, *batch)
    parts = jax.device_get(parts)
    return {"scalars": [float(loss), float(norm)], "own_loss": float(own),
            "routed": np.asarray(parts["routed"]), "pairs": parts["pairs"],
            "score_rows": np.asarray(parts["score_rows"]),
            "index_kl": parts["index_kl"], "ce": float(parts["ce"]),
            "grads": jax.device_get(leaves)}


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


def _compare_dsa(check: dict, dtype: str, loss: float, counters: dict,
                 grads, want: dict, held: bool = True) -> dict:
    """`train._compare`'s record with this runner's readings added
    (DSA_RTOL, above); with `held` off they are recorded and decide
    nothing."""
    routed = np.asarray(counters["routed"])
    moved = (np.abs(routed - want["routed"]).sum(-1) / 2
             / want["routed"].sum(-1))                     # a layer
    by_leaf = {}
    for name, ref in want["grads"].items():
        diff = np.square(grads[name].astype(np.float64) - ref).sum(-1)
        norm = np.square(ref, dtype=np.float64).sum(-1)    # (layers, groups)
        # an expert no row reached has no gradient in either; the median
        # over a leaf's groups (one group: the leaf itself)
        by_leaf[name] = np.median(
            np.sqrt(diff / np.where(norm > 0, norm, 1.0)), axis=-1).tolist()
    # numpy's max: a NaN anywhere is the reading
    worst = lambda names: float(np.max([by_leaf[n] for n in names]))
    # the index score of the last rows, the causal keys (the rest is what
    # a kernel's tile and an einsum make of pairs nobody reads)
    got_rows, ref_rows = want["probe_rows"], want["score_rows"]
    t = ref_rows.shape[-1]
    seen = (np.arange(t)[None, :]
            <= (t - ref_rows.shape[-2] + np.arange(ref_rows.shape[-2]))[:, None])
    score = [float(np.sqrt(np.square((g - r) * seen, dtype=np.float64).sum()
                           / np.square(r * seen, dtype=np.float64).sum()))
             for g, r in zip(got_rows, ref_rows)]
    own, given, both, tied = np.asarray(want["pairs"], np.float64).T
    overlap = both / np.maximum(given, 1.0)
    rows = np.asarray(counters["dsa_rows"], np.float64)
    ties = np.abs(np.asarray(counters["dsa_tau_ties"], np.float64) - tied)
    err = {"index_score": float(np.max(score)),
           "select_miss": float(1.0 - overlap.min()),
           "select_count": float(np.max(np.abs(given - own)
                                        / np.maximum(own, 1.0))),
           "tie_rows": float(np.max(ties / rows)),
           "own_loss": abs(loss - want["own_loss"]) / abs(want["own_loss"]),
           "routed_moved": float(moved.mean()),
           "moe_grad": worst(EXPERT_LEAVES),
           "attn_grad": worst(ATTN_LEAVES),
           "index_grad": worst(INDEX_LEAVES)}
    rtol = DSA_RTOL[dtype] if held else {}
    ok = all(math.isfinite(err[k]) and err[k] <= limit
             for k, limit in rtol.items())
    return {**check, "ok": bool(check["ok"] and ok),
            "rel_err": {**check["rel_err"], **err},
            "rtol": {**check["rtol"], **rtol},
            "routed_moved_by_layer": moved.tolist(),
            "index_score_by_layer": score,
            "select_overlap_by_layer": overlap.tolist(),
            "pairs_own_given_both_tied": np.asarray(want["pairs"]).tolist(),
            "tied_rows_program": np.asarray(
                counters["dsa_tau_ties"]).tolist(),
            "router_grad": worst(["router"]),
            "reference_ce_and_index_kl": [want["ce"],
                                          np.asarray(want["index_kl"]).tolist()],
            "grad_by_leaf": by_leaf}

