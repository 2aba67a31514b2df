"""The `train_program` runner: `gpt2-medium.train-b12-t1024`'s job through the
program's own entry point. One process calls `train()`
(`distributed_pytorch_from_scratch_tpu/train.py`, what `python -m
distributed_pytorch_from_scratch_tpu.train` runs) with the argument list the
README's command would pass, lets it run until the window has closed, and
reads every number from what the program wrote: the returned record,
`logs/metrics.jsonl`, `logs/trace.jsonl` and, with `--trace 1`, the capture
its own `--profile_steps` made under `logs/profile`.

This file holds no loop, no step, no set-up and no clock of its own round
`train()`. If it needs one, the program is missing a span or a stamp.

Before `train()`: the token file (`pre_tokenize`'s schema), drawn from
`--seed` with `benchmark/data/zipf.py`'s draw and cut into documents whose
lengths are log-uniform between the workload's `documents.min` and `.max`,
`data.steps` steps' worth, so that no epoch ends inside the window. It is
written into a fresh scratch directory and is part of `setup_s`.

The window is the program's. `train()` syncs once a log interval and stamps
that moment on the interval's `train/ce_loss` record. The window opens at
the stamp of step `2 x log_interval` (the compile, the first interval's
first-time programs and the remat selection are before it) or, in the
`--trace 1` run, of the first interval that begins after the program's
capture has stopped; it closes at the first stamp at least `--seconds`
later. `train()` is told to stop there through its `stop` argument, which it
polls once a step: the callable reads the stamps the program has written so
far, and at its first poll refuses a backend that is not a TPU.

End-to-end metrics: `tokens_per_s_per_chip` (batch x seqlen x the steps
between the two stamps / the seconds between them) and `setup_s` (process
start to the opening stamp). No `step_ms_p90`: the program syncs once an
interval, so a per-step completion time does not exist in it.

`correct`: the run's first loss and first gradient norm (the record's
`first_loss`, `first_grad_norm`) against the family's plain float32
reference on the same weights (`model.init` at `--random_seed`, the model
built the family's way) and the same first batch (the program's loader on
the same file), under the `train` runner's limits; every interval's loss
finite; the window's last interval's mean loss under the run's first; and
the parameter count of the model `train()` built equal to the family's
sizes'. The reference runs after `train()` has returned and freed the chip,
outside `setup_s` and the window.
"""

from __future__ import annotations

import contextlib
import inspect
import json
import math
import os
import shutil
import sys
import tempfile
from types import SimpleNamespace

import numpy as np

from benchmark.lib import flops, peaks, timing, trace, train_spans
from benchmark.lib.files import load_module
from benchmark.lib.job import Job, Outcome, data_seed, init_seed
from benchmark.lib.program_trace import jsonl_events
from benchmark.runners.train import (
    _compare, _memory, _peak_bytes, _reference, log)
from benchmark.runners.train_ckpt import _by_name, _quiet

# `ProfilerTrace` starts the program's capture at the run's step 3
CAPTURE_FROM = 3
SPECIAL_IDS = {"<BOS>": 0, "<EOS>": 1, "<UNK>": 2}


def write_tokens(path: str, spec: dict, vocab: int, tokens_a_step: int,
                 seed: int) -> dict:
    """The run's token file. Ids are `zipf.TokenBatches`' (one row of all
    of them), document lengths log-uniform; the documents, framed with a
    BOS and an EOS each as the packed loader frames them, fill `steps`
    steps and one token over."""
    need = int(spec["steps"]) * tokens_a_step + 1
    lo, hi = (int(spec["documents"][k]) for k in ("min", "max"))
    rng = np.random.default_rng(seed)
    lengths = []
    while sum(lengths) + 2 * len(lengths) < need:
        draw = np.exp(rng.uniform(math.log(lo), math.log(hi), 1024))
        lengths.extend(int(n) for n in draw)
    framed = np.cumsum(np.asarray(lengths) + 2)
    lengths = lengths[:int(np.searchsorted(framed, need)) + 1]
    drawn = load_module("data", spec["kind"]).TokenBatches(
        spec, vocab, 1, sum(lengths) - 1, seed)
    inputs, targets, _ = drawn.next()    # one row, shifted by one
    ids = np.append(inputs[0], targets[0, -1])
    ends = np.cumsum(lengths)
    docs = [ids[a:b].tolist() for a, b in zip(ends - lengths, ends)]
    with open(path, "w") as f:
        # (`dumps`, the C encoder: `dump` walks 4.9M ids in Python)
        f.write(json.dumps({"train": docs, "validation": docs[:1],
                            "vocab_size": vocab,
                            "special_ids": SPECIAL_IDS}))
    return {"documents": len(docs), "tokens": int(ends[-1]),
            "bytes": os.path.getsize(path)}


def arguments(job: Job, sizes, tokens: str, save_dir: str) -> list:
    """What the README's command would pass for this cell: the
    configuration's sizes as the dimension flags, the workload's job, and
    every other knob left at `train()`'s default by not passing it."""
    w = job.workload
    if w["dtype"] != "bfloat16":
        raise SystemExit(f"benchmark: {job.name}: --bf16 or nothing")
    args = ["--data_path", tokens, "--save_dir", save_dir,
            "--family", job.config["family"],
            "--attn_dim", sizes.d_model, "--ffn_dim", sizes.d_ff,
            "--num_heads", sizes.n_head, "--num_layers", sizes.n_layer,
            "--maxlen", w["seqlen"], "--bf16", "--batch_size", w["batch"],
            "--data_mode", "packed", "--remat", "auto",
            "--steps_per_dispatch", 1,
            "--dp_size", w["mesh"]["dp"], "--tp_size", w["mesh"]["tp"],
            "--log_interval", w["log_interval"],
            # past the end of any run: no save inside it
            "--save_interval", 10 ** 9,
            "--random_seed", init_seed(job)]
    args += (["--profile_steps", w["trace_steps"]] if job.trace
             else ["--no_trace"])
    return [str(a) for a in args]


class Stamps:
    """`train()`'s `stop`: reads the interval stamps the program has written
    to `metrics.jsonl` so far (`train/ce_loss`: `step`, `ts`), once an
    interval, and says stop at the first stamp `seconds` past the opening
    one. Holds the program's compile counters as they stood when the window
    opened (`entry.*` read them)."""

    def __init__(self, path: str, interval: int, open_step: int,
                 seconds: float, rehearse: bool, stats):
        self.path, self.interval, self.open_step = path, interval, open_step
        self.seconds, self.rehearse, self.stats = seconds, rehearse, stats
        self.by_step: dict = {}
        self.close_step = None
        self.cache_setup = None
        self._read_to = 0

    def read(self) -> None:
        with open(self.path, "rb") as f:
            f.seek(self._read_to)
            lines = f.read().splitlines(True)
        for line in lines:
            if not line.endswith(b"\n"):
                break       # a line still being written: next time
            self._read_to += len(line)
            record = json.loads(line)
            if record.get("tag") == "train/ce_loss":
                self.by_step[record["step"]] = record["ts"]

    def __call__(self, step: int) -> bool:
        if step == 0 and not self.rehearse:
            import jax
            platform = jax.devices()[0].platform
            if platform != "tpu":
                raise SystemExit(
                    f"benchmark: backend is {platform!r}, not a TPU; "
                    f"nothing is measured off the chip")
        if step == 0 or step % self.interval:
            return False
        self.read()
        if step == self.open_step:
            self.cache_setup = dict(self.stats())
        opened = self.by_step.get(self.open_step)
        if opened is None or self.by_step[step] - opened < self.seconds:
            return False
        self.close_step = step
        return True


def interval_losses(records: list) -> dict:
    """{step: the mean loss of the interval that ended there}, from the
    running mean the program logs (`train/ce_loss`: the mean since step 0)."""
    out, before, at = {}, 0.0, 0
    for r in records:
        if r.get("tag") == "train/ce_loss":
            total = r["value"] * r["step"]
            out[r["step"]] = (total - before) / (r["step"] - at)
            before, at = total, r["step"]
    return out


def run(job: Job) -> Outcome:
    from distributed_pytorch_from_scratch_tpu import train as program
    if "stop" not in inspect.signature(program.train).parameters:
        raise SystemExit(
            "benchmark: this program's train() takes no `stop`: its caller "
            "cannot end it at a stamp, so the cell cannot run on it")
    from distributed_pytorch_from_scratch_tpu.runtime.compile_cache import (
        compile_cache_stats, enable_compile_cache)

    w = job.workload
    held_to = os.environ.get("JAX_PLATFORMS")
    if not job.rehearse and held_to and "tpu" not in held_to:
        # the check that counts is `Stamps`' at the first poll, with the
        # backend up; this one spares a CPU the full-size weights
        raise SystemExit(f"benchmark: backend is {held_to!r}, not a TPU; "
                         f"nothing is measured off the chip")
    chips, interval = int(w["chips"]), int(w["log_interval"])
    batch, seqlen = int(w["batch"]), int(w["seqlen"])
    mesh_sizes = dict(w["mesh"])
    if math.prod(mesh_sizes.values()) != chips:
        raise SystemExit(f"benchmark: mesh {mesh_sizes} is not {chips} chips")
    family = job.family.build(job.config, mesh_sizes, w["dtype"])
    sizes = family.sizes
    cache_dir = enable_compile_cache()      # as the program's `main()` does

    scratch = tempfile.mkdtemp(prefix="bench-train-program-")
    tokens = os.path.join(scratch, "tokens.json")
    save_dir = os.path.join(scratch, "run")
    logs = os.path.join(save_dir, "logs")
    try:
        wrote = write_tokens(tokens, w["data"], sizes.vocab, batch * seqlen,
                             data_seed(job))
        argv = arguments(job, sizes, tokens, save_dir)
        # the first interval whose steps all follow the capture's stop
        capture_to = CAPTURE_FROM + int(w["trace_steps"])
        open_step = (interval * (capture_to // interval + 1) if job.trace
                     else 2 * interval)
        stamps = Stamps(os.path.join(logs, "metrics.jsonl"), interval,
                        open_step, job.seconds, job.rehearse,
                        compile_cache_stats)
        # the program's lines for people go where this command's go: its
        # standard output holds JSON lines and nothing else
        with contextlib.redirect_stdout(sys.stderr):
            record = program.train(program.get_train_args(argv), stop=stamps)
        close_step = stamps.close_step
        if close_step is None:
            raise SystemExit(
                f"benchmark: {job.name}: train() ended at step "
                f"{record['steps']} before the window closed")

        import jax
        devices = jax.devices()[:chips]
        memory = _memory(devices)
        peak_bytes = memory and _peak_bytes(memory)
        with open(os.path.join(logs, "metrics.jsonl")) as f:
            records = [json.loads(line) for line in f]
        timeline = ([ev for ev in jsonl_events(
            os.path.join(logs, "trace.jsonl")) if ev["ph"] == "X"]
            if job.trace else None)
        captured = None
        if job.trace:
            captured = trace.load_xplane(trace.find_xplane(
                os.path.join(logs, "profile")))
            if job.dump_dir:
                os.makedirs(job.dump_dir, exist_ok=True)
                with open(os.path.join(job.dump_dir,
                                       job.name + ".trace.json"), "w") as f:
                    json.dump(trace.to_plain(captured), f)
                shutil.copy(os.path.join(logs, "trace.jsonl"), os.path.join(
                    job.dump_dir, job.name + ".timeline.jsonl"))
        check = _check(job, family, record, program, argv)
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
    log(event="check", **check)

    opened, closed = stamps.by_step[open_step], stamps.by_step[close_step]
    seconds, steps = closed - opened, close_step - open_step
    tokens_per_s = steps * batch * seqlen / seconds
    setup_s = opened - job.t_process_start
    losses = interval_losses(records)
    finite = [math.isfinite(x) for x in losses.values()]
    first, last = losses[interval], losses[close_step]
    built = sum(record["param_bytes_by_device"].values()) // 4
    correct = bool(check["ok"] and all(finite) and last < first
                   and built == flops.param_count(sizes))
    compared = {**{k: [v, check["rtol"][k]]
                   for k, v in check["rel_err"].items()},
                "losses_not_finite": [finite.count(False), 0],
                "loss_last_interval": [last, first],
                "params_built": [built, flops.param_count(sizes)]}
    end_to_end = {"tokens_per_s_per_chip": tokens_per_s / chips,
                  "setup_s": setup_s}

    # the readers that are there take the window's length from a
    # `timing.Window`: this one's stamps are the program's, one an interval
    window = timing.Window(
        [stamps.by_step[s] - opened
         for s in range(open_step, close_step + 1, interval)], [])
    measured = SimpleNamespace(
        workload=w, sizes=sizes, mesh=mesh_sizes, chips=chips, window=window,
        tokens_per_s=tokens_per_s, setup_s=setup_s,
        compile_s=record["compile_s"], cache_setup=stamps.cache_setup,
        cache_window=record["compile_cache"],
        flops_per_token=flops.train_flops_per_token(sizes, seqlen),
        peak=None if job.rehearse else peaks.peak_for(record["device_kind"]),
        peak_bytes=peak_bytes,
        devices=trace.device_traces(captured) if captured else [],
        timeline=timeline, window_steps=(open_step, close_step),
        recompiles=[r for r in records if r.get("tag") == "recompile"
                    and opened < r["ts"] <= closed])
    window_events = None
    if timeline is not None:
        lo, hi = train_spans.window_us(measured)
        window_events = [ev for ev in timeline
                         if lo < ev["ts"] + ev["dur"] <= hi]
    # every thread's, for the readers of the `train_ckpt` runner's spans
    measured.window_spans = window_events

    device = {"platform": record["platform"], "kind": record["device_kind"],
              "count": record["device_count"], "memory_peak_bytes": peak_bytes,
              "peak_bytes_in_use": memory and memory["peak_bytes_in_use"],
              "peak_bytes_reserved": memory and memory["peak_bytes_reserved"]}
    breakdown = None
    devs = measured.devices
    if job.trace and devs:
        device["busy_s"] = sum(d.busy_ns() for d in devs) / len(devs) / 1e9
        device["window_s"] = sum(d.window_ns for d in devs) / len(devs) / 1e9
        breakdown = {"device_ops": trace.top_ops(devs[0]),
                     "idle_gaps": trace.top_gaps(
                         devs[0], train_spans.loop_thread_spans(captured))}
    if job.rehearse:
        device.update(busy_s=None, window_s=None)

    lines = [
        dict(event="window", open_step=open_step, close_step=close_step,
             steps=steps, seconds=seconds, intervals=steps // interval,
             stamps=[stamps.by_step[s] for s in sorted(stamps.by_step)],
             loss_first10=first, loss_last_interval=last,
             losses_finite=all(finite), loss_fell=last < first,
             run_steps=record["steps"], recompiles=record["recompiles"]),
        dict(event="setup", setup_s=setup_s, init_seed=init_seed(job),
             data_seed=data_seed(job), argv=argv[4:], token_file=wrote,
             compile_cache={"dir": cache_dir, **stamps.cache_setup},
             compile_cache_after_window=record["compile_cache"],
             attn_impl=record["attn_impl"], memory_after_window=memory,
             memory_peak_bytes=peak_bytes)]
    if timeline is not None:
        loop = train_spans.loop_events(timeline)
        opened_us = train_spans.window_us(measured)[0]
        lines.append(dict(
            event="timeline",
            # `setup.unspanned_s` in its two parts: this process before
            # `train()`'s first line (imports, the token file), and what of
            # `train()` up to the window is under no span
            before_train_s=setup_s - opened_us / 1e6,
            train_unspanned_s=(opened_us - train_spans.covered_us(
                loop, float("-inf"), opened_us)) / 1e6,
            # the set-up tree: each span of the loop's thread that began
            # before the loop did, in order, in milliseconds
            setup_spans_ms=[[ev["name"], ev["dur"] / 1e3] for ev in loop
                            if ev["name"].startswith("setup.")
                            or ev["name"] == "compile"],
            # the window's spans by name: count, mean milliseconds
            window_spans_ms=_by_name(ev for ev in window_events
                                     if ev in loop)))
    for fields in lines:
        log(**(_quiet(fields) if job.rehearse else fields))
    return Outcome(correct=correct, attempted=steps,
                   failed=finite.count(False), end_to_end=end_to_end,
                   measured=measured, device=device, breakdown=breakdown,
                   compared=compared)


def _check(job: Job, family, record: dict, program, argv: list) -> dict:
    """The run's first loss and gradient norm against the float32
    reference: the weights `train()` started from (the family's model
    initialised from the same key) and the batch it saw first (the
    program's loader, as `train()` makes it, on the same file)."""
    import jax
    from distributed_pytorch_from_scratch_tpu.config import (
        IGNORE_INDEX, MeshConfig)
    from distributed_pytorch_from_scratch_tpu.data.dataset import (
        get_dataloader)
    from distributed_pytorch_from_scratch_tpu.runtime.mesh import make_mesh

    args = program.get_train_args(argv)
    first = next(get_dataloader(
        args.data_path, args.batch_size, IGNORE_INDEX, split="train",
        maxlen=args.maxlen, shuffle=True, seed=args.random_seed,
        data_mode=args.data_mode).epoch(0))
    mesh = make_mesh(MeshConfig(**job.workload["mesh"]),
                     devices=jax.devices()[:int(job.workload["chips"])])
    params = jax.jit(family.model.init)(jax.random.key(args.random_seed))
    want = _reference(family, mesh, params, first["input_ids"],
                      first["target_ids"], first["position_ids"])
    return _compare([record["first_loss"], record["first_grad_norm"]], want,
                    job.workload["dtype"])
