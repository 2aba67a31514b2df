"""Training metrics: TensorBoard scalars + JSONL fallback + device memory.

Reference parity (`/root/reference/train.py:85,117-120`): `train/ce_loss`,
`train/lr` and a per-rank reserved-memory scalar go to TensorBoard
(`tensorboardX`). We keep tensorboardX when importable and always mirror to a
plain `metrics.jsonl` (grep-able, no proto deps). The reference's
`torch.cuda.memory_reserved` becomes `jax.Device.memory_stats()`.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import List, Optional

import jax

from ..obs.trace import span_of


class MetricsWriter:
    """Multihost-safe: process 0 writes `metrics.jsonl`, every other
    process writes `metrics.proc{i}.jsonl`, so concurrent processes
    sharing one log dir never interleave lines in one file. TensorBoard
    stays per-rank (its tfevents filenames embed hostname+pid, so writers
    never clobber even in a shared dir) — per-host curves are how
    multi-host divergence is compared. Also a context manager, so the
    file handle closes on error paths."""

    def __init__(self, log_dir: str, process_index: Optional[int] = None,
                 max_bytes: int = 0):
        from ..runtime.mesh import process_info
        if process_index is None:
            process_index = process_info()[0]
        self.process_index = process_index
        os.makedirs(log_dir, exist_ok=True)
        name = ("metrics.jsonl" if process_index == 0
                else f"metrics.proc{process_index}.jsonl")
        self.path = os.path.join(log_dir, name)
        # size-based rotation (ISSUE 12): once the current file passes
        # max_bytes, a schema-valid `rotated` event naming the NEXT file
        # is appended as its LAST line and the stream continues there
        # (metrics.jsonl -> metrics.001.jsonl -> ...). The old file is
        # never renamed, so a live tailer's open handle stays valid and
        # follows the chain (obs/collector.JsonlTailer). 0 = unbounded.
        self.max_bytes = max_bytes
        self._base, self._ext = os.path.splitext(self.path)
        self._gen = 0
        self._jsonl = open(self.path, "a")
        # the obs watchdog writes events from its daemon thread while the
        # train loop writes scalars — serialize, or lines tear
        self._lock = threading.Lock()
        self._closed = False
        self._tb = None
        try:
            from tensorboardX import SummaryWriter  # optional
            self._tb = SummaryWriter(log_dir=log_dir)
        except Exception:
            pass

    def _write(self, rec: dict) -> None:
        with self._lock:
            if self._closed:
                return
            self._jsonl.write(json.dumps(rec) + "\n")
            self._jsonl.flush()
            if self.max_bytes and self._jsonl.tell() >= self.max_bytes:
                self._rotate_locked()

    def _rotate_locked(self) -> None:
        from ..obs.schema import EVENT_SCHEMA_VERSION
        self._gen += 1
        nxt = f"{self._base}.{self._gen:03d}{self._ext}"
        self._jsonl.write(json.dumps(
            {"tag": "rotated", "ts": time.time(),
             "schema_version": EVENT_SCHEMA_VERSION,
             "next": os.path.basename(nxt), "generation": self._gen}) + "\n")
        self._jsonl.close()
        self.path = nxt
        self._jsonl = open(nxt, "a")

    def scalar(self, tag: str, value: float, step: int,
               ts: Optional[float] = None) -> None:
        """`ts`: a `time.time()` sample of the moment the value belongs to,
        where that is earlier than the write (`train()`'s interval record:
        when the interval's last step was done); else now."""
        self._write({"tag": tag, "value": float(value), "step": int(step),
                     "ts": time.time() if ts is None else ts})
        # post-close writes drop entirely: tensorboardX would resurrect a
        # fresh, never-flushed event file on a late add_scalar
        if self._tb is not None and not self._closed:
            self._tb.add_scalar(tag, value, step)

    def text(self, tag: str, value: str, step: int = 0) -> None:
        self._write({"tag": tag, "text": value, "step": int(step)})
        if self._tb is not None and not self._closed:
            self._tb.add_text(tag, value, step)

    def event(self, tag: str, step: Optional[int] = None, **fields) -> None:
        """Structured one-off record (goodput summary, sentinel/watchdog
        events, cost analysis, request traces) — jsonl only; TB has no
        sane rendering for these. Every event carries `schema_version`
        (obs/schema.py) so consumers can fail loudly on drift instead of
        silently dropping sections."""
        from ..obs.schema import EVENT_SCHEMA_VERSION
        rec = {"tag": tag, "ts": time.time(),
               "schema_version": EVENT_SCHEMA_VERSION, **fields}
        if step is not None:
            rec["step"] = int(step)
        self._write(rec)

    def close(self) -> None:
        with self._lock:
            if self._closed:
                return
            self._closed = True
            self._jsonl.close()
        if self._tb is not None:
            self._tb.close()

    def __enter__(self) -> "MetricsWriter":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def chip_peak_flops(device: Optional[jax.Device] = None) -> Optional[float]:
    """Peak bf16 FLOP/s of the chip — the MFU denominator — from the one
    peaks table (obs/attribution.CHIP_SPECS, keyed by `device_kind`).

    None on the CPU backend: there is no chip, so MFU is "not measured"
    there (callers print that, never a percentage of some TPU's peak). An
    accelerator the table does not list raises."""
    from ..obs.attribution import chip_key_for, chip_specs

    device = device or jax.devices()[0]
    if device.platform == "cpu":
        return None
    return chip_specs(chip_key_for(device.device_kind))[0]


def model_flops_per_step(cfg, batch: int, seqlen: int,
                         num_params: int) -> float:
    """Model FLOPs for one fwd+bwd train step (no remat recompute counted),
    as the family that reads `cfg` states them (its `flops_per_step`; the
    dense and capacity-MoE formula is `models/stack.DecoderStack`'s).
    `num_params` is the family's count of `cfg`, `model.num_params(cfg)`
    (gpt2's 2-matmul MLP + tied head are a third of the FFN and a
    vocabulary short of the llama formula in `cfg.num_params()`)."""
    from ..models import facts_family
    return facts_family(cfg).flops_per_step(cfg, batch, seqlen, num_params)


def bd_counters_summary(counters: dict) -> dict:
    """The block-diffusion step's own counters (`models/bd_moe.py`) as a
    log line's numbers: the share of positions masked (about a half: the
    level is uniform), the batch's mean level, and the weighted MEAN CE of
    the masked positions (the loss over its draw's factor `weight_sum /
    positions`, which is 1 in expectation)."""
    positions = float(counters["positions"])
    return {"masked_share": float(counters["masked"]) / positions,
            "p_mean": float(counters["p_sum"]) / positions,
            "masked_ce_mean": float(counters["loss_main"]) * positions
            / max(float(counters["weight_sum"]), 1e-9)}


def dsa_counters_summary(counters: dict) -> dict:
    """The counters of layers that CHOOSE their keys (`models/dsa_moe.py`,
    `ops/index_select.SUMS`, a row a layer) as a log line's numbers: the
    pairs the rows kept over the causal pairs (`sum_t min(t + 1, topk)` over
    the triangle: the selection is live where this is under 1), the
    indexer's loss a layer, summed (what the step adds to the CE), the mean
    entropy of the index scores' softmax over a row's set (a fresh indexer's
    is the log of the set's size) and the share of rows whose threshold is
    shared by keys on both sides of the budget (the tie rule decided
    them)."""
    import numpy as np

    rows = np.asarray(counters["dsa_rows"], np.float64)
    of = lambda name: np.asarray(counters[name], np.float64)
    return {"kept_share": float(np.sum(of("dsa_kept"))
                                / np.sum(of("dsa_causal"))),
            "index_kl": float(np.sum(of("dsa_index_kl") / rows)),
            "index_entropy": float(np.mean(of("dsa_index_entropy") / rows)),
            "tau_ties": float(np.mean(of("dsa_tau_ties") / rows))}


def loop_counters_summary(counters: dict) -> dict:
    """The counters of a stack passed R times a step (`DecoderStack.
    _loop_loss`) as a log line's numbers: the whole objective, each exit's
    mean CE (`loss_exit_<r>`, r from 1), the mean of the exit
    distribution a pass (`exit_p_<r>`), its mean entropy, and the mean
    exit step `sum_r r p_r` (1.875 of 4 where the gate says 1/2
    everywhere)."""
    import numpy as np

    p = np.asarray(counters["exit_p_mean"], np.float64)
    out = {"loss_main": float(counters["loss_main"]),
           "exit_entropy": float(counters["exit_entropy"]),
           "exit_step_mean": float(np.sum(p * np.arange(1, len(p) + 1)))}
    for r, (ce, share) in enumerate(zip(counters["loss_exit"], p), 1):
        out[f"loss_exit_{r}"] = float(ce)
        out[f"exit_p_{r}"] = float(share)
    return out


def mixer_counters_summary(counters: dict) -> dict:
    """The counters of a DENSE family whose mixers count (the `ssm_dense`
    and `sambay` families: no router, so no row of
    `moe_counters_summary`'s) as a log line's numbers: the loss and the RMS
    of the residual stream that enters the final norm (`resid_rms_last`),
    and what the family's mixers count: the worst Mamba-2 layer's most
    negative `dt A` summed over a chunk (`ssm_decay_min`,
    parallel/mamba.py), or the worst Mamba-1 layer's of one step
    (`sscan_decay_min`, parallel/mamba1.py), the differential attention
    layers' mean `lambda` (`diff_lambda`), the RMS of the memory one layer
    leaves the layers above it (`memory_rms`) and the layers that read the
    one layer's keys and values (`shared_kv_readers`)."""
    import numpy as np

    out = {"loss_main": float(counters["loss_main"])}
    for name, fold in (("ssm_decay_min", np.min), ("sscan_decay_min", np.min),
                       ("diff_lambda", np.mean), ("memory_rms", np.mean),
                       ("shared_kv_readers", np.mean)):
        if name in counters:
            out[name] = float(fold(counters[name]))
    return {**out, "resid_rms_last": float(counters["resid_rms_last"])}


def moe_counters_summary(counters: dict, cfg, tokens: int) -> dict:
    """The step's counters of an expert model (`DecoderStack.loss_shard`
    with `with_counters`, fetched to the host) as the few numbers a log
    line carries: the main and multi-token-prediction losses apart, the
    rows routed to the held experts per token and expert layer (`top_k x
    held / routed` under uniform routing; per DATA token, so twice that,
    in the bd_moe family, whose layers see two rows a token) beside the
    rows the grouped products' groups covered (the same) and the rows the
    dispatch's movers and passes walked (whole chunks: over `rows_here` it
    is the padding they still pay), the windows of token-sorted rows
    `sum_held` took a block of tokens of a live chunk
    (`sum_windows_per_block`: 1.0 says every block's rows fitted one
    window, more is what a skewed router costs the mover, or a job that
    holds every expert, whose one chunk is top_k rows a token; 0.0 that
    no chunk was live), and the held
    experts' load as max over mean, averaged over the expert layers (1.0 is
    balance); and, where the step ran the selection bias's rule, the mean
    size of a bias entry's step (`router_bias_step`); the mixers'
    counters of a family with residual streams; a delta layer's decay
    (`kda_g_min`, `kda_g_spread`) and the groups' load (`groups_hit_max`)
    of the `kda_mla_moe` family; a Mamba-2 layer's decay sums
    (`ssm_decay_min`) of the `ssm_moe` family."""
    import numpy as np

    lo = cfg.expert_offset
    routed = np.asarray(counters["routed"])[:, lo:lo + cfg.experts_held]
    out = {"loss_main": float(counters["loss_main"])}
    if "loss_mtp" in counters:
        out["loss_mtp"] = float(counters["loss_mtp"])
    for name in ("rows_here", "rows_computed", "rows_walked"):
        out[f"{name}_per_token"] = float(
            np.mean(counters[name])) / max(tokens, 1)
    out["sum_windows_per_block"] = float(
        np.sum(counters["sum_windows"])) / max(
            float(np.sum(counters["sum_blocks"])), 1.0)
    out["load_max_over_mean"] = float(np.mean(
        routed.max(-1) / np.maximum(routed.mean(-1), 1e-9)))
    if "router_bias_step" in counters:
        out["router_bias_step"] = float(counters["router_bias_step"])
    if "hc_sinkhorn_err" in counters:
        # a family of hyper-connection streams (parallel/hyper.py): the
        # worst layer's Sinkhorn error, the layers' mean mixing
        out["hc_sinkhorn_err"] = float(np.max(counters["hc_sinkhorn_err"]))
        out["hc_res_offdiag"] = float(np.mean(counters["hc_res_offdiag"]))
    if "kda_g_min" in counters:
        # a family of Kimi Delta Attention layers (parallel/kda.py): the
        # worst layer's most negative decay (never under the gate's bound)
        # and the layers' mean spread of the decay over a head's channels
        out["kda_g_min"] = float(np.min(counters["kda_g_min"]))
        out["kda_g_spread"] = float(np.mean(counters["kda_g_spread"]))
    if "ssm_decay_min" in counters:
        # a family of Mamba-2 layers (parallel/mamba.py): the worst layer's
        # most negative `dt A` summed over a chunk
        out["ssm_decay_min"] = float(np.min(counters["ssm_decay_min"]))
    if "groups_hit" in counters:
        # a group-limited selection (parallel/moe.SharedRoutedFFN.select):
        # the tokens the busiest group got a choice of, over the groups'
        # mean, averaged over the expert layers (1.0 is balance)
        hit = np.asarray(counters["groups_hit"])
        out["groups_hit_max"] = float(np.mean(
            hit.max(-1) / np.maximum(hit.mean(-1), 1e-9)))
    return out


class ProfilerTrace:
    """Start/stop `jax.profiler` tracing over a step window — the TPU
    analogue of the reference's (absent) torch profiler; SURVEY §5.1. View
    the trace with TensorBoard's profile plugin or xprof."""

    def __init__(self, log_dir: str, start_step: int, num_steps: int,
                 tracer=None):
        """`tracer`: anything with `SpanTracer.span` (`train()` hands its
        `LoopSpans`): starting and stopping the capture hold the loop's
        thread, the stop for as long as the capture takes to write, and are
        the spans `profile.start` and `profile.stop` of bucket `profile`."""
        self.log_dir = os.path.join(log_dir, "profile")
        self.start_step = start_step
        self.stop_step = start_step + num_steps
        self._tracer = tracer
        self._active = False
        self._done = False

    def maybe_start(self, step: int) -> None:
        # ">= start" rather than "inside the window": the caller's step
        # counter may jump by steps_per_dispatch and clear the whole window
        # in one hop — the trace then starts at the first boundary past
        # start_step and covers at least num_steps (`_done` stops it from
        # restarting every later step)
        if not self._active and not self._done and step >= self.start_step:
            with span_of(self._tracer, "profile.start", cat="profile",
                         step=step):
                os.makedirs(self.log_dir, exist_ok=True)
                # no Python frames (JAX's default traces them): the capture
                # must not slow the steps it reads
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                jax.profiler.start_trace(self.log_dir,
                                         profiler_options=options)
            self._active = True

    def maybe_stop(self, step: int, sync=None) -> None:
        """`sync`: a device value from the last profiled step (e.g. the loss);
        dispatch is async, so without blocking on it stop_trace would fire
        while the profiled steps are still executing and truncate the trace."""
        if self._active and step >= self.stop_step:
            with span_of(self._tracer, "profile.stop", cat="profile",
                         step=step):
                if sync is not None:
                    jax.block_until_ready(sync)
                jax.profiler.stop_trace()
            self._active = False
            self._done = True
            import sys
            # stderr: serve.py/bench.py reserve stdout for the one
            # machine-parsed JSON record
            print(f"profiler trace written to {self.log_dir}",
                  file=sys.stderr)

    def close(self, sync=None) -> None:
        if self._active:
            if sync is not None:
                jax.block_until_ready(sync)
            jax.profiler.stop_trace()
            self._active = False
            import sys
            print(f"profiler trace written to {self.log_dir} (window "
                  f"overlapped the end of training; it may cover fewer "
                  f"steps than requested)", file=sys.stderr)


def _dir_bytes(path: str) -> int:
    """Recursive on-disk size of a capture dir (the duty sampler's disk
    budget is charged per finished window)."""
    total = 0
    for dirpath, _, files in os.walk(path):
        for name in files:
            try:
                total += os.path.getsize(os.path.join(dirpath, name))
            except OSError:
                pass
    return total


def emit_profile_attribution(writer, capture_dir: str, trigger: str,
                             steps: int, analytic=None) -> Optional[dict]:
    """Parse a FINISHED capture dir (obs/profparse) and land it as one
    versioned `profile_attribution` MetricsWriter event (ISSUE 15): the
    measured phase taxonomy, and — when the caller supplies the analytic
    phase report its run was priced with — the full measured-vs-analytic
    reconcile. A capture that fails to parse still lands an event (with
    `error` and empty phases): a window that silently vanished is the
    rot mode the measured plane exists to kill. Returns the event's
    fields (sans tag), or None when parsing failed."""
    from ..obs import profparse
    try:
        measured = profparse.parse_capture(capture_dir)
    except (ValueError, OSError) as e:
        if writer is not None:
            writer.event("profile_attribution", capture=capture_dir,
                         trigger=trigger, steps=int(steps), phases={},
                         error=f"{type(e).__name__}: {e}")
        return None
    fields = {
        "capture": capture_dir,
        "trigger": trigger,
        "steps": int(steps),
        "phases": profparse.phase_ms_map(measured),
        "device_busy_ms": measured["device_busy_ms"],
        "host_gap_ms": measured["host_gap_ms"],
        "events": measured["events"],
        "devices": measured["devices"],
    }
    if analytic is not None:
        fields["reconcile"] = profparse.reconcile(measured, analytic,
                                                  steps=steps)
    if writer is not None:
        writer.event("profile_attribution", **fields)
    return fields


class DutyCycleProfiler:
    """Duty-cycled continuous device profiling (ISSUE 15): every `every`
    dispatches, capture a bounded `jax.profiler` window of `window`
    dispatches, parse it at stop (obs/profparse), and land a versioned
    `profile_attribution` event — so a long run accumulates MEASURED
    attribution points instead of one hand-triggered capture.

    Same thread contract as `AnomalyProfiler`: `tick()` runs on the host
    loop (the thread that owns the device queue) once per dispatch, and
    reuses `ProfilerTrace`'s window mechanics (the stop blocks on `sync`
    so a window never truncates). The disk budget (`budget_mb`) is
    charged per FINISHED capture and checked only between windows — an
    open window always completes ("never mid-window"); once the budget
    is exhausted, further due windows are counted in `windows_skipped`
    with a one-time loud note, and the run keeps going unprofiled.

    The first window opens at the `every`-th tick, not the first — the
    initial dispatches are compile/layout churn a steady-state
    attribution must not average in."""

    def __init__(self, log_dir: str, every: int, window: int = 4,
                 budget_mb: float = 64.0, writer=None, analytic=None,
                 on_attribution=None):
        if every < 1:
            raise ValueError(f"profile_every must be >= 1, got {every}")
        if not 1 <= window <= every:
            raise ValueError(
                f"profile window must be in [1, profile_every] (a window "
                f"longer than the duty period would re-arm mid-capture): "
                f"got window {window}, every {every}")
        if budget_mb <= 0:
            raise ValueError(f"profile_budget_mb must be > 0, got "
                             f"{budget_mb}")
        if writer is None:
            raise ValueError(
                "duty-cycled profiling needs a MetricsWriter: the parsed "
                "profile_attribution events ARE the product — a capture "
                "nothing reads is the pre-ISSUE-15 state")
        self.log_dir = log_dir
        self.every = every
        self.window = window
        self.budget_bytes = int(budget_mb * 2**20)
        self.writer = writer
        self.analytic = analytic     # profparse.analytic_phase_report(...)
        # ISSUE 16: called with each parsed capture's event fields right
        # after the window FINISHES — i.e. between capture windows, the
        # documented control-plane safe point (obs/control.RetuneAdvisor
        # hooks here; never mid-window, never inside a traced function)
        self.on_attribution = on_attribution
        self._ticks = 0
        self._trace: Optional[ProfilerTrace] = None
        self._started_tick = 0
        self._capture_no = 0
        self.captures: List[str] = []       # capture dirs written
        self.capture_steps: List[int] = []  # dispatches each one covered
        self.attributions = 0               # events successfully parsed
        self.windows_skipped = 0            # due windows past the budget
        self.bytes_used = 0
        self.exhausted = False

    def tick(self, step: int = 0, sync=None) -> None:
        """Once per dispatch from the host loop. `sync`: a device value
        from this dispatch (the stop barrier). Window boundaries count
        in TICKS (dispatches), not the caller's step numbers — a
        steps_per_dispatch > 1 loop advances `step` by N per tick, and
        pricing the window in that domain would close it N x early."""
        if self._trace is not None:
            self._trace.maybe_stop(self._ticks, sync=sync)
            if self._trace._done:
                self._finish(end_tick=self._ticks)
        # not elif: a window finishing exactly on a duty boundary must
        # not swallow the window due at that same tick — W == N means
        # back-to-back capture, not half the documented cadence
        if self._trace is None and self._ticks \
                and self._ticks % self.every == 0:
            if self.exhausted:
                self.windows_skipped += 1
            else:
                self._start()
        self._ticks += 1

    def _start(self) -> None:
        self._capture_no += 1
        d = os.path.join(self.log_dir,
                         f"profile_duty_{self._capture_no:03d}")
        self._trace = ProfilerTrace(d, start_step=self._ticks,
                                    num_steps=self.window)
        self._started_tick = self._ticks
        self._trace.maybe_start(self._ticks)
        self.captures.append(self._trace.log_dir)

    def _finish(self, end_tick: int) -> None:
        trace, self._trace = self._trace, None
        # the dispatches this capture ACTUALLY covered: a close()-forced
        # window is shorter than `window`, and attributing it at the
        # full count would deflate measured_step_ms (and the record the
        # regression gate checks) by the truncation factor. `end_tick`
        # is the last tick the window saw: the stop path passes the
        # in-flight tick index; close() passes _ticks - 1 (the counter
        # already advanced past the final dispatch).
        steps = max(1, min(self.window, end_tick - self._started_tick))
        self.capture_steps.append(steps)
        self.bytes_used += _dir_bytes(trace.log_dir)
        if self.bytes_used >= self.budget_bytes and not self.exhausted:
            self.exhausted = True
            import sys
            print(f"duty profiler: disk budget exhausted after "
                  f"{self._capture_no} capture(s) "
                  f"({self.bytes_used / 2**20:.1f} MiB >= "
                  f"{self.budget_bytes / 2**20:.1f} MiB) — sampling "
                  f"stops; skipped windows are counted in the summary",
                  file=sys.stderr)
        fields = emit_profile_attribution(self.writer, trace.log_dir,
                                          "duty", steps, self.analytic)
        if fields is not None:
            self.attributions += 1
            if self.on_attribution is not None:
                self.on_attribution(fields)

    def close(self, sync=None) -> None:
        """Finish an open window at run end (shorter than requested beats
        an unparsed truncated capture) and attribute it."""
        if self._trace is not None:
            self._trace.close(sync=sync)
            self._finish(end_tick=self._ticks - 1)


class AnomalyProfiler:
    """Anomaly-triggered device profiling (ISSUE 12): when a flight dump
    fires (sentinel halt, watchdog stall, PoolExhausted preemption, SLO
    collapse), ARM a bounded `jax.profiler` window so the dump cross-links
    a device timeline of the steps right after the anomaly — instead of
    only host-side ring contents.

    Split across threads by design: `arm()` may be called from ANY thread
    (the watchdog's dump path included) and only records the request under
    a lock; the actual `jax.profiler` start/stop runs inside `tick()`,
    which the host loop calls once per dispatch — the same thread that
    owns the device queue (reusing `ProfilerTrace`'s window mechanics, so
    the stop blocks on `sync` and never truncates the profiled steps).
    `max_captures` bounds what an anomaly storm can spend: device tracing
    is the one obs tool too expensive to leave on, which is why it is
    armed by anomalies rather than always-on."""

    def __init__(self, log_dir: str, window_steps: int = 4,
                 max_captures: int = 1, writer=None, analytic=None):
        if window_steps < 1:
            raise ValueError(f"profile window must be >= 1 step, got "
                             f"{window_steps}")
        self.log_dir = log_dir
        self.window_steps = window_steps
        self.max_captures = max_captures
        # ISSUE 15: anomaly captures flow through the SAME parse as the
        # duty sampler's — when a writer is attached, every finished
        # window lands a profile_attribution event tagged with its
        # anomaly trigger, so flight dumps cross-link an ATTRIBUTED
        # timeline, not just a dir
        self.writer = writer
        self.analytic = analytic
        self.attributions = 0
        self._lock = threading.Lock()
        self._pending = None          # (tag, capture_dir) awaiting a tick
        self._armed_total = 0
        self._trace: Optional[ProfilerTrace] = None  # tick-thread only
        self._trace_tag: Optional[str] = None
        self._trace_started = 0       # step the open window started at
        self._last_step = 0           # the host loop's latest tick step
        self.captures = []            # capture dirs actually written

    def arm(self, tag: str) -> Optional[str]:
        """Reserve a capture for the NEXT tick; returns the directory the
        profile will land in (the flight dump stamps it), or None when the
        capture budget is spent or a capture is already pending/active —
        an anomaly storm profiles once, not once per dump."""
        with self._lock:
            if self._armed_total >= self.max_captures or \
                    self._pending is not None or self._trace is not None:
                return None
            self._armed_total += 1
            path = os.path.join(
                self.log_dir,
                f"profile_anomaly_{tag}_{self._armed_total:02d}")
            self._pending = (tag, path)
        return os.path.join(path, "profile")  # ProfilerTrace's subdir

    def tick(self, step: int, sync=None) -> None:
        """Drive the armed window from the host loop (one thread). The
        window opens at this step and closes `window_steps` later;
        `sync` is a device value from the last dispatched step, so the
        stop never fires while profiled steps are still executing."""
        with self._lock:
            pending = self._pending
            self._pending = None
        self._last_step = step
        if pending is not None and self._trace is None:
            tag, path = pending
            self._trace = ProfilerTrace(path, start_step=step,
                                        num_steps=self.window_steps)
            self._trace_tag = tag
            self._trace_started = step
            self._trace.maybe_start(step)
            self.captures.append(self._trace.log_dir)
        elif self._trace is not None:
            self._trace.maybe_stop(step, sync=sync)
            if self._trace._done:
                self._attribute()

    def _attribute(self) -> None:
        """Parse the finished anomaly window into a profile_attribution
        event (tick/close-thread only; no-op without a writer). The step
        count is what the window ACTUALLY covered — a close()-forced
        window is shorter than window_steps, and attributing it at the
        full count would deflate the measured per-step ms."""
        trace, self._trace = self._trace, None
        tag, self._trace_tag = self._trace_tag, None
        steps = max(1, min(self.window_steps,
                           self._last_step - self._trace_started))
        if self.writer is not None:
            if emit_profile_attribution(
                    self.writer, trace.log_dir, f"anomaly:{tag}",
                    steps, self.analytic) is not None:
                self.attributions += 1

    def close(self, sync=None) -> None:
        """Finish an open window at run end (shorter than requested beats
        a truncated unreadable capture). An ARMED window the loop never
        ticked again (the anomaly fired on the run's last step) still
        captures whatever device activity remains right now — the dump's
        cross-linked path must point at a readable trace, not at
        nothing."""
        with self._lock:
            pending = self._pending
            self._pending = None
        if pending is not None and self._trace is None:
            tag, path = pending
            self._trace = ProfilerTrace(path, start_step=0, num_steps=1)
            self._trace_tag = tag
            # never ticked: whatever close() captures counts as one step
            self._trace_started = self._last_step
            self._trace.maybe_start(0)
            self.captures.append(self._trace.log_dir)
        if self._trace is not None:
            self._trace.close(sync=sync)
            self._attribute()


def allreduce_p50_us(mesh, axis: str = "tp", nbytes: int = 4 * 1024 * 1024,
                     iters: int = 30) -> float:
    """p50 latency of a single all-reduce over `axis` (BASELINE metric #2).

    Shared by `bench.py` (real ICI number when tp > 1) and
    `__graft_entry__.dryrun_multichip` (virtual-CPU correctness-grade
    number). Each timed call ends in a one-element D2H copy.
    """
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..ops.collectives import reduce_from

    x = jnp.ones((nbytes // 4,), jnp.float32)
    f = jax.jit(jax.shard_map(lambda x: reduce_from(x, axis), mesh=mesh,
                              in_specs=(P(),), out_specs=P()))
    jax.block_until_ready(f(x))  # compile
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        f(x)[0].item()  # D2H sync
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] * 1e6


def device_memory_stats(device: Optional[jax.Device] = None) \
        -> Optional[dict]:
    """One device's `memory_stats()`, or **None when the backend has no
    stats** (the CPU backend returns None; some platform backends raise).
    Callers must render None as 'unavailable' — the pre-ISSUE-15 code
    folded it into 0, exporting a fake 0-GiB watermark that reads as "this
    run used no HBM" on every chip-less box (the silent-zero fix)."""
    if device is None:
        # local: in a multi-process run, jax.devices()[0] can belong to
        # another process — MemoryStats on a non-addressable device raises
        device = jax.local_devices()[0]
    try:
        stats = getattr(device, "memory_stats", lambda: None)()
    except Exception:  # platform backends without stats raise, not None
        return None
    return stats or None


def device_memory_gib(device: Optional[jax.Device] = None) \
        -> Optional[float]:
    """Bytes in use on the device, in GiB (analogue of
    `torch.cuda.memory_reserved`, reference `train.py:119`) — or None
    when the backend reports no memory stats (say 'n/a', never 0)."""
    stats = device_memory_stats(device)
    if stats is None:
        return None
    return stats.get("bytes_in_use", 0) / 1024 ** 3


def hbm_watermarks() -> Optional[List[dict]]:
    """Per-local-device HBM watermark snapshot (ISSUE 15): one dict per
    addressable device with `bytes_in_use`, `peak_bytes` (the high-water
    mark, when the backend tracks one) and `limit_bytes`. None when NO
    local device reports stats — the unavailable case stays a distinct
    value, not an all-zeros list."""
    out = []
    for d in jax.local_devices():
        stats = device_memory_stats(d)
        if stats is None:
            continue
        out.append({
            "device": f"{d.platform}:{d.id}",
            "bytes_in_use": int(stats.get("bytes_in_use", 0)),
            "peak_bytes": int(stats.get("peak_bytes_in_use",
                                        stats.get("bytes_in_use", 0))),
            "limit_bytes": int(stats.get("bytes_limit")
                               or stats.get("bytes_reservable_limit") or 0),
        })
    return out or None


def param_bytes_by_device(params) -> dict:
    """{'platform:id': bytes} of the parameter shards each local device
    holds — who really carries the model (a replicated leaf counts once per
    device, a sharded one its shard)."""
    out: dict = {}
    for leaf in jax.tree.leaves(params):
        for sh in leaf.addressable_shards:
            key = f"{sh.device.platform}:{sh.device.id}"
            out[key] = out.get(key, 0) + sh.data.nbytes
    return out


def publish_hbm(telemetry=None, writer=None, step: Optional[int] = None,
                pool_accounted_bytes: Optional[int] = None,
                event: bool = False) -> Optional[List[dict]]:
    """Publish live HBM watermark gauges (and optionally one
    `hbm_watermark` event) from `memory_stats()` (ISSUE 15).

    Gauges: `hbm/available` (0/1 — an unavailable backend is exported
    LOUDLY as 0-available, never as 0 bytes), and when available
    `hbm/bytes_in_use` / `hbm/peak_bytes` / `hbm/limit_bytes` (worst
    local device — the watermark that OOMs first) plus per-device
    `hbm/d<i>/...` gauges. `pool_accounted_bytes` (the PagedKVPool's
    pages_in_use x page_bytes) rides as `hbm/kv_accounted_bytes` and the
    `hbm/kv_accounted_frac` cross-check — accounted pool bytes over
    measured bytes-in-use; a fraction drifting toward 0 while the pool
    thinks it is full means something else is eating the device.

    Returns the per-device snapshot (None when unavailable) so callers
    can reuse it without a second stats round."""
    marks = hbm_watermarks()
    if telemetry is not None:
        telemetry.gauge("hbm/available", 0.0 if marks is None else 1.0)
        if marks is not None:
            telemetry.gauge("hbm/bytes_in_use",
                            max(m["bytes_in_use"] for m in marks))
            telemetry.gauge("hbm/peak_bytes",
                            max(m["peak_bytes"] for m in marks))
            telemetry.gauge("hbm/limit_bytes",
                            max(m["limit_bytes"] for m in marks))
            for i, m in enumerate(marks):
                telemetry.gauge(f"hbm/d{i}/bytes_in_use", m["bytes_in_use"])
                telemetry.gauge(f"hbm/d{i}/peak_bytes", m["peak_bytes"])
        if pool_accounted_bytes is not None:
            telemetry.gauge("hbm/kv_accounted_bytes", pool_accounted_bytes)
            if marks is not None:
                in_use = max(m["bytes_in_use"] for m in marks)
                if in_use:
                    telemetry.gauge("hbm/kv_accounted_frac",
                                    pool_accounted_bytes / in_use)
    if event and writer is not None:
        fields = {"devices": marks or [],
                  "available": marks is not None}
        if pool_accounted_bytes is not None:
            fields["pool_accounted_bytes"] = int(pool_accounted_bytes)
        writer.event("hbm_watermark", step=step, **fields)
    return marks
