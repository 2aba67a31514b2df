"""TrainObserver: the one handle the training loop holds on the whole
observability stack — tracer + goodput meter + health sentinel + hang
watchdog — so instrumenting a call site is a single
`with observer.span("bucket"):` line.

One span call feeds three consumers at once: the Chrome-trace timeline
(where exactly did the wall clock go), the goodput buckets (aggregate
accounting, guaranteed consistent with the timeline because they share the
measurement), and the watchdog heartbeat (any activity is liveness). The
sentinel rides the loop's existing logging-interval D2H via
`check_health()`.
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import Optional

from .flight import FlightRecorder
from .goodput import GoodputMeter
from .sentinel import HealthSentinel
from .trace import SpanTracer
from .watchdog import HangWatchdog


class LoopSpans:
    """What `train()` hands, as their `tracer`, to the modules that time
    their own work (`data/prefetch.Prefetcher`, `runtime/mesh.batch_feeder`,
    `training/checkpoint.AsyncCheckpointer`): `span(name, cat, **args)` as
    `SpanTracer` has it. On the thread that made this object (the loop's) a
    span goes through `TrainObserver.span`: timeline, goodput bucket `cat`,
    flight ring, watchdog. On any other thread (the prefetch worker, the
    checkpoint writer) it goes to the timeline alone: that wall time runs
    beside the loop's and is not the loop's to account."""

    def __init__(self, observer: "TrainObserver"):
        self._observer = observer
        self._loop_thread = threading.get_ident()

    def span(self, name: str, cat: Optional[str] = None, **args):
        if threading.get_ident() != self._loop_thread:
            return self._observer.tracer.span(name, cat=cat, **args)
        return self._observer.span(cat or name, name, **args)


class SpanSequence:
    """Spans of one bucket that follow one another on one thread, none
    inside another: `enter(name)` ends the open one and starts the next as
    `observer.span(bucket, name)`, `end()` ends the last (a second call
    does nothing). For a long stretch of straight-line code, `train()`'s
    set-up, whose phases a `with` each would indent by the hundred lines."""

    def __init__(self, observer: "TrainObserver", bucket: str):
        self._observer = observer
        self._bucket = bucket
        self._open = None

    def enter(self, name: str, **args) -> None:
        self.end()
        self._open = self._observer.span(self._bucket, name, **args)
        self._open.__enter__()

    def end(self) -> None:
        if self._open is not None:
            span, self._open = self._open, None
            span.__exit__(None, None, None)


class TrainObserver:
    def __init__(self, log_dir: str, writer=None, trace: bool = True,
                 watchdog_secs: float = 0.0, sentinel: bool = True,
                 spike_factor: float = 3.0, halt_on_nonfinite: bool = True,
                 process_index: int = 0, flight_ring: int = 256,
                 profile_on_anomaly: int = 0,
                 started: Optional[float] = None):
        """`started`: a `time.perf_counter()` sample from before the
        observer could exist (its directory needs the process index, which
        needs the backend). The timeline's zero and the goodput meter's
        wall start there, and `span_done` books what ran since."""
        self.writer = writer
        self.process_index = process_index
        self.tracer = SpanTracer(log_dir, enabled=trace, pid=process_index,
                                 process_name=f"train-p{process_index}",
                                 t0=started)
        self.goodput = GoodputMeter(
            started_ago=0.0 if started is None
            else time.perf_counter() - started)
        # anomaly-triggered device profiling (ISSUE 12): a flight dump
        # arms a bounded jax.profiler window that tick()s from heartbeat
        profiler = None
        if profile_on_anomaly > 0 and flight_ring > 0:
            from ..training.metrics import AnomalyProfiler
            # writer: the finished anomaly window parses into a
            # profile_attribution event (obs v4) — without it the train
            # path's captures would dodge the measured plane
            profiler = AnomalyProfiler(log_dir,
                                       window_steps=profile_on_anomaly,
                                       writer=writer)
        self.profiler = profiler
        # the anomaly flight recorder: every span/heartbeat lands in the
        # ring, and the sentinel/watchdog flush it on their halt/stall
        # paths so a post-mortem has the preceding seconds, not just the
        # triggering event (flight_ring 0 disables)
        self.flight = (FlightRecorder(log_dir, maxlen=flight_ring,
                                      profiler=profiler)
                       if flight_ring > 0 else None)
        self.sentinel = (HealthSentinel(
            log_dir, spike_factor=spike_factor,
            halt_on_nonfinite=halt_on_nonfinite,
            writer=writer, tracer=self.tracer,
            flight=self.flight) if sentinel else None)
        self.watchdog = (HangWatchdog(
            watchdog_secs, process_index=process_index, writer=writer,
            tracer=self.tracer,
            flight=self.flight) if watchdog_secs > 0 else None)
        self._closed = False
        self._local = threading.local()
        self.loop_spans = LoopSpans(self)

    @contextmanager
    def span(self, bucket: str, name: Optional[str] = None, **args):
        """Trace a span AND attribute its wall time to a goodput bucket.
        `bucket` is one of obs.goodput.BUCKETS (or any new category);
        `name` defaults to the bucket for the timeline label. Nested spans
        all appear on the timeline, but only the OUTERMOST one accounts
        goodput time (else nesting would double-count the wall clock and
        the buckets would sum past 100%)."""
        if self.watchdog is not None:
            self.watchdog.beat(phase=name or bucket)
        depth = getattr(self._local, "depth", 0)
        self._local.depth = depth + 1
        t0 = time.perf_counter()
        try:
            with self.tracer.span(name or bucket, cat=bucket,
                                  **args) as found:
                yield found
        finally:
            self._local.depth = depth
            if depth == 0:
                dur = time.perf_counter() - t0
                self.goodput.account(bucket, dur)
                if self.flight is not None:
                    self.flight.record("span", bucket=bucket,
                                       name=name or bucket,
                                       dur_s=round(dur, 6), **args)
            if self.watchdog is not None:
                # beat on exit too: after a long compile/checkpoint the
                # stall clock restarts from completion, and the watchdog's
                # "recovered" line marks the moment it finished
                self.watchdog.beat(phase=f"{name or bucket}:done")

    def span_done(self, bucket: str, name: str, start: float, end: float,
                  **args) -> None:
        """A span of this thread that was over before the observer existed,
        from two `time.perf_counter()` samples: the timeline's event, the
        bucket's seconds, the flight ring's record and the watchdog's beat,
        as `span` gives them. No profiler annotation: that cannot be
        entered after the fact."""
        self.tracer.complete_span(name, start, end, cat=bucket, **args)
        self.goodput.account(bucket, end - start)
        if self.flight is not None:
            self.flight.record("span", bucket=bucket, name=name,
                               dur_s=round(end - start, 6), **args)
        if self.watchdog is not None:
            self.watchdog.beat(phase=f"{name}:done")

    def sequence(self, bucket: str) -> SpanSequence:
        return SpanSequence(self, bucket)

    def instant(self, name: str, **args) -> None:
        self.tracer.instant(name, **args)

    def heartbeat(self, step: int, tokens: int = 0, steps: int = 1,
                  sync=None) -> None:
        """Called once per completed dispatch: liveness + progress.
        `sync`: a device value from this dispatch — the anomaly
        profiler's stop barrier, so an armed window never truncates."""
        self.goodput.add_progress(tokens, steps)
        if self.flight is not None:
            self.flight.record("heartbeat", step=step, tokens=tokens)
            self.flight.tick(step, sync=sync)
        if self.watchdog is not None:
            self.watchdog.beat(step=step)

    def check_health(self, step: int, loss: float,
                     grad_norm: Optional[float] = None) -> None:
        """Raises TrainingHealthError on non-finite values (sentinel off ->
        no-op)."""
        if self.sentinel is not None:
            self.sentinel.check(step, loss, grad_norm=grad_norm)

    def report_compiled(self, analysis: dict, model_flops: float,
                        steps_in_program: int = 1,
                        expected_flops: Optional[float] = None,
                        step: int = 0) -> None:
        """Log the introspection record (obs.introspect.analyze_compiled)
        as the `cost_analysis` event; the caller prints the human line.
        `expected_flops` = the hand-rolled estimate scaled to THIS program
        (x steps per dispatch, / world size for SPMD per-device HLO)."""
        if self.writer is not None:
            self.writer.event(
                "cost_analysis", step=step,
                flops=analysis.get("flops"),
                bytes_accessed=analysis.get("bytes_accessed"),
                peak_hbm_bytes=analysis.get("peak_hbm_bytes"),
                collectives=analysis.get("collectives"),
                comm_bytes=analysis.get("comm_bytes"),
                model_flops_per_step=model_flops,
                steps_in_program=steps_in_program,
                expected_program_flops=expected_flops)

    def close(self, print_summary: bool = True) -> Optional[dict]:
        """Stop the watchdog, write trace.json, log + return the goodput
        summary. Idempotent (later calls return None)."""
        if self._closed:
            return None
        self._closed = True
        if self.watchdog is not None:
            self.watchdog.close()
        if self.profiler is not None:
            self.profiler.close()
        summary = self.goodput.summary()
        if self.writer is not None:
            self.writer.event("goodput_summary", **summary)
            # proc-tagged per-rank phase timings: the cross-rank skew
            # attribution's input (obs/attribution.rank_skew) — each
            # process writes its own metrics*.jsonl, so the collection
            # across files IS the per-rank view
            self.writer.event(
                "rank_phase_stats", process=self.process_index,
                phases_s=summary["buckets_s"], steps=summary["steps"],
                tokens=summary["tokens"], wall_s=summary["wall_s"])
        if print_summary:
            print(GoodputMeter.format_summary(summary))
        path = self.tracer.close()
        if path is not None and print_summary:
            print(f"host timeline trace written to {path} "
                  f"(open in https://ui.perfetto.dev)")
        return summary
