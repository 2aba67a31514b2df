"""Goodput/badput accounting: bucket total wall time by what the host
was doing, in the sense of the goodput literature (e.g. Google's ML
Goodput): goodput = time the accelerators were training on tokens / total
wall time; everything else — compile, input-pipeline stalls, H2D, checkpoint
I/O, eval — is badput with a named cause.

The meter is driven by the same spans the tracer records (TrainObserver
feeds both from one `with observer.span(bucket)`), so the timeline view and
the aggregate view can never disagree. Time in no span (what the loop does
between two spans: the token count, the loss sum, the heartbeat) lands in
`other`, so the buckets always sum to wall time exactly. `train()`'s set-up
(backend, data, model, weights, Adam state, step build) is the `setup.*`
spans' bucket `setup`, and the host work of a log interval the `log` span's.
"""

from __future__ import annotations

import time
from typing import Callable, Dict

# Every interval of wall time is attributed to exactly one of these.
# "step" = dispatching the train step + blocked waiting on device results:
# the tokens-on-device bucket that defines goodput. The rest is badput.
BUCKETS = ("setup", "compile", "data_wait", "h2d", "step", "log",
           "checkpoint", "eval")


class GoodputMeter:
    def __init__(self, clock: Callable[[], float] = time.monotonic,
                 started_ago: float = 0.0):
        """`started_ago`: seconds the run was already under way when the
        meter could be made; its wall starts that much earlier."""
        self._clock = clock
        self._t0 = clock() - started_ago
        self._buckets: Dict[str, float] = {b: 0.0 for b in BUCKETS}
        self.tokens = 0
        self.steps = 0

    def account(self, bucket: str, seconds: float) -> None:
        """Attribute `seconds` of wall time to `bucket`. Unknown buckets are
        created on the fly (they show up in the summary like any other)."""
        self._buckets[bucket] = self._buckets.get(bucket, 0.0) + seconds

    def bucket(self, name: str) -> float:
        """Seconds accounted to `name` so far."""
        return self._buckets.get(name, 0.0)

    def add_progress(self, tokens: int, steps: int = 1) -> None:
        self.tokens += tokens
        self.steps += steps

    def wall(self) -> float:
        return self._clock() - self._t0

    def summary(self) -> dict:
        """Buckets + derived numbers. `other` is the unattributed remainder,
        clamped at 0 (nested spans could in principle double-account; the
        train loop's spans do not nest across buckets)."""
        wall = max(self.wall(), 1e-9)
        buckets = dict(self._buckets)
        buckets["other"] = max(0.0, wall - sum(buckets.values()))
        return {
            "wall_s": wall,
            "buckets_s": {k: round(v, 6) for k, v in buckets.items()},
            "goodput": buckets.get("step", 0.0) / wall,
            "tokens": self.tokens,
            "steps": self.steps,
            "tokens_per_sec_wall": self.tokens / wall,
        }

    @staticmethod
    def format_summary(s: dict) -> str:
        wall = s["wall_s"]
        parts = ", ".join(
            f"{k} {v:.2f}s ({100 * v / wall:.1f}%)"
            for k, v in sorted(s["buckets_s"].items(),
                               key=lambda kv: -kv[1]) if v > 0)
        return (f"goodput {100 * s['goodput']:.1f}% over {wall:.2f}s wall "
                f"({s['tokens']} tokens, {s['steps']} steps): {parts}")
