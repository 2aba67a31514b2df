"""From the times of single requests to the serving metrics: the arithmetic
every serving cell shares, checked on hand-made lists in
benchmark/tests/test_serving.py.

A request is timed from when it was **due** (the open loop's schedule), not
from when the generator got round to submitting it: a stall of the engine
then lengthens the time to the first token of every request that came due
during it. All times of a `Served` are seconds after the window opened, on
one host clock.

* time to first token = first token on the host - due;
* time per output token = (last token - first token) / (tokens produced - 1);
* a request **failed** if it was refused, was not finished when the drain
  ended, or produced another number of tokens than planned. A failed request
  stays in both samples with the longest time it can be shown to have taken
  (up to the end of the drain), and it misses both limits.

Copied in meaning from the program's `serving/engine.Request` properties
and `serving/loadgen.run_loadgen` (PERF.md, Open questions).

Readers. `READERS` holds, by metric name, the `read(measured)` of the ten
per-layer metrics the `serve` runner's `measured` feeds. They are not in
`BENCHMARK.json`, nor files of `layer_metrics/`, until a cell runs that
runner (PERF.md section 7, PR 27); `layer_metrics/<name>.py` is then
`read = READERS["<name>"]`. The existing `entry.*` readers work on that
`measured` as they stand.
"""

from __future__ import annotations

from typing import List, NamedTuple, Optional, Sequence

from benchmark.lib.timing import quantile


class Served(NamedTuple):
    rid: int
    due: float
    submitted: float                # when the generator called submit
    admitted: Optional[float]       # left the queue
    first: Optional[float]          # first token on the host
    finished: Optional[float]       # last token on the host
    planned: int                    # tokens it had to produce
    produced: int
    refused: bool = False           # submit raised (QueueFull, ValueError)


def failed(r: Served) -> bool:
    return r.refused or r.finished is None or r.produced != r.planned


def ttft_ms(r: Served, t_end: float) -> float:
    first = t_end if r.first is None else r.first
    return (first - r.due) * 1e3


def tpot_ms(r: Served, t_end: float) -> float:
    if r.first is None:             # never started: it waited all that time
        return (t_end - r.due) * 1e3
    last = t_end if failed(r) else r.finished
    return (last - r.first) * 1e3 / max(r.produced - 1, 1)


def met(r: Served, limits: dict, t_end: float) -> bool:
    return (not failed(r) and ttft_ms(r, t_end) <= limits["ttft_ms"]
            and tpot_ms(r, t_end) <= limits["tpot_ms"])


def backlog(rs: Sequence[Served], t: float) -> int:
    """Requests due by `t` and not finished by then."""
    return sum(r.due <= t and (r.finished is None or r.finished > t)
               for r in rs)


def summarize(rs: Sequence[Served], limits: dict, seconds: float,
              t_end: float) -> dict:
    """The window's numbers over every request sent. `seconds` is the span
    in which requests came due (the backlog is read at its middle and its
    end), `t_end` when the drain ended."""
    if not rs:
        raise ValueError("no request was sent")
    ttft = [ttft_ms(r, t_end) for r in rs]
    tpot = [tpot_ms(r, t_end) for r in rs]
    waits = [(r.admitted - r.due) * 1e3 for r in rs if r.admitted is not None]
    late = [(r.submitted - r.due) * 1e3 for r in rs]
    return {
        "sent": len(rs),
        "finished": sum(r.finished is not None for r in rs),
        "failed": sum(failed(r) for r in rs),
        "samples": len(ttft),
        "ttft_p50_ms": quantile(ttft, 0.5),
        "ttft_p95_ms": quantile(ttft, 0.95),
        "tpot_p50_ms": quantile(tpot, 0.5),
        "tpot_p95_ms": quantile(tpot, 0.95),
        "queue_wait_p95_ms": quantile(waits, 0.95) if waits else None,
        "lateness_p95_ms": quantile(late, 0.95),
        "attained_pct": 100.0 * sum(met(r, limits, t_end) for r in rs)
                        / len(rs),
        "backlog_mid": backlog(rs, seconds / 2),
        "backlog_close": backlog(rs, seconds),
        "out_tokens_per_s": sum(r.produced for r in rs) / t_end,
    }


# ---- the program's timeline (obs/trace.SpanTracer's trace.jsonl) ----

WINDOW_OPEN, WINDOW_CLOSE = "bench.window_open", "bench.window_close"


def window_events(events: Sequence[dict]) -> List[dict]:
    """Complete events ("X") and counters ("C") written between the two
    instants (an event is written when it ends)."""
    out, inside = [], False
    for ev in events:
        if ev.get("ph") == "i":
            if ev["name"] == WINDOW_OPEN:
                inside = True
            elif ev["name"] == WINDOW_CLOSE:
                inside = False
        elif inside and ev.get("ph") in ("X", "C"):
            out.append(ev)
    return out


def span_ms(events: Sequence[dict], name: str) -> List[float]:
    return [ev["dur"] / 1e3 for ev in events
            if ev["ph"] == "X" and ev["name"] == name]


def counter_values(events: Sequence[dict], name: str) -> List[float]:
    return [ev["args"]["value"] for ev in events
            if ev["ph"] == "C" and ev["name"] == name]


# ---- the per-layer readers of a serving cell (staged: PERF.md section 7) ----

def _summary(key: str):
    return lambda m: m.summary[key]


def _decode_idle_pct(m):
    """Share of the captured slice of the window in which no op ran on the
    device: 1 - union of op intervals / slice."""
    if not m.devices:
        return None
    return 100.0 * (1.0 - m.devices[0].busy_ns() / m.devices[0].window_ns)


def _decode_step_ms_median(m):
    steps = span_ms(m.events, "decode_step")
    return quantile(steps, 0.5) if steps else None


def _pages_in_use_peak_pct(m):
    used = counter_values(m.events, "pages_in_use")
    return 100.0 * max(used) / m.num_pages if used else None


def _decode_batch_mean(m):
    live = counter_values(m.events, "slots_live")
    return sum(live) / len(live) if live else None


def _prefill_share_pct(m):
    prefill = sum(span_ms(m.events, "prefill_chunk"))
    decode = sum(span_ms(m.events, "decode_step"))
    return 100.0 * prefill / (prefill + decode) if prefill + decode else None


READERS = {
    # ms, host_clock: submit - due. The generator is the loop that steps the
    # engine, so it runs up to one step late; a request is timed from when
    # it was due, so this moves nothing: the guard that a starved generator
    # is not read as a fast server
    "loadgen.lateness_p95_ms": _summary("lateness_p95_ms"),
    # ms, program_span: due -> the engine's own `admit_t`; moves ttft_p95_ms
    "sched.queue_wait_p95_ms": _summary("queue_wait_p95_ms"),
    # %, program_span: requests sent that met both of the file's `limits`
    "sched.limits_met_pct": _summary("attained_pct"),
    # slots, program_counter: `slots_live`, one a decode step; tpot_p95_ms
    "sched.decode_batch_mean": _decode_batch_mean,
    # %, program_span: host time under `prefill_chunk` over that under
    # `prefill_chunk` + `decode_step`; moves both tails
    "sched.prefill_share_pct": _prefill_share_pct,
    # %, program_counter: peak of `pages_in_use` over the pool's pages: at
    # 100 the next page costs a preemption
    "kv.pages_in_use_peak_pct": _pages_in_use_peak_pct,
    # count, program_counter: `stats()["preemptions"]` over the window
    "kv.preemptions": lambda m: m.preemptions,
    # ms, program_span: span `decode_step`, one dispatch and the wait for
    # its tokens; moves tpot_p95_ms
    "engine.decode_step_ms_median": _decode_step_ms_median,
    # %, device_trace
    "device.decode_idle_pct": _decode_idle_pct,
    # tokens/s, host_clock: tokens produced over the window and its drain
    "engine.out_tokens_per_s": _summary("out_tokens_per_s"),
}
