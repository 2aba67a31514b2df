"""What the loop's two worker threads did over the window, from the spans
they emit themselves: the checkpoint writer's two rates and its busy share,
and the prefetch worker's busy share. Arithmetic on the `train_ckpt`
runner's `measured.window_spans` (the events of the program's
`obs/trace.SpanTracer` timeline that ENDED inside the window: name, `dur` in
microseconds, `args`) and `measured.window` (`benchmark/lib/timing.Window`).

The spans and their arguments (the program's, not the benchmark's):

    `ckpt.d2h`   `training/checkpoint.save_checkpoint`'s writer: the
                 snapshot over device -> host; `bytes` = what
                 `AsyncCheckpointer.bytes_moved` grows by for the save
    `ckpt.write` the same thread: slicing, the `.npz` writes, pruning;
                 `bytes` = what `bytes_written` grows by, `files`
    `prefetch_window`  `data/prefetch.Prefetcher`'s worker: one item's draw
                 and transform, not the wait for room in the queue

A writer that is busy the whole interval between two saves makes the next
save wait for it in `ckpt.join_prev`, on the loop's thread: the busy share
is the cell's distance from that. A prefetch worker that is busy the whole
window can no longer stay a batch ahead: `input.data_wait_ms` rises only
from there on.

A reader gives None where the run wrote no timeline (`--trace 0`, another
runner) and, for a rate, where a span carries no `bytes` (a program from
before the spans had them).
"""

from __future__ import annotations

from typing import List, Optional, Sequence

from benchmark.lib.program_trace import CKPT_WRITER_SPANS, span_ms

PREFETCH_SPANS = ("prefetch_window",)


def _timeline(m) -> Optional[List[dict]]:
    """The window's spans; None where the run wrote no timeline."""
    return getattr(m, "window_spans", None) or None


def _gb_per_s(name: str):
    """Summed `bytes` of the window's spans called `name` over their summed
    duration, in 1e9 bytes a second."""
    def read(m):
        spans = [ev for ev in _timeline(m) or () if ev["name"] == name]
        moved = [ev.get("args", {}).get("bytes") for ev in spans]
        ms = span_ms(spans, (name,))
        if not ms or None in moved:
            return None
        return sum(moved) / ms / 1e6
    return read


def _busy_pct(names: Sequence[str]):
    """Time under the window's spans called one of `names`, as a share of
    the window. The spans of one name set are one thread's and do not
    overlap, so the sum is that thread's busy time."""
    def read(m):
        spans = _timeline(m)
        if spans is None:
            return None
        return 100.0 * span_ms(spans, names) / 1e3 / m.window.seconds
    return read


READERS = {
    # GB/s, higher is better: what a slow link or a slow disk looks like
    "checkpoint.d2h_gb_s": _gb_per_s("ckpt.d2h"),
    "checkpoint.write_gb_s": _gb_per_s("ckpt.write"),
    # %, lower is better: at 100 every save waits for the one before it
    "checkpoint.writer_busy_pct": _busy_pct(CKPT_WRITER_SPANS),
    # %, lower is better: at 100 the input pipeline starves the chip
    "input.prefetch_busy_pct": _busy_pct(PREFETCH_SPANS),
}
