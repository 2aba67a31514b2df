"""benchmark/lib/loop_spans.py on a hand-built `measured` with the values
worked out beside it, and the admitted cell end to end on the CPU: the
rehearsal prints every metric the manifest lists for it in both trace modes
(that the program's spans carry the bytes the readers divide is held where
the spans are made: tests/test_program_spans.py)."""

import json
import os
from types import SimpleNamespace

import pytest

from benchmark.lib import loop_spans
from benchmark.lib.timing import Window
from benchmark.tests.test_run import MANIFEST, rehearsal, reported

CELL = "gpt2-medium.train-ckpt-every40"


def span(name, dur_s, tid, **args):
    ev = {"name": name, "ph": "X", "ts": 0.0, "dur": dur_s * 1e6, "tid": tid}
    if args:
        ev["args"] = args
    return ev


# a window of 40 s: two saves' writer spans ended in it, the loop pulled and
# fed 4 batches, the prefetch worker made 4
SPANS = [
    span("prefetch_window", 0.020, 3), span("data_wait", 0.0001, 1, step=0),
    span("h2d", 0.001, 1, step=0),
    span("prefetch_window", 0.030, 3), span("prefetch_window", 0.010, 3),
    span("ckpt.snapshot", 0.004, 1, step=40),
    span("ckpt.d2h", 1.5, 2, step=40, bytes=4_000_000_000),
    span("ckpt.write", 5.0, 2, step=40, bytes=4_500_000_000, files=1),
    span("prefetch_window", 0.040, 3),
    span("ckpt.join_prev", 0.0002, 1, step=40),
    span("ckpt.d2h", 2.5, 2, step=80, bytes=4_000_000_000),
    span("ckpt.write", 4.0, 2, step=80, bytes=4_500_000_000, files=1),
]
WINDOW = Window([100.0, 100.3, 140.0], [None, None])


def measured(spans=SPANS):
    return SimpleNamespace(window_spans=spans, window=WINDOW)


def test_the_four_readers_on_worked_values():
    read = lambda name: loop_spans.READERS[name](measured())
    # 8.0 GB over 1.5 + 2.5 s; 9.0 GB over 5.0 + 4.0 s
    assert read("checkpoint.d2h_gb_s") == pytest.approx(2.0)
    assert read("checkpoint.write_gb_s") == pytest.approx(1.0)
    # 13 s of the writer's 40 s window; 0.1 s of the worker's
    assert read("checkpoint.writer_busy_pct") == pytest.approx(32.5)
    assert read("input.prefetch_busy_pct") == pytest.approx(0.25)
    assert len(loop_spans.READERS) == 4


@pytest.mark.parametrize("name", sorted(loop_spans.READERS))
def test_no_timeline_reads_as_nothing(name):
    """An untraced run and another runner's `measured`: no value, no raise."""
    read = loop_spans.READERS[name]
    assert read(SimpleNamespace()) is None
    assert read(SimpleNamespace(window_spans=[], window=WINDOW)) is None


def test_a_span_without_bytes_gives_no_rate_and_still_its_time():
    """The parent's program: the spans are there and carry `step` alone."""
    bare = [{k: v for k, v in ev.items() if k != "args"} for ev in SPANS]
    read = lambda name: loop_spans.READERS[name](measured(bare))
    assert read("checkpoint.d2h_gb_s") is None
    assert read("checkpoint.write_gb_s") is None
    assert read("checkpoint.writer_busy_pct") == pytest.approx(32.5)
    # one span of the two without its bytes: no rate either
    half = list(SPANS)
    half[6] = span("ckpt.d2h", 1.5, 2, step=40)
    assert loop_spans.READERS["checkpoint.d2h_gb_s"](measured(half)) is None
    # a window with a timeline and no save in it: no rate, a share of 0
    quiet = [ev for ev in SPANS if not ev["name"].startswith("ckpt.")]
    assert loop_spans.READERS["checkpoint.d2h_gb_s"](measured(quiet)) is None
    assert loop_spans.READERS["checkpoint.writer_busy_pct"](
        measured(quiet)) == 0.0


def test_every_reader_has_its_file_and_its_entry():
    listed = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in loop_spans.READERS:
        m = listed[name]
        assert m["workloads"] == [CELL] and m["source"] == "program_span"
        assert m["moves"] == "tokens_per_s_per_chip"
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "layer_metrics", name + ".py")
        assert 'READERS["%s"]' % name in open(path).read()


@pytest.mark.parametrize("trace", [0, 1])
def test_the_admitted_cell_prints_every_metric_the_manifest_lists(trace):
    done = rehearsal(CELL, 1, 3000000019, trace)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(l) for l in done.stdout.strip().splitlines()]
    line = lines[-1]
    wanted = {m["name"] for m in reported(
        "per_layer" if trace else "end_to_end", CELL)}
    assert set(line["metrics"]) == wanted
    if not trace:
        assert wanted == {"tokens_per_s_per_chip", "setup_s"}
        return
    assert len(wanted) == 7 + 7 + 15
    assert "entry.compiles_in_window" not in wanted
    assert set(loop_spans.READERS) <= wanted
    assert line["correct"] is True
    assert line["compared"]["saves_failed"] == [0, 0]
    assert line["compared"]["final_state_not_read_back"] == [0, 0]
