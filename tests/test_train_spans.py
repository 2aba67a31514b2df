"""`train()`'s own timeline: the set-up phase by phase, the log interval, the
caller's stop, and what `--no_trace` leaves.

Two tiny runs of `train()` on the CPU, shared by every case: one with the
timeline on that the caller stops at step 23 of 200, one with `--no_trace`.
"""

import json
import os
import random
import types
from collections import Counter

import pytest

from distributed_pytorch_from_scratch_tpu.config import (BOS_TOKEN, EOS_TOKEN,
                                                         UNK_TOKEN)

SETUP_ORDER = ["setup.backend", "setup.logs", "setup.data", "setup.model",
               "setup.init", "setup.opt_state", "setup.build_step"]
LOG_INTERVAL, STOP_AT = 5, 23


def _args(tmp, name, *more):
    from distributed_pytorch_from_scratch_tpu.train import get_train_args

    rng = random.Random(0)
    tokens = tmp / f"{name}.json"
    tokens.write_text(json.dumps({
        "train": [[rng.randint(4, 63) for _ in range(rng.randint(8, 30))]
                  for _ in range(256)],
        "validation": [[5, 6, 7]],
        "special_ids": {BOS_TOKEN: 1, EOS_TOKEN: 2, UNK_TOKEN: 3},
        "vocab_size": 64}))
    return get_train_args([
        "--data_path", str(tokens), "--save_dir", str(tmp / name),
        "--batch_size", "4", "--max_steps", "200",
        "--log_interval", str(LOG_INTERVAL), "--save_interval", "1000",
        "--warmup_steps", "2", "--data_mode", "packed", "--family", "gpt2",
        "--attn_dim", "32", "--ffn_dim", "64", "--num_heads", "4",
        "--num_layers", "2", "--maxlen", "32", *more])


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    from distributed_pytorch_from_scratch_tpu import train as train_mod
    from distributed_pytorch_from_scratch_tpu.obs.observer import (
        TrainObserver)
    from distributed_pytorch_from_scratch_tpu.obs.watchdog import HangWatchdog

    tmp = tmp_path_factory.mktemp("train_spans")
    beats, spans, polls = [], Counter(), []
    beat, span = HangWatchdog.beat, TrainObserver.span

    def counted_beat(self, step=None, phase=None):
        beats.append(phase)
        return beat(self, step=step, phase=phase)

    def counted_span(self, bucket, name=None, **args):
        spans[name or bucket] += 1
        return span(self, bucket, name, **args)

    def stop(n):
        polls.append(n)
        return n >= STOP_AT

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(HangWatchdog, "beat", counted_beat)
        traced = train_mod.train(_args(tmp, "traced"), stop=stop)
        patch.setattr(TrainObserver, "span", counted_span)
        untraced = train_mod.train(_args(tmp, "untraced", "--no_trace",
                                         "--max_steps", "12"))

    def logs(name, file):
        return os.path.join(str(tmp), name, "logs", file)

    events = [json.loads(line) for line in open(logs("traced", "trace.jsonl"))]
    loop_tid = next(e["tid"] for e in events if e["name"] == "step")
    mine = [e for e in events if e.get("ph") == "X" and e["tid"] == loop_tid]
    return types.SimpleNamespace(
        traced=traced, untraced=untraced, beats=beats, polls=polls,
        spans_untraced=spans, loop=mine, events=events, logs=logs,
        metrics=[json.loads(line)
                 for line in open(logs("traced", "metrics.jsonl"))])


def _end(e):
    return e["ts"] + e["dur"]


def setup_spans_once_in_order(r):
    setup = [e for e in r.events if e["name"].startswith("setup.")]
    assert [e["name"] for e in sorted(setup, key=lambda e: e["ts"])] \
        == SETUP_ORDER
    # on the loop's thread, each of the bucket `setup`, from the run's zero
    assert all(e in r.loop and e["cat"] == "setup" for e in setup)
    assert setup[0]["ts"] == 0
    # none inside another, and all over before the step is compiled
    for before, after in zip(setup, setup[1:]):
        assert _end(before) <= after["ts"] + 1e-3, (before, after)
    (compiled,) = [e for e in r.loop if e["name"] == "compile"]
    assert _end(setup[-1]) <= compiled["ts"]


def log_spans_follow_their_syncs(r):
    syncs = [e for e in r.loop if e["name"] == "device_sync"]
    logs = [e for e in r.loop if e["name"] == "log"]
    steps = list(range(LOG_INTERVAL, STOP_AT + 1, LOG_INTERVAL))
    assert [e["args"]["step"] for e in logs] == steps
    assert [e["args"]["step"] for e in syncs] == steps
    for sync, log in zip(syncs, logs):
        assert log["ts"] >= _end(sync) - 1e-3
        assert log["cat"] == "log" and log["args"]["programs"] >= 0
    # a steady interval builds nothing
    assert logs[-1]["args"]["programs"] == 0


def goodput_buckets_equal_span_sums(r):
    (good,) = [m for m in r.metrics if m["tag"] == "goodput_summary"]
    for bucket, names in (("setup", SETUP_ORDER), ("log", ["log"])):
        spans = sum(e["dur"] for e in r.loop if e["name"] in names) / 1e6
        assert good["buckets_s"][bucket] == pytest.approx(spans, abs=5e-3)
    # the wall starts at train()'s first line: `other` is what is in no span
    assert sum(good["buckets_s"].values()) == pytest.approx(good["wall_s"],
                                                            rel=0.02)
    last = max(_end(e) for e in r.loop) / 1e6
    assert good["wall_s"] >= last


def interval_record_carries_the_sync_stamp(r):
    by_step = {}
    for m in r.metrics:
        if m["tag"] in ("train/ce_loss", "train/lr"):
            by_step.setdefault(m["step"], {})[m["tag"]] = m["ts"]
    assert sorted(by_step) == list(range(LOG_INTERVAL, STOP_AT + 1,
                                         LOG_INTERVAL))
    stamps = [by_step[s]["train/ce_loss"] for s in sorted(by_step)]
    assert stamps == sorted(stamps)
    # taken as the sync returned, before the interval's work; `train/lr`
    # is stamped where it is written, inside the `log` span
    logs = [e for e in r.loop if e["name"] == "log"]
    for step, log in zip(sorted(by_step), logs):
        assert by_step[step]["train/ce_loss"] <= by_step[step]["train/lr"]
    gaps = [b - a for a, b in zip(stamps, stamps[1:])]
    spans = [(b["ts"] - a["ts"]) / 1e6 for a, b in zip(logs, logs[1:])]
    assert gaps == pytest.approx(spans, abs=2e-3)


def no_trace_writes_no_timeline_and_keeps_the_three(r):
    assert not os.path.exists(r.logs("untraced", "trace.jsonl"))
    assert not os.path.exists(r.logs("untraced", "trace.json"))
    steps = r.untraced["steps"]
    assert steps == 12
    per_step = {k: v for k, v in r.spans_untraced.items() if v >= steps}
    assert per_step == {"step": steps, "h2d": steps, "data_wait": steps}
    assert r.spans_untraced["log"] == steps // LOG_INTERVAL
    assert all(r.spans_untraced[name] == 1 for name in SETUP_ORDER[2:])


def callers_stop_ends_the_run_at_a_poll(r):
    # polled once a window from step 0 on, and the first true ends it
    assert r.polls == list(range(STOP_AT + 1))
    assert r.traced["steps"] == STOP_AT
    assert r.traced["first_loss"] is not None
    # ended as a run that reached --max_steps: the observer closed (summary
    # and trace.json written), no shutdown checkpoint
    assert [m["steps"] for m in r.metrics
            if m["tag"] == "goodput_summary"] == [STOP_AT]
    assert os.path.exists(r.logs("traced", "trace.json"))
    assert not [f for f in os.listdir(os.path.dirname(
        os.path.dirname(r.logs("traced", "")))) if f.endswith(".npz")]


def watchdog_beats_from_every_setup_span(r):
    phases = [b for b in r.beats if b and b.startswith("setup.")]
    for name in SETUP_ORDER:
        assert f"{name}:done" in phases, name
    # a span that is entered beats on the way in too; the two booked after
    # the fact could not
    for name in SETUP_ORDER[2:]:
        assert phases.index(name) < phases.index(f"{name}:done")
    assert "log" in r.beats and "log:done" in r.beats


CASES = [setup_spans_once_in_order, log_spans_follow_their_syncs,
         goodput_buckets_equal_span_sums,
         interval_record_carries_the_sync_stamp,
         no_trace_writes_no_timeline_and_keeps_the_three,
         callers_stop_ends_the_run_at_a_poll,
         watchdog_beats_from_every_setup_span]


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.__name__)
def test_train_timeline(runs, case):
    case(runs)
