"""The serving yardstick off the chip: the `chat` data kind, the arithmetic
from request times to the two tails, the open loop that times a request
from when it was due, and the staged `serve` runner end to end at its
rehearsal shape (no cell of BENCHMARK.json names it yet: PERF.md section 7,
so test_run.py does not reach it)."""

import json
import os
import statistics
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import serving  # noqa: E402
from benchmark.lib.files import load_json, load_module  # noqa: E402
from benchmark.lib.serving import Served  # noqa: E402

CELL = "gpt2-medium.serve-chat-32slots"
LIMITS = {"ttft_ms": 250, "tpot_ms": 100}


@pytest.fixture(scope="module")
def chat():
    return load_module("data", "chat")


@pytest.fixture(scope="module")
def spec():
    return load_json("workloads", CELL + ".json")["data"]


# ---- the data kind ----

def test_same_seed_same_requests(chat, spec):
    a = chat.plan(spec, 50257, 1024, 40.0, 2147483659)
    b = chat.plan(spec, 50257, 1024, 40.0, 2147483659)
    assert a == b
    c = chat.plan(spec, 50257, 1024, 40.0, 2147483660)
    assert [p.prompt for p in a] != [p.prompt for p in c]


def test_one_schedule_a_seed_at_exactly_the_rate(chat, spec):
    plans = [chat.plan(spec, 50257, 1024, 40.0, seed) for seed in (5, 6, 77)]
    span = 40.0 * spec["arrivals"].get("window_share", 1.0)
    assert [(p.due_s, len(p.prompt)) for p in plans[0]] != [
        (p.due_s, len(p.prompt)) for p in plans[1]]
    for plan in plans:
        assert len(plan) == round(spec["arrivals"]["rate_rps"] * span)
        due = [p.due_s for p in plan]
        assert due[0] == 0.0 and due == sorted(due) and due[-1] < span
    for group, value in (("arrivals", {"process": "uniform", "rate_rps": 1}),
                         ("prompt_len", {"dist": "fixed", "value": 8})):
        with pytest.raises(ValueError):
            chat.plan({**spec, group: value}, 50257, 1024, 40.0, 5)


def test_lengths_stay_inside_their_clips(chat, spec):
    plan = chat.plan({**spec, "arrivals": {"process": "poisson",
                                           "rate_rps": 100.0}},
                     50257, 1024, 40.0, 3)
    lo, hi = spec["prompt_len"]["min"], spec["prompt_len"]["max"]
    assert all(lo <= len(p.prompt) <= hi for p in plan)
    lo, hi = spec["output_len"]["min"], spec["output_len"]["max"]
    assert all(lo <= p.output_len <= hi for p in plan)
    assert all(len(p.prompt) + p.output_len <= 1024 for p in plan)
    assert all(3 <= t < 50257 for p in plan for t in p.prompt)
    firsts = [p.prompt[0] for p in plan]
    assert len(set(firsts)) == len(firsts)      # no prefix shared by accident
    with pytest.raises(ValueError):
        chat.plan(spec, 50257, 512, 40.0, 3)


def test_medians_over_ten_thousand_draws(chat, spec):
    rng = np.random.default_rng(0)
    for group in ("prompt_len", "output_len"):
        draws = chat.draw_lengths(spec[group], 10_000, rng)
        assert abs(statistics.median(draws) / spec[group]["median"] - 1) < 0.05


def test_poisson_gaps(chat, spec):
    rng = np.random.default_rng(0)
    gaps = chat.draw_gaps(10_000, 1000.0, rng)
    assert abs(gaps.mean() / 0.1 - 1) < 0.02           # 10 requests a second
    assert abs(gaps.std() / gaps.mean() - 1) < 0.05    # exponential, not even
    assert gaps.sum() == pytest.approx(1000.0)


# ---- from request times to the two tails ----

def served(rid, due, first, finished, planned=11, produced=None, **more):
    return Served(rid, due, due + 0.001, due + 0.002, first, finished,
                  planned, planned if produced is None else produced, **more)


def test_ttft_and_tpot_by_hand():
    r = served(0, due=1.0, first=1.2, finished=2.1, planned=11)
    assert serving.ttft_ms(r, 50.0) == pytest.approx(200.0)
    assert serving.tpot_ms(r, 50.0) == pytest.approx(90.0)   # 0.9 s / 10 gaps
    assert serving.met(r, LIMITS, 50.0)
    assert not serving.met(r, {"ttft_ms": 199, "tpot_ms": 100}, 50.0)
    assert not serving.met(r, {"ttft_ms": 250, "tpot_ms": 89}, 50.0)


def test_percentiles_by_hand():
    # 21 requests due at 0..20 s: time to first token 10, 20, ..., 210 ms,
    # 10 ms a token after the first
    rs = [served(k, float(k), k + 0.010 * (k + 1), k + 0.010 * (k + 1) + 0.1)
          for k in range(21)]
    s = serving.summarize(rs, LIMITS, seconds=21.0, t_end=22.0)
    assert s["sent"] == s["finished"] == s["samples"] == 21
    assert s["failed"] == 0
    assert s["ttft_p50_ms"] == pytest.approx(110.0)
    assert s["ttft_p95_ms"] == pytest.approx(200.0)     # the 20th of 21
    assert s["tpot_p95_ms"] == pytest.approx(10.0)
    assert s["attained_pct"] == pytest.approx(100.0)
    assert s["lateness_p95_ms"] == pytest.approx(1.0)
    assert s["queue_wait_p95_ms"] == pytest.approx(2.0)
    assert s["out_tokens_per_s"] == pytest.approx(21 * 11 / 22.0)


def test_refused_and_unfinished_fail_and_miss_both_limits():
    good = served(0, 0.0, 0.1, 0.5)
    refused = Served(1, 1.0, 1.0, None, None, None, 11, 0, refused=True)
    unfinished = served(2, 2.0, 2.1, None, planned=11, produced=4)
    short = served(3, 3.0, 3.1, 3.2, planned=11, produced=7)
    rs = [good, refused, unfinished, short]
    assert [serving.failed(r) for r in rs] == [False, True, True, True]
    assert [serving.met(r, LIMITS, 20.0) for r in rs] == [True] + [False] * 3
    s = serving.summarize(rs, LIMITS, seconds=10.0, t_end=20.0)
    assert (s["sent"], s["failed"], s["samples"]) == (4, 3, 4)
    assert s["attained_pct"] == pytest.approx(25.0)
    # a failed request stays in the sample with the time it is known to
    # have taken at least: up to the end of the drain
    assert serving.ttft_ms(refused, 20.0) == pytest.approx(19_000.0)
    assert serving.tpot_ms(unfinished, 20.0) == pytest.approx(17_900.0 / 3)
    assert s["ttft_p95_ms"] > 10_000


def test_backlog():
    rs = [served(0, 0.0, 0.1, 1.0), served(1, 2.0, 2.1, 9.0),
          served(2, 4.0, 4.1, None, produced=3)]
    assert [serving.backlog(rs, t) for t in (0.5, 1.5, 5.0, 9.5)] == [1, 0, 2, 1]


def test_window_events_and_counters():
    events = [
        {"ph": "X", "name": "decode_step", "dur": 9e3},
        {"ph": "i", "name": serving.WINDOW_OPEN},
        {"ph": "X", "name": "decode_step", "dur": 10e3},
        {"ph": "C", "name": "slots_live", "args": {"value": 3.0}},
        {"ph": "X", "name": "prefill_chunk", "dur": 30e3},
        {"ph": "i", "name": serving.WINDOW_CLOSE},
        {"ph": "C", "name": "slots_live", "args": {"value": 9.0}},
    ]
    inside = serving.window_events(events)
    assert serving.span_ms(inside, "decode_step") == [10.0]
    assert serving.counter_values(inside, "slots_live") == [3.0]
    m = type("M", (), {"events": inside, "num_pages": 4, "devices": []})
    assert serving.READERS["sched.prefill_share_pct"](m) == pytest.approx(75.0)
    assert serving.READERS["sched.decode_batch_mean"](m) == 3.0
    assert serving.READERS["engine.decode_step_ms_median"](m) == 10.0
    assert serving.READERS["kv.pages_in_use_peak_pct"](m) is None
    assert serving.READERS["device.decode_idle_pct"](m) is None
    assert len(serving.READERS) == 10


# ---- the open loop ----

class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += max(s, 1e-4)


class FakeEngine:
    """Every step takes `step_s` and gives every waiting request its first
    and last token; step number `stall_at` takes `stall_s` more."""

    def __init__(self, clock, step_s=0.01, stall_at=None, stall_s=0.0):
        self.clock, self.step_s = clock, step_s
        self.stall_at, self.stall_s = stall_at, stall_s
        self.waiting, self.steps = [], 0

    def submit(self, req):
        if req.rid == 13:
            raise ValueError("refused for the test")
        self.waiting.append(req)

    def has_work(self):
        return bool(self.waiting)

    def step(self):
        self.clock.t += self.step_s
        if self.steps == self.stall_at:
            self.clock.t += self.stall_s
        self.steps += 1
        for req in self.waiting:
            req.first_token_t = req.finish_t = self.clock.t
        self.waiting = []


class FakeRequest:
    def __init__(self, rid):
        self.rid, self.submit_t = rid, None
        self.first_token_t = self.finish_t = None


def drive(stall_s):
    runner = load_module("runners", "serve")
    clock = FakeClock()
    engine = FakeEngine(clock, stall_at=10, stall_s=stall_s)
    due = [0.1 * k for k in range(40)]          # ten a second for 4 s
    requests = [FakeRequest(k) for k in range(40)]
    drove = runner.drive(engine, requests, due, seconds=4.0, drain_s=1.0,
                         clock=clock, sleep=clock.sleep)
    ttft = {r.rid: (r.first_token_t - r.submit_t) * 1e3 for r in requests
            if r.first_token_t is not None}
    return drove, requests, ttft


def test_a_request_is_timed_from_when_it_was_due():
    drove, requests, ttft = drive(stall_s=0.0)
    assert drove.sent == 40 and list(drove.refused) == [13]
    assert all(r.submit_t == pytest.approx(drove.t_open + 0.1 * r.rid)
               for r in requests)
    assert max(ttft.values()) < 25.0


def test_a_stall_lengthens_the_ttft_of_requests_due_during_it():
    quiet = drive(stall_s=0.0)[2]
    drove, requests, ttft = drive(stall_s=1.0)
    # the stalled step began near t = 1.0 s and held the loop for 1 s: the
    # requests due at 1.1 .. 1.9 s were submitted late, and waited from
    # when they were due
    late = [k for k in range(40) if drove.submitted[k] - 0.1 * k > 0.05]
    assert set(range(11, 20)) - {13} <= set(late)
    for k in (11, 12, 14, 15, 19):
        assert ttft[k] == pytest.approx((2.0 - 0.1 * k) * 1e3, abs=60.0)
        assert ttft[k] > quiet[k] + 50.0
    assert ttft[30] < 25.0                      # long after the stall


# ---- the staged runner end to end ----

# what benchmark/run.py --workload <CELL> --seed 3000000019 --seconds 2
# --trace <t> --rehearse does up to the runner's Outcome and the readers
REHEARSE = """
import json, sys, time
t0 = time.time()
sys.path.insert(0, {root!r})
from benchmark.lib.cells import load_cell
from benchmark.lib.files import load_module
from benchmark.lib.job import Job
from benchmark.lib.serving import READERS
workload, config = load_cell({cell!r}, rehearse=True)
job = Job(t0, {cell!r}, workload, config,
          load_module("families", config["family"]), 3000000019, 2.0,
          bool({trace}), True, None)
out = load_module("runners", workload["runner"]).run(job)
readers = dict(READERS, **{{"entry.compiles_in_window": load_module(
    "layer_metrics", "entry.compiles_in_window").read}})
print(json.dumps(dict(
    correct=out.correct, attempted=out.attempted, failed=out.failed,
    end_to_end=sorted(out.end_to_end), device=out.device,
    breakdown=out.breakdown,
    per_layer={{k: read(out.measured) for k, read in readers.items()}})))
"""


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_staged_runner(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1",
               JAX_COMPILATION_CACHE_DIR=os.path.join(
                   ROOT, ".jax_cache", "rehearse"))
    done = subprocess.run(
        [sys.executable, "-c",
         REHEARSE.format(root=ROOT, cell=CELL, trace=trace)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(l) for l in done.stdout.strip().splitlines()]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 20
    assert last["end_to_end"] == ["setup_s", "tpot_p95_ms", "ttft_p95_ms"]
    assert last["device"]["platform"] == "cpu"
    assert last["device"]["memory_peak_bytes"] is None
    (check,) = [l for l in lines if l.get("event") == "check"]
    assert check["ok"] is True and check["positions"] == 16
    (window,) = [l for l in lines if l.get("event") == "window"]
    assert window["sent"] == window["finished"] == last["attempted"]
    assert window["pages_in_use_after"] == 0 and window["every_request_sent"]
    # no time taken on the CPU stands on a log line
    for line in lines[:-1]:
        for key, value in line.items():
            if key.endswith(("_s", "_ms", "seconds")) or "_ms_" in key:
                assert value is None, (key, value)
    per_layer = last["per_layer"]
    assert per_layer["entry.compiles_in_window"] == 0
    assert per_layer["kv.preemptions"] == 0
    assert per_layer["device.decode_idle_pct"] is None   # no device on a CPU
    if trace:
        assert last["device"]["busy_s"] is None
        assert 0 < per_layer["sched.decode_batch_mean"] <= 8
        assert 0 < per_layer["kv.pages_in_use_peak_pct"] <= 100
        assert 0 < per_layer["sched.prefill_share_pct"] < 100
    else:
        assert per_layer["sched.decode_batch_mean"] is None
