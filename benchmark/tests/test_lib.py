"""The window's bookkeeping and the batch generator."""

import numpy as np
import pytest

from benchmark.lib import timing
from benchmark.lib.files import load_module

data = load_module("data", "zipf")


class FakeDevice:
    """Each dispatched step takes 125 ms (exact in binary) of a clock only
    `wait` advances."""

    def __init__(self, slow_step=None):
        self.now, self.dispatched, self.slow = 0.0, 0, slow_step

    def dispatch(self):
        self.dispatched += 1
        return self.dispatched

    def wait(self, handle):
        self.now += 0.5 if handle == self.slow else 0.125

    def clock(self):
        return self.now


def test_window_counts_whole_steps_and_keeps_one_queued():
    dev = FakeDevice()
    w = timing.run_window(dev.dispatch, dev.wait, 1.0, clock=dev.clock)
    # opens at the stamp after step 1; closes at the first stamp >= 1 s
    assert w.steps == 8 and w.seconds == 1.0
    assert w.results == list(range(2, 10))
    assert dev.dispatched == 10           # one more was in flight, not counted
    assert w.step_intervals_ms == [125.0] * 8


def test_a_stall_lands_in_the_tail_not_in_the_median():
    dev = FakeDevice(slow_step=5)
    w = timing.run_window(dev.dispatch, dev.wait, 2.0, clock=dev.clock)
    ms = w.step_intervals_ms
    assert max(ms) == pytest.approx(500.0)
    assert timing.quantile(ms, 0.5) == 125.0
    assert (w.steps - 1) * 0.125 + 0.5 == w.seconds


def test_max_steps_closes_the_window():
    dev = FakeDevice()
    w = timing.run_window(dev.dispatch, dev.wait, float("inf"), max_steps=4,
                          clock=dev.clock)
    assert w.steps == 4


def test_quantile_is_numpys():
    xs = [3.0, 1.0, 4.0, 1.0, 5.0, 9.0, 2.0, 6.0]
    for q in (0.0, 0.5, 0.9, 1.0):
        assert timing.quantile(xs, q) == pytest.approx(np.quantile(xs, q))
    with pytest.raises(ValueError):
        timing.quantile([], 0.5)


def test_batches_come_from_the_seed_and_keep_their_shape():
    spec = {"kind": "zipf", "exponent": 1.0, "reserved_ids": 3}
    big = 2**31 + 12345                   # the driver's seeds are large
    a = data.TokenBatches(spec, 50257, 4, 16, big)
    b = data.TokenBatches(spec, 50257, 4, 16, big)
    c = data.TokenBatches(spec, 50257, 4, 16, big + 1)
    ids, tgt, pos = a.next()
    ids_b, tgt_b, _ = b.next()
    assert np.array_equal(ids, ids_b) and np.array_equal(tgt, tgt_b)
    assert not np.array_equal(ids, c.next()[0])
    assert not np.array_equal(ids, a.next()[0])      # fresh each step
    assert ids.shape == tgt.shape == pos.shape == (4, 16)
    assert ids.dtype == np.int32
    assert np.array_equal(ids[:, 1:], tgt[:, :-1])   # next-token targets
    assert ids.min() >= 3 and ids.max() < 50257
    assert np.array_equal(pos[0], np.arange(16))


def test_zipf_favours_low_ranks():
    spec = {"kind": "zipf", "exponent": 1.0, "reserved_ids": 0}
    ids = data.TokenBatches(spec, 1000, 64, 256, 0).next()[0]
    assert (ids == 0).mean() == pytest.approx(1 / np.log(1000) / 1.08,
                                              rel=0.2)


def test_a_data_kind_is_a_file():
    with pytest.raises(SystemExit, match="no data/uniform.py"):
        load_module("data", "uniform")
