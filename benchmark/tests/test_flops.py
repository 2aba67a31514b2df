"""The FLOP and byte counts against hand-worked numbers."""

import json
import os

import pytest

from benchmark.families import gpt2
from benchmark.lib import flops, peaks

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def sizes(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return gpt2.sizes_of(json.load(f))


# N by hand: V*d + P*d + L*(12 d^2 + 13 d) + 2 d
#   medium: 50257*1024 + 1024*1024 + 24*(12*1024^2 + 13*1024) + 2048
#   large:  50257*1280 + 1024*1280 + 36*(12*1280^2 + 13*1280) + 2560
@pytest.mark.parametrize("name,params,gflop_per_token", [
    ("gpt2-medium", 354_823_168, 2.43),
    ("gpt2-large", 774_030_080, 5.21),
])
def test_train_flops_per_token(name, params, gflop_per_token):
    s = sizes(name)
    assert flops.param_count(s) == params
    # 6 N + 12 L h hd T, T = 1024, attention at the full T^2
    by_hand = 6 * params + 12 * s.n_layer * s.d_model * 1024
    assert flops.train_flops_per_token(s, 1024) == by_hand
    assert round(by_hand / 1e9, 2) == gflop_per_token


def test_share_of_flops_medium():
    """The cell's `why`: layer-body matmuls 75%, head 13%, attention 12%."""
    s = sizes("gpt2-medium")
    total = flops.train_flops_per_token(s, 1024)
    body = 6 * s.n_layer * 12 * s.d_model ** 2
    head = 6 * s.vocab * s.d_model
    attention = 12 * s.n_layer * s.d_model * 1024
    assert [round(100 * x / total) for x in (body, head, attention)] == [
        75, 13, 12]


def test_flash_call_cost_by_hand():
    # 2 rows of 4 x 8, 2-byte elements: 2 * 4*5/2 = 20 score entries
    fwd = flops.flash_call_cost(2, 4, 8, 2, backward=False)
    assert fwd.flops == 4 * 20 * 8
    assert fwd.bytes == 4 * (2 * 4 * 8 * 2) + 2 * 4 * 4
    bwd = flops.flash_call_cost(2, 4, 8, 2, backward=True)
    assert bwd.flops == 10 * 20 * 8
    assert bwd.bytes == 8 * (2 * 4 * 8 * 2) + 2 * (2 * 4 * 4)


def test_roofline_says_which_bound_binds():
    assert flops.roofline_seconds(flops.CallCost(200.0, 10.0), 100.0,
                                  10.0) == (2.0, "compute")
    assert flops.roofline_seconds(flops.CallCost(50.0, 20.0), 100.0,
                                  10.0) == (2.0, "memory")
    # the cell's flash forward on a v5e: compute bound
    v5e = peaks.peak_for("TPU v5 lite")
    cost = flops.flash_call_cost(12 * 16, 1024, 64, 2, backward=False)
    assert flops.roofline_seconds(cost, v5e.flops_per_s,
                                  v5e.hbm_bytes_per_s)[1] == "compute"


def test_unknown_chip_raises():
    assert peaks.peak_for("TPU v5e").flops_per_s == 197e12
    with pytest.raises(ValueError):
        peaks.peak_for("cpu")
