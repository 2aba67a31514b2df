"""The serving check's control, at a size a test run can hold. The control
is the program itself with its int8 pages switched on, the precision below
the cell's bfloat16 cache. The runner's limit is read at the published
widths on the chip (PERF.md section 2) and says nothing at this shape, where
bfloat16's own rounding swings more from seed to seed; what holds at every
shape is that with the same seed (the same weights and sequences) the
control reads worse than the sound engine, in the runner's own number."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib.files import load_module  # noqa: E402

CELL = "gpt2-medium.serve-chat-32slots"
SEEDS = [1, 2147483659, 3000000019]


def test_the_control_reads_worse_than_the_program_on_every_seed():
    tool = load_module("tools", "check_seeds")
    sound = tool.readings(CELL, SEEDS, None, rehearse=True)
    control = tool.readings(CELL, SEEDS, "int8_kv", rehearse=True)
    for s, c in zip(sound, control):
        assert c["rel_l2_mean"] > 1.05 * s["rel_l2_mean"], (s, c)


def test_a_reading_over_the_limit_is_not_correct():
    import numpy as np
    compare = load_module("runners", "serve").compare
    want = np.ones((2, 3, 8), np.float32)
    assert compare(want * 1.01, want, 0.0117)["ok"]
    assert not compare(want * 1.0125, want, 0.0117)["ok"]
    assert not compare(np.full_like(want, np.nan), want, 0.0117)["ok"]
