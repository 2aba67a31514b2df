"""The `train_scopes` check's control, at the cell's rehearsal shape. The
limits of `runners/train_scopes.MOE_RTOL` are read at the published widths
on the chip (PERF.md section 2) and say nothing at this shape; what holds at
every shape is that with the same seed the control (the held experts' inputs
rounded to float8_e4m3, the precision below the cell's bfloat16) reads worse
than the sound program in the runner's own number."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib.files import load_module  # noqa: E402

CELL = "joyai-llm-flash.train-ep16share-b4-t4096"


@pytest.mark.parametrize("seed", [1, 2147483693])
def test_the_fp8_control_reads_worse_than_the_program(seed):
    tool = load_module("tools", "moe_control")
    sound = tool.reading(CELL, seed, None, rehearse=True)
    control = tool.reading(CELL, seed, "fp8_expert_inputs", rehearse=True)
    experts = lambda r: max(max(v) for k, v in r["moe_grad_by_leaf"].items()
                            if k.split("/")[-1] in ("gate", "up", "down")
                            and "shared" not in k)
    assert experts(control) > 5 * experts(sound), (sound, control)


def test_a_reading_over_a_limit_is_not_correct():
    runner = load_module("runners", "train_scopes")
    limit = runner.MOE_RTOL["bfloat16"]
    passed = {"ok": True, "rel_err": {}, "rtol": {}}
    routed = np.array([[40.0, 24.0, 0.0, 0.0]])
    want = {"layers/gate": np.ones((1, 2, 6), np.float32),
            "layers/shared/up": np.ones((1, 1, 6), np.float32)}
    compare = lambda r=routed, **off: runner._compare_moe(
        passed, "bfloat16", r, routed,
        {k: v * off.get(k.split("/")[1], 1.0) for k, v in want.items()},
        want)
    assert compare()["ok"]
    moved = np.array([[-64.0, 0.0, 64.0, 0.0]]) * limit["routed_moved"]
    assert compare(routed + 0.9 * moved)["ok"]
    assert not compare(routed + 1.1 * moved)["ok"]
    assert compare(gate=1 + 0.9 * limit["moe_grad"])["ok"]
    assert not compare(gate=1 + 1.1 * limit["moe_grad"])["ok"]
    # the shared expert's leaves have the tighter limit
    assert not compare(shared=1 + 1.1 * limit["shared_grad"])["ok"]
    assert not compare(gate=np.nan)["ok"]
    assert not runner._compare_moe({**passed, "ok": False}, "bfloat16",
                                   routed, routed, want, want)["ok"]
