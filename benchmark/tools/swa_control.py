"""What the `train_swa_moe` check reads for the sound program and for a
control, in the runner's own numbers.

    python3 benchmark/tools/swa_control.py --workload <cell> --seed <n> \
        [--control window_as_causal|fp8_attn_inputs|fp8_router_inputs| \
                   bf16_router|rule_off|rule_stilled] \
        [--seconds <s>] [--trace <0|1>] [--rehearse]

Runs the cell's runner as `run.py` does, by default with a window of no
length (the check is the step's first call, before any window), and prints
the runner's `check` log line with the control's name added; with
`--seconds` the run's last line too (`--trace 1` for its per-layer
metrics: what a knock-out costs or saves in time). A control is the program
itself with one thing wrong:

* `window_as_causal`: the family declares no mask for its window layers, so
  they attend to their whole past under the causal call (the knock-out of
  ISSUE 46: what the window's kernel path is worth, and the check must
  FAIL it);
* `fp8_attn_inputs`: q, k and v as they enter the attention rounded to
  float8_e4m3 (the cell states bfloat16), forward only
  (`tools/hybrid_control._fp8`, and benchmark/tools/moe_control.py says why
  no pair of `astype`s): the precision below the cell's;
* `fp8_router_inputs`: the router's input rounded to float8_e4m3 (it
  arrives in bfloat16, the cell's compute dtype): the precision below;
* `bf16_router`: the router's product in ONE bfloat16 pass, which is its
  weights rounded to bfloat16 too and the sum kept in float32 (the program
  states float32 at precision highest over a bfloat16 input). For the
  record: it reads as the sound program, whose input has already rounded by
  as much (PERF.md section 2; cell 5's control of the same name read so);
* `rule_off`: the family publishes no speed, so nothing updates the
  selection bias (the check's `bias_rule` must fail). At the published
  widths that is ANOTHER program, whose plan is 51 MB over the chip where
  the sound step's fits to 12 MB (my chip run, PR 46), so on the chip:
* `rule_stilled`: the rule at a speed of 1e-30, the sound program with one
  constant changed: the bias moves by what no score feels, `bias_rule`
  fails, and a timed run shows `moe.load_max_over_mean` without the rule.

Each limit of `runners/train_swa_moe.SWA_RTOL` stands between the sound
runs' largest reading and a control's smallest (PERF.md, section 2). On the
chip one run a process: the reference and the step fill the chip.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.tools.hybrid_control import _fp8  # noqa: E402 (after the path)


def _window_as_causal():
    from distributed_pytorch_from_scratch_tpu.models.swa_moe import (
        SlidingWindowMoETransformer)
    return [(SlidingWindowMoETransformer, "_attn_mask",
             lambda self, t, kind=None: None)]


def _fp8_attn_inputs():
    from distributed_pytorch_from_scratch_tpu.models import stack
    masked, causal = stack.masked_attention, stack.causal_attention

    def under_mask(q, k, v, mask, impl="auto"):
        return masked(_fp8(q), _fp8(k), _fp8(v), mask, impl=impl)

    def under_triangle(q, k, v, impl="auto", t_real=None):
        return causal(_fp8(q), _fp8(k), _fp8(v), impl=impl, t_real=t_real)

    return [(stack, "masked_attention", under_mask),
            (stack, "causal_attention", under_triangle)]


def _router_operands(rounded, weights_too: bool):
    """`SharedRoutedFFN.route` with its input (and its weights) rounded:
    the program's own text around them, so the step's plan stays the sound
    step's."""
    import jax.numpy as jnp
    from distributed_pytorch_from_scratch_tpu.parallel.moe import (
        SharedRoutedFFN)
    sound = SharedRoutedFFN.route

    def route(self, params, xf):
        if weights_too:
            params = {**params, "router": rounded(params["router"])}
        return sound(self, params, rounded(xf.astype(jnp.float32)))

    return [(SharedRoutedFFN, "route", route)]


def _fp8_router_inputs():
    return _router_operands(_fp8, weights_too=False)


def _bf16_router():
    from jax import lax
    # `reduce_precision`: XLA:TPU drops an `astype` pair as excess
    # precision (benchmark/tools/moe_control.py)
    return _router_operands(lambda x: lax.reduce_precision(
        x, exponent_bits=8, mantissa_bits=7), weights_too=True)


def _rule_at(speed):
    from distributed_pytorch_from_scratch_tpu.models.swa_moe import (
        SlidingWindowMoETransformer)
    return [(SlidingWindowMoETransformer, "router_bias_speed", speed)]


CONTROLS = {"window_as_causal": _window_as_causal,
            "fp8_attn_inputs": _fp8_attn_inputs,
            "fp8_router_inputs": _fp8_router_inputs,
            "bf16_router": _bf16_router,
            "rule_off": lambda: _rule_at(None),
            "rule_stilled": lambda: _rule_at(1e-30)}


def reading(workload: str, seed: int, control=None, rehearse=False,
            seconds: float = 0.0, trace: int = 0) -> dict:
    """The runner's `check` log line for one run of the cell, and the run's
    last line where it was timed."""
    from benchmark import run
    argv = ["--workload", workload, "--seed", str(seed),
            "--seconds", str(seconds), "--trace", str(trace)]
    argv.append("--unpinned")      # weights and batches from the seed
    if rehearse:
        argv.append("--rehearse")
    with contextlib.ExitStack() as undo:
        for owner, name, patched in (CONTROLS[control]() if control else ()):
            undo.callback(setattr, owner, name, getattr(owner, name))
            setattr(owner, name, patched)
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            run.main(argv)
    lines = [json.loads(x) for x in out.getvalue().splitlines()
             if x.startswith("{")]
    check = next(x for x in lines if x.get("event") == "check")
    said = {"seed": seed, "control": control, **check}
    if seconds:
        said["window"] = next(x for x in lines if x.get("event") == "window")
        said["result"] = {k: v for k, v in lines[-1].items()
                          if k != "breakdown"}
        if "breakdown" in lines[-1]:
            said["scopes_ms_per_step"] = lines[-1]["breakdown"].get(
                "scopes_ms_per_step")
    return said


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--control", choices=sorted(CONTROLS), default=None)
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    print(json.dumps(reading(args.workload, args.seed, args.control,
                             args.rehearse, args.seconds, args.trace)),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
