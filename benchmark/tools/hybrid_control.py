"""What the `train_hybrid` check reads for the sound program and for a
control, in the runner's own numbers.

    python3 benchmark/tools/hybrid_control.py --workload <cell> --seed <n> \
        [--control fp8_rule_inputs|fp8_router_inputs] [--rehearse]

Runs the cell's runner as `run.py` does, with a window of no length (the
check is the step's first call, before any window), and prints the runner's
`check` log line with the control's name added. A control is the program
itself with one input taken in the precision below the one the cell states:

* `fp8_rule_inputs`: q, k and v as they enter the chunked gated delta rule
  rounded to float8_e4m3 (the cell states bfloat16);
* `fp8_router_inputs`: the router's input rounded to float8_e4m3.

Each limit of `runners/train_hybrid.HYBRID_RTOL` stands between the sound
runs' largest reading and a control's smallest (PERF.md, section 2). On the
chip one run a process: the reference and the step fill the chip. Rounded
with `lax.reduce_precision` (benchmark/tools/moe_control.py says why).
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


def _fp8(x):
    """x rounded to float8_e4m3 on the way FORWARD; the cotangent passes as
    it is. `reduce_precision`'s own transpose rounds the cotangent to the
    same format, whose smallest number is 2^-9: a fresh model's gradients
    are smaller and come out as zeros, and a control whose gradient error
    reads exactly 1.0 says nothing about precision (my chip run, PR 35)."""
    from jax import lax
    return x + lax.stop_gradient(
        lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3) - x)


def _fp8_rule_inputs():
    from distributed_pytorch_from_scratch_tpu.parallel import gdn
    sound = gdn.gated_delta_rule

    def rule(q, k, v, g, beta, **kw):
        return sound(_fp8(q), _fp8(k), _fp8(v), g, beta, **kw)

    return gdn, "gated_delta_rule", rule


def _fp8_router_inputs():
    import jax
    import jax.numpy as jnp
    from jax import lax
    from distributed_pytorch_from_scratch_tpu.parallel.moe import (
        SharedRoutedFFN)

    def route(self, params, xf):
        """`SharedRoutedFFN.route` for softmax scores with its input
        rounded."""
        s = jax.nn.softmax(jnp.dot(
            _fp8(xf.astype(jnp.float32)), params["router"],
            precision=lax.Precision.HIGHEST), axis=-1)
        _, chosen = lax.top_k(s, self.top_k)
        w = jnp.take_along_axis(s, chosen, axis=-1)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * self.scaling
        return chosen, w

    return SharedRoutedFFN, "route", route


CONTROLS = {"fp8_rule_inputs": _fp8_rule_inputs,
            "fp8_router_inputs": _fp8_router_inputs}


def reading(workload: str, seed: int, control=None, rehearse=False) -> dict:
    """The runner's `check` log line for one run of the cell."""
    from benchmark import run
    argv = ["--workload", workload, "--seed", str(seed), "--seconds", "0"]
    argv.append("--unpinned")      # weights and batches from the seed
    if rehearse:
        argv.append("--rehearse")
    with contextlib.ExitStack() as undo:
        if control:
            owner, name, patched = CONTROLS[control]()
            undo.callback(setattr, owner, name, getattr(owner, name))
            setattr(owner, name, patched)
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            run.main(argv)
    lines = [json.loads(x) for x in out.getvalue().splitlines()
             if x.startswith("{")]
    check = next(x for x in lines if x.get("event") == "check")
    return {"seed": seed, "control": control, **check}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--control", choices=sorted(CONTROLS), default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    print(json.dumps(reading(args.workload, args.seed, args.control,
                             args.rehearse)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
