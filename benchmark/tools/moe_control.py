"""What the `train_scopes` check reads for the sound program and for a
control, in the runner's own numbers.

    python3 benchmark/tools/moe_control.py --workload <cell> --seed <n> \
        [--control fp8_expert_inputs|fp8_router_inputs|bf16_router] \
        [--rehearse]

Runs the cell's runner as `run.py` does, with a window of no length (the
check is the step's first call, before any window), and prints the runner's
`check` log line with the control's name added. A control is the program
itself with one input taken in the precision below the one the cell
states:

* `fp8_expert_inputs`: what goes into the held experts' gate / up products
  rounded to float8_e4m3 (the cell states bfloat16);
* `fp8_router_inputs`: the router's input rounded to float8_e4m3;
* `bf16_router`: the router's product in one bfloat16 pass (the program
  states float32 at precision "highest"). For the record: it reads as the
  sound program, whose router input bfloat16 has already rounded by as much
  (PERF.md section 2).

Each limit of `runners/train_scopes.MOE_RTOL` stands between the sound
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


def _fp8(x):
    """x rounded to float8_e4m3's 4 exponent and 3 mantissa bits
    (`reduce_precision`: the compiler drops a pair of converts as excess
    precision, and a control that reads as the sound program to the last
    digit is no control: my chip run, PR 33)."""
    from jax import lax
    return lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)


def _router(product):
    """`SharedRoutedFFN.route` with its scores' product replaced."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from distributed_pytorch_from_scratch_tpu.parallel.moe import (
        SharedRoutedFFN)

    def route(self, params, xf):
        s = jax.nn.sigmoid(product(xf, params["router"]))
        _, chosen = lax.top_k(s + lax.stop_gradient(params["bias"]),
                              self.top_k)
        w = jnp.take_along_axis(s, chosen, axis=-1)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * self.scaling
        return chosen, w

    return SharedRoutedFFN, "route", route


def _bf16_router():
    import jax.numpy as jnp
    return _router(lambda x, w: jnp.dot(
        x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32))


def _fp8_router_inputs():
    import jax.numpy as jnp
    from jax import lax
    return _router(lambda x, w: jnp.dot(
        _fp8(x.astype(jnp.float32)), w, precision=lax.Precision.HIGHEST))


def _fp8_expert_inputs():
    from jax import lax
    sound = lax.ragged_dot

    def ragged_dot(lhs, rhs, group_sizes, **kw):
        """`lax.ragged_dot` (only `SharedRoutedFFN` calls it) with the rows
        of the gate / up product rounded to float8_e4m3. That product's
        rows are d wide and its output 2 f, the down product's f and d,
        and d >= 2 f at both of the cell's shapes."""
        if lhs.shape[-1] >= rhs.shape[-1]:
            lhs = _fp8(lhs)
        return sound(lhs, rhs, group_sizes, **kw)

    return lax, "ragged_dot", ragged_dot


CONTROLS = {"bf16_router": _bf16_router,
            "fp8_router_inputs": _fp8_router_inputs,
            "fp8_expert_inputs": _fp8_expert_inputs}


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
