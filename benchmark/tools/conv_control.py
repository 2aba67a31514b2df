"""What the `train_conv_moe` check reads for the sound program and for a
control, in the runner's own numbers.

    python3 benchmark/tools/conv_control.py --workload <cell> --seed <n> \
        [--control fp8_conv_inputs|fp8_router_inputs] [--rehearse]

Runs the cell's runner as `run.py` does, with a window of no length (the
check is the step's first call, before any window), and prints the runner's
`check` log line with the control's name added. A control is the program
itself with one input taken in the precision below the one the cell states:

* `fp8_conv_inputs`: the gated product `B * u` as it enters the short
  convolution's taps rounded to float8_e4m3 (the cell states bfloat16);
* `fp8_router_inputs`: the router's input rounded to float8_e4m3.

Each limit of `runners/train_conv_moe.CONV_RTOL` stands between the sound
runs' largest reading and a control's smallest (PERF.md, section 2). On the
chip one run a process: the reference and the step fill the chip. Rounded
with `lax.reduce_precision`, forward only (`tools/hybrid_control._fp8`, and
benchmark/tools/moe_control.py says why no pair of `astype`s).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.tools import hybrid_control  # noqa: E402 (after the path)
from benchmark.tools.hybrid_control import _fp8  # noqa: E402


def _fp8_conv_inputs():
    from distributed_pytorch_from_scratch_tpu.parallel import shortconv
    sound = shortconv.causal_depthwise_conv

    def conv(u, w):
        return sound(_fp8(u), w)

    return shortconv, "causal_depthwise_conv", conv


def _fp8_router_inputs():
    import jax
    import jax.numpy as jnp
    from jax import lax
    from distributed_pytorch_from_scratch_tpu.parallel.moe import (
        SharedRoutedFFN)

    def route(self, params, xf):
        """`SharedRoutedFFN.route` for sigmoid scores with its input
        rounded."""
        s = jax.nn.sigmoid(jnp.dot(
            _fp8(xf.astype(jnp.float32)), params["router"],
            precision=lax.Precision.HIGHEST))
        _, chosen = lax.top_k(s + lax.stop_gradient(params["bias"]),
                              self.top_k)
        w = jnp.take_along_axis(s, chosen, axis=-1)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * self.scaling
        return chosen, w

    return SharedRoutedFFN, "route", route


CONTROLS = {"fp8_conv_inputs": _fp8_conv_inputs,
            "fp8_router_inputs": _fp8_router_inputs}


def reading(workload: str, seed: int, control=None, rehearse=False) -> dict:
    """`hybrid_control.reading` with this tool's controls."""
    saved = hybrid_control.CONTROLS
    hybrid_control.CONTROLS = CONTROLS
    try:
        return hybrid_control.reading(workload, seed, control, rehearse)
    finally:
        hybrid_control.CONTROLS = saved


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
