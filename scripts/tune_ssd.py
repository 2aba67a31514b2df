"""Time the state-space recurrence's Pallas kernels alone on the attached
TPU chip, at the two benchmark cells' shapes (cell 15: 64 heads, one group,
chunks of 256; cell 13: 32 heads, two groups, chunks of 128; one sequence of
4096 tokens, heads of 64, a state of 128, bf16).

    python scripts/tune_ssd.py [--cells 15,13] [--blocks 4,8,16] [--check]

prints, in device milliseconds from a profiler capture (the host clock
around a call this short also reads the dispatch):

  - `ssd_fwd` (with and without the residual states) and `ssd_bwd`
    (ops/pallas/ssd.py) for every `--blocks` (the most heads a grid step,
    `HEAD_BLOCK`), and the whole rule, value and gradients, as the kernels'
    path and as the XLA text (`ops/ssd._ssd_text`): busy time;
  - with `--check`, the kernels against the text ON THE CHIP, both held to
    the text in float32 at `Precision.HIGHEST`: the value, the five
    gradients and `decay_min` (scripts/tpu_checks.py's `ssd_checks`).

The variants are built HERE, by setting the module's constant before a
trace; the program has no switch for them. The readings behind the
constant are PERF.md's (section 6, PR 69; TPU v5 lite).
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from distributed_pytorch_from_scratch_tpu.ops import ssd as rule
from distributed_pytorch_from_scratch_tpu.ops.pallas import ssd as kernels
from distributed_pytorch_from_scratch_tpu.runtime.compile_cache import (
    enable_compile_cache)
from tpu_checks import CELL_SHAPES, ssd_inputs, ssd_errors
from tune_delta_rule import capture_ms, named


def time_cell(cell: str, blocks, check: bool) -> dict:
    H, G, chunk = CELL_SHAPES[cell]
    args = ssd_inputs(1, 4096, H, G, jnp.bfloat16)
    w = jax.random.normal(jax.random.key(9), args[0].shape, jnp.bfloat16)
    loss = lambda fn: lambda *a: jnp.sum(
        (fn(*a)[0] * w).astype(jnp.float32))
    out = {"cell": cell, "heads": H, "groups": G, "chunk": chunk}
    text = lambda *a: rule._ssd_text(*a, chunk, jnp.float32)
    ms = capture_ms(jax.jit(jax.grad(loss(text), range(5))), *args)
    out["text fwd+bwd busy"] = round(ms["busy"], 3)
    out["text fwd busy"] = round(
        capture_ms(jax.jit(text), *args)["busy"], 3)
    for block in blocks:
        kernels.HEAD_BLOCK = block
        # a fresh function a block: a trace is cached by its function
        kern = lambda *a: rule.ssd(*a, chunk)
        fwd = capture_ms(jax.jit(kern), *args)
        both = capture_ms(jax.jit(jax.grad(loss(kern), range(5))), *args)
        out[f"block {kernels.head_block(H // G)}"] = {
            "ssd_fwd": round(named(fwd, kernels.FWD_NAME), 3),
            "fwd busy": round(fwd["busy"], 3),
            "ssd_fwd with states": round(named(both, kernels.FWD_NAME), 3),
            "ssd_bwd": round(named(both, kernels.BWD_NAME), 3),
            "fwd+bwd busy": round(both["busy"], 3)}
        if check:
            out[f"block {kernels.head_block(H // G)}"]["errors"] = \
                ssd_errors(args, chunk, interpret=False)
    return out


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--cells", default="15,13")
    p.add_argument("--blocks", default="8")
    p.add_argument("--check", action="store_true")
    p.add_argument("--out", default=None)
    args = p.parse_args()
    enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("tune_ssd: no TPU attached; a time comes from the "
                         "chip alone")
    default = kernels.HEAD_BLOCK
    results = [time_cell(cell, [int(b) for b in args.blocks.split(",")],
                         args.check) for cell in args.cells.split(",")]
    kernels.HEAD_BLOCK = default
    record = {"device": dev.device_kind, "results": results}
    print(json.dumps(record, indent=1))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(record, f, indent=1)


if __name__ == "__main__":
    main()
