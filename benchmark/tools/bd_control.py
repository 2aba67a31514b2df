"""What the `train_bd_moe` check reads for the sound program and for a
control, in the runner's own numbers.

    python3 benchmark/tools/bd_control.py --workload <cell> --seed <n> \
        [--control mask_off_by_one_block|causal_over_rows|fp8_attn_inputs] \
        [--rehearse]

Runs the cell's runner as `run.py` does, with a window of no length (the
check is the step's first call, before any window), and prints the runner's
`check` log line with the control's name added. A control is the program
itself with one thing wrong that a freshly initialised model's loss hardly
sees:

* `mask_off_by_one_block`: noised to clean live for `blk(j) <= blk(i)` where
  the mask says `<`: a noised block reads its own clean block, the answer
  leaks. Patched where the two attention paths read the declaration: the
  dense path's `mask_matrix` and the kernels' plan of the noised / clean
  diagonal tile (`_bd_plan`, role "nc": the clean diagonal's staircase);
* `causal_over_rows`: the family declares the plain causal mask over its 2L
  rows;
* `fp8_attn_inputs`: q, k and v as they enter the attention rounded to
  float8_e4m3 (the cell states bfloat16), forward only
  (`tools/hybrid_control._fp8`, and benchmark/tools/moe_control.py says why
  no pair of `astype`s).

Each limit of `runners/train_bd_moe.BD_RTOL` stands between the sound runs'
largest reading and a control's smallest (PERF.md, section 2). On the chip
one run a process: the reference and the step fill the chip.
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


def _mask_off_by_one_block():
    import jax.numpy as jnp
    from distributed_pytorch_from_scratch_tpu.ops import attention
    from distributed_pytorch_from_scratch_tpu.ops.pallas import (
        flash_attention as fa)

    def mask_matrix(mask, t):
        L, B = mask.half, mask.block
        i = jnp.arange(t)
        noised, blk = i < L, (i % L) // B
        q_noised, k_noised = noised[:, None], noised[None, :]
        q_blk, k_blk = blk[:, None], blk[None, :]
        return jnp.where(q_noised,
                         jnp.where(k_noised, k_blk == q_blk, k_blk <= q_blk),
                         ~k_noised & (k_blk <= q_blk))

    sound = fa._bd_plan.__wrapped__

    def bd_plan(role, block, stair, head_dim, backward, num_kb):
        if role != "nc":
            return sound(role, block, stair, head_dim, backward, num_kb)
        return sound("cc", block, stair, head_dim, backward,
                     num_kb)._replace(role="nc")

    return [(attention, "mask_matrix", mask_matrix),
            (fa, "_bd_plan", bd_plan)]


def _causal_over_rows():
    from distributed_pytorch_from_scratch_tpu.models.bd_moe import (
        BlockDiffusionMoETransformer)
    from distributed_pytorch_from_scratch_tpu.ops.attention import CAUSAL
    return [(BlockDiffusionMoETransformer, "_attn_mask",
             lambda self, t: CAUSAL)]


def _fp8_attn_inputs():
    from distributed_pytorch_from_scratch_tpu.models import stack
    sound = stack.masked_attention

    def attention(q, k, v, mask, impl="auto"):
        return sound(_fp8(q), _fp8(k), _fp8(v), mask, impl=impl)

    return [(stack, "masked_attention", attention)]


CONTROLS = {"mask_off_by_one_block": _mask_off_by_one_block,
            "causal_over_rows": _causal_over_rows,
            "fp8_attn_inputs": _fp8_attn_inputs}


def reading(workload: str, seed: int, control=None, rehearse=False) -> dict:
    """The runner's `check` log line for one run of the cell
    (`hybrid_control.reading`, with a control that may patch several
    names)."""
    from benchmark import run
    argv = ["--workload", workload, "--seed", str(seed), "--seconds", "0"]
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
