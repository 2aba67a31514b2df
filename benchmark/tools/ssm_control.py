"""What the `train_ssm_moe` check reads for the sound program and for a
control, in the runner's own numbers.

    python3 benchmark/tools/ssm_control.py --workload <cell> --seed <n> \
        [--control bf16_state|one_group|relu_experts] [--rehearse]

Runs the cell's runner as `run.py` does, with a window of no length (the
check is the step's first call, before any window), and prints the runner's
`check` log line with the control's name added. A control is the program
itself with one thing in the precision below the one the configuration
states, or one fact of the architecture left out:

* `bf16_state`: the recurrence's decay sums (a chunk's running `dt A`),
  every decay made from them and the states handed from chunk to chunk
  rounded to bfloat16's 8 mantissa bits where the configuration says
  float32 (benchmark/configs/nemotron-3-super-120b-a12b.json,
  `assumed.recurrence_state`): `ops/ssd.ssd(state_dtype=bfloat16)`, which
  rounds with `lax.reduce_precision` (a pair of converts the compiler drops
  as excess precision, and a control that reads as the sound program is no
  control: PERF.md section 6, PR 33);
* `one_group`: every head reads group 0's B and C, where head h reads group
  `h // (H / G)`'s;
* `relu_experts`: the experts', held and shared, activation is ReLU and not
  its square.

`runners/train_ssm_moe.SSM_RTOL`: `ssm_grad`'s limit stands between the
sound runs' largest reading and the smallest of `bf16_state`'s and
`one_group`'s; `relu_experts` must fail `moe_grad`
(`train_scopes.MOE_RTOL`). PERF.md section 2 has the readings. On the chip
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


def _bf16_state():
    import functools
    import jax.numpy as jnp
    from distributed_pytorch_from_scratch_tpu.parallel import mamba
    return [(mamba, "ssd", functools.partial(mamba.ssd,
                                             state_dtype=jnp.bfloat16))]


def _one_group():
    import jax.numpy as jnp
    from distributed_pytorch_from_scratch_tpu.parallel import mamba
    sound = mamba.ssd

    def ssd(x, dt, A, B, C, chunk):
        first = lambda a: jnp.broadcast_to(a[:, :, :1], a.shape)
        return sound(x, dt, A, first(B), first(C), chunk)

    return [(mamba, "ssd", ssd)]


def _relu_experts():
    import jax
    from distributed_pytorch_from_scratch_tpu.parallel import moe
    # (the layer looks its activation up by name when it is applied)
    return [(moe, "ACTIVATIONS", {**moe.ACTIVATIONS, "relu2": jax.nn.relu})]


CONTROLS = {"bf16_state": _bf16_state, "one_group": _one_group,
            "relu_experts": _relu_experts}


def reading(workload: str, seed: int, control=None, rehearse=False) -> dict:
    """The runner's `check` log line for one run of the cell."""
    from benchmark import run
    argv = ["--workload", workload, "--seed", str(seed), "--seconds", "0"]
    argv.append("--unpinned")      # weights and batches from the seed
    if rehearse:
        argv.append("--rehearse")
    with contextlib.ExitStack() as undo:
        if control:
            for owner, name, patched in CONTROLS[control]():
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
