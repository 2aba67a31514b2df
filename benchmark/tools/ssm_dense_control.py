"""What the `train_ssm_dense` check reads for the sound program and for a
wrong one, in the runner's own numbers.

    python3 benchmark/tools/ssm_dense_control.py --workload <cell> --seed <n> \
        [--control bf16_state|residual_one|softmax_default|logits_unscaled|
                   embed_unscaled|norm_before_gate] [--rehearse]

Runs the cell's runner as `run.py` does, with a window of no length (the
check is the step's first call, before any window), and prints the runner's
`check` log line with the control's name added. A control is the program
itself with one thing in the precision below the one the configuration
states, or one fact of the architecture left out:

* `bf16_state`: the recurrence's decay sums (a chunk's running `dt A`),
  every decay made from them and the states handed from chunk to chunk
  rounded to bfloat16's 8 mantissa bits where the configuration says
  float32 (benchmark/configs/granite-4.0-h-micro.json,
  `assumed.recurrence_state`): `ops/ssd.ssd(state_dtype=bfloat16)`, which
  rounds with `lax.reduce_precision` (a pair of converts the compiler drops
  as excess precision, and a control that reads as the sound program is no
  control: PERF.md section 6, PR 33);
* `residual_one`: `residual_multiplier` 1, both sublayers' outputs added
  whole (`DecoderStack.residual_scale` None);
* `softmax_default`: the softmax over `q k^T / sqrt(64)`, the kernels' own
  scale, where the configuration says `attention_multiplier` 1 / 64
  (`softmax_scale` None);
* `logits_unscaled`: the tied head's logits not divided by `logits_scaling`
  8 (`logit_scale` None);
* `embed_unscaled`: the embedding's rows not multiplied by
  `embedding_multiplier` 12 (`embed_scale` 1);
* `norm_before_gate`: the mixer norms y and gates after, `w * RMSNorm(y) *
  silu(z)`, where the configuration says the gate first.

`runners/train_ssm_dense.GRAD_RTOL` and `train.RTOL`: every control must
read over at least one limit, and every sound run under all of them.
PERF.md section 2 has the readings. On the chip one run a process: the
reference and the step fill the chip.
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


def _fact(name, value):
    """The family's fact `name` at its neutral `value`."""
    def patch():
        from distributed_pytorch_from_scratch_tpu.models import ssm_dense
        return [(ssm_dense.SsmDenseTransformer, name,
                 property(lambda self: value))]
    return patch


def _norm_before_gate():
    import jax
    import jax.numpy as jnp
    from distributed_pytorch_from_scratch_tpu.parallel import mamba

    def norm_then_gate(self, w, y, z):
        b, t, _ = y.shape
        g = y.reshape(b, t, self.groups, -1)
        g = g * jax.lax.rsqrt(
            jnp.mean(g * g, axis=-1, keepdims=True) + self.eps)
        return (w * g.reshape(b, t, self.inner)
                * jax.nn.silu(z.astype(jnp.float32)))

    return [(mamba.Mamba2Mixer, "_gate_norm", norm_then_gate)]


CONTROLS = {"bf16_state": _bf16_state,
            "residual_one": _fact("residual_scale", None),
            "softmax_default": _fact("softmax_scale", None),
            "logits_unscaled": _fact("logit_scale", None),
            "embed_unscaled": _fact("embed_scale", 1.0),
            "norm_before_gate": _norm_before_gate}


def reading(workload: str, seed: int, control=None, rehearse=False) -> dict:
    """The runner's `check` log line for one run of the cell."""
    from benchmark import run
    argv = ["--workload", workload, "--seed", str(seed), "--seconds", "0"]
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
    check.pop("grad_by_leaf", None)
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
