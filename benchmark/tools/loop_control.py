"""What the `train_loop` check reads for the sound program and for a wrong
one, in the runner's own numbers.

    python3 benchmark/tools/loop_control.py --workload <cell> --seed <n> \
        [--control one_pass_short|no_norm_between|p_detached|no_entropy|
                   bf16_grad_sum|fp8_ffn_inputs] [--rehearse]

Runs the cell's runner as `run.py` does, with a window of no length (the
check is the step's first call, before any window), and prints the runner's
`check` log line with the control's name added. A control is the program
itself with one fact of the architecture left out, or one thing in the
precision below the one the configuration states:

* `one_pass_short`: the stack is passed R - 1 times (the step then counts
  R - 1 exits: `exit_losses` reads inf);
* `no_norm_between`: a pass reads the last pass's output as the layers left
  it, not the final norm's (the exits still read the normed state);
* `p_detached`: `p` under a `stop_gradient` (the weighted sum's and the
  entropy's), so the gate's gradient is what `log p` in the entropy passes;
* `no_entropy`: the entropy term is dropped (beta = 0);
* `bf16_grad_sum`: the layers' weights enter the scan of passes in
  bfloat16, so its transpose sums a weight's R gradients in bfloat16 where
  the program sums them in the parameters' float32 (ON THE CHIP IT READS AS
  THE SOUND PROGRAM, for the record: `shared_grad` 0.0333 against 0.0332;
  the rounding of a sum of four is under the bfloat16 gradients' own);
* `fp8_ffn_inputs`: the SwiGLU's input rounded to an 8-bit float's 3
  mantissa bits (`lax.reduce_precision`: a pair of converts the compiler
  drops as excess precision), the precision below the bfloat16 the cell
  states.

`runners/train_loop.LOOP_RTOL`: each limit stands between the sound runs'
largest reading and the smallest of the controls it is there to refuse.
PERF.md section 2 has the readings. On the chip one run a process.
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


def _one_pass_short():
    from distributed_pytorch_from_scratch_tpu.models import loop_llama
    return [(loop_llama.LoopedTransformer, "loop_steps", property(
        lambda self: self.cfg.loop_llama.loop_steps - 1))]


def _no_norm_between():
    from jax import lax
    from distributed_pytorch_from_scratch_tpu.models import stack

    def passes(self, one_pass, params, x):
        def body(z, _):
            z, _ = one_pass(z)
            return z, self.final_norm.apply(params["norm"], z)
        return lax.scan(body, x, None, length=self.loop_steps)[1], None

    return [(stack.DecoderStack, "_loop_passes", passes)]


def _bf16_grad_sum():
    import jax
    import jax.numpy as jnp
    from distributed_pytorch_from_scratch_tpu.models import stack
    sound = stack.DecoderStack._trunk

    def trunk(self, params, *args, **kw):
        low = {**params, "layers": jax.tree.map(
            lambda a: a.astype(jnp.bfloat16), params["layers"])}
        return sound(self, low, *args, **kw)

    return [(stack.DecoderStack, "_trunk", trunk)]


def _p_detached():
    from jax import lax
    from distributed_pytorch_from_scratch_tpu.models import stack
    sound = stack.exit_distribution

    def detached(z):
        p, log_p = sound(z)
        return lax.stop_gradient(p), log_p

    return [(stack, "exit_distribution", detached)]


def _no_entropy():
    from distributed_pytorch_from_scratch_tpu.models import loop_llama
    return [(loop_llama.LoopedTransformer, "exit_entropy_coef",
             property(lambda self: 0.0))]


def _fp8_ffn_inputs():
    from jax import lax
    from distributed_pytorch_from_scratch_tpu.models import loop_llama
    sound = loop_llama.LoopedTransformer._mlp

    def mlp(self, lp, y, tp, dtype):
        return sound(self, lp, lax.reduce_precision(y, 4, 3), tp, dtype)

    return [(loop_llama.LoopedTransformer, "_mlp", mlp)]


CONTROLS = {"one_pass_short": _one_pass_short,
            "no_norm_between": _no_norm_between, "p_detached": _p_detached,
            "no_entropy": _no_entropy, "bf16_grad_sum": _bf16_grad_sum,
            "fp8_ffn_inputs": _fp8_ffn_inputs}


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
