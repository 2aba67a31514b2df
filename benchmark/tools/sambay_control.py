"""What the `train_sambay` check reads for the sound program and for a wrong
one, in the runner's own numbers.

    python3 benchmark/tools/sambay_control.py --workload <cell> --seed <n> \
        [--control bf16_state|lambda_at_init|no_out_scale|memory_after_gate|
                   cross_own_keys|window_unbounded|
                   memory_reader_dropped|kv_reader_dropped] [--rehearse]

Runs the cell's runner as `run.py` does, with a window of no length (the
check is the step's first call, before any window), and prints the runner's
`check` log line with the control's name added. A control is the program
itself with one thing in the precision below the one the configuration
states, or one fact of the architecture left out:

* `bf16_state`: the scan's decays `exp(dt A)` and its state rounded to
  bfloat16's 8 mantissa bits where the configuration says float32
  (benchmark/configs/phi-4-mini-flash-reasoning.json, `assumed.scan_state`):
  `ops/selective_scan.selective_scan(state_dtype=bfloat16)`, whose state is
  a bfloat16 loop carry (a rounding the compiler cannot drop as excess
  precision);
* `lambda_at_init`: `lambda` held at `lambda_init`, the four learned vectors
  unread;
* `no_out_scale`: the normed heads NOT multiplied by `1 - lambda_init`;
* `memory_after_gate`: layer 16 leaves `y * silu(z)`, its gated output, as
  the memory, where the published model leaves `y`;
* `cross_own_keys`: the cross layer reads keys made of ITS OWN input (the
  normed activation's first 1280 columns) in place of layer 17's;
* `window_unbounded`: the `swa` layers read the whole triangle (a window
  ONE key short, 511 of 512, read as the sound program on the chip in
  bfloat16, every reading inside the sound runs' range: my chip run, PR
  76; its guard is the float32 test, tests/test_sambay.py, so it is no
  control here);
* `memory_reader_dropped`, `kv_reader_dropped`: a reader's cotangent
  dropped from the memory (the gated memory unit reads it under a
  stop-gradient), or from layer 17's keys and values (the cross layer
  does): the SUM over the readers is what the stack's shared values are for.

`runners/train_sambay.GRAD_RTOL` and `train.RTOL`: every control must read
over at least one limit, and every sound run under all of them. PERF.md
section 2 has the readings. On the chip one run a process: the reference
and the step fill the chip.
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
    from distributed_pytorch_from_scratch_tpu.parallel import mamba1
    return [(mamba1, "selective_scan", functools.partial(
        mamba1.selective_scan, state_dtype=jnp.bfloat16))]


def _attention(name, patched):
    def patch():
        from distributed_pytorch_from_scratch_tpu.parallel import (
            diff_attention)
        return [(diff_attention.DifferentialAttention, name, patched)]
    return patch


def _window(width):
    """The `swa` layers' mask with a window of `width` keys (None: the
    triangle)."""
    def patch():
        from distributed_pytorch_from_scratch_tpu.models import sambay
        from distributed_pytorch_from_scratch_tpu.ops.attention import (
            sliding_window)

        def mask(self, t, kind=None):
            return (sliding_window(width)
                    if kind == "swa" and width is not None else None)
        return [(sambay.SambaYTransformer, "_attn_mask", mask)]
    return patch


def _mixing(change):
    """`SambaYTransformer._mix_sharing` with `change(self, kind, lp, y,
    dtype, shared, out)` -> (shared to read, out to return) around it."""
    def patch():
        from distributed_pytorch_from_scratch_tpu.models import sambay
        sound = sambay.SambaYTransformer._mix_sharing

        def mix(self, lp, y, dtype, kind, told, shared):
            shared = change(self, kind, lp, y, dtype, shared, None)[0]
            out = sound(self, lp, y, dtype, kind, told, shared)
            return change(self, kind, lp, y, dtype, shared, out)[1]
        return [(sambay.SambaYTransformer, "_mix_sharing", mix)]
    return patch


def _memory_after_gate(self, kind, lp, y, dtype, shared, out):
    if kind != "memory" or out is None:
        return shared, out
    from distributed_pytorch_from_scratch_tpu.parallel.mamba1 import gate
    _, z, _ = self._mods["mamba"].scan(lp["mamba"], y, dtype)
    return shared, (out[0], out[1], {"memory": gate(out[2]["memory"], z)})


def _cross_own_keys(self, kind, lp, y, dtype, shared, out):
    if kind == "cross":
        shared = {**shared, "k": y[..., :self.kv_dim].astype(dtype)}
    return shared, out


def _dropped(reader, names):
    def change(self, kind, lp, y, dtype, shared, out):
        if kind == reader:
            import jax
            shared = {**shared, **{n: jax.lax.stop_gradient(shared[n])
                                   for n in names}}
        return shared, out
    return change


CONTROLS = {
    "bf16_state": _bf16_state,
    "lambda_at_init": _attention(
        "lambda_of", lambda self, params, lambda_init: lambda_init),
    "no_out_scale": _attention("out_scale", lambda self, lambda_init: 1.0),
    "memory_after_gate": _mixing(_memory_after_gate),
    "cross_own_keys": _mixing(_cross_own_keys),
    "window_unbounded": _window(None),
    "memory_reader_dropped": _mixing(_dropped("gmu", ("memory",))),
    "kv_reader_dropped": _mixing(_dropped("cross", ("k", "v"))),
}


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
