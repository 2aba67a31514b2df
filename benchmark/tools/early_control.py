"""What the `train_early_moe` check reads for the sound program and for a
control, in the runner's own numbers.

    python3 benchmark/tools/early_control.py --workload <cell> --seed <n> \
        [--control router_post_attention|silu_experts|window_as_causal| \
                   rope_on_full|fp8_expert_inputs|fp8_router_inputs] \
        [--seconds <s>] [--trace <0|1>] [--rehearse]

Runs the cell's runner as `run.py` does, by default with a window of no
length (the check is the step's first call, before any window), and prints
the runner's `check` log line with the control's name added; with
`--seconds` the run's last line too (`--trace 1` for its per-layer
metrics: what a knock-out costs or saves in time). A control is the program
itself with one thing wrong, and each must FAIL at least one limit of
`runners/train_early_moe.EARLY_RTOL`:

* `router_post_attention`: the family's routers read what every other
  family's read, the normed post-attention stream (`router_reads_layer_input`
  off): the experts chosen are others altogether;
* `silu_experts`: SiLU where the family states ReLU in the held experts'
  gate;
* `window_as_causal`: the family declares no mask for its window layers, so
  they attend to their whole past under the causal call (what the window's
  kernel path is worth, timed);
* `rope_on_full`: RoPE on q and k in the full-attention layer too
  (`unrotated_kinds` empty);
* `fp8_expert_inputs`: the experts' input (the normed post-attention
  stream, as `apply` receives it) rounded to float8_e4m3, forward only
  (`tools/hybrid_control._fp8`, and benchmark/tools/moe_control.py says why
  no pair of `astype`s): the precision below the cell's bfloat16;
* `fp8_router_inputs`: the router's input (the layer's input) rounded to
  float8_e4m3, forward only: the precision below.

Each limit stands between the sound runs' largest reading and a control's
smallest (PERF.md, section 2). On the chip one run a process: the reference
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

from benchmark.tools.hybrid_control import _fp8  # noqa: E402 (after the path)


def _family():
    from distributed_pytorch_from_scratch_tpu.models.early_moe import (
        EarlyRouterMoETransformer)
    return EarlyRouterMoETransformer


def _router_post_attention():
    return [(_family(), "router_reads_layer_input", False)]


def _silu_experts():
    import jax
    from distributed_pytorch_from_scratch_tpu.parallel import moe
    return [(moe, "ACTIVATIONS", {**moe.ACTIVATIONS, "relu": jax.nn.silu})]


def _window_as_causal():
    return [(_family(), "_attn_mask", lambda self, t, kind=None: None)]


def _rope_on_full():
    return [(_family(), "unrotated_kinds", ())]


def _fp8_expert_inputs():
    from distributed_pytorch_from_scratch_tpu.parallel.moe import (
        SharedRoutedFFN)
    sound = SharedRoutedFFN.apply

    def apply(self, params, x, compute_dtype, router_x=None):
        return sound(self, params, _fp8(x), compute_dtype, router_x)

    return [(SharedRoutedFFN, "apply", apply)]


def _fp8_router_inputs():
    """`SharedRoutedFFN.route` with its input rounded: the program's own
    text around it, so the step's plan stays the sound step's."""
    import jax.numpy as jnp
    from distributed_pytorch_from_scratch_tpu.parallel.moe import (
        SharedRoutedFFN)
    sound = SharedRoutedFFN.route

    def route(self, params, xf):
        return sound(self, params, _fp8(xf.astype(jnp.float32)))

    return [(SharedRoutedFFN, "route", route)]


CONTROLS = {"router_post_attention": _router_post_attention,
            "silu_experts": _silu_experts,
            "window_as_causal": _window_as_causal,
            "rope_on_full": _rope_on_full,
            "fp8_expert_inputs": _fp8_expert_inputs,
            "fp8_router_inputs": _fp8_router_inputs}


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
