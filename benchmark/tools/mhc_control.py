"""What the `train_mhc` check reads for the sound program and for a control,
in the runner's own numbers.

    python3 benchmark/tools/mhc_control.py --workload <cell> --seed <n> \
        [--control bf16_maps|plain_rope] [--rehearse]

Runs the cell's runner as `run.py` does, with a window of no length (the
check is the step's first call, before any window), and prints the runner's
`check` log line with the control's name added. A control is the program
itself with one thing in the precision below the one the configuration
states, or left out:

* `bf16_maps`: every mixer's maps computed in bfloat16 where the
  configuration says float32 (benchmark/configs/xing4-29b-a4b.json,
  `assumed.mixer_precision`): the streams and W rounded to bfloat16 into
  the product, and m, the sigmoids, exp and every Sinkhorn round's result
  rounded to bfloat16's 8 mantissa bits (`lax.reduce_precision`: a pair of
  converts the compiler drops as excess precision, and a control that reads
  as the sound program is no control; PERF.md section 6, PR 33);
* `plain_rope`: the rotary tables of plain RoPE where the configuration
  says YaRN (the blended frequencies left out; the softmax scale's
  mscale^2 stays).

`runners/train_mhc.HC_RTOL`: `hc_colsum`'s limit stands between the sound
runs' largest reading and `bf16_maps`' smallest, `hc_grad`'s between the
sound runs' largest and `plain_rope`'s smallest (PERF.md, section 2). For
the record: `bf16_maps` reads as the sound program in `hc_grad`, and
`plain_rope` passes `shared_grad`'s limit too. On the chip one run a
process: the reference and the step fill the chip.
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


def _bf16_maps():
    import jax
    import jax.numpy as jnp
    from jax import lax
    from distributed_pytorch_from_scratch_tpu.parallel.hyper import (
        StreamMaps, StreamMixer)

    bf = lambda x: lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    def maps(self, params, X):
        """`StreamMixer.maps` with every result rounded to bfloat16."""
        n, d = self.n, self.d
        alpha = bf(params["alpha"].astype(jnp.float32))
        b = bf(params["b"].astype(jnp.float32))[:, None]
        xf = bf(X.reshape(n, -1, d).astype(jnp.float32))
        w = bf(params["w"].astype(jnp.float32)).reshape(n, d, self.width)
        with jax.named_scope("mhc"):
            with jax.named_scope("maps"):
                m = bf(jnp.einsum("ntc,nck->kt", xf.astype(jnp.bfloat16),
                                  w.astype(jnp.bfloat16),
                                  preferred_element_type=jnp.float32))
                mean_sq = bf(jnp.sum(jnp.square(xf), axis=(0, 2)) / (n * d))
                m = bf(m * bf(lax.rsqrt(mean_sq + self.norm_eps)))
                pre = bf(bf(jax.nn.sigmoid(bf(alpha[0] * m[:n] + b[:n])))
                         + self.eps)
                if self.exit_only:
                    return StreamMaps(pre, None, None)
                post = bf(2.0 * bf(jax.nn.sigmoid(
                    bf(alpha[1] * m[n:2 * n] + b[n:2 * n]))))
                h = bf(jnp.clip(bf(alpha[2] * m[2 * n:] + b[2 * n:]),
                                self.clamp_min, self.clamp_max))
            with jax.named_scope("sinkhorn"):
                mat = bf(jnp.exp(h.reshape(n, n, -1)))
                for _ in range(self.sinkhorn_iters):
                    mat = bf(mat / bf(jnp.sum(mat, axis=1, keepdims=True)
                                      + self.eps))
                    mat = bf(mat / bf(jnp.sum(mat, axis=0, keepdims=True)
                                      + self.eps))
            return StreamMaps(pre, post, mat)

    return StreamMixer, "maps", maps


def _plain_rope():
    from distributed_pytorch_from_scratch_tpu.models.mla_moe import (
        LatentMoETransformer)
    from distributed_pytorch_from_scratch_tpu.models.stack import (
        DecoderStack)

    def _positions(self, params, x, position_ids, dtype):
        """The stack's own: `rope_angles` with no scaling."""
        return DecoderStack._positions(self, params, x, position_ids, dtype)

    return LatentMoETransformer, "_positions", _positions


CONTROLS = {"bf16_maps": _bf16_maps, "plain_rope": _plain_rope}


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
