"""What the `train_kda` check reads for the sound program and for a control,
in the runner's own numbers.

    python3 benchmark/tools/kda_control.py --workload <cell> --seed <n> \
        [--control scalar_decay|fp8_rule_inputs|no_groups|bf16_decay_and_state]
        [--rehearse]

Runs the cell's runner as `run.py` does, with a window of no length (the
check is the step's first call, before any window), and prints the runner's
`check` log line with the control's name added. A control is the program
itself with one thing in the precision below the one the configuration
states, or left out:

* `fp8_rule_inputs`: q, k and v as they enter the chunked rule rounded to
  float8_e4m3 (the cell states bfloat16), as cell 6's control of the same
  name rounds its rule's;
* `bf16_decay_and_state`: the delta rule's decay `g`, its running sums `G`
  (`ops/delta_rule._running_decay`: every decay ratio of a chunk is the
  exponential of a difference of two of its rows), every operand made from
  them and the carried state `S` rounded to bfloat16's 8 mantissa bits
  where the configuration says float32
  (benchmark/configs/ling-3-flash.json, `assumed.rule_state`); rounded with
  `lax.reduce_precision` (a pair of converts the compiler drops as excess
  precision, and a control that reads as the sound program is no control:
  PERF.md section 6, PR 33);
* `scalar_decay`: ONE decay a head, the channels' mean of `g`: the third
  family's rule (cell 6's) under this model's name;
* `no_groups`: the top-8 over all 512 experts, the selection's groups left
  out.

`runners/train_kda.KDA_RTOL`: `kda_grad`'s limit stands between the sound
runs' largest reading and the smallest of `scalar_decay`'s and
`fp8_rule_inputs`'; `no_groups` must pass `routed_moved`'s limit
(`train_scopes.MOE_RTOL`). **`bf16_decay_and_state` reads as the sound
program on fresh weights, in every reading, and is kept for the record**:
a fresh layer forgets slowly (`|G|` under about 1 inside a chunk for all
but a few channels), so bfloat16's step on `G`, 0.4%, is the step of the
bfloat16 operands the products take anyway; what holds the rule to
float32 is `tests/test_kda_mla_moe.py`'s case at the gate's bound on every
channel, where `G` reaches -320 (PERF.md, section 2). On the chip one run a process: the reference and the
step fill the chip.
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


def _bf16_decay_and_state():
    import functools
    import jax
    import jax.numpy as jnp
    from jax import lax
    from distributed_pytorch_from_scratch_tpu.ops import delta_rule as dr
    from distributed_pytorch_from_scratch_tpu.ops.collectives import copy_to

    bf = lambda x: lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    sound_operands = dr._channel_chunk_operands

    def running_decay(g):
        """`delta_rule._running_decay` from a decay rounded to bfloat16,
        its sums rounded to bfloat16."""
        return bf(jnp.cumsum(bf(g), axis=2))

    def operands(q, k, v, g, beta, **kw):
        """The sound operands (made from the rounded `G`), each of them
        rounded again."""
        return tuple(bf(z) for z in sound_operands(q, k, v, g, beta, **kw))

    def walk(WU, attn, q_in, k_out, G_end, t, dtype):
        """`delta_rule._walk_chunks` with the carried state rounded to
        bfloat16 after every chunk."""
        h, dk = WU.shape[0], q_in.shape[-1]
        dv = WU.shape[-1] - dk
        dot = functools.partial(dr._dot, dtype)

        @jax.checkpoint
        def one_chunk(S, c):
            W_c, U_c, attn_c, q_c, k_c, end_c = c
            v_new = U_c - dot("hik,hkv->hiv", W_c, S)
            o = (dot("hik,hkv->hiv", q_c, S)
                 + dot("hij,hjv->hiv", attn_c, v_new))
            S = bf(bf(jnp.exp(end_c))[..., None] * S
                   + dot("hik,hiv->hkv", k_c, v_new))
            return S, o.astype(dtype)

        chunk_first = lambda z: jnp.moveaxis(z, 1, 0)
        operand = lambda z: chunk_first(z.astype(dtype))
        S0 = jnp.zeros((h, dk, dv), jnp.float32)
        vma = tuple(jax.typeof(WU).vma)
        if vma:
            S0 = copy_to(S0, vma)
        S, o = lax.scan(one_chunk, S0, (
            operand(WU[..., :dk]), chunk_first(WU[..., dk:]), operand(attn),
            operand(q_in), operand(k_out), chunk_first(G_end)))
        o = jnp.moveaxis(o, 0, 1).reshape(h, -1, dv)
        return o[:, :t], S

    return [(dr, "_running_decay", running_decay),
            (dr, "_channel_chunk_operands", operands),
            (dr, "_walk_chunks", walk)]


def _fp8(x):
    """x rounded to float8_e4m3 on the way FORWARD; the cotangent passes as
    it is (benchmark/tools/hybrid_control.py says why)."""
    from jax import lax
    return x + lax.stop_gradient(
        lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3) - x)


def _fp8_rule_inputs():
    from distributed_pytorch_from_scratch_tpu.parallel import kda
    sound = kda.channel_delta_rule

    def rule(q, k, v, g, beta, **kw):
        return sound(_fp8(q), _fp8(k), _fp8(v), g, beta, **kw)

    return [(kda, "channel_delta_rule", rule)]


def _scalar_decay():
    import jax.numpy as jnp
    from distributed_pytorch_from_scratch_tpu.parallel.kda import (
        KimiDeltaAttention)
    sound = KimiDeltaAttention._rule_inputs

    def rule_inputs(self, params, xd, compute_dtype):
        """The sound inputs with every channel's decay the channels'
        mean."""
        q, k, v, g, beta = sound(self, params, xd, compute_dtype)
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
        return q, k, v, g, beta

    return [(KimiDeltaAttention, "_rule_inputs", rule_inputs)]


def _no_groups():
    from jax import lax
    from distributed_pytorch_from_scratch_tpu.parallel.moe import (
        SharedRoutedFFN)

    def select(self, biased):
        """`SharedRoutedFFN.select` at one group: the largest of them
        all."""
        return lax.top_k(biased, self.top_k)[1]

    return [(SharedRoutedFFN, "select", select)]


CONTROLS = {"bf16_decay_and_state": _bf16_decay_and_state,
            "fp8_rule_inputs": _fp8_rule_inputs,
            "scalar_decay": _scalar_decay, "no_groups": _no_groups}


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
