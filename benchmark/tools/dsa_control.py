"""What the `train_dsa_moe` check reads for the sound program and for a
control, in the runner's own numbers.

    python3 benchmark/tools/dsa_control.py --workload <cell> --seed <n> \
        [--control bf16_scores|topk_one_short|batch_shared_selection| \
                   index_loss_leaks_out] \
        [--two_sequences] [--seconds <s>] [--trace <0|1>] [--rehearse]

Runs the cell's runner as `run.py` does, by default with a window of no
length (the check is the step's first call, before any window), and prints
the runner's `check` log line with the control's name added; with
`--seconds` the run's last line too. A control is the program itself with
one thing wrong, and each must FAIL at least one limit of
`runners/train_dsa_moe.DSA_RTOL`:

* `bf16_scores`: the index score rounded to bfloat16 wherever it is made
  (the kernels' tile and the XLA text), before anything is chosen by it:
  the precision below the float32 the configuration states for it. The
  rounding makes buckets of equal scores, the tie rule fills a row's budget
  from a bucket's EARLIEST keys, and the choice moves at the margin;
* `topk_one_short`: every row keeps 2047 keys (`top_k - 1`);
* `batch_shared_selection`: every sequence of the batch attends over the
  keys the FIRST sequence's index scores choose. The cell's batch is one
  sequence, where that is the sound program, so this control runs the
  cell's 16384 tokens as 2 x 8192 (`--rehearse`: the rehearsal's batch;
  `--two_sequences` runs the SOUND program at that shape, to read beside
  it);
* `index_loss_leaks_out`: the indexer reads the layer's input itself and
  not a `stop_gradient` of it, so its loss's gradient runs on into the
  layer's input and every leaf upstream of it: the split between the two
  losses broken from the indexer's side. (ISSUE 72 names the leak the other
  way, a CE gradient at an indexer leaf. That one cannot be planted by
  taking a stop-gradient away: the CE reaches the indexer only through the
  choice, and a choice has no gradient; tests/test_dsa_moe.py holds both
  zeros exactly.)

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
from types import SimpleNamespace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _bf16_scores():
    import jax.numpy as jnp
    from distributed_pytorch_from_scratch_tpu.ops import index_select
    from distributed_pytorch_from_scratch_tpu.ops.pallas import dsa_attention
    rounded = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    tile, text = dsa_attention._index_tile, index_select.index_scores

    def index_tile(qi, ki, w, bq):
        z, score = tile(qi, ki, w, bq)
        return z, rounded(score)

    return [(dsa_attention, "_index_tile", index_tile),
            (index_select, "index_scores", lambda *a: rounded(text(*a)))]


def _both(change):
    """The attention call and the probe's, as `parallel/dsa.py` calls them,
    each with `change(q_idx, k_idx, w, top_k)` laid over its arguments."""
    from distributed_pytorch_from_scratch_tpu.parallel import dsa
    attend, probe = dsa.selected_attention, dsa.selection_probe

    def selected_attention(q, k, v, q_idx, k_idx, w, top_k, impl="auto"):
        return attend(q, k, v, *change(q_idx, k_idx, w, top_k), impl=impl)

    def selection_probe(q_idx, k_idx, w, top_k, impl="auto"):
        return probe(*change(q_idx, k_idx, w, top_k), impl=impl)

    return [(dsa, "selected_attention", selected_attention),
            (dsa, "selection_probe", selection_probe)]


def _topk_one_short():
    return _both(lambda q_idx, k_idx, w, top_k: (q_idx, k_idx, w, top_k - 1))


def _batch_shared_selection():
    import jax.numpy as jnp
    first = lambda a: jnp.broadcast_to(a[:1], a.shape)
    return _both(lambda q_idx, k_idx, w, top_k:
                 (first(q_idx), first(k_idx), first(w), top_k))


def _index_loss_leaks_out():
    from distributed_pytorch_from_scratch_tpu.parallel import dsa
    return [(dsa, "lax", SimpleNamespace(stop_gradient=lambda x: x))]


CONTROLS = {"bf16_scores": _bf16_scores,
            "topk_one_short": _topk_one_short,
            "batch_shared_selection": _batch_shared_selection,
            "index_loss_leaks_out": _index_loss_leaks_out}
# the controls that need more than one sequence a batch: the cell's tokens
# as two sequences (the rehearsal's batch is two already)
TWO_SEQUENCES = ("batch_shared_selection",)


def reading(workload: str, seed: int, control=None, rehearse=False,
            seconds: float = 0.0, trace: int = 0,
            two_sequences: bool = False) -> dict:
    """The runner's `check` log line for one run of the cell, and the run's
    last line where it was timed."""
    from benchmark import run
    argv = ["--workload", workload, "--seed", str(seed),
            "--seconds", str(seconds), "--trace", str(trace)]
    argv.append("--unpinned")      # weights and batches from the seed
    if rehearse:
        argv.append("--rehearse")
    patches = list(CONTROLS[control]()) if control else []
    if (control in TWO_SEQUENCES or two_sequences) and not rehearse:
        load = run.load_cell

        def two(name, tiny=False):
            w, config = load(name, tiny)
            tokens = int(w["batch"]) * int(w["seqlen"])
            return {**w, "batch": 2, "seqlen": tokens // 2}, config
        patches.append((run, "load_cell", two))
    with contextlib.ExitStack() as undo:
        for owner, name, patched in patches:
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
    ap.add_argument("--two_sequences", action="store_true")
    args = ap.parse_args(argv)
    print(json.dumps(reading(args.workload, args.seed, args.control,
                             args.rehearse, args.seconds, args.trace,
                             args.two_sequences)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
