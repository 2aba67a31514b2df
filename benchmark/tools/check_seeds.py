"""The serving check's two readings, in one process: what sound runs of the
program give over many seeds, and what the control gives.

    python3 benchmark/tools/check_seeds.py --workload <cell> \
        --seeds 1,2,3 [--control int8_kv] [--rehearse]

For each seed: weights from the seed, the reference's logits, a fresh engine
built as the `serve` runner builds it, and the runner's own comparison
(`runners/serve.compare`). The control is the program itself with its own
path of the precision below the cell's bfloat16 switched on: int8 pages
(`int8_kv`); `int8` adds its int8 decode weights, for the record. The limit
in `runners/serve.LIMITS` stands between the sound runs' largest reading
and the control's smallest (PERF.md, section 2). No window runs.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# engine keywords of the program's own lower-precision paths
CONTROLS = {"int8_kv": {"kv_dtype": "int8"},
            "int8": {"kv_dtype": "int8", "decode_weight_dtype": "int8"}}


def readings(workload_name: str, seeds, control=None, rehearse=False):
    """One dict a seed: the runner's `compare` fields."""
    import jax
    from benchmark.lib.cells import load_cell
    from benchmark.lib.files import load_module
    from benchmark.lib.job import Job
    from distributed_pytorch_from_scratch_tpu.config import MeshConfig
    from distributed_pytorch_from_scratch_tpu.runtime.compile_cache import (
        enable_compile_cache)
    from distributed_pytorch_from_scratch_tpu.runtime.mesh import make_mesh

    enable_compile_cache()
    w, config = load_cell(workload_name, rehearse)
    if not rehearse and jax.devices()[0].platform != "tpu":
        raise SystemExit("check_seeds: not a TPU; use --rehearse off the chip")
    family_module = load_module("families", config["family"])
    runner = load_module("runners", w["runner"])
    mesh = make_mesh(MeshConfig(**w["mesh"]),
                     devices=jax.devices()[:int(w["chips"])])
    family = family_module.build(config, dict(w["mesh"]), w["dtype"])
    model, sizes, chk = family.model, family.sizes, w["check"]
    out = []
    for seed in seeds:
        job = Job(time.time(), workload_name, w, config, family_module, seed,
                  0.0, False, rehearse, None)
        params = jax.jit(model.init, out_shardings=model.shardings(mesh))(
            jax.random.key(seed))
        ids = runner.check_ids(w, sizes.vocab, seed)
        want = runner.reference_logits(job, family, params, ids,
                                       int(chk["decode"]))
        engine = runner.build_engine(
            model, mesh, params, w["engine"], sizes.vocab, sizes.n_positions,
            None, **(CONTROLS[control] if control else {}))
        got = runner.engine_logits(engine, ids, int(chk["prefill"]))
        row = {"seed": seed, "control": control,
               **runner.compare(got, want, runner.LIMITS[w["dtype"]])}
        print(json.dumps(row), flush=True)
        out.append(row)
        # each engine jits its own programs and the jit cache keeps them
        # loaded: on the chip the tool died at its sixth engine without this
        del engine, params
        jax.clear_caches()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", choices=sorted(CONTROLS), default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    rows = readings(args.workload, [int(s) for s in args.seeds.split(",")],
                    args.control, args.rehearse)
    read = [r["rel_l2_mean"] for r in rows]
    print(json.dumps({"event": "readings", "control": args.control,
                      "seeds": len(rows), "rel_l2_mean_smallest": min(read),
                      "rel_l2_mean_largest": max(read)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
