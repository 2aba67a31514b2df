"""How the program's kernels are picked out of a device trace, and what each
call costs by its shapes.

The flash calls carry no explicit `name=` in the program today; the names
below are what the capture shows for them (looked at by hand, PR 24; see
PERF.md "For the tracing issue"). A kernel a later PR adds brings a file of
its own beside this one.
"""

from __future__ import annotations

import re

from benchmark.lib.flops import flash_call_cost

# any Pallas kernel: a Mosaic custom call (`meta` as benchmark/lib/trace.py
# cuts it from the HLO text)
CUSTOM_CALL = re.compile(r"^custom-call tpu_custom_call ")
# The flash calls are the only Mosaic calls of the train step, and their
# instruction names today are accidents of JAX's name stack (`closed_call.8`
# the forward, `rematted_computation.10` the recomputed forward,
# `checkpoint.10` the backward). What tells them apart for sure is the
# operand count: q, k, v forward; q, k, v, o-or-do, lse, delta backward
# (fused or split). Names the tracing issue is asked to add are accepted too.
FLASH_FORWARD = re.compile(
    r"^custom-call tpu_custom_call operands=3$|^flash_fwd")
FLASH_BACKWARD = re.compile(
    r"^custom-call tpu_custom_call operands=6$|^flash_bwd")
FLASH = re.compile(FLASH_FORWARD.pattern + "|" + FLASH_BACKWARD.pattern)


def flash_cost(m, backward: bool):
    """One device's flash call at the cell's shapes, from what a runner's
    `measured` always carries: the workload file, the family's sizes and
    the mesh. A device holds its data-parallel share of the batch and its
    tensor-parallel share of the heads."""
    import jax.numpy as jnp

    w, s = m.workload, m.sizes
    rows = ((int(w["batch"]) // m.mesh.get("dp", 1))
            * (s.n_head // m.mesh.get("tp", 1)))
    return flash_call_cost(rows, int(w["seqlen"]), s.head_dim,
                           jnp.dtype(w["dtype"]).itemsize, backward)
