"""Device-mesh runtime: the TPU-native replacement for the reference's
process-group machinery.

The reference binds one OS process per GPU via `mp.spawn`
(`/root/reference/train.py:151`), rendezvouses over TCP
(`/root/reference/utils.py:19-24`) and keeps a module-global
`ProcessGroupManager` singleton with the TP topology
(`/root/reference/process_manager.py:8-25`). On TPU one process drives all
local chips, topology is a `jax.sharding.Mesh` with named axes, and
collectives are XLA ops over ICI — so this module is mostly a thin, typed
factory plus multi-host init.

Axis names: 'dp' (data parallel) and 'tp' (tensor parallel). The reference
only has 'tp' (`process_manager.py:13` asserts tp_size == world_size); the
2-D mesh is the BASELINE.json config-5 extension.
"""

from __future__ import annotations

import os
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..config import MeshConfig
from ..obs.trace import span_of

DP_AXIS = "dp"
PP_AXIS = "pp"
CP_AXIS = "cp"
EP_AXIS = "ep"
TP_AXIS = "tp"
AXIS_NAMES = (DP_AXIS, PP_AXIS, CP_AXIS, EP_AXIS, TP_AXIS)


def make_mesh(cfg: MeshConfig, devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build the ('dp', 'pp', 'cp', 'ep', 'tp') mesh.

    Replaces `init_pgm` (`/root/reference/process_manager.py:23-25`): where the
    reference carved a 1-D `torch.arange(world).view(tp_size)` grid into one
    NCCL group (`process_manager.py:16-17`), here the mesh itself is the
    topology and XLA lowers named-axis collectives onto ICI rings.

    The 'tp' axis is innermost (fastest-varying over devices) so TP
    collectives — the per-layer latency-critical ops, see SURVEY §3.1 —
    ride neighbouring ICI links. 'ep' (MoE all-to-all, twice per MoE layer)
    and 'cp' (ring-attention KV hops, once per ring step) sit between;
    'pp' (one activation ppermute per microbatch per stage boundary) and
    'dp' (one gradient all-reduce per step) are outermost.
    """
    if devices is None:
        devices = jax.devices()
    n = cfg.world_size
    if n > len(devices):
        raise ValueError(
            f"Mesh {cfg.dp}x{cfg.pp}x{cfg.cp}x{cfg.ep}x{cfg.tp} needs {n} "
            f"devices but only {len(devices)} are visible"
        )
    grid = np.asarray(devices[:n]).reshape(cfg.dp, cfg.pp, cfg.cp, cfg.ep,
                                           cfg.tp)
    return Mesh(grid, AXIS_NAMES,
                axis_types=(jax.sharding.AxisType.Auto,) * len(AXIS_NAMES))


def single_device_mesh() -> Mesh:
    """1x1 mesh: the TP=1 degenerate case (the reference's de-facto 'vanilla'
    path, where every comm op no-ops — `/root/reference/models/comm_ops.py:13-14`)."""
    return make_mesh(MeshConfig(dp=1, tp=1))


def tp_mesh(tp: int) -> Mesh:
    return make_mesh(MeshConfig(dp=1, tp=tp))


def mesh_shape(mesh: Mesh) -> MeshConfig:
    return MeshConfig(dp=mesh.shape[DP_AXIS], tp=mesh.shape[TP_AXIS],
                      cp=mesh.shape.get(CP_AXIS, 1),
                      ep=mesh.shape.get(EP_AXIS, 1),
                      pp=mesh.shape.get(PP_AXIS, 1))


def named(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))


def batch_feeder(mesh: Mesh, tracer=None):
    """Host-batch -> device-array function for (b, t)-shaped (or leading-
    stacked) token batches, multi-host-aware: `feed(x)` -> array,
    `feed(x, y, ...)` -> tuple of arrays.

    Single process: `jnp.asarray` (jit reshards per the step's in-specs).
    Multi-process: a host-local full batch cannot be passed to a jit whose
    shardings span non-addressable devices, so the global array is
    assembled via `jax.make_array_from_callback` — every process holds the
    identical (same-seed) host batch and contributes the shards it owns.
    The leading dims beyond (b, t) (steps_per_dispatch / grad-accum
    stacking) stay unsharded, matching the jnp.asarray path.

    `tracer`: optional obs.SpanTracer (anything with its `span`) — each
    call is one "h2d" span on the caller's thread; keyword arguments of the
    call (the loop's `step=`) go on the span. `feed.bytes_fed` counts the
    host bytes handed over, with or without a tracer."""
    import jax.numpy as jnp

    if jax.process_count() == 1:
        put = jnp.asarray
    else:
        def put(x):
            spec = P(*([None] * (x.ndim - 2)), (DP_AXIS, EP_AXIS), CP_AXIS)
            return jax.make_array_from_callback(
                x.shape, NamedSharding(mesh, spec), lambda idx: x[idx])

    def feed(*xs, **span_args):
        with span_of(tracer, "h2d", cat="h2d", **span_args):
            out = tuple(put(x) for x in xs)
        feed.bytes_fed += sum(x.nbytes for x in xs)
        return out[0] if len(out) == 1 else out

    feed.bytes_fed = 0
    return feed


def process_info() -> "tuple[int, int]":
    """(process_index, process_count) — safe to call before (or without)
    `init_multihost`: backendless failures degrade to a single-process view.
    Shared by MetricsWriter (per-process file tagging) and the obs layer
    (trace pid, watchdog messages)."""
    try:
        return jax.process_index(), jax.process_count()
    except Exception:
        return 0, 1


def init_multihost(coordinator: Optional[str] = None,
                   num_processes: Optional[int] = None,
                   process_id: Optional[int] = None) -> None:
    """Multi-host (DCN) initialisation.

    The reference's analogue is `dist.init_process_group('nccl', 'env://')`
    (`/root/reference/utils.py:23`). For a single host this is a no-op: one
    process sees all local chips. Across hosts, `jax.distributed.initialize`
    wires the DCN rendezvous; afterwards `jax.devices()` spans the slice and
    the same mesh code works unchanged.
    """
    if coordinator is None and "COORDINATOR_ADDRESS" in os.environ:
        coordinator = os.environ["COORDINATOR_ADDRESS"]
    if coordinator is None:
        return  # single host
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )
