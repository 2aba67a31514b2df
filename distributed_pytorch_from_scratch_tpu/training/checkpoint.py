"""Sharded checkpointing with the reference's filename convention + resume.

The reference saves one `.pth` per TP rank, metadata encoded in the filename
`tprank-{r}_iter-{n}_loss-{avg:.4f}.pth`, re-parsed by regex at eval time
(`/root/reference/train.py:121-133`, `test.py:94-95`), with retention pruning
via `--reserv_last_n_ckpts`. It never saves optimizer/step state, so training
cannot resume (SURVEY §5.4).

Here: same per-TP-shard layout and filename convention (extension `.npz`),
each shard keyed by mesh coordinate, but the checkpoint also carries the Adam
moments and step count so `--resume` restarts training exactly. Arrays are
sliced/reassembled along whichever dimension the param's PartitionSpec marks
as 'tp' — the checkpoint format is mesh-independent (save at TP=8, load at
TP=2: the global arrays are identical).
"""

from __future__ import annotations

import glob
import os
import re
import threading
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..obs.trace import span_of
from .optim import AdamState

CKPT_RE = re.compile(r"tprank-(\d+)_iter-(\d+)_loss-(.+?)\.npz$")

# One jitted identity-copy shared by every async save: jit caches by tree
# structure/shape, so each (params, opt) layout compiles once per run. The
# copy gives the writer thread buffers that survive the train step's
# donate_argnums (device_get on a donated-away array would raise) at the
# cost of one transient on-device replica of params + moments.
_SNAPSHOT = jax.jit(lambda tree: jax.tree.map(jnp.copy, tree))


class AsyncSaveHandle:
    """Join handle for a background checkpoint write (`async_write=True`).

    The write happens on a daemon thread: device->host transfer, per-rank
    slicing, npz writes, retention pruning. `join()` blocks until the files
    are on disk and returns their paths (re-raising any writer exception).
    """

    def __init__(self, step: int, stats: Dict[str, int]):
        self.step = step
        # what the writer moved, filled in as it goes: `bytes_moved` over
        # D2H, `files` and `bytes_written` on disk
        self.stats = stats
        self._paths: List[str] = []
        self._error: Optional[BaseException] = None
        self._thread: Optional[threading.Thread] = None

    def _run(self, fn) -> None:
        def wrapped():
            try:
                self._paths = fn()
            except BaseException as e:  # surfaced at join()
                self._error = e
        self._thread = threading.Thread(target=wrapped, daemon=True)
        self._thread.start()

    def join(self) -> List[str]:
        if self._thread is not None:
            self._thread.join()
        if self._error is not None:
            raise self._error
        return self._paths


def _tp_dim(spec: P) -> Optional[int]:
    for i, axis in enumerate(spec):
        if axis == "tp" or (isinstance(axis, tuple) and "tp" in axis):
            return i
    return None


def _flatten(tree: Any, prefix: str) -> Dict[str, Any]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = prefix + "".join(
            f"/{p.key}" if hasattr(p, "key") else f"/{p.idx}" for p in path)
        flat[key] = leaf
    return flat


def _shard_slice(arr: np.ndarray, spec: P, rank: int, tp_size: int) -> np.ndarray:
    dim = _tp_dim(spec)
    if dim is None or tp_size == 1:
        return arr
    n = arr.shape[dim] // tp_size
    sl = [slice(None)] * arr.ndim
    sl[dim] = slice(rank * n, (rank + 1) * n)
    return arr[tuple(sl)]


def _get_leafwise(tree: Any) -> Any:
    """device->host one LEAF at a time (np.asarray assembles each leaf's
    addressable shards; dp/tp-sharded global arrays come back as their
    full numpy values with no device-side collective). The whole-tree
    `jax.device_get` it replaces materialised every transfer before the
    first byte was written; leaf-wise streaming keeps the transient
    device->host working set to one leaf, which is what lets dp-sharded
    ZeRO-2/3 state save through this path without a full-tree gather
    stall (the npz format still holds global values, so any mesh/stage
    can reload the file — resharding happens at device_put)."""
    return jax.tree.map(lambda x: np.asarray(jax.device_get(x)), tree)


def save_checkpoint(save_dir: str, step: int, avg_loss: float, params: Any,
                    specs: Any, tp_size: int,
                    opt_state: Optional[AdamState] = None,
                    reserve_last_n: int = -1,
                    async_write: bool = False,
                    tracer=None,
                    zero_stage: int = 0,
                    mesh_axes=None) -> "List[str] | AsyncSaveHandle":
    """Write one npz per TP rank; returns the paths written.

    Works unchanged for ZeRO-sharded state (dp-sharded moments at stage
    1/2, dp-sharded params+moments at stage 3): leaves stream through the
    host one at a time (`_get_leafwise`) and land as GLOBAL arrays, so
    the on-disk format stays mesh- and stage-independent — a dp4 ZeRO-3
    run reloads on a dp2 ZeRO-1 mesh by plain device_put. `zero_stage` is
    recorded as `__zero_stage__` metadata (observability only; loaders
    ignore it).

    `async_write=True` returns an `AsyncSaveHandle` instead: the arrays are
    snapshotted on-device (one jitted copy, so later donated train steps
    can't invalidate them), then a daemon thread performs the device->host
    transfer and file writes while training continues. The train loop joins
    the previous handle before issuing the next save, bounding in-flight
    saves to one. This removes the per-save stall the synchronous path has
    (full params + both Adam moments over D2H — ~1.5 GB at the 124M-param
    BASELINE config) from the hot loop.

    `tracer`: optional obs.SpanTracer — the device->host transfer records
    a "ckpt.d2h" span and the slicing, npz writes and pruning a
    "ckpt.write" span, both carrying the save's `step`, on whichever thread
    performs them (the async writer shows up as its own track in the
    timeline), and in the timeline's event what they moved: `bytes` over
    D2H, `bytes` and `files` on disk, the numbers `AsyncCheckpointer` adds
    to its counters, so a save's two rates can be read from the timeline
    alone. The async path's on-device copy is a "ckpt.snapshot" span on
    the caller's thread.

    `mesh_axes`: the saving mesh (a live Mesh, or (axis, size) pairs) for
    the ``__layout__`` stamp — mesh shape + per-leaf PartitionSpec + zero
    stage, everything the reshard planner needs to load this checkpoint
    onto a DIFFERENT mesh (reshard/layout.py). Defaults to the tp-only
    mesh the filename convention already implies; `assemble` skips
    ``__``-prefixed members, so pre-stamp readers are unaffected.
    """
    os.makedirs(save_dir, exist_ok=True)
    from ..reshard.layout import make_layout
    layout = make_layout(mesh_axes if mesh_axes is not None
                         else (("tp", tp_size),), specs,
                         zero_stage=zero_stage)

    stats: Dict[str, int] = {}

    def write(params, opt_state) -> List[str]:
        with span_of(tracer, "ckpt.d2h", cat="checkpoint",
                     step=step) as found:
            params_np = _get_leafwise(params)
            moments_np = (None if opt_state is None else
                          (_get_leafwise(opt_state.mu),
                           _get_leafwise(opt_state.nu)))
            stats["bytes_moved"] = sum(
                x.nbytes for x in jax.tree.leaves((params_np, moments_np)))
            if found is not None:
                found["bytes"] = stats["bytes_moved"]
        with span_of(tracer, "ckpt.write", cat="checkpoint",
                     step=step) as found:
            paths = _write(params_np, moments_np)
            stats["files"] = len(paths)
            stats["bytes_written"] = sum(os.path.getsize(p) for p in paths)
            if found is not None:
                found.update(bytes=stats["bytes_written"],
                             files=stats["files"])
        return paths

    def _write(params_np, moments_np) -> List[str]:
        flat_p = _flatten(params_np, "param")
        flat_s = _flatten(specs, "param")
        flat_opt: Dict[str, Any] = {}
        if moments_np is not None:
            flat_opt.update(_flatten(moments_np[0], "mu"))
            flat_opt.update(_flatten(moments_np[1], "nu"))
            # moments shard exactly like their params
            flat_s.update({k.replace("param", "mu", 1): v for k, v in
                           _flatten(specs, "param").items()})
            flat_s.update({k.replace("param", "nu", 1): v for k, v in
                           _flatten(specs, "param").items()})

        paths = []
        for rank in range(tp_size):
            shard = {}
            for key, arr in {**flat_p, **flat_opt}.items():
                shard[key] = _shard_slice(np.asarray(arr), flat_s[key], rank,
                                          tp_size)
            shard["__step__"] = np.asarray(step, np.int64)
            shard["__tp_size__"] = np.asarray(tp_size, np.int64)
            shard["__has_opt__"] = np.asarray(opt_state is not None)
            shard["__zero_stage__"] = np.asarray(zero_stage, np.int64)
            shard["__layout__"] = np.asarray(layout.to_json())
            path = os.path.join(
                save_dir, f"tprank-{rank}_iter-{step}_loss-{avg_loss:.4f}.npz")
            # Atomic publish: a hard kill mid-write (preemption grace
            # expiring) must never leave a truncated file at a
            # CKPT_RE-matching name, or the next --resume would pick it as
            # newest and crash. The .tmp suffix keeps the partial file
            # invisible to list_checkpoints; rename is atomic on POSIX.
            tmp = path + ".tmp"
            with open(tmp, "wb") as f:
                np.savez(f, **shard)
            os.replace(tmp, path)
            paths.append(path)

        if reserve_last_n > 0:
            prune_checkpoints(save_dir, reserve_last_n, tp_size)
        return paths

    if not async_write:
        return write(params, opt_state)

    with span_of(tracer, "ckpt.snapshot", cat="checkpoint", step=step):
        snap_p = _SNAPSHOT(params)
        snap_o = _SNAPSHOT(opt_state) if opt_state is not None else None
    handle = AsyncSaveHandle(step, stats)
    handle._run(lambda: write(snap_p, snap_o))
    return handle


def map_moments(opt_state: AdamState, fn) -> AdamState:
    """Apply `fn` (a params-tree transform, e.g. model.to_canonical) to the
    Adam moments — they shard/reshape exactly like their params. Identity
    transforms return the state unchanged."""
    return opt_state.__class__(step=opt_state.step, mu=fn(opt_state.mu),
                               nu=fn(opt_state.nu))


class AsyncCheckpointer:
    """The train loop's periodic save, at most one write in flight:
    `save(step, accum_loss, params, opt_state)` every save interval,
    `join()` before exit. `train()` and the benchmark's `train_ckpt` runner
    both save through here, so what one times is what the other does.

    A save, on the caller's thread: sync the running loss sum to the host
    for the file name's average (span "ckpt.loss_sync": this drains the
    device), join the previous write ("ckpt.join_prev"), bring params and
    moments to the checkpoint's canonical layout, run `gather` if given,
    and start `save_checkpoint(async_write=True)`, whose on-device copy is
    "ckpt.snapshot". The writer thread then records "ckpt.d2h" and
    "ckpt.write", whose events also carry the `bytes` (and `files`) this
    object's counters grow by. All five carry the save's `step`, cat
    "checkpoint".

    `gather(params, opt_state) -> (params, opt_state) | None`: the
    multi-host hook. Cross-host shards are not addressable from one
    process, so `train()` all-gathers to host arrays there (a collective:
    every process calls `save`) and returns None on every process but the
    one that writes. `on_saved(step, paths)` is called at each join.
    `bytes_moved`, `bytes_written`, `files`, `saves` count what the joined
    writes did."""

    def __init__(self, save_dir: str, model, tp_size: int, *,
                 start_step: int = 0, reserve_last_n: int = -1,
                 zero_stage: int = 0, mesh_axes=None, tracer=None,
                 gather=None, on_saved=None):
        self.save_dir = save_dir
        self._model = model
        self._start_step = start_step
        self._gather = gather
        self._on_saved = on_saved
        self._tracer = tracer
        self._save_args = dict(
            tp_size=tp_size, reserve_last_n=reserve_last_n,
            zero_stage=zero_stage, mesh_axes=mesh_axes, tracer=tracer)
        self._pending: Optional[AsyncSaveHandle] = None
        self.last_saved = start_step
        self.saves = self.files = self.bytes_moved = self.bytes_written = 0

    def _span(self, name: str, step: int):
        return span_of(self._tracer, name, cat="checkpoint", step=step)

    def save(self, step: int, accum_loss, params, opt_state) -> None:
        with self._span("ckpt.loss_sync", step):
            avg = float(accum_loss) / (step - self._start_step)
        self.join()  # bound in-flight async writes to one
        params = self._model.to_canonical(params)
        opt_state = map_moments(opt_state, self._model.to_canonical)
        self.last_saved = step
        if self._gather is not None:
            with self._span("ckpt.gather", step):
                gathered = self._gather(params, opt_state)
            if gathered is None:
                return
            params, opt_state = gathered
        self._pending = save_checkpoint(
            self.save_dir, step, avg, params, self._model.canonical_specs(),
            opt_state=opt_state, async_write=True, **self._save_args)

    def join(self) -> Optional[List[str]]:
        """Wait for the write in flight, if any; its paths."""
        pending, self._pending = self._pending, None
        if pending is None:
            return None
        with self._span("ckpt.join_prev", pending.step):
            paths = pending.join()
        self.saves += 1
        self.files += pending.stats["files"]
        self.bytes_moved += pending.stats["bytes_moved"]
        self.bytes_written += pending.stats["bytes_written"]
        if self._on_saved is not None:
            self._on_saved(pending.step, paths)
        return paths


def prune_checkpoints(save_dir: str, reserve_last_n: int, tp_size: int) -> None:
    """Keep only the newest N iterations per rank
    (reference `train.py:127-132`)."""
    for rank in range(tp_size):
        ckpts = glob.glob(os.path.join(save_dir, f"tprank-{rank}_iter-*_loss-*.npz"))
        ckpts.sort(key=lambda p: int(CKPT_RE.search(os.path.basename(p)).group(2)))
        for old in ckpts[:-reserve_last_n]:
            os.remove(old)


def list_checkpoints(save_dir: str, rank: int = 0) -> List[Tuple[int, str]]:
    """(iter, path) pairs for one rank, sorted by iter
    (reference `test.py:94-95`)."""
    out = []
    for p in glob.glob(os.path.join(save_dir, f"tprank-{rank}_iter-*_loss-*.npz")):
        m = CKPT_RE.search(os.path.basename(p))
        if m:
            out.append((int(m.group(2)), p))
    return sorted(out)


def _unflatten_into(template: Any, flat: Dict[str, np.ndarray], prefix: str) -> Any:
    paths = jax.tree_util.tree_flatten_with_path(template)[0]
    treedef = jax.tree.structure(template)
    leaves = []
    for path, _ in paths:
        key = prefix + "".join(
            f"/{p.key}" if hasattr(p, "key") else f"/{p.idx}" for p in path)
        leaves.append(flat[key])
    return jax.tree.unflatten(treedef, leaves)


def find_rank_shards(ckpt_dir: str, step: int, ext: str = "npz"
                     ) -> Dict[int, str]:
    """{rank: path} for `tprank-{r}_iter-{step}_loss-*.{ext}` files — the
    single owner of the reference filename contract
    (`/root/reference/train.py:121-126`), shared by the npz loader and the
    torch-checkpoint importer (interop.py, ext='pth')."""
    pat = re.compile(rf"tprank-(\d+)_iter-(\d+)_loss-(.+?)\.{ext}$")
    rank_files: Dict[int, str] = {}
    for p in glob.glob(os.path.join(ckpt_dir,
                                    f"tprank-*_iter-{step}_loss-*.{ext}")):
        m = pat.search(os.path.basename(p))
        if m and int(m.group(2)) == step:
            rank_files[int(m.group(1))] = p
    return rank_files


def validate_checkpoint(ckpt_dir: str, step: int, ext: str = "npz"
                        ) -> Tuple[int, Dict[int, str]]:
    """Refuse an incomplete shard set EARLY, before any assembly work.

    Returns (tp_size, {rank: path}) when every rank shard of iteration
    `step` is present. Raises FileNotFoundError naming the missing rank
    list otherwise — a partial copy (one rank file lost in transfer) used
    to surface as a cryptic KeyError mid-assemble in `find_rank_shards`
    consumers; the serving loader (serving/serve.py), `load_checkpoint`,
    and the torch-checkpoint interop all validate through here now.

    The expected rank count comes from the `__tp_size__` metadata any one
    npz shard carries; formats without it (ext='pth') fall back to
    max(rank)+1, which still catches every hole below the highest
    surviving rank."""
    rank_files = find_rank_shards(ckpt_dir, step, ext=ext)
    if not rank_files:
        raise FileNotFoundError(f"no checkpoint for iter {step} in "
                                f"{ckpt_dir}")
    tp_size = None
    if ext == "npz":
        any_rank = next(iter(rank_files))
        try:
            tp_size = int(np.load(rank_files[any_rank])["__tp_size__"])
        except KeyError:  # pre-metadata file: fall back to the rank span
            tp_size = None
    if tp_size is None:
        tp_size = max(rank_files) + 1
    missing = sorted(set(range(tp_size)) - set(rank_files))
    if missing:
        raise FileNotFoundError(
            f"checkpoint iter {step} was written with tp_size={tp_size} but "
            f"shard files for rank(s) {missing} are missing from {ckpt_dir} "
            f"— restore the missing rank file(s) or re-save the checkpoint")
    return tp_size, rank_files


def load_checkpoint(save_dir: str, step: int, params_template: Any,
                    specs: Any, with_opt: bool = False):
    """Reassemble global arrays from all per-rank shards of iteration `step`.

    Returns (params, opt_state | None, step).
    """
    tp_size, rank_files = validate_checkpoint(save_dir, step)
    shards = {r: dict(np.load(rank_files[r])) for r in range(tp_size)}

    flat_specs = _flatten(specs, "param")

    def assemble(prefix: str) -> Dict[str, np.ndarray]:
        out = {}
        for key in shards[0]:
            if not key.startswith(prefix + "/"):
                continue
            spec_key = "param" + key[len(prefix):]
            dim = _tp_dim(flat_specs[spec_key])
            if dim is None or tp_size == 1:
                out[key] = shards[0][key]
            else:
                out[key] = np.concatenate(
                    [shards[r][key] for r in range(tp_size)], axis=dim)
        return out

    params = _unflatten_into(params_template, assemble("param"), "param")

    opt_state = None
    if with_opt and bool(shards[0]["__has_opt__"]):
        mu = _unflatten_into(params_template,
                             {k: v for k, v in assemble("mu").items()}, "mu")
        nu = _unflatten_into(params_template,
                             {k: v for k, v in assemble("nu").items()}, "nu")
        opt_state = AdamState(step=np.asarray(int(shards[0]["__step__"]),
                                              np.int32), mu=mu, nu=nu)
    return params, opt_state, int(shards[0]["__step__"])


def latest_step(save_dir: str) -> Optional[int]:
    ckpts = list_checkpoints(save_dir, rank=0)
    return ckpts[-1][0] if ckpts else None
