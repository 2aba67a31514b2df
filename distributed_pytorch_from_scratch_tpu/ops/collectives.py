"""The four conjugate communication primitives, TPU-native.

These re-express the reference's `torch.autograd.Function` collectives
(`/root/reference/models/comm_ops.py`) over a named mesh axis, for use inside
`jax.shard_map`-partitioned code. The conjugate-pair structure (Megatron's
f/g operators) maps directly onto JAX primitives whose transposes are already
the right thing:

  reference op                      JAX primitive          transpose
  ------------------------------    -------------------    -------------------
  Copy    (fwd id, bwd all-reduce,  lax.pcast(to=varying)  lax.psum
           comm_ops.py:47-60)
  Reduce  (fwd all-reduce, bwd id,  lax.psum               lax.pcast(to=varying)
           comm_ops.py:31-44)
  Split   (fwd slice, bwd gather,   slice at axis_index    zero-pad + psum
           comm_ops.py:7-28)                                (== all-gather)
  Gather  (fwd all-gather, bwd      lax.all_gather         lax.psum_scatter
           slice, comm_ops.py:63-83)                        (== slice when the
                                                            cotangent is the
                                                            1/n-scaled mean)

so no custom VJPs are needed: JAX's vma (varying-manual-axes) machinery
derives exactly the Megatron conjugate gradients.

Unlike the reference, the ops do NOT short-circuit when the axis has size 1
(its `tp_size == 1` early-outs, `comm_ops.py:13-14,37-38,57-58,70-71`):
XLA compiles size-1 collectives to nothing, and the vma type system needs the
ops to run so values keep consistent varying/invariant tags on every mesh
shape (a size-1 'tp' axis otherwise leaves stale varying-over-tp tags that
break out_specs replication checks).

All ops MUST be called from inside `shard_map` code partitioned over `axis`.
"""

from __future__ import annotations

import functools

import jax
from jax import lax


def _axis_size(axis: str) -> int:
    return lax.axis_size(axis)


def vma_tracked(axis: str) -> bool:
    """Is this shard_map body typed with varying-manual-axes (the default),
    or was it built with check_vma=False? `axis_index` varies over its axis
    by definition, so its type says which: under check_vma=False every
    value's vma is empty."""
    return bool(jax.typeof(lax.axis_index(axis)).vma)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _copy_untyped(x, axes):
    return x


_copy_untyped.defvjp(lambda x, axes: (x, None),
                     lambda axes, _, ct: (lax.psum(ct, axes),))


def copy_to(x: jax.Array, axis: str = "tp") -> jax.Array:
    """Identity forward; all-reduce(SUM) backward.

    Megatron's f operator — placed at the input of a column-parallel block so
    each shard's input-gradient contributions are summed
    (reference `Copy`, `/root/reference/models/comm_ops.py:47-60`).

    No-op when `x` is already varying over `axis`: an already-varying input
    got its tag from an upstream collective (e.g. the sequence-parallel
    all-gather) whose own transpose performs the gradient sum — a second
    varying cast would be ill-typed, and the psum belongs to that producer.

    Inside a shard_map built with check_vma=False (training/zero.py's
    per-shard-grad builders) there are no tags and a varying cast does not
    transpose; the same forward/backward pair is then spelled as a custom
    VJP.
    """
    axes = (axis,) if isinstance(axis, str) else tuple(axis)
    if not vma_tracked(axes[0]):
        return _copy_untyped(x, axes)
    vma = jax.typeof(x).vma
    need = tuple(a for a in axes if a not in vma)
    if not need:
        return x
    return lax.pcast(x, need, to="varying")


def reduce_from(x: jax.Array, axis: str = "tp") -> jax.Array:
    """All-reduce(SUM) forward; identity backward.

    Megatron's g operator — sums partial outputs of a row-parallel block
    (reference `Reduce`, `/root/reference/models/comm_ops.py:31-44`).
    """
    return lax.psum(x, axis)


def split_to(x: jax.Array, axis: str = "tp") -> jax.Array:
    """Slice the last dim to this shard's chunk forward; all-gather backward.

    (reference `Split`, `/root/reference/models/comm_ops.py:7-28`.)
    `x` must be replicated over `axis`; the transpose of the slice under
    shard_map reassembles the full cotangent, which is exactly the
    all-gather-and-concat the reference's `Split.backward` performs.
    """
    n = _axis_size(axis)
    dim = x.shape[-1]
    assert dim % n == 0, f"last dim {dim} not divisible by axis size {n}"
    shard = dim // n
    idx = lax.axis_index(axis)
    return lax.dynamic_slice_in_dim(x, idx * shard, shard, axis=-1)


def gather_from(x: jax.Array, axis: str = "tp", tiled_axis: int = -1) -> jax.Array:
    """All-gather shards along the last dim forward; slice backward.

    (reference `Gather`, `/root/reference/models/comm_ops.py:63-83`.)
    The JAX transpose is psum_scatter, which generalises the reference's
    slice-the-grad rule: when every shard holds an identical (replicated)
    cotangent scaled by 1/n — the situation the reference relies on, since
    each rank computes the same loss from the same gathered logits —
    psum_scatter reproduces the sliced gradient.
    """
    return lax.all_gather(x, axis, axis=tiled_axis, tiled=True)


def reduce_scatter(x: jax.Array, axis: str = "tp", scatter_axis: int = -1) -> jax.Array:
    """Sum across the axis, scattering the result (each shard keeps a chunk).

    Absent from the reference (NCCL reduce-scatter unused) but required for
    sequence-parallel and ZeRO-style extensions — SURVEY §5.8.
    """
    return lax.psum_scatter(x, axis, scatter_dimension=scatter_axis % x.ndim,
                            tiled=True)


def all_to_all(x: jax.Array, axis: str, split_axis: int, concat_axis: int) -> jax.Array:
    """All-to-all: re-shard from one tensor dim to another over `axis`.

    The Ulysses sequence-parallel primitive (head<->sequence swap); no
    reference counterpart (SURVEY §2.4: Ulysses absent).
    """
    return lax.all_to_all(x, axis, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=True)


def ring_permute(x: jax.Array, axis: str, shift: int = 1) -> jax.Array:
    """One ring hop over `axis` in an EXPLICIT direction.

    `shift` is the perm direction, not an offset convenience: shift=+k
    builds the forward ring perm [(i, (i+k) % n)] — rank i SENDS to i+k, so
    after s hops of shift=+1 rank r HOLDS the value originated by rank
    (r - s) mod n. shift=-k is the reverse ring. TPU ICI rings are
    bidirectional, so both directions cost the same; the overlap kernels
    (ops/overlap.py) pin shift=+1 for every hop — the all-gather ring walks
    chunk origins DOWN (r-s) while the reduce ring walks accumulator
    destinations UP (r + n-1-s), and both statements assume the forward
    perm. Callers composing with them must use the same convention (the
    ring-CP attention does: ops/ring_attention.py rotates k/v with
    shift=+1). shift=0 would silently self-send; refused.
    """
    if shift == 0:
        raise ValueError("ring_permute needs an explicit nonzero shift "
                         "(direction); shift=0 would self-send every rank")
    n = _axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis, perm)


def axis_index(axis: str = "tp") -> jax.Array:
    """This shard's coordinate along `axis` (lax.axis_index).

    Pipeline live-gating contract: the pp bubble predicates derive ONLY
    from (pipeline step, axis_index('pp')) — never from data — so every
    member of a tp/ep/sp group (which shares a pp stage, hence the same
    index) agrees on the branch, keeping the collectives inside the live
    branch uniform. Code that adds new gating must preserve this: a
    predicate mixing in axis_index of a NON-pp axis would diverge within
    the group and deadlock its collectives.
    """
    return lax.axis_index(axis)
