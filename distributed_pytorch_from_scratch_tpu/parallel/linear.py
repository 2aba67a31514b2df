"""Column- and row-parallel linear layers.

TPU-native re-expression of `/root/reference/models/layers.py:14-100`.
Design differences from the reference (all deliberate, all idiomatic JAX):

* **Functional modules.** A layer is a frozen dataclass of static shape info
  with `init(key) -> params`, `specs() -> PartitionSpec pytree` and
  `apply(params, x)`. No mutable state, no ambient process-group singleton.

* **Global params + NamedSharding.** `init` materialises the FULL weight from
  an explicit PRNG key; `specs` says how it shards over the mesh. This
  replaces the reference's init-full/broadcast-from-rank-0/slice dance
  (`layers.py:78-87`) — the property its tests assert (every shard is a slice
  of one consistent full init) holds by construction.

* **(idim, odim) weight layout**, `y = x @ W`, instead of torch's
  (odim, idim) `F.linear` layout — row-major friendly for the MXU.

* `apply` is written per-shard and must run inside `shard_map`; the comm ops
  (`ops/collectives.py`) carry the Megatron conjugate-gradient semantics.

Bias placement matches the reference exactly: column-parallel bias is SHARDED
and added before the gather (`layers.py:74,94-96`); row-parallel bias is FULL
and added after the reduce (`layers.py:29,53-54`).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.collectives import (copy_to, gather_from, reduce_from,
                               reduce_scatter, split_to)
from ..ops.overlap import ag_matmul, matmul_rs, ring_order

Params = Dict[str, Any]

OVERLAP_MODES = ("off", "ring", "ring_q")


def _check_overlap(overlap: str) -> None:
    if overlap not in OVERLAP_MODES:
        raise ValueError(f"overlap must be one of {OVERLAP_MODES}, "
                         f"got {overlap!r}")


def _check_seq_order(seq_order: str, on_ring: bool) -> None:
    """`seq_order` names how the FULL-sequence side of a ring collective
    matmul holds its chunks: 'rank' (token order, the default) or 'ring'
    (ops/overlap.py, "RING ORDER": what the rings produce and consume at no
    cost). 'ring' means something on the ring path only."""
    if seq_order not in ("rank", "ring"):
        raise ValueError(f"seq_order must be 'rank' or 'ring', got "
                         f"{seq_order!r}")
    if seq_order == "ring" and not on_ring:
        raise ValueError("seq_order='ring' is the ring collective matmuls' "
                         "layout: it needs overlap != 'off' and the "
                         "'seq_sharded' layout")


def _torch_linear_init(key: jax.Array, idim: int, odim: int) -> jax.Array:
    """Uniform(-1/sqrt(idim), 1/sqrt(idim)) — identical distribution to the
    reference's `kaiming_uniform_(a=sqrt(5))` on a (odim, idim) weight
    (`/root/reference/models/layers.py:36,81`), which reduces to exactly this
    bound. Returned in (idim, odim) layout."""
    bound = 1.0 / math.sqrt(idim)
    return jax.random.uniform(key, (idim, odim), jnp.float32, -bound, bound)


def uniform_fan_in(key: jax.Array, shape, fan_in: int) -> jax.Array:
    """`_torch_linear_init`'s distribution, Uniform(+-1/sqrt(fan_in)), for a
    weight of any `shape` (a projection kept (d, heads, columns), a
    depthwise convolution's taps)."""
    bound = 1.0 / math.sqrt(fan_in)
    return jax.random.uniform(key, shape, jnp.float32, -bound, bound)


@dataclass(frozen=True)
class ColumnParallelLinear:
    """Y = X @ W + b with W's output dim sharded over `axis`.

    Reference: `/root/reference/models/layers.py:58-100`.
    forward: copy -> local matmul -> + sharded bias -> optional gather.
    """

    idim: int
    odim: int
    add_bias: bool = True
    gather_output: bool = True
    axis: str = "tp"
    # 'ring' decomposes the sequence-parallel input all-gather into a ring
    # collective matmul (ops/overlap.ag_matmul): each ppermute hop overlaps
    # with the partial dot of the chunk already in hand. 'ring_q' is the
    # same ring with int8 codes + per-row scales on every hop (half the
    # bf16 wire bytes; bounds pinned in tests/test_quant.py). Only the
    # input_layout='seq_sharded' path changes; 'off' stays bit-identical.
    overlap: str = "off"

    def __post_init__(self):
        _check_overlap(self.overlap)

    def init(self, key: jax.Array) -> Params:
        p: Params = {"weight": _torch_linear_init(key, self.idim, self.odim)}
        if self.add_bias:
            p["bias"] = jnp.zeros((self.odim,), jnp.float32)  # zeros: layers.py:87
        return p

    def specs(self) -> Params:
        s: Params = {"weight": P(None, self.axis)}
        if self.add_bias:
            s["bias"] = P(self.axis)
        return s

    def apply(self, params: Params, x: jax.Array,
              compute_dtype: jnp.dtype = jnp.float32,
              input_layout: str = "replicated",
              seq_order: str = "rank") -> jax.Array:
        w = params["weight"].astype(compute_dtype)      # local (idim, odim/n)
        on_ring = input_layout == "seq_sharded" and self.overlap != "off"
        _check_seq_order(seq_order, on_ring)
        if on_ring:
            # ring collective matmul: the gather's ppermute hops hide under
            # the per-chunk partial dots; the custom VJP rings the backward
            # too (matmul_rs for dx, a re-gather ring for dw). 'ring_q'
            # quantizes every hop's payload (ops/overlap.py). The output
            # comes in ring order; seq_order='ring' hands it on as it is.
            y = ag_matmul(x.astype(compute_dtype), (w,), self.axis,
                          self.overlap == "ring_q")[0]
            if seq_order == "rank":
                y = ring_order(y, self.axis)
            return self._epilogue(params, y, compute_dtype)
        if input_layout == "replicated":
            x = copy_to(x, self.axis)                   # bwd: all-reduce input grads
        elif input_layout == "seq_sharded":
            # Megatron sequence parallelism: x arrives (b, t/n, d); all-gather
            # the sequence dim. The transpose (psum_scatter over seq) is the
            # conjugate reduce-scatter, replacing copy_to's all-reduce — same
            # bytes on the wire, but activations upstream are 1/n-sized.
            x = gather_from(x, self.axis, tiled_axis=-2)
        elif input_layout == "gathered":
            # caller already all-gathered x (e.g. once per sublayer, shared by
            # wq/wk/wv): use as-is; fan-out cotangents sum at the caller's
            # single gather, whose transpose is one psum_scatter.
            pass
        else:
            raise ValueError(f"unknown input_layout {input_layout!r}")
        y = x.astype(compute_dtype) @ w
        return self._epilogue(params, y, compute_dtype)

    def _epilogue(self, params: Params, y: jax.Array,
                  compute_dtype) -> jax.Array:
        if self.add_bias:
            y = y + params["bias"].astype(compute_dtype)
        if self.gather_output:
            y = gather_from(y, self.axis)               # (.., odim/n) -> (.., odim)
        return y


@dataclass(frozen=True)
class RowParallelLinear:
    """Y = X @ W + b with W's input dim sharded over `axis`.

    Reference: `/root/reference/models/layers.py:14-55`.
    forward: optional split -> local matmul -> reduce (all-reduce) -> + full bias.
    `split_input=False` is the Megatron fused pattern: the input is already
    sharded (it came from a gather_output=False column-parallel layer).
    """

    idim: int
    odim: int
    add_bias: bool = True
    split_input: bool = True
    axis: str = "tp"
    # 'ring' decomposes the sequence-parallel output reduce-scatter into a
    # ring collective matmul (ops/overlap.matmul_rs): partial dots feed the
    # reduce ring chunk by chunk instead of blocking on one psum_scatter.
    # 'ring_q' additionally requantizes the circulating accumulator to
    # int8 before each hop. Only the output_layout='seq_sharded' path
    # changes; 'off' is today's.
    overlap: str = "off"

    def __post_init__(self):
        _check_overlap(self.overlap)

    def init(self, key: jax.Array) -> Params:
        p: Params = {"weight": _torch_linear_init(key, self.idim, self.odim)}
        if self.add_bias:
            p["bias"] = jnp.zeros((self.odim,), jnp.float32)
        return p

    def specs(self) -> Params:
        s: Params = {"weight": P(self.axis, None)}
        if self.add_bias:
            s["bias"] = P(None)  # replicated, added after the reduce
        return s

    def apply(self, params: Params, x: jax.Array,
              compute_dtype: jnp.dtype = jnp.float32,
              output_layout: str = "replicated",
              seq_order: str = "rank") -> jax.Array:
        if self.split_input:
            x = split_to(x, self.axis)                  # (.., idim) -> (.., idim/n)
        w = params["weight"].astype(compute_dtype)      # local (idim/n, odim)
        on_ring = output_layout == "seq_sharded" and self.overlap != "off"
        _check_seq_order(seq_order, on_ring)
        if on_ring:
            # ring collective matmul: per-chunk partial dots interleave with
            # the reduce ring's hops instead of one blocking psum_scatter.
            # It reads x in ring order: seq_order='ring' says x already is.
            x = x.astype(compute_dtype)
            if seq_order == "rank":
                x = ring_order(x, self.axis)
            y = matmul_rs(x, w, self.axis, self.overlap == "ring_q")
        elif output_layout == "replicated":
            y = reduce_from(x.astype(compute_dtype) @ w, self.axis)
        elif output_layout == "seq_sharded":
            # Megatron sequence parallelism: reduce-scatter the partial sums
            # over the sequence dim — each shard keeps summed (b, t/n, odim).
            # Bias (full over odim) still applies per token, after the reduce
            # like the reference (`layers.py:53-54`).
            y = reduce_scatter(x.astype(compute_dtype) @ w, self.axis,
                               scatter_axis=-2)
        else:
            raise ValueError(f"unknown output_layout {output_layout!r}")
        if self.add_bias:
            y = y + params["bias"].astype(compute_dtype)
        return y


def apply_column_ring_fused(params_list, x: jax.Array, compute_dtype,
                            axis: str = "tp", quantized: bool = False,
                            seq_order: str = "rank"):
    """Several column-parallel projections of ONE seq-sharded input on ONE
    shared ring (wq/wk/wv, gate/up): the fused ag_matmul moves exactly the
    bytes of the single shared all-gather the monolithic path uses, and the
    custom VJP sums the fan-out cotangents on one reverse ring — the same
    one-psum_scatter-per-sublayer traffic as the shared-gather transpose.

    `params_list` is a sequence of ColumnParallelLinear param dicts (the
    layers must all be gather_output=False, which the model pattern
    guarantees). Returns one local (.., t, odim/n) output per entry.
    `quantized` (tp_overlap='ring_q') puts int8 payloads on the shared
    ring — still one quantization per chunk, however many weights ride it.
    `seq_order='ring'` leaves the outputs in the ring's own chunk order
    (`_check_seq_order`), for consumers that do not care where a token sits.
    """
    _check_seq_order(seq_order, True)
    ws = tuple(p["weight"].astype(compute_dtype) for p in params_list)
    ys = ag_matmul(x.astype(compute_dtype), ws, axis, quantized)
    out = []
    for p, y in zip(params_list, ys):
        if seq_order == "rank":
            y = ring_order(y, axis)
        if "bias" in p:
            y = y + p["bias"].astype(compute_dtype)
        out.append(y)
    return out
