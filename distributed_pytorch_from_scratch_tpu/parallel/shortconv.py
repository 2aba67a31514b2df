"""The gated short convolution, LFM2's mixer (training form).

For the normed activation `x` (b, t, d), over `d` channels:

    [B | C | u] = x W_in                    (d -> 3 d, the thirds in that order)
    h = B * u
    c_t = sum_j w[:, j] * h_{t - (taps - 1) + j}     causal, depthwise, no
                                                     bias, NO activation
    y = (C * c) W_out                       (d -> d)

The products' operands are the compute dtype, the taps' sum float32
(`ops/conv.causal_depthwise_conv`, the tree's one convolution).

**Parameter layout.** `w_in` is (d, 3, d): `w_in[:, 0]` makes B, `[:, 1]` C,
`[:, 2]` u; the published checkpoint's `in_proj` (3 d, d) is its transpose
with the thirds stacked. `conv` is (d, taps) with tap `taps - 1` reading the
token itself; `w_out` (d, d), rows by channel. Tensor parallelism shards the
CHANNELS: `w_in` by its last axis (a channel's B, C and u on one rank), the
convolution with them, `w_out` by rows, the Megatron pattern: one
all-reduce after `w_out`.

Scope for a device trace: `shortconv`.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.collectives import copy_to, reduce_from
from ..ops.conv import causal_depthwise_conv
from ..runtime.prng import fold
from .linear import uniform_fan_in

Params = Dict[str, Any]


@dataclass(frozen=True)
class ShortConv:
    d: int
    taps: int = 3
    tp_size: int = 1
    tp_axis: str = "tp"

    def __post_init__(self):
        if self.d % self.tp_size:
            raise ValueError(f"the convolution's {self.d} channels are not "
                             f"divisible by tp_size {self.tp_size}")
        if self.taps < 1:
            raise ValueError(f"taps must be >= 1, got {self.taps}")

    def num_params(self) -> int:
        return 3 * self.d * self.d + self.d * self.taps + self.d * self.d

    def init(self, key: jax.Array) -> Params:
        d = self.d
        w = lambda name, shape, fan_in: uniform_fan_in(fold(key, name),
                                                       shape, fan_in)
        return {"w_in": w("w_in", (d, 3, d), d),
                "conv": w("conv", (d, self.taps), self.taps),
                "w_out": w("w_out", (d, d), d)}

    def specs(self) -> Params:
        tp = self.tp_axis
        return {"w_in": P(None, None, tp), "conv": P(tp, None),
                "w_out": P(tp, None)}

    def apply(self, params: Params, x: jax.Array,
              compute_dtype: jnp.dtype = jnp.float32) -> jax.Array:
        """x (b, t, d), replicated over tp -> the sublayer's output (b, t,
        d), reduced over tp."""
        with jax.named_scope("shortconv"):
            xd = copy_to(x.astype(compute_dtype), self.tp_axis)
            w_in = params["w_in"].astype(compute_dtype)
            # one product for the three thirds: (d, 3 x local channels)
            proj = xd @ w_in.reshape(w_in.shape[0], -1)
            B, C, u = jnp.split(proj, 3, axis=-1)
            c = causal_depthwise_conv(B * u, params["conv"])
            y = (C * c.astype(compute_dtype)) @ params["w_out"].astype(
                compute_dtype)
            return reduce_from(y, self.tp_axis)
