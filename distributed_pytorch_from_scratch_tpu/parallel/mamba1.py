"""The Mamba-1 mixer, SambaY's state-space layer (training form).

For the normed activation `n` (b, t, d), with `inner` = expand x d channels,
a state `N` wide a channel and a dt rank `R`:

    [u | z] = n W_in                         widths  inner | inner, no bias
    u <- silu(causal depthwise conv over time (u) + bias)
    [dt_r | B | C] = u W_x                   widths  R | N | N, no bias
    dt = softplus(dt_r W_dt + dt_bias)       (inner,), float32
    A = -exp(A_log)                          (inner, N), float32
    y = selective_scan(u, dt, A, B, C) + D u      (ops/selective_scan.py)
    out = (y * silu(z)) W_out                no bias

`scan` returns `y`, the scan's output BEFORE the gate, in the compute dtype,
beside `z`; `project` gates and projects it. They are two calls because
SambaY's cross-decoder reads the `y` of ONE layer as its memory
(`parallel/gmu.py`): the family hands it on (models/sambay.py).

**Parameter layout**, the published checkpoint's (`Phi3Mamba`): `w_in` (d, 2
inner) with the columns `[u | z]`, the convolution `conv` (inner, taps) and
its bias, `w_x` (inner, R + 2 N) with the columns `[dt_r | B | C]`, `w_dt`
(R, inner) and `dt_bias`, `A_log` (inner, N), `D` (inner,), `w_out` (inner,
d). At init `A_log = log(1..N)` a channel, `D` = 1, `dt_bias` the inverse
softplus of `exp(U(log dt_min, log dt_max))` floored, `w_dt` uniform at `1 /
sqrt(R)`: Mamba-1's own defaults.

Nothing here reduces over a mesh axis: the mixer is whole (ROADMAP: the scan
split by channels over a `tp` axis).

Scopes for a device trace: `mamba1/in_proj`, `mamba1/conv`, `mamba1/x_proj`,
`mamba1/dt_proj`, `mamba1/sscan`, `mamba1/gate`, `mamba1/out_proj`. The
layer counts `sscan_decay_min`, the most negative `dt A` of one step (how
close a step's `exp` comes to underflow).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.conv import causal_depthwise_conv
from ..ops.selective_scan import selective_scan
from ..runtime.prng import fold
from .linear import uniform_fan_in

Params = Dict[str, Any]


def gate(y: jax.Array, z: jax.Array) -> jax.Array:
    """`y * silu(z)`, taken in float32, in y's dtype: the gate's text,
    shared with the gated memory unit."""
    return (y.astype(jnp.float32)
            * jax.nn.silu(z.astype(jnp.float32))).astype(y.dtype)


@dataclass(frozen=True)
class Mamba1Mixer:
    d: int
    inner: int
    state: int = 16
    dt_rank: int = 0            # 0: ceil(d / 16)
    conv_kernel: int = 4
    # dt at init: exp(U(log dt_min, log dt_max)), floored
    dt_min: float = 1e-3
    dt_max: float = 1e-1
    dt_floor: float = 1e-4
    interpret: bool = False     # the scan's kernels under the interpreter

    @property
    def rank(self) -> int:
        return self.dt_rank or -(-self.d // 16)

    def num_params(self) -> int:
        return (self.d * 2 * self.inner + self.inner * (self.conv_kernel + 1)
                + self.inner * (self.rank + 2 * self.state)
                + self.rank * self.inner + self.inner
                + self.inner * self.state + self.inner + self.inner * self.d)

    # ---- init / specs ----

    def init(self, key: jax.Array) -> Params:
        w = lambda name, shape, fan_in: uniform_fan_in(fold(key, name),
                                                       shape, fan_in)
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            fold(key, "dt_bias"), (self.inner,), jnp.float32,
            math.log(self.dt_min), math.log(self.dt_max))), self.dt_floor)
        return {
            "w_in": w("w_in", (self.d, 2 * self.inner), self.d),
            "conv": w("conv", (self.inner, self.conv_kernel),
                      self.conv_kernel),
            "conv_bias": w("conv_bias", (self.inner,), self.conv_kernel),
            "w_x": w("w_x", (self.inner, self.rank + 2 * self.state),
                     self.inner),
            "w_dt": w("w_dt", (self.rank, self.inner), self.rank),
            # the inverse softplus of dt
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, self.state + 1, dtype=jnp.float32)),
                (self.inner, self.state)),
            "D": jnp.ones((self.inner,), jnp.float32),
            "w_out": w("w_out", (self.inner, self.d), self.inner),
        }

    def specs(self) -> Params:
        return {"w_in": P(None, None), "conv": P(None, None),
                "conv_bias": P(None), "w_x": P(None, None),
                "w_dt": P(None, None), "dt_bias": P(None),
                "A_log": P(None, None), "D": P(None), "w_out": P(None, None)}

    # ---- forward (per-shard, inside shard_map) ----

    def scan(self, params: Params, n: jax.Array,
             compute_dtype: jnp.dtype = jnp.float32
             ) -> Tuple[jax.Array, jax.Array, Params]:
        """n (b, t, d) -> (y (b, t, inner), the scan's output with `D u`,
        before the gate; the gate's logits z; the counter
        `sscan_decay_min`, a scalar)."""
        f32 = jnp.float32
        R, N = self.rank, self.state
        with jax.named_scope("mamba1"):
            with jax.named_scope("in_proj"):
                u, z = jnp.split(n.astype(compute_dtype)
                                 @ params["w_in"].astype(compute_dtype), 2, -1)
            with jax.named_scope("conv"):
                u = jax.nn.silu(causal_depthwise_conv(u, params["conv"])
                                + params["conv_bias"]).astype(compute_dtype)
            with jax.named_scope("x_proj"):
                dt_r, B, C = jnp.split(
                    u @ params["w_x"].astype(compute_dtype), (R, R + N), -1)
            with jax.named_scope("dt_proj"):
                dt = jax.nn.softplus(
                    (dt_r @ params["w_dt"].astype(compute_dtype)).astype(f32)
                    + params["dt_bias"])
            with jax.named_scope("sscan"):
                y, decay_min = selective_scan(
                    u, dt, -jnp.exp(params["A_log"]), B.astype(f32),
                    C.astype(f32), interpret=self.interpret)
                y = (y + params["D"] * u.astype(f32)).astype(compute_dtype)
        return y, z, {"sscan_decay_min": decay_min}

    def project(self, params: Params, y: jax.Array, z: jax.Array,
                compute_dtype: jnp.dtype = jnp.float32) -> jax.Array:
        """The gated output through `w_out`: (b, t, d)."""
        with jax.named_scope("mamba1"):
            with jax.named_scope("gate"):
                y = gate(y, z)
            with jax.named_scope("out_proj"):
                return y @ params["w_out"].astype(compute_dtype)
