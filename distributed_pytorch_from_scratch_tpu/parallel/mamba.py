"""The Mamba-2 mixer, Nemotron-H's state-space layer (training form).

For the normed activation `u` (b, t, d), with `H` heads of width `P`, a
state `N` wide and `G` groups of B and C (a group's are read by its `H / G`
heads):

    [z | xBC | dt] = u W_in              widths  H P | H P + 2 G N | H
    xBC <- silu(causal depthwise conv over time (xBC) + bias)
    [x | B | C] = xBC                    widths  H P | G N | G N
    dt = softplus(dt + dt_bias)   A = -exp(A_log)           (float32, a head)
    y = ssd(x, dt, A, B, C) + D x                           (ops/ssd.py)
    y <- w * RMSNorm over groups of H P / G channels (y * silu(z))
    out = y W_out

The gate comes FIRST and the norm after it, over a group's channels (the
published `norm_before_gate` false; `parallel/norm.GatedRMSNorm` norms first
and over one head).

**Parameter layout**, the published checkpoint's: `w_in` (d, 2 H P + 2 G N +
H) with the columns in the order above, the convolution `conv` (H P + 2 G N,
taps) and its bias over the `[x | B | C]` channels, `A_log`, `D`, `dt_bias`
(H,), the norm's weight (H P,), `w_out` (H P, d).

**A share of the heads.** The mixer is BUILT at the heads and groups it
holds (`heads`, `groups`): one tensor-parallel rank's of a deployment that
splits the `total_heads` by heads (Nemotron-H's `n_groups` 8 exists so that
up to eight ranks each hold whole groups), heads `[head_offset, head_offset
+ heads)`. A head's `A_log` at init is `log(1 + its index among ALL the
heads)`, so a share starts as the uncut mixer's heads do. The gated norm's
group is `P x heads / groups` channels either way. Nothing here reduces
over a mesh axis: the sum over the ranks' `w_out` products is the
deployment's (ROADMAP: a mixer split by heads over a `tp` axis).

The recurrence is `ops/ssd.ssd`'s: two Pallas kernels on a TPU that read
and write x and y as `(b, t, H P)`, the layout they have here (the `(b, t, H,
P)` its signature takes is a reshape that cancels against its own), and XLA
text everywhere else.

Scopes for a device trace: `mamba/in_proj`, `mamba/conv`, `mamba/ssd`,
`mamba/gate_norm`, `mamba/out_proj`. The layer counts `ssm_decay_min`, the
smallest `dt A` summed over a chunk (how close a chunk's `exp` comes to
underflow).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.conv import causal_depthwise_conv
from ..ops.ssd import CHUNK, ssd
from ..runtime.prng import fold
from .linear import uniform_fan_in

Params = Dict[str, Any]


@dataclass(frozen=True)
class Mamba2Mixer:
    d: int
    heads: int                  # the heads HELD
    head_dim: int
    state: int
    groups: int                 # the B / C groups HELD
    conv_kernel: int = 4
    chunk: int = CHUNK
    eps: float = 1e-5
    head_offset: int = 0        # the first head held, among all the heads
    # dt at init: exp(U(log dt_min, log dt_max)), floored (`time_step_*`)
    dt_min: float = 1e-3
    dt_max: float = 1e-1
    dt_floor: float = 1e-4

    def __post_init__(self):
        if self.heads % self.groups:
            raise ValueError(
                f"the mixer's {self.heads} heads must be whole groups of "
                f"its {self.groups} B / C groups")

    @property
    def inner(self) -> int:
        return self.heads * self.head_dim

    @property
    def conv_channels(self) -> int:
        """[x | B | C]."""
        return self.inner + 2 * self.groups * self.state

    def num_params(self) -> int:
        return (self.d * (self.inner + self.conv_channels + self.heads)
                + self.conv_channels * (self.conv_kernel + 1)
                + 3 * self.heads + self.inner + self.inner * self.d)

    # ---- init / specs ----

    def init(self, key: jax.Array) -> Params:
        w = lambda name, shape, fan_in: uniform_fan_in(fold(key, name),
                                                       shape, fan_in)
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            fold(key, "dt_bias"), (self.heads,), jnp.float32,
            math.log(self.dt_min), math.log(self.dt_max))), self.dt_floor)
        return {
            "w_in": w("w_in", (self.d, self.inner + self.conv_channels
                               + self.heads), self.d),
            "conv": w("conv", (self.conv_channels, self.conv_kernel),
                      self.conv_kernel),
            "conv_bias": w("conv_bias", (self.conv_channels,),
                           self.conv_kernel),
            "A_log": jnp.log(self.head_offset + 1.0
                             + jnp.arange(self.heads, dtype=jnp.float32)),
            "D": jnp.ones((self.heads,), jnp.float32),
            # the inverse softplus of dt
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "norm": jnp.ones((self.inner,), jnp.float32),
            "w_out": w("w_out", (self.inner, self.d), self.inner),
        }

    def specs(self) -> Params:
        return {"w_in": P(None, None), "conv": P(None, None),
                "conv_bias": P(None), "A_log": P(None), "D": P(None),
                "dt_bias": P(None), "norm": P(None), "w_out": P(None, None)}

    # ---- forward (per-shard, inside shard_map) ----

    def _gate_norm(self, w: jax.Array, y: jax.Array,
                   z: jax.Array) -> jax.Array:
        """`w * RMSNorm over a group's channels (y * silu(z))` in float32:
        the gate first, then the norm (y (b, t, H P) float32, z the gate's
        logits). A method of its own so that a control can run the other
        order (benchmark/tools/ssm_dense_control.py)."""
        b, t, _ = y.shape
        g = (y * jax.nn.silu(z.astype(jnp.float32))).reshape(
            b, t, self.groups, -1)
        g = g * jax.lax.rsqrt(
            jnp.mean(g * g, axis=-1, keepdims=True) + self.eps)
        return w * g.reshape(b, t, self.inner)

    def apply(self, params: Params, u: jax.Array,
              compute_dtype: jnp.dtype = jnp.float32
              ) -> Tuple[jax.Array, Params]:
        """u (b, t, d) -> (the sublayer's output (b, t, d); the counter
        `ssm_decay_min`, a scalar)."""
        b, t, _ = u.shape
        H, Pd, GN = self.heads, self.head_dim, self.groups * self.state
        f32 = jnp.float32
        with jax.named_scope("mamba"):
            with jax.named_scope("in_proj"):
                proj = u.astype(compute_dtype) @ params["w_in"].astype(
                    compute_dtype)
                z, xBC, dt = jnp.split(
                    proj, (self.inner, self.inner + self.conv_channels), -1)
            with jax.named_scope("conv"):
                xBC = jax.nn.silu(
                    causal_depthwise_conv(xBC, params["conv"])
                    + params["conv_bias"]).astype(compute_dtype)
                x, B, C = jnp.split(xBC, (self.inner, self.inner + GN), -1)
            with jax.named_scope("ssd"):
                dt = jax.nn.softplus(dt.astype(f32) + params["dt_bias"])
                y, decay_min = ssd(
                    x.reshape(b, t, H, Pd), dt, -jnp.exp(params["A_log"]),
                    B.reshape(b, t, self.groups, self.state),
                    C.reshape(b, t, self.groups, self.state), self.chunk)
                # `D x` on (b, t, H P) as it lies, D a head's over its
                # channels: a (.., H, P) array of 64-wide heads is tiled
                # otherwise, and each turn between the two is a copy
                y = (y.reshape(b, t, self.inner).astype(f32)
                     + jnp.repeat(params["D"], Pd) * x.astype(f32))
            with jax.named_scope("gate_norm"):
                y = self._gate_norm(params["norm"], y, z).astype(
                    compute_dtype)
            with jax.named_scope("out_proj"):
                out = y @ params["w_out"].astype(compute_dtype)
        return out, {"ssm_decay_min": decay_min}
