"""RMSNorm + LayerNorm — replicated (not parallel), computed in f32.

RMSNorm mirrors `/root/reference/models/layers.py:145-155` ("Borrowed from
LLama"): `scale * x * rsqrt(mean(x^2) + eps)`, f32 compute, cast back.
LayerNorm (scale + bias, mean-centered) serves the GPT-2 model family
(`models/gpt2.py`) — the reference has no GPT-2 family; this is a framework
extension built on the same functional-module pattern. eps=1e-5 for both.
`ZeroCenteredRMSNorm` (`x / rms * (1 + w)`, w from zeros) and `GatedRMSNorm`
(`w * x / rms * silu(z)`, the gated delta rule's output norm) serve the
`gdn_moe` family (`models/gdn_moe.py`).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

Params = Dict[str, Any]


@dataclass(frozen=True)
class RMSNorm:
    hdim: int
    eps: float = 1e-5

    def init(self, key: jax.Array) -> Params:
        del key
        return {"scale": jnp.ones((self.hdim,), jnp.float32)}

    def specs(self) -> Params:
        return {"scale": P(None)}

    def apply(self, params: Params, x: jax.Array) -> jax.Array:
        xf = x.astype(jnp.float32)
        normed = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + self.eps)
        return (params["scale"].astype(x.dtype) * normed.astype(x.dtype))


@dataclass(frozen=True)
class LayerNorm:
    hdim: int
    eps: float = 1e-5

    def init(self, key: jax.Array) -> Params:
        del key
        return {"scale": jnp.ones((self.hdim,), jnp.float32),
                "bias": jnp.zeros((self.hdim,), jnp.float32)}

    def specs(self) -> Params:
        return {"scale": P(None), "bias": P(None)}

    def apply(self, params: Params, x: jax.Array) -> jax.Array:
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
        normed = ((xf - mean) * jax.lax.rsqrt(var + self.eps)).astype(x.dtype)
        return (params["scale"].astype(x.dtype) * normed
                + params["bias"].astype(x.dtype))


def _rms_normed(x: jax.Array, eps: float) -> jax.Array:
    """x / rms(x) over the last axis, float32."""
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)


@dataclass(frozen=True)
class ZeroCenteredRMSNorm:
    """`x / rms(x) * (1 + w)` in float32, `w` from zeros (Qwen3-Next's
    norms: the stored weight is the offset from one)."""

    hdim: int
    eps: float = 1e-6

    def init(self, key: jax.Array) -> Params:
        del key
        return {"scale": jnp.zeros((self.hdim,), jnp.float32)}

    def specs(self) -> Params:
        return {"scale": P(None)}

    def apply(self, params: Params, x: jax.Array) -> jax.Array:
        return (_rms_normed(x, self.eps)
                * (1.0 + params["scale"])).astype(x.dtype)


@dataclass(frozen=True)
class GatedRMSNorm:
    """`w * x / rms(x) * silu(z)` in float32 over the last axis (one head),
    `w` from ones: the plain norm, gated by `z` of x's shape. `gate` names
    the gate's activation: "silu" (Gated DeltaNet's) or "sigmoid" (Kimi
    Delta Attention's)."""

    hdim: int
    eps: float = 1e-6
    gate: str = "silu"

    def init(self, key: jax.Array) -> Params:
        del key
        return {"scale": jnp.ones((self.hdim,), jnp.float32)}

    def specs(self) -> Params:
        return {"scale": P(None)}

    def apply(self, params: Params, x: jax.Array, z: jax.Array) -> jax.Array:
        act = {"silu": jax.nn.silu, "sigmoid": jax.nn.sigmoid}[self.gate]
        return (params["scale"] * _rms_normed(x, self.eps)
                * act(z.astype(jnp.float32))).astype(x.dtype)
