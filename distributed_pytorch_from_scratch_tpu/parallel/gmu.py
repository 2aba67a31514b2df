"""The gated memory unit, SambaY's cross-decoder layer that reads a memory.

For the normed activation `n` (b, t, d) and the memory `M` (b, t, inner),
the scan output ONE Mamba-1 layer of the self-decoder left (parallel/
mamba1.py; models/sambay.py hands it down):

    out = (M * silu(n W_1)) W_2           W_1 (d, inner), W_2 (inner, d)

no bias, no state of its own: an element-wise gate on the memory, token by
token, between two matrices. The parameters are the published `in_proj` /
`out_proj` of a `Phi3Mamba` built with `yoco_cross`: `w_in`, `w_out`.

Scope for a device trace: `gmu`.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..runtime.prng import fold
from .linear import uniform_fan_in
from .mamba1 import gate

Params = Dict[str, Any]


@dataclass(frozen=True)
class GatedMemoryUnit:
    d: int
    inner: int

    def num_params(self) -> int:
        return 2 * self.d * self.inner

    def init(self, key: jax.Array) -> Params:
        return {"w_in": uniform_fan_in(fold(key, "w_in"),
                                       (self.d, self.inner), self.d),
                "w_out": uniform_fan_in(fold(key, "w_out"),
                                        (self.inner, self.d), self.inner)}

    def specs(self) -> Params:
        return {"w_in": P(None, None), "w_out": P(None, None)}

    def apply(self, params: Params, n: jax.Array, memory: jax.Array,
              compute_dtype: jnp.dtype = jnp.float32) -> jax.Array:
        """n (b, t, d), memory (b, t, inner) -> (b, t, d)."""
        with jax.named_scope("gmu"):
            z = n.astype(compute_dtype) @ params["w_in"].astype(compute_dtype)
            return (gate(memory.astype(compute_dtype), z)
                    @ params["w_out"].astype(compute_dtype))
