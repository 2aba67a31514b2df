"""Differential attention, SambaY's attention layers (arXiv:2410.05258).

`H` differential heads over `J` key-value pairs (`H / J` heads read a pair):
a head m has two queries `q1`, `q2` (`h` wide each), its pair j = m // (H /
J) two keys `k1`, `k2` (`h` wide) and ONE value `v` (2 h wide):

    A_i = softmax(q_i k_i^T / sqrt(h) + mask)            i = 1, 2
    o   = (A_1 - lambda A_2) v
    lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init
    o  <- w * RMSNorm over the head's 2 h (o) * (1 - lambda_init)
    out = concat_heads(o) W_o + b_o

`lambda_init` is the layer's own constant, `0.8 - 0.6 exp(-0.3 l)` at its
PUBLISHED index l (`lambda_init_of`); the four vectors (h,) and the norm's
weight (2 h,) are learned a layer. The mask is the caller's, causal or a
window; no positions.

**One attention call.** `(A_1 - lambda A_2) v = A_1 v - lambda A_2 v`: the
two maps are `2 H` ordinary heads `h` wide over `2 J` key heads, each
reading the pair's value, `2 h` wide, which is the flash kernel's path with
keys and values of different widths under grouping `H / J`. So the columns
are laid out for that call (the published fused `W_qkv`'s columns permuted;
a permutation of columns, stated once, the same in the reference):

* `wq` (d, 2 H h): query head `(2 j + i) (H / J) + r` is map i of
  differential head `m = j (H / J) + r`;
* `wk` (d, 2 J h): key head `2 j + i` is key i of pair j;
* `wv` (d, J 2 h): value j, handed to the call twice (once a map);

so query heads `[(2 j + i) (H / J), (2 j + i + 1) (H / J))` read key head `2
j + i`: the call's own grouping. `qkv` makes the three, `heads` lays them out
for the call ((b, heads, t, width), the value repeated), `project` takes the
call's output (b, 2 H, t, 2 h) to the sublayer's. A layer that reads ANOTHER
layer's keys and values (SambaY's cross-attention) holds `wq` and its bias
only: `queries_only`.

Biases on `wq` / `wk` / `wv` and `wo` (`bias`); the ladder's names `q_proj`
/ `k_proj` / `v_proj` on the projections. Nothing here reduces over a mesh
axis. Scope: the caller's (`diff_attn` / `cross_attn`). The layer counts
`diff_lambda`.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ..runtime.prng import fold
from .linear import uniform_fan_in

Params = Dict[str, Any]


def lambda_init_of(layer_index) -> float:
    """The published rule, at a layer's PUBLISHED index."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer_index)


@dataclass(frozen=True)
class DifferentialAttention:
    d: int
    heads: int                  # differential heads H
    pairs: int                  # key-value pairs J
    head_dim: int               # h: a query's and a key's width
    eps: float = 1e-5
    bias: bool = True
    lambda_std: float = 0.1
    queries_only: bool = False  # the keys and values are another layer's

    def __post_init__(self):
        if self.heads % self.pairs:
            raise ValueError(f"differential heads {self.heads} must be a "
                             f"multiple of key-value pairs {self.pairs}")

    @property
    def group(self) -> int:
        return self.heads // self.pairs

    @property
    def widths(self) -> Dict[str, int]:
        h = self.head_dim
        own = {"wq": 2 * self.heads * h}
        if not self.queries_only:
            own.update(wk=2 * self.pairs * h, wv=self.pairs * 2 * h)
        return own

    def num_params(self) -> int:
        h = self.head_dim
        out = self.heads * 2 * h
        return (sum(self.d * w + self.bias * w for w in self.widths.values())
                + out * self.d + self.bias * self.d + 4 * h + 2 * h)

    def init(self, key: jax.Array) -> Params:
        h = self.head_dim
        out = self.heads * 2 * h
        lin = lambda name, i, o: {
            "weight": uniform_fan_in(fold(key, name), (i, o), i),
            **({"bias": jnp.zeros((o,), jnp.float32)} if self.bias else {})}
        vec = lambda name: self.lambda_std * jax.random.normal(
            fold(key, name), (h,), jnp.float32)
        return {**{name: lin(name, self.d, w)
                   for name, w in self.widths.items()},
                "wo": lin("wo", out, self.d),
                "lambda_q1": vec("lambda_q1"), "lambda_k1": vec("lambda_k1"),
                "lambda_q2": vec("lambda_q2"), "lambda_k2": vec("lambda_k2"),
                "subln": jnp.ones((2 * h,), jnp.float32)}

    def specs(self) -> Params:
        lin = {"weight": P(None, None),
               **({"bias": P(None)} if self.bias else {})}
        return {**{name: dict(lin) for name in (*self.widths, "wo")},
                "lambda_q1": P(None), "lambda_k1": P(None),
                "lambda_q2": P(None), "lambda_k2": P(None),
                "subln": P(None)}

    # ---- forward (per-shard, inside shard_map) ----

    def _linear(self, p: Params, x: jax.Array, dtype) -> jax.Array:
        y = x @ p["weight"].astype(dtype)
        return y + p["bias"].astype(dtype) if self.bias else y

    def qkv(self, params: Params, n: jax.Array,
            dtype) -> Tuple[jax.Array, ...]:
        """n (b, t, d) -> the projections this layer holds, (b, t, width)
        each: (q, k, v), or (q,) of a `queries_only` layer."""
        n = n.astype(dtype)
        return tuple(
            checkpoint_name(self._linear(params[name], n, dtype),
                            f"{name[1]}_proj") for name in self.widths)

    def heads_of(self, q: jax.Array, k: jax.Array, v: jax.Array):
        """The projections (b, t, width) as the attention call takes them:
        q (b, 2 H, t, h), k (b, 2 J, t, h), v (b, 2 J, t, 2 h), a pair's
        value once a map."""
        b, t, _ = q.shape
        split = lambda z, w: z.reshape(b, t, -1, w).transpose(0, 2, 1, 3)
        h = self.head_dim
        return split(q, h), split(k, h), jnp.repeat(split(v, 2 * h), 2,
                                                    axis=1)

    def lambda_of(self, params: Params, lambda_init) -> jax.Array:
        f32 = jnp.float32
        dot = lambda a, b: jnp.sum(params[a].astype(f32)
                                   * params[b].astype(f32))
        return (jnp.exp(dot("lambda_q1", "lambda_k1"))
                - jnp.exp(dot("lambda_q2", "lambda_k2")) + lambda_init)

    def out_scale(self, lambda_init):
        """What the normed heads are multiplied by: `1 - lambda_init`."""
        return 1.0 - lambda_init

    def project(self, params: Params, o: jax.Array, lambda_init,
                dtype) -> Tuple[jax.Array, Params]:
        """The call's output o (b, 2 H, t, 2 h), map i of head m at `(2 j +
        i) (H / J) + r`, -> (the sublayer's output (b, t, d); the counter
        `diff_lambda`)."""
        b, _, t, w = o.shape
        f32 = jnp.float32
        lam = self.lambda_of(params, lambda_init)
        o = o.reshape(b, self.pairs, 2, self.group, t, w).astype(f32)
        o = o[:, :, 0] - lam * o[:, :, 1]           # (b, J, H / J, t, 2 h)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + self.eps)
        o = (params["subln"] * self.out_scale(lambda_init)) * o
        # (b, J, H / J, t, 2 h) -> (b, t, H 2 h), head m = j (H / J) + r
        o = o.transpose(0, 3, 1, 2, 4).reshape(b, t, self.heads * w)
        return (self._linear(params["wo"], o.astype(dtype), dtype),
                {"diff_lambda": jax.lax.stop_gradient(lam)})
