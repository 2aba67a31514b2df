"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880): a layer's
residual state as `n` streams, mixed at every sublayer by maps made from
the streams themselves.

Per token, X in R^{n x C} the residual state and F a sublayer with its norm:

    x = flatten(X) in R^{nC}
    m = (x W) rsqrt(mean(x^2) + norm_eps)                  n^2 + 2n numbers
    pre  = sigmoid(alpha_0 m[0:n]  + b[0:n]) + eps
    post = 2 sigmoid(alpha_1 m[n:2n] + b[n:2n])
    H~   = clamp(alpha_2 mat(m[2n:]) + b[2n:], clamp_min, clamp_max)
    H    = Sinkhorn(exp(H~)): `sinkhorn_iters` rounds of every row over
           (its sum + eps), then every column over (its sum + eps)
    u = sum_i pre_i X[i];  y = F(u);  X'[i] = sum_j H[i, j] X[j] + post_i y

`StreamMixer` is one such mixer: `maps` (m, the sigmoids, the Sinkhorn
rounds), `pre` (the weighted sum a sublayer reads) and `post` (what the
sublayer's output joins). The mixer behind the last layer (`exit_only`) has
`pre` alone: h = sum_i pre_i X[i], which the final norm reads (`exit`).

Layout: the streams are carried `(n, b, t, C)` in the compute dtype, the
stream axis leading, so that a stream is a contiguous (b, t, C) activation
like any other family's residual and nothing n wide lands on a tile's
sublanes; the maps are `(n, T)` / `(n, n, T)` with the tokens `T = b t` on
the lanes. **The maps are computed in float32 whatever the compute dtype**
(m's product at precision "highest": a sigmoid's argument and a Sinkhorn
round amplify what bfloat16 rounds away), the two weighted sums accumulate
in float32 and are rounded once to the streams' dtype. W, alpha and b are
float32 and replicated (every device mixes its own tokens).

Named scopes, for a device trace's `op_name`: `mhc` around every mixer,
with `mhc/maps`, `mhc/sinkhorn`, `mhc/pre`, `mhc/post`, `mhc/exit` beneath.

**Where the passes over the streams are made** (PR 58). On a TPU at a shape
they hold (`ops/pallas/stream_mixer.holds`: C a multiple of 128, bfloat16 or
float32 streams) every pass is one Pallas kernel over blocks of tokens
(ops/pallas/stream_mixer.py): `maps` calls the READ kernel, which makes m
and, from the same block in VMEM, u = sum_i pre_i X[i] (`StreamMaps.u`:
`pre` then hands it over), and `post` calls the WRITE kernel; their
transposes are a kernel each, and the write's part of dX is made in the
read's backward (`StreamMaps.through`), so no cotangent of the streams is
written, or added, in a pass of its own. What stays `jax.numpy` on every
path: the sigmoids, the clamp, exp and the Sinkhorn rounds on the `(width,
T)` maps.
Everywhere else (off the TPU, other widths) a mixer is the `jax.numpy` text
below: the CPU path and the kernels' oracle. The call decides from what it
sees, no option chooses; a test asks for the kernels under the Pallas
interpreter with `StreamMixer(..., interpret=True)`. Each joint says which
it took on the program's tracer (`mhc_joint`: `path` "kernel" | "xla").
The kernels' calls carry the scope of the part they replace: the read's
`mhc/maps` (`mhc/exit` of an exit mixer), the write's `mhc/post`.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..obs.trace import current_tracer
from ..ops.collectives import copy_to
from ..ops.pallas import stream_mixer as kernels
from ..runtime.prng import fold
from .linear import _torch_linear_init

Params = Dict[str, Any]


class StreamMaps(NamedTuple):
    """One mixer's maps of one layer input, float32, tokens last."""

    pre: jax.Array                      # (n, T)
    post: Optional[jax.Array]           # (n, T); None of an exit mixer
    res: Optional[jax.Array]            # (n, n, T): H[i, j], rows i
    # what the read kernel made beside the maps (None on the XLA path, and
    # of maps built by hand): u = sum_i pre_i X[i], (b, t, C), and what the
    # write joint takes of the read joint, the streams and the slot its
    # backward answers in (`kernels.read_streams`)
    u: Optional[jax.Array] = None
    through: Optional[tuple] = None


@dataclass(frozen=True)
class StreamMixer:
    """Static shape of one mixer (module docstring)."""

    d: int                      # C, a stream's width
    n: int                      # the streams (`hc_mult`)
    sinkhorn_iters: int = 20
    eps: float = 1e-6           # `hc_eps`
    norm_eps: float = 1e-6      # the model's `rms_norm_eps`
    clamp_min: float = -30.0
    clamp_max: float = 30.0
    exit_only: bool = False
    interpret: bool = False     # the kernels under the Pallas interpreter

    @property
    def width(self) -> int:
        """The numbers a token's maps are made of."""
        return self.n if self.exit_only else self.n * self.n + 2 * self.n

    def num_params(self) -> int:
        return self.n * self.d * self.width + (1 if self.exit_only
                                               else 3) + self.width

    def init(self, key: jax.Array) -> Params:
        """W uniform at 1/sqrt(nC) as every linear layer's, alpha at one
        and b standard normal: m is of order one, so H is visibly not the
        identity, `pre` not one stream, and the maps differ by token (a zero
        W makes every token's maps equal and hides a mixer that does not
        read its input)."""
        return {"w": _torch_linear_init(fold(key, "w"), self.n * self.d,
                                        self.width),
                "alpha": jnp.ones((1 if self.exit_only else 3,),
                                  jnp.float32),
                "b": jax.random.normal(fold(key, "b"), (self.width,),
                                       jnp.float32)}

    def specs(self) -> Params:
        return {"w": P(None, None), "alpha": P(None), "b": P(None)}

    # ---- the maps (float32) ----

    def _m(self, params: Params, X: jax.Array) -> jax.Array:
        """(x W) rsqrt(mean(x^2) + norm_eps) of every token: (width, T)."""
        n, d = self.n, self.d
        xf = X.reshape(n, -1, d).astype(jnp.float32)
        w = params["w"].astype(jnp.float32).reshape(n, d, self.width)
        m = jnp.einsum("ntc,nck->kt", xf, w,
                       precision=lax.Precision.HIGHEST)
        mean_sq = jnp.sum(jnp.square(xf), axis=(0, 2)) / (n * d)
        return m * lax.rsqrt(mean_sq + self.norm_eps)

    def sinkhorn(self, h: jax.Array) -> jax.Array:
        """exp(h), (n, n, T), through the rounds: rows first."""
        with jax.named_scope("sinkhorn"):
            mat = jnp.exp(h)
            for _ in range(self.sinkhorn_iters):
                mat = mat / (jnp.sum(mat, axis=1, keepdims=True) + self.eps)
                mat = mat / (jnp.sum(mat, axis=0, keepdims=True) + self.eps)
            return mat

    # ---- which path a joint takes ----

    def _kernels(self, X: jax.Array) -> bool:
        """Whether the passes over X are the Pallas kernels' (module
        docstring): decided from the backend and the shape."""
        held = kernels.holds(self.n, self.d, X.dtype)
        if self.interpret and not held:
            raise ValueError(
                f"the mixers' kernels do not hold {self.n} streams of width "
                f"{self.d} in {X.dtype}: C must be a multiple of 128")
        return self.interpret or (held and jax.default_backend() == "tpu")

    def _say(self, part: str, X: jax.Array, kernel: bool) -> None:
        """The joint's path on the program's tracer, once a trace."""
        tracer = current_tracer()
        if tracer is not None:
            tokens = X.shape[1] * X.shape[2]
            tracer.instant(
                "mhc_joint", path="kernel" if kernel else "xla", part=part,
                block=kernels.forward_block(self.n, self.d, tokens, X.dtype)
                if kernel else None, n=self.n, d=self.d, tokens=tokens,
                dtype=str(X.dtype))

    def _read(self, params: Params, X: jax.Array):
        """The read kernel's (m, u, through): `through` what a layer's
        mixer's write joint takes (`StreamMaps.through`), None of an exit
        mixer. Inside shard_map the parameters
        vary over what the streams do (their cotangents are the
        streams')."""
        w = params["w"].astype(jnp.float32)
        alpha0 = params["alpha"].astype(jnp.float32)[0]
        b_pre = params["b"].astype(jnp.float32)[:self.n]
        vma = tuple(jax.typeof(X).vma)
        if vma:
            w, alpha0, b_pre = (copy_to(a, vma) for a in (w, alpha0, b_pre))
        joint = kernels.Joint(self.width, self.eps, self.norm_eps,
                              self.interpret)
        m, u, *through = kernels.read_streams(
            joint, not self.exit_only, X, w, alpha0, b_pre)
        return m, u, through[0] if through else None

    def maps(self, params: Params, X: jax.Array) -> StreamMaps:
        """The maps of the streams X (n, b, t, C); on the kernel path with
        u and `through` for the write joint beside them."""
        n = self.n
        alpha = params["alpha"].astype(jnp.float32)
        b = params["b"].astype(jnp.float32)[:, None]
        kernel = self._kernels(X)
        self._say("exit" if self.exit_only else "read", X, kernel)
        u = through = None
        with jax.named_scope("mhc"):
            if kernel:
                with jax.named_scope("exit" if self.exit_only else "maps"):
                    m, u, through = self._read(params, X)
            with jax.named_scope("maps"):
                if not kernel:
                    m = self._m(params, X)
                pre = jax.nn.sigmoid(alpha[0] * m[:n] + b[:n]) + self.eps
                if self.exit_only:
                    return StreamMaps(pre, None, None, u)
                post = 2.0 * jax.nn.sigmoid(alpha[1] * m[n:2 * n]
                                            + b[n:2 * n])
                h = jnp.clip(alpha[2] * m[2 * n:] + b[2 * n:],
                             self.clamp_min, self.clamp_max)
            return StreamMaps(pre, post, self.sinkhorn(h.reshape(n, n, -1)),
                              u, through)

    # ---- the streams (the compute dtype, accumulated in float32) ----

    def pre(self, maps: StreamMaps, X: jax.Array,
            scope: str = "pre") -> jax.Array:
        """u = sum_i pre_i X[i]: (b, t, C), what the sublayer reads; the
        read kernel's where it made the maps."""
        if maps.u is not None:
            return maps.u
        with jax.named_scope("mhc"), jax.named_scope(scope):
            w = maps.pre.reshape(self.n, *X.shape[1:3], 1)
            return jnp.sum(w * X.astype(jnp.float32), axis=0).astype(X.dtype)

    def post(self, maps: StreamMaps, X: jax.Array,
             y: jax.Array) -> jax.Array:
        """X'[i] = sum_j H[i, j] X[j] + post_i y: the streams past the
        sublayer whose output is y (b, t, C)."""
        n = self.n
        kernel = self._kernels(X)
        self._say("write", X, kernel)
        with jax.named_scope("mhc"), jax.named_scope("post"):
            if kernel and maps.through is not None:
                return kernels.write_streams_through(
                    self.interpret, maps.through, y, maps.res, maps.post)
            if kernel:
                return kernels.write_streams(self.interpret, X, y, maps.res,
                                             maps.post)
            res = maps.res.reshape(n, n, *X.shape[1:3], 1)
            gain = maps.post.reshape(n, *X.shape[1:3], 1)
            xf = X.astype(jnp.float32)
            mixed = sum(res[:, j] * xf[j] for j in range(n))
            return (mixed + gain * y.astype(jnp.float32)).astype(X.dtype)

    def exit(self, params: Params, X: jax.Array) -> jax.Array:
        """h = sum_i pre_i X[i] of the exit mixer: what the final norm
        reads."""
        return self.pre(self.maps(params, X), X, scope="exit")

    # ---- what a layer counts of its mixers ----

    @staticmethod
    def counters(*maps: StreamMaps) -> Params:
        """Of the mixers of one layer (nothing here carries a gradient):
        `hc_sinkhorn_err`, the largest |row sum - 1| or |column sum - 1| of
        H over the tokens after the last round (the rows': what the rounds
        left undone); `hc_colsum_err`, the columns' alone, which were
        normalised last: `hc_eps` over a column's sum and the arithmetic's
        own rounding, so it says what the rounds were computed in; and
        `hc_res_offdiag`, the mean mass of a row of H off the diagonal (0:
        the streams never mix)."""
        rows, cols, off = [], [], []
        for mp in maps:
            res = lax.stop_gradient(mp.res)
            n = res.shape[0]
            rows.append(jnp.max(jnp.abs(jnp.sum(res, axis=1) - 1.0)))
            cols.append(jnp.max(jnp.abs(jnp.sum(res, axis=0) - 1.0)))
            diag = sum(res[i, i] for i in range(n))
            off.append(jnp.mean(jnp.sum(res, axis=(0, 1)) - diag) / n)
        cols = jnp.max(jnp.stack(cols))
        return {"hc_sinkhorn_err": jnp.maximum(jnp.max(jnp.stack(rows)),
                                               cols),
                "hc_colsum_err": cols,
                "hc_res_offdiag": jnp.mean(jnp.stack(off))}
