"""The Mamba-1 selective scan.

A channel c holds a state of `N` numbers, float32, and every channel reads
the token's one `B_t` and `C_t`:

    h_t[c, n] = exp(dt_t[c] A[c, n]) h_{t-1}[c, n] + dt_t[c] B_t[n] u_t[c]
    y_t[c]    = sum_n C_t[n] h_t[c, n]

with `A` negative, a (channel, state) TABLE, and `dt_t[c]` > 0. Mamba-2
(`ops/ssd.py`) has one decay a head, which is what lets a chunk be three
matrix products; here the decay differs for every (channel, state) pair, so
no `C B^T` a chunk exists and the recurrence is `c x N` scalar scans of t
steps, in order: work for the vector unit. The states of every step are `(t,
c, N)` float32 (5.4 GB at 16,384 tokens of 5120 channels) and are never
held: the state, the decays and their products are float32 whatever u's
dtype (`D u` and the gate are the mixer's, parallel/mamba1.py).

**Two paths, one rule.** On a TPU, at shapes the kernels hold
(`ops/pallas/selective_scan.holds`: channels in whole lane tiles, a state in
whole sublane tiles) and a float32 state, `selective_scan` runs two Pallas
kernels under a `jax.custom_vjp` (`sscan_fwd`, `sscan_bwd`): the state stays
in vector registers over a chunk's tokens and in VMEM between chunks, and
between forward and backward a layer keeps its inputs and the state each
chunk of 128 tokens ENTERED with. Everywhere else it runs `_scan_text`: a
`lax.scan` over chunks of `chunk` tokens whose body, a `lax.scan` over the
chunk's tokens, is a `jax.checkpoint`, so autodiff keeps a state a chunk and
makes a chunk's states again in its backward; the CPU's path and the
kernels' oracle. Decided in `selective_scan` from what the call sees and said
on the program's tracer (the instant `sscan`, once a trace); `interpret=True`
asks for the kernels under the Pallas interpreter.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..obs.trace import current_tracer
from .collectives import copy_to
from .pallas import selective_scan as kernels

CHUNK = 64      # tokens a checkpointed chunk of the text


def selective_scan(u: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                   C: jax.Array, chunk: int = CHUNK, state_dtype=jnp.float32,
                   interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """u (b, t, c), dt (b, t, c) float32 and positive, A (c, N) float32 and
    negative, B and C (b, t, N) -> (y (b, t, c) float32, `decay_min`: the
    most negative `dt A` of one step). `state_dtype` is the precision the
    decays and the state are kept at (float32; a test and the benchmark's
    control hand bfloat16 to show what that loses, which takes the text on
    every backend). The kernels or the text: module docstring."""
    b, t, c = u.shape
    N = A.shape[1]
    held = jnp.finfo(state_dtype).bits == 32 and kernels.holds(c, N)
    if interpret and not held:
        raise ValueError(
            f"the selective scan's kernels do not hold {c} channels over a "
            f"state of {N} in {jnp.dtype(state_dtype).name}: channels in "
            f"multiples of 128, a state in multiples of 8, float32")
    on_kernels = interpret or (held and jax.default_backend() == "tpu")
    tracer = current_tracer()
    if tracer is not None:
        tracer.instant("sscan", path="kernel" if on_kernels else "xla",
                       channels=c, state=N, tokens=b * t, dtype=str(u.dtype))
    decay_min = lax.stop_gradient(jnp.min(dt * jnp.min(A, axis=1)))
    if not on_kernels:
        return _scan_text(u, dt, A, B, C, chunk, state_dtype), decay_min
    f32 = jnp.float32
    # (the table varies over the mesh axes the tokens vary over before the
    # hand-written transpose sees it: the cast's own transpose is the sum of
    # the shards' dA)
    vma = tuple(jax.typeof(u).vma)
    At = copy_to(A.T.astype(f32), vma) if vma else A.T.astype(f32)
    y = _scan_kernels(interpret, u, dt.astype(f32), At, B.astype(f32),
                      C.astype(f32))
    return y, decay_min


def _scan_text(u, dt, A, B, C, chunk: int, state_dtype):
    """The recurrence as XLA text: the state `(b, N, c)` (the channels
    last), a token at a time inside checkpointed chunks."""
    b, t, c = u.shape
    At = A.T.astype(state_dtype)

    def token(h, row):
        u_t, dt_t, B_t, C_t = row            # (b, c), (b, c), (b, N), (b, N)
        a = jnp.exp(dt_t.astype(state_dtype)[:, None, :] * At)
        x = (dt_t * u_t.astype(jnp.float32))[:, None, :] * B_t[:, :, None]
        h = (a * h + x.astype(state_dtype)).astype(state_dtype)
        return h, jnp.sum(C_t[:, :, None] * h.astype(jnp.float32), axis=1)

    @jax.checkpoint
    def block(h, rows):
        return lax.scan(token, h, rows)

    pad = -t % chunk
    rows = tuple(
        jnp.moveaxis(jnp.pad(z, ((0, 0), (0, pad), (0, 0))), 1, 0).reshape(
            (t + pad) // chunk, chunk, b, z.shape[-1])
        for z in (u, dt, B.astype(jnp.float32), C.astype(jnp.float32)))
    # (zeros that vary over the mesh axes the operands vary over)
    h = (dt[:, 0, None, :] * At * 0).astype(state_dtype)
    _, y = lax.scan(block, h, rows)
    return jnp.moveaxis(y.reshape(t + pad, b, c), 0, 1)[:, :t]


# ---------------------------------------------------------------- kernels

def _kernel_inputs(u, dt, B, C):
    """The operands as the kernels take them: t padded to whole chunks
    (zeros: a padded step's decay is 1 and it adds nothing), B's and C's
    columns spread over one lane tile."""
    pad = -u.shape[1] % kernels.CHUNK
    rows = lambda z: jnp.pad(z, ((0, 0), (0, pad), (0, 0)))
    cols = lambda z: jnp.broadcast_to(
        rows(z)[..., None], (*z.shape[:1], z.shape[1] + pad, z.shape[2],
                             kernels.LANES))
    return rows(u), rows(dt), cols(B), cols(C)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _scan_kernels(interpret: bool, u, dt, At, B, C):
    """`_scan_text` at a float32 state as one kernel call; its backward is
    one more, by hand. u (b, t, c), dt (b, t, c), At (N, c), B and C (b, t,
    N), all float32 but u -> y (b, t, c) float32."""
    return _kernels_fwd(interpret, u, dt, At, B, C, residuals=False)[0]


def _kernels_fwd(interpret, u, dt, At, B, C, residuals=True):
    # the caller's fusions end here and begin again after (a fused producer
    # would be made once a grid step)
    up, dtp, Bb, Cb = _kernel_inputs(u, dt, B, C)
    y, *H_in = kernels.forward(up, dtp, At, Bb, Cb, residuals=residuals,
                               interpret=interpret)
    return y[:, :u.shape[1]], (u, dt, At, B, C, *H_in)


def _kernels_bwd(interpret, saved, dy):
    u, dt, At, B, C, H_in = saved
    t = u.shape[1]
    up, dtp, Bb, Cb = _kernel_inputs(u, dt, B, C)
    dy = jnp.pad(dy.astype(jnp.float32),
                 ((0, 0), (0, up.shape[1] - t), (0, 0)))
    du, ddt, dA, dBb, dCb = kernels.backward(up, dtp, At, Bb, Cb, H_in, dy,
                                             interpret=interpret)
    return (du[:, :t].astype(u.dtype), ddt[:, :t], jnp.sum(dA, axis=(0, 1)),
            jnp.sum(dBb[:, :t], axis=-1), jnp.sum(dCb[:, :t], axis=-1))


_scan_kernels.defvjp(_kernels_fwd, _kernels_bwd)
