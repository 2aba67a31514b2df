"""The Mamba-2 state-space recurrence in its chunked (SSD) form.

A head h holds a state `S` (P, N), float32, and reads the B and C of its
group (`G` groups of `H / G` heads each share one B and one C):

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        y_t = S_t C_t

with `A` a negative scalar a head and `dt_t` > 0 a scalar a head and token.
The recurrence is linear in the state, so a chunk of `chunk` tokens is three
products around float32 decay sums `cum_i = sum_{r <= i} dt_r A` (inclusive,
from the chunk's first row):

* inside the chunk, `y_i += sum_{j <= i} exp(cum_i - cum_j) (C_i . B_j) dt_j
  x_j`: the chunk's masked `C B^T` scores (a group's, shared by its heads)
  times the heads' decays, then times the chunk's `dt x`;
* the chunk's own state, `sum_j exp(cum_last - cum_j) dt_j x_j B_j^T`;
* what the state that ENTERED the chunk adds, `y_i += exp(cum_i) C_i . S_in`,
  the states passed from chunk to chunk by a scan, `S_out = exp(cum_last)
  S_in + the chunk's own`.

Every exponent is a difference of sums INSIDE one chunk and is at most 0
(the mask is applied to the exponent, before `exp`), so nothing overflows and
what underflows is a contribution that has decayed away; `decay_min` says
how far the sums reach. The decay sums, the exponentials and the states are
float32 whatever the operands' dtype; the products take their operands in
x's dtype and sum in float32. There is no triangular solve (the delta rules
of `ops/delta_rule.py` have one).

**Two paths, one rule.** On a TPU, at shapes the kernels hold
(`ops/pallas/ssd.holds`: heads of 64 lanes in pairs of a group, a state and
a chunk in whole lane tiles) and float32 sums, `ssd` runs two Pallas kernels
under a `jax.custom_vjp` (`ssd_fwd`, `ssd_bwd`): a chunk's decays, its masked
`C B^T` and the heads' states stay in VMEM, forward and backward, and between
the two a layer keeps its inputs and the state each chunk ENTERED with.
Everywhere else it runs `_ssd_text`, the same sums as XLA text, which is the
CPU's path and the kernels' oracle. Decided in `ssd` from what the call sees
and said on the program's tracer (the instant `ssd`, once a trace);
`interpret=True` asks for the kernels under the Pallas interpreter.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..obs.trace import current_tracer
from .pallas import ssd as ssd_kernels

CHUNK = 128     # Nemotron-H's published `chunk_size`


def ssd_flops_per_token(head_dim: int, state: int, heads_a_group: int,
                        chunk: int = CHUNK) -> float:
    """The chunked form's forward FLOPs a token and HEAD: the chunk's C B^T
    scores (2 chunk N, once a group), the scores times `dt x` (2 chunk P),
    the chunk's own state and the entering state's part (2 P N each)."""
    return (2.0 * chunk * state / heads_a_group + 2.0 * chunk * head_dim
            + 4.0 * head_dim * state)


def ssd(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
        C: jax.Array, chunk: int = CHUNK, state_dtype=jnp.float32,
        interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """x (b, t, H, P), dt (b, t, H) float32 and positive, A (H,) float32 and
    negative, B and C (b, t, G, N) with G dividing H -> (y (b, t, H, P) in
    x's dtype, `decay_min`: the smallest `dt A` summed over a chunk).
    `state_dtype` is the precision the decay sums, the decays and the
    states are kept at (float32; a test and the benchmark's control hand
    bfloat16 to show what that loses: that is the text's to show, and takes
    the text on every backend). The kernels or the text: module docstring."""
    b, t, H, P = x.shape
    G, N = B.shape[2:]
    held = (jnp.finfo(state_dtype).bits == 32
            and ssd_kernels.holds(P, N, chunk, H // G))
    if interpret and not held:
        raise ValueError(
            f"the recurrence's kernels do not hold head_dim {P}, state {N}, "
            f"chunk {chunk}, {H // G} heads a group, sums in "
            f"{jnp.dtype(state_dtype).name}: heads of 64 in pairs of a "
            f"group, a state and a chunk in multiples of 128, float32 sums")
    kernels = interpret or (held and jax.default_backend() == "tpu")
    tracer = current_tracer()
    if tracer is not None:
        tracer.instant(
            "ssd", path="kernel" if kernels else "xla", heads=H, groups=G,
            tokens=b * t, chunk=chunk, head_dim=P, state=N,
            dtype=str(x.dtype),
            block=ssd_kernels.head_block(H // G) if kernels else None)
    if not kernels:
        return _ssd_text(x, dt, A, B, C, chunk, state_dtype)
    # the caller's fusions end here and begin again after, as the delta
    # rules'; x, B and C cross the barriers as the mixer holds them, their
    # last two axes as one (a (.., 64, 64) array is laid out in other tiles
    # than a (.., 4096) one, and each turn between the two is a copy)
    flat = lambda a: a.reshape(b, t, -1)
    x, dt, A, B, C = lax.optimization_barrier((flat(x), dt, A, flat(B),
                                               flat(C)))
    y, decay_min = _ssd_kernels(chunk, G, interpret, x, dt, dt * A, B, C)
    return (lax.optimization_barrier(y).reshape(b, t, H, P),
            lax.stop_gradient(decay_min))


def _ssd_text(x, dt, A, B, C, chunk: int, state_dtype
              ) -> Tuple[jax.Array, jax.Array]:
    """`ssd` as XLA text. The sums are rounded to `state_dtype` by
    `lax.reduce_precision`, which no compiler pass takes back out as a pair
    of converts is."""
    b, t, H, P = x.shape
    G, N = B.shape[2:]
    R = H // G                                    # heads a group
    pad = -t % chunk
    if pad:
        # a padding row has dt = 0: it decays nothing and adds nothing
        x, dt, B, C = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (x, dt, B, C))
    c = (t + pad) // chunk
    dtype = x.dtype
    kind = jnp.finfo(state_dtype)
    kept = (lambda a: a) if kind.bits == 32 else (
        lambda a: lax.reduce_precision(a, kind.nexp, kind.nmant))
    chunks = lambda a: a.reshape(b, c, chunk, *a.shape[2:])
    # (b, c, Q, G, R, ...): a group's heads beside its B and C
    xdt = chunks((x * dt[..., None].astype(dtype)).reshape(b, -1, G, R, P))
    B, C = chunks(B), chunks(C)
    cum = kept(jnp.cumsum(chunks(kept(dt * A)), axis=2)).reshape(
        b, c, chunk, G, R)
    last = cum[:, :, -1]                          # (b, c, G, R)
    decay_min = jnp.min(lax.stop_gradient(last))

    # inside the chunk: the group's scores, masked before the exponential
    scores = jnp.einsum("bcign,bcjgn->bcgij", C, B,
                        preferred_element_type=jnp.float32)
    seen = jnp.tril(jnp.ones((chunk, chunk), bool))
    gap = (cum[:, :, :, None] - cum[:, :, None]).transpose(0, 1, 4, 5, 2, 3)
    decay = kept(jnp.exp(jnp.where(seen, kept(gap), -jnp.inf)))
    mixed = (scores[:, :, :, None] * decay).astype(dtype)  # (b,c,G,R,Q,Q)
    y = jnp.einsum("bcgrij,bcjgrp->bcigrp", mixed, xdt,
                   preferred_element_type=jnp.float32)

    # the chunk's own state, and the states handed from chunk to chunk
    to_end = kept(jnp.exp(kept(last[:, :, None] - cum))).astype(dtype)
    own = kept(jnp.einsum("bcjgrp,bcjgn->bcgrpn", xdt * to_end[..., None], B,
                          preferred_element_type=jnp.float32))

    def hand_on(S, chunk_):
        own_c, last_c = chunk_
        return kept(kept(jnp.exp(last_c))[..., None, None] * S + own_c), S

    # (zeros that vary over the mesh axes the operands vary over)
    _, entering = lax.scan(
        hand_on, own[:, 0] * 0,
        (jnp.moveaxis(own, 1, 0), jnp.moveaxis(last, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)       # (b, c, G, R, P, N)
    carried = jnp.einsum("bcign,bcgrpn->bcigrp", C, entering.astype(dtype),
                         preferred_element_type=jnp.float32)
    y = y + carried * kept(jnp.exp(cum))[..., None]
    return y.reshape(b, c * chunk, H, P)[:, :t].astype(dtype), decay_min


# ---- the recurrence as the Pallas kernels (ops/pallas/ssd.py) ----

def _kernel_inputs(x, dt, dtA, B, C, *, chunk: int, groups: int):
    """x (b, t, H P), dt and `dt A` (b, t, H), B and C (b, t, G N) as the
    kernels take them: padded to whole chunks (a padding row has dt = 0),
    `[dt | cum]` a column a head as they lie (in whole lane tiles) and
    `cum` a row a head, the heads in blocks of `hb` (the kernels'
    docstring) -> ((x, B, C, cols, rows), cum (b, c, chunk, H))."""
    b, t, H = dt.shape
    pad = -t % chunk
    if pad:
        x, dt, dtA, B, C = (jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
                            for a in (x, dt, dtA, B, C))
    T = t + pad
    hb = ssd_kernels.head_block(H // groups)
    cum = jnp.cumsum(dtA.reshape(b, T // chunk, chunk, H), axis=2)
    cols = jnp.concatenate([dt, cum.reshape(b, T, H)], -1)
    cols = jnp.pad(cols, ((0, 0), (0, 0),
                          (0, ssd_kernels.columns_width(H) - 2 * H)))
    rows = jnp.moveaxis(cum.reshape(b, -1, chunk, H // hb, hb), 3, 1)
    return (x, B, C, cols, rows.swapaxes(-1, -2)), cum


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _ssd_kernels(chunk: int, groups: int, interpret: bool, x, dt, dtA, B, C):
    """`_ssd_text` at float32 sums as one kernel call on x (b, t, H P), B
    and C (b, t, G N), `dt A` made by the caller (A is a parameter: its
    cotangent is autodiff's) -> (y (b, t, H P), `decay_min`). Its backward
    is one more, by hand: between forward and backward a layer keeps its
    inputs and the state each chunk entered with."""
    return _kernels_fwd(chunk, groups, interpret, x, dt, dtA, B, C)[0]


def _kernels_fwd(chunk, groups, interpret, x, dt, dtA, B, C,
                 residuals=False):
    inputs, cum = _kernel_inputs(x, dt, dtA, B, C, chunk=chunk,
                                 groups=groups)
    y, *S_in = ssd_kernels.forward(
        *inputs, heads_a_group=dt.shape[2] // groups, residuals=residuals,
        interpret=interpret)
    return (y[:, :x.shape[1]], jnp.min(cum[:, :, -1])), (x, dt, dtA, B, C,
                                                          *S_in)


def _kernels_bwd(chunk, groups, interpret, saved, cotangents):
    x, dt, dtA, B, C, S_in = saved
    b, t, H = dt.shape
    P = x.shape[2] // H
    # the inputs as the kernels take them again (a pad, reshapes and the
    # cumsum): what the forward made of them was not kept
    inputs, cum = _kernel_inputs(x, dt, dtA, B, C, chunk=chunk,
                                 groups=groups)
    T = inputs[0].shape[1]
    dy = jnp.pad(cotangents[0].astype(x.dtype), ((0, 0), (0, T - t), (0, 0)))
    dx, dB, dC, dcols, dlast = ssd_kernels.backward(
        *inputs, S_in, dy, heads_a_group=H // groups, interpret=interpret)
    # what a chunk's last sum gets besides its row; then `cum` is the
    # running sum of `dt A` inside a chunk: its transpose runs back
    dcum = dcols[..., H:2 * H].reshape(cum.shape) + jnp.pad(
        dlast.reshape(b, -1, 1, H, P).sum(-1),
        ((0, 0), (0, 0), (chunk - 1, 0), (0, 0)))
    ddtA = lax.cumsum(dcum, axis=2, reverse=True).reshape(b, T, H)
    tokens = lambda a, like: a[:, :t].astype(like.dtype)
    return (tokens(dx, x), tokens(dcols[..., :H], dt),
            tokens(ddtA, dtA), tokens(dB, B), tokens(dC, C))


_ssd_kernels.defvjp(
    functools.partial(_kernels_fwd, residuals=True), _kernels_bwd)
