"""Blockwise flash attention under a declared mask for TPU, written in Pallas.

The mask is a declaration (`ops/attention.AttnMask`), not a second copy of
the kernels: a tile's static plan, the walks over a head's tiles, the
cost_estimate and `obs/attribution.flash_tile_stats` all read it. Three
instances: `CAUSAL` (everything below, as it was before the declaration
existed), `block_diffusion(B, L)` (PR 41; "The block-diffusion mask",
under the sub-tile plan) and `sliding_window(W)` (PR 46; "The sliding
window", under that).

The fused HBM-friendly attention path the reference lacks: its naive
attention materialises the full (b, heads, t, t) score tensor in device
memory (`/root/reference/models/model.py:73-77`). This kernel streams
K/V blocks through VMEM with an online softmax, so HBM traffic and
residual memory are O(t) instead of O(t^2), and the q@k^T / softmax / @v
chain is fused into one MXU-resident loop.

Math matches `ops.attention.causal_attention_xla` exactly: masked
positions get an additive -10000 there, which underflows to probability
exactly 0.0 in the f32 softmax whenever any real score exceeds
-9900 or so (always, in practice); here masked positions are hard-zeroed,
giving the same result.

Forward + backward are both Pallas kernels wired through `jax.custom_vjp`
(the backward recomputes p = exp(s - logsumexp) blockwise from the saved
row-logsumexp, the standard flash-attention-2 scheme). Compiled by Mosaic
on TPU; the Pallas interpreter runs only when a caller passes
`interpret=True` (the cluster-free tests do), never as a fallback.

**Grouped-query attention is native to the kernels** (VERDICT r2 #3): when
k/v arrive with fewer heads than q (hkv < hq), the BlockSpec index maps
route query-head row `b*hq + h` to kv row `b*hkv + h // group` — no
`jnp.repeat` materialises the expanded K/V in HBM, so the GQA bandwidth
saving survives training, not just decode. The dk/dv backward accumulates
over the `group` query heads of each kv head through an extra sequential
grid dimension.

Below a call's arguments everything is decided from its shapes, at trace
time, in this module; each rule's readings stand beside its constant:

- the grid blocks and the padded length: `flash_blocks` (`DEFAULT_BLOCK`,
  clamped to the sequence or to the mask's block), which
  `obs/attribution.flash_tile_stats` asks too;
- inside a grid tile, a static sub-tile plan (`causal_subtile_plan`;
  `subtile_plan` under a mask; `_subtile_shape` takes the sub-tile from q/k's
  width and the key blocks a head has), so one tile a head at t = 1024 does
  not compute the dead half of its square, and `t_real` skips inside a tile;
- the forward's walk (`_fwd_call`; instant `flash_fwd_walk`: `tile` / `row`
  / `grid`): a head of several blocks keeps its K and V resident where they
  fit `KV_ROW_VMEM_BYTES`, fetched once a head, the online softmax in
  registers (a row over `KV_ROW_SCOPED_BYTES` asks Mosaic for its scoped
  VMEM, `_vmem_limit`); longer rows walk the grid's key blocks, their K / V
  index maps clamped to the diagonal;
- the backward's walk (`_bwd_call`; instant `flash_bwd_walk`, with
  `buffers`): ONE kernel (`_bwd_row_kernel`: s, p, dp and ds formed once a
  rectangle, five products where the split kernels run seven) where the
  head's whole rows fit `BWD_ROW_VMEM_BYTES` double-buffered, or
  `BWD_ROW_ONCE_VMEM_BYTES` with every block kept ONCE (`pl.Buffered(1)`);
  heads over both keep the split dq and dk / dv kernels.

`t_real` makes the kernels pad-aware for sequence bucketing
(`flash_attention`'s docstring).
"""

from __future__ import annotations

import functools
import math
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...obs.trace import current_tracer
from ..attention import CAUSAL, AttnMask

MASK = -1e30  # hard mask; equivalent to the XLA path's -10000 (see module doc)

# Swept on v5e at the reference shape (b*h=256, t=1000->1024, hd=64):
# 1024x1024 runs the fwd kernel 2.0x and fwd+bwd 1.8x faster than the
# previous 512x1024 default (2.45ms vs 4.93ms fwd; 5.77ms vs 10.58ms
# fwd+bwd per layer) — fewer grid steps amortize the VMEM pipeline better
# at these small head dims. Blocks clamp to the padded sequence length, so
# shorter sequences are unaffected. The backward kernels are swept
# separately (they keep larger per-block VMEM working sets).
# At t = 4096, q/k 192 over v 128 (PR 34, b*h 128; FWD_SUBTILE_WIDE's
# table): the forward 6.90 / 6.09 / 5.72 ms at blocks 512 / 1024 / 2048, the
# backward's one kernel 13.43 / 12.49 ms at 512 / 1024 (PR 40) and over the
# scoped VMEM at 2048; the sweeps of PRs 52 - 56 at the longer cells' shapes
# found 1024 again. So ONE value for all four blocks at every shape
# (`flash_blocks`); a sweep (`scripts/tune_flash_blocks.py`, device time from
# a capture) that finds another is adopted by editing this or that rule,
# with its reading here.
DEFAULT_BLOCK = 1024


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _out_struct(shape, dtype, like: jax.Array) -> jax.ShapeDtypeStruct:
    """ShapeDtypeStruct carrying the varying-manual-axes tag of `like`, so
    the kernel composes with shard_map's vma type checking (the kernel runs
    per-shard on tp-varying values inside the TP transformer)."""
    vma = getattr(jax.typeof(like), "vma", None)
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


# ------------------------------------------------------ causal sub-tile plan
#
# `DEFAULT_BLOCK` at t = 1024, head_dim 64 is ONE grid tile a head (its
# sweep note: grid-step overhead makes smaller grid blocks slower), so the
# grid guards can skip nothing there. What the kernels skip instead is
# decided inside the tile, at trace time: the tile's
# (block_q x block_k) score square is cut into sub-tiles, and a plan that
# depends on static values alone says which are never computed (wholly
# above the diagonal, or wholly at or past t_real), which are computed with
# no iota / compare / select (wholly live), and which build the mask (the
# diagonal or the t_real edge crosses them). In the backward, adjacent
# sub-tiles of one kind run as one rectangle.

# Sub-tile shape (sub_q, sub_k). Swept on v5e (TPU v5 lite, jax 0.9.0, PR 30)
# at the two benchmark cells' shapes, t=1024 hd64 bf16, one tile a head;
# device time of one call from a profiler capture (`python scripts/
# tune_flash_blocks.py --subtile --bh 192,80 --edges 1024,512,256,128,128x256`;
# the host clock around a call this short reads 0.5 ms of dispatch on top).
# ms a call, forward / backward (both set to the row's shape):
#
#   sub_q x sub_k    b*h=192 (gpt2-medium b12)   b*h=80 (gpt2-large dp2 x tp2)
#   before this PR     0.657 / 1.364               0.273 / 0.568
#   1024 (one masked)  0.615 / 1.150               0.255 / 0.479
#   512                0.501 / 0.896               0.207 / 0.374
#   256                0.487 / 0.833               0.202 / 0.347
#   128                0.523 / 1.105               0.217 / 0.461
#   128 x 256          0.475 / 0.833               0.198 / 0.347
#   256 x 128          0.578 / 1.095
#   256 x 512, 512 x 256   0.498 / 0.896, 0.558 / 0.954
#
# The area computed falls 1.0 -> 0.75 -> 0.625 -> 0.5625 from 1024 to 128, the
# time does not follow below 256: at head_dim 64 every dot uses half the
# 128-wide MXU (K = 64 or N = 64) and the kernels are bound by it, not by the
# vector unit (knocking out the exp or the mask moves the forward by under
# 0.02 ms, knocking out p @ v by 0.125), so what a finer plan saves in area
# it loses in shorter dots. Two more readings shaped the kernels: the
# forward is fastest on UNMERGED sub-tiles (a 128 x 256 float32 tile is 32
# vregs; one merged 256 x 768 rectangle a sub-row read 0.527 against 0.487),
# the backward on MERGED rectangles (0.833 against 0.887); and DMA alone
# (no compute) is 0.300 / 0.668 ms, of which the (t, 1) float32 lse and
# delta blocks, padded to 128 lanes in HBM, are 0.31 of the backward's.
FWD_SUBTILE = (128, 256)
BWD_SUBTILE = (256, 256)

# The forward's sub-tile where q/k pass the MXU's 128 rows or a head has
# several key blocks (`_subtile_shape`; the spot readings that drew the line
# are at the end of this note).
# Swept on v5e (TPU v5 lite, jax 0.9.0, PR 34) at the latent-attention
# cell's shape: b*h 128, t = 4096, q/k 192 against v 128, bf16, a head's K
# and V resident (`_fwd_call`'s row walk); device time of one call from a
# capture (`python scripts/tune_flash_blocks.py --subtile --bh 128 --t 4096
# --d 192 --dv 128 --blocks 512,1024,2048 --edges 128x256,128x512,256x256,
# 256x512,512x256`). Forward ms a call by the grid's square block; the
# backward (its own sub-tile set to the row's shape too) in brackets:
#
#   sub_q x sub_k   block 512        block 1024       block 2048
#   before this PR                   16.81 (22.2)     gridded, 128 x 256
#   128 x 256       12.00 (29.3)     10.78 (23.0)     13.00
#   128 x 512        9.23 (29.9)      8.08 (23.8)      7.48
#   256 x 256        8.36 (29.1)      7.54 (22.8)      7.09
#   256 x 512        6.90 (29.9)      6.09 (23.7)      5.72
#   512 x 256        7.55 (29.9)      6.50 (23.7)      over scoped VMEM
#   512 x 512                         6.07             over scoped VMEM
#   256 x 1024                        6.89             6.33
#
# (the backward's kernels pass the 16 MiB of scoped VMEM at block 2048.) What
# bound the kernel before, by knocking parts out of the gridded walk at
# blocks 1024, 128 x 256 (wrong numbers, right time; 16.81 ms whole): m and
# l through their (block_q, 1) scratch 5.6 ms (acc alone through scratch
# costs 0.08: one value in 128 lanes is what is dear, not the bytes), `p @ v`
# 7.2, the rescale 0.3, the `exp` 0.1, the six dead tiles' K / V fetch 0.5
# (index maps clamped to the diagonal), DMA alone 3.2. In the row walk at
# 128 x 256: `p @ v` 4.4 of 10.78, `exp` 0.2, rescale 0.4: the matrix unit
# binds at these widths as at 64, and a taller, wider sub-tile feeds it
# better (a 128-row left operand streams through each latched weight tile
# for no longer than the tile took to load). The plan at 256 x 512 computes
# 1.125 of the causal triangle (1.06 at 128 x 256); the MXU's floor for it,
# K = 192 in two passes, is ~4.7 ms. The loop over the key tiles left of the
# diagonal wants a body of several sub-tiles: one sub-column an iteration
# read 14.2 / 10.1 / 6.6 ms at 128 x 256 / 256 x 256 / 256 x 512 against
# 10.8 / 7.5 / 6.1 with a key tile of 1024 as the body (some 200 cycles an
# iteration with nothing in flight), two tiles an iteration bought nothing
# more, the loop before the diagonal tile (every rectangle then rescales)
# 14.3, every unmasked sub-tile in the loop 15.6. Block 2048 is 6% faster
# than 1024 and is not taken: 8 s of Mosaic's time a kernel against 2, and
# what it leaves of the scoped VMEM is under 2 MiB.
#
# Which shapes take it: the same tool at block 1024, forward ms a call at
# 128 x 256 / 256 x 256 / 256 x 512. ONE tile a head (t = 1024): q/k 192, v
# 128, b*h 128: 0.657 / 0.503 / 0.458; 128 / 128, b*h 128: 0.315 / 0.323 /
# 0.332; 64 / 64, b*h 192 (FWD_SUBTILE's table): 0.475 / 0.487 / 0.498.
# SEVERAL blocks a head, K and V resident: t = 4096 at 128 / 128, b*h 128:
# 8.19 / 5.83 / 4.43; t = 4096 at 64 / 64, b*h 32: 2.09 / 1.49 / 1.13;
# t = 8192 at 64 / 64, b*h 16: 3.89 / 2.74 / 2.05; and the gridded walk at
# this note's shape 16.29 / - / 9.37. A loop over key tiles wants the long
# dots at every width; one tile a head only where q/k pass 128.
FWD_SUBTILE_WIDE = (256, 512)

# The backward of several blocks a head, the head resident (`_bwd_row_kernel`,
# PR 40). A call alone on v5e (TPU v5 lite, jax 0.9.0), bf16, device time
# from a capture, `python scripts/tune_flash_blocks.py --backward --bh 128
# --t 4096 --d 192 --dv 128 --blocks 1024,512` and `--backward --bh 64
# --t 8192 --d 64 --group 4`; ms a backward, knock-outs each alone (wrong
# numbers, right time):
#
#                           192 / 128, t 4096, b*h 128    64 / 64, t 8192,
#                           block 1024     block 512      b*h 64, group 4
#   split: dq + dkv         10.86 + 11.95  13.37 + 15.73  11.05 + 16.24
#          = a backward     22.81          29.10          27.29
#   resident, whole         12.49          13.43          13.80
#   DMA alone                2.92           2.92           1.44
#   no s  = q k^T            9.34 (-3.14)  10.72          11.24 (-2.56)
#   no dp = dO v^T          10.80 (-1.69)  11.50          11.50 (-2.30)
#   no dq = ds k            11.12 (-1.37)  12.45          11.42 (-2.38)
#   no dk^T = q^T ds        10.41 (-2.08)  11.40          12.09 (-1.71)
#   no dv^T = dO^T p        11.45 (-1.04)  11.66          12.04 (-1.76)
#
# Five products where the split kernels run seven, and each runs better:
# under the diagonal a rectangle is a whole query tile's 1024 rows against a
# key sub-column, so s, dp and dq stream 1024 rows through each latched
# weight tile where the split kernels' sub-rows stream 256. At 192 / 128 the
# five knock-outs sum to 9.3 of the 12.5 ms; s is the dearest (K = 192 is two
# passes, the second half empty). The backward's sub-tile, re-read with the
# head resident (`--subtile --bh 128 --t 4096 --d 192 --dv 128 --blocks 1024
# --edges ...`; under the diagonal only sub_k matters, the rectangle being
# the tile's height): 256 x 256 12.49 (128 x 256 is the same plan), 256 x
# 512 and 512 x 512 12.92, 512 x 256 13.15, 256 x 128 14.90. BWD_SUBTILE
# stands. Mosaic's time a kernel: 2.9 - 4.2 s with tracing and lowering
# against 1.7 + 2.2 for the two it replaces.


def _subtile_shape(block_q: int, block_k: int, head_dim: int,
                   backward: bool, num_kb: int = 1) -> Tuple[int, int]:
    """(sub_q, sub_k) inside a (block_q x block_k) grid tile. A block no
    larger than the sub-tile is one sub-tile: the plan of a 128-token tile
    is a single masked sub-tile, the kernels' text before sub-tiles; it
    skips something from 512-token blocks up. The forward's shape follows
    what the sweeps found (the two notes above): the wide one where q/k
    pass the MXU's 128 rows (two passes a product) or a head has several
    key blocks (`num_kb` > 1: the walk is then a loop, in the kernel or
    the grid's, and wants long dots), the narrow one for one tile a head
    at head_dim <= 128; the backward's was swept at 64 with one tile a head
    (BWD_SUBTILE's note) and at 192 / 128 with four, the head resident (the
    note under FWD_SUBTILE_WIDE): one shape won both."""
    if backward:
        sq, sk = BWD_SUBTILE
    else:
        sq, sk = FWD_SUBTILE_WIDE if head_dim > 128 or num_kb > 1 \
            else FWD_SUBTILE
    return min(block_q, sq), min(block_k, sk)


def _runs(cells, major: int, minor: int, merge: bool):
    """A grid of sub-tile flags (None skipped, False unmasked, True masked;
    `cells[a][b]`, edges `major` x `minor`) as
    ((a0, a_len, ((b0, b_len, masked), ...)), ...). With `merge`, runs of
    one flag along b become one rectangle, and neighbours along a are fused
    where they hold the same unmasked runs."""
    out = []
    for a, row in enumerate(cells):
        runs = []
        for b, flag in enumerate(row):
            if flag is None:
                continue
            if merge and runs and runs[-1][2] == flag \
                    and runs[-1][0] + runs[-1][1] == b * minor:
                runs[-1] = (runs[-1][0], runs[-1][1] + minor, flag)
            else:
                runs.append((b * minor, minor, flag))
        if not runs:
            continue
        runs = tuple(runs)
        prev = out[-1] if out else None
        if (merge and prev and prev[2] == runs
                and prev[0] + prev[1] == a * major
                and not any(m for _, _, m in runs)):
            out[-1] = (prev[0], prev[1] + major, runs)
        else:
            out.append((a * major, major, runs))
    return tuple(out)


class SubtilePlan(NamedTuple):
    """What one (block_q x block_k) grid tile computes. Coordinates are
    tile-relative; entry (r, c) is live iff c <= (r | (stair - 1)) + diag
    and r < cut, and, with `band`, c >= (r & ~(stair - 1)) + diag too, and,
    with `low`, c >= r + low too (a sliding window's left edge).
    `stair` is 1 under the causal mask (the plain diagonal) and the block
    length under the block-diffusion mask (a block's rows share its last
    row's bound: a STAIRCASE on the diagonal), where `role` says which of
    the mask's tiles the plan is for (`_bd_role`).

    Two views of the same computed sub-tiles, for the two loop orders:
    `bands`: ((r0, rows, ((c0, cols, masked), ...)), ...) — query sub-rows,
    each with its key rectangles; `columns`: ((c0, cols, ((r0, rows,
    masked), ...)), ...) — key sub-columns, each with its query rectangles.
    The three counts are in sub-tiles of sub_q x sub_k."""

    sub_q: int
    sub_k: int
    diag: int
    cut: int
    bands: tuple
    columns: tuple
    computed_unmasked: int
    computed_masked: int
    skipped: int
    stair: int = 1
    band: bool = False
    role: str = ""
    low: Optional[int] = None

    @property
    def work_elems(self) -> int:
        """Score entries the tile computes (live or masked)."""
        return (self.computed_unmasked + self.computed_masked) \
            * self.sub_q * self.sub_k


@functools.lru_cache(maxsize=None)
def causal_subtile_plan(block_q: int, block_k: int, q_block: int,
                        k_block: int, t_real: int, head_dim: int,
                        backward: bool = False,
                        num_kb: int = 1) -> SubtilePlan:
    """The static plan of grid tile (q_block, k_block): the ONE source for
    what the kernels walk, what `_fwd_call`'s cost_estimate counts and what
    `obs/attribution.flash_tile_stats` reports.

    Causality with a real length reduces to two tile-relative numbers:
    `diag` = first row - first column (the diagonal's place in the tile)
    and `cut` = t_real - first row (rows at or past it are dead; a live
    row r < t_real only sees columns c <= r, so no column test is needed).
    Both are clamped to where they stop mattering, so every tile wholly
    under the diagonal and inside t_real has the same plan. `num_kb` is
    the number of key blocks a head has (`_subtile_shape` asks)."""
    sq, sk = _subtile_shape(block_q, block_k, head_dim, backward, num_kb)
    diag = max(-block_q, min(q_block * block_q - k_block * block_k,
                             block_k - 1))
    cut = max(0, min(t_real - q_block * block_q, block_q))
    return _plan_of(block_q, block_k, sq, sk, diag, cut, backward)


def _plan_of(block_q: int, block_k: int, sq: int, sk: int, diag: int,
             cut: int, backward: bool, stair: int = 1, band: bool = False,
             role: str = "", low: Optional[int] = None) -> SubtilePlan:
    """The plan of a tile whose live entries are `SubtilePlan`'s rule: each
    sub-tile skipped (no live entry), unmasked (all live) or masked (a
    bound crosses it)."""
    cells = []
    for r0 in range(0, block_q, sq):
        last_live = min(r0 + sq, cut) - 1   # < r0: every row is dead
        # the upper bound of the sub-row's last live row and of its first
        # (bounds rise with the row); with `band`, the lower bounds too
        hi_last = (last_live | (stair - 1)) + diag
        hi_first = (r0 | (stair - 1)) + diag
        cells.append([
            None if last_live < r0 or c0 > hi_last
            or (band and c0 + sk - 1 < hi_first - (stair - 1))
            or (low is not None and c0 + sk - 1 < r0 + low)
            else r0 + sq > cut or c0 + sk - 1 > hi_first
            or (band and c0 < hi_last - (stair - 1))
            or (low is not None and c0 < r0 + sq - 1 + low)
            for c0 in range(0, block_k, sk)])
    flat = [f for row in cells for f in row]
    # the backward walks merged rectangles, the forward single sub-tiles
    # (FWD_SUBTILE's sweep note)
    return SubtilePlan(
        sq, sk, diag, cut, _runs(cells, sq, sk, backward),
        _runs([list(col) for col in zip(*cells)], sk, sq, backward),
        flat.count(False), flat.count(True), flat.count(None),
        stair, band, role, low)


# -------------------------------------------------- the block-diffusion mask
#
# `block_diffusion(B, L)` over the 2L rows [noised ; clean] of a sequence
# (ops/attention.AttnMask has the rule). With square blocks that divide L,
# no tile spans two quadrants, and with B dividing the sub-tile edges each
# live quadrant is the causal one with a staircase on its diagonal:
# `blk(j) <= blk(i)` is `c <= (r | (B - 1))`, `blk(j) < blk(i)` is
# `c <= (r | (B - 1)) - B`, and the block diagonal is the first with a lower
# bound `c >= (r & ~(B - 1))`. So a tile wholly under a quadrant's diagonal
# keeps the causal mask's unmasked plan, only `_rect_live`'s compare changes
# in the sub-tiles a diagonal crosses, and a tile has one of four plans by
# its place (`_bd_role`), whatever the sequence's length:
#
#   "nn"    noised query block i, noised key tile i: the block diagonal
#   "nc"    noised query block i, clean key tile i: strictly earlier blocks
#   "cc"    clean query block i, clean key tile i: earlier blocks and its own
#   "under" the clean key tiles 0 .. i - 1 of either: all live
#
# and every other tile (the clean-query / noised-key quadrant, noised /
# noised off the diagonal, above the diagonals) is never computed: a query
# block's walk is the short list (noised i: noised tile i, clean tiles
# 0 .. i; clean i: clean tiles 0 .. i), by the kernels' loops where the head
# is resident and by the grid's guards otherwise.


def mask_block(mask: AttnMask, t: int, block: int) -> int:
    """The square grid block the kernels run a block-diffusion mask over `t`
    rows with, asked for `block`: clamped to the mask's half, so that a tile
    lies in one quadrant. What they cannot plan is refused."""
    if t != 2 * mask.half:
        raise ValueError(f"a block_diffusion mask over {mask.half} positions "
                         f"takes {2 * mask.half} rows, got {t}")
    block = min(block, 1 << (mask.half.bit_length() - 1))
    if 128 % mask.block or block % 128 or mask.half % block:
        raise ValueError(
            f"the flash kernels plan a block_diffusion mask whose block "
            f"length divides 128 and whose half is a multiple of the grid "
            f"block (128 at least), got block length {mask.block}, half "
            f"{mask.half}, grid block {block}; use the XLA attention")
    return block


def _bd_role(mask: AttnMask, block: int, qb, kb) -> str:
    """The role of grid tile (qb, kb), static ints, under the
    block-diffusion mask; "" for a tile that is never computed."""
    nh = mask.half // block         # tiles a half, along either side
    if kb < nh:
        return "nn" if kb == qb else ""
    i = qb % nh                     # the query block's place in its half
    if kb - nh == i:
        return "nc" if qb < nh else "cc"
    return "under" if kb - nh < i else ""


@functools.lru_cache(maxsize=None)
def _bd_plan(role: str, block: int, stair: int, head_dim: int,
             backward: bool, num_kb: int) -> SubtilePlan:
    sq, sk = _subtile_shape(block, block, head_dim, backward, num_kb)
    if not role:
        return _plan_of(block, block, sq, sk, 0, 0, backward)   # no band
    if role == "under":
        return _plan_of(block, block, sq, sk, block - 1, block, backward,
                        role=role)
    return _plan_of(block, block, sq, sk, -stair if role == "nc" else 0,
                    block, backward, stair, role == "nn", role)


# ---------------------------------------------------------- the sliding window
#
# `sliding_window(W)`: key <= query and query - key < W. With square blocks
# that divide the sequence (no row of padding), the plan of grid tile (qb,
# kb) depends on D = qb - kb alone: live iff c <= r + D * block (the
# diagonal: binds in tile D = 0 only) and c >= r + D * block - W + 1 (the
# window's LEFT edge, `SubtilePlan.low`). A query block's row of tiles is
#
#   D = 0                   the tile on the diagonal (the causal plan, with a
#                           left edge too where W < block)
#   0 < D <= W // block - 1 wholly inside the band: the causal mask's
#                           unmasked plan, one loop
#   the next one or two     the tiles the left edge crosses (one where block
#                           divides W: its strict upper triangle is live),
#                           planned at sub-tile grain like the diagonal
#
# and every tile further left is never computed: skipped by the kernels'
# loops where the head is resident (the forward's loop over key tiles starts
# at the band's first whole tile, the backward's over query tiles ends at
# its last), by the grid's guards and clamped index maps otherwise. A window
# that covers the sequence is the triangle and takes `CAUSAL`'s own text
# (`flash_attention`).


def window_block(mask: AttnMask, t: int, block: int) -> int:
    """The square grid block the kernels run a sliding window over `t` rows
    with, asked for `block`. What they cannot plan is refused."""
    block = min(block, max(128, 1 << (t - 1).bit_length()))
    if t % block:
        raise ValueError(
            f"the flash kernels plan a sliding window over a sequence that "
            f"is a multiple of the grid block (no padded rows), got {t} rows, "
            f"grid block {block}, window {mask.window}; use the XLA "
            f"attention")
    return block


@functools.lru_cache(maxsize=None)
def _window_plan(block: int, tiles_back: int, window: int, head_dim: int,
                 backward: bool, num_kb: int) -> SubtilePlan:
    """The plan of a tile `tiles_back` = query block - key block tiles left
    of the diagonal; no band where nothing of the tile is live."""
    sq, sk = _subtile_shape(block, block, head_dim, backward, num_kb)
    diag = max(-block, min(tiles_back * block, block - 1))
    low = tiles_back * block - window + 1
    # a left edge at or before the tile's last row's first column never binds
    low = None if low <= -(block - 1) else min(low, block)
    return _plan_of(block, block, sq, sk, diag, block, backward, low=low)


def _window_walk(mask: AttnMask, block: int, num_b: int, head_dim: int,
                 backward: bool):
    """A sliding window's row of tiles, by tiles back from the diagonal:
    (the diagonal tile's plan, the unmasked plan or None, how many tiles
    back it reaches, ((tiles back, plan of a tile the left edge crosses),
    ...))."""
    at = lambda back: _window_plan(block, back, mask.window, head_dim,
                                   backward, num_b)
    whole = max(mask.window // block - 1, 0)
    edges = tuple((back, at(back)) for back in range(whole + 1, num_b)
                  if at(back).bands)
    return at(0), (at(1) if whole else None), whole, edges


def subtile_plan(mask: AttnMask, block_q: int, block_k: int, q_block: int,
                 k_block: int, t_real: int, head_dim: int,
                 backward: bool = False, num_kb: int = 1) -> SubtilePlan:
    """`causal_subtile_plan` under a declared mask: the static plan of grid
    tile (q_block, k_block)."""
    if mask.kind == "causal":
        return causal_subtile_plan(block_q, block_k, q_block, k_block,
                                   t_real, head_dim, backward, num_kb)
    if mask.kind == "sliding_window":
        return _window_plan(block_q, q_block - k_block, mask.window,
                            head_dim, backward, num_kb)
    return _bd_plan(_bd_role(mask, block_q, q_block, k_block), block_q,
                    mask.block, head_dim, backward, num_kb)


def plan_stats(mask: AttnMask, t_pad: int, block_q: int, block_k: int,
               t_real: int, head_dim: int, backward: bool = False
               ) -> Dict[str, int]:
    """`subtile_plan` summed over the grid of one head."""
    out = {"computed_unmasked": 0, "computed_masked": 0, "skipped": 0,
           "work_elems": 0}
    num_kb = t_pad // block_k
    for qb in range(t_pad // block_q):
        for kb in range(num_kb):
            plan = subtile_plan(mask, block_q, block_k, qb, kb, t_real,
                                head_dim, backward, num_kb)
            # a tile that is never computed has no plan: all of it skipped
            out["computed_unmasked"] += plan.computed_unmasked
            out["computed_masked"] += plan.computed_masked
            out["skipped"] += plan.skipped
            out["work_elems"] += plan.work_elems
    out["sub_q"], out["sub_k"] = plan.sub_q, plan.sub_k
    return out


def causal_plan_stats(t_pad: int, block_q: int, block_k: int, t_real: int,
                      head_dim: int, backward: bool = False
                      ) -> Dict[str, int]:
    """`causal_subtile_plan` summed over the grid of one head."""
    return plan_stats(CAUSAL, t_pad, block_q, block_k, t_real, head_dim,
                      backward)


def _tile_plans(block_q: int, block_k: int, num_qb: int, num_kb: int,
                t_real: int, head_dim: int, backward: bool = False,
                mask: AttnMask = CAUSAL):
    """The distinct plans of the grid's live tiles. A plan is a function of
    its clamped (diag, cut), or under the block-diffusion mask of its role,
    which is how a kernel picks it from the program ids (`_plan_is`)."""
    plans = {subtile_plan(mask, block_q, block_k, qb, kb, t_real, head_dim,
                          backward, num_kb)
             for qb in range(num_qb) for kb in range(num_kb)}
    return sorted((p for p in plans if p.bands),
                  key=lambda p: (p.diag, p.cut, p.role, p.low is not None,
                                 p.low or 0))


def _plan_is(plan: SubtilePlan, qi, ki, block_q: int, block_k: int,
             t_real: int, mask: AttnMask = CAUSAL):
    """Does grid tile (qi, ki) — program ids — run `plan`?"""
    if mask.kind == "sliding_window":
        reach = (qi - ki) * block_q
        low = jnp.clip(reach - mask.window + 1, -(block_q - 1), block_q)
        return ((jnp.clip(reach, -block_q, block_q - 1) == plan.diag)
                & (low == (-(block_q - 1) if plan.low is None else plan.low)))
    if mask.kind != "causal":
        nh = mask.half // block_q
        own = ki - nh == qi % nh        # the clean tile of the block's place
        if plan.role == "nn":
            return (ki == qi) & (qi < nh)
        if plan.role == "nc":
            return own & (qi < nh)
        if plan.role == "cc":
            return own & (qi >= nh)
        return (ki >= nh) & (ki - nh < qi % nh)
    diag = jnp.maximum(-block_q, jnp.minimum(qi * block_q - ki * block_k,
                                             block_k - 1))
    cut = jnp.maximum(0, jnp.minimum(t_real - qi * block_q, block_q))
    return (diag == plan.diag) & (cut == plan.cut)


def _row_walk_plans(plans):
    """The row walk's reading of a square-block grid's plans (`_fwd_kernel`):
    ((plan of a tile on the diagonal, plan of the tiles left of it or None),
    ...). Query block i walks the first at key tile i and the second at key
    tiles 0 .. i - 1; the two are paired by the rows t_real leaves (`cut`),
    and a cut that only the first query block has leaves no tile to its
    left."""
    under = {p.cut: p for p in plans if p.diag > 0}
    return tuple((p, under.get(p.cut)) for p in plans if p.diag == 0)


def _band_at(plan: SubtilePlan, r0: int):
    """The key rectangles of `plan`'s query sub-row at `r0` (the forward's
    plans are not merged: a band is one sub-row); none where every entry of
    the sub-row is dead."""
    return next((rects for at, _, rects in plan.bands if at == r0), ())


def _rect_live(plan: SubtilePlan, r0: int, rows: int, c0: int, cols: int,
               transposed: bool = False):
    """Mask of a masked rectangle, (rows, cols) or transposed, from the
    conditions that cross it alone."""
    shape = (cols, rows) if transposed else (rows, cols)
    rdim = 1 if transposed else 0
    at = r0 + jax.lax.broadcasted_iota(jnp.int32, shape, rdim)
    step = plan.stair - 1
    # a staircase: the rows of a block share its last row's bound
    row = at | step if step else at
    live = col = None
    if c0 + cols - 1 > (r0 | step) + plan.diag:  # the diagonal crosses it
        col = c0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - rdim)
        live = col <= row + plan.diag
    if plan.band and c0 < ((r0 + rows - 1) | step) - step + plan.diag:
        if col is None:     # the band's lower staircase crosses it
            col = c0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - rdim)
        above = col >= row + (plan.diag - step)
        live = above if live is None else live & above
    if plan.low is not None and c0 < r0 + rows - 1 + plan.low:
        if col is None:     # the window's left edge crosses it
            col = c0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - rdim)
        inside = col >= at + plan.low
        live = inside if live is None else live & inside
    if r0 + rows > plan.cut:                    # the t_real edge crosses it
        inside = at < plan.cut
        live = inside if live is None else live & inside
    return live


_NT = (((1,), (1,)), ((), ()))   # a @ b^T
_NN = (((1,), (0,)), ((), ()))   # a @ b


def _dot(a, b, dims):
    # Dots run in the INPUT dtype with f32 accumulation: for bf16 inputs the
    # result is identical to upcasting first (bf16->f32 is exact, the MXU
    # accumulates f32 either way) but runs in one MXU pass instead of the
    # multi-pass f32 decomposition.
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


# ---------------------------------------------------------------- forward


def _softmax_step(state, s, v, masked: bool):
    """One online-softmax update of a query sub-row by the scores `s` of
    one rectangle. `state` is (m, l, acc) or None for a sub-row's first
    rectangle, which has nothing to rescale."""
    m_new = jnp.max(s, axis=-1, keepdims=True)
    if state is not None:
        m_new = jnp.maximum(state[0], m_new)
    # clamp: an all-dead row (>= t_real, or no live entry yet) keeps
    # m_new = MASK, and exp(MASK - MASK) = 1 would resurrect masked entries
    # (the guard _pos_fwd_kernel carries); live rows have m_new > MASK/2 and
    # are unaffected. A wholly live rectangle has no such row.
    m_safe = jnp.maximum(m_new, MASK / 2) if masked else m_new
    p = jnp.exp(s - m_safe)                                  # (rows, cols)
    l = jnp.sum(p, axis=-1, keepdims=True)
    acc = _dot(p.astype(v.dtype), v, _NN)                    # (rows, d)
    if state is not None:
        alpha = jnp.exp(state[0] - m_safe)                   # (rows, 1)
        l, acc = alpha * state[1] + l, state[2] * alpha + acc
    return m_new, l, acc


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch,
                scale: float, t_real: int, block_q: int, block_k: int,
                num_kb: int, plans, row_walk: bool = False,
                mask: AttnMask = CAUSAL):
    """A tile wholly above the diagonal or wholly padding has no plan: it is
    skipped. Every other tile runs the one plan that is its own; a query
    sub-row keeps ONE online softmax across its rectangles.

    A sub-row that sees its whole key row in one grid step finalises from
    values: (m, l, acc) never leave registers and no scratch exists. That
    is the one-key-block grid (`DEFAULT_BLOCK` at t = 1024) and, with
    `row_walk`, a head whose sequence spans several blocks but whose K and V
    stay resident (`_fwd_call` decides): the key blocks are then the row of
    tiles the plans describe, walked inside the step. The tile on the
    diagonal runs its static plan; the tiles left of it all run the one
    plan of a tile under the diagonal, so they are a loop over key tiles
    with that plan's sub-row as its body (the kernel's text does not grow
    with the sequence), and a sub-row's first rectangle, the diagonal
    tile's, has nothing to rescale.

    Where K and V of a head are too large for that, (m, l, acc) live in
    scratch across the grid's key blocks. The round trip costs (at
    t = 1024, one tile, it doubled the kernel's time; FWD_SUBTILE_WIDE's
    note has it at t = 4096), so it is paid only there."""
    qi = pl.program_id(1)
    # the key tile whose plan this step runs: the grid's, or in the row walk
    # the one on the diagonal (block_q == block_k there)
    ki = qi if row_walk else pl.program_id(2)

    def finalize(rs, m, l, acc):
        l_safe = jnp.where(l == 0.0, 1.0, l)  # dead (padded) q rows only
        o_ref[0, rs, :] = (acc / l_safe).astype(o_ref.dtype)
        lse_ref[0, rs, :] = m + jnp.log(l_safe)              # (rows, 1)

    def dead(rs, rows):
        # dead rows (>= t_real) emit o = 0 / lse = MASK: the invariant the
        # backward's dead-row guards rely on, and the public t_real contract
        o_ref[0, rs, :] = jnp.zeros((rows, o_ref.shape[-1]), o_ref.dtype)
        lse_ref[0, rs, :] = jnp.full((rows, 1), MASK, jnp.float32)

    if scratch:
        acc_ref, m_ref, l_ref = scratch

        @pl.when(ki == 0)
        def _init():
            acc_ref[:] = jnp.zeros_like(acc_ref)
            m_ref[:] = jnp.full_like(m_ref, MASK)
            l_ref[:] = jnp.zeros_like(l_ref)

    def step(plan, state, q, r0, rows, c0, cols, masked, tile=None):
        """One rectangle of `plan`, in key tile `tile` of the resident row
        (None: the block the grid fetched)."""
        cs = slice(c0, c0 + cols) if tile is None else pl.ds(
            pl.multiple_of(tile * block_k + c0, cols), cols)
        s = _dot(q, k_ref[0, cs, :], _NT) * scale
        if masked:
            s = jnp.where(_rect_live(plan, r0, rows, c0, cols), s, MASK)
        return _softmax_step(state, s, v_ref[0, cs, :], masked)

    # What a query block walks, a case a `when`: the tiles it runs by their
    # static plans, the first one's sub-rows leading (key tile, plan; None
    # is the block the grid fetched), and then the key tiles `first` ..
    # `past` - 1 it loops over by the one unmasked plan `left`.
    if not row_walk:
        walks = [(lambda p=p: _plan_is(p, qi, ki, block_q, block_k, t_real,
                                       mask), ((None, p),), None)
                 for p in plans]
    elif mask.kind == "causal":
        walks = [(lambda p=p: _plan_is(p, qi, ki, block_q, block_k, t_real),
                  ((ki, p),), left and (0, qi, left))
                 for p, left in _row_walk_plans(plans)]
    elif mask.kind == "sliding_window":
        diagonal, left, whole, edges = _window_walk(mask, block_q, num_kb,
                                                    q_ref.shape[-1], False)
        # a case for each count of edge tiles the query block has to its
        # left (the first query blocks have none); the loop starts at the
        # band's first whole tile
        backs = [back for back, _ in edges]

        def has(n):     # does the query block have exactly n edge tiles
            if n == len(edges):
                return qi >= backs[-1]
            return (qi < backs[n]) & (qi >= backs[n - 1]) if n \
                else qi < backs[0]

        walks = [
            (functools.partial(has, n),
             ((qi, diagonal),) + tuple((qi - back, p)
                                       for back, p in edges[:n]),
             left and (jnp.maximum(qi - whole, 0), qi, left))
            for n in range(len(edges) + 1)]
    else:
        nh = mask.half // block_q
        by = {p.role: p for p in plans}
        left = by.get("under")          # None with one tile a half
        # (a block length of the sub-tile's edge leaves "nc" no live entry)
        walks = [
            (lambda: qi < nh, ((qi, by["nn"]),) + (
                ((qi + nh, by["nc"]),) if "nc" in by else ()),
             left and (nh, qi + nh, left)),
            (lambda: qi >= nh, ((qi, by["cc"]),), left and (nh, qi, left))]

    for when, tiles, loop in walks:
        @pl.when(when())
        def _compute(tiles=tiles, loop=loop):
            plan = tiles[0][1]
            left = loop[2] if loop else None
            left_rects = {r0: rects for r0, _, rects in left.bands} \
                if left else {}
            done = 0
            for r0, rows, rects in plan.bands:
                rs = slice(r0, r0 + rows)
                q = q_ref[0, rs, :]
                state = (m_ref[rs], l_ref[rs], acc_ref[rs]) if scratch \
                    else None
                for tile, of in tiles:
                    for c0, cols, masked in (
                            rects if of is plan else _band_at(of, r0)):
                        state = step(of, state, q, r0, rows, c0, cols,
                                     masked, tile)
                if r0 in left_rects:
                    def key_tile(kb, state, r0=r0, rows=rows, q=q):
                        for c0, cols, masked in left_rects[r0]:
                            state = step(left, state, q, r0, rows, c0, cols,
                                         masked, kb)
                        return state
                    state = jax.lax.fori_loop(loop[0], loop[1], key_tile,
                                              state)
                if scratch:
                    m_ref[rs], l_ref[rs], acc_ref[rs] = state
                else:
                    finalize(rs, *state)
                done = r0 + rows
            if not scratch and done < block_q:     # sub-rows past t_real
                dead(slice(done, block_q), block_q - done)

    if scratch:
        @pl.when(ki == num_kb - 1)
        def _finalize():
            finalize(slice(None), m_ref[:], l_ref[:], acc_ref[:])
    else:
        @pl.when(qi * block_q >= t_real)       # a query block of padding
        def _dead_tile():
            dead(slice(None), block_q)


def _call_name(name: str, mask: AttnMask) -> str:
    """A kernel call's name in a device trace: a window layer's calls say
    so, so that a trace can tell them from the full layers' in one step."""
    return name + "_window" if mask.kind == "sliding_window" else name


def _window_clamp(mask: AttnMask, block: int, j, i, num_b: int,
                  key_of_query: bool):
    """The gridded walks' index maps under a sliding window: the block index
    `j` a grid step names, clamped to the tiles that are computed beside
    block `i` of the other side (the key tiles of query block i, or the query
    blocks of key tile i), so that the pipeline fetches nothing for a tile
    the guards skip."""
    reach = (mask.window + block - 2) // block      # tiles back with a band
    if key_of_query:
        return jnp.clip(j, jnp.maximum(i - reach, 0), i)
    return jnp.clip(j, i, jnp.minimum(i + reach, num_b - 1))


def _kv_row(bh, hq: int, hkv: int):
    """BlockSpec index-map routing for grouped-query attention: query-head
    row `b*hq + h` reads kv row `b*hkv + h // group`. Identity when
    hq == hkv."""
    group = hq // hkv
    return (bh // hq) * hkv + (bh % hq) // group


def _q_row(bkv, g, hq: int, hkv: int):
    """Inverse routing for the dk/dv backward: kv row `b*hkv + hk` with
    group offset g reads query-head row `b*hq + hk*group + g`."""
    group = hq // hkv
    return (bkv // hkv) * hq + (bkv % hkv) * group + g


# What a head's K and V may take of VMEM, double-buffered by the pipeline and
# each width padded to the 128 lanes a VMEM tile has, for the forward to
# keep them resident while it walks the head's query blocks (`_fwd_call`,
# `_fwd_resident_bytes`). A v5e has 128 MiB of VMEM; the budget is what a
# chip run has read beside the gridded walk, and no size past it has been
# timed. Compiled for a v5e beside 1024-row q, o and lse blocks (Mosaic's own
# count of the kernel's scoped VMEM is the row plus some 3.5 MiB: 11.4 MB at
# 8 MiB, 19.7 at 16): INSIDE the 16 MiB of scoped VMEM a kernel gets that
# asks for nothing, t = 4096 at q/k 192 and v 128 in bf16 (6 MiB), t = 8192
# at 64 / 64 or 128 / 128 and t = 4096 at 256 / 256 (8), t = 8192 at 192 /
# 128 (12); 16 does not. WITH the limit `_fwd_call` asks for since PR 52
# (`_vmem_limit`: the row and 24 MiB, so 40 at 16 and 56 at 32): 16 MiB (t =
# 16,384 at 64 / 64 or 128 / 128, t = 8192 at 192 / 192 or 256 / 256) and 32
# (t = 32,768 at 128 / 128, t = 16,384 at 256 / 256).
# A call alone on v5e (TPU v5 lite, jax 0.9.0, PR 52), bf16, blocks of 1024,
# device time from a capture, the gridded walk's budget set to 0 in the same
# process (`python scripts/tune_flash_blocks.py --forward --bh 28 --t 16384
# --d 128 --group 7 [--window 4096]`, `--bh 32 --t 8192 --d 256 --group 8`);
# ms a forward, o and lse of the two walks within a bf16 ulp of each other:
#
#   K and V   shape (t, q/k / v, b*h, group)        mask          grid    row
#   16 MiB    16,384  128 / 128   28   7            causal       25.67  13.62
#             the same                              window 4096  13.54   6.47
#              8,192  256 / 256   32   8            causal        9.67   7.38
#             16,384   64 /  64   32   4            causal       28.90  15.48
#   12 MiB     8,192  192 / 128   32   1            causal        8.61   5.59
#   32 MiB    32,768  128 / 128    8   1            causal       28.21  15.17
#             the same                              window 4096   8.54   4.01
#             16,384  256 / 256   16   8            causal       18.06  14.08
#
# The row walk wins at every shape read: by half where q/k are up to 128 wide
# (m and l through their (block_q, 1) scratch are what the gridded walk pays,
# FWD_SUBTILE_WIDE's note, and under a window 5,208 of its 7,168 grid steps
# at t = 16,384 fetch and compute nothing), by a quarter at 256 / 256, whose
# dots are twice as long a round trip through scratch.
KV_ROW_VMEM_BYTES = 32 * 2 ** 20

# The resident K and V up to which `_fwd_call` asks Mosaic for nothing: every
# row it admitted before PR 52 (they compile inside the default scoped VMEM,
# the note above) keeps the call it had. A row over it asks for its own size
# and the body's room (`_vmem_limit`).
KV_ROW_SCOPED_BYTES = 8 * 2 ** 20

# What a head's backward may keep in VMEM for ONE kernel to make dq, dk and dv
# of it (`_bwd_call`'s row walk, `_bwd_resident_bytes`): q, k, v, dO, lse and
# delta in, dq, dk and dv out, all whole rows, double-buffered by the
# pipeline and each width padded to 128 lanes (lse and delta, (t, 1) float32,
# are 128 lanes of one value), plus the float32 accumulators. The kernel
# asks Mosaic for that much and the body's room (`_vmem_limit`; a v5e has
# 128 MiB, the default scoped limit is 16). Compiled for a v5e: t = 4096 at
# q/k 192 and v 128 in bf16 is 34 MiB; t = 8192 at 64 / 64 with a group of 4
# or at 128 / 128 with a group of 8 is 56. t = 8192 at 256 / 256 with a
# group of 8 is 96 and t = 16,384 at 128 / 128 with a group of 7 is 112:
# with the body's room they pass what `_vmem_limit` will ask for, and are
# held to `BWD_ROW_ONCE_VMEM_BYTES` instead.
BWD_ROW_VMEM_BYTES = 64 * 2 ** 20

# What a head over `BWD_ROW_VMEM_BYTES` may keep in VMEM for the same ONE
# kernel with its nine whole-row blocks kept ONCE (`pl.Buffered(1)` on each
# `BlockSpec` of `_bwd_row_call`; the float32 accumulators are scratch and
# were never doubled). A block's index changes once a query head (q, dO,
# lse, delta, dq) or once a key-value head (k, v, dk, dv), so the second
# buffer hides one head's DMA behind the head before it and nothing else;
# without it the kernel waits for its rows at each head's start. 68 MiB is
# t = 16,384 at 128 / 128 with a group of 7 (44 of blocks + 24 of
# accumulators; 112 with two buffers); t = 8192 at 256 / 256 with a group of
# 8 is 60 (36 + 24; 96). What is asked of Mosaic is `_vmem_limit` of the
# bytes as taken: 92 and 84 MiB of the chip's 128. t = 65,536 at 128 / 128
# (272 MiB once) stays with the split kernels.
# A backward call alone on v5e (TPU v5 lite, jax 0.9.0), bf16, blocks of
# 1024, device time from a capture, the split kernels in the same process
# with both budgets at 0 (`python scripts/tune_flash_blocks.py --backward
# --bh 28 --t 16384 --d 128 --group 7 [--window 4096]`, `--bh 32 --t 8192
# --d 256 --group 8`, `--bh 32 --t 8192 --d 128 --group 8`); ms a backward,
# the row walk's dq / dk / dv within 9.8e-4 / 3.9e-3 / 3.9e-3 of the split
# kernels' (values up to 3.1 / 6.9 / 12.8; one kernel sums dq over the key
# tiles in float32 VMEM where the split one sums it a grid step at a time).
# PR 56's readings; PR 54, whose change was lost to the yardstick, read the
# same to 0.01 ms (PERF.md section 6):
#
#  twice / once  t, q/k / v, b*h, group  mask         split  row (buffers)  DMA
#  112 / 68 MiB  16,384 128/128  28  7   window 4096  20.95  14.20 (1)     1.28
#                the same                causal       46.66  29.08 (1)     1.28
#   96 / 60 MiB   8,192 256/256  32  8   causal       24.48  16.67 (1)     1.04
#                the same, two buffers by hand               15.75 (2)
#   56 / 34 MiB   8,192 128/128  32  8   causal       13.10   8.24 (2)     0.67
#                the same, kept once by hand                  8.82 (1)
#
# The last pair is the control: a head that fits twice loses 7% of its call
# to the waits the second buffer hid, so it keeps both; a head that does not
# fit twice gains a third by leaving the split kernels (seven products a
# rectangle where the row walk runs five, and under the window 5,208 of the
# split kernels' 7,168 grid steps fetch and compute nothing). At 96 MiB
# Mosaic still takes two buffers a block under `_vmem_limit`'s cap of 100
# and reads 6% faster than kept once; at 112 it refuses ("scoped allocation
# with size 104.00M and limit 100.00M"), so the first budget stays where
# the body's room is sure, and 96 is held to the second.
BWD_ROW_ONCE_VMEM_BYTES = 68 * 2 ** 20


def _vmem_limit(resident_bytes: int) -> int:
    """The scoped VMEM a kernel asks Mosaic for where the default 16 MiB is
    not enough: what it keeps there (its blocks double-buffered, its
    scratch) and room for the body's own values."""
    return min(resident_bytes + 24 * 2 ** 20, 100 * 2 ** 20)


def _fwd_resident_bytes(t_pad: int, d: int, dv: int, itemsize: int) -> int:
    """What the forward's row walk keeps of a head in VMEM beside its q, o
    and lse blocks, as Mosaic lays it out: K and V whole rows, each width
    padded to 128 lanes, double-buffered."""
    return 2 * t_pad * (_round_up(d, 128) + _round_up(dv, 128)) * itemsize


def _fwd_call(q, k, v, *, t_real: int, block_q: int, block_k: int,
              hq: int, hkv: int, interpret: bool, mask: AttnMask = CAUSAL):
    bh, t_pad, d = q.shape
    dv = v.shape[-1]            # v (and o) may be narrower than q/k
    num_qb = t_pad // block_q
    num_kb = t_pad // block_k
    scale = 1.0 / math.sqrt(d)
    # Several key blocks a head: where the head's K and V fit the budget the
    # key block IS the row, fetched once a head (its block index no longer
    # depends on the query block), and the kernel walks the row's tiles
    # itself (`_fwd_kernel`: needs the diagonal to cross one tile a query
    # block, the square one). Otherwise the grid walks them. Decided from
    # what this call sees.
    resident = _fwd_resident_bytes(t_pad, d, dv, k.dtype.itemsize)
    if num_kb == 1:
        walk = "tile"
    elif block_q == block_k and resident <= KV_ROW_VMEM_BYTES:
        walk = "row"
    else:
        walk = "grid"
    tracer = current_tracer()
    if tracer is not None:
        tracer.instant("flash_fwd_walk", walk=walk, t=t_pad, d=d, dv=dv,
                       group=hq // hkv, resident_bytes=resident,
                       budget_bytes=KV_ROW_VMEM_BYTES, mask=mask.kind,
                       window=mask.window)
    row_walk, gridded = walk == "row", walk == "grid"
    # a row the default scoped VMEM holds asks for nothing: the call is then
    # the one it has been since PR 34
    vmem_limit = _vmem_limit(resident) \
        if row_walk and resident > KV_ROW_SCOPED_BYTES else None

    kernel = functools.partial(
        _fwd_kernel, scale=scale, t_real=t_real,
        block_q=block_q, block_k=block_k, num_kb=num_kb,
        plans=_tile_plans(block_q, block_k, num_qb, num_kb, t_real, d,
                          mask=mask),
        row_walk=row_walk, mask=mask)

    def kv_index(b, i, j):
        if row_walk:
            return _kv_row(b, hq, hkv), 0, 0
        if gridded and mask.kind == "causal":
            # a tile above the diagonal is skipped: name the block that is
            # already there, and the pipeline fetches nothing for it
            j = jnp.minimum(j, (i * block_q + block_q - 1) // block_k)
        if gridded and mask.kind == "sliding_window":
            j = _window_clamp(mask, block_q, j, i, num_kb, key_of_query=True)
        return _kv_row(b, hq, hkv), j, 0

    kv_rows = t_pad if row_walk else block_k
    # what the plan computes, masked entries of a crossed sub-tile included
    entries = bh * plan_stats(mask, t_pad, block_q, block_k, t_real,
                              d)["work_elems"]
    o, lse = pl.pallas_call(
        kernel,
        grid=(bh, num_qb, 1 if row_walk else num_kb),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, kv_rows, d), kv_index),
            pl.BlockSpec((1, kv_rows, dv), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            _out_struct((bh, t_pad, dv), q.dtype, q),
            _out_struct((bh, t_pad, 1), jnp.float32, q),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, dv), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ] if gridded else [],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit),
        cost_estimate=pl.CostEstimate(
            flops=2 * (d + dv) * entries,
            bytes_accessed=(2 * q.size + bh * t_pad * dv) * q.dtype.itemsize,
            transcendentals=entries),
        interpret=interpret,
        name=_call_name("flash_fwd", mask),
    )(q, k, v)
    return o, lse


# ---------------------------------------------------------------- backward
#
# Every backward kernel forms p, dp and ds a rectangle of its tile's plan.
# Input-dtype dots + f32 accumulation throughout (see `_dot`); p and ds are
# cast back to the input dtype before their dots — the standard
# flash-attention-2 bf16 backward. For f32 inputs every cast is a no-op,
# keeping the tight-tolerance CPU tests exact. Masked entries are
# hard-zeroed: dead rows (>= t_real) carry lse = MASK, and exp(s - MASK)
# would fabricate p there — harmless only while their cotangents are exactly
# zero, which the public t_real path must not rely on (e.g. MoE aux losses
# touch every row).


def _rect_p_ds(plan, rect, q, k, v, do, lse, delta, scale):
    """p and ds of one rectangle, (rows, cols), cast to the input dtype."""
    r0, rows, c0, cols, masked = rect
    p = jnp.exp(_dot(q, k, _NT) * scale - lse)
    if masked:
        p = jnp.where(_rect_live(plan, r0, rows, c0, cols), p, 0.0)
    dp = _dot(do, v, _NT)
    ds = (p * (dp - delta) * scale).astype(q.dtype)
    return p.astype(do.dtype), ds


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_acc, *, scale: float, t_real: int,
               block_q: int, block_k: int, num_kb: int, plans,
               mask: AttnMask = CAUSAL):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    for plan in plans:
        @pl.when(_plan_is(plan, qi, ki, block_q, block_k, t_real, mask))
        def _compute(plan=plan):
            for r0, rows, rects in plan.bands:
                rs = slice(r0, r0 + rows)
                q, do = q_ref[0, rs, :], do_ref[0, rs, :]
                lse, delta = lse_ref[0, rs, :], delta_ref[0, rs, :]
                for c0, cols, masked in rects:
                    k = k_ref[0, c0:c0 + cols, :]
                    _, ds = _rect_p_ds(
                        plan, (r0, rows, c0, cols, masked), q, k,
                        v_ref[0, c0:c0 + cols, :], do, lse, delta, scale)
                    dq_acc[rs] += _dot(ds, k, _NN)

    @pl.when(ki == num_kb - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, scale: float, t_real: int,
                block_q: int, block_k: int, num_qb: int, plans,
                group: int = 1, mask: AttnMask = CAUSAL):
    """dk/dv accumulate over the sequential grid dim 2 = (g, qi) — under
    grouped-query attention every one of a kv head's `group` query heads
    contributes; the index maps route each (g, qi) step to its query row.
    Scores are formed transposed, (cols, rows), so that both accumulating
    dots contract the minor dimension."""
    ki = pl.program_id(1)
    gq = pl.program_id(2)
    qi = gq % num_qb

    @pl.when(gq == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    for plan in plans:
        @pl.when(_plan_is(plan, qi, ki, block_q, block_k, t_real, mask))
        def _compute(plan=plan):
            for c0, cols, rects in plan.columns:
                cs = slice(c0, c0 + cols)
                k, v = k_ref[0, cs, :], v_ref[0, cs, :]
                for r0, rows, masked in rects:
                    rs = slice(r0, r0 + rows)
                    q, do = q_ref[0, rs, :], do_ref[0, rs, :]
                    pt = jnp.exp(_dot(k, q, _NT) * scale
                                 - jnp.transpose(lse_ref[0, rs, :]))
                    if masked:                               # (cols, rows)
                        pt = jnp.where(
                            _rect_live(plan, r0, rows, c0, cols,
                                       transposed=True), pt, 0.0)
                    dv_acc[cs] += _dot(pt.astype(do.dtype), do, _NN)
                    dpt = _dot(v, do, _NT)
                    dst = (pt * (dpt - jnp.transpose(delta_ref[0, rs, :]))
                           * scale).astype(q.dtype)
                    dk_acc[cs] += _dot(dst, q, _NN)

    @pl.when(gq == group * num_qb - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, dq_acc, *kv_acc,
                      scale: float, plan: SubtilePlan):
    """Single-block backward: when the whole (padded) sequence fits one
    block, compute dq/dk/dv in ONE kernel — s and p are built once a
    rectangle and dp is shared, 5 MXU dots instead of the split kernels' 7,
    one launch instead of two. Grid (b*hkv, group): each step handles one
    query head of the kv head's group (one step when hq == hkv).

    The walk is key sub-column by key sub-column (`plan.columns`): dk and
    dv of a sub-column are complete when its rectangles are done, dq
    accumulates in float32 scratch. dk and dv are formed TRANSPOSED,
    dv^T = do^T @ p and dk^T = q^T @ ds with p and ds as the MXU's
    stationary operand: `p^T @ do` has Mosaic transpose every (rows, cols)
    rectangle through the XLU, which cost 0.13 ms of a 1.00 ms call at
    b*h = 192 (FWD_SUBTILE's sweep note); this way only (rows, d) and
    (d, cols) arrays are transposed. With group > 1, dk/dv accumulate in
    `kv_acc` across the sequential group dim.

    Refs here are (t, d)/(t, 1): the leading batch*heads dim is a squeezed
    (None) block dim — `ref[0]` discharges to a vma-mismatched
    dynamic_slice under the shard_map interpreter."""
    if kv_acc:
        dk_acc, dv_acc = kv_acc
        g, group = pl.program_id(1), pl.num_programs(1)

        @pl.when(g == 0)
        def _init():
            dk_acc[:] = jnp.zeros_like(dk_acc)
            dv_acc[:] = jnp.zeros_like(dv_acc)

    t_pad, d = dq_ref.shape
    dv = dv_ref.shape[-1]
    dq_acc[:] = jnp.zeros_like(dq_acc)
    done = 0
    for c0, cols, rects in plan.columns:
        cs = slice(c0, c0 + cols)
        k, v = k_ref[cs, :], v_ref[cs, :]
        dkt = jnp.zeros((d, cols), jnp.float32)
        # one zero where the widths agree: the kernel's text at equal widths
        # is then the one it has always been
        dvt = dkt if dv == d else jnp.zeros((dv, cols), jnp.float32)
        for r0, rows, masked in rects:
            rs = slice(r0, r0 + rows)
            q, do = q_ref[rs, :], do_ref[rs, :]
            p, ds = _rect_p_ds(plan, (r0, rows, c0, cols, masked), q, k, v,
                               do, lse_ref[rs, :], delta_ref[rs, :], scale)
            dvt += _dot(jnp.transpose(do), p, _NN)           # (d, cols)
            dkt += _dot(jnp.transpose(q), ds, _NN)
            dq_acc[rs] += _dot(ds, k, _NN)
        if kv_acc:
            dk_acc[cs] += jnp.transpose(dkt)
            dv_acc[cs] += jnp.transpose(dvt)
        else:
            dk_ref[cs, :] = jnp.transpose(dkt).astype(dk_ref.dtype)
            dv_ref[cs, :] = jnp.transpose(dvt).astype(dv_ref.dtype)
        done = c0 + cols
    dq_ref[...] = dq_acc[:].astype(dq_ref.dtype)

    if kv_acc:
        @pl.when(g == group - 1)
        def _finalize():
            dk_ref[...] = dk_acc[:].astype(dk_ref.dtype)
            dv_ref[...] = dv_acc[:].astype(dv_ref.dtype)
    elif done < t_pad:                  # key sub-columns wholly past t_real
        dk_ref[done:, :] = jnp.zeros((t_pad - done, d), dk_ref.dtype)
        dv_ref[done:, :] = jnp.zeros((t_pad - done, dv), dv_ref.dtype)


def _col_walk_plans(plans, block: int):
    """The resident backward's reading of a square-block grid's plans
    (`_bwd_row_kernel`), as `_row_walk_plans` is the forward's, by key tile
    where that is by query block: (full, edge), each (plan of the tile on
    the diagonal, plan of the tiles under it) or None. `full` is a key tile
    whose own query block t_real leaves whole, `edge` the one t_real cuts:
    key tile j walks its diagonal tile by `full[0]`, the whole query tiles
    j + 1 .. under it by `full[1]` and the cut one, if there is one, by
    `edge[1]`; the cut key tile has its diagonal tile, `edge[0]`, alone."""
    by = {(p.diag, p.cut): p for p in plans}
    cuts = sorted({p.cut for p in plans})
    pair = lambda cut: (by[0, cut], by.get((block - 1, cut)))
    full = pair(block) if (0, block) in by else None
    edge = pair(cuts[0]) if cuts[0] < block else None
    return full, edge


def _rects_at(plan: Optional[SubtilePlan], c0: int):
    """The query rectangles of `plan`'s key sub-column that holds column
    `c0`. A plan fuses neighbouring sub-columns that hold the same unmasked
    runs, so a sub-column of the diagonal tile's plan may be part of one of
    the plan under it."""
    if plan is None:
        return ()
    return next((rects for at, cols, rects in plan.columns
                 if at <= c0 < at + cols), ())


def _bwd_row_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dq_ref, dk_ref, dv_ref, dq_acc, *kv_acc, scale: float,
                    t_real: int, block: int, plans,
                    mask: AttnMask = CAUSAL):
    """Several blocks a head, the head resident: dq, dk and dv in ONE kernel,
    what `_bwd_fused_kernel` is for one tile. s, p, dp and ds are formed
    once a rectangle and feed all three (5 MXU dots where `_dq_kernel` and
    `_dkv_kernel` run 7). Grid (b*hkv, group) and refs as there: q, do, lse
    and delta of one query head, k and v of its kv head, whole rows.

    The walk is key tile by key tile, and inside one key sub-column by key
    sub-column: its rectangles in the tile on the diagonal by that tile's
    static plan, then a loop over the query tiles under it whose body is
    the sub-column of the unmasked tile's plan (one merged rectangle a
    query tile), then the query tile t_real cuts, if any. dk and dv of the
    sub-column, formed transposed, are the loop's carry and are complete
    when it ends; dq accumulates in float32 scratch over the whole row. The
    key tiles are a loop too: every one left of t_real's tile walks the
    same plans, so the kernel's text is at most four tiles' plans whatever
    the sequence's length (`_col_walk_plans`).

    Under the block-diffusion mask the walk is the same with other lists:
    a noised key tile has its one tile on the block diagonal; clean key
    tile j has the two diagonal tiles of place j (the clean query block's
    and the noised one's) and, under them, the later query tiles of BOTH
    halves by the unmasked plan, as one loop."""
    if kv_acc:
        dk_acc, dv_acc = kv_acc
        g, group = pl.program_id(1), pl.num_programs(1)

        @pl.when(g == 0)
        def _init():
            dk_acc[:] = jnp.zeros_like(dk_acc)
            dv_acc[:] = jnp.zeros_like(dv_acc)

    t_pad, d = dq_ref.shape
    dv = dv_ref.shape[-1]
    n_full = t_real // block                # query (and key) tiles left whole
    dq_acc[:] = jnp.zeros_like(dq_acc)

    def rows_of(tile, r0, rows):
        if isinstance(tile, int):
            return slice(tile * block + r0, tile * block + r0 + rows)
        return pl.ds(pl.multiple_of(tile * block + r0, math.gcd(block, r0)),
                     rows)

    def rect(plan, tile, acc, k, v, r0, rows, c0, cols, masked):
        """One rectangle of `plan` in query tile `tile`: its share of the
        sub-column's (dk^T, dv^T) and of dq."""
        rs = rows_of(tile, r0, rows)
        q, do = q_ref[rs, :], do_ref[rs, :]
        p, ds = _rect_p_ds(plan, (r0, rows, c0, cols, masked), q, k, v, do,
                           lse_ref[rs, :], delta_ref[rs, :], scale)
        dq_acc[rs] += _dot(ds, k, _NN)
        return (acc[0] + _dot(jnp.transpose(q), ds, _NN),    # (d, cols)
                acc[1] + _dot(jnp.transpose(do), p, _NN))

    def key_tile(kb, diags, under, cut_tile, loop=None):
        """Key tile `kb`: its diagonal tiles `diags`, ((query tile, plan),
        ...), the first one's sub-columns leading; then the query tiles
        under them by `under`, kb + 1 .. n_full - 1 or, with `loop` =
        (first, past, query tile of an index), those; then query tile
        n_full by `cut_tile`; each where it exists."""
        tile_of = loop and loop[2]
        for c0, cols, rects in diags[0][1].columns:
            cs = rows_of(kb, c0, cols)
            k, v = k_ref[cs, :], v_ref[cs, :]
            acc = (jnp.zeros((d, cols), jnp.float32),
                   jnp.zeros((dv, cols), jnp.float32))
            for at, (tile, diag) in enumerate(diags):
                for r0, rows, masked in (_rects_at(diag, c0) if at
                                         else rects):
                    acc = rect(diag, tile, acc, k, v, r0, rows, c0, cols,
                               masked)
            under_rects = _rects_at(under, c0)
            if under_rects:
                def q_tile(qi, acc, c0=c0, cols=cols, k=k, v=v,
                           under_rects=under_rects):
                    if tile_of:
                        qi = tile_of(qi)
                    for r0, rows, masked in under_rects:
                        acc = rect(under, qi, acc, k, v, r0, rows, c0, cols,
                                   masked)
                    return acc
                acc = jax.lax.fori_loop(
                    *(loop[:2] if loop else (kb + 1, n_full)), q_tile, acc)
            for r0, rows, masked in _rects_at(cut_tile, c0):
                acc = rect(cut_tile, n_full, acc, k, v, r0, rows, c0, cols,
                           masked)
            dkt, dvt = acc
            if kv_acc:
                dk_acc[cs] += jnp.transpose(dkt)
                dv_acc[cs] += jnp.transpose(dvt)
            else:
                dk_ref[cs, :] = jnp.transpose(dkt).astype(dk_ref.dtype)
                dv_ref[cs, :] = jnp.transpose(dvt).astype(dv_ref.dtype)

    done = n_full * block                   # key columns walked
    if mask.kind == "causal":
        full, edge = _col_walk_plans(plans, block)
        if full:
            def whole_key_tile(kb, carry):
                key_tile(kb, ((kb, full[0]),), full[1], edge and edge[1])
                return carry
            jax.lax.fori_loop(0, n_full, whole_key_tile, 0)
        if edge:
            key_tile(n_full, ((n_full, edge[0]),), None, None)
            done += sum(edge[0].columns[-1][:2])
    elif mask.kind == "sliding_window":
        diagonal, under, whole, edges = _window_walk(mask, block, n_full, d,
                                                     True)
        # a loop over key tiles for each count of edge tiles under one (the
        # last key tiles have none); the loop over the query tiles under a
        # key tile ends at the band's last whole tile
        bounds = [n_full] + [n_full - back for back, _ in edges]
        for n in range(len(edges) + 1):
            def window_key_tile(kb, carry, n=n):
                key_tile(kb, ((kb, diagonal),) + tuple(
                    (kb + back, p) for back, p in edges[:n]), under, None,
                         (kb + 1, jnp.minimum(kb + whole + 1, n_full), None))
                return carry
            first = bounds[n + 1] if n < len(edges) else 0
            if first < bounds[n]:
                jax.lax.fori_loop(first, bounds[n], window_key_tile, 0)
    else:
        nh = mask.half // block
        by = {p.role: p for p in plans}

        def noised_key_tile(kb, carry):
            key_tile(kb, ((kb, by["nn"]),), None, None)
            return carry

        def clean_key_tile(kb, carry):
            later = n_full - 1 - kb         # query tiles under it, a half
            key_tile(kb, ((kb, by["cc"]),) + (
                ((kb - nh, by["nc"]),) if "nc" in by else ()),
                     by.get("under"), None,
                     (0, 2 * later, lambda at: jnp.where(
                         at < later, kb - nh + 1 + at, kb + 1 + at - later)))
            return carry
        jax.lax.fori_loop(0, nh, noised_key_tile, 0)
        jax.lax.fori_loop(nh, n_full, clean_key_tile, 0)
    dq_ref[...] = dq_acc[:].astype(dq_ref.dtype)

    if kv_acc:
        @pl.when(g == group - 1)
        def _finalize():
            dk_ref[...] = dk_acc[:].astype(dk_ref.dtype)
            dv_ref[...] = dv_acc[:].astype(dv_ref.dtype)
    elif done < t_pad:                      # key columns wholly past t_real
        dk_ref[done:, :] = jnp.zeros((t_pad - done, d), dk_ref.dtype)
        dv_ref[done:, :] = jnp.zeros((t_pad - done, dv), dv_ref.dtype)


def _bwd_resident_bytes(t_pad: int, d: int, dv: int, itemsize: int,
                        group: int, buffers: int = 2) -> int:
    """What `_bwd_row_kernel` keeps of a head in VMEM, as Mosaic lays it out
    (each width padded to 128 lanes): the nine whole-row blocks, `buffers`
    of each (the pipeline's two, or one), the float32 dq accumulator and,
    under grouped-query attention, dk's and dv's."""
    wide, narrow = _round_up(d, 128), _round_up(dv, 128)
    blocks = t_pad * ((4 * wide + 3 * narrow) * itemsize + 2 * 128 * 4)
    scratch = t_pad * 4 * (wide + (wide + narrow if group > 1 else 0))
    return buffers * blocks + scratch


def _bwd_row_call(q, k, v, do, lse, delta, *, t_real: int, block: int,
                  hq: int, hkv: int, interpret: bool, resident: int,
                  buffers: int = 2, mask: AttnMask = CAUSAL):
    bh, t_pad, d = q.shape
    dv = v.shape[-1]
    bhkv = k.shape[0]
    group = hq // hkv
    num_b = t_pad // block
    # two buffers a block are the pipeline's own: the spec names none then
    once = {"pipeline_mode": pl.Buffered(1)} if buffers == 1 else {}
    row = lambda width, of_q: pl.BlockSpec(
        (None, t_pad, width),
        (lambda b, g: (_q_row(b, g, hq, hkv), 0, 0)) if of_q
        else (lambda b, g: (b, 0, 0)), **once)
    acc = lambda width: pltpu.VMEM((t_pad, width), jnp.float32)
    entries = bh * plan_stats(mask, t_pad, block, block, t_real, d,
                              backward=True)["work_elems"]
    return pl.pallas_call(
        functools.partial(
            _bwd_row_kernel, scale=1.0 / math.sqrt(d), t_real=t_real,
            block=block,
            plans=_tile_plans(block, block, num_b, num_b, t_real, d,
                              backward=True, mask=mask), mask=mask),
        grid=(bhkv, group),
        in_specs=[row(d, True), row(d, False), row(dv, False),
                  row(dv, True), row(1, True), row(1, True)],
        out_specs=[row(d, True), row(d, False), row(dv, False)],
        out_shape=[_out_struct((bh, t_pad, d), q.dtype, q),
                   _out_struct((bhkv, t_pad, d), k.dtype, q),
                   _out_struct((bhkv, t_pad, dv), v.dtype, q)],
        scratch_shapes=[acc(d)] + ([acc(d), acc(dv)] if group > 1 else []),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(resident)),
        cost_estimate=pl.CostEstimate(
            flops=2 * (3 * d + 2 * dv) * entries,
            bytes_accessed=(2 * (q.size + k.size + v.size) + do.size)
            * q.dtype.itemsize + 8 * bh * t_pad,
            transcendentals=entries),
        interpret=interpret,
        name=_call_name("flash_bwd", mask),
    )(q, k, v, do, lse, delta)


def _bwd_call(q, k, v, o, lse, do, *, t_real: int, block_q: int, block_k: int,
              hq: int, hkv: int, interpret: bool, mask: AttnMask = CAUSAL):
    bh, t_pad, d = q.shape
    dv = v.shape[-1]
    bhkv = k.shape[0]
    group = hq // hkv
    num_qb = t_pad // block_q
    num_kb = t_pad // block_k
    scale = 1.0 / math.sqrt(d)
    kv = lambda b: _kv_row(b, hq, hkv)

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                    keepdims=True)                           # (bh, t_pad, 1)

    # Fused path gate: under the interpreter inside shard_map (vma tags
    # present), the discharged kernel jaxpr fails shard_map's vma check on
    # plain elementwise ops (the split kernels pass only because their ops
    # sit inside pl.when/cond, which unifies vma). Compiled TPU execution
    # never discharges, so real hardware always takes the fused path; the
    # CPU grad tests outside shard_map still cover its math.
    interp_vma = interpret and getattr(jax.typeof(q), "vma", None)
    # Several blocks a head: where the blocks are square and what the head's
    # backward keeps in VMEM fits a budget, one kernel holds the head and
    # walks its tiles itself (`_bwd_row_kernel`): its whole-row blocks
    # double-buffered where that fits `BWD_ROW_VMEM_BYTES`, kept once where
    # only that fits `BWD_ROW_ONCE_VMEM_BYTES`; otherwise the grid walks the
    # tiles, dq apart from dk and dv. Decided from what this call sees.
    twice, kept_once = (_bwd_resident_bytes(
        t_pad, d, dv, q.dtype.itemsize, group, buffers=n) for n in (2, 1))
    walk, buffers = "grid", 2
    if interp_vma:
        pass
    elif num_qb == 1 and num_kb == 1:
        walk = "tile"
    elif block_q == block_k and twice <= BWD_ROW_VMEM_BYTES:
        walk = "row"
    elif block_q == block_k and kept_once <= BWD_ROW_ONCE_VMEM_BYTES:
        walk, buffers = "row", 1
    resident, budget = ((twice, BWD_ROW_VMEM_BYTES) if buffers == 2 else
                        (kept_once, BWD_ROW_ONCE_VMEM_BYTES))
    tracer = current_tracer()
    if tracer is not None:
        # the bytes as taken and the budget they were held to; a walk that
        # is not `row` says what the row walk would have kept with two
        tracer.instant("flash_bwd_walk", walk=walk, buffers=buffers,
                       t=t_pad, d=d, dv=dv, group=group,
                       resident_bytes=resident, budget_bytes=budget,
                       kept_once_bytes=kept_once, mask=mask.kind,
                       window=mask.window)
    if walk == "row":
        return _bwd_row_call(q, k, v, do, lse, delta, t_real=t_real,
                             block=block_q, hq=hq, hkv=hkv,
                             interpret=interpret, resident=resident,
                             buffers=buffers, mask=mask)
    if walk == "tile":
        q_td = pl.BlockSpec((None, t_pad, d),
                            lambda b, g: (_q_row(b, g, hq, hkv), 0, 0))
        q_t1 = pl.BlockSpec((None, t_pad, 1),
                            lambda b, g: (_q_row(b, g, hq, hkv), 0, 0))
        kv_td = pl.BlockSpec((None, t_pad, d), lambda b, g: (b, 0, 0))
        acc = pltpu.VMEM((t_pad, d), jnp.float32)
        if dv == d:
            do_td, v_td, v_acc = q_td, kv_td, acc
        else:
            do_td = pl.BlockSpec((None, t_pad, dv),
                                 lambda b, g: (_q_row(b, g, hq, hkv), 0, 0))
            v_td = pl.BlockSpec((None, t_pad, dv), lambda b, g: (b, 0, 0))
            v_acc = pltpu.VMEM((t_pad, dv), jnp.float32)
        return pl.pallas_call(
            functools.partial(
                _bwd_fused_kernel, scale=scale,
                plan=subtile_plan(mask, t_pad, t_pad, 0, 0, t_real, d,
                                  backward=True)),
            grid=(bhkv, group),
            in_specs=[q_td, kv_td, v_td, do_td, q_t1, q_t1],
            out_specs=[q_td, kv_td, v_td],
            out_shape=[_out_struct((bh, t_pad, d), q.dtype, q),
                       _out_struct((bhkv, t_pad, d), k.dtype, q),
                       _out_struct((bhkv, t_pad, dv), v.dtype, q)],
            scratch_shapes=[acc] + ([acc, v_acc] if group > 1 else []),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
            name=_call_name("flash_bwd", mask),
        )(q, k, v, do, lse, delta)

    plans = _tile_plans(block_q, block_k, num_qb, num_kb, t_real, d,
                        backward=True, mask=mask)
    # under a sliding window a grid step names only blocks of tiles that
    # are computed (`_window_clamp`)
    kblk = lambda i, j: j
    if mask.kind == "sliding_window":
        kblk = lambda i, j: _window_clamp(mask, block_q, j, i, num_kb, True)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, t_real=t_real,
                          block_q=block_q, block_k=block_k, num_kb=num_kb,
                          plans=plans, mask=mask),
        grid=(bh, num_qb, num_kb),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, i, j: (kv(b), kblk(i, j), 0)),
            pl.BlockSpec((1, block_k, dv),
                         lambda b, i, j: (kv(b), kblk(i, j), 0)),
            pl.BlockSpec((1, block_q, dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=_out_struct((bh, t_pad, d), q.dtype, q),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=_call_name("flash_bwd_dq", mask),
    )(q, k, v, do, lse, delta)

    # dk/dv: grid dim 2 runs (group x num_qb) sequential steps per kv block;
    # the index maps pick query head `hk*group + g` at q-block `qi`.
    qrow = lambda b, gq: _q_row(b, gq // num_qb, hq, hkv)
    qblk = lambda gq: gq % num_qb
    if mask.kind == "sliding_window":
        qblk_of = lambda j, gq: _window_clamp(mask, block_q, gq % num_qb, j,
                                              num_qb, False)
    else:
        qblk_of = lambda j, gq: qblk(gq)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, t_real=t_real,
                          block_q=block_q, block_k=block_k, num_qb=num_qb,
                          plans=plans, group=group, mask=mask),
        grid=(bhkv, num_kb, group * num_qb),
        in_specs=[
            pl.BlockSpec((1, block_q, d),
                         lambda b, j, gq: (qrow(b, gq), qblk_of(j, gq), 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, gq: (b, j, 0)),
            pl.BlockSpec((1, block_k, dv), lambda b, j, gq: (b, j, 0)),
            pl.BlockSpec((1, block_q, dv),
                         lambda b, j, gq: (qrow(b, gq), qblk_of(j, gq), 0)),
            pl.BlockSpec((1, block_q, 1),
                         lambda b, j, gq: (qrow(b, gq), qblk_of(j, gq), 0)),
            pl.BlockSpec((1, block_q, 1),
                         lambda b, j, gq: (qrow(b, gq), qblk_of(j, gq), 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, gq: (b, j, 0)),
            pl.BlockSpec((1, block_k, dv), lambda b, j, gq: (b, j, 0)),
        ],
        out_shape=[
            _out_struct((bhkv, t_pad, d), k.dtype, q),
            _out_struct((bhkv, t_pad, dv), v.dtype, q),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=_call_name("flash_bwd_dkv", mask),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ------------------------------------------- the blocks a call runs with


def flash_blocks(t: int, head_dim: int, mask: AttnMask = CAUSAL, *,
                 t_real: Optional[int] = None, block_q: Optional[int] = None,
                 block_k: Optional[int] = None,
                 bwd_block_q: Optional[int] = None,
                 bwd_block_k: Optional[int] = None):
    """(t_pad, block_q, block_k, bwd_block_q, bwd_block_k, mask) as
    `flash_attention` runs `t` rows at q/k width `head_dim`: a function of
    its arguments alone and the one place that says it
    (`obs/attribution.flash_tile_stats` asks here). A block not named is
    `DEFAULT_BLOCK` at every width (its note: `head_dim` decides nothing
    yet); `mask` comes back `CAUSAL` where a window covers the rows."""
    asked = []
    for name, blk in (("block_q", block_q), ("block_k", block_k),
                      ("bwd_block_q", bwd_block_q),
                      ("bwd_block_k", bwd_block_k)):
        blk = blk or DEFAULT_BLOCK
        if blk % 128 or blk & (blk - 1):
            raise ValueError(
                f"{name} must be a power-of-two multiple of 128, got {blk}")
        asked.append(blk)
    if mask.kind == "sliding_window" and mask.window >= t:
        mask = CAUSAL       # every earlier row is inside the window
    if mask.kind != "causal":
        if t_real not in (None, t):
            raise ValueError(f"a {mask.kind} mask takes no t_real, got "
                             f"t={t}, t_real={t_real}")
        clamp = window_block if mask.kind == "sliding_window" else mask_block
        bq = bk = clamp(mask, t, min(asked[:2]))
        bbq = bbk = clamp(mask, t, min(asked[2:]))
    else:
        # Clamp blocks to the next power of two >= t so that max(bq, bk) is
        # a common multiple of both and t_pad divides evenly into full q AND
        # k blocks (a non-power-of-two clamp once left q rows >= block_q
        # unwritten). Padded blocks are skipped by the kernels' block_live
        # guards, so over-padding costs only grid overhead. All four block
        # sizes share one t_pad, so the bwd blocks participate in the clamp.
        pow2 = max(128, 1 << (t - 1).bit_length())
        bq, bk, bbq, bbk = (min(blk, pow2) for blk in asked)
    return _round_up(t, max(bq, bk, bbq, bbk)), bq, bk, bbq, bbk, mask


# ---------------------------------------------------------------- public


def _require_tpu(what: str, interpret: bool) -> None:
    if not interpret and jax.default_backend() != "tpu":
        raise ValueError(
            f"{what} is compiled by Mosaic and needs a TPU backend (got "
            f"{jax.default_backend()!r}); off-TPU use the XLA attention, or "
            f"ask for the Pallas interpreter explicitly with interpret=True "
            f"(the tests do)")


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    block_q: int = None,
                    block_k: int = None,
                    bwd_block_q: int = None,
                    bwd_block_k: int = None,
                    t_real: int = None,
                    interpret: bool = False,
                    mask: AttnMask = CAUSAL) -> jax.Array:
    """Flash attention, causal unless `mask` declares another
    (`ops/attention.block_diffusion`: the blocks are then square, clamped
    to the mask's half so that no tile spans two quadrants, and must divide
    it; its block length divides 128; no `t_real`.
    `ops/attention.sliding_window`: square blocks that divide the sequence;
    no `t_real`; a window that covers the sequence is `CAUSAL`). q: (b, heads, t,
    head_dim); k, v may carry
    FEWER heads (b, kv_heads, t, head_dim) with heads % kv_heads == 0 —
    grouped-query attention routed inside the kernels (no K/V repeat in HBM).
    v may be of ANOTHER width than q and k (latent attention: q/k of 192
    against v of 128); the output has v's width, the scores are scaled by
    q's, and `flash_blocks` is asked by q's.

    Drop-in replacement for `causal_attention_xla`
    (`/root/reference/models/model.py:73-77` semantics). Sequence length is
    padded to the block size internally; padded keys are masked, padded
    query rows are sliced off. `flash_blocks` says which blocks the call runs
    with (explicit values override `DEFAULT_BLOCK`); `bwd_block_*` tune the
    dq/dkv kernels independently of the forward.

    `t_real` (pad-aware bucketing): when the caller's sequence buffer is
    itself padded — e.g. t=1000 real tokens bucketed into a t=1024 buffer
    so every surrounding matmul tiles cleanly — pass the real length and
    the kernels do only ~t_real work (block-granular: fully-dead tiles are
    skipped by the grid guards, exactly like the internal padding). Rows
    >= t_real read as zeros and emit exact zero gradients.

    `interpret=True` runs the kernels under the Pallas interpreter (CPU
    tests); without it a non-TPU backend is an error, not a fallback.
    """
    _require_tpu("flash_attention", interpret)
    b, h, t, d = q.shape
    hkv = k.shape[1]
    if k.shape[-1] != d:
        raise ValueError(f"q and k widths differ: {d} vs {k.shape[-1]}")
    if h % hkv or v.shape[1] != hkv:
        raise ValueError(f"q heads {h} must be a multiple of kv heads "
                         f"{k.shape[1]}/{v.shape[1]}")
    if t_real is None:
        t_real = t
    elif not 1 <= t_real <= t:
        raise ValueError(f"t_real {t_real} must be in [1, t={t}]")
    t_pad, bq, bk, bbq, bbk, mask = flash_blocks(
        t, d, mask, t_real=t_real, block_q=block_q, block_k=block_k,
        bwd_block_q=bwd_block_q, bwd_block_k=bwd_block_k)

    def prep(x, nh):
        x = x.reshape(b * nh, t, x.shape[-1])
        if t_pad != t:
            x = jnp.pad(x, ((0, 0), (0, t_pad - t), (0, 0)))
        return x

    o = _flash_with_t(prep(q, h), prep(k, hkv), prep(v, hkv), t_real,
                      bq, bk, bbq, bbk, h, hkv, interpret, mask)
    return o[:, :t, :].reshape(b, h, t, v.shape[-1])


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10, 11))
def _flash_with_t(q, k, v, t_real: int, block_q: int, block_k: int,
                  bwd_block_q: int, bwd_block_k: int, hq: int, hkv: int,
                  interpret: bool, mask: AttnMask = CAUSAL):
    o, _ = _fwd_call(q, k, v, t_real=t_real, block_q=block_q,
                     block_k=block_k, hq=hq, hkv=hkv, interpret=interpret,
                     mask=mask)
    return o


def _flash_with_t_fwd(q, k, v, t_real, block_q, block_k,
                      bwd_block_q, bwd_block_k, hq, hkv, interpret, mask):
    o, lse = _fwd_call(q, k, v, t_real=t_real,
                       block_q=block_q, block_k=block_k, hq=hq, hkv=hkv,
                       interpret=interpret, mask=mask)
    # Name the kernel outputs so a remat policy can keep them: from the
    # 'flash' rung of models/transformer.REMAT_LADDER the backward finds
    # o/lse saved; below it, it re-runs the forward kernel to rebuild them.
    # What is named, and so kept and stacked over the layers, is lse as
    # (b h, t), t on the lanes: the kernels' (b h, t, 1) pads its one lane
    # to 128 in HBM, 128 times the bytes (twice `flash_out` at a head of
    # 128). The backward puts the lane back for its kernels.
    o = checkpoint_name(o, "flash_out")
    lse = checkpoint_name(lse[..., 0], "flash_lse")
    return o, (q, k, v, o, lse)


def _flash_with_t_bwd(t_real, block_q, block_k, bwd_block_q, bwd_block_k,
                      hq, hkv, interpret, mask, res, do):
    q, k, v, o, lse = res
    return _bwd_call(q, k, v, o, lse[..., None], do, t_real=t_real,
                     block_q=bwd_block_q, block_k=bwd_block_k,
                     hq=hq, hkv=hkv, interpret=interpret, mask=mask)


_flash_with_t.defvjp(_flash_with_t_fwd, _flash_with_t_bwd)


# ------------------------------------------------- positional block kernel
#
# Building block for ring attention (ops/ring_attention.py): one
# (Q-chunk, KV-chunk) pair where causality is decided by the GLOBAL token
# positions carried around the cp ring, not by a static triangular mask.
# Returns normalized per-block output plus the block's row logsumexp so the
# caller can combine blocks with the online-softmax recurrence
#     lse' = logaddexp(lse_a, lse_b);  o' = o_a*e^(lse_a-lse') + o_b*e^(...)
# The custom VJP therefore takes BOTH cotangents (do, dlse): the extra
# dlse term enters ds as p * dlse (d lse / d s_ij = p_ij), the rest is the
# standard flash-attention-2 backward. Dead rows (no visible kv in this
# block) emit lse = MASK, so their combine weight underflows to exactly 0
# and both their cotangents arrive as zeros.

_QPOS_PAD = -(2 ** 30)  # padded q rows see nothing
_KPOS_PAD = 2 ** 30     # padded kv cols are seen by nothing


def _pos_fwd_kernel(q_ref, k_ref, v_ref, qp_ref, kp_ref, o_ref, lse_ref,
                    acc_ref, m_ref, l_ref, *, scale: float, num_kb: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, MASK)
        l_ref[:] = jnp.zeros_like(l_ref)

    s = jax.lax.dot_general(
        q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale           # (bq, bk)
    live = qp_ref[0] >= kp_ref[0]                    # (bq, 1) vs (1, bk)
    s = jnp.where(live, s, MASK)

    m_prev = m_ref[:]
    l_prev = l_ref[:]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    # clamp: for all-dead rows m_new stays MASK; exp(MASK - MASK) = 1 would
    # resurrect masked entries, so guard the subtraction
    p = jnp.where(live, jnp.exp(s - jnp.maximum(m_new, MASK / 2)), 0.0)
    alpha = jnp.exp(m_prev - jnp.maximum(m_new, MASK / 2))
    alpha = jnp.where(m_prev <= MASK / 2, 0.0, alpha)
    l_ref[:] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[:] = m_new
    pv = jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    acc_ref[:] = acc_ref[:] * alpha + pv

    @pl.when(ki == num_kb - 1)
    def _finalize():
        l = l_ref[:]
        dead = l == 0.0
        l_safe = jnp.where(dead, 1.0, l)
        o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = jnp.where(dead, MASK, m_ref[:] + jnp.log(l_safe))


def _pos_dq_kernel(q_ref, k_ref, v_ref, qp_ref, kp_ref, do_ref, lse_ref,
                   delta_ref, dlse_ref, dq_ref, dq_acc, *, scale: float,
                   num_kb: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    s = jax.lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    live = qp_ref[0] >= kp_ref[0]                    # (bq, 1) vs (1, bk)
    # dead rows carry lse = MASK; exp(MASK - MASK) = 1 would fabricate p, so
    # hard-zero masked entries (their cotangents are exact zeros anyway)
    p = jnp.where(live, jnp.exp(s - lse_ref[0]), 0.0)
    dp = jax.lax.dot_general(
        do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    ds = (p * (dp - delta_ref[0] + dlse_ref[0]) * scale).astype(q_ref.dtype)
    dq_acc[:] += jax.lax.dot_general(
        ds, k_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ki == num_kb - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _pos_dkv_kernel(q_ref, k_ref, v_ref, qp_ref, kp_ref, do_ref, lse_ref,
                    delta_ref, dlse_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                    scale: float, num_qb: int, group: int):
    gq = pl.program_id(2)

    @pl.when(gq == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    st = jax.lax.dot_general(k_ref[0], q_ref[0], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32) * scale
    live_t = kp_ref[0] <= qp_ref[0]           # (bk, 1) vs (1, bq) -> (bk, bq)
    pt = jnp.where(live_t, jnp.exp(st - jnp.transpose(lse_ref[0])), 0.0)
    dv_acc[:] += jax.lax.dot_general(
        pt.astype(do_ref.dtype), do_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dpt = jax.lax.dot_general(
        v_ref[0], do_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                  # (bk, bq)
    dst = (pt * (dpt - jnp.transpose(delta_ref[0])
                 + jnp.transpose(dlse_ref[0])) * scale).astype(q_ref.dtype)
    dk_acc[:] += jax.lax.dot_general(
        dst, q_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(gq == group * num_qb - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _pos_pad(x, t_pad, fill=0):
    t = x.shape[1]
    if t_pad == t:
        return x
    return jnp.pad(x, ((0, 0), (0, t_pad - t)) + ((0, 0),) * (x.ndim - 2),
                   constant_values=fill)


def block_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    q_pos: jax.Array, kv_pos: jax.Array,
                    block_q: int = 512, block_k: int = 512,
                    interpret: bool = False):
    """Position-masked attention over ONE (Q-chunk, KV-chunk) pair.

    q: (b, h, tq, d); k, v: (b, hkv, tk, d) (hkv may divide h — grouped
    query heads route like `flash_attention`); q_pos: (b, tq) and kv_pos:
    (b, tk) global token positions (int32). A query attends to every kv
    with kv_pos <= q_pos. Returns (o, lse): o (b, h, tq, d) in q's dtype,
    normalized within the block; lse (b, h, tq) f32, MASK for rows with no
    visible kv here. Differentiable in q/k/v through both outputs.
    `interpret` as in `flash_attention`.
    """
    _require_tpu("block_attention", interpret)
    b, h, tq, d = q.shape
    hkv, tk = k.shape[1], k.shape[2]
    if h % hkv or v.shape[1] != hkv:
        raise ValueError(f"q heads {h} must be a multiple of kv heads "
                         f"{k.shape[1]}/{v.shape[1]}")
    bq = min(block_q, max(128, 1 << (tq - 1).bit_length()))
    bk = min(block_k, max(128, 1 << (tk - 1).bit_length()))
    tq_pad, tk_pad = _round_up(tq, bq), _round_up(tk, bk)

    def prep(x, nh, t_pad):
        x = x.reshape(b * nh, x.shape[2], d)
        if t_pad != x.shape[1]:
            x = jnp.pad(x, ((0, 0), (0, t_pad - x.shape[1]), (0, 0)))
        return x

    qf = prep(q, h, tq_pad)
    kf, vf = prep(k, hkv, tk_pad), prep(v, hkv, tk_pad)
    qp = _pos_pad(q_pos.astype(jnp.int32), tq_pad, _QPOS_PAD)
    kp = _pos_pad(kv_pos.astype(jnp.int32), tk_pad, _KPOS_PAD)
    o, lse = _block_attn_vjp(qf, kf, vf, qp, kp, bq, bk, h, hkv, interpret)
    return (o[:, :tq].reshape(b, h, tq, d),
            lse[:, :tq, 0].reshape(b, h, tq))


# Position operands of the block kernels. Mosaic wants a block's last two
# dims divisible by (8, 128) or equal to the array's, which a (1, block)
# slice of a (b, t) array is not. So positions ride in twice-shaped: down
# the score tile's ROWS as a (b, t, 1) array in (1, block, 1) blocks, along
# its COLUMNS as (b, 1, t) in (1, 1, block) blocks — which also hands the
# kernels the column and the row they broadcast, with no in-kernel relayout.


def _block_calls(qf, kf, vf, qp, kp, block_q, block_k, hq, hkv):
    bh, tq_pad, d = qf.shape
    bhkv, tk_pad = kf.shape[0], kf.shape[1]
    num_qb, num_kb = tq_pad // block_q, tk_pad // block_k
    scale = 1.0 / math.sqrt(d)
    kv = lambda bb: _kv_row(bb, hq, hkv)
    posrow = lambda bb: bb // hq  # q/pos batch row of a flattened q-head row
    return dict(bh=bh, bhkv=bhkv, tq_pad=tq_pad, tk_pad=tk_pad, d=d,
                num_qb=num_qb, num_kb=num_kb, scale=scale, kv=kv,
                posrow=posrow)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _block_attn_vjp(qf, kf, vf, qp, kp, block_q, block_k, hq, hkv,
                    interpret):
    return _block_fwd_call(qf, kf, vf, qp, kp, block_q, block_k, hq, hkv,
                           interpret)


def _block_fwd_call(qf, kf, vf, qp, kp, block_q, block_k, hq, hkv,
                    interpret):
    c = _block_calls(qf, kf, vf, qp, kp, block_q, block_k, hq, hkv)
    kvr, posr = c["kv"], c["posrow"]
    o, lse = pl.pallas_call(
        functools.partial(_pos_fwd_kernel, scale=c["scale"],
                          num_kb=c["num_kb"]),
        grid=(c["bh"], c["num_qb"], c["num_kb"]),
        in_specs=[
            pl.BlockSpec((1, block_q, c["d"]), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, c["d"]),
                         lambda b, i, j: (kvr(b), j, 0)),
            pl.BlockSpec((1, block_k, c["d"]),
                         lambda b, i, j: (kvr(b), j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (posr(b), i, 0)),
            pl.BlockSpec((1, 1, block_k), lambda b, i, j: (posr(b), 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, c["d"]), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            _out_struct((c["bh"], c["tq_pad"], c["d"]), qf.dtype, qf),
            _out_struct((c["bh"], c["tq_pad"], 1), jnp.float32, qf),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, c["d"]), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_fwd_block",
    )(qf, kf, vf, qp[:, :, None], kp[:, None, :])
    return o, lse


def _block_attn_vjp_fwd(qf, kf, vf, qp, kp, block_q, block_k, hq, hkv,
                        interpret):
    o, lse = _block_fwd_call(qf, kf, vf, qp, kp, block_q, block_k, hq, hkv,
                             interpret)
    return (o, lse), (qf, kf, vf, qp, kp, o, lse)


def _block_attn_vjp_bwd(block_q, block_k, hq, hkv, interpret, res, cts):
    import numpy as np

    qf, kf, vf, qp, kp, o, lse = res
    do, dlse = cts
    c = _block_calls(qf, kf, vf, qp, kp, block_q, block_k, hq, hkv)
    kvr, posr = c["kv"], c["posrow"]
    group = hq // hkv
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                    keepdims=True)
    dlse = dlse.astype(jnp.float32)
    if dlse.ndim == 2:  # caller may drop the trailing singleton
        dlse = dlse[..., None]

    q_spec = pl.BlockSpec((1, block_q, c["d"]), lambda b, i, j: (b, i, 0))
    q1_spec = pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0))
    kv_spec = pl.BlockSpec((1, block_k, c["d"]),
                           lambda b, i, j: (kvr(b), j, 0))
    dq = pl.pallas_call(
        functools.partial(_pos_dq_kernel, scale=c["scale"],
                          num_kb=c["num_kb"]),
        grid=(c["bh"], c["num_qb"], c["num_kb"]),
        in_specs=[
            q_spec, kv_spec, kv_spec,
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (posr(b), i, 0)),
            pl.BlockSpec((1, 1, block_k), lambda b, i, j: (posr(b), 0, j)),
            q_spec, q1_spec, q1_spec, q1_spec,
        ],
        out_specs=q_spec,
        out_shape=_out_struct((c["bh"], c["tq_pad"], c["d"]), qf.dtype, qf),
        scratch_shapes=[pltpu.VMEM((block_q, c["d"]), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dq_block",
    )(qf, kf, vf, qp[:, :, None], kp[:, None, :], do, lse, delta, dlse)

    num_qb = c["num_qb"]
    qrow = lambda b, gq: _q_row(b, gq // num_qb, hq, hkv)
    qblk = lambda gq: gq % num_qb
    qg_spec = pl.BlockSpec((1, block_q, c["d"]),
                           lambda b, j, gq: (qrow(b, gq), qblk(gq), 0))
    qg1_spec = pl.BlockSpec((1, block_q, 1),
                            lambda b, j, gq: (qrow(b, gq), qblk(gq), 0))
    kvo_spec = pl.BlockSpec((1, block_k, c["d"]), lambda b, j, gq: (b, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_pos_dkv_kernel, scale=c["scale"], num_qb=num_qb,
                          group=group),
        grid=(c["bhkv"], c["num_kb"], group * num_qb),
        in_specs=[
            qg_spec, kvo_spec, kvo_spec,
            pl.BlockSpec((1, 1, block_q),
                         lambda b, j, gq: (b // hkv, 0, qblk(gq))),
            pl.BlockSpec((1, block_k, 1), lambda b, j, gq: (b // hkv, j, 0)),
            qg_spec, qg1_spec, qg1_spec, qg1_spec,
        ],
        out_specs=[kvo_spec, kvo_spec],
        out_shape=[
            _out_struct((c["bhkv"], c["tk_pad"], c["d"]), kf.dtype, qf),
            _out_struct((c["bhkv"], c["tk_pad"], c["d"]), vf.dtype, qf),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, c["d"]), jnp.float32),
                        pltpu.VMEM((block_k, c["d"]), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dkv_block",
    )(qf, kf, vf, qp[:, None, :], kp[:, :, None], do, lse, delta, dlse)

    zero_pos = lambda p: np.zeros(p.shape, jax.dtypes.float0)
    return dq, dk, dv, zero_pos(qp), zero_pos(kp)


_block_attn_vjp.defvjp(_block_attn_vjp_fwd, _block_attn_vjp_bwd)
