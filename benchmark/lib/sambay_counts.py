"""Operations, bytes and parameters of the sambay family from its shapes: the
yardstick's own counts for the metrics the `train_sambay` runner feeds
(pinned by benchmark/tests/test_sambay_counts.py).

Conventions, beside those of benchmark/lib/flops.py:

* **Parameters** (`param_counts`): what the job holds. EVERY layer is a
  mixer, a SwiGLU of three matrices `d x d_ff` and two LayerNorms (weight
  and bias). By kind the mixer is a Mamba-1 mixer (`mamba`: in `d x 2 c`,
  taps and their bias, `c x (R + 2 N)`, `R x c` and its bias, `A_log` `c x
  N`, `D`, out `c x d`), differential attention (`swa`, `full`: q, k, v, o
  with their biases, four lambda vectors and the heads' norm weight), a
  gated memory unit (`gmu`: `d x c` and `c x d`) or a cross-attention
  (`cross`: q and o only). The TIED table counts once; the final LayerNorm.
* **Model FLOPs per trained token** (`train_flops_per_token`), the numerator
  of `train_step.mfu_pct` in this family's cells: 6 x the parameters a
  token's matmuls touch (the table ONCE: its matrix is the head's matmul,
  its lookup is none; taps, norms, biases, lambdas and the scan's own few
  are no matmuls), attention at the FULL T^2 in the `full` and `cross`
  layers and at `T x min(W, T)` in the `swa` layers, as every `mfu` of this
  benchmark counts it: two maps a differential head, a map `h` wide against
  its keys and `2 h` against the pair's value (`6 x 2 x 3 h` a query head,
  key and token), plus three times the scan's forward multiply-adds.
  Recompute is not counted.
* **The selective scan** (`sscan_cost`, ONE layer over a step): the
  mathematics, whatever implements it: `T x c x N` state updates of 7
  vector operations forward (the decay's product, its exponential, the
  decay times the state, the input's outer product, the add, the read's
  product and its sum) and twice that backward, on the VECTOR unit, and the
  bytes of u, dt, y once each way and B, C. Its roofline
  (`sscan_floor_seconds`) is the larger of the bytes over the HBM peak and
  the operations over the vector unit's peak, `vector_ops_per_s`.
* **Differential attention's calls** (`diff_flash_call_cost`): a call's LIVE
  (query, key) entries (the triangle, or the window's band) of `n_head`
  maps, `2 h` FLOPs an entry against the keys and `4 h` against the value
  forward, 2.5 times that backward (five products for two), and the bytes
  of q, k, v (the value once a KEY head: the call reads it once a map), o
  once forward; those and do, dq, dk, dv backward.
* **The gated memory unit** (`gmu_cost`): its two matrices' FLOPs, forward
  and twice that backward, and the bytes of the activation, the memory, the
  gate's logits and the output.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Tuple

from benchmark.lib.flops import CallCost as Cost

KINDS = ("mamba", "swa", "full", "gmu", "cross")


class SambaYSizes(NamedTuple):
    d_model: int
    d_ff: int              # the SwiGLU's width, every layer's
    n_head: int            # query heads: two a differential head
    n_kv_head: int         # key heads: two a key-value pair
    head_dim: int          # h: a query's and a key's width; a value is 2 h
    swa_window: int
    m_inner: int           # c: the scan's channels
    m_state: int           # N
    m_rank: int            # R
    conv: int              # the convolution's taps
    layers: Tuple[Tuple[int, str], ...]     # (published index, kind) held
    vocab: int             # the slice held
    bias: bool = True      # on the attention's projections

    def count(self, kind: str) -> int:
        return sum(k == kind for _, k in self.layers)

    @property
    def n_layer(self) -> int:
        return len(self.layers)

    @property
    def n_mamba_layer(self) -> int:
        return self.count("mamba")

    @property
    def kv_dim(self) -> int:
        return self.n_kv_head * self.head_dim


def mamba_matmul_params(s: SambaYSizes) -> int:
    c = s.m_inner
    return (s.d_model * 2 * c + c * (s.m_rank + 2 * s.m_state)
            + s.m_rank * c + c * s.d_model)


def mamba_params(s: SambaYSizes) -> int:
    c = s.m_inner
    return (mamba_matmul_params(s) + c * (s.conv + 1) + c + c * s.m_state + c)


def attn_matmul_params(s: SambaYSizes, queries_only: bool = False) -> int:
    d = s.d_model
    own = d * d if queries_only else d * (d + 2 * s.kv_dim)
    return own + d * d


def attn_params(s: SambaYSizes, queries_only: bool = False) -> int:
    d = s.d_model
    biases = (d if queries_only else d + 2 * s.kv_dim) + d
    return (attn_matmul_params(s, queries_only) + s.bias * biases
            + 4 * s.head_dim + 2 * s.head_dim)


def gmu_params(s: SambaYSizes) -> int:
    return 2 * s.d_model * s.m_inner


def mlp_params(s: SambaYSizes) -> int:
    return 3 * s.d_model * s.d_ff


def param_counts(s: SambaYSizes) -> Dict[str, int]:
    norms = 4 * s.d_model
    mixer = {"mamba": mamba_params(s), "swa": attn_params(s),
             "full": attn_params(s), "gmu": gmu_params(s),
             "cross": attn_params(s, True)}
    return {"embedding": s.vocab * s.d_model, "final_norm": 2 * s.d_model,
            **{f"{kind}_layers": s.count(kind) * (mixer[kind] + mlp_params(s)
                                                 + norms) for kind in KINDS}}


def matmul_params(s: SambaYSizes) -> int:
    mixer = {"mamba": mamba_matmul_params(s), "swa": attn_matmul_params(s),
             "full": attn_matmul_params(s), "gmu": gmu_params(s),
             "cross": attn_matmul_params(s, True)}
    return (s.vocab * s.d_model
            + sum(s.count(kind) * (mixer[kind] + mlp_params(s))
                  for kind in KINDS))


SCAN_OPS_FORWARD = 7


def train_flops_per_token(s: SambaYSizes, seqlen: int) -> float:
    """Model FLOPs a trained token (module docstring)."""
    keys = (s.count("swa") * min(s.swa_window, seqlen)
            + (s.count("full") + s.count("cross")) * seqlen)
    return (6.0 * matmul_params(s)
            + 6.0 * s.n_head * keys * 3 * s.head_dim
            + 3.0 * s.n_mamba_layer * SCAN_OPS_FORWARD * s.m_inner
            * s.m_state)


def live_entries(t: int, window: "int | None") -> int:
    """Live (query, key) pairs of one map over `t` rows: the triangle, or the
    band of a window (the row's own key included)."""
    w = t if window is None else min(window, t)
    return w * (2 * t - w + 1) // 2


def vector_ops_per_s(peak) -> float:
    """The vector unit's peak in `lib/peaks.py`'s terms: four vector ALUs
    of 8 x 128 lanes a clock beside four matrix units of 128 x 128
    multiply-adds (two operations each) a clock, so `flops_per_s / 32`
    float32 operations a second (6.2e12 on a v5e)."""
    return peak.flops_per_s * (4 * 8 * 128) / (4 * 128 * 128 * 2)


def sscan_cost(batch: int, seqlen: int, s: SambaYSizes, itemsize: int
               ) -> Cost:
    """ONE Mamba layer's scan over a step, forward and backward: `flops` are
    VECTOR operations (module docstring), bytes u in the compute dtype, dt
    and y float32, forward; those, dy, du and ddt backward; B and C both
    ways."""
    rows = batch * seqlen
    ops = 3.0 * SCAN_OPS_FORWARD * rows * s.m_inner * s.m_state
    wide = rows * s.m_inner
    moved = (wide * (itemsize + 8) + wide * (2 * itemsize + 16)
             + 6 * rows * s.m_state * 4)
    return Cost(flops=ops, bytes=float(moved))


def sscan_floor_seconds(cost: Cost, peak) -> float:
    """The least time a layer's scan can take: the larger of its HBM floor
    and its vector-unit floor."""
    return max(cost.bytes / peak.hbm_bytes_per_s,
               cost.flops / vector_ops_per_s(peak))


def diff_flash_call_cost(batch: int, seqlen: int, s: SambaYSizes,
                         itemsize: int, backward: bool,
                         window: "int | None") -> Cost:
    """One attention call of a differential layer (module docstring)."""
    h = s.head_dim
    live = batch * s.n_head * live_entries(seqlen, window)
    forward = live * (2 * h + 4 * h)
    rows = batch * seqlen
    q, k, v, o = (rows * s.n_head * h, rows * s.n_kv_head * h,
                  rows * s.n_kv_head * 2 * h, rows * s.n_head * 2 * h)
    moved = (q + k + v + o) * itemsize
    if backward:
        return Cost(flops=2.5 * forward,
                    bytes=float(2 * moved + rows * s.n_head * 4))
    return Cost(flops=float(forward), bytes=float(moved))


def gmu_cost(batch: int, seqlen: int, s: SambaYSizes, itemsize: int) -> Cost:
    """ONE gated memory unit over a step, forward and backward."""
    rows = batch * seqlen
    return Cost(flops=3.0 * 2 * rows * gmu_params(s),
                bytes=float(3 * rows * (2 * s.d_model + 3 * s.m_inner)
                            * itemsize + 3 * gmu_params(s) * itemsize))
