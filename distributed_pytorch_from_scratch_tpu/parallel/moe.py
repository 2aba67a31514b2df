"""Mixture-of-Experts FFN with expert parallelism (EP), TPU-native.

No reference counterpart: the reference's FFN is dense SwiGLU and it has no
router or expert sharding of any kind (SURVEY §2.4 "EP ❌",
`/root/reference/models/model.py:81-95`). This module is the framework
extension that turns the dense SwiGLU sublayer into a top-k routed MoE, with

* **Expert parallelism over the mesh axis 'ep'**: each ep shard owns
  `num_experts / ep` experts (leading expert dim of every expert weight is
  sharded with `P('ep', ...)`). Tokens are exchanged with ONE
  `lax.all_to_all` before and one after expert compute — the GShard/Switch
  dispatch pattern, riding ICI like every other collective here.

* **Tensor parallelism inside each expert over 'tp'**: gate/up are
  column-sharded, down is row-sharded — the same Megatron pattern as the
  dense FFN (`parallel/linear.py`), expressed as batched-over-experts
  einsums so the MXU sees one big (E_local, tokens, d) x (E_local, d, f)
  contraction instead of a Python loop over experts.

* **Static shapes throughout** (XLA requirement): routing uses the
  capacity-factor formulation — each expert accepts at most C tokens per ep
  shard; overflow tokens fall through the residual connection (standard
  Switch behaviour). With a generous `capacity_factor` nothing drops and
  the layer is exactly `sum_k gate_k * expert_k(x)`, which the equivalence
  tests exploit (routing is sharding-invariant in expectation AND in value
  when no token drops).

* **Dispatch/combine as static-shape scatter/gather**: each (token, k)
  routing resolves to a flat slot id `e * C + c`; dispatch is one
  scatter-add into the (E*C, d) expert buffer and combine is one gather
  back, weighted by the top-k gate values. Memory is O(S*k + E*C*d) —
  the earlier dense one-hot formulation built (S, E, C) masks, which is
  O(cf*k*S^2) and could not fit HBM at bench scale (ADVICE r2: ~4.1e9
  mask elements at b32 x t1000 x E8). Each expert slot receives at most
  one token (slot positions are a per-expert cumsum), so the scatter has
  no duplicate-index accumulation and stays bit-deterministic; dropped
  tokens route to one trash row that is sliced off. The transpose
  (backward) of scatter-add is a gather and vice versa — no sorts, no
  dynamic shapes.

Auxiliary losses follow Switch/ST-MoE: load-balance loss
`E * sum_e(frac_tokens_e * mean_prob_e)` and router z-loss
`mean(logsumexp(router_logits)^2)`. `apply` returns LOCAL sums; the model's
loss_shard psums them over the batch axes so the totals are independent of
how tokens are sharded (tests assert this).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..ops.collectives import copy_to, reduce_from
from ..runtime.prng import fold

Params = Dict[str, Any]


@dataclass(frozen=True)
class MoEFFN:
    """Top-k routed SwiGLU experts; drop-in for the dense FFN sublayer."""

    d: int                 # model dim
    f: int                 # per-expert hidden dim
    num_experts: int
    top_k: int = 2
    # Per-expert slots per ep shard: C = ceil(capacity_factor * S * k / E)
    # where S = local tokens. >= E/k guarantees zero drops for any routing;
    # 2.0 is a training-friendly default with rare drops.
    capacity_factor: float = 2.0
    # Renormalise the top-k gate weights to sum to 1 (Mixtral style). False
    # keeps raw softmax mass (Switch style).
    renormalize: bool = True
    ep_size: int = 1
    tp_size: int = 1
    ep_axis: str = "ep"
    tp_axis: str = "tp"

    def __post_init__(self):
        if self.num_experts % self.ep_size != 0:
            raise ValueError(f"num_experts {self.num_experts} not divisible "
                             f"by ep_size {self.ep_size}")
        if self.f % self.tp_size != 0:
            raise ValueError(f"expert ffn dim {self.f} not divisible by "
                             f"tp_size {self.tp_size}")
        if not (1 <= self.top_k <= self.num_experts):
            raise ValueError(f"top_k {self.top_k} out of range for "
                             f"{self.num_experts} experts")

    # ---- init / specs ----

    def init(self, key: jax.Array) -> Params:
        E, d, f = self.num_experts, self.d, self.f

        def expert_w(k, idim, odim):
            bound = 1.0 / math.sqrt(idim)
            return jax.random.uniform(k, (E, idim, odim), jnp.float32,
                                      -bound, bound)

        return {
            # router kept tiny + f32; zero-init (standard: uniform routing at
            # step 0, so early training matches the dense layer's scale)
            "router": jnp.zeros((d, E), jnp.float32),
            "gate": expert_w(fold(key, "gate"), d, f),
            "up": expert_w(fold(key, "up"), d, f),
            "down": expert_w(fold(key, "down"), f, d),
        }

    def specs(self) -> Params:
        ep, tp = self.ep_axis, self.tp_axis
        return {
            "router": P(None, None),
            "gate": P(ep, None, tp),
            "up": P(ep, None, tp),
            "down": P(ep, tp, None),
        }

    # ---- routing (static-shape, per ep shard) ----

    def _capacity(self, tokens: int) -> int:
        c = math.ceil(self.capacity_factor * tokens * self.top_k
                      / self.num_experts)
        return max(4, c)

    def _route(self, logits: jax.Array) -> Tuple[jax.Array, jax.Array, Params]:
        """(S, E) router logits -> flat slot ids (S, k) into the (E*C) expert
        buffer (E*C = trash for dropped tokens), combine weights (S, k), aux
        local sums."""
        S, E = logits.shape
        C = self._capacity(S)
        probs = jax.nn.softmax(logits, axis=-1)            # (S, E) f32
        topv, topi = lax.top_k(probs, self.top_k)          # (S, k)
        if self.renormalize:
            topv = topv / jnp.sum(topv, axis=-1, keepdims=True)

        # Position of each (slot, token) routing within its expert. Slot-major
        # priority (all slot-0 picks beat slot-1 picks), token order within a
        # slot — the Switch convention.
        onehot = jax.nn.one_hot(topi, E, dtype=jnp.int32)  # (S, k, E)
        flat = onehot.transpose(1, 0, 2).reshape(self.top_k * S, E)
        pos_flat = jnp.cumsum(flat, axis=0) - flat          # (k*S, E)
        pos = (pos_flat.reshape(self.top_k, S, E)
               .transpose(1, 0, 2))                         # (S, k, E)
        pos_tok = jnp.sum(pos * onehot, axis=-1)            # (S, k)
        keep = (pos_tok < C) & (topv > 0)                   # (S, k)

        # Flat slot id per (token, k): expert-major, trash slot E*C for drops.
        slots = jnp.where(keep, topi * C + pos_tok, E * C)  # (S, k)
        weights = jnp.where(keep, topv, 0.0)                # (S, k)

        aux = {
            # routed (pre-drop) assignment counts, the Switch f_e numerator
            "tokens_per_expert": jnp.sum(onehot, axis=(0, 1)).astype(jnp.float32),
            "prob_sum": jnp.sum(probs, axis=0),             # (E,)
            "z_sum": jnp.sum(jax.nn.logsumexp(logits, axis=-1) ** 2),
            "tokens": jnp.asarray(S, jnp.float32),
            "dropped": jnp.sum(1.0 - keep.astype(jnp.float32)),
        }
        return slots, weights, aux

    # ---- forward (per-shard, inside shard_map) ----

    def apply(self, params: Params, x: jax.Array,
              compute_dtype: jnp.dtype = jnp.float32
              ) -> Tuple[jax.Array, Params]:
        """x (b, t, d) -> (y (b, t, d), aux local sums).

        Must run inside shard_map over ('ep', 'tp'); x is the ep shard's
        local tokens, replicated over tp.
        """
        b, t, d = x.shape
        S = b * t
        xf = x.reshape(S, d)

        # Router in f32 for a stable softmax; stop-gradient-free (the router
        # trains through the combine weights).
        logits = xf.astype(jnp.float32) @ params["router"]
        slots, weights, aux = self._route(logits)
        E, C = self.num_experts, self._capacity(S)

        xd = xf.astype(compute_dtype)
        # Dispatch: scatter each kept (token, k) copy into its expert slot.
        # Every slot receives at most one token, plus the trash row E*C that
        # absorbs drops and is sliced off — deterministic, O(S*k*d) work.
        xk = jnp.broadcast_to(xd[:, None, :], (S, self.top_k, d))
        expert_in = (jnp.zeros((E * C + 1, d), compute_dtype)
                     .at[slots.reshape(-1)]
                     .add(xk.reshape(S * self.top_k, d), mode="drop")
                     [: E * C].reshape(E, C, d))

        if self.ep_size > 1:
            # (E, C, d) -> (E/ep, ep*C, d): each ep shard receives its own
            # experts' slots from every peer.
            expert_in = lax.all_to_all(expert_in, self.ep_axis,
                                       split_axis=0, concat_axis=1,
                                       tiled=True)

        # Batched Megatron FFN over the local experts: gate/up column-sharded
        # over tp (copy_to installs the psum of input grads), down
        # row-sharded (reduce_from sums the partial products).
        h_in = copy_to(expert_in, self.tp_axis)
        gate = jnp.einsum("ecd,edf->ecf", h_in,
                          params["gate"].astype(compute_dtype))
        up = jnp.einsum("ecd,edf->ecf", h_in,
                        params["up"].astype(compute_dtype))
        h = jax.nn.silu(gate) * up
        out = jnp.einsum("ecf,efd->ecd", h,
                         params["down"].astype(compute_dtype))
        out = reduce_from(out, self.tp_axis)

        if self.ep_size > 1:
            out = lax.all_to_all(out, self.ep_axis,
                                 split_axis=1, concat_axis=0, tiled=True)

        # Combine: gather each (token, k)'s expert output back (trash row ->
        # zeros) and sum weighted by the top-k gate values.
        out_flat = jnp.concatenate(
            [out.reshape(E * C, d), jnp.zeros((1, d), out.dtype)])
        picked = out_flat[slots.reshape(-1)].reshape(S, self.top_k, d)
        y = jnp.sum(picked * weights[..., None].astype(compute_dtype), axis=1)
        return y.reshape(b, t, d), aux


# ---- the sorted dispatch's row movers (SharedRoutedFFN) ----
#
# A chunk of the sorted pairs is a partial permutation of the (token,
# choice) pairs: sorted row r of the chunk holds token `tok[r]`, and pair
# (s, j) sits in row `idx[s, j]` of it, or `idx[s, j] == M` (one past the
# chunk: it reads as a zero row) where its expert is absent or its row lies
# in another chunk. So rows can move by GATHERS in both directions, and
# each mover is the other's transpose and says so, where autodiff would
# make the gather's a row scatter-add. The chunk's first `n` rows are the
# held pairs'; the rows past them are PADDING, which the movers own:
# `take_rows` makes them zeros, `sum_rows` does not read them, and so no
# cotangent of a padding row is read either (the grouped products'
# transposes write whatever they like there).
#
# What a 4 KB row costs on a v5e (bf16 x 2048; `scripts/
# tune_moe_dispatch.py` alone on the chip at the four expert cells' shapes,
# PERF.md section 6, PR 42): XLA:TPU's row scatter-add, which has to allow
# for rows that collide, 75 - 82 ns a row of the M it adds, whatever the
# shape; a gathered row of the S k that `sum_rows` reads from the chunk,
# the sum over k included, 42 - 50 ns. The gathers win where the pairs are
# under 1.6 times the chunk's rows (a held share of an eighth or more:
# 3.28 ms against 5.15 a call at S k = M = 65,536, 6.25 against 7.36 at
# 131,072 over 98,304) and lose at a sixteenth (6.31 against 4.04 at
# 131,072 over 49,152, fifteen of sixteen gathered rows the zero row), so
# `SharedRoutedFFN.apply` asks the two costs, on its static shapes, a layer
# at a time, and keeps the scatter-add (its text of before) where they say.
ROW_GATHER_NS = 48
ROW_SCATTER_NS = 78


def _held(tok: jax.Array, n: jax.Array) -> jax.Array:
    return (jnp.arange(tok.shape[0]) < n)[:, None]


@jax.custom_vjp
def take_rows(x: jax.Array, tok: jax.Array, idx: jax.Array, n: jax.Array
              ) -> jax.Array:
    """(S, d) tokens -> the chunk's (M, d) rows: `x[tok]` in the first n,
    SELECTED zeros past them."""
    with jax.named_scope("moe_route"), jax.named_scope("take_rows"):
        return jnp.where(_held(tok, n), jnp.take(x, tok, axis=0), 0)


@jax.custom_vjp
def sum_rows(y: jax.Array, r: jax.Array, tok: jax.Array, idx: jax.Array,
             n: jax.Array) -> jax.Array:
    """(S, d) sums and the chunk's (M, d) rows -> `y[s] + sum_j r[idx[s,
    j]]`: k row gathers a token, summed in float32 and cast once."""
    with jax.named_scope("moe_route"), jax.named_scope("sum_rows"):
        (S, k), M = idx.shape, r.shape[0]
        # The columns of `idx` are walked a gather of at most M - 2 S rows
        # at a time: the gathered rows and the sum, in and out, then take
        # what the chunk's rows take, which is what the scatter-add's own
        # sorted copy of its updates took (the compiler's plan of this
        # layer alone at S k = 131,072 over M = 98,304: 3,899 MB against
        # the scatter-add's 3,897, where six columns at once read 4,032
        # and all eight 4,065). A gather's columns are summed in float32
        # as adds of (S, d) slabs: ONE element-wise fusion reads the
        # gathered rows once (as a `reduce` over a float32 copy of them it
        # ran three passes in the step where it ran one alone, PERF.md
        # section 6, PR 42). The barrier keeps a gather behind the sum
        # before it, which it hands on in y's dtype.
        at_once = max(1, M // S - 2)
        for a in range(0, k, at_once):
            cols = idx.T[a:a + at_once]
            if a:
                y, cols = lax.optimization_barrier((y, cols))
            picked = jnp.take(r, cols.reshape(-1), axis=0,
                              mode="clip").reshape(-1, S, r.shape[1])
            acc = y.astype(jnp.float32)
            for j in range(cols.shape[0]):
                acc = acc + jnp.where((cols[j] < M)[:, None], picked[j], 0)
            y = acc.astype(y.dtype)
        return y


def _take_rows_bwd(res, g):
    tok, idx, n = res
    zeros = jnp.zeros((idx.shape[0], g.shape[1]), g.dtype)
    return sum_rows(zeros, g, tok, idx, n), None, None, None


take_rows.defvjp(
    lambda x, tok, idx, n: (take_rows(x, tok, idx, n), (tok, idx, n)),
    _take_rows_bwd)
sum_rows.defvjp(
    lambda y, r, tok, idx, n: (sum_rows(y, r, tok, idx, n), (tok, idx, n)),
    lambda res, g: (g, take_rows(g, *res), None, None, None))


# ---- the sorted dispatch's index work (SharedRoutedFFN) ----
#
# Beside the rows, a layer moves four bytes a (token, choice) pair: the
# chosen experts' scores, the weights into sorted order, the counts of the
# held and of all routed experts. Written as `take_along_axis`, `w[order]`
# and `bincount` each is an XLA scalar gather or scatter-add, which the
# chip walks an element at a time: 8 - 10 ns an element in the step, what a
# 4 KB row costs to move (PERF.md section 6, PR 43). So none is: a value
# picked by an index is a compare against an iota and a reduce over a
# one-hot that is never stored (one term is not zero: exact), a count is a
# column sum of such a one-hot, and a value that follows the sort rides it
# as an operand. Autodiff transposes the first into the same compare
# (a select of the cotangent, summed over the choices); the sort says its
# own transpose below.


def pick_scores(s: jax.Array, chosen: jax.Array) -> jax.Array:
    """(S, E) scores, (S, k) chosen -> `take_along_axis(s, chosen, -1)`."""
    with jax.named_scope("index"):
        hot = chosen[..., None] == jnp.arange(s.shape[-1], dtype=chosen.dtype)
        return jnp.sum(jnp.where(hot, s[:, None, :], 0), axis=-1)


def count_keys(key: jax.Array, length: int) -> jax.Array:
    """(N,) keys in [0, length) -> `bincount(key, length=length)`, int32."""
    hot = key[:, None] == jnp.arange(length, dtype=key.dtype)
    return jnp.sum(hot, axis=0, dtype=jnp.int32)


@jax.custom_vjp
def sort_pairs(key: jax.Array, w: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(N,) keys and weights -> `order = argsort(key, stable=True)` and
    `w[order]`: ONE sort with the pair's number and its weight as operands.
    The cotangent of `w` goes back by a sort on `order` (a permutation's
    inverse is the sort of it), where autodiff would gather through the
    sort and transpose that into a scalar scatter-add."""
    with jax.named_scope("moe_route"), jax.named_scope("index"):
        _, order, w_sorted = lax.sort(
            (key, lax.iota(jnp.int32, key.shape[0]), w), num_keys=1,
            is_stable=True)
        return order, w_sorted


def _sort_pairs_fwd(key, w):
    order, w_sorted = sort_pairs(key, w)
    return (order, w_sorted), order


def _sort_pairs_bwd(order, g):
    with jax.named_scope("moe_route"), jax.named_scope("index"):
        return None, lax.sort((order, g[1]), num_keys=1)[1]


sort_pairs.defvjp(_sort_pairs_fwd, _sort_pairs_bwd)


# A chunk of `SharedRoutedFFN`'s sorted pairs holds this many times the
# job's mean share of them (its rows move by the movers above: gathers both
# ways at a share of an eighth or more, `ROW_GATHER_NS` / `ROW_SCATTER_NS`).
# One reading set it, not a law: Zipf ids through
# a freshly initialised router on a v5e, where no step's held rows passed
# 5.4 times the mean share and a first chunk of 4 shares was crossed in a
# tenth of one run's steps in twelve (PERF.md section 6, PR 33). It is
# MEMORY (the chunk's rows in and out, and the movers' and the route's
# passes over them) and the unit the `cond` skips by; the grouped products'
# time follows the held rows, not the chunk: see the class docstring.
CHUNK_SHARES = 6


@dataclass(frozen=True)
class SharedRoutedFFN:
    """A router over `num_experts` routed SwiGLU experts, of which this job
    HOLDS `held` (experts [offset, offset + held)), plus shared experts
    every token takes: the DeepSeek-V3 FFN, as one chip of an
    expert-parallel deployment computes it between two all-to-alls.

    Two facts a family states (fields, below the DeepSeek-V3 defaults):
    `score` "softmax" scores by a softmax over all routed experts and has
    no selection bias (no `bias` leaf); `shared_gate` multiplies the shared
    expert's output by `sigmoid(x w_sg)`, one scalar a token (the leaf
    `shared["gate_score"]`, d -> 1): Qwen3-Next's expert layer.

    Routing (float32): `s = sigmoid(x W_r)` over all routed experts; the
    `top_k` largest of `s + bias` are chosen (`bias` is the selection bias
    of auxiliary-loss-free balancing: a leaf no gradient reaches, moved
    after every optimizer step by `training/optim.router_bias_step` from
    this layer's `routed` counter where the family's configuration
    publishes the rule's speed, `DecoderStack.router_bias_speed`, and left
    at zero where it does not); the weights are
    `s[chosen]`, normalised over ALL chosen experts, held or not, times
    `scaling`. The layer adds `w_e E_e(x)` for the chosen experts it holds
    and the shared expert; what an absent expert would have added is left
    out (with `held == num_experts` nothing is). No value here is looked up
    by an index: `s[chosen]` is a compare of `chosen` against an iota and a
    sum over the one-hot (`pick_scores`), the held and the routed experts'
    counts are column sums of such one-hots (`count_keys`), and the
    weights reach sorted order as an operand of the sort (`sort_pairs`):
    a scalar gather or scatter-add costs the chip what a 4 KB row costs
    (above `pick_scores`).

    Dispatch is sorted and grouped, with no capacity and NO DROP: the
    (token, choice) pairs are sorted by held expert (absent ones last) and
    the held experts' rows go through grouped matrix products
    (`lax.ragged_dot`: XLA:TPU makes it a grouped-matmul kernel whose grid
    follows the group sizes). The sort is a permutation and the layer
    keeps both directions of it: `order` (the pair of a sorted row) and
    its inverse `pos` (the sorted row of a pair, from a prefix sum over a
    one-hot of the keys). Rows go in by a gather (`take_rows`: `x[tok]`,
    zeros in the padding rows) and come back by `sum_rows`: k row gathers
    a token through `pos`, summed in float32, where the pairs are under
    1.6 times the chunk's rows (a held share of an eighth or more), the
    row scatter-add `y.at[tok].add` where they are more (a sixteenth);
    each mover is the other's transpose by `jax.custom_vjp`, so the
    backward moves rows the same way and reads no padding row's cotangent
    (`ROW_GATHER_NS` / `ROW_SCATTER_NS`, above `CHUNK_SHARES`: one rule
    on static shapes, measured). The sorted pairs are walked in chunks
    (`chunk_rows`) under one `lax.scan`; a chunk past the last held row is
    skipped by a `lax.cond` (where there are several: a chunk of ALL the
    pairs runs without one), so memory follows the chunk, while every pair
    that exists is computed whatever the routing (tests force all tokens
    onto a few experts). **The products follow the rows**: each held
    expert's group ends at its own last row, the rows of a live chunk past
    its last held pair belong to NO group, and XLA:TPU's grouped kernel
    walks the groups it is given, so the products' time is the held rows'
    (at a held share of an eighth a chunk is 98,304 rows for some 20,000
    held: handed whole, four rows in five were zeros and the products ran
    at 6 - 8% of their roofline, PERF.md section 6, PR 47). The chunk is
    MEMORY and the unit the `cond` skips by. What that makes load-bearing:
    a row no group holds comes back from a product AND from its transposes
    as whatever the buffer held, so every such row is selected, never
    multiplied, on both sides of the products (`live`, below). The step's
    time now follows the routing, seed by seed, where nothing balances the
    router (PR 33 read 1.2 - 2.3% between seeds).

    Tensor parallelism: every expert's gate/up are column-sharded and its
    down row-sharded over `tp_axis`, like the dense FFN; the router and the
    bias are replicated. There is no expert axis here: a job that holds
    several shares runs several of these (ROADMAP, expert parallelism).
    """

    d: int
    f: int                       # per-expert hidden width
    num_experts: int             # routed experts the router scores
    top_k: int
    held: "int | None" = None    # None: all
    offset: int = 0
    n_shared: int = 1
    scaling: float = 1.0
    tp_size: int = 1
    tp_axis: str = "tp"
    score: str = "sigmoid"       # or "softmax" (class docstring)
    shared_gate: bool = False

    def __post_init__(self):
        held = self.num_held
        if self.score not in ("sigmoid", "softmax"):
            raise ValueError(f"score must be 'sigmoid' or 'softmax', got "
                             f"{self.score!r}")
        if self.shared_gate and not self.n_shared:
            raise ValueError("shared_gate needs a shared expert")
        if not (0 <= self.offset and self.offset + held <= self.num_experts):
            raise ValueError(
                f"held experts [{self.offset}, {self.offset + held}) are not "
                f"among the {self.num_experts} routed experts")
        if not (1 <= self.top_k <= self.num_experts):
            raise ValueError(f"top_k {self.top_k} out of range for "
                             f"{self.num_experts} experts")
        if self.f % self.tp_size:
            raise ValueError(f"expert width {self.f} not divisible by "
                             f"tp_size {self.tp_size}")

    @property
    def num_held(self) -> int:
        return self.num_experts if self.held is None else self.held

    # ---- init / specs ----

    def init(self, key: jax.Array) -> Params:
        d, f, H = self.d, self.f, self.num_held

        def w(k, shape, idim):
            bound = 1.0 / math.sqrt(idim)
            return jax.random.uniform(k, shape, jnp.float32, -bound, bound)

        p = {
            # a RANDOM router (the zero one of MoEFFN would send every
            # token to the first top_k experts: sigmoid ties at 0.5)
            "router": w(fold(key, "router"), (d, self.num_experts), d),
            # the selection bias: zeros, which only the rule of
            # training/optim.router_bias_step moves (never a gradient)
            "bias": jnp.zeros((self.num_experts,), jnp.float32),
            "gate": w(fold(key, "gate"), (H, d, f), d),
            "up": w(fold(key, "up"), (H, d, f), d),
            "down": w(fold(key, "down"), (H, f, d), f),
        }
        if self.score == "softmax":
            del p["bias"]
        if self.n_shared:
            fs = self.n_shared * f
            p["shared"] = {"gate": w(fold(key, "shared_gate"), (d, fs), d),
                           "up": w(fold(key, "shared_up"), (d, fs), d),
                           "down": w(fold(key, "shared_down"), (fs, d), fs)}
            if self.shared_gate:
                p["shared"]["gate_score"] = w(fold(key, "shared_gate_score"),
                                              (d, 1), d)
        return p

    def specs(self) -> Params:
        tp = self.tp_axis
        s = {"router": P(None, None), "bias": P(None),
             "gate": P(None, None, tp), "up": P(None, None, tp),
             "down": P(None, tp, None)}
        if self.n_shared:
            s["shared"] = {"gate": P(None, tp), "up": P(None, tp),
                           "down": P(tp, None)}
            if self.shared_gate:
                s["shared"]["gate_score"] = P(None, None)
        if self.score == "softmax":
            del s["bias"]
        return s

    # ---- routing ----

    def route(self, params: Params, xf: jax.Array
              ) -> Tuple[jax.Array, jax.Array]:
        """(S, d) tokens -> chosen experts (S, k) int32 and their combine
        weights (S, k) float32. The router's product runs in float32 at
        precision "highest": a bf16 pass moves scores by 2^-9, which flips
        a top-k choice wherever two experts sit that close."""
        logits = jnp.dot(xf.astype(jnp.float32), params["router"],
                         precision=lax.Precision.HIGHEST)
        if self.score == "softmax":
            s = jax.nn.softmax(logits, axis=-1)
            _, chosen = lax.top_k(s, self.top_k)
        else:
            s = jax.nn.sigmoid(logits)
            _, chosen = lax.top_k(s + lax.stop_gradient(params["bias"]),
                                  self.top_k)
        w = pick_scores(s, chosen)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * self.scaling
        return chosen, w

    def index(self, chosen: jax.Array, w: jax.Array, inverse: bool):
        """The sorted dispatch's index work over the (S, k) pairs, with no
        scalar gather or scatter (above `sort_pairs`): `order`, the pair of
        a sorted row, pairs sorted by held expert and absent experts last;
        `w_sorted`, the weights in that order; `ends` (held,), the sorted
        row each held expert's pairs end at; `pos` (S, k), the sorted row
        of a pair (`order`'s inverse; None unless `inverse`); `routed`
        (num_experts,) int32, the pairs each routed expert was chosen
        for."""
        S, k = chosen.shape
        H = self.num_held
        local = chosen - self.offset
        here = (local >= 0) & (local < H)
        key = jnp.where(here, local, H).reshape(-1)             # (S*k,)
        order, w_sorted = sort_pairs(key, w.reshape(-1))
        with jax.named_scope("index"):
            pos = None
            if inverse:
                # its expert's first row plus the earlier pairs of it: a
                # prefix sum over the one-hot whose column sums `ends` are
                hot = jax.nn.one_hot(key, H + 1, dtype=jnp.int32)
                ends = jnp.cumsum(jnp.sum(hot, axis=0)[:H])
                first = jnp.concatenate([jnp.zeros((1,), ends.dtype), ends])
                pos = jnp.sum((jnp.cumsum(hot, axis=0) - hot + first) * hot,
                              axis=1).reshape(S, k)
            else:
                ends = jnp.cumsum(count_keys(key, H + 1)[:H])
            routed = count_keys(chosen.reshape(-1), self.num_experts)
        return order, w_sorted, ends, pos, routed

    @property
    def chunk_share(self) -> float:
        """The part of the (token, choice) pairs one chunk holds:
        `CHUNK_SHARES` times this job's mean share of them, at most all
        (the family sizes the dispatch's buffers from it for
        `training/memory.py`)."""
        return min(1.0, CHUNK_SHARES * self.num_held / self.num_experts)

    def chunk_rows(self, pairs: int) -> int:
        """Rows a chunk of `pairs` sorted pairs holds: `chunk_share` of
        them, up to a multiple of 512 (the grouped kernel's row tile); all
        of them where that is more than there are. The last chunk may run
        past the pairs (`apply` pads)."""
        want = max(512, -(-int(self.chunk_share * pairs) // 512) * 512)
        return pairs if want >= pairs else want

    # ---- forward (per-shard, inside shard_map) ----

    def apply(self, params: Params, x: jax.Array,
              compute_dtype: jnp.dtype = jnp.float32
              ) -> Tuple[jax.Array, Params]:
        """x (b, t, d), replicated over tp -> (y (b, t, d), counters):
        `routed` (num_experts,) the pairs each routed expert was chosen
        for, `rows_here` the pairs whose expert is held, `rows_computed`
        the rows of the groups the grouped products were handed, over the
        chunks (the held rows: the counter says so of the program that
        ran), all float32 and local to this shard."""
        b, t, d = x.shape
        S, k = b * t, self.top_k
        xf = x.reshape(S, d)
        xd = copy_to(xf.astype(compute_dtype), self.tp_axis)

        M = self.chunk_rows(S * k)
        # rows move by gathers both ways or by the row scatter-add: the
        # movers' two costs decide, on static shapes, a layer at a time
        gathers = S * k * ROW_GATHER_NS <= M * ROW_SCATTER_NS
        with jax.named_scope("moe_route"):
            chosen, w = self.route(params, xf)
            order, w_sorted, ends, pos, routed = self.index(
                chosen, w, inverse=gathers)
            rows_here = ends[-1]
            token = order // k
            counters = {"routed": routed.astype(jnp.float32),
                        "rows_here": rows_here.astype(jnp.float32)}

        chunks = -(-S * k // M)
        if chunks * M > S * k:        # the last chunk runs past the pairs
            token = jnp.pad(token, (0, chunks * M - S * k))
            w_sorted = jnp.pad(w_sorted, (0, chunks * M - S * k))
        f = params["gate"].shape[-1]                  # local expert width
        # gate and up as one grouped product: one pass over the rows
        gate_up = jnp.concatenate([params["gate"], params["up"]],
                                  axis=-1).astype(compute_dtype)
        down = params["down"].astype(compute_dtype)

        def chunk(y, c):
            lo = c * M
            with jax.named_scope("moe_route"):
                # rows of each held expert inside [lo, lo + M): every
                # group ends at its expert's own last row, so the groups
                # cover the chunk's held rows and nothing more
                sizes = jnp.diff(jnp.clip(ends - lo, 0, M),
                                 prepend=0).astype(jnp.int32)

            def live(y):
                with jax.named_scope("moe_route"):
                    tok = lax.dynamic_slice_in_dim(token, lo, M)
                    wc = lax.dynamic_slice_in_dim(w_sorted, lo, M)
                    valid = ((lo + jnp.arange(M)) < rows_here)[:, None]
                    # The grouped kernels write the rows of their groups
                    # and NOTHING ELSE: a row no group holds comes back as
                    # whatever the buffer held, from the forward products
                    # and from their transposes alike (a 5,000-fold
                    # gradient norm on the chip, PR 33; the CPU lowering
                    # zero-fills). The rows past the held pairs have NO
                    # group, so they are SELECTED away, never multiplied:
                    # going in and on the cotangent side by the movers or
                    # by `valid`, coming out by `valid` (the weights'
                    # cotangent reads every row of `out`). Between the two
                    # products they are garbage that nothing reads: a
                    # product and its transposes read their groups' rows.
                    if gathers:
                        # a held pair whose row is in this chunk; every
                        # other reads the zero row
                        n, at = rows_here - lo, pos - lo
                        idx = jnp.where(
                            (at >= 0) & (at < jnp.minimum(n, M)), at, M)
                        rows = take_rows(xd, tok, idx, n)
                    else:
                        rows = jnp.where(valid, jnp.take(xd, tok, axis=0), 0)
                with jax.named_scope("moe_experts"):
                    gu = lax.ragged_dot(rows, gate_up, sizes)
                    out = lax.ragged_dot(
                        jax.nn.silu(gu[:, :f]) * gu[:, f:], down, sizes)
                with jax.named_scope("moe_route"):
                    # select BEFORE the weights multiply: a weight's
                    # cotangent is the row itself
                    out = (jnp.where(valid, out, 0)
                           * wc[:, None].astype(out.dtype))
                    out = out.astype(y.dtype)
                    if gathers:
                        return sum_rows(y, out, tok, idx, n)
                    return y.at[tok].add(out)

            if chunks == 1:
                # the one chunk is ALL the pairs (a held share of a sixth
                # or more): there is no later chunk to skip to, and a layer
                # whose held experts got nothing this step runs its
                # products over zero groups (a `cond` around the one chunk
                # cost 25 ms a step and 1.0 GiB, PERF.md section 6, PR 39)
                return live(y), jnp.sum(sizes)
            return (lax.cond(lo < rows_here, live, lambda y: y, y),
                    jnp.sum(sizes))

        # the carry varies over what the rows vary over (batch axes and tp)
        vma = tuple(jax.typeof(xd).vma)
        y = jnp.zeros((S, d), compute_dtype)
        y = copy_to(y, vma) if vma else y
        y, computed = lax.scan(jax.checkpoint(chunk), y,
                               jnp.arange(chunks, dtype=jnp.int32))
        counters["rows_computed"] = jnp.sum(computed).astype(jnp.float32)

        if self.n_shared:
            with jax.named_scope("moe_shared"):
                sp = params["shared"]
                g = xd @ sp["gate"].astype(compute_dtype)
                u = xd @ sp["up"].astype(compute_dtype)
                out = (jax.nn.silu(g) * u) @ sp["down"].astype(compute_dtype)
                if self.shared_gate:
                    # the gate's product reads whole tokens on every tp
                    # rank and scales this rank's partial sum
                    gate = jax.nn.sigmoid(
                        xd @ sp["gate_score"].astype(compute_dtype))
                    out = out * gate
                y = y + out
        y = reduce_from(y, self.tp_axis)
        return y.reshape(b, t, d), counters


def aux_zeros(num_experts: int) -> Params:
    """Zero aux sums with the same structure `MoEFFN.apply` returns — used
    as the scan unit for dense layers so MoE and dense bodies scan alike."""
    z = jnp.zeros((), jnp.float32)
    return {"tokens_per_expert": jnp.zeros((num_experts,), jnp.float32),
            "prob_sum": jnp.zeros((num_experts,), jnp.float32),
            "z_sum": z, "tokens": z, "dropped": z}


def aux_losses(aux: Params, num_experts: int, top_k: int
               ) -> Tuple[jax.Array, jax.Array]:
    """(load_balance_loss, z_loss) from GLOBALLY-summed aux stats.

    Switch load balance: E * sum_e(f_e * P_e) with f_e the fraction of
    routed assignments to expert e and P_e the mean router prob — minimised
    (== 1) by uniform routing. Callers psum the aux sums over the batch axes
    first so the value is sharding-invariant.
    """
    tokens = jnp.maximum(aux["tokens"], 1.0)
    f = aux["tokens_per_expert"] / (tokens * top_k)
    p = aux["prob_sum"] / tokens
    lb = num_experts * jnp.sum(f * p)
    z = aux["z_sum"] / tokens
    return lb, z
