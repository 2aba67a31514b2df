"""The `sambay` family: a DECODER-HYBRID-DECODER (SambaY, arXiv:2507.06607,
with differential attention, arXiv:2410.05258; Phi-4-mini-flash-reasoning,
`phi4flash`), dense, on the same decoder stack as the other families.

Every layer is a mixer and then the stack's SwiGLU between two LayerNorms
(weight and bias), `h = x + mixer(LN1(x))`, `x' = h + mlp(LN2(h))`, no
positions anywhere (the scans carry order), a final LayerNorm and a head
tied to the table. The mixer is one of FIVE kinds, by the published rule
(`layer_kinds(N, mb_per_layer)`; N = 32 gives 9 / 8 / 1 / 7 / 7):

* `mamba` (layers 0, 2, .., N / 2): a Mamba-1 mixer (`parallel/mamba1.py`
  around `ops/selective_scan.py`). **Layer N / 2 also LEAVES `memory`**, its
  scan's output before the gate;
* `swa` (1, 3, .., N / 2 - 1): differential attention
  (`parallel/diff_attention.py`) under `sliding_window` keys, the row's own
  included;
* `full` (N / 2 + 1): differential attention, causal. **It also LEAVES its
  keys and values** (`k`, `v`, the projections as they leave the linears);
* `gmu` (N / 2 + 2, N / 2 + 4, ..): a gated memory unit (`parallel/gmu.py`)
  on `memory`: no scan, no state;
* `cross` (N / 2 + 3, ..): differential attention with queries of its own
  over the `full` layer's `k` and `v`, causal.

`SambaYTransformer` is a subclass of `models/stack.DecoderStack` and holds
only what differs: the pattern, its modules, its counts and its refusals.
The ONE fact it asks of the stack is `shares_values`: a layer hands LATER
layers something beside the residual stream (`_mix_sharing`'s `left` and
`shared`). The two makers are segments of one layer each, between a period
(`mamba`, `swa`) that repeats below and a period (`gmu`, `cross`) above; the
values are outputs of the makers' checkpoints (never remade) and constants
of the upper period's scan, whose transpose sums the readers' cotangents.

**A cut** (`cfg.sambay.layers_here`: the published indices held, in order)
keeps each layer's published kind and `lambda_init` (a layer is TOLD its
`lambda_init`, `_told`: no parameter). A cut that holds a reader holds its
maker: a pipeline cut between the two is not written.

Counters, a row a layer that counts: `sscan_decay_min` (a Mamba layer),
`diff_lambda` (an attention layer); `memory_rms` (the RMS of what layer N /
2 leaves), `shared_kv_readers` (the layers that read the `full` layer's keys
and values, itself among them) and `resid_rms_last` (train's
`mixer_counters` event).

What is not made to work is refused with a message: where the model is
built (`refuses`), by ZeRO 2/3 and the bucketed reducer
(`hand_reduced_grads`), by `models/decode.py` and the serving engines
(`decodable`: a scan state, a shared KV and a memory are not in
`serving/kv_manager.py`).

Named scopes inside the step, for a device trace's `op_name`:
`mamba1/in_proj|conv|x_proj|dt_proj|sscan|gate|out_proj`, `diff_attn` (a
`swa` or `full` layer's projections, lambda, norm and `W_o`; the flash calls
keep the kernels' names), `cross_attn`, `gmu`, `dense_ffn` and the stack's
`head_loss`.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..config import IGNORE_INDEX, ModelConfig
from ..ops.attention import (causal_attention, live_entries,
                             masked_attention, sliding_window, CAUSAL)
from ..parallel.diff_attention import DifferentialAttention, lambda_init_of
from ..parallel.embedding import VocabParallelEmbedding
from ..parallel.gmu import GatedMemoryUnit
from ..parallel.linear import ColumnParallelLinear, RowParallelLinear
from ..parallel.mamba1 import Mamba1Mixer
from ..parallel.norm import LayerNorm
from .conv_moe import layer_blocks, pattern_of
from .stack import DecoderStack, Params, TPSublayers

KINDS = ("mamba", "swa", "full", "gmu", "cross")
# the module of `_mods` that is a kind's mixer
MIXER = {"mamba": "mamba", "swa": "attn", "full": "attn", "gmu": "gmu",
         "cross": "cross"}
DENSE = ("gate_proj", "up_proj", "down_proj")
# `layer_extra_elems_per_token` in model widths a token: set from the chip's
# reading of the benchmark's cell (PERF.md section 5)
LAYER_FIT_WIDTHS = -19.0


def layer_kinds(num_hidden_layers: int, mb_per_layer: int = 2
                ) -> Tuple[str, ...]:
    """The published rule (`Phi4FlashDecoderLayer`): layer i is a scan where
    `i % mb_per_layer == 0`, else an attention; below N / 2 a Mamba-1 mixer
    or a window attention; layer N / 2 the Mamba-1 mixer that leaves the
    memory, N / 2 + 1 the full attention that leaves its keys and values;
    above, a scan is a gated memory unit and an attention a
    cross-attention."""
    N, mb = num_hidden_layers, mb_per_layer
    if N < 4 or N % 4:
        raise ValueError(f"num_hidden_layers {N} must be a multiple of 4 "
                         f"(the published model's own check)")
    half = N // 2
    if mb < 1 or half % mb or (half + 1) % mb == 0:
        raise ValueError(
            f"mb_per_layer {mb}: layer {half} must be a scan (it leaves the "
            f"memory) and layer {half + 1} an attention (it leaves the keys "
            f"and values)")
    scan = lambda i: i % mb == 0
    return tuple(
        ("mamba" if scan(i) else "swa") if i < half
        else "mamba" if i == half else "full" if i == half + 1
        else "gmu" if scan(i) else "cross" for i in range(N))


def module_names(kind: str):
    """The modules of a layer whose mixer is of `kind`."""
    return ("norm1", MIXER[kind], "norm2", *DENSE)


def layers_held(cfg: ModelConfig) -> Tuple[Tuple[int, str], ...]:
    """(published index, kind) of every layer this job holds, in order."""
    sy = cfg.sambay
    kinds = layer_kinds(sy.num_hidden_layers, sy.mb_per_layer)
    here = (tuple(range(sy.num_hidden_layers)) if sy.layers_here is None
            else tuple(sy.layers_here))
    if list(here) != sorted(set(here)) or not here or not (
            0 <= here[0] and here[-1] < sy.num_hidden_layers):
        raise ValueError(f"layers_here {here} must be published indices "
                         f"below {sy.num_hidden_layers}, ascending")
    return tuple((i, kinds[i]) for i in here)


def layer_counts(cfg: ModelConfig) -> Dict[str, int]:
    """Layers held, by kind."""
    held = [kind for _, kind in layers_held(cfg)]
    return {kind: held.count(kind) for kind in KINDS}


def blocks_of(cfg: ModelConfig):
    """The blocks of the pattern (`models/conv_moe.layer_blocks`' form) and
    the published indices of every key's layers: the lower half by run
    length (a period `mamba`, `swa`), the two makers a segment each
    (`memory_layers`, `full_layers`), the upper half by run length (a period
    `gmu`, `cross`)."""
    half = cfg.sambay.num_hidden_layers // 2
    held = layers_held(cfg)
    parts = ([(i, k) for i, k in held if i < half],
             [(i, k) for i, k in held if i == half],
             [(i, k) for i, k in held if i == half + 1],
             [(i, k) for i, k in held if i > half + 1])
    blocks, indices = [], {}
    for part, maker in zip(parts, (None, "memory", "full", None)):
        if not part:
            continue
        if maker:
            key = f"{maker}_layers"
            blocks.append((None, ((key, maker, False, 1),)))
            indices[key] = [part[0][0]]
            continue
        cut = layer_blocks([k for _, k in part], 0,
                           {k: k for k in KINDS}, "sambay")
        at = 0
        for repeats, keys in cut:
            for _ in range(repeats):
                for key, _, _, n in keys:
                    indices.setdefault(key, []).extend(
                        i for i, _ in part[at:at + n])
                    at += n
        blocks.extend(cut)
    return tuple(blocks), indices


@dataclass(frozen=True)
class SambaYTransformer(DecoderStack):
    """The sambay family (module docstring)."""

    family = "sambay"
    ffn_inputs = 2            # gate and up both read the MLP's input
    tied_head = True
    decodable = False
    hand_reduced_grads = False
    config_extra = "sambay"
    shares_values = True
    # no router anywhere: what the layers hand back are the mixers' counters
    _router_aux_losses = False
    # a minimum over the tokens, a layer's one value, a mean of squares over
    # the tokens, a count of layers
    _counter_reduces = {"sscan_decay_min": lax.pmin, "diff_lambda": lax.pmax,
                        "memory_rms": lax.pmean,
                        "shared_kv_readers": lax.pmax}
    refuses = {
        "tp_size > 1": "the Mamba-1 mixer's channels, the memory and the "
                       "differential heads are whole; no reduce of a "
                       "counted mixer split over a tp axis is written",
        "pp_size > 1": "layers above read what layer N / 2 and N / 2 + 1 "
                       "left: a pipeline cut between a maker and a reader "
                       "of a shared value is not written, and the pattern "
                       "has five kinds of mixer",
        "cp_size > 1": "the scan's state and the convolution's taps run "
                       "along the whole sequence; no hand-over of either "
                       "between sequence shards is written",
        "sequence_parallel=True": "the convolution and the scan read whole "
                                  "sequences",
        "attn_t_real": "pad tokens would enter the convolution and move "
                       "the state",
        "ZeRO stage 3": "",
    }

    def _check_facts(self):
        cfg, sy = self.cfg, self.cfg.sambay
        held = layers_held(cfg)
        if len(held) != cfg.num_layers:
            raise ValueError(f"layers_here names {len(held)} layers, "
                             f"num_layers is {cfg.num_layers}")
        if cfg.num_experts:
            raise ValueError("the sambay family's layers are dense: "
                             "cfg.num_experts must be 0")
        n = layer_counts(cfg)
        half = sy.num_hidden_layers // 2
        indices = [i for i, _ in held]
        for reader, maker in (("gmu", half), ("cross", half + 1)):
            if n[reader] and maker not in indices:
                raise ValueError(
                    f"layers_here holds {n[reader]} {reader} layer(s) and "
                    f"not layer {maker}, which makes what they read: a cut "
                    f"between a maker and a reader of a shared value is not "
                    f"written")
        if cfg.num_heads % 2 or cfg.kv_heads % 2:
            raise ValueError("differential attention pairs the heads: "
                             "num_heads and num_kv_heads must be even")
        self._mods              # heads that are not whole pairs

    # ---- the layer pattern ----

    @functools.cached_property
    def _blocks(self):
        return blocks_of(self.cfg)

    @property
    def _pattern(self):
        return pattern_of(self._blocks[0])

    @property
    def _segments(self):
        """(parameter key, layers, module names) of every stacked key."""
        return tuple((key, (repeats or 1) * n,
                      module_names("mamba" if kind == "memory" else kind))
                     for repeats, parts in self._blocks[0]
                     for key, kind, _, n in parts)

    @functools.cached_property
    def _keys(self) -> Dict[str, Tuple[str, bool]]:
        """Parameter key -> (its layers' kind, is it a period's)."""
        return {key: (kind, repeats is not None)
                for repeats, parts in self._blocks[0]
                for key, kind, _, _ in parts}

    def _kind(self, key: str) -> str:
        return self._keys[key][0]

    def _told(self, key: str, layers: Params) -> Params:
        """An attention layer is told its `lambda_init`, from its PUBLISHED
        index, stacked like the key's layers."""
        kind, in_period = self._keys[key]
        if MIXER.get(kind) not in ("attn", "cross"):
            return layers
        # (periods, layers a period, ...) or a segment's (layers, ...)
        stacked = jax.tree.leaves(layers)[0].shape[:2 if in_period else 1]
        return {**layers, "told": {"lambda_init": jnp.asarray(
            [lambda_init_of(i) for i in self._blocks[1][key]],
            jnp.float32).reshape(stacked)}}

    # ---- facts for training/memory.py ----

    @property
    def v_head_dim(self) -> int:
        """A map's output is the pair's value wide."""
        return 2 * self.head_dim

    @property
    def shared_elems_per_token(self) -> float:
        """The memory (the scan's channels) and the full layer's keys and
        values, where the cut holds their makers."""
        n = layer_counts(self.cfg)
        return float((n["gmu"] > 0) * self.cfg.sambay.mamba_expand * self.d
                     + (n["cross"] > 0) * 2 * self.kv_dim)

    @property
    def layer_extra_elems_per_token(self) -> float:
        """SET FROM THE CHIP'S READING, and negative: the benchmark's cell
        on a v5e counts 12.068 GiB at rung `true`, the floor, beside 10.39
        GiB of state (my chip run, PR 76; PERF.md section 5), where the
        untuned count made 13.80: what a layer's backward holds at its
        fullest (by count a Mamba layer's `[u | z]`, the convolution's
        float32 sums, dt, the scan's float32 output and the gated copy, each
        with its cotangent: more than the dense skeleton's 6 d + 3.4 f a
        token) is live in the MIDDLE of the backward scan, when the later
        layers' gradients do not exist yet in the runtime's count, as in
        `models/ssm_dense.py`. `LAYER_FIT_WIDTHS` model widths a token come
        back off, which makes 12.31 (+2.0%). The next group, `ffn`, keeps 4.0
        GiB of `ffn_gate` / `ffn_up` stacks at 16k and does not fit: `auto`
        passes over it and keeps the flash outputs and q, k, v of the three
        attention layers behind it (`true+flash+dots`), where the count
        makes 13.18 and the chip held 13.018 (+1.3%; my chip run, PR 77;
        12.79 against 12.856 with the flash group alone, -0.5%)."""
        return LAYER_FIT_WIDTHS * self.d

    # ---- sub-module definitions ----

    @functools.cached_property
    def embedding(self) -> VocabParallelEmbedding:
        return VocabParallelEmbedding(
            self.cfg.vocab_size, self.d, tp_size=self.tp_size,
            init_std=self.cfg.sambay.initializer_range)

    @property
    def scan_interpreted(self) -> bool:
        """The scan's kernels under the interpreter: with the flash
        kernels', by the one name (`attn_impl="flash_interpret"`), where
        they hold the shape."""
        from ..ops.pallas.selective_scan import holds
        sy = self.cfg.sambay
        return (self.attn_impl == "flash_interpret"
                and holds(sy.mamba_expand * self.d, sy.mamba_d_state))

    @functools.cached_property
    def _mods(self) -> Dict[str, Any]:
        cfg, sy = self.cfg, self.cfg.sambay
        d, eps = self.d, sy.layer_norm_eps
        col = functools.partial(ColumnParallelLinear, add_bias=False,
                                gather_output=False)
        row = functools.partial(RowParallelLinear, add_bias=False,
                                split_input=False)
        attn = functools.partial(
            DifferentialAttention, d, cfg.num_heads // 2, cfg.kv_heads // 2,
            self.head_dim, eps, sy.attention_bias, sy.lambda_std)
        return {
            "norm1": LayerNorm(d, eps),
            "norm2": LayerNorm(d, eps),
            "mamba": Mamba1Mixer(
                d, sy.mamba_expand * d, sy.mamba_d_state,
                sy.mamba_dt_rank or 0, sy.mamba_d_conv, sy.time_step_min,
                sy.time_step_max, sy.time_step_floor,
                interpret=self.scan_interpreted),
            "attn": attn(),
            "cross": attn(queries_only=True),
            "gmu": GatedMemoryUnit(d, sy.mamba_expand * d),
            "gate_proj": col(d, cfg.ffn_dim),
            "up_proj": col(d, cfg.ffn_dim),
            "down_proj": row(cfg.ffn_dim, d),
        }

    # ---- what differs inside the forward (per-shard, inside shard_map) ----

    def _positions(self, params: Params, x: jax.Array,
                   position_ids: jax.Array, dtype):
        """No layer takes positions."""
        return x.astype(dtype), ()

    def _attn_mask(self, t: int, kind: "str | None" = None):
        """A `swa` layer's band; the triangle everywhere else."""
        if kind == "swa":
            return sliding_window(self.cfg.sambay.sliding_window)
        return None

    def _mix_sharing(self, lp: Params, y: jax.Array, dtype, kind, told,
                     shared: Params):
        m, left = self._mods, None
        if kind in ("mamba", "memory"):
            scanned, z, counted = m["mamba"].scan(lp["mamba"], y, dtype)
            if kind == "memory":
                left = self._made(lp, {"memory": scanned})
                scanned = left["memory"]
                counted = {**counted, "memory_rms": jnp.mean(jnp.square(
                    lax.stop_gradient(scanned).astype(jnp.float32)))}
            return m["mamba"].project(lp["mamba"], scanned, z, dtype), \
                counted, left
        if kind == "gmu":
            return m["gmu"].apply(lp["gmu"], y, shared["memory"], dtype), \
                None, None
        name = MIXER[kind]
        scope = "cross_attn" if kind == "cross" else "diff_attn"
        with jax.named_scope(scope):
            q, *kv = m[name].qkv(lp[name], y, dtype)
            k, v = kv if kv else (shared["k"], shared["v"])
            if kind == "full":
                left = self._made(lp, {"k": k, "v": v})
                k, v = left["k"], left["v"]
            heads = m[name].heads_of(q, k, v)
        mask = self._attn_mask(y.shape[1], kind)
        o = (causal_attention(*heads, impl=self.attn_impl) if mask is None
             else masked_attention(*heads, mask, impl=self.attn_impl))
        with jax.named_scope(scope):
            out, counted = m[name].project(lp[name], o, told["lambda_init"],
                                           dtype)
        if kind != "swa":
            counted = {**counted, "shared_kv_readers": jnp.ones((),
                                                                jnp.float32)}
        return out, counted, left

    def _made(self, lp: Params, values: Params) -> Params:
        """What a maker leaves, as it is made and before the maker's own
        use of it: here as it is (a test adds a probe, to read a value's
        summed cotangent)."""
        return values

    def _mlp(self, lp: Params, y: jax.Array, tp: TPSublayers,
             dtype) -> jax.Array:
        with jax.named_scope("dense_ffn"):      # every layer's SwiGLU
            return super()._mlp(lp, y, tp, dtype)

    def _extra_loss(self, params: Params, loss: jax.Array, x: jax.Array,
                    aux, trunk, input_ids, target_ids, position_ids,
                    mode: str, batch_axes):
        """No further loss term. The layers' counters, `memory_rms` as an
        RMS and `shared_kv_readers` as a count, and `resid_rms_last`, the
        RMS over the width of what enters the final norm, float32, a mean
        over the tokens that are not ignored."""
        with jax.named_scope("head_loss"):
            live = (target_ids != IGNORE_INDEX).astype(jnp.float32)
            rms = jnp.sqrt(jnp.mean(jnp.square(
                lax.stop_gradient(x).astype(jnp.float32)), axis=-1))
            sums = lax.psum((jnp.sum(rms * live), jnp.sum(live)), batch_axes)
        counters = self._counters(aux, batch_axes)
        if "memory_rms" in counters:
            counters["memory_rms"] = jnp.sqrt(counters["memory_rms"][0])
        if "shared_kv_readers" in counters:
            counters["shared_kv_readers"] = jnp.sum(
                counters["shared_kv_readers"])
        return loss, {**counters,
                      "resid_rms_last": sums[0] / jnp.maximum(sums[1], 1.0)}

    # ---- counts ----

    @staticmethod
    def param_counts(cfg: ModelConfig) -> Dict[str, int]:
        """The family's parameters by part (`DecoderStack.num_params`); the
        tied table counts once."""
        sy, d, n = cfg.sambay, cfg.attn_dim, layer_counts(cfg)
        h = d // cfg.num_heads
        attn = functools.partial(
            DifferentialAttention, d, cfg.num_heads // 2,
            cfg.kv_heads // 2, h, bias=sy.attention_bias)
        mlp = 3 * d * cfg.ffn_dim + 4 * d       # + the layer's two norms
        mixer = {
            "mamba": Mamba1Mixer(d, sy.mamba_expand * d, sy.mamba_d_state,
                                 sy.mamba_dt_rank or 0,
                                 sy.mamba_d_conv).num_params(),
            "swa": attn().num_params(), "full": attn().num_params(),
            "gmu": 2 * d * sy.mamba_expand * d,
            "cross": attn(queries_only=True).num_params()}
        return {"embedding": cfg.vocab_size * d, "final_norm": 2 * d,
                **{f"{kind}_layers": n[kind] * (mixer[kind] + mlp)
                   for kind in KINDS}}

    @staticmethod
    def flops_per_step(cfg, batch, seqlen, num_params) -> float:
        """Every parameter but the norms', the biases' and the scan's own
        few is a matmul's (the tied table's lookup is none, its one matrix
        is the head's); attention at each kind's LIVE entries, two maps a
        differential head, a map `h` wide against keys and `2 h` against
        values; the scan's `7 c N` multiply-adds a token (the decay, the
        update, the read), forward and twice that backward."""
        sy, n = cfg.sambay, layer_counts(cfg)
        h = cfg.attn_dim // cfg.num_heads
        live = (n["swa"] * live_entries(sliding_window(sy.sliding_window),
                                        seqlen)
                + (n["full"] + n["cross"]) * live_entries(CAUSAL, seqlen))
        scan = 7 * sy.mamba_expand * cfg.attn_dim * sy.mamba_d_state
        return (6 * num_params * batch * seqlen
                + 3 * batch * cfg.num_heads * live * 2 * (h + 2 * h)
                + 3 * n["mamba"] * scan * batch * seqlen)
