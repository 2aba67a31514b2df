"""The decoder stack every model family runs on: `DecoderStack`.

One dataclass holds what a decoder-only transformer is to the rest of the
program, whatever its family: the parallelism fields and their validation,
the tp layout rule (`resolve_tp_layout`), the remat ladder, the layer
skeleton (ZeRO-3 gather, sequence-parallel / ring plumbing, attention
dispatch with the pipeline gate, the MoE branch), the forward (embed, scan
or pipeline, `head_loss`), both pipeline schedules, the losses and the
jitted entry points. A change to any of these is one edit here.

A family is a subclass (`models.FAMILIES` names them) that supplies what
differs and nothing else (docs/DESIGN.md, "What a family file holds"):

* its name (`family`) and its declarations, which `training/memory.py`,
  `obs/attribution.py` and this file ask the class (nothing infers one from
  another): the layer pattern (`_pattern`, `_segments`), the `ModelConfig`
  field with its facts (`config_extra`), what it does not run with and why
  (`refuses`), `ffn_inputs`, `tied_head`, `uses_rope`, `rotary_dim`,
  `decodable`, `draws_noise`, `layer_extra_elems_per_token`; and, where it
  has them, the norms after a sublayer (`post_attn_norm_key`,
  `post_ffn_norm_key`), the embedding's multiplier (`embed_scale`), the
  three other scalars a published configuration may state
  (`residual_scale`, `softmax_scale`, `logit_scale`), the
  kinds of attention layer by `_pattern` key (`_kind`, `_attn_mask(t,
  kind)`, `unrotated_kinds`), the speed of its routers' selection bias
  (`router_bias_speed`), whether its routers read the layer's input
  (`router_reads_layer_input`), the mixer of its residual streams where
  a layer's residual state is several (`stream_mixer`), whether a layer
  is ONE norm and ONE sublayer (`one_sublayer`) and how often a step runs
  the pattern over the same weights, with an exit after every pass
  (`loop_steps`; the exits' gate and objective: `exit_entropy_coef`);
* `_mods`, the per-layer modules (the attention projections are
  `wq`/`wk`/`wv`/`wo` wherever the stack's (q, k, v) dispatch runs, which
  `models/decode.py` and `interop.py` read by name), and its mixer where
  that is not the stack's (`_qkv`, `_mix`); `_mlp`, a dense block's;
* how positions enter where not by RoPE from the ids (`_positions`,
  `_position_qk`);
* its counts: `param_counts(cfg)` (or `num_params(cfg)`) and
  `flops_per_step(cfg, batch, seqlen, num_params)`.

The parameter tree (`init`, `specs`), the facts check, the refusals, the
head and the counters of a family with facts of its own are made here; the
two older families keep the trees they have always made.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from dataclasses import dataclass
from types import SimpleNamespace
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..config import IGNORE_INDEX, ModelConfig, resolve_dtype
from ..ops.attention import causal_attention, masked_attention
from ..ops.collectives import copy_to, gather_from, reduce_from
from ..ops.overlap import ag_matmul, exchange_grads, ring_order
from ..ops.ring_attention import ring_attention, ulysses_attention
from ..ops.rope import apply_rotary_leading, rope_angles
from ..parallel.embedding import VocabParallelEmbedding
from ..parallel.gated_attention import gate_heads
from ..parallel.linear import (OVERLAP_MODES, ColumnParallelLinear,
                               apply_column_ring_fused)
from ..parallel.moe import aux_losses, aux_zeros, zeros_like_varying
from ..runtime.prng import fold

Params = Dict[str, Any]

NEG_INF = -1e9  # mask value for padded vocab logits


def validate_pp(num_layers: int, pp_size: int, pp_microbatches: int,
                pp_schedule: str = "gpipe", pp_virtual: int = 2) -> None:
    """Pipeline construction checks shared by both model families."""
    if pp_size > 1 and num_layers % pp_size != 0:
        raise ValueError(
            f"num_layers {num_layers} not divisible by pp_size "
            f"{pp_size} (stages hold equal layer counts)")
    if pp_microbatches and pp_size == 1:
        raise ValueError(
            "pp_microbatches requires pp_size > 1 (a non-pipelined model "
            "runs no microbatch schedule; the setting would be silently "
            "ignored)")
    if pp_microbatches and pp_microbatches < pp_size:
        raise ValueError(
            f"pp_microbatches {pp_microbatches} < pp_size "
            f"{pp_size} would leave permanent pipeline bubbles")
    if pp_schedule not in ("gpipe", "interleaved"):
        raise ValueError(f"pp_schedule must be 'gpipe' or 'interleaved', "
                         f"got {pp_schedule!r}")
    if pp_schedule == "interleaved":
        if pp_size == 1:
            raise ValueError("pp_schedule='interleaved' requires pp_size > 1")
        if pp_virtual < 2:
            raise ValueError(
                f"pp_virtual {pp_virtual} < 2: one virtual stage per device "
                f"IS the gpipe schedule; use pp_schedule='gpipe'")
        if num_layers % (pp_size * pp_virtual) != 0:
            raise ValueError(
                f"num_layers {num_layers} not divisible by "
                f"pp_size*pp_virtual {pp_size * pp_virtual} (each device "
                f"holds pp_virtual equal round-robin layer blocks)")
        M = pp_microbatches or pp_size
        if M % pp_size != 0:
            raise ValueError(
                f"interleaved schedule needs pp_microbatches {M} divisible "
                f"by pp_size {pp_size} (microbatches circulate the ring in "
                f"groups of pp_size)")


def validate_cp(cfg: ModelConfig, tp: int, cp_size: int, cp_impl: str,
                cp_layout: str) -> None:
    """Context-parallel construction checks shared by both model families
    (llama + gpt2): cp_impl/cp_layout membership, Ulysses head
    divisibility (q AND kv local heads), zigzag-requires-ring."""
    if cp_impl not in ("ring", "ulysses"):
        raise ValueError(f"cp_impl must be 'ring' or 'ulysses', got "
                         f"{cp_impl!r}")
    if (cp_size > 1 and cp_impl == "ulysses"
            and ((cfg.num_heads // tp) % cp_size != 0
                 or (cfg.kv_heads // tp) % cp_size != 0)):
        raise ValueError(
            f"ulysses needs local q heads {cfg.num_heads // tp} and kv "
            f"heads {cfg.kv_heads // tp} divisible by cp_size {cp_size}; "
            f"use cp_impl='ring'")
    if cp_layout not in ("contiguous", "zigzag"):
        raise ValueError(f"cp_layout must be 'contiguous' or 'zigzag', "
                         f"got {cp_layout!r}")
    if cp_layout == "zigzag" and cp_impl != "ring":
        raise ValueError("cp_layout='zigzag' requires cp_impl='ring' "
                         "(Ulysses assumes rank-order contiguous chunks)")


def validate_t_real(attn_t_real, cp_size: int, num_experts: int = 0) -> None:
    """Sequence-bucketing construction checks shared by both families."""
    if attn_t_real is None:
        return
    if attn_t_real < 1:
        raise ValueError(f"attn_t_real must be >= 1, got {attn_t_real}")
    if cp_size > 1:
        raise ValueError(
            "attn_t_real (pad-aware sequence bucketing) requires cp_size "
            "== 1: the ring/ulysses paths shard the sequence over 'cp' and "
            "mask by carried global positions, so a static real-length cut "
            "would land mid-chunk")
    if num_experts:
        raise ValueError(
            "attn_t_real (pad-aware sequence bucketing) does not compose "
            "with MoE: the router sees every position, so pad tokens would "
            "claim expert-capacity slots ahead of later rows' real tokens "
            "and inflate the load-balance/z aux statistics — bucketed MoE "
            "training would silently diverge from unbucketed")


_RING = ("ring", "ring_q")      # the OVERLAP_MODES that ride the rings


def validate_tp_overlap(tp_overlap: str, sequence_parallel,
                        num_experts: int = 0) -> None:
    """tp_overlap / sequence_parallel construction checks shared by both
    model families. Either may be 'auto' (`resolve_tp_layout`)."""
    if tp_overlap != "auto" and tp_overlap not in OVERLAP_MODES:
        raise ValueError(f"tp_overlap must be 'auto', 'off', 'ring' or "
                         f"'ring_q', got {tp_overlap!r}")
    if sequence_parallel not in (True, False, "auto"):
        raise ValueError(f"sequence_parallel must be True, False or "
                         f"'auto', got {sequence_parallel!r}")
    if tp_overlap in _RING and sequence_parallel is False:
        raise ValueError(
            f"tp_overlap={tp_overlap!r} requires sequence_parallel: the "
            "ring decomposes the SP all-gather/reduce-scatter pair; the "
            "non-SP path's monolithic all-reduce has no chunk schedule to "
            "overlap (or quantize per hop)")
    if tp_overlap in _RING and num_experts:
        raise ValueError(
            f"tp_overlap={tp_overlap!r} does not compose with MoE yet: "
            "the router consumes the full-token gather that the ring "
            "collective matmul deliberately never materialises")


def resolve_tp_layout(sequence_parallel, tp_overlap: str, *, tp_size: int,
                      t_local: int, dense: bool,
                      pp_size: int = 1) -> Tuple[bool, str]:
    """(sequence_parallel, tp_overlap) with every 'auto' replaced: the ONE
    rule for how activations lie over 'tp' between sublayers, read by the
    models at trace time (`DecoderStack._resolved`) and by everything that
    has to agree with them (training/memory.py, training/zero.py,
    train.py). `t_local` is the cp-local sequence length of the batch being
    traced, `dense` whether the FFN is (not MoE).

    'auto' sequence parallelism is on where nothing stands against it:
    tp_size > 1, a dense FFN (the MoE router wants the gathered tokens) and
    a sequence the tp ranks split evenly. 'auto' overlap is 'ring' exactly
    where 'auto' sequence parallelism turned itself on: the ring collective
    matmuls (ops/overlap.py) are what a v5e measured fastest on GPT-2 large
    at dp2 x tp2, ahead of the replicated layout and of the monolithic
    gather/reduce-scatter (PERF.md section 6, PR 28); not under pp, where
    the rings would run on every bubble step. At tp_size == 1 both are off,
    so a one-chip program is the one it has always been. An explicit value
    does what it always did: `sequence_parallel=True` alone is the
    monolithic path and still raises on a sequence that does not divide,
    `False` is the replicated layout, and an explicit ring turns an 'auto'
    sequence parallelism on."""
    sp, ov = sequence_parallel, tp_overlap
    chose_sp = sp == "auto"
    if chose_sp:
        sp = ov in _RING or (tp_size > 1 and dense
                             and t_local % tp_size == 0)
    if ov == "auto":
        ov = "ring" if chose_sp and sp and pp_size == 1 else "off"
    return bool(sp), ov


def resolve_dp_reduce(*, dp_size: int, dense: bool, pp_size: int = 1) -> str:
    """Who sums a layer's weight cotangents over 'dp' in the default step:
    'none' at dp_size 1 (the layer body is the text it has always been),
    'exchange' for a dense model with pp_size 1 (`ops/overlap.
    exchange_grads`: a typed gather a leaf, asynchronous under the
    backward's dots), else 'psum' (the all-reduce the transpose of the
    varying cast inserts: MoE and pp > 1 keep it, untimed either way). The
    rule rests on one timed shape, GPT-2 large at dp2 x tp2 on a v5e
    (PERF.md section 6, PR 32); tp does not enter it. Read by the layer
    body at trace time (`DecoderStack._exchanges_dp_grads`) and by train.py
    for the line that names the layout."""
    if dp_size == 1:
        return "none"
    return "exchange" if dense and pp_size == 1 else "psum"


# The residuals a layer's backward may keep instead of recomputing, in the
# order they are bought: milliseconds of recompute saved per byte kept, as a
# v5e measured them on GPT-2 medium (tp 1) and large (dp2 x tp2); PERF.md
# section 5 has the numbers. Each name is a `checkpoint_name` tag set where
# the tensor is made: the flash kernel's outputs in
# ops/pallas/flash_attention.py, the projections' in `_layer_body` here, the
# MLP's in each family's `_mlp`. A family tags what it has (`ffn_fc` is the
# GPT-2 MLP's, `ffn_gate`/`ffn_up` the SwiGLU's), and a name nothing tags
# saves nothing:
# `attn_proj`, the attention projection PAST its all-reduce, is tagged only
# where there is a reduce (tp > 1). Keeping it takes the one collective out
# of the recomputed forward, which is worth more per byte than anything
# else; with tp = 1 it would buy one d x d matmul for a stack as large as
# the layer input's, and the chip measured that as a loss. Rung k keeps the
# names of rungs 1..k; rung 0 keeps the layer input only (full remat); the
# top rung keeps every matmul output a layer's backward reads, which is
# what 'dots' has always meant here. That is what a rung's NAME means.
# `remat="auto"` resolves to a SET of the groups below: one climb in this
# order keeps each group that fits on top of those kept and passes over one
# that does not (`training/memory._pick`), so a set need not be a prefix
# (`remat_groups` has the spelling). Weights gathered by ZeRO-3 are never
# on the ladder.
REMAT_LADDER = (
    ("true", ()),
    ("attn_proj", ("attn_proj",)),
    ("ffn", ("ffn_fc", "ffn_gate", "ffn_up")),
    ("flash", ("flash_out", "flash_lse")),
    ("dots", ("q_proj", "k_proj", "v_proj")),
)
REMAT_RUNGS = tuple(name for name, _ in REMAT_LADDER)
# Which of a layer's modules makes each name: a layer tags a name where its
# parameters hold one of them (the stack's own attention is `wq` ... `wo`;
# a family's whole-attention modules are `attn`, gated or differential, `mla`,
# latent, whose q, k and v carry no name, and `cross`, whose keys and values
# are another layer's). `attn_proj` is tagged past any mixer's
# reduce, so by every layer. `DecoderStack.tagged_layers` counts by it.
LADDER_MADE_BY = {
    "ffn_fc": ("fc",), "ffn_gate": ("gate_proj",), "ffn_up": ("up_proj",),
    "flash_out": ("wo", "attn", "mla", "cross"),
    "flash_lse": ("wo", "attn", "mla", "cross"),
    "q_proj": ("wq", "attn", "cross"), "k_proj": ("wk", "attn"),
    "v_proj": ("wv", "attn"),
}


def remat_groups(remat) -> Tuple[str, ...]:
    """The ladder's groups a `remat` value keeps, in the ladder's order:
    none for True, a rung's name its prefix of the ladder, and the ONE
    spelling of a set that is no prefix: the floor's name and the groups'
    joined by '+' in the ladder's order ('true+flash+dots': the flash
    kernel's outputs and q, k, v without the MLP's stacks)."""
    if remat is True:
        return ()
    if isinstance(remat, str):
        if remat in REMAT_RUNGS:
            return REMAT_RUNGS[1:REMAT_RUNGS.index(remat) + 1]
        floor, *groups = remat.split("+")
        if floor == REMAT_RUNGS[0] and groups and groups == [
                g for g in REMAT_RUNGS[1:] if g in groups]:
            return tuple(groups)
    raise ValueError(
        f"remat must be True, False, 'auto', one of {REMAT_RUNGS} or "
        f"'{REMAT_RUNGS[0]}' and groups of the ladder joined by '+' in its "
        f"order, got {remat!r}")


def remat_names(remat) -> Tuple[str, ...]:
    """The `checkpoint_name` tags a `remat` value keeps: its groups'."""
    ladder = dict(REMAT_LADDER)
    return tuple(n for group in remat_groups(remat) for n in ladder[group])


def remat_spelling(groups, empty=()) -> str:
    """The value that keeps `groups` (in the ladder's order): the name of
    the lowest rung whose prefix holds them and beside them only groups of
    `empty` (those that keep nothing in the model at hand: a name none of
    its layers tags), else the joined spelling `remat_groups` reads."""
    groups = tuple(groups)
    for k, rung in enumerate(REMAT_RUNGS):
        prefix = REMAT_RUNGS[1:k + 1]
        if set(groups) <= set(prefix) <= set(groups) | set(empty):
            return rung
    return "+".join(REMAT_RUNGS[:1] + groups)


def validate_remat(remat) -> None:
    if remat is not False and remat != "auto":
        remat_groups(remat)


def remat_wrap(layer_fn, remat, static_argnums=(), looped: bool = True):
    """Apply a per-layer remat policy; shared by every model family.

    `remat` is False (keep everything autodiff saves) or names groups of
    REMAT_LADDER (`remat_groups`: a rung's name, or a joined set): the
    layer is a `jax.checkpoint` whose policy saves the groups' names and
    recomputes the rest. Rung 0 passes no policy, so it is the program
    `remat=True` has always been. 'auto' is resolved by the caller
    (`resolve_remat`) before it gets here: what fits depends on the shapes
    the layer is traced with. `looped`: does the layer run in a scan of
    several layers.
    """
    if remat is False:
        return layer_fn
    names = remat_names(remat)
    if not names:
        return jax.checkpoint(layer_fn, static_argnums=static_argnums)
    # prevent_cse=False where the layer runs inside a lax.scan of several
    # layers: its forward and backward are separate loops, so there is
    # nothing to CSE the recomputation with. The barrier that guards
    # against it is a `reduce_precision` pass over each kept tensor (1.9 ms
    # a step for `flash_out` alone on GPT-2 medium) and pins the kernel's
    # padded layout on the stack. A scan of ONE layer is no loop once XLA
    # has simplified it: forward and backward then share a computation,
    # the recompute is CSE'd with the forward and the layer keeps
    # everything (cell 7 at `dots`: +1.75 GiB on the chip for 0.6 of
    # named stacks, PERF.md section 6, PR 62), so there the barrier stays.
    return jax.checkpoint(
        layer_fn, static_argnums=static_argnums, prevent_cse=not looped,
        policy=jax.checkpoint_policies.save_only_these_names(*names))


def _pull_of(pull, params: Params):
    """`pull` (a `jax.vjp`'s) as a function of `params`, a tree the function
    was taken at: the residuals that ARE leaves of `params` (a
    `jax.checkpoint` holds its inputs) are left out, and the call with the
    same `params` puts them back. A scan then stacks what a step kept of
    its other inputs, not a copy of the weights a step."""
    leaves, tree = jax.tree.flatten(pull)
    given = jax.tree.leaves(params)
    at = [next((i for i, p in enumerate(given) if p is leaf), None)
          for leaf in leaves]

    def again(rest, params):
        rest, given = iter(rest), jax.tree.leaves(params)
        return jax.tree.unflatten(
            tree, [next(rest) if i is None else given[i] for i in at])

    return jax.tree_util.Partial(
        again, [leaf for leaf, i in zip(leaves, at) if i is None])


def resolve_remat(model, params: Params, ids_shape):
    """`model.remat`, with 'auto' replaced by what `select_remat_traced`
    keeps at the shapes this trace holds (a rung's name, or a joined set):
    `params` and `ids_shape` are the per-shard ones (this is called inside
    shard_map). Nothing is compiled to find out; the answer is cached per
    (model, shapes)."""
    if model.remat != "auto":
        return model.remat
    from ..training.memory import select_remat_traced
    count = lambda tree: sum(int(x.size) for x in jax.tree.leaves(tree))
    b, t = ids_shape
    return select_remat_traced(
        model, count(params),
        sum(count(params[key]) for key in model._layer_keys),
        int(b), int(t))


# What a family may say it does not run with (`DecoderStack.refuses`): the
# name the refusal prints -> does the model being built ask for it
REFUSABLE = {
    "tp_size > 1": lambda m: m.tp_size > 1,
    "pp_size > 1": lambda m: m.pp_size > 1,
    "cp_size > 1": lambda m: m.cp_size > 1,
    "ep_size > 1": lambda m: m.ep_size > 1,
    "sequence_parallel=True": lambda m: m.sequence_parallel is True,
    "attn_t_real": lambda m: m.attn_t_real is not None,
    "ZeRO stage 3": lambda m: m.zero3_axis is not None,
}


def idle_expert_params(cfg: ModelConfig, expert_layers: int,
                       width: int) -> float:
    """What `flops_per_step` takes off a count for the experts HELD: of
    them a token takes `top_k x held / routed` on average (the rest of its
    top_k live on other chips), over `expert_layers` layers of SwiGLU
    experts `width` wide."""
    held = cfg.experts_held
    return expert_layers * (held - cfg.moe_top_k * held
                            / cfg.num_experts) * (3 * cfg.attn_dim * width)


def exit_distribution(z: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(`p`, `log p`), each (R, ...), of the exit distribution from the exit
    gate's logits `z` (R, ...): `log p_r = log lam_r + sum_{j<r} log(1 -
    lam_j)` with `lam = sigmoid(z)`, and the last pass takes the remainder,
    `log p_R = sum_{j<R} log(1 - lam_j)` (its own gate's logit is not
    read). Made of log-sigmoids, so one pass gives `p = 1` exactly."""
    stay = jax.nn.log_sigmoid(-z[:-1])          # log(1 - lam_j), j < R
    left = jnp.concatenate([jnp.zeros_like(z[:1]), jnp.cumsum(stay, axis=0)])
    log_p = left + jnp.concatenate(
        [jax.nn.log_sigmoid(z[:-1]), jnp.zeros_like(z[:1])])
    return jnp.exp(log_p), log_p


@dataclass(frozen=True)
class TPSublayers:
    """How a layer's sublayers meet the 'tp' axis, from the resolved
    (sequence_parallel, tp_overlap) of the model being traced.

    In sequence-parallel mode x is (b, t/tp, d) between sublayers; the
    column-linears all-gather it back to the full local sequence t and the
    row-linears reduce-scatter their outputs. Under tp_overlap='ring' the
    per-sublayer gather never materialises: the fused ring collective
    matmul (one ring SHARED by the projections of one input, wq/wk/wv or
    gate/up: same bytes as the shared gather) consumes the seq-sharded
    activation directly, and its custom VJP sums the fan-out cotangents on
    one reverse ring (the same one-psum_scatter-per-sublayer traffic as the
    shared gather's transpose). Otherwise the normed activation is gathered
    ONCE per sublayer and shared between the projections: the fan-out
    cotangents sum at the single gather, whose transpose is one
    psum_scatter per sublayer (canonical Megatron SP traffic), not one per
    projection."""

    mods: Dict[str, Any]
    sp: bool
    ring_ov: bool
    ring_quant: bool

    @property
    def out_layout(self) -> str:
        return "seq_sharded" if self.sp else "replicated"

    @property
    def ffn_order(self) -> Dict[str, str]:
        # nothing between an MLP's input and output projections cares where
        # a token sits: under the rings the hidden activation stays in the
        # ring's own chunk order (ops/overlap.py, "RING ORDER")
        return dict(seq_order="ring") if self.ring_ov else {}

    def gather(self, z: jax.Array) -> jax.Array:
        """A normed activation as the column-linears of its sublayer take
        it: gathered over 'tp' under monolithic sequence parallelism."""
        if self.sp and not self.ring_ov:
            return gather_from(z, "tp", tiled_axis=-2)
        return z

    def columns(self, lp: Params, names: Tuple[str, ...], y: jax.Array,
                dtype, **order) -> Tuple[jax.Array, ...]:
        """`y` (from `gather`) through the column-linears `names`, which
        share it: one fused ring under ring overlap, else one apply each.
        `order` is `ffn_order` where the consumer takes ring order."""
        if self.ring_ov:
            return tuple(apply_column_ring_fused(
                tuple(lp[n] for n in names), y, dtype,
                quantized=self.ring_quant, **order))
        in_layout = "gathered" if self.sp else "replicated"
        return tuple(self.mods[n].apply(lp[n], y, dtype,
                                        input_layout=in_layout)
                     for n in names)

    def row(self, lp: Params, name: str, z: jax.Array, dtype,
            **order) -> jax.Array:
        """`z` through the row-linear `name`, reduced (or reduce-scattered
        under sequence parallelism) over 'tp'."""
        return self.mods[name].apply(lp[name], z, dtype,
                                     output_layout=self.out_layout, **order)


def _rows_in_order(auxs):
    """Several blocks' (or a period's keys') counters, a row a layer each,
    as one: the rows in the order the layers ran. Where the layers do not
    all count the same (every layer its mixers and an expert layer its
    router too; a linear-attention layer its decay), each counter's rows
    follow the layers that count it."""
    counted = [aux for aux in auxs if aux is not None]
    if len(counted) < len(auxs):
        # some of the layers count nothing (a layer that is one sublayer,
        # and that a mixer with no counter)
        if len(counted) < 2:
            return counted[0] if counted else None
        auxs = counted
    keys = [set(aux) if isinstance(aux, dict) else None for aux in auxs]
    if None not in keys and any(k != keys[0] for k in keys):
        return {k: jnp.concatenate([a[k] for a in auxs if k in a])
                for k in sorted(set().union(*keys))}
    return jax.tree.map(lambda *a: jnp.concatenate(a), *auxs)


@dataclass(frozen=True)
class DecoderStack:
    """Static model definition; params live in an explicit pytree. The
    module docstring lists what a family (a subclass) supplies."""

    cfg: ModelConfig
    tp_size: int = 1
    attn_impl: str = "auto"  # flash kernel on TPU, XLA path on CPU
    # Expert parallelism (with cfg.num_experts > 0): experts are sharded
    # over the mesh axis 'ep', which doubles as an extra data axis for the
    # dense sublayers (the batch shards over dp x ep). parallel/moe.py.
    ep_size: int = 1
    # Pipeline parallelism over the mesh axis 'pp': the stacked layer dim is
    # sharded (each stage owns num_layers/pp layers) and microbatches flow
    # through a GPipe schedule built from ONE lax.scan over pipeline steps
    # with a ppermute between stages. JAX autodiff transposes the schedule
    # into the backward pipeline (reverse ppermute, reverse time) for free.
    # No reference counterpart (SURVEY §2.4 "PP ❌"). Bubble fraction is
    # (pp-1)/(microbatches+pp-1); raise pp_microbatches to amortise it.
    pp_size: int = 1
    pp_microbatches: int = 0  # 0 -> pp_size (the minimum that fills the pipe)
    # Pipeline schedule (VERDICT r3 #7):
    #   'gpipe'       — contiguous layer blocks, bubble (pp-1)/(M+pp-1).
    #   'interleaved' — Megatron-style virtual stages: each device owns
    #     pp_virtual NON-contiguous layer blocks assigned round-robin
    #     (device p runs virtual stages p, pp+p, 2pp+p, ...), and every
    #     microbatch circulates pp_virtual times around the same ring.
    #     Bubble shrinks to (pp-1)/(pp_virtual*M + pp-1) — the fill/drain
    #     cost amortises over pp_virtual x more ring steps — at the price
    #     of pp_virtual x more ppermute hops of the (mb, t, d) carry (the
    #     standard interleaved trade-off: less bubble, more wire).
    pp_schedule: str = "gpipe"
    pp_virtual: int = 2  # virtual stages per device ('interleaved' only)
    # Rematerialise each pipeline STEP: backward-pipeline residuals shrink
    # to the (mb, t, d) step carries (layer internals recompute), cutting
    # the M-proportional activation footprint — the practical core of a
    # 1F1B schedule's memory advantage, expressed scan-side (the schedule
    # itself stays GPipe; autodiff derives the reverse pipeline).
    pp_remat_steps: bool = False
    # Context parallelism: shard the sequence dim over the mesh axis 'cp'
    # (absent from the reference — SURVEY §5.7 documents it has no
    # long-context story at all). cp_impl: 'ring' rotates KV chunks around
    # the cp ring with online-softmax combination; 'ulysses' all-to-alls
    # heads<->sequence and runs the dense kernel on the full sequence.
    cp_size: int = 1
    cp_impl: str = "ring"
    # cp_layout='zigzag' feeds each cp shard an equally early+late pair of
    # sequence sub-chunks (ops/ring_attention.zigzag_perm), balancing the
    # causal ring's per-step work ~2x vs contiguous chunks. Pure input
    # permutation: ring attention masks by the carried global positions, so
    # both layouts are exact. Ring-only — Ulysses gathers the sequence in
    # rank order and runs a position-oblivious triangular mask, which a
    # permuted layout would silently break.
    cp_layout: str = "contiguous"
    # Megatron-style sequence parallelism over 'tp' (absent from the
    # reference: its norms are replicated and inter-block activations are
    # full-size on every rank — SURVEY §2.4 "SP ❌"). When on, activations
    # between sublayers are sequence-sharded over tp: the per-sublayer
    # all-reduce splits into a reduce-scatter (row-linear output) and an
    # all-gather (next column-linear input) — same bytes on the wire, but
    # norms/residuals compute on t/tp tokens and inter-block activation
    # memory drops by 1/tp. Composes with cp (t is sharded over cp first,
    # then tp). 'auto' (the default) is on at tp_size > 1 for a dense
    # model whose sequence the tp ranks split evenly, decided per trace
    # (`resolve_tp_layout`); True / False are taken as given.
    sequence_parallel: "bool | str" = "auto"
    # Communication overlap for the tp collectives (requires
    # sequence_parallel): 'ring' swaps the monolithic per-sublayer
    # all-gather/reduce-scatter for ring-decomposed collective matmuls
    # (ops/overlap.py) — each ppermute hop hides under the partial dot of
    # the chunk already in hand, fwd and bwd. 'off' is the monolithic
    # path; 'auto' (the default) is 'ring' wherever 'auto' sequence
    # parallelism turned itself on and pp_size == 1. Composes with
    # dp/cp/pp; under a pp mesh the ring's ppermutes must execute on EVERY
    # pipeline step (collective-permute lowers with a global participant
    # list), so the dense segments run ungated and bubble steps burn their
    # FLOPs — garbage flows only into garbage (see _pipeline_layers) —
    # trading bubble compute for hidden wire. Not yet composed with MoE
    # (the router needs the full-token gather the ring never materialises).
    tp_overlap: str = "auto"
    # Rematerialise each decoder layer in the backward pass instead of saving
    # its activations (the naive O(T^2) attention otherwise stores
    # (L, b, heads, t, t) softmax residuals — 11.7 GiB for the reference's
    # 45M config at b=32, t=1000, which OOMs a 16G v5e chip). Trading these
    # HBM residuals for recompute FLOPs is the standard TPU playbook
    # (SURVEY §0 / scaling-book); the reference has no analogue (PyTorch
    # keeps all residuals and simply needs a bigger GPU).
    #   True   — rung 0: full per-layer remat (lowest memory, the whole
    #            layer forward runs again in the backward)
    #   a rung of REMAT_LADDER by name — keep that rung's named residuals
    #            and recompute the rest; "dots", the top rung, keeps every
    #            matmul output a layer's backward reads (needs flash
    #            attention or short t: the XLA attention path's softmax
    #            residual is O(t^2) and is recomputed, never kept)
    #   groups of the ladder joined ('true+flash+dots') — keep just those
    #   False  — no remat (reference behaviour; OOMs the 45M b32xt1000 run
    #            on a 16G chip)
    # 'auto' (the default) keeps what the chip has room to keep: the groups
    # of REMAT_LADDER ('attn_proj', 'ffn', 'flash', 'dots') that fit, asked
    # in that order, one that does not fit passed over (a rung's name where
    # they are its prefix), found while the model is traced from the
    # per-shard shapes and the device's memory_stats
    # (training/memory.select_remat_traced). A backend with no memory_stats
    # (the CPU) gets rung 0 unless `remat_budget_gib` names the HBM to size
    # against.
    remat: "bool | str" = "auto"
    remat_budget_gib: "float | None" = None
    # Pad-aware sequence bucketing: when the caller pads its (b, t) batch up
    # to a bucket boundary (e.g. t=1000 real tokens in a t=1024 buffer so
    # every matmul tiles cleanly on the 8x128 vector lanes AND the flash
    # kernel's internal padding vanishes), set attn_t_real to the REAL
    # token count. Attention then does only ~t_real work (the kernels skip
    # fully-dead tiles and emit exact zeros/zero-grads for pad rows), and
    # the CE loss masks the pad targets via IGNORE_INDEX as usual. None =
    # every position is real (the default, and the only mode under cp > 1 —
    # the ring/ulysses paths shard the sequence and carry their own
    # position masking).
    attn_t_real: "int | None" = None
    # ZeRO-3 (training/zero.py): when set to a mesh axis name (normally
    # 'dp'), the layer body ring-all-gathers each layer's dp-sharded param
    # leaves on entry — INSIDE the remat boundary, so the gathered weights
    # are recomputed (never saved as backward residuals) and peak param
    # HBM stays full/dp + one layer. Only `build_zero3_grad_fn` sets this
    # (via dataclasses.replace on its private model copy); every other
    # path keeps params at model.specs() layouts and must leave it None.
    zero3_axis: "str | None" = None
    # The seed of the noise a family draws inside its step (`draws_noise`:
    # folded with the optimizer state's step count). No other family reads
    # it.
    noise_seed: int = 0

    family = None               # the family's name: its key in models.FAMILIES
    uses_rope = True            # RoPE on q/k (vs positions at the embedding)
    attn_norm_key = "norm1"     # the pre-attention norm's key in `_mods`
    ffn_norm_key = "norm2"      # the pre-FFN norm's
    # a family that norms a sublayer's OUTPUT before the residual adds it
    # (x + N(attn(N(x))), x + N(ffn(N(x)))) names those norms' keys too
    post_attn_norm_key = None
    post_ffn_norm_key = None
    # what the embedding's rows are multiplied by as they enter (None: 1)
    embed_scale = None
    # Three scalars a published configuration may state, each applied in ONE
    # place and each None where the program is the one it has always been:
    # what BOTH sublayers' outputs are multiplied by, in the compute dtype,
    # before the residual adds them, `x + s F(N(x))` (`_layer_body`'s
    # `join`: the forward, the remat and the pipeline paths all add there);
    residual_scale = None
    # the softmax's scale where it is not `1 / sqrt(head_dim)`: the flash
    # kernels and `ops/attention.py` scale by that themselves, so `_qkv`
    # multiplies q by `softmax_scale x sqrt(head_dim)` before the dispatch
    # (as `parallel/mla.LatentAttention.softmax_scale` is folded);
    softmax_scale = None
    # what the head's logits are multiplied by before the loss reads them
    # (`_masked_logits`: every exit, the vocabulary-parallel CE after it)
    logit_scale = None
    # the kinds of attention layer (`_kind`) that take NO positions at q
    # and k, of a family with two kinds of attention layer over one
    # parameter tree; the mask by kind is `_attn_mask`
    unrotated_kinds = ()
    # the speed of the rule that updates the routers' selection bias after
    # every optimizer step from the step's own counts
    # (training/optim.router_bias_step); None: the family's configuration
    # publishes none and nothing updates a bias it may hold
    router_bias_speed = None
    # does an expert layer's router read the LAYER'S INPUT (the residual
    # stream as it enters the layer, before the attention half and before
    # any norm) where its experts read the normed post-attention stream:
    # `_layer_body` then carries that input past the attention half to
    # `_ffn` (it is the remat boundary's own operand: no new saved tensor),
    # and the router's gradient enters the residual stream before attention
    router_reads_layer_input = False
    # Hyper-connections: the mixer (`parallel/hyper.StreamMixer`) of a family
    # whose residual state is n STREAMS, (n, b, t, d) from the embedding's
    # rows n times to the head. A sublayer F then reads the mixer's weighted
    # sum of the streams and its output joins them through the mixer's maps,
    # X' = H X + post F(sum_i pre_i X[i]), where every other family computes
    # x + F(x): `_layer_body`'s two joints, what `_trunk` carries from layer
    # to layer and where `_head` leaves. Every layer then holds two mixers'
    # parameters (`hc_attn`, `hc_ffn`) and the tree one more for the exit
    # (`hc_exit`); the layers count `StreamMixer.counters`, one row a layer.
    # None: one stream, and the program is the one it has always been
    stream_mixer = None
    # Is a layer ONE norm (`attn_norm_key`) and ONE sublayer, `x +
    # sublayer(norm(x))`, where every other family's is a mixer and then a
    # feed-forward part, each behind its norm (Nemotron-H: a layer is a
    # state-space mixer, an attention or an expert FFN, and the pattern
    # says which). What a layer's parameters hold says which sublayer it is,
    # as ever: the routed experts (`moe`) make it the feed-forward part
    # with no mixer before it, a `wo` the stack's attention and neither the
    # family's own mixer (`_mix`), each with no feed-forward part behind
    # it. `_segments` then lists one norm and one sublayer's modules a key,
    # which is all `init`, `specs`, the counters' rows (a layer that counts
    # nothing has no row: `_rows_in_order`) and `tagged_layers` read.
    # False: the layer every other family has, the program it has always been
    one_sublayer = False
    # How often a step RUNS THE PATTERN, over the same weights (a looped
    # language model, Ouro's `total_ut_steps`): `_trunk` then scans R passes
    # around the scan of layers, `final_norm` after every pass (the normed
    # state is what the next pass reads) and hands back the R normed states
    # (R, b, t, d); every state leaves through the one head (an exit a
    # pass) and the loss weighs the exits' per-token CEs by the learned
    # exit gate's distribution over the passes (`_loop_loss`; the tree then
    # holds `exit_gate`, d + 1 float32 parameters, replicated), less
    # `exit_entropy_coef` times that distribution's entropy. A weight's
    # gradient is then a sum over R uses, accumulated in float32 by the
    # outer scan's transpose. `training/memory.py` reads it (R x L kept
    # layer inputs, the R states), `flops_per_step` is the family's. The
    # family declares it ONCE, from its configuration alone (`passes(cfg)`,
    # which `obs/attribution.py` asks with no model in hand); None: the
    # pattern runs once, the program it has always been
    @staticmethod
    def passes(cfg: ModelConfig) -> "int | None":
        return None

    @property
    def loop_steps(self) -> "int | None":
        return self.passes(self.cfg)

    exit_entropy_coef = 0.0
    # Do layers LEAVE VALUES FOR LATER LAYERS beside the residual stream (a
    # decoder-hybrid-decoder: ONE layer's scan output and ONE layer's keys
    # and values, read by every layer above). A layer whose mixer is the
    # family's (`_mix_sharing`) is then handed `shared`, a dict of what the
    # blocks before its own left, and may hand back more (`left`, a dict);
    # what the LAST layer of a SEGMENT leaves joins `shared` for every
    # later block (a layer of a period leaves nothing: a period's layers
    # are alike). A value is made once: it is an OUTPUT of its maker's
    # `jax.checkpoint` (kept whatever the rung, never remade) and an INPUT
    # of each reader's (kept by reference), a constant of a later period's
    # scan, whose transpose sums the readers' cotangents into it; the sum
    # reaches the maker beside its own use's. A pipeline cut between a
    # maker and a reader is not written (the family refuses `pp_size`).
    # False: a layer hands on the stream alone, the program it has always
    # been
    shares_values = False
    # the jax.named_scope of `_qkv` and `_attn_project` in a device trace
    attn_scope = None
    # ---- what a family may say it cannot do (refused with a message where
    # it is asked for; every family that says nothing can do all of it) ----
    # the fields of the stack it does not run with: a name of REFUSABLE ->
    # the family's own reason ("" for none), refused where the model is built
    refuses = {}
    decodable = True            # models/decode.py and the serving engine
    hand_reduced_grads = True   # training/zero.py's builders (ZeRO 2/3,
                                # the bucketed reducer)
    # does the loss draw noise inside the step: `make_loss`'s function then
    # takes the optimizer state's step count as a fifth argument
    # (training/train_step.py), draws with the family's `_draw_noise` and
    # hands `loss_shard` the draw (`noise`)
    draws_noise = False
    # the ModelConfig field that carries facts only this family reads
    # (None: the config's own fields are all it needs). A family that names
    # one holds a SHARE of the routed experts its router scores
    config_extra = None
    # The LAYER PATTERN, declared once: the blocks the forward runs, in
    # order. One stack, one remat policy, one `_layer_body`; what a layer's
    # mixer and FFN are follows from what its parameters hold
    # (`_layer_body`, `_ffn`). A block is either
    #   * a SEGMENT: the key (a str) of the parameter tree that holds its
    #     layers stacked (layers, ...), scanned once; or
    #   * a PERIOD that repeats: a tuple of (parameter key, layers a
    #     period) pairs in the order a period runs them, each key holding
    #     its layers stacked (periods, layers a period, ...): ONE scan over
    #     periods whose body scans each key's layers in turn
    #     (`_scan_periods`), so a cut to one period and the published depth
    #     are the same program.
    # A family derives it from its configuration: leading dense layers and
    # then expert layers are two segments (`mla_moe`), one period that
    # repeats is one block (`gdn_moe`), a leading segment and then periods
    # of two lengths are three (`conv_moe`). A pipeline splits one segment
    # only.
    _pattern = ("layers",)
    # does the loss add the Switch load-balance and z terms of
    # parallel/moe.MoEFFN's router sums. A family whose router balances
    # without one says no, and its expert layers hand back COUNTERS: one
    # row a layer (`_fold_aux`), summed over the batch axes (`_counters`)
    _router_aux_losses = True
    # the counters that are not sums over the batch: name -> the collective
    # that joins the shards' rows (`_counters`). The stack's own are its
    # stream mixers' (maxima and a mean over the tokens); a family whose
    # mixer counts (`_mix_counted`) names its own
    _counter_reduces = {"hc_sinkhorn_err": lax.pmax,
                        "hc_colsum_err": lax.pmax,
                        "hc_res_offdiag": lax.pmean}
    # A LAYER'S OWN LOSS: the counters among a layer's aux that are terms
    # of the step's loss, name of the term's sum over the rows -> name of
    # the counter that counts those rows. `_extra_loss` adds every layer's
    # `sum / rows`, both summed over the batch axes first, to the main CE
    # with weight 1; the term carries the gradient the layer gave it (a
    # layer that trains some of its parameters on a loss of its own splits
    # them off with stop-gradients where it computes the term:
    # parallel/dsa.py). {}: no layer has one
    layer_losses = {}

    def __post_init__(self):
        cfg, tp = self.cfg, self.tp_size
        if self.config_extra:
            if getattr(cfg, self.config_extra) is None:
                raise ValueError(f"the {self.family} family needs "
                                 f"cfg.{self.config_extra} (its facts: "
                                 f"config.py)")
            if not cfg.num_experts and hasattr(
                    getattr(cfg, self.config_extra), "experts_held"):
                raise ValueError(
                    f"the {self.family} family needs cfg.num_experts > 0 "
                    f"(the routed experts its router scores)")
        self._check_facts()
        self._refuse()
        if self.stream_mixer is not None and (
                self.pp_size > 1 or self.router_reads_layer_input):
            raise ValueError(
                f"the {self.family} family carries "
                f"{self.residual_streams} residual streams: the pipeline's "
                f"carries and a router that reads the layer's input take "
                f"one")
        if self.residual_scale is not None and self.stream_mixer is not None:
            raise ValueError(
                f"the {self.family} family scales its sublayers' outputs "
                f"before the residual add: the stream mixers' `post` map "
                f"joins them by its own weights")
        if self.one_sublayer and (self.stream_mixer is not None
                                  or self.router_reads_layer_input):
            raise ValueError(
                f"the {self.family} family's layers are one sublayer each: "
                f"the stream mixers' two joints and a router that reads "
                f"what entered the attention half take a layer of two")
        if self.loop_steps is not None and (
                self.loop_steps < 1 or self.is_moe or self.stream_mixer
                or self.draws_noise or self._pattern != self._layer_keys[:1]):
            raise ValueError(
                f"the {self.family} family passes its stack "
                f"{self.loop_steps} times a step: at least once, over ONE "
                f"segment of dense layers of one residual stream and a "
                f"loss that draws no noise (the layers' counters, the "
                f"streams' exit and a weight a position have no R exits to "
                f"join; the walk of the passes indexes one stack)")
        validate_remat(self.remat)
        if cfg.num_heads % tp != 0:
            raise ValueError(f"num_heads {cfg.num_heads} not divisible by tp_size {tp}")
        if cfg.attn_dim % tp != 0 or cfg.ffn_dim % tp != 0:
            raise ValueError(
                f"attn_dim {cfg.attn_dim} and ffn_dim {cfg.ffn_dim} must be "
                f"divisible by tp_size {tp}")
        if cfg.num_heads % cfg.kv_heads != 0:
            raise ValueError(f"num_heads {cfg.num_heads} must be a multiple "
                             f"of num_kv_heads {cfg.kv_heads}")
        if cfg.kv_heads % tp != 0:
            raise ValueError(f"num_kv_heads {cfg.kv_heads} not divisible by "
                             f"tp_size {tp}")
        validate_cp(cfg, tp, self.cp_size, self.cp_impl, self.cp_layout)
        validate_tp_overlap(self.tp_overlap, self.sequence_parallel,
                            cfg.num_experts)
        if not cfg.num_experts and self.ep_size > 1:
            raise ValueError("ep_size > 1 requires cfg.num_experts > 0 "
                             "(a dense model has nothing to shard over 'ep'; "
                             "use dp for a pure data axis)")
        validate_pp(cfg.num_layers, self.pp_size, self.pp_microbatches,
                    self.pp_schedule, self.pp_virtual)
        validate_t_real(self.attn_t_real, self.cp_size, cfg.num_experts)

    def _check_facts(self) -> None:
        """A family's own checks of its facts, before anything else."""

    @classmethod
    def owns_facts(cls, cfg: ModelConfig) -> bool:
        """Of the families that read ONE `config_extra` field: are `cfg`'s
        facts this family's (`models.facts_family`, `train.py`'s preset
        check). A field only one family reads is that family's."""
        return True

    def _refuse(self) -> None:
        """Raise for the first of `refuses` this model asks for."""
        for what, why in self.refuses.items():
            if REFUSABLE[what](self):
                raise ValueError(
                    f"the {self.family} family does not run with {what}"
                    + (f" ({why})" if why else ""))

    @property
    def d(self) -> int:
        return self.cfg.attn_dim

    @property
    def vocab_padded(self) -> int:
        return self.cfg.padded_vocab_size(self.tp_size)

    @property
    def head_dim(self) -> int:
        """The attention heads' width: the model's width over its heads,
        unless the family's facts name it."""
        return self.cfg.head_dim

    @property
    def kv_dim(self) -> int:
        """Output width of wk / wv."""
        return self.cfg.kv_heads * self.head_dim

    @property
    def num_local_heads(self) -> int:
        assert self.cfg.num_heads % self.tp_size == 0, (
            f"num_heads {self.cfg.num_heads} not divisible by tp {self.tp_size}")
        return self.cfg.num_heads // self.tp_size

    @property
    def num_local_kv_heads(self) -> int:
        return self.cfg.kv_heads // self.tp_size

    @property
    def is_moe(self) -> bool:
        return self.cfg.num_experts > 0

    @property
    def _layer_keys(self) -> Tuple[str, ...]:
        """The keys of the parameter tree that hold stacked layers, in the
        order `_pattern` runs them."""
        return tuple(key for block in self._pattern for key in (
            (block,) if isinstance(block, str) else (k for k, _ in block)))

    @property
    def _segments(self):
        """(parameter key, layers, module names or None for all of `_mods`)
        of every stacked key: what `init` and `specs` make."""
        return (("layers", self.cfg.num_layers, None),)

    @property
    def _layers_a_period(self) -> Dict[str, int]:
        """key -> layers a period, of the keys `_pattern`'s periods hold."""
        return {key: n for block in self._pattern
                if not isinstance(block, str) for key, n in block}

    # what training/memory.py asks beside the config's widths
    @property
    def stacked_layers(self) -> int:
        """Layers whose input the backward keeps, over all segments."""
        return self.cfg.num_layers

    @property
    def tagged_layers(self) -> Dict[str, int]:
        """Name of REMAT_LADDER -> the stacked layers that tag it, over all
        segments (`LADDER_MADE_BY`): what a rung's stack of that name is
        long. A drawn family tags the MLP's names in its dense layers only
        and the flash names in its attention layers only."""
        held = [(layers, self._segment_mods(names))
                for _, layers, names in self._segments]
        return {name: sum(layers for layers, mods in held
                          if any(m in mods for m in makers))
                for name, makers in LADDER_MADE_BY.items()}

    @property
    def v_head_dim(self) -> int:
        """The width of a head's attention OUTPUT (`flash_out`): the
        heads', unless the family's v is of another width than q and k."""
        return self.head_dim

    layer_extra_elems_per_token = 0.0   # see training/memory.step_bytes
    # elements a token of the values layers leave for later layers
    # (`shares_values`), in the compute dtype: kept whatever the rung
    shared_elems_per_token = 0.0
    # bytes a (row, key) pair of a sequence that a layer keeps under the name
    # `flash_lse` beside the heads' lse (a mask that is data: models/dsa_moe)
    flash_lse_bytes_per_pair = 0.0
    head_rows_share = 1.0       # the part of a batch's rows the head reads

    @property
    def residual_streams(self) -> int:
        """How many d-wide streams a kept layer input is (`stream_mixer`)."""
        return self.stream_mixer.n if self.stream_mixer else 1

    @classmethod
    def num_params(cls, cfg: ModelConfig) -> int:
        """The leaves of `init`'s tree: the sum of the family's
        `param_counts(cfg)`, its parameters by part (the experts HELD)."""
        return sum(cls.param_counts(cfg).values())

    @staticmethod
    def flops_per_step(cfg, batch, seqlen, num_params) -> float:
        """Model FLOPs of one forward and backward (no remat recompute): 6
        N_active a token and the 12 L h T^2 hd attention term, `num_params`
        the family's count of `cfg`. Of parallel/moe.MoEFFN's experts only
        the top_k a token is routed through count (dropped tokens are
        ignored). A family whose layers are not these states its own."""
        n = num_params
        if cfg.num_experts:
            inactive = ((cfg.num_experts - cfg.moe_top_k)
                        * 3 * cfg.attn_dim * cfg.ffn_dim)
            n -= cfg.num_layers * max(0, inactive)
        return (6 * n * batch * seqlen
                + 12 * cfg.num_layers * batch * cfg.num_heads
                * seqlen * seqlen * cfg.head_dim)

    def tp_layout(self, t_local: int) -> Tuple[bool, str]:
        """(sequence_parallel, tp_overlap) as a batch of cp-local sequence
        length `t_local` is traced: `resolve_tp_layout` on this model."""
        return resolve_tp_layout(
            self.sequence_parallel, self.tp_overlap, tp_size=self.tp_size,
            t_local=t_local, dense=not self.is_moe, pp_size=self.pp_size)

    def _resolved(self, t_local: int):
        """This model with both 'auto's replaced for `t_local`: what the
        per-shard entry points (`_forward_with_aux`, `loss_shard`) rebind
        `self` to, so everything under them reads plain values."""
        sp, ov = self.tp_layout(t_local)
        if (sp, ov) == (self.sequence_parallel, self.tp_overlap):
            return self
        return dataclasses.replace(self, sequence_parallel=sp, tp_overlap=ov)

    @property
    def _linear_overlap(self) -> str:
        # The linears read `overlap` only on the seq-sharded layouts, which
        # a model that still says 'auto' never asks for: the decoder
        # (models/decode.py) runs them replicated, and a training trace
        # builds them from the `_resolved` model.
        return "off" if self.tp_overlap == "auto" else self.tp_overlap

    @property
    def _tp_sublayers(self) -> TPSublayers:
        """Read on a `_resolved` model (inside a trace)."""
        sp = self.sequence_parallel
        return TPSublayers(self._mods, sp, sp and self.tp_overlap in _RING,
                           self.tp_overlap == "ring_q")

    def _exchanges_dp_grads(self, x: jax.Array) -> bool:
        """Read inside a trace: does the layer body hand its weights over
        through `exchange_grads`? `resolve_dp_reduce` on the axis size the
        trace sees, where the layer's input `x` varies over 'dp': a caller
        that feeds every replica the same rows (evaluate.py's greedy
        decode) has nothing to sum over it, and under check_vma=False
        nothing is typed varying (the hand-reduced builders of
        training/zero.py, which sum once, by hand)."""
        return (resolve_dp_reduce(dp_size=lax.axis_size("dp"),
                                  dense=not self.is_moe,
                                  pp_size=self.pp_size) == "exchange"
                and "dp" in jax.typeof(x).vma)

    # ---- top-level modules: a family overrides the one that differs ----

    @functools.cached_property
    def embedding(self) -> VocabParallelEmbedding:
        return VocabParallelEmbedding(self.cfg.vocab_size, self.d,
                                      tp_size=self.tp_size)

    @property
    def final_norm(self):
        """The final norm is a layer's norm."""
        return self._mods[self.ffn_norm_key]

    @functools.cached_property
    def lm_head(self) -> ColumnParallelLinear:
        """The head of a family that does not tie it to the embedding."""
        return ColumnParallelLinear(self.d, self.vocab_padded,
                                    add_bias=False, gather_output=False)

    # ---- parameter tree ----

    def init(self, key: jax.Array) -> Params:
        """Full (global) parameter pytree, float32: embedding, `_segments`
        stacked for scan, final norm, the head unless tied, `_init_more`."""
        head = {} if self.tied_head else {"lm_head": self._init_head(key)}
        leave = self._exit_leaves(lambda m: m.init(fold(key, "hc_exit")))
        return {"embedding": self.embedding.init(fold(key, "embedding")),
                **{name: self._init_layers(key, name, count, names)
                   for name, count, names in self._segments},
                "norm": self.final_norm.init(fold(key, "norm")),
                **head, **leave, **self._exit_gate(
                    lambda: self._init_exit_gate(fold(key, "exit_gate"))),
                **self._init_more(key)}

    def specs(self) -> Params:
        """PartitionSpec pytree matching `init`'s structure."""
        head = {} if self.tied_head else {"lm_head": self.lm_head.specs()}
        leave = self._exit_leaves(lambda m: m.specs())
        return {"embedding": self.embedding.specs(),
                **{name: self._layer_specs(names, name)
                   for name, _, names in self._segments},
                "norm": self.final_norm.specs(),
                **head, **leave,
                **self._exit_gate(lambda: {"weight": P(None), "bias": P()}),
                **self._more_specs()}

    def _init_more(self, key: jax.Array) -> Params:
        """Top-level groups of the family's own, after the head."""
        return {}

    def _exit_gate(self, make) -> Params:
        """`{"exit_gate": make()}` where the stack is passed `loop_steps`
        times, else nothing."""
        return {} if self.loop_steps is None else {"exit_gate": make()}

    def _init_exit_gate(self, key: jax.Array) -> Params:
        """The exit gate, a `Linear(d, 1)` with bias on a pass's normed
        state, float32 and replicated: a linear's uniform draw, so a fresh
        gate's `lam` lies about 1/2."""
        bound = self.d ** -0.5
        kw, kb = jax.random.split(key)
        draw = lambda k, shape: jax.random.uniform(
            k, shape, jnp.float32, -bound, bound)
        return {"weight": draw(kw, (self.d,)), "bias": draw(kb, ())}

    def _more_specs(self) -> Params:
        return {}

    def _init_head(self, key: jax.Array) -> Params:
        lm_head = self.lm_head.init(fold(key, "lm_head"))
        if self.vocab_padded != self.cfg.vocab_size:
            # zero the padded output columns so checkpoints stay
            # permutation-stable; padded logits are masked to NEG_INF anyway.
            w = lm_head["weight"]
            mask = (jnp.arange(self.vocab_padded)
                    < self.cfg.vocab_size)[None, :]
            lm_head["weight"] = jnp.where(mask, w, 0.0)
            if "bias" in lm_head:
                lm_head["bias"] = jnp.where(mask[0], lm_head["bias"], 0.0)
        return lm_head

    def _init_layers(self, key: jax.Array, segment: str = "layers",
                     count: "int | None" = None, names=None) -> Params:
        """`_mods`' params stacked along a leading num_layers axis for scan
        (in the schedule's layout under the interleaved pipeline). A family
        with a layer pattern calls it once a segment: `count` layers of the
        modules `names`, keyed by the segment's name."""
        layer_keys = jax.random.split(
            fold(key, segment),
            self.cfg.num_layers if count is None else count)
        mods = self._segment_mods(names)

        def one_layer(k: jax.Array) -> Params:
            return {name: mod.init(fold(k, name))
                    for name, mod in mods.items()}

        layers = jax.vmap(one_layer)(layer_keys)
        if self._interleaved:
            layers = self._layers_to_schedule(layers)
        a_period = self._layers_a_period.get(segment)
        if a_period:
            # (periods, layers a period, ...): `_scan_periods`
            layers = jax.tree.map(
                lambda a: a.reshape(-1, a_period, *a.shape[1:]), layers)
        return layers

    def _segment_mods(self, names=None) -> Dict[str, Any]:
        """The modules a layer's parameters are made of: `names` of `_mods`
        and, where the residual state is streams, the two mixers."""
        mods = (self._mods if names is None
                else {n: self._mods[n] for n in names})
        if self.stream_mixer:
            mods = {**mods, "hc_attn": self.stream_mixer,
                    "hc_ffn": self.stream_mixer}
        return mods

    @property
    def exit_mixer(self):
        """The mixer behind the last layer: its `pre` alone."""
        return dataclasses.replace(self.stream_mixer, exit_only=True)

    def _exit_leaves(self, make) -> Params:
        """`{"hc_exit": make(the exit mixer)}` where the residual state is
        streams, else nothing: the group a tree (or a family's module with
        a head of its own) holds for where its streams leave."""
        return {"hc_exit": make(self.exit_mixer)} if self.stream_mixer else {}

    @property
    def _interleaved(self) -> bool:
        return self.pp_size > 1 and self.pp_schedule == "interleaved"

    def _layers_to_schedule(self, layers: Params) -> Params:
        """Canonical stacked layers (L, ...) -> the interleaved layout
        (V, pp, Lv, ...). Row-major flatten of (v, p, l) is
        (v*pp + p)*Lv + l — exactly the execution order of virtual stage
        v*pp + p — so the two layouts are plain reshapes of each other and
        checkpoints stay schedule-independent (`to_canonical`)."""
        V, pp = self.pp_virtual, self.pp_size
        Lv = self.cfg.num_layers // (V * pp)
        return jax.tree.map(
            lambda a: a.reshape(V, pp, Lv, *a.shape[1:]), layers)

    def _layers_to_canonical(self, layers: Params) -> Params:
        L = self.cfg.num_layers
        return jax.tree.map(lambda a: a.reshape(L, *a.shape[3:]), layers)

    def to_canonical(self, params: Params) -> Params:
        """Params with layers in the canonical (num_layers, ...) stack —
        identity unless this model is interleaved-pipelined. Checkpoints
        are always saved canonical so any mesh/schedule can reload them."""
        if not self._interleaved:
            return params
        out = dict(params)
        out["layers"] = self._layers_to_canonical(params["layers"])
        return out

    def from_canonical(self, params: Params) -> Params:
        """Inverse of `to_canonical` (e.g. a checkpoint or an oracle's
        params entering an interleaved model)."""
        if not self._interleaved:
            return params
        out = dict(params)
        out["layers"] = self._layers_to_schedule(params["layers"])
        return out

    def canonical_specs(self) -> Params:
        """PartitionSpec tree for the canonical layout — what checkpoints
        are saved/loaded with (the gpipe specs of this same model)."""
        if not self._interleaved:
            return self.specs()
        return dataclasses.replace(self, pp_schedule="gpipe").specs()

    def _layer_specs(self, names=None, segment: str = "layers") -> Params:
        """PartitionSpecs matching `_init_layers`."""
        lead = ("pp" if self.pp_size > 1 else None,)
        if segment in self._layers_a_period:
            lead = (None, None)     # periods, layers a period

        def stack(spec_dict: Params) -> Params:
            # stacked num_layers axis: sharded over 'pp' when pipelining
            # (each stage owns its num_layers/pp slice — contiguous for
            # gpipe; the (V, pp, Lv) dim-1 slice = V round-robin virtual
            # blocks for the interleaved schedule), else unsharded
            if self._interleaved:
                return jax.tree.map(lambda s: P(None, "pp", None, *s),
                                    spec_dict,
                                    is_leaf=lambda x: isinstance(x, P))
            return jax.tree.map(lambda s: P(*lead, *s), spec_dict,
                                is_leaf=lambda x: isinstance(x, P))
        return {name: stack(mod.specs())
                for name, mod in self._segment_mods(names).items()}

    def shardings(self, mesh: Mesh) -> Params:
        return jax.tree.map(lambda s: NamedSharding(mesh, s), self.specs(),
                            is_leaf=lambda x: isinstance(x, P))

    # ---- per-shard forward (call inside shard_map) ----

    def _layer_body(self, x: jax.Array, layer_params: Params, layer_pos,
                    pos: jax.Array, dtype, live=None,
                    kind: "str | None" = None,
                    shared: "Params | None" = None) -> jax.Array:
        """One decoder layer: x + attn(norm(x)), then x + mlp(norm(x)) or,
        with cfg.num_experts > 0, x + MoE(norm(x)) (parallel/moe.py); a
        family that names post-norms adds N(attn(..)) and N(mlp(..)); a
        family whose layers are `one_sublayer` runs the one of the two its
        parameters hold.
        `layer_pos` is what the family's `_positions` hands every layer;
        `kind` is the kind of the layer's `_pattern` key (`_kind`; static):
        a family with two kinds of attention layer over one parameter tree
        tells them apart by it (`_attn_mask`, `unrotated_kinds`).
        `shared` (a family that `shares_values`): what earlier blocks left;
        the layer then returns `(x, (aux, what it leaves or None))`. A
        layer's `told` (`_told`) are not parameters and leave the tree here.

        `live` (optional scalar bool) is the
        pipeline-bubble gate used ONLY on pp meshes with ring CP: the dense
        segments (projections / attention epilogue / FFN) wrap in
        `lax.cond(live, ...)` — their collectives (tp psums/gathers, ep
        all_to_alls) lower with per-group participant lists, and every
        member of those groups shares the pp stage, so the branch is
        uniform — while the ring's ppermutes run UNCONDITIONALLY (XLA
        collective-permute lists every device as a participant; a measured
        deadlock otherwise) with the per-block MXU work gated inside the
        ring (ops/ring_attention.py). Bubble steps therefore cost only the
        ring's wire traffic, not layer FLOPs (VERDICT r3 #3)."""
        told = layer_params.get("told")
        if told is not None:
            layer_params = {k: v for k, v in layer_params.items()
                            if k != "told"}
        if self.zero3_axis:
            # ZeRO-3: this layer's dp-sharded leaves gather here, inside
            # the remat boundary, so the gathered weights are transient in
            # the forward and REPLAYED (not saved) for the backward; the
            # gather's transpose reduce-scatters the weight grads back to
            # this rank's shard. training/zero.py owns the layout rule.
            from ..training.zero import zero3_layer_gather
            layer_params = zero3_layer_gather(self, layer_params,
                                              self.zero3_axis)
        if self._exchanges_dp_grads(x):
            # every module casts its leaves to the compute dtype before it
            # uses them; cast once here, so the cotangent the exchange sums
            # is the one the psum summed (bf16 on the wire where the compute
            # is bf16, accumulated in float32 past the cast's transpose)
            layer_params = exchange_grads(
                jax.tree.map(lambda a: a.astype(dtype), layer_params), "dp")
        m, tp = self._mods, self._tp_sublayers
        # full (cp-local) sequence length, not x.shape[1]
        b, t = pos.shape
        mixer, mixed = self.stream_mixer, {}

        def read(x, name):
            """What the sublayer behind the joint `name` reads of the
            residual state: the stream or, of several, the mixer's sum."""
            if mixer is None:
                return x
            mixed[name] = mixer.maps(layer_params[name], x)
            return mixer.pre(mixed[name], x)

        def join(x, y, name):
            """The residual state past the sublayer whose output is `y`."""
            if mixer is None:
                if self.residual_scale is not None:
                    y = y * jnp.asarray(self.residual_scale, y.dtype)
                return x + y
            return mixer.post(mixed[name], x, y)

        def qkv(x):
            norm = self.attn_norm_key
            y = tp.gather(m[norm].apply(layer_params[norm],
                                        read(x, "hc_attn")))
            with self._attn_scoped():
                return self._qkv(
                    layer_params, y, tp,
                    () if kind in self.unrotated_kinds else layer_pos,
                    dtype, b, t)

        def attn_out(args):
            x, o, *gate = args
            # (b, heads, t, v's width) -> (b, t, heads * width)
            o = o.transpose(0, 2, 1, 3).reshape(
                b, t, self.num_local_heads * o.shape[-1])
            with self._attn_scoped():
                a = self._attn_project(layer_params, o, tp, dtype, *gate)
            return ffn_half(x, a)

        def ffn_half(x, a, counted=None):
            if self.tp_size > 1:
                # named PAST the row-linear's reduce, so keeping it drops
                # the recomputed forward's collective with the matmul;
                # not named where there is no reduce (REMAT_LADDER)
                a = checkpoint_name(a, "attn_proj")
            if norm := self.post_attn_norm_key:
                a = m[norm].apply(layer_params[norm], a)
            # (what entered the layer, for a router that reads it)
            router_x = x if self.router_reads_layer_input else None
            x = join(x, a, "hc_attn")
            if self.one_sublayer:       # the mixer was the layer
                return x, counted

            norm = self.ffn_norm_key
            y = tp.gather(m[norm].apply(layer_params[norm],
                                        read(x, "hc_ffn")))
            ff, aux = self._ffn(layer_params, y, tp, dtype, router_x)
            if norm := self.post_ffn_norm_key:
                ff = m[norm].apply(layer_params[norm], ff)
            if mixer is not None:
                aux = {**(aux or {}), **mixer.counters(*mixed.values())}
            if counted:     # what the layer's own mixer counted (`_mix`)
                aux = {**(aux or {}), **counted}
            return join(x, ff, "hc_ffn"), aux

        # Under ring overlap the dense segments run even on pipeline-bubble
        # steps (live is ignored except by ring attention): their tp
        # ppermutes lower with a GLOBAL participant list, so hiding them in
        # a stage-divergent lax.cond would deadlock — the same constraint
        # the cp ring documents below. Bubble steps burn the layer FLOPs;
        # their outputs are structurally discarded (garbage flows only into
        # garbage — see _pipeline_layers).
        if self.one_sublayer and "moe" in layer_params:
            # the layer is its feed-forward part, with no mixer before it
            norm = self.attn_norm_key
            y = tp.gather(m[norm].apply(layer_params[norm], x))
            ff, aux = self._ffn(layer_params, y, tp, dtype)
            return join(x, ff, None), aux
        if "wo" not in layer_params:
            # no output projection of the stack's: the layer's mixer hands
            # back the sublayer's output itself (`_mix`)
            norm = self.attn_norm_key
            y = tp.gather(m[norm].apply(layer_params[norm],
                                        read(x, "hc_attn")))
            if self.shares_values:
                a, counted, left = self._mix_sharing(
                    layer_params, y, dtype, kind, told, shared)
                x, aux = ffn_half(x, a, counted)
                return x, (aux, left)
            return ffn_half(x, *self._mix_counted(layer_params, y, layer_pos,
                                                  dtype))
        if live is None or tp.ring_ov:
            q, k, v, *gate = qkv(x)
            if self.cp_size > 1:
                if self.cp_impl == "ring":
                    o = ring_attention(q, k, v, pos, axis="cp",
                                       impl=self.attn_impl, live=live)
                else:
                    o = ulysses_attention(q, k, v, axis="cp",
                                          impl=self.attn_impl)
            # (a family of one kind of layer is asked as it always was,
            # `_attn_mask(t)`: the benchmark's controls patch that form)
            elif (mask := self._attn_mask(t) if kind is None
                  else self._attn_mask(t, kind)) is not None:
                o = masked_attention(q, k, v, mask, impl=self.attn_impl)
            else:
                o = causal_attention(q, k, v, impl=self.attn_impl,
                                     t_real=self._t_real(t))
            return attn_out((x, o, *gate))
        return self._live_gated_ring(x, qkv, attn_out, pos, live)

    def _qkv(self, lp: Params, y: jax.Array, tp: TPSublayers, layer_pos,
             dtype, b: int, t: int):
        """The attention half's inputs from the normed activation `y`:
        (q, k, v), each (b, heads, t, width), positions applied. This one
        is multi-head / grouped-query attention over `wq`/`wk`/`wv`; a
        family with another attention (latent) supplies its own. A layer
        whose parameters hold `wg` gates its heads' outputs: the gate's
        logits (b, t, heads * width) come back fourth, for
        `_attn_project`."""
        h = self.head_dim
        q, k, v, *gate = tp.columns(
            lp, ("wq", "wk", "wv") + (("wg",) if "wg" in lp else ()), y,
            dtype)
        # REMAT_LADDER's names, as the linears return them: (b, t,
        # heads*h), the lane-dense shape; the positions and the head
        # split are recomputed from them
        q = checkpoint_name(q, "q_proj")
        k = checkpoint_name(k, "k_proj")
        v = checkpoint_name(v, "v_proj")
        # (b, t, heads*h) -> (b, heads, t, h); under grouped-query
        # attention wk/wv produce fewer heads and k/v STAY at the
        # kv-head count — every attention impl handles the grouping
        # itself (the flash kernel and ring path route query-head
        # blocks onto kv rows with no HBM repeat; the XLA fallback
        # expands at its own boundary, ops/attention.py).
        split = lambda z, nh: z.reshape(b, t, nh, h).transpose(0, 2, 1, 3)
        q = split(q, self.num_local_heads)
        k = split(k, self.num_local_kv_heads)
        v = split(v, self.num_local_kv_heads)
        if "q_norm" in lp:
            # a family whose attention norms q and k per head, before the
            # positions, holds the two norms in its layers
            q = self._mods["q_norm"].apply(lp["q_norm"], q)
            k = self._mods["k_norm"].apply(lp["k_norm"], k)
        q, k = self._position_qk(q, k, layer_pos)
        if self.softmax_scale is not None:
            # the kernels scale the scores by 1 / sqrt(h) themselves
            q = q * jnp.asarray(self.softmax_scale * math.sqrt(h), q.dtype)
        # (the gate's logits are on no rung of REMAT_LADDER: recomputed)
        return (q, k, v, *gate)

    def _mix(self, lp: Params, y: jax.Array, layer_pos, dtype) -> jax.Array:
        """For a layer whose parameters hold no `wo` (a mixer that is not
        the stack's (q, k, v) dispatch): the mixer sublayer's output (b, t,
        d), reduced over 'tp', from the normed activation `y`. Which mixer
        a layer runs follows from what its parameters hold."""
        raise NotImplementedError

    def _mix_counted(self, lp: Params, y: jax.Array, layer_pos, dtype):
        """(`_mix`'s output, what the mixer counted or None). A family whose
        mixer counts something writes this one instead: the counters join
        the layer's aux, a row a layer (`_counter_reduces`)."""
        return self._mix(lp, y, layer_pos, dtype), None

    def _mix_sharing(self, lp: Params, y: jax.Array, dtype,
                     kind: "str | None", told: "Params | None",
                     shared: Params):
        """`_mix_counted` of a family that `shares_values`: (the mixer
        sublayer's output, what it counted or None, what the layer LEAVES
        for later blocks, a dict, or None). `kind` is the layer's, `told`
        what `_told` gave it, `shared` what the blocks before left."""
        raise NotImplementedError

    def _told(self, key: str, layers: Params) -> Params:
        """The stacked layers of `_pattern` key `key` with what a layer is
        TOLD beside its parameters under `told` (arrays stacked like the
        layers', made here from the configuration: a layer's published
        index; no parameter, so no gradient and no optimizer state). Here
        nothing: the tree as it is."""
        return layers

    def _attn_project(self, lp: Params, o: jax.Array, tp: TPSublayers,
                      dtype, gate: "jax.Array | None" = None) -> jax.Array:
        """The heads' outputs (b, t, heads * width) through `wo`, times
        the sigmoid of `gate`'s logits first where the layer has a gate."""
        if gate is not None:
            o = gate_heads(o, gate)
        return tp.row(lp, "wo", o, dtype)

    def _attn_scoped(self):
        return (jax.named_scope(self.attn_scope) if self.attn_scope
                else contextlib.nullcontext())

    def _ffn(self, lp: Params, y: jax.Array, tp: TPSublayers, dtype,
             router_x: "jax.Array | None" = None):
        """The FFN half of a layer on the normed activation `y`: (output,
        aux or None). The routed experts (`_mods["moe"]`) where the layer's
        parameters hold them, else the family's dense `_mlp`. `router_x` is
        the layer's input, handed on by a family whose routers read it
        (`router_reads_layer_input`)."""
        if "moe" in lp:
            early = {} if router_x is None else {"router_x": router_x}
            ff, aux = self._mods["moe"].apply(lp["moe"], y, dtype, **early)
            if tp.sp:
                # The router saw the tp-gathered full tokens (identical
                # on every tp rank, so routing agrees) and the expert
                # internals already all-reduced over tp — ff is the
                # full-value FFN output on every rank. Keep only this
                # rank's sequence slice so the residual stays
                # seq-sharded; the slice's transpose zero-pads,
                # composing with the gather's psum_scatter.
                tl = ff.shape[1] // self.tp_size
                ff = lax.dynamic_slice_in_dim(
                    ff, lax.axis_index("tp") * tl, tl, axis=1)
            return ff, aux
        return self._mlp(lp, y, tp, dtype), None

    def _fold_aux(self, auxs):
        """What a segment's scan stacked per layer -> the segment's aux:
        the router sums of parallel/moe.MoEFFN add up over layers; a
        family's counters stay one row a layer (`_router_aux_losses`)."""
        if not self.is_moe or not self._router_aux_losses:
            return auxs
        return jax.tree.map(lambda a: jnp.sum(a, axis=0), auxs)

    @property
    def rotary_dim(self) -> int:
        """The leading part of a head that RoPE rotates."""
        return self.head_dim

    def _positions(self, params: Params, x: jax.Array,
                   position_ids: jax.Array, dtype):
        """(x in the compute dtype with the positions that enter at the
        embedding, the arrays every layer gets). Here nothing enters at the
        embedding (a family's `embed_scale` multiplies the rows, in
        float32) and a layer gets `rotary_dim`'s (cos, sin) at
        `position_ids`, computed from the positions."""
        if self.embed_scale is not None:
            x = x * self.embed_scale
        return x.astype(dtype), rope_angles(position_ids, self.rotary_dim,
                                            self.cfg.rope_theta)

    def _position_qk(self, q: jax.Array, k: jax.Array, layer_pos):
        """(q, k) with the positions a layer takes at its attention: none
        where they all entered at the embedding."""
        if not layer_pos:
            return q, k
        return (apply_rotary_leading(q, *layer_pos, self.rotary_dim),
                apply_rotary_leading(k, *layer_pos, self.rotary_dim))

    def _mlp(self, lp: Params, y: jax.Array, tp: TPSublayers,
             dtype) -> jax.Array:
        """A dense block's feed-forward; SwiGLU here, down(silu(gate(y)) *
        up(y)) (model.py:94-95): `ffn_inputs` 2."""
        g, u = tp.columns(lp, ("gate_proj", "up_proj"), y, dtype,
                          **tp.ffn_order)
        g = checkpoint_name(g, "ffn_gate")
        u = checkpoint_name(u, "ffn_up")
        return tp.row(lp, "down_proj", jax.nn.silu(g) * u, dtype,
                      **tp.ffn_order)

    def _attn_mask(self, t: int, kind: "str | None" = None):
        """The attention mask a family declares over a sequence of `t` rows
        for its layers of kind `kind` (`ops/attention.AttnMask`); None is
        the causal triangle, with `attn_t_real`."""
        return None

    def _kind(self, key: str) -> "str | None":
        """The kind of the layers a `_pattern` key holds, which the layer
        body is told: None where a layer's parameters say all there is to
        say (every family with one kind of attention layer)."""
        return None

    def _t_real(self, t: int) -> "int | None":
        """attn_t_real clamped to the runtime sequence length (a shorter
        batch than the bucket simply has no pad rows to skip)."""
        if self.attn_t_real is None or self.attn_t_real >= t:
            return None
        return self.attn_t_real

    @property
    def _pp_vary_axes(self) -> Tuple[str, ...]:
        """Axes the pipeline's step carry varies over: the stage-dependent
        'pp', the batch axes, and 'tp' when sequence parallelism shards t."""
        return (("pp", "dp", "ep", "cp")
                + (("tp",) if self.sequence_parallel else ()))

    def _live_gated_ring(self, x, qkv, attn_out, pos, live):
        """Live-gated layer execution for pp x ring-CP meshes — shared by
        both model families (see `_layer_body`'s docstring for why the ring
        runs unconditionally while the dense segments take `lax.cond`).

        `qkv(x) -> (q, k, v)` is the pre-attention segment and
        `attn_out((x, o)) -> (x', aux)` the epilogue; both run only on live
        steps. Bubble steps permute zeros around the ring (wire traffic
        only — every block's MXU work is skipped inside `ring_attention`
        by the same `live` scalar) and pass the carry through unchanged.

        vma discipline: `lax.cond` branches must produce identical avals
        INCLUDING varying-manual-axes tags, so both branches lift their
        outputs to a common tag set with `copy_to` (idempotent pvary —
        only ever ADDS tags, a semantically weaker claim that is always
        sound). q/k/v carry 'tp' on top of the pipeline vary axes (the
        projection weights are tp-sharded); the epilogue's outputs carry
        exactly the pipeline carry's axes.
        """
        qkv_tag = ("pp", "dp", "ep", "cp", "tp")
        out_tag = self._pp_vary_axes
        b, t = pos.shape
        h = self.cfg.head_dim

        def qkv_live(x):
            return tuple(copy_to(z, qkv_tag) for z in qkv(x))

        def qkv_zero(x):
            dtype = resolve_dtype(self.cfg.compute_dtype)
            shapes = [(b, self.num_local_heads, t, h),
                      (b, self.num_local_kv_heads, t, h),
                      (b, self.num_local_kv_heads, t, h)]
            return tuple(copy_to(jnp.zeros(s, dtype), qkv_tag)
                         for s in shapes)

        q, k, v = lax.cond(live, qkv_live, qkv_zero, x)
        o = ring_attention(q, k, v, pos, axis="cp", impl=self.attn_impl,
                           live=live)

        def post_live(args):
            x2, aux = attn_out(args)
            if self.is_moe:
                aux = jax.tree.map(lambda a: copy_to(a, out_tag), aux)
            return copy_to(x2, out_tag), aux

        def post_skip(args):
            x2, _ = args
            aux = (jax.tree.map(lambda a: copy_to(a, out_tag),
                                aux_zeros(self.cfg.num_experts))
                   if self.is_moe else None)
            return copy_to(x2, out_tag), aux

        return lax.cond(live, post_live, post_skip, (x, o))

    def forward_shard(self, params: Params, input_ids: jax.Array,
                      position_ids: jax.Array,
                      head_layout: str = "replicated") -> jax.Array:
        """(b_local, t) ids -> (b_local, t, vocab_padded / tp) LOCAL logits.

        Runs per-shard inside shard_map. The caller chooses whether to stitch
        (out_spec P('dp', None, 'tp')) or explicitly `gather_from` the result.
        `head_layout`: see `_forward_with_aux`."""
        logits, _ = self._forward_with_aux(params, input_ids, position_ids,
                                           head_layout=head_layout)
        return logits

    def _forward_with_aux(self, params: Params, input_ids: jax.Array,
                          position_ids: jax.Array,
                          head_layout: str = "replicated"):
        """forward_shard + the MoE aux-stat sums (None for dense models),
        summed over layers but still LOCAL to this shard — loss_shard psums
        them over the batch axes before forming the aux losses.

        `head_layout` (pipeline only): 'pp_scatter' hands each pp stage a
        disjoint 1/pp batch chunk for norm/lm_head (see _pipeline_layers);
        the returned logits then have b/pp rows."""
        self = self._resolved(input_ids.shape[1])
        x, aux, trunk = self._trunk(params, input_ids, position_ids,
                                    head_layout)
        if self.loop_steps is not None:
            # the last pass's exit (its state is already normed)
            with jax.named_scope("head_loss"):
                return self._masked_logits(params, x[-1], trunk.dtype), aux
        return self._head(params, params["norm"], x, trunk.dtype), aux

    def _trunk(self, params: Params, input_ids: jax.Array,
               position_ids: jax.Array, head_layout: str = "replicated"):
        """Embedding and every layer segment, on a `_resolved` model: (the
        last layer's output, aux, what a further segment over the same
        batch runs with: `SimpleNamespace(dtype, run)` where `run(z,
        layers)` scans `layers` from `z` under this trace's remat rung and
        positions). `_head` turns the output into logits. A family that
        passes its stack `loop_steps` times gets the R normed states, (R,
        b, t, d), in the output's place (`_loop_passes`)."""
        dtype = resolve_dtype(self.cfg.compute_dtype)
        sp = self.sequence_parallel
        if sp and input_ids.shape[1] % self.tp_size != 0:
            raise ValueError(
                f"sequence_parallel needs the (cp-local) sequence length "
                f"{input_ids.shape[1]} divisible by tp_size {self.tp_size}")
        x = self.embedding.apply(params["embedding"], input_ids,
                                 output_layout="seq_sharded" if sp else "replicated")
        # x in the compute dtype with the family's positions in it, and the
        # (b, t, ...) arrays every layer gets (`_position_qk`), if any
        x, layer_pos = self._positions(params, x, position_ids, dtype)
        if self.stream_mixer:
            # X_0: the embedding's row, n times
            x = jnp.broadcast_to(x, (self.residual_streams, *x.shape))

        rung = resolve_remat(self, params, input_ids.shape)

        def layer_step(layers, *mb, live=None, kind=None, shared=None):
            # the step of a scan over `layers`, layers of one `kind`, under
            # this trace's remat rung; `mb` is (*layer_pos, position_ids),
            # whole or, under the pipeline, one microbatch's rows; `shared`
            # (a family that `shares_values`) an input of every layer
            layer_fn = remat_wrap(
                self._layer_body, rung, static_argnums=(4, 6),
                looped=jax.tree.leaves(layers)[0].shape[0] > 1
                or (self.loop_steps or 1) > 1)

            def body(carry, lp):
                return layer_fn(carry, lp, mb[:-1], mb[-1], dtype, live, kind,
                                *(() if shared is None else (shared,)))
            return body

        def stage_fn(z, layers, *mb, live=None, kind=None, shared=None):
            z, auxs = lax.scan(layer_step(layers, *mb, live=live, kind=kind,
                                          shared=shared), z, layers)
            if shared is not None:
                # (.., what the segment's LAST layer leaves)
                auxs, left = auxs
                return (z, self._fold_aux(auxs),
                        jax.tree.map(lambda a: a[-1], left))
            # auxs: None for dense; for MoE a dict of (L,...) stacked sums
            return z, self._fold_aux(auxs)

        run = lambda z, layers, key=None, shared=None: stage_fn(
            z, layers, *layer_pos, position_ids,
            kind=key and self._kind(key), shared=shared)
        if self.pp_size > 1:
            x, aux = self._pipeline_layers(stage_fn, x, params["layers"],
                                           (*layer_pos, position_ids),
                                           head_layout=head_layout)
        else:
            def one_pass(x):
                auxs = []
                shared = {} if self.shares_values else None
                for block in self._pattern:
                    if not isinstance(block, str):
                        x, aux = self._scan_periods(run, x, params, block,
                                                    shared)
                    elif shared is None:
                        x, aux = run(x, self._told(block, params[block]),
                                     block)
                    else:
                        x, aux, left = run(
                            x, self._told(block, params[block]), block,
                            shared)
                        shared = {**shared, **(left or {})}
                    auxs.append(aux)
                # a block of dense layers has none; where several blocks
                # count (a row a layer), the rows follow the layers
                auxs = [aux for aux in auxs if aux is not None]
                return x, (_rows_in_order(auxs) if len(auxs) > 1
                           else auxs[0] if auxs else None)

            if self.loop_steps is None:
                x, aux = one_pass(x)
            else:
                # (what `_loop_passes` walks a layer at a time)
                one_pass.step = lambda key, layers, *mb: layer_step(
                    layers, *mb, kind=self._kind(key))
                one_pass.mb = (*layer_pos, position_ids)
                x, aux = self._loop_passes(one_pass, params, x)
        return x, aux, SimpleNamespace(dtype=dtype, run=run)

    def _loop_passes(self, one_pass, params: Params, x: jax.Array):
        """`loop_steps` passes of the pattern over the SAME parameters, the
        final norm after every pass: `h_r = N_f(Layers(h_{r-1}))`. Returns
        the R normed states, (R, b, t, d), and no aux (the layers are
        dense). The norm runs under `head_loss`: it is the exits' (the
        scope a device trace splits the step by); the rest of the walk
        under `loop_pass`.

        ONE walk of R x L layer applications over the stacked layers (layer
        `i % L` at step i, the final norm where a pass ends: a `cond`), one
        traced copy of the layer body, WITH ITS TRANSPOSE WRITTEN OUT (a
        `jax.custom_vjp`, as `parallel/moe.walk_chunks` is). Autodiff's
        transpose of a scan of passes around the scan of layers holds the
        stacked layers' float32 gradient twice, what a pass's backward scan
        stacks and the running sum it is then added to, and adds the two
        whole stacks once a pass (1.53 GiB and 29 ms a step at cell 14's
        shapes, which held `remat auto` on its floor: PERF.md section 6,
        PR 67). Here the gradient is ONE stack in the parameters' own dtype
        that the backward walk carries: a layer application adds its weight
        gradient into its slice of it, in place, in the order the scans'
        transpose summed (the last pass first).

        A step of the forward is `jax.vjp` of the layer `stage_fn` scans
        (`one_pass.step`: the one `_layer_body` under the trace's remat
        rung), so what the walk keeps of a layer application is what the
        rung names and the layer's input, R x L of each, stacked by the one
        scan (a scan of passes around the scan of layers copies a pass's
        stacks into the R x L deep ones and back out); the weights and
        positions a checkpoint holds among its residuals are not stacked
        but handed back by the backward walk (`_pull_of`). With
        `remat=False` a layer keeps the casts of its weights, one a step.
        Of a pass the walk keeps the final norm's input and output, R of
        each; the norm's transpose is taken again from its input.

        `one_pass(z)` is still a whole pass that autodiff can take: what
        replaces this method builds on it (benchmark/tools/loop_control.
        py)."""
        (key,), R, norm = self._pattern, self.loop_steps, self.final_norm.apply
        L = jax.tree.leaves(params[key])[0].shape[0]
        take = lambda tree, i: jax.tree.map(
            lambda a: lax.dynamic_index_in_dim(a, i, 0, keepdims=False), tree)

        def forward(layers, norm_params, x, *mb):
            # (a call of its own: its ops keep the names they were traced
            # under, `dense_ffn`, `flash_fwd`, where the ops of a function
            # `jax.vjp` runs inline are all named `jvp(...)`)
            step = jax.jit(one_pass.step(key, layers, *mb))

            def exit_norm(z):
                with jax.named_scope("head_loss"):
                    return norm(norm_params, z)

            @jax.named_scope("loop_pass")
            def layer(carry, i):
                z, ends, states = carry
                l, r = i % L, i // L
                lp = take(layers, l)
                (z, _), pull = jax.vjp(step, z, lp)
                # (every layer of a pass writes the pass's slot, the last
                # one last: no select, and no buffer through the `cond`)
                ends = lax.dynamic_update_index_in_dim(ends, z, r, 0)
                z = lax.cond(l == L - 1, exit_norm, lambda z: z, z)
                states = lax.dynamic_update_index_in_dim(states, z, r, 0)
                return (z, ends, states), _pull_of(pull, (lp, mb))

            slots = zeros_like_varying(jnp.broadcast_to(x, (R, *x.shape)))
            (_, ends, states), pulls = lax.scan(
                layer, (x, slots, slots), jnp.arange(R * L))
            return states, (layers, norm_params, mb, ends, pulls)

        def backward(kept, d_states):
            layers, norm_params, mb, ends, pulls = kept
            zeros = lambda tree: jax.tree.map(zeros_like_varying, tree)

            @jax.named_scope("loop_pass")
            def layer(carry, at):
                d_z, d_layers, d_norm = carry
                i, pull = at
                l, r = i % L, i // L

                def exit_norm(d_z):
                    with jax.named_scope("head_loss"):
                        return jax.vjp(norm, norm_params, take(ends, r))[1](
                            d_z + take(d_states, r))

                d, d_z = lax.cond(l == L - 1, exit_norm,
                                  lambda d_z: (zeros(norm_params), d_z), d_z)
                d_norm = jax.tree.map(jnp.add, d_norm, d)
                lp = take(layers, l)
                d_z, d_lp = pull((lp, mb))((d_z, None))
                return (d_z, jax.tree.map(
                    lambda sums, d: lax.dynamic_update_index_in_dim(
                        sums, lax.dynamic_index_in_dim(sums, l, 0) + d[None],
                        l, 0), d_layers, d_lp), d_norm), None

            (d_x, d_layers, d_norm), _ = lax.scan(
                layer, (zeros(d_states[0]), zeros(layers),
                        zeros(norm_params)),
                (jnp.arange(R * L), pulls), reverse=True)
            return d_layers, d_norm, d_x, *(None for _ in mb)

        walk = jax.custom_vjp(lambda *args: forward(*args)[0])
        walk.defvjp(forward, backward)
        return walk(params[key], params["norm"], x, *one_pass.mb), None

    def _scan_periods(self, run, x: jax.Array, params: Params, period,
                      shared: "Params | None" = None):
        """One scan over the periods of a `_pattern` block: the body runs
        each key's layers of the period through `run` (the one layer
        skeleton under the one remat policy; it is told the key, for the
        layers' kind), in the period's order. The
        aux comes back one row a layer, in the order the layers ran.
        `shared` (a family that `shares_values`): what earlier blocks left,
        a constant of the scan; a period's layers read and leave nothing."""
        keys = [key for key, _ in period]

        def period(z, layers):
            auxs = []
            for key in keys:
                if shared is None:
                    z, aux = run(z, layers[key], key)
                else:
                    z, aux, _ = run(z, layers[key], key, shared)
                auxs.append(aux)
            return z, _rows_in_order(auxs)

        x, aux = lax.scan(period, x, {key: self._told(key, params[key])
                                      for key in keys})
        # (periods, layers a period, ...) -> (layers, ...)
        return x, jax.tree.map(
            lambda a: a.reshape(a.shape[0] * a.shape[1], *a.shape[2:]), aux)

    def _head(self, params: Params, norm_params: Params, x: jax.Array,
              dtype, scope: "str | None" = "head_loss",
              exit_params: "Params | None" = None) -> jax.Array:
        """Final norm (with `norm_params`) and the head: LOCAL logits.
        `head_loss` is the one boundary inside the loss that a device trace
        is split at (final norm, head, CE; benchmark/lib/program_trace.py);
        a caller already inside a scope of its own passes None. Residual
        streams leave through the exit mixer first (with `exit_params`;
        None: the tree's own `hc_exit`)."""
        if self.stream_mixer:
            x = self.exit_mixer.exit(
                params["hc_exit"] if exit_params is None else exit_params, x)
        with (jax.named_scope(scope) if scope
              else contextlib.nullcontext()):
            x = self.final_norm.apply(norm_params, x)
            return self._masked_logits(params, x, dtype)

    def _masked_logits(self, params: Params, x: jax.Array,
                       dtype) -> jax.Array:
        """The head on a normed state: LOCAL logits, the padded vocabulary
        entries masked so they carry no probability mass."""
        logits = self._head_logits(params, x, dtype)
        if self.logit_scale is not None:
            logits = logits * jnp.asarray(self.logit_scale, logits.dtype)
        if self.vocab_padded != self.cfg.vocab_size:
            local_v = self.vocab_padded // self.tp_size
            start = lax.axis_index("tp") * local_v
            col = start + jnp.arange(local_v)
            logits = jnp.where(col[None, None, :] < self.cfg.vocab_size,
                               logits, jnp.asarray(NEG_INF, logits.dtype))
        return logits

    def _head_logits(self, params: Params, x: jax.Array, dtype) -> jax.Array:
        """The local vocabulary shard of the logits: through `lm_head`, or,
        where the head is tied, against this shard's embedding rows."""
        if not self.tied_head:
            return self.lm_head.apply(
                params["lm_head"], x, dtype,
                input_layout="seq_sharded" if self.sequence_parallel
                else "replicated")
        tp = self._tp_sublayers
        w = params["embedding"]["weight"].astype(dtype)  # (vp/tp, d)
        if tp.ring_ov:
            # the gather's hops hide under the per-chunk logits dots; the
            # VJP's reverse ring reduce-scatters the head's input cotangent
            return ring_order(ag_matmul(x.astype(dtype), (w.T,), "tp",
                                        tp.ring_quant)[0], "tp")
        # under sequence parallelism the tied head takes the full sequence;
        # the gather's transpose reduce-scatters the input cotangent
        return tp.gather(x).astype(dtype) @ w.T           # (b, t, vp/tp)

    def _pipeline_layers(self, stage_fn, x: jax.Array, layers: Params,
                         mb_arrays: Tuple[jax.Array, ...],
                         head_layout: str = "replicated"):
        """GPipe microbatch pipeline over the 'pp' mesh axis — family-
        agnostic: `stage_fn(z, layers, *mb) -> (z', aux_or_None)` runs this
        stage's layer stack on one microbatch, and `mb_arrays` are the
        per-microbatch auxiliary inputs (leading dim = local batch b) each
        family needs (its `_positions` arrays, then position_ids).

        `layers` arrive ALREADY sliced by shard_map to this stage's block:
        gpipe — the contiguous (num_layers/pp, ...) slice (specs() shards
        the stacked layer dim over 'pp'); interleaved — the (V, 1, Lv, ...)
        slice of the (V, pp, Lv, ...) layout, i.e. this device's V
        round-robin virtual blocks. The gpipe schedule is one lax.scan over
        M + pp - 1 pipeline steps; at step s, stage p runs microbatch s - p
        through its local layers and ppermutes the activation to stage
        p + 1. The interleaved schedule scans V*M + pp - 1 steps over the
        SAME ring: with r = s - p, stage p runs virtual block
        (r // pp) % V on microbatch (r // (V*pp))*pp + r % pp — each
        microbatch circulates V times, stage 0 consuming the ring wrap for
        blocks > 0 and fresh injections for block 0. Autodiff transposes
        either schedule into the reverse-time backward pipeline.

        Bubble steps take a `lax.cond` identity branch — no layer FLOPs are
        burned on discarded microbatches (VERDICT r2 weak #2a). The
        predicate depends only on (step, stage), so every member of a
        tp/ep/dp/cp group agrees on the branch and the collectives inside
        the live branch stay uniform.

        MoE router aux sums ride the scan carry, gated to live steps, so
        expert models pipeline too (VERDICT r2 #4); each stage returns the
        aux sums for ITS layers x all microbatches (psum over 'pp' in
        loss_shard totals them).

        Returns (x_final, aux):
          head_layout='replicated' — x_final is the final-layer activation
            for the FULL local batch, replicated over 'pp' (psum broadcast)
            so norm/lm_head code is pipeline-oblivious; callers must mask
            per-stage duplicates (make_forward's contract).
          head_layout='pp_scatter' (requires b % pp == 0) — x_final is this
            stage's 1/pp batch chunk (psum_scatter): norm + lm_head + CE
            then run pp-way parallel on disjoint chunks instead of
            pp-way replicated (VERDICT r2 weak #2c — no duplicated lm_head
            FLOPs, and the broadcast's (b,t,d) wire bytes drop by 1/pp).
        """
        pp = self.pp_size
        M = self.pp_microbatches or pp
        b, t, d = x.shape
        if b % M != 0:
            raise ValueError(f"local batch {b} not divisible by "
                             f"pp_microbatches {M}")
        mb = b // M
        stage = lax.axis_index("pp")
        last = pp - 1

        # (M, mb, ...) microbatch views; the mb_arrays are replicated over
        # pp so every stage can index its current microbatch locally.
        xs = x.reshape(M, mb, t, d)
        mb_views = [a.reshape(M, mb, *a.shape[1:]) for a in mb_arrays]

        vary_axes = self._pp_vary_axes

        def pvary(z):
            # copy_to is the tag-aware (idempotent) varying cast: router aux
            # leaves mix constants — invariant — with token-derived values,
            # and cond branches must agree exactly
            return copy_to(z, vary_axes)

        def local_layers(z, lyrs, *mb_in, **kw):
            z, aux = stage_fn(z, lyrs, *mb_in, **kw)
            if self.is_moe:
                aux = jax.tree.map(pvary, aux)
            return z, aux

        aux0 = (jax.tree.map(pvary, aux_zeros(self.cfg.num_experts))
                if self.is_moe else None)
        # Bubble-step execution mode: a whole-stage lax.cond is only sound
        # when the layer body contains no ppermute (see pipe_step below).
        # Two features put ppermutes in the body: the cp ring, and the
        # tp_overlap ring collective matmuls — either forces the
        # run-unconditionally mode, where the layer body itself decides what
        # to gate (the cp ring gates per-block MXU work on `live`; the tp
        # rings run in full, burning bubble FLOPs whose outputs are
        # structurally discarded).
        ring_cp = (self.cp_size > 1 and self.cp_impl == "ring") or (
            self.sequence_parallel
            and self.tp_overlap in ("ring", "ring_q"))

        if self.pp_schedule == "interleaved":
            return self._pipeline_interleaved(
                xs, mb_views, layers, local_layers, aux0, pvary, ring_cp,
                head_layout)

        def pipe_step(carry, s):
            z_prev, aux_acc = carry
            # which microbatch this stage works on; bubble steps (before the
            # pipe fills / after this stage drains) skip compute entirely
            m = jnp.clip(s - stage, 0, M - 1)
            live = (s >= stage) & (s - stage <= M - 1)
            inject = lax.dynamic_index_in_dim(xs, jnp.clip(s, 0, M - 1), 0,
                                              keepdims=False)
            z = jnp.where(stage == 0, inject, z_prev)
            take = lambda a: lax.dynamic_index_in_dim(a, m, 0,
                                                      keepdims=False)

            def run(z):
                return local_layers(z, layers, *[take(v) for v in mb_views])

            def skip(z):
                return z, aux0

            # Bubble skip: a whole-stage `lax.cond` is only sound when the
            # layer body contains no ppermute — XLA lowers
            # collective-permute with a GLOBAL participant list (every
            # device must execute it; measured: the cp ring inside a
            # stage-divergent cond deadlocks the CPU rendezvous), while
            # psum/all_gather/psum_scatter/all_to_all lower with proper
            # per-group participant lists (tp/ep/sp members share a pp
            # stage, so they agree on the branch). The ring-CP path
            # therefore gates at FINER granularity instead: `live` flows
            # into every layer body, the ring's ppermutes execute
            # unconditionally on every step (zeros on bubbles), and the
            # dense segments + per-block MXU work skip inside the layer
            # (_live_gated_ring / ring_attention's live gate) — bubble
            # steps cost wire traffic only, the same M-layer-passes FLOPs
            # accounting as the cond path (VERDICT r3 #3).
            if ring_cp:
                y, aux_step = local_layers(
                    z, layers, *[take(v) for v in mb_views], live=live)
            else:
                y, aux_step = lax.cond(live, run, skip, z)
            if self.is_moe:
                aux_acc = jax.tree.map(lambda acc, a: acc + a, aux_acc,
                                       aux_step)
            out = jnp.where(stage == last, y, jnp.zeros_like(y))
            # stage p -> p + 1; the wrap to stage 0 is overwritten by inject
            y_send = lax.ppermute(y, "pp",
                                  [(i, (i + 1) % pp) for i in range(pp)])
            return (y_send, aux_acc), out

        if self.pp_remat_steps:
            # Per-step remat: residuals for the backward pipeline are the
            # (mb, t, d) step carries only; each step's layer internals
            # recompute. Cuts the M-proportional layer-activation footprint
            # (the practical core of a 1F1B schedule's memory win) at ~33%
            # extra FLOPs.
            pipe_step = jax.checkpoint(pipe_step)

        # vma: the carried activation varies over 'pp' (stage-dependent) and
        # over the batch axes (x is batch-sharded) — and over 'tp' when
        # sequence parallelism shards t.
        carry0 = pvary(jnp.zeros((mb, t, d), x.dtype))
        (_, aux), outs = lax.scan(pipe_step, (carry0, aux0),
                                  jnp.arange(M + pp - 1, dtype=jnp.int32))
        # outs[last + m] is microbatch m off the last stage (zeros on every
        # other stage).
        x_final = outs[last:].reshape(b, t, d)
        if head_layout == "pp_scatter":
            x_final = lax.psum_scatter(x_final, "pp", scatter_dimension=0,
                                       tiled=True)        # (b/pp, t, d)
        else:
            x_final = lax.psum(x_final, "pp")
        return x_final, aux

    def _pipeline_interleaved(self, xs, mb_views, layers, local_layers,
                              aux0, pvary, ring_cp, head_layout):
        """Interleaved (virtual-stage) schedule body — see _pipeline_layers'
        docstring for the step/stage/block algebra. Completed microbatches
        accumulate into an (M, mb, t, d) carry buffer on the last stage
        (with V circulations their completion steps are no longer one
        contiguous outs slice)."""
        pp, V = self.pp_size, self.pp_virtual
        M, mb, t, d = xs.shape
        stage = lax.axis_index("pp")
        last = pp - 1
        # (V, 1, Lv, ...) shard_map slice -> (V, Lv, ...)
        layers = jax.tree.map(lambda a: a.reshape(a.shape[0], *a.shape[2:]),
                              layers)

        def pipe_step(carry, s):
            z_prev, aux_acc, out_buf = carry
            r = s - stage
            live = (r >= 0) & (r <= V * M - 1)
            j = (r // pp) % V                      # this device's block
            m = jnp.clip((r // (V * pp)) * pp + (r % pp), 0, M - 1)
            # stage 0 injects fresh microbatches into virtual block 0 and
            # consumes the ring wrap (stage pp-1's output entering block
            # j) otherwise; the wrap arriving during block-0 steps carries
            # FINAL outputs, already banked into out_buf below.
            inject = lax.dynamic_index_in_dim(xs, m, 0, keepdims=False)
            z = jnp.where((stage == 0) & (j == 0), inject, z_prev)
            lyrs = jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, jnp.clip(j, 0, V - 1),
                                                   0, keepdims=False),
                layers)
            take = lambda a: lax.dynamic_index_in_dim(a, m, 0,
                                                      keepdims=False)

            def run(z):
                return local_layers(z, lyrs, *[take(v) for v in mb_views])

            def skip(z):
                return z, aux0

            if ring_cp:  # same finer-grained gating as the gpipe path
                y, aux_step = local_layers(
                    z, lyrs, *[take(v) for v in mb_views], live=live)
            else:
                y, aux_step = lax.cond(live, run, skip, z)
            if self.is_moe:
                aux_acc = jax.tree.map(lambda acc, a: acc + a, aux_acc,
                                       aux_step)
            done = live & (stage == last) & (j == V - 1)
            upd = lax.dynamic_update_slice(out_buf, y[None],
                                           (m, 0, 0, 0))
            out_buf = jnp.where(done, upd, out_buf)
            y_send = lax.ppermute(y, "pp",
                                  [(i, (i + 1) % pp) for i in range(pp)])
            return (y_send, aux_acc, out_buf), None

        if self.pp_remat_steps:
            pipe_step = jax.checkpoint(pipe_step)

        carry0 = (pvary(jnp.zeros((mb, t, d), xs.dtype)), aux0,
                  pvary(jnp.zeros((M, mb, t, d), xs.dtype)))
        (_, aux, out_buf), _ = lax.scan(
            pipe_step, carry0,
            jnp.arange(V * M + pp - 1, dtype=jnp.int32))
        x_final = out_buf.reshape(M * mb, t, d)
        if head_layout == "pp_scatter":
            x_final = lax.psum_scatter(x_final, "pp", scatter_dimension=0,
                                       tiled=True)
        else:
            x_final = lax.psum(x_final, "pp")
        return x_final, aux

    # ---- losses (per-shard, inside shard_map) ----

    def _token_ce(self, logits: jax.Array, target_ids: jax.Array,
                  mode: str) -> Tuple[jax.Array, jax.Array]:
        """Per-token CE from the LOCAL vocab-shard logits: (token_loss f32,
        valid mask), both (..., t). Shared by the training loss and the
        per-document eval loss."""
        logits = logits.astype(jnp.float32)
        valid = target_ids != IGNORE_INDEX
        tgt = jnp.where(valid, target_ids, 0)

        if mode == "gather":
            # Reference data path: materialise full logits (lm_head
            # gather_output=True, model.py:137), CE on every shard, then
            # average the tp-identical copies so the result is tp-invariant.
            full = gather_from(logits, "tp")
            lse = jax.nn.logsumexp(full, axis=-1)
            tgt_logit = jnp.take_along_axis(full, tgt[..., None], axis=-1)[..., 0]
            # average the tp-identical copies: makes the value tp-invariant
            token_loss = reduce_from(lse - tgt_logit, "tp") / self.tp_size
        elif mode == "vocab_parallel":
            # Megatron-style vocab-parallel CE: never materialise the full
            # (b, t, vocab) tensor — two scalar-field psums instead of an
            # all-gather. Wins when vocab is large (BASELINE config 4).
            local_v = logits.shape[-1]
            start = lax.axis_index("tp") * local_v
            # softmax is shift-invariant, so the max subtraction carries no
            # gradient (and pmax has no differentiation rule anyway).
            local_max = jnp.max(lax.stop_gradient(logits), axis=-1)
            gmax = lax.stop_gradient(lax.pmax(local_max, "tp"))
            sumexp = reduce_from(
                jnp.sum(jnp.exp(logits - gmax[..., None]), axis=-1), "tp")
            lse = jnp.log(sumexp) + gmax
            local_tgt = tgt - start
            owned = (local_tgt >= 0) & (local_tgt < local_v)
            safe_tgt = jnp.where(owned, local_tgt, 0)
            tgt_logit = jnp.take_along_axis(logits, safe_tgt[..., None], axis=-1)[..., 0]
            tgt_logit = reduce_from(jnp.where(owned, tgt_logit, 0.0), "tp")
            token_loss = lse - tgt_logit
        else:
            raise ValueError(f"unknown loss mode {mode!r}")
        return token_loss, valid

    def loss_shard(self, params: Params, input_ids: jax.Array,
                   target_ids: jax.Array, position_ids: jax.Array,
                   mode: str = "vocab_parallel",
                   batch_axes: Tuple[str, ...] = ("dp", "ep", "cp"),
                   with_counters: bool = False, noise=None):
        """Mean cross-entropy over non-ignored tokens, global over the mesh,
        unless the family weights it (below).

        f32 loss with ignore-index masking, matching the reference's
        `F.cross_entropy(logits.float(), ..., ignore_index=-1, 'mean')`
        (`/root/reference/train.py:101-104`).

        `noise` (a family that `draws_noise`): the step's draw for this
        shard's sequences. The family makes of it the rows the stack sees,
        the targets and a weight a position (`_noised_rows`); the head and
        the CE then run on the targets' rows only, the first of a
        sequence's, and the loss is the WEIGHTED sum of the CE over all of
        them, divided by their number.

        `with_counters` returns (loss, counters): a dict of what the loss
        is made of and what the layers counted on the way (`loss_main`
        always; a family adds its own: `_extra_loss`), summed over the
        mesh. Nothing in it carries a gradient.
        """
        # Pipeline head layout: with a pp-divisible batch each stage computes
        # norm/lm_head/CE on a DISJOINT 1/pp chunk (no duplicated head FLOPs
        # — VERDICT r2 weak #2c); otherwise every stage sees the broadcast
        # full batch and the sums are masked to the last stage below.
        self = self._resolved(input_ids.shape[1])
        weight, counts = None, {}
        if noise is not None:
            input_ids, target_ids, position_ids, weight, counts = \
                self._noised_rows(input_ids, position_ids, noise)
        pp_scatter = (self.pp_size > 1
                      and input_ids.shape[0] % self.pp_size == 0)
        x, aux, trunk = self._trunk(
            params, input_ids, position_ids,
            head_layout="pp_scatter" if pp_scatter else "replicated")
        if self.loop_steps is not None:
            return self._loop_loss(params, x, target_ids, trunk.dtype, mode,
                                   batch_axes, with_counters)
        if weight is not None:
            x = x[:, :target_ids.shape[1]]
        logits = self._head(params, params["norm"], x, trunk.dtype)
        if pp_scatter:
            chunk = input_ids.shape[0] // self.pp_size
            target_ids = lax.dynamic_slice_in_dim(
                target_ids, lax.axis_index("pp") * chunk, chunk, axis=0)
        # the CE belongs to the head's scope (see _forward_with_aux)
        with jax.named_scope("head_loss"):
            token_loss, valid = self._token_ce(logits, target_ids, mode)
            if weight is None:
                loss_sum = jnp.sum(jnp.where(valid, token_loss, 0.0))
                count = jnp.sum(valid.astype(jnp.float32))
            else:
                loss_sum = jnp.sum(token_loss * weight)
                count = jnp.sum(jnp.ones_like(token_loss))
        if self.pp_size > 1:
            if not pp_scatter:
                # Fallback (batch not pp-divisible): every stage computed
                # the same CE from the psum-broadcast x_final, so count it
                # ONCE: mask to the last stage. This also zeroes the CE
                # cotangent on the other stages — without it, shard_map's
                # transpose would psum pp_size identical lm_head/embedding
                # cotangents (they are replicated over 'pp') and scale
                # their gradients by pp_size. (The scatter path needs no
                # mask: the chunks are disjoint, so the psum over 'pp' IS
                # the batch total and per-stage cotangents are per-chunk.)
                is_last = (lax.axis_index("pp") == self.pp_size - 1)
                is_last = is_last.astype(jnp.float32)
                loss_sum = loss_sum * is_last
                count = count * is_last
            batch_axes = tuple(batch_axes) + ("pp",)
        loss_sum = lax.psum(loss_sum, batch_axes)
        count = lax.psum(count, batch_axes)
        loss = loss_sum / jnp.maximum(count, 1.0)
        if self.is_moe and self._router_aux_losses:
            # Globally-summed router stats -> sharding-invariant aux losses
            # (load balance + z), added with their Switch/ST-MoE weights.
            if self.sequence_parallel:
                # Under SP the router ran on the tp-GATHERED tokens: every
                # tp rank holds identical aux sums, but they carry the
                # gather's tp-varying tag. pmean is a value-identity that
                # clears the tag (and its transpose splits the cotangent
                # 1/tp per rank, whose contributions re-sum downstream).
                aux = jax.tree.map(lambda a: lax.pmean(a, "tp"), aux)
            aux_g = jax.tree.map(lambda a: lax.psum(a, batch_axes), aux)
            lb, z = aux_losses(aux_g, self.cfg.num_experts,
                               self.cfg.moe_top_k)
            loss = (loss + self.cfg.moe_aux_coef * lb
                    + self.cfg.moe_z_coef * z)
        counters = {"loss_main": loss,
                    **{k: lax.psum(v, batch_axes) for k, v in counts.items()}}
        loss, more = self._extra_loss(
            params, loss, x, aux, trunk, input_ids, target_ids,
            position_ids, mode, batch_axes)
        if with_counters:
            return loss, jax.tree.map(lax.stop_gradient,
                                      {**counters, **more})
        return loss

    def _loop_loss(self, params: Params, states: jax.Array,
                   target_ids: jax.Array, dtype, mode: str, batch_axes,
                   with_counters: bool):
        """The loss of a stack passed R = `loop_steps` times, from its R
        normed states (R, b, t, d): an exit a pass through the one head,
        `l_r[i]` the per-token CE of exit r; the exit gate a token at a
        time, `lam_r[i] = sigmoid(w_g . h_r[i] + b_g)`, `p_r = lam_r
        prod_{j<r} (1 - lam_j)` and the last pass takes what is left; and

            loss = mean_i [ sum_r p_r[i] l_r[i] - beta H(p[i]) ]

        over the tokens that are not ignored (`exit_entropy_coef` = beta, H
        the entropy: a uniform prior over the exit steps). The gate gets
        gradient from both terms, the layers from all R exits through all
        later passes.

        An exit's logits are made again in the backward, not kept: the
        exits are one scan whose body is a `jax.checkpoint`, so the peak
        holds ONE exit's float32 logits beside their cotangent, not R. The
        gate and `p` are float32 (a product over the width on the vector
        unit, not a matmul at the default precision) and `log p` is made of
        log-sigmoids: R = 1 gives p = 1 and H = 0 exactly. Under sequence
        parallelism a shard holds its own rows of the states and the whole
        rows of the CEs: it weighs its own rows and the sums go over 'tp'
        too.

        Counters (`with_counters`): `loss_main` (the whole objective),
        `loss_exit` (R: each exit's mean CE), `exit_p_mean` (R: the mean of
        `p_r` over the tokens) and `exit_entropy` (the mean H)."""
        R = self.loop_steps

        def exit_ce(h):
            return self._token_ce(self._masked_logits(params, h, dtype),
                                  target_ids, mode)[0]

        with jax.named_scope("head_loss"):
            # (a scan of one pass is no loop once XLA has simplified it:
            # the barrier stays there, as `remat_wrap` says of a layer)
            ces = lax.map(jax.checkpoint(exit_ce, prevent_cse=R == 1),
                          states)                           # (R, b, t) f32
            valid = target_ids != IGNORE_INDEX
            if self.sequence_parallel:
                tl = states.shape[2]
                own = lambda a: lax.dynamic_slice_in_dim(
                    a, lax.axis_index("tp") * tl, tl, axis=-1)
                ces, valid = own(ces), own(valid)
                batch_axes = tuple(batch_axes) + ("tp",)
            with jax.named_scope("exit_gate"):
                gate = params["exit_gate"]
                z = (jnp.sum(states.astype(jnp.float32) * gate["weight"],
                             axis=-1) + gate["bias"])       # (R, b, t)
                p, log_p = exit_distribution(z)
                entropy = -jnp.sum(p * log_p, axis=0)           # (b, t)
            token = jnp.sum(p * ces, axis=0) - self.exit_entropy_coef * entropy
            live = valid.astype(jnp.float32)
            sums = lax.psum(
                {"loss": jnp.sum(token * live), "count": jnp.sum(live),
                 "exit": jnp.sum(ces * live, axis=(1, 2)),
                 "p": jnp.sum(p * live, axis=(1, 2)),
                 "entropy": jnp.sum(entropy * live)}, batch_axes)
        count = jnp.maximum(sums["count"], 1.0)
        loss = sums["loss"] / count
        if not with_counters:
            return loss
        return loss, jax.tree.map(lax.stop_gradient, {
            "loss_main": loss, "loss_exit": sums["exit"] / count,
            "exit_p_mean": sums["p"] / count,
            "exit_entropy": sums["entropy"] / count})

    def _extra_loss(self, params: Params, loss: jax.Array, x: jax.Array,
                    aux, trunk, input_ids, target_ids, position_ids,
                    mode: str, batch_axes):
        """A family's further loss terms on top of the main CE (`x` is the
        last layer's output, `trunk` what `_trunk` returned): (loss,
        counters of its own). Here the layers' own losses (`layer_losses`)
        and the layers' counters of a family that carries them."""
        counters = self._counters(aux, batch_axes)
        for term, rows in self.layer_losses.items():
            loss = loss + jnp.sum(counters[term] / counters[rows])
        return loss, counters

    def _counters(self, aux, batch_axes) -> Params:
        """The layers' counters summed over the batch axes; none where the
        aux is the router sums of the auxiliary losses, or nothing (a dense
        family whose mixers count says `_router_aux_losses` False too)."""
        if aux is None or self._router_aux_losses:
            return {}
        over = self._counter_reduces
        if any(k in over for k in aux):
            return {k: over.get(k, lax.psum)(a, batch_axes)
                    for k, a in aux.items()}
        return jax.tree.map(lambda a: lax.psum(a, batch_axes), aux)

    def expert_layer_rows(self, params: Params, rows: jax.Array) -> Params:
        """What the expert layers counted, one row a layer in the order
        the layers ran (`_counters`), as {parameter key: the key's rows,
        stacked as the key's layers are}: how a rule outside the gradient
        finds the leaf a row belongs to (`router_bias_speed`)."""
        out, at = {}, 0
        for block in self._pattern:
            if isinstance(block, str):
                if "moe" in params[block]:
                    n = jax.tree.leaves(params[block])[0].shape[0]
                    out[block] = rows[at:at + n]
                    at += n
                continue
            keys = [(key, n) for key, n in block if "moe" in params[key]]
            periods = jax.tree.leaves(params[block[0][0]])[0].shape[0]
            a_period = sum(n for _, n in keys)
            of_block = rows[at:at + periods * a_period].reshape(
                periods, a_period, *rows.shape[1:])
            at += periods * a_period
            first = 0
            for key, n in keys:
                out[key] = of_block[:, first:first + n]
                first += n
        return out

    # ---- global (jitted) entry points ----

    @property
    def _zigzag(self) -> bool:
        return self.cp_layout == "zigzag" and self.cp_size > 1

    def make_forward(self, mesh: Mesh):
        """Jitted global forward: (params, input_ids, position_ids) -> full
        logits (b, t, vocab_padded), vocab dim sharded over 'tp'.

        With cp_layout='zigzag', inputs are permuted into the zig-zag order
        before the shard_map and the logits inverse-permuted after, so the
        caller sees natural token order either way."""
        from ..ops.ring_attention import zigzag_perm

        fwd = jax.shard_map(
            self.forward_shard, mesh=mesh,
            in_specs=(self.specs(), P(("dp", "ep"), "cp"),
                      P(("dp", "ep"), "cp")),
            out_specs=P(("dp", "ep"), "cp", "tp"),
        )
        if not self._zigzag:
            return jax.jit(fwd)

        def zz(params, input_ids, position_ids):
            perm = zigzag_perm(input_ids.shape[1], self.cp_size)
            inv = perm.argsort()
            logits = fwd(params, input_ids[:, perm], position_ids[:, perm])
            return logits[:, inv]

        return jax.jit(zz)

    def make_loss(self, mesh: Mesh, mode: str = "vocab_parallel",
                  with_counters: bool = False, given_noise: bool = False):
        """Jitted global loss; with `with_counters`, (loss, counters) as
        `loss_shard` gives them (for `jax.value_and_grad(has_aux=True)`).

        A family that `draws_noise`: the function is `(params, input_ids,
        target_ids, position_ids, step)` and the noise is drawn HERE, by
        `_draw_noise(step, input_ids)` on the global batch before the
        shard_map, so it does not depend on the mesh; with `given_noise` it
        is `(params, input_ids, position_ids, *noise)`: the draw as arrays
        (`target_ids` is not read either way: the family makes its targets
        of the draw)."""
        from ..ops.ring_attention import zigzag_perm

        if self.draws_noise:
            batch = P(("dp", "ep"), "cp")

            def shard(params, input_ids, position_ids, *noise):
                return self.loss_shard(params, input_ids, input_ids,
                                       position_ids, mode=mode,
                                       with_counters=with_counters,
                                       noise=noise)

            fn = jax.shard_map(
                shard, mesh=mesh,
                in_specs=(self.specs(), batch, batch, *self._noise_specs()),
                out_specs=(P(), P()) if with_counters else P())
            if given_noise:
                return jax.jit(fn)

            def noised(params, input_ids, target_ids, position_ids, step):
                return fn(params, input_ids, position_ids,
                          *self._draw_noise(step, input_ids))

            return jax.jit(noised)

        loss = functools.partial(self.loss_shard, mode=mode,
                                 with_counters=with_counters)
        fn = jax.shard_map(
            loss, mesh=mesh,
            in_specs=(self.specs(), P(("dp", "ep"), "cp"),
                      P(("dp", "ep"), "cp"), P(("dp", "ep"), "cp")),
            out_specs=(P(), P()) if with_counters else P(),
        )
        if not self._zigzag:
            return jax.jit(fn)

        def zz(params, input_ids, target_ids, position_ids):
            # masked token-mean CE is permutation-invariant: permute all
            # three together, no unpermute needed
            perm = zigzag_perm(input_ids.shape[1], self.cp_size)
            return fn(params, input_ids[:, perm], target_ids[:, perm],
                      position_ids[:, perm])

        return jax.jit(zz)

    def doc_loss_shard(self, params: Params, input_ids: jax.Array,
                       target_ids: jax.Array, position_ids: jax.Array,
                       mode: str = "vocab_parallel"):
        """Per-DOCUMENT mean CE: ((b_local,) means f32, (b_local,) real-row
        mask). Uses the same vocab-parallel CE as training — no (b, t, V)
        logits gather. Padding rows (all IGNORE_INDEX) report mask False.

        Eval-only (forward under no grad); pp meshes are not supported here
        (evaluation runs dp x cp x tp, like the reference's)."""
        if self.pp_size > 1:
            raise ValueError("doc_loss runs on a pp=1 eval mesh")
        logits, _ = self._forward_with_aux(params, input_ids, position_ids)
        token_loss, valid = self._token_ce(logits, target_ids, mode)
        # per-row sums over this shard's sequence chunk, then totals over cp
        row_sum = lax.psum(jnp.sum(jnp.where(valid, token_loss, 0.0), axis=-1),
                           "cp")
        row_cnt = lax.psum(jnp.sum(valid.astype(jnp.float32), axis=-1), "cp")
        return row_sum / jnp.maximum(row_cnt, 1.0), row_cnt > 0

    def make_doc_loss(self, mesh: Mesh, mode: str = "vocab_parallel"):
        """Jitted per-document eval loss (see doc_loss_shard); the row dim
        stays sharded over ('dp', 'ep') like the batch."""
        from ..ops.ring_attention import zigzag_perm

        fn = jax.shard_map(
            functools.partial(self.doc_loss_shard, mode=mode), mesh=mesh,
            in_specs=(self.specs(), P(("dp", "ep"), "cp"),
                      P(("dp", "ep"), "cp"), P(("dp", "ep"), "cp")),
            out_specs=(P(("dp", "ep")), P(("dp", "ep"))),
        )
        if not self._zigzag:
            return jax.jit(fn)

        def zz(params, input_ids, target_ids, position_ids):
            # per-document masked means are token-permutation-invariant
            perm = zigzag_perm(input_ids.shape[1], self.cp_size)
            return fn(params, input_ids[:, perm], target_ids[:, perm],
                      position_ids[:, perm])

        return jax.jit(zz)
