"""Step-memory estimate + the selector of what the backward keeps.

`models/transformer.REMAT_LADDER` orders the groups of per-layer residuals
a backward may keep instead of recomputing. `select_remat` climbs it once,
in that order, and keeps every group whose estimated peak, on top of those
kept so far, fits the device; a group that does not fit is passed over and
the climb goes on (`_pick`). It is what a model built with `remat="auto"`
(the default) calls at trace time with the per-shard shapes it is traced
with (`select_remat_traced`), and what `train.py --remat auto` calls with
the ZeRO stage only it knows. A rung's NAME still means its prefix of the
ladder; what `auto` resolves to is a set of groups, spelt as a rung where
it is one.

`estimate_step_gib` is held to what the chip counts (`memory_stats()`:
`peak_bytes_in_use + peak_bytes_reserved`), not to the compiler's plan: on
a v5e the plan (`memory_analysis().temp_size_in_bytes`) charges every
stack that lives from the forward loop to the backward loop twice, the
runtime reserves it once (PERF.md section 5 has both columns, per rung, for
the two GPT-2 cells; tests/test_attribution.py pins them). What a model
says of itself (`traced_step_bytes`) is held to the chip in all eleven
cells of the benchmark, at the floor and at the rung `auto` picks: never
under its count by more than 1%, never over by more than 5% (PERF.md
section 5, PR 62; tests/test_remat_topology.py has a case a cell and rung).
A drawn family's count of what a layer holds is SET from its cell's
reading, at that cell's shape: a job of another shape reads it as far off
as the families' docstrings say the untuned counts were (6 - 12%).
"""

from __future__ import annotations

import functools
import sys
from typing import Dict, Optional, Tuple

GIB = 1024 ** 3

# Of (limit - reserve), the share the chosen rung's estimate may take. The
# estimate has read within 1% of the chip at every rung measured in the two
# GPT-2 cells (cells 1 and 2: PR 26, PR 28) and within -0.5% / +4.5% at the
# floor and at the picked rung of the eight expert cells (PR 62; PERF.md
# section 5).
MARGIN = 0.93


def zero_state_bytes_per_param(zero_stage: int, dp: int,
                               cfg=None) -> float:
    """f32 bytes of RESIDENT train-state per parameter per dp rank under
    the ZeRO ladder (params + grads + 2 Adam moments; training/zero.py):

        stage 0:  4 + 4 + 8            = 16
        stage 1:  4 + 4 + 8/dp         (moments dp-sharded)
        stage 2:  4 + 4/dp + 8/dp      (grads reduce-scattered too)
        stage 3:  (4 + 4 + 8)/dp + transient gathered working set

    Stage 3's transient term (one gathered layer + the gathered non-layer
    leaves that live through the step) needs `cfg` for the layer split;
    it is charged as 4 bytes x (per-layer params + embed/head params) on
    top of the 16/dp resident floor. The itemised table lives in
    docs/PERF.md ("ZeRO ladder") and tests/test_attribution.py pins both
    against each other.
    """
    dp = max(dp, 1)
    if zero_stage <= 0 or dp == 1:
        return 16.0
    if zero_stage == 1:
        return 8.0 + 8.0 / dp
    if zero_stage == 2:
        return 4.0 + 12.0 / dp
    # stage 3: everything resident is sharded; the gather working set is
    # one layer (the scan bound) plus the embedding/head/final-norm leaves
    # gathered at their use sites and saved as backward residuals
    extra = 0.0
    if cfg is not None:
        P = cfg.num_params()
        nonlayer = (2 * cfg.vocab_size * cfg.attn_dim + cfg.vocab_size
                    + cfg.attn_dim)
        per_layer = max((P - nonlayer) / max(cfg.num_layers, 1), 0.0)
        extra = 4.0 * (per_layer + nonlayer) / max(P, 1)
    return 16.0 / dp + extra


def _kept_names(remat) -> Optional[Tuple[str, ...]]:
    """The ladder's names a remat value or CLI key keeps; None for no
    remat."""
    from ..models.stack import remat_names
    if remat is False or str(remat).lower() == "false":
        return None
    return remat_names(remat if remat is True else str(remat).lower())


def step_bytes(remat, *, param_count: float, layer_param_count: float,
               b: int, t: int, d: int, kd: int, f: int, heads: int,
               head_dim: int, layers: int, vocab: int, tp: int = 1,
               dtype_bytes: int = 2, ffn_inputs: int = 2,
               sequence_parallel: bool = False,
               state_bytes_per_param: float = 16.0,
               grad_bytes_per_param: float = 4.0,
               layer_extra_elems_per_token: float = 0.0,
               flash_lse_bytes_per_pair: float = 0.0,
               head_rows_share: float = 1.0,
               residual_streams: int = 1,
               tagged_layers: Optional[Dict[str, float]] = None,
               v_head_dim: Optional[int] = None,
               passes: Optional[int] = None,
               shared_elems_per_token: float = 0.0) -> Dict[str, float]:
    """Bytes one device holds at the peak of a fwd+bwd+Adam step, itemised.

    Everything is PER DEVICE: `param_count` / `layer_param_count` are this
    device's parameters (all, and those of the stacked layers), `b` the
    sequences of its data shard, `d`/`kd`/`f` the model's full widths
    (sharded here by `tp`), `layers` the layers of its pipeline stage.

    resident  params and Adam moments (and ZeRO's share of them): what
              `peak_bytes_in_use` reads, and what a snapshot doubles
    grads     the f32 gradient tree, live from the backward to Adam
    cast      the stacked layer weights in the compute dtype (the compiler
              hoists the casts out of the layer loops)
    stacks    the layer input every rung keeps (`residual_streams` x d wide:
              a family of hyper-connections carries several,
              `DecoderStack.stream_mixer`), L of it, plus the residuals of
              the groups `remat` keeps (`models/stack.remat_groups`: a
              rung's prefix of the ladder, or a joined set) at their
              logical sizes (the chip's count: the stack
              of `flash_out` is not kept in the kernel's layout, which pads
              a head of 64 to the 128 lanes, and `flash_lse` is named as
              (b h, t) float32, t on the lanes; a family whose mask is data
              keeps `flash_lse_bytes_per_pair` a (row, key) pair of a
              sequence under that name too), each over the layers that
              tag it: `tagged_layers` (`DecoderStack.tagged_layers`: a drawn
              family's MLP names are its dense layers', the flash names its
              attention layers'; None: all L), `flash_out` at `v_head_dim`
              a head where v is not of q's width; and the values layers
              LEAVE for later layers (`DecoderStack.shares_values`:
              `shared_elems_per_token` elements a token in the compute
              dtype), kept whatever the rung
    head      logits in f32 and once more in the compute dtype, on
              `head_rows_share` of the rows (a family whose loss reads part
              of the rows the stack sees: `DecoderStack.head_rows_share`)
    layer     one layer's recompute + backward working set; a family whose
              layer holds more than the dense skeleton's six d-wide and
              3.4 f-wide tensors says how many elements a token more
              (`DecoderStack.layer_extra_elems_per_token`)
    The peak is resident + cast + stacks + max(head, grads + layer): the
    head's backward is over before the layers' gradients exist.

    `passes` (`DecoderStack.loop_steps`: a family whose stack a step passes
    R times over the same weights; None: once): the kept layer input and
    every rung's named stack are R x `layers` deep (the walk of R x
    `layers` layer applications keeps each one's, `DecoderStack.
    _loop_passes`), `stacks` also holds what each pass keeps around its
    final norm, R times the norm's input and its output (the R normed
    states the exits read), and those states once more in float32 (the
    exit gate's product over the width reads them so, and its backward
    again), the head's transient is still ONE exit's (an exit's logits are
    made again in the backward), and the layers' gradient is counted ONCE,
    as every family's is: the backward walk adds a layer application's
    weight gradient into its slice of the one stack (PR 67; the scans'
    own transpose held a second stack, and PR 66 counted it). Held to the
    chip at the one cell that passes (cell 14: PERF.md section 5, PR 67).
    """
    kept = _kept_names(remat)
    tok = b * t
    R = passes or 1
    depth = layers * R
    act = 1.0 / tp if sequence_parallel else 1.0
    wide = tok * d * dtype_bytes * act       # a (b, t, d) tensor
    # a column-linear's output (the heads' whole width: the model's, unless
    # a family's heads are wider)
    q_w = tok * (heads * head_dim / tp) * dtype_bytes
    kv_w = tok * (kd / tp) * dtype_bytes
    f_w = tok * (f / tp) * dtype_bytes
    h_local = heads / tp
    names = {
        "flash_out": tok * h_local * (v_head_dim or head_dim) * dtype_bytes,
        "flash_lse": tok * (h_local * 4 + t * flash_lse_bytes_per_pair),
        "q_proj": q_w, "k_proj": kv_w, "v_proj": kv_w,
        "attn_proj": wide if tp > 1 else 0.0,   # named only past a reduce
        "ffn_fc": f_w if ffn_inputs == 1 else 0.0,
        "ffn_gate": f_w if ffn_inputs == 2 else 0.0,
        "ffn_up": f_w if ffn_inputs == 2 else 0.0,
    }
    if kept is None:
        # no remat: everything autodiff saves on the flash path — layer
        # input, 2 norm outputs, q/k/v and their head-split copies, flash
        # o/lse + the projection's input, both row-linear outputs, the
        # FFN's inputs and activation
        per_layer = ((4 + residual_streams) * wide + 2 * q_w + 4 * kv_w
                     + names["flash_out"] + names["flash_lse"]
                     + (ffn_inputs + 1) * f_w)
        stacks = depth * per_layer
    else:
        tagged = tagged_layers or {}
        stacks = depth * residual_streams * wide + sum(
            tagged.get(n, layers) * R * names[n] for n in kept)
    if passes is not None:
        stacks += (2 + 4 / dtype_bytes) * passes * wide
    stacks += tok * shared_elems_per_token * dtype_bytes
    out = {
        "resident": param_count * (state_bytes_per_param
                                   - grad_bytes_per_param),
        "grads": param_count * grad_bytes_per_param,
        "cast": (layer_param_count * dtype_bytes if dtype_bytes < 4
                 else 0.0),
        "stacks": stacks,
        "head": tok * head_rows_share * (vocab / tp) * (4 + dtype_bytes),
        "layer": tok * dtype_bytes * (6 * d * act + 3.4 * f / tp
                                      + layer_extra_elems_per_token),
    }
    out["total"] = (out["resident"] + out["cast"] + out["stacks"]
                    + max(out["head"], out["grads"] + out["layer"]))
    return out


def _cfg_step_bytes(cfg, batch: int, seqlen: int, remat, tp: int, world: int,
                    dtype_bytes: int, zero_stage: int, dp: int, family: str,
                    sequence_parallel: bool) -> Dict[str, float]:
    """`step_bytes` of `cfg` built as `family`, at the GLOBAL `batch` over
    `world` devices. Parameters shard over tp (the big matrices and the
    vocabulary do; the norms and biases that do not are a thousandth of the
    count), the batch over world / tp. ZeRO shrinks the state per
    `zero_state_bytes_per_param`."""
    tp = max(tp, 1)
    f = cfg.ffn_dim
    if cfg.num_experts:
        # each token's residuals touch top_k expert FFNs plus the dispatch
        # buffers (~capacity_factor x the dense width)
        f = int(f * max(cfg.moe_top_k, 1) * cfg.moe_capacity_factor / 2)
    from ..models import family_class
    fam = family_class(family)
    P = fam.num_params(cfg)
    nonlayer = cfg.vocab_size * cfg.attn_dim * (1 if fam.tied_head else 2)
    return step_bytes(
        remat, param_count=P / tp, layer_param_count=(P - nonlayer) / tp,
        b=max(batch // max(world // tp, 1), 1), t=seqlen, d=cfg.attn_dim,
        kd=cfg.kv_dim, f=f, heads=cfg.num_heads, head_dim=cfg.head_dim,
        layers=cfg.num_layers, vocab=cfg.padded_vocab_size(tp), tp=tp,
        dtype_bytes=dtype_bytes, ffn_inputs=fam.ffn_inputs,
        sequence_parallel=sequence_parallel,
        state_bytes_per_param=zero_state_bytes_per_param(zero_stage, dp,
                                                         cfg),
        grad_bytes_per_param=4.0 / max(dp, 1) if zero_stage >= 2 else 4.0)


def estimate_step_gib(cfg, batch: int, seqlen: int, remat,
                      tp: int = 1, world: int = 1,
                      dtype_bytes: int = 2, zero_stage: int = 0,
                      dp: int = 1, family: str = "llama",
                      sequence_parallel: bool = False) -> float:
    """Peak-HBM estimate (GiB, per device) for one fwd+bwd+Adam train step
    of `cfg` built as `family`, at the GLOBAL `batch` over `world` devices.
    `remat` is a rung of the ladder ('true' ... 'dots'), a joined set of
    its groups or 'false'."""
    return _cfg_step_bytes(cfg, batch, seqlen, remat, tp, world, dtype_bytes,
                           zero_stage, dp, family,
                           sequence_parallel)["total"] / GIB


def hbm_budget_gib() -> float:
    """Per-device HBM of the attached backend, from `memory_stats()`. A
    backend that reports none (the CPU test mesh) raises: a remat policy
    sized against an assumed 16 GiB is a decision about a chip that is not
    there — callers off-chip pass `select_remat(budget_gib=...)` or name
    the policy."""
    import jax
    dev = jax.local_devices()[0]
    stats = dev.memory_stats() or {}
    limit = stats.get("bytes_limit") or stats.get("bytes_reservable_limit")
    if not limit:
        raise ValueError(
            f"the {dev.platform} backend reports no memory_stats, so there "
            f"is no HBM budget to size 'remat auto' against: name the "
            f"policy (--remat true|dots|false), or pass budget_gib to "
            f"select_remat")
    return limit / GIB


def _pick(parts, budget_gib: Optional[float], reserve_gib: Optional[float],
          allow_false: bool, verbose: bool, note: str = "") -> str:
    """The one selector: 'false' (above the ladder) where it fits, else ONE
    climb of the ladder in its order from the floor (the layer input only).
    Each group is sized on top of those kept so far, `parts(value)["total"]`
    (a `step_bytes`), and kept if that fits MARGIN x (budget - reserve); a
    group that does not fit is PASSED OVER and the climb goes on to the
    next (dense layers at long context: the MLP's stacks are 4 GiB where
    the flash outputs and q, k, v behind them are 0.7); a group that adds
    nothing here (a name no layer of this model tags: `attn_proj` at tp 1,
    the MLP's names where every layer is routed) is neither. What comes
    back names the groups kept (`models/stack.remat_spelling`): the lowest
    rung whose prefix keeps exactly that, else the joined spelling.

    `budget_gib` None reads the device; a backend with no `memory_stats`
    (the CPU) then gets rung 0, the program `remat=True` has always been.
    `reserve_gib` None leaves room for one more copy of the resident state,
    `AsyncCheckpointer`'s snapshot, which a model cannot know its caller
    makes, wherever the floor rung fits beside it; where even the floor
    does not, nothing kept could honour the reserve, no snapshot can be
    taken and nothing is held back (`reserve_held` False: a chip's share of
    an expert model, 7 - 9 GiB of state). A `reserve_gib` the caller names
    is held as given. Says what it chose on stderr and on the program's
    tracer: the instant's `kept` (the groups, in order), `passed_over`
    (group -> the estimate that refused it) and `grads_gib` (the estimate's
    gradient tree, the same whatever is kept)."""
    from ..models.stack import REMAT_RUNGS, remat_spelling
    from ..obs.trace import current_tracer
    floor = REMAT_RUNGS[0]
    if budget_gib is None:
        try:
            budget_gib = hbm_budget_gib()
        except ValueError:
            return floor        # nothing was sized: nothing to say
    at_floor = parts(floor)
    reserve, held = reserve_gib, True
    if reserve is None:
        reserve = at_floor["resident"] / GIB
        held = at_floor["total"] / GIB <= (budget_gib - reserve) * MARGIN
        if not held:
            reserve = 0.0
    usable = (budget_gib - reserve) * MARGIN
    sizes = {}      # in the order they were asked for: the line's order

    def size(key):
        if key not in sizes:
            sizes[key] = (at_floor if key == floor
                          else parts(key))["total"] / GIB
        return sizes[key]

    kept, empty, passed_over = [], [], {}
    picked = "false" if allow_false and size("false") <= usable else floor
    size(floor)     # always said
    for group in REMAT_RUNGS[1:] if picked == floor else ():
        asked = remat_spelling(kept + [group], empty)
        if size(asked) <= size(picked):
            empty.append(group)
        elif size(asked) <= usable:
            kept.append(group)
            picked = asked
        else:
            passed_over[group] = size(asked)
    fields = dict(rung=picked, estimate_gib=sizes[picked],
                  budget_gib=budget_gib, reserve_gib=reserve,
                  reserve_held=held, usable_gib=usable,
                  grads_gib=at_floor["grads"] / GIB, kept=kept,
                  passed_over=passed_over,
                  **{f"estimate_gib.{k}": v for k, v in sizes.items()})
    tracer = current_tracer()
    if tracer is not None:
        tracer.instant("remat_auto", **fields)
    if verbose:
        est = ", ".join(f"{k}={v:.2f}GiB" for k, v in sizes.items())
        over = (" passing over " + ", ".join(passed_over)
                if passed_over else "")
        print(f"remat auto: picked '{picked}'{over} (estimates {est}; "
              f"budget {budget_gib:.2f} GiB - reserve {reserve:.2f} GiB, x "
              f"margin {MARGIN}; reserve_held={held}{note})",
              file=sys.stderr)
    return picked


def select_remat(cfg, batch: int, seqlen: int, tp: int = 1, world: int = 1,
                 budget_gib: Optional[float] = None, verbose: bool = True,
                 zero_stage: int = 0, dp: int = 1, family: str = "llama",
                 reserve_gib: Optional[float] = None,
                 sequence_parallel: bool = False) -> str:
    """What a step may keep of a layer with its estimated peak fitting the
    device: 'false', or the groups of `models/transformer.REMAT_LADDER` the
    climb keeps (`_pick`), under a rung's name ('true' ... 'dots') where
    they are its prefix and joined ('true+flash+dots') where a group in
    the middle was passed over; a value `Transformer(remat=...)` takes
    once 'true'/'false' go through `config.REMAT_CHOICES`.

    `zero_stage`/`dp` size the train state per the ZeRO ladder. Stage 3
    never picks 'false': without remat, autodiff saves every layer's
    GATHERED weights as backward residuals — the full replica the stage
    exists to eliminate (the train CLI refuses the explicit combination
    with the same rationale). See `_pick` for budget and reserve.
    """
    dtype_bytes = 2 if cfg.compute_dtype == "bfloat16" else 4
    zn = f"; zero{zero_stage} dp{dp}" if zero_stage else ""
    return _pick(lambda key: _cfg_step_bytes(
        cfg, batch, seqlen, key, tp, world, dtype_bytes, zero_stage, dp,
        family, sequence_parallel), budget_gib, reserve_gib,
        allow_false=zero_stage < 3, verbose=verbose, note=zn)


@functools.lru_cache(maxsize=None)
def select_remat_traced(model, param_count: int, layer_param_count: int,
                        b: int, t: int) -> str:
    """`select_remat` for a model that is being traced: `remat="auto"`
    resolves here, once per (model, per-shard shapes), from what the trace
    holds — this device's parameter count and its (b, t) token block — and
    the device's `memory_stats()`. No second compile, no flag. The model
    cannot see a ZeRO stage (stage 0's state is the largest) and never
    picks 'false': what the climb keeps is always a rematerialising model,
    which is what ZeRO-3's gather inside the layer body needs."""
    return _pick(traced_step_bytes(model, param_count, layer_param_count, b,
                                   t),
                 model.remat_budget_gib, None, allow_false=False,
                 verbose=True,
                 note=f"; traced b{b} x t{t}, tp{model.tp_size}")


def traced_step_bytes(model, param_count: int, layer_param_count: int,
                      b: int, t: int):
    """remat value -> `step_bytes` of `model` at one device's parameter
    counts and (b, t) token block, with what the model says of itself
    beside its config's widths."""
    cfg = model.cfg
    pp = model.pp_size
    return functools.partial(
        step_bytes, param_count=param_count,
        layer_param_count=layer_param_count, b=b,
        t=t, d=cfg.attn_dim, kd=model.kv_dim,
        f=cfg.ffn_dim, heads=cfg.num_heads, head_dim=model.head_dim,
        layers=model.stacked_layers // pp,
        vocab=cfg.padded_vocab_size(model.tp_size), tp=model.tp_size,
        dtype_bytes=2 if cfg.compute_dtype == "bfloat16" else 4,
        ffn_inputs=model.ffn_inputs,
        sequence_parallel=model.tp_layout(t)[0],
        layer_extra_elems_per_token=model.layer_extra_elems_per_token,
        flash_lse_bytes_per_pair=model.flash_lse_bytes_per_pair,
        head_rows_share=model.head_rows_share,
        residual_streams=model.residual_streams,
        tagged_layers={name: n // pp
                       for name, n in model.tagged_layers.items()},
        v_head_dim=model.v_head_dim, passes=model.loop_steps,
        shared_elems_per_token=model.shared_elems_per_token)
