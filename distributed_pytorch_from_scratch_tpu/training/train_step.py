"""The jitted training step: loss + grads + Adam/OneCycle update.

The TPU-native analogue of the reference's hot loop body
(`/root/reference/train.py:94-109`): one XLA program per step — forward,
backward (shard_map transpose inserts the conjugate collectives), optimizer
update — with params and optimizer state donated so updates happen in-place
in HBM (no reallocation per step; the reference relies on torch's in-place
`optimizer.step()` for the same effect).
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..config import OptimizerConfig
from ..models.transformer import Transformer
from .optim import (AdamState, adam_update, global_norm,
                    update_router_bias)
from .zero import (build_bucketed_grad_fn, build_zero3_grad_fn,
                   zero1_moment_shardings, zero3_shardings)


def resolve_zero_stage(zero, zero1: bool = False) -> int:
    """The ZeRO stage from the `zero`/`zero1` kwargs: explicit `zero`
    wins; `zero1=True` is the PR 4-era alias for stage 1. The ONE owner
    of the precedence rule — the builders and the train CLI both resolve
    through here."""
    if zero is not None:
        stage = int(zero)
        if stage not in (0, 1, 2, 3):
            raise ValueError(f"zero stage must be 0..3, got {zero!r}")
        return stage
    return 1 if zero1 else 0


_resolve_stage = resolve_zero_stage  # internal alias used by the builders


def _make_grad_fn(model: Transformer, mesh, loss_mode: str,
                  dp_reduce_bucket_mb: float = 0.0, dp_reduce_dtype=None,
                  zero_stage: int = 0, with_counters: bool = False):
    """(params, ids, tgt, pos) -> (loss, grads), or with `with_counters`
    ((loss, counters), grads): the transpose-derived
    whole-tree reducer by default; with dp_reduce_bucket_mb > 0 the
    bucketed-overlap reducer (training/zero.build_bucketed_grad_fn — DP
    psums issued per size-bounded bucket, optionally bf16/int8 on the
    wire). zero_stage=2 swaps the bucketed all-reduce for the bucketed
    REDUCE-SCATTER (grads come back dp-sharded, half the wire bytes);
    zero_stage=3 is the gather-on-demand path (params AND grads dp-sharded,
    training/zero.build_zero3_grad_fn) — both default bucket_mb to 25 when
    the caller left it 0, since their wire IS the bucketed one."""
    if zero_stage >= 2 or dp_reduce_bucket_mb:
        what = (f"ZeRO stage {zero_stage}" if zero_stage >= 2
                else "the bucketed gradient reducer")
        if not model.hand_reduced_grads:
            raise ValueError(
                f"{what} is not made to work with the "
                f"{type(model).__name__} family: training/zero.py's "
                f"builders reduce per-shard gradients by hand over the "
                f"parameter tree of a dense stack (use ZeRO stage 0 or 1)")
        if with_counters:
            raise ValueError(
                f"with_counters needs the default gradient path; {what} "
                f"builds its own loss call")
    if zero_stage >= 3:
        if dp_reduce_dtype is not None:
            # the CLIs refuse this with their own message; the builder is
            # the backstop so a library caller can't silently lose the
            # compressed wire it asked for
            raise ValueError(
                "dp_reduce_dtype with zero stage 3: the ZeRO-3 grad "
                "reduce-scatter rides the parameter all-gather's "
                "transpose (an f32 ppermute ring), so a compressed wire "
                "would silently not apply — use stage 2, whose bucketed "
                "reduce-scatter carries the compressed payload")
        return build_zero3_grad_fn(model, mesh, loss_mode,
                                   bucket_mb=dp_reduce_bucket_mb or 25.0)
    if zero_stage == 2:
        return build_bucketed_grad_fn(model, mesh, loss_mode,
                                      bucket_mb=dp_reduce_bucket_mb or 25.0,
                                      reduce_dtype=dp_reduce_dtype,
                                      zero_stage=2)
    if dp_reduce_bucket_mb:
        return build_bucketed_grad_fn(model, mesh, loss_mode,
                                      bucket_mb=dp_reduce_bucket_mb,
                                      reduce_dtype=dp_reduce_dtype)
    if with_counters:
        return jax.value_and_grad(
            model.make_loss(mesh, mode=loss_mode, with_counters=True),
            has_aux=True)
    return jax.value_and_grad(model.make_loss(mesh, mode=loss_mode))


def _step_body(model: Transformer, mesh, ocfg: OptimizerConfig,
               loss_mode: str, with_grad_norm: bool = False,
               dp_reduce_bucket_mb: float = 0.0, dp_reduce_dtype=None,
               zero_stage: int = 0, with_counters: bool = False):
    """The one train-step body shared by both builders: grad + Adam/OneCycle.
    Keeping it single-sourced means the scanned (multi-step) program can
    never silently diverge from the per-step one.

    `with_grad_norm=True` (the train CLI's mode) makes the third output
    `(loss, grad_norm)` instead of `loss` — computed on-device inside the
    same program, fetched only at the loop's logging-interval D2H, so the
    sentinel costs no extra syncs. `with_counters=True` appends the loss's
    counters (`DecoderStack.loss_shard`) to that output: `(loss, grad_norm,
    counters)`, or `(loss, counters)` without the norm.

    A family whose configuration publishes the speed of its routers'
    selection bias (`DecoderStack.router_bias_speed`) gets the rule run
    after Adam, inside `optimizer` under the scope `router_bias`, from the
    `routed` counts this same step returned (summed over the batch axes as
    every counter is): the step then always asks the loss for its counters,
    and hands them on only `with_counters`, with `router_bias_step` (the
    mean size of a bias entry's step) among them."""
    bias_speed = model.router_bias_speed
    grad_fn = _make_grad_fn(model, mesh, loss_mode,
                            dp_reduce_bucket_mb, dp_reduce_dtype,
                            zero_stage=zero_stage,
                            with_counters=with_counters
                            or bias_speed is not None)

    def step(params, opt_state: AdamState, input_ids, target_ids,
             position_ids):
        # The scopes are what a device trace's `op_name` can say that JAX
        # cannot know (jvp / transpose / rematted_computation it adds
        # itself): benchmark/lib/program_trace.py splits the step by them.
        # a family whose loss draws noise gets the step's count to fold
        # into its key (`DecoderStack.draws_noise`)
        noise_step = (opt_state.step,) if model.draws_noise else ()
        with jax.named_scope("loss_and_grad"):
            loss, grads = grad_fn(params, input_ids, target_ids,
                                  position_ids, *noise_step)
        extra = ()
        if with_counters or bias_speed is not None:
            loss, counters = loss
        if with_counters:
            extra = (counters,)
        # grad norm: optim.global_norm — the SAME reduction the clipper
        # uses, so the logged/sentinel-watched norm equals the one
        # acted on (and XLA can CSE the two when both are present)
        if with_grad_norm:
            with jax.named_scope("grad_norm"):
                out = (loss, global_norm(grads)) + extra
        else:
            out = (loss,) + extra if extra else loss
        with jax.named_scope("optimizer"):
            params, opt_state = adam_update(ocfg, params, grads, opt_state)
            if bias_speed is not None:
                with jax.named_scope("router_bias"):
                    params, moved = update_router_bias(
                        params, model.expert_layer_rows(
                            params, counters["routed"]), bias_speed)
                if with_counters:
                    out = out[:-1] + ({**counters,
                                       "router_bias_step": moved},)
        return params, opt_state, out

    return step


def _jit_with_zero(fn, model, mesh, zero_stage, moment_shardings,
                   loss_sharding):
    """jit `fn` with donated params/opt state; under a ZeRO stage, pin the
    state to its sharded layouts (training/zero.py) so XLA derives the
    stage's schedule:

    * stage 1 — Adam moments dp-sharded, params replicated: the
      partitioner computes each moment/param update on the owning dp shard
      and all-gathers the fresh params.
    * stage 2 — same out_shardings as stage 1; the grads ARRIVE dp-sharded
      from the bucketed reduce-scatter (zero1-layout, so the update is
      local to the moment shard) and the params' end-of-step all-gather
      replaces the grad reduction's gather half.
    * stage 3 — params AND moments pinned to `zero3_shardings`: grads come
      back on the same layout from the gather transposes, the Adam update
      is fully local (no collective at all in the optimizer), and the
      fresh params REST sharded — the next step's forward re-gathers per
      layer.

    `moment_shardings` lets the caller pass the tree it already built for
    `device_put`-ing the initial state, so there is exactly one source of
    the moment layout; derived here when omitted.

    The ids/tgt/pos batch buffers are deliberately NOT donated: XLA
    donation is strictly input->output aliasing, and the int32 batch
    stack has no compatible output to alias — donating it frees nothing
    and warns on every compile. Donation hygiene is instead VERIFIED:
    obs/introspect reports the program's aliased bytes, so a refactor
    that silently breaks the params/opt donation (e.g. a dtype change
    un-aliasing the Adam moments) shows up in the train log's compile
    report instead of as a quiet 2x optimizer-state footprint."""
    donate = (0, 1)
    if zero_stage >= 3:
        param_sh = zero3_shardings(model, mesh)
        moment_sh = (moment_shardings if moment_shardings is not None
                     else param_sh)
    else:
        # Stage 0 pins its outputs too (moments on the params' own
        # shardings): without out_shardings XLA picks output layouts
        # freely, and on this jax/XLA a dozen small leaves (norm gains,
        # biases) come back in a layout that does NOT match their donated
        # input — the donation is silently dropped and those leaves
        # double-buffer. Found by graftcheck's donation-aliased contract
        # (ISSUE 11); value-parity is covered by the stage-0 train tests.
        param_sh = model.shardings(mesh)
        if moment_shardings is not None:
            moment_sh = moment_shardings
        else:
            moment_sh = (zero1_moment_shardings(model, mesh)
                         if zero_stage else param_sh)
    scalar = NamedSharding(mesh, P())
    opt_sh = AdamState(step=scalar, mu=moment_sh, nu=moment_sh)

    def shard_tree(spec):
        # isinstance-P first: PartitionSpec is tuple-like on older jax
        if isinstance(spec, P):
            return NamedSharding(mesh, spec)
        return tuple(shard_tree(s) for s in spec)

    return jax.jit(fn, donate_argnums=donate,
                   out_shardings=(param_sh, opt_sh,
                                  shard_tree(loss_sharding)))


def build_train_step(model: Transformer, mesh, ocfg: OptimizerConfig,
                     loss_mode: str = "vocab_parallel",
                     zero1: bool = False, moment_shardings=None,
                     with_grad_norm: bool = False,
                     dp_reduce_bucket_mb: float = 0.0, dp_reduce_dtype=None,
                     zero: "int | None" = None,
                     with_counters: bool = False):
    """Returns jitted
    (params, opt_state, input_ids, target_ids, position_ids)
      -> (params, opt_state, loss)            [default]
      -> (params, opt_state, (loss, gnorm))   [with_grad_norm=True]
      -> (params, opt_state, (loss, gnorm, counters))  [+ with_counters:
         what the loss is made of and what the layers counted, a dict of
         replicated arrays; off by default]

    `dp_reduce_bucket_mb > 0` swaps the whole-tree DP grad reduction for
    the bucketed-overlap reducer (with `dp_reduce_dtype=jnp.bfloat16` for
    a compressed wire) — see training/zero.build_bucketed_grad_fn.

    `zero` picks the ZeRO stage (0..3; supersedes the `zero1` bool, kept
    as an alias for stage 1). Stage 2 routes grads through the bucketed
    reduce-scatter; stage 3 additionally expects params (and the initial
    moments) device_put at `zero3_shardings` — they rest dp-sharded and
    the forward gathers per layer.
    """
    stage = _resolve_stage(zero, zero1)
    step = _step_body(model, mesh, ocfg, loss_mode,
                      with_grad_norm=with_grad_norm,
                      dp_reduce_bucket_mb=dp_reduce_bucket_mb,
                      dp_reduce_dtype=dp_reduce_dtype, zero_stage=stage,
                      with_counters=with_counters)
    # a P() stands for a whole subtree (the counters' dict)
    out_spec = (P(),) * (1 + with_grad_norm + with_counters)
    if len(out_spec) == 1:
        out_spec = P()
    return _jit_with_zero(step, model, mesh, stage, moment_shardings,
                          out_spec)


def build_train_step_multi(model: Transformer, mesh, ocfg: OptimizerConfig,
                           loss_mode: str = "vocab_parallel",
                           zero1: bool = False, moment_shardings=None,
                           with_grad_norm: bool = False,
                           dp_reduce_bucket_mb: float = 0.0,
                           dp_reduce_dtype=None,
                           zero: "int | None" = None):
    """Multi-step-per-dispatch variant: one jitted program runs
    `lax.scan` over a leading steps axis of the batch.

    (params, opt_state, input_ids(N,B,T), target_ids(N,B,T),
     position_ids(N,B,T)) -> (params, opt_state, losses(N))

    Identical training to N calls of `build_train_step`'s program (the scan
    body IS `_step_body`, same Adam/OneCycle state threading) but with ONE
    host dispatch, so the per-dispatch host cost is amortised N-fold (what
    that cost is on the chip has not been measured on this code: PERF.md).
    The reference has no analogue — its hot loop is necessarily
    one `optimizer.step()` per Python iteration
    (`/root/reference/train.py:94-109`).
    """
    stage = _resolve_stage(zero, zero1)
    step = _step_body(model, mesh, ocfg, loss_mode,
                      with_grad_norm=with_grad_norm,
                      dp_reduce_bucket_mb=dp_reduce_bucket_mb,
                      dp_reduce_dtype=dp_reduce_dtype, zero_stage=stage)

    def multi_step(params, opt_state: AdamState, input_ids, target_ids,
                   position_ids):
        def body(carry, batch):
            p, o, out = step(*carry, *batch)
            return (p, o), out

        (params, opt_state), outs = jax.lax.scan(
            body, (params, opt_state), (input_ids, target_ids, position_ids))
        # with_grad_norm: outs is (losses(N), gnorms(N)) — scan stacks each
        return params, opt_state, outs

    out_spec = (P(None), P(None)) if with_grad_norm else P(None)
    return _jit_with_zero(multi_step, model, mesh, stage, moment_shardings,
                          out_spec)


def build_grad_accum_step(model: Transformer, mesh, ocfg: OptimizerConfig,
                          loss_mode: str = "vocab_parallel",
                          zero1: bool = False, moment_shardings=None,
                          with_grad_norm: bool = False,
                          dp_reduce_bucket_mb: float = 0.0,
                          dp_reduce_dtype=None,
                          zero: "int | None" = None):
    """Gradient accumulation: ONE optimizer step from the MEAN of the
    microbatch gradients.

    (params, opt_state, input_ids(A,B,T), target_ids(A,B,T),
     position_ids(A,B,T)) -> (params, opt_state, mean_loss)

    Semantics are torch-DDP-style mean-of-means: each microbatch's masked
    token-mean CE and its gradient get equal weight regardless of how many
    valid tokens each holds (identical to a single A*B batch whenever the
    valid counts match). Peak activation memory stays that of ONE microbatch
    — the scan carries only the f32 grad sum — so effective batch scales
    without scaling HBM. The reference has no accumulation (SURVEY
    non-goals); this is the TPU-native extension of its loop.
    """
    stage = _resolve_stage(zero, zero1)
    if model.router_bias_speed is not None:
        raise ValueError(
            f"gradient accumulation is not made to work with the "
            f"{type(model).__name__} family: the rule that updates its "
            f"routers' selection bias reads ONE step's counts "
            f"(training/optim.router_bias_step), and the microbatches' "
            f"counters are not summed")
    grad_fn = _make_grad_fn(model, mesh, loss_mode,
                            dp_reduce_bucket_mb, dp_reduce_dtype,
                            zero_stage=stage)

    def step(params, opt_state: AdamState, input_ids, target_ids,
             position_ids):
        zeros = jax.tree.map(jnp.zeros_like, params)

        def body(acc, batch):
            loss_sum, g_sum = acc
            with jax.named_scope("loss_and_grad"):
                loss, g = grad_fn(params, *batch)
            return (loss_sum + loss, jax.tree.map(jnp.add, g_sum, g)), None

        (loss_sum, g_sum), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), zeros),
            (input_ids, target_ids, position_ids))
        a = input_ids.shape[0]
        grads = jax.tree.map(lambda x: x / a, g_sum)
        # the norm of the MEAN gradient — the quantity Adam actually sees
        if with_grad_norm:
            with jax.named_scope("grad_norm"):
                out = (loss_sum / a, global_norm(grads))
        else:
            out = loss_sum / a
        with jax.named_scope("optimizer"):
            params, opt_state = adam_update(ocfg, params, grads, opt_state)
        return params, opt_state, out

    out_spec = (P(), P()) if with_grad_norm else P()
    return _jit_with_zero(step, model, mesh, stage, moment_shardings,
                          out_spec)


