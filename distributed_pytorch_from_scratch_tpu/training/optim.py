"""Adam + OneCycle LR schedule, hand-rolled (from scratch, like the rest).

The reference uses `optim.Adam(params, lr)` + `OneCycleLR(optimizer, max_lr,
total_steps, pct_start=warmup/max_steps)` (`/root/reference/train.py:83-84`).
This module reproduces torch's semantics exactly:

* Adam: bias-corrected first/second moments, eps inside the sqrt's
  denominator (torch defaults, betas=(0.9, 0.999), eps=1e-8). With
  `weight_decay > 0` the update is torch.optim.AdamW's instead: decoupled
  decay `p *= 1 - lr*wd` applied before the moment update, never entering
  the moments.
* OneCycleLR (torch defaults): two cosine phases —
  warmup  `initial_lr = max_lr/div_factor -> max_lr` over pct_start,
  anneal  `max_lr -> initial_lr/final_div_factor` over the rest;
  and because torch's `cycle_momentum=True` default applies to Adam via its
  betas, **beta1 is cycled too**: max_momentum (0.95) -> base_momentum (0.85)
  during warmup and back up during annealing. (torch overwrites Adam's 0.9
  beta1 at scheduler construction — subtle but real, and we match it.)

Equivalence against torch.optim itself is asserted in
tests/test_optim.py (torch-CPU is available in the image for testing only;
the framework itself never imports torch).

The optimizer state pytree mirrors the param pytree, so the same
PartitionSpecs shard it: each TP rank keeps Adam moments only for its own
weight shard — the same property the reference gets from per-rank
`optim.Adam(model.parameters())` (`train.py:83`).

ZeRO contract (training/zero.py): `adam_update` is deliberately
stage-oblivious. Every per-leaf operation below is elementwise, so when
the moments (ZeRO-1), the grads (ZeRO-2, from the bucketed
reduce-scatter) and/or the params (ZeRO-3) arrive dp-sharded on MATCHING
layouts, XLA computes the update on whichever dp shard owns the data —
the sharded-weight-update schedule falls out of the layouts alone, and
this module cannot drift out of sync with a stage it never sees. The two
cross-leaf reductions (`global_norm`, `clip_by_global_norm`) are global
sums at the jit level, so the clip threshold and the logged grad norm are
stage-invariant (XLA partial-sums per shard and all-reduces one scalar).
"""

from __future__ import annotations

from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ..config import OptimizerConfig


class AdamState(NamedTuple):
    step: jax.Array      # int32 scalar
    mu: Any              # first moment, same pytree as params
    nu: Any              # second moment


def init_adam_state(params: Any) -> AdamState:
    zeros = lambda p: jnp.zeros_like(p)
    return AdamState(
        step=jnp.zeros((), jnp.int32),
        mu=jax.tree.map(zeros, params),
        nu=jax.tree.map(zeros, params),
    )


def _anneal_cos(start: float, end: float, pct: jax.Array) -> jax.Array:
    return end + (start - end) / 2.0 * (1.0 + jnp.cos(jnp.pi * pct))


def onecycle_lr(cfg: OptimizerConfig, step: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(lr, beta1) at optimizer step `step` (0-based, i.e. the schedule value
    used by the (step+1)-th update — torch applies the initial lr at
    construction and steps the scheduler after each optimizer.step())."""
    total = cfg.max_steps
    pct_start = cfg.warmup_steps / cfg.max_steps
    # torch's phase boundaries: warmup ends at pct_start*total - 1, annealing
    # at total - 1 (OneCycleLR._schedule_phases).
    up_end = float(pct_start * total) - 1.0
    down_end = float(total) - 1.0
    initial_lr = cfg.lr / cfg.div_factor
    min_lr = initial_lr / cfg.final_div_factor

    stepf = step.astype(jnp.float32) if hasattr(step, "astype") else float(step)
    up_pct = jnp.clip(stepf / jnp.maximum(up_end, 1e-9), 0.0, 1.0)
    down_pct = jnp.clip((stepf - up_end) / jnp.maximum(down_end - up_end, 1e-9),
                        0.0, 1.0)
    in_warmup = stepf <= up_end

    lr = jnp.where(in_warmup,
                   _anneal_cos(initial_lr, cfg.lr, up_pct),
                   _anneal_cos(cfg.lr, min_lr, down_pct))
    if cfg.cycle_momentum:
        beta1 = jnp.where(in_warmup,
                          _anneal_cos(cfg.max_momentum, cfg.base_momentum, up_pct),
                          _anneal_cos(cfg.base_momentum, cfg.max_momentum, down_pct))
    else:
        beta1 = jnp.asarray(cfg.betas[0], jnp.float32)
    return lr, beta1


def cosine_lr(cfg: OptimizerConfig, step: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Linear warmup over warmup_steps -> cosine decay to
    cosine_min_ratio * lr at max_steps. beta1 stays fixed (momentum cycling
    is a OneCycle-ism). The standard pretraining schedule; the reference
    only has OneCycle (`/root/reference/train.py:84`)."""
    stepf = step.astype(jnp.float32) if hasattr(step, "astype") else float(step)
    warm = float(max(cfg.warmup_steps, 1))
    total = float(max(cfg.max_steps - cfg.warmup_steps, 1))
    min_lr = cfg.lr * cfg.cosine_min_ratio
    warm_lr = cfg.lr * jnp.minimum(1.0, (stepf + 1.0) / warm)
    pct = jnp.clip((stepf - cfg.warmup_steps) / total, 0.0, 1.0)
    decay_lr = _anneal_cos(cfg.lr, min_lr, pct)
    lr = jnp.where(stepf < cfg.warmup_steps, warm_lr, decay_lr)
    return lr, jnp.asarray(cfg.betas[0], jnp.float32)


def schedule_lr(cfg: OptimizerConfig, step: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(lr, beta1) for this step under cfg.lr_schedule."""
    if cfg.lr_schedule == "cosine":
        return cosine_lr(cfg, step)
    if cfg.lr_schedule != "onecycle":
        raise ValueError(f"unknown lr_schedule {cfg.lr_schedule!r} "
                         "(choices: 'onecycle', 'cosine')")
    return onecycle_lr(cfg, step)


def global_norm(grads: Any) -> jnp.ndarray:
    """Global L2 norm over a gradient pytree, reduced in float32 — shared
    by the clipper below and the train step's logged/sentinel-watched
    grad norm (training/train_step.py), so the two can never diverge."""
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree.leaves(grads)))


def clip_by_global_norm(grads: Any, max_norm: float) -> Any:
    """torch `clip_grad_norm_` semantics: one L2 norm over every grad leaf,
    scaled by max_norm/(norm + 1e-6) only when the norm exceeds max_norm."""
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-6))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype),
                        grads)


def router_bias_step(routed: jax.Array, speed: float) -> jax.Array:
    """What a router's selection bias moves by after a step in which its
    layer's routed experts were chosen for `routed` (..., experts) pairs
    (the step's own counts, over the whole batch): `speed` towards the
    experts under the mean load and away from those over it, zero-mean
    over the experts, in float32 (auxiliary-loss-free balancing as
    torchtitan writes it). Outside the gradient and outside Adam."""
    n = routed.astype(jnp.float32)
    delta = speed * jnp.sign(jnp.mean(n, axis=-1, keepdims=True) - n)
    return delta - jnp.mean(delta, axis=-1, keepdims=True)


def update_router_bias(params: Any, routed_by_key: Any, speed: float
                       ) -> Tuple[Any, jax.Array]:
    """`params` with `router_bias_step` added to the selection bias of every
    expert layer: `routed_by_key` is {parameter key: the counts of the key's
    layers, stacked as the layers are} (`DecoderStack.expert_layer_rows`).
    Also the mean size of a bias entry's step, which is 0 only where the
    rule did not run or every expert sat on the mean."""
    out, sizes = dict(params), []
    for key, routed in routed_by_key.items():
        moe = dict(out[key]["moe"])
        delta = router_bias_step(routed, speed)
        moe["bias"] = moe["bias"] + delta.astype(moe["bias"].dtype)
        out[key] = {**out[key], "moe": moe}
        sizes.append(jnp.abs(delta).reshape(-1))
    return out, jnp.mean(jnp.concatenate(sizes))


def adam_update(cfg: OptimizerConfig, params: Any, grads: Any,
                state: AdamState) -> Tuple[Any, AdamState]:
    """One Adam(W) step with this step's scheduled (lr, beta1)
    (OneCycle incl. cycled beta1, or warmup+cosine — cfg.lr_schedule).

    Matches torch.optim.Adam's update exactly:
        mu    <- b1*mu + (1-b1)*g
        nu    <- b2*nu + (1-b2)*g^2
        p     <- p - lr * (mu/(1-b1^t)) / (sqrt(nu/(1-b2^t)) + eps)
    and torch.optim.AdamW's when cfg.weight_decay > 0 (decay applied to p
    first; tests/test_optim.py asserts both against torch.optim itself).
    """
    if cfg.clip_grad_norm is not None:
        grads = clip_by_global_norm(grads, cfg.clip_grad_norm)
    step = state.step  # 0-based count of completed steps
    lr, beta1 = schedule_lr(cfg, step)
    beta2 = cfg.betas[1]
    t = (step + 1).astype(jnp.float32)
    # Bias correction with a *cycled* beta1: torch computes `1 - beta1**t`
    # with the CURRENT beta1 (the scheduler rewrites param_groups), so we do
    # the same.
    bc1 = 1.0 - jnp.power(beta1, t)
    bc2 = 1.0 - jnp.power(jnp.asarray(beta2, jnp.float32), t)

    def upd(p, g, m, v):
        g = g.astype(p.dtype)
        if cfg.weight_decay:
            # torch.optim.AdamW: p.mul_(1 - lr*wd) BEFORE the Adam step
            # (decoupled decay — never enters the moments)
            p = p * (1.0 - lr * cfg.weight_decay)
        m_new = beta1 * m + (1.0 - beta1) * g
        v_new = beta2 * v + (1.0 - beta2) * (g * g)
        m_hat = m_new / bc1
        v_hat = v_new / bc2
        p_new = p - lr * m_hat / (jnp.sqrt(v_hat) + cfg.eps)
        return p_new, m_new, v_new

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(state.mu)
    flat_v = treedef.flatten_up_to(state.nu)
    out = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
    new_p = treedef.unflatten([o[0] for o in out])
    new_m = treedef.unflatten([o[1] for o in out])
    new_v = treedef.unflatten([o[2] for o in out])
    return new_p, AdamState(step=step + 1, mu=new_m, nu=new_v)
