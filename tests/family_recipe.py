"""One recipe that holds a drawn family to its plain reference: what every
`tests/test_<family>.py` wrote out for itself until PR 74.

A family's file states what is its own (its reference's loss, its batch's
length, the leaves it counts, a floor a leaf needs, a subclass under the
interpreter) and hands it to this module, which owns the rest:

* the tiny configuration (`TINY_PRESETS`, the one table; `tests/
  test_model_families.py` reads it too), the batch, the mesh and the model;
* the initialised parameters ONCE a (family, cfg, seed), the reference's
  loss and gradients (and logits) ONCE a (cfg, batch), the program's ONCE a
  (cfg, layout, batch): the configurations are frozen dataclasses, so an
  in-process `lru_cache` keyed on them is enough. A grid's cases differ in
  what they COMPARE and take what they share from here;
* one leaf-by-leaf comparison that names the leaf that fails;
* the helpers that were the same text in several files (`apply_moe`,
  `lowered_text`, the train CLI's token file, `jitted` for an eager sweep).

Nothing here is on disk, and nothing is a pytest option: a plain module the
family files import (`tests/` is on the path: rootdir conftest, no package).
"""

import dataclasses
import functools
import re
from typing import Callable, Optional

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from distributed_pytorch_from_scratch_tpu.config import (IGNORE_INDEX,
                                                         MeshConfig,
                                                         OptimizerConfig,
                                                         model_preset)
from distributed_pytorch_from_scratch_tpu.models import FAMILIES, build_model
from distributed_pytorch_from_scratch_tpu.runtime.mesh import make_mesh
from distributed_pytorch_from_scratch_tpu.training.optim import (
    init_adam_state)
from distributed_pytorch_from_scratch_tpu.training.train_step import (
    build_train_step)

TINY_PRESETS = {"llama": "tiny", "gpt2": "tiny", "mla_moe": "tiny-mla-moe",
                "gdn_moe": "tiny-gdn-moe", "conv_moe": "tiny-conv-moe",
                "bd_moe": "tiny-bd-moe", "swa_moe": "tiny-swa-moe",
                "early_moe": "tiny-early-moe",
                "mhc_mla_moe": "tiny-mhc-mla-moe",
                "kda_mla_moe": "tiny-kda-mla-moe", "ssm_moe": "tiny-ssm-moe",
                "loop_llama": "tiny-loop-llama",
                "ssm_dense": "tiny-ssm-dense", "dsa_moe": "tiny-dsa-moe",
                "sambay": "tiny-sambay"}


# ---- the configuration, the batch, the mesh, the model ----

def batch(cfg, b=2, t=64, seed=0, low=3, ignore=()):
    """(ids, targets, positions) of `b` seeded sequences of `t` tokens
    (`low`: the smallest id drawn; a family that keeps ids for itself says
    3; `ignore`: the (row, column)s whose target is no target)."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(low, cfg.vocab_size, (b, t + 1)).astype(np.int32)
    tgt = ids[:, 1:].copy()
    for at in ignore:
        tgt[at] = IGNORE_INDEX
    return ids[:, :-1], tgt, np.tile(np.arange(t, dtype=np.int32), (b, 1))


@functools.lru_cache(maxsize=None)
def mesh_of(tp=1, dp=1):
    return make_mesh(MeshConfig(dp=dp, tp=tp),
                     devices=jax.devices()[:dp * tp])


@functools.lru_cache(maxsize=None)
def init_params(family, cfg, seed):
    """`init`'s global tree (it does not depend on the layout), made once a
    (configuration, seed). Eagerly, as the files did: its small programs
    are shared by every configuration of a process, where one jitted `init`
    a configuration is a whole compile each (read in the lane: slower)."""
    return build_model(family, cfg).init(jax.random.key(seed))


# ---- the reference and the program, each once ----

@functools.lru_cache(maxsize=None)
def _reference(family, fn, cfg, seed, batch_key, has_aux, variant):
    params = init_params(family, cfg, seed)
    ids, tgt, pos = batch(cfg, **dict(batch_key))
    with jax.default_matmul_precision("highest"):
        return params, jax.jit(jax.value_and_grad(
            lambda p: fn(cfg, p, ids, tgt, pos, **dict(variant)),
            has_aux=has_aux))(params)


@functools.lru_cache(maxsize=None)
def _reference_logits(family, fn, cfg, seed, batch_key):
    ids, _, pos = batch(cfg, **dict(batch_key))
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p: fn(cfg, p, ids, pos))(
            init_params(family, cfg, seed))


@functools.lru_cache(maxsize=None)
def _program(family, cfg, seed, batch_key, tp, dp, cls, with_counters,
             logits, params_of, kw):
    mesh = mesh_of(tp, dp)
    model = (cls or FAMILIES[family])(cfg, tp_size=tp, **dict(kw))
    params = jax.device_put(init_params(family, params_of or cfg, seed),
                            model.shardings(mesh))
    ids, tgt, pos = batch(cfg, **dict(batch_key))
    loss = model.make_loss(mesh, with_counters=with_counters)
    with jax.default_matmul_precision("highest"):
        out = jax.jit(jax.value_and_grad(loss, has_aux=with_counters))(
            params, ids, tgt, pos)
        if logits:
            out = (*out, model.make_forward(mesh)(params, ids, pos))
    return out


def _key(**kw):
    return tuple(sorted(kw.items()))


@dataclasses.dataclass(frozen=True)
class Recipe:
    """A family's own data, and the recipe bound to it. `loss(cfg, params,
    ids, targets, positions, **variant)` is the plain reference's loss,
    `logits(cfg, params, ids, positions)` its logits; `t`, `low` and
    `ignore` the file's usual batch (`batch`)."""
    family: str
    loss: Callable
    logits: Optional[Callable] = None
    t: int = 64
    low: int = 3
    ignore: tuple = ()

    def tiny(self, dtype="float32", **facts):
        """The family's tiny preset, `facts` replaced in its facts field."""
        cfg = model_preset(TINY_PRESETS[self.family], compute_dtype=dtype)
        extra = FAMILIES[self.family].config_extra
        return dataclasses.replace(cfg, **{extra: dataclasses.replace(
            getattr(cfg, extra), **facts)})

    def _batch_key(self, t, b):
        return _key(t=self.t if t is None else t, b=b, low=self.low,
                    ignore=self.ignore)

    def batch(self, cfg, b=2, t=None, seed=0):
        return batch(cfg, seed=seed, **dict(self._batch_key(t, b)))

    def on_mesh(self, cfg, tp=1, dp=1, **kw):
        return mesh_of(tp, dp), build_model(self.family, cfg, tp_size=tp,
                                            **kw)

    def params(self, cfg, seed=3):
        return init_params(self.family, cfg, seed)

    def reference(self, cfg, t=None, seed=3, b=2, has_aux=False,
                  cached=True, **variant):
        """(parameters, the reference's (loss, gradients)) on `batch(cfg,
        t)`, compiled once for every case that compares with it (`variant`:
        what the reference's loss takes beyond the batch). `cached=False`
        where a test has patched what the reference runs."""
        fn = _reference if cached else _reference.__wrapped__
        return fn(self.family, self.loss, cfg, seed, self._batch_key(t, b),
                  has_aux, _key(**variant))

    def reference_logits(self, cfg, t=None, seed=3, b=2):
        return _reference_logits(self.family, self.logits, cfg, seed,
                                 self._batch_key(t, b))

    def program(self, cfg, tp=1, dp=1, t=None, seed=3, b=2, cls=None,
                with_counters=False, logits=False, params_of=None,
                cached=True, **kw):
        """The program's (loss, gradients) on the same parameters and batch
        at a layout, through `cls` where a file steers a subclass; with
        `with_counters` ((loss, counters), gradients); with `logits` the
        forward's logits behind them; on the parameters of `params_of`
        where the reference is another configuration's. Once a distinct
        call: a rung or a layout two cases share is compiled for the first
        (`cached=False` where a test has patched what the program runs)."""
        fn = _program if cached else _program.__wrapped__
        return fn(self.family, cfg, seed, self._batch_key(t, b), tp, dp, cls,
                  with_counters, logits, params_of, _key(**kw))

    def train(self, cfg, tp=2, dp=1, steps=6, t=64, b=2, seed=0,
              max_steps=20, **model_kw):
        """`steps` train steps (gradient norm and counters on, the files'
        schedule) from `init`'s parameters on one batch: (the losses, what
        the last step returned beside the state, (mesh, model, params, opt,
        batch) for a file that goes on from there)."""
        mesh, model = self.on_mesh(cfg, tp, dp, **model_kw)
        params = jax.device_put(self.params(cfg, seed),
                                model.shardings(mesh))
        opt = init_adam_state(params)
        ocfg = OptimizerConfig(lr=3e-3, warmup_steps=2, max_steps=max_steps)
        step = build_train_step(model, mesh, ocfg, with_grad_norm=True,
                                with_counters=True)
        data = self.batch(cfg, b=b, t=t)
        losses = []
        for _ in range(steps):
            params, opt, out = step(params, opt, *data)
            losses.append(float(out[0]))
        return losses, out, (mesh, model, params, opt, data)


# ---- one comparison, leaf by leaf ----

def leaf_errors(want_g, got_g, floor=1e-6, err=None):
    """[(error, leaf's name, whether the reference's leaf is nonzero)] of
    `got_g` against the reference's `want_g`, in tree order: a leaf's
    largest difference over its largest entry (over `floor` where the leaf
    is smaller: a number, or a function of the leaf's name), or `err(got,
    want)` where a file measures otherwise."""
    flat = jax.tree_util.tree_leaves_with_path(want_g)
    out = []
    for (path, a), b in zip(flat, jax.tree.leaves(got_g), strict=True):
        name = jax.tree_util.keystr(path)
        a, b = np.asarray(a), np.asarray(b)
        if err is not None:
            e = err(b, a)
        else:
            least = floor(name) if callable(floor) else floor
            e = np.max(np.abs(a - b)) / max(np.max(np.abs(a)), least)
        out.append((float(e), name, bool(np.any(a != 0))))
    return out


def worst_leaf(want_g, got_g, floor=1e-6):
    """(the largest of `leaf_errors`, the leaf it is at)."""
    return max(leaf_errors(want_g, got_g, floor))[:2]


def hold_leaves(want_g, got_g, rtol=1e-5, floor=1e-6, err=None):
    """Every leaf of `got_g` within `rtol` (a number, or a function of the
    leaf's name) of the reference's, the failing leaf named. Returns the
    names of all leaves and of those the reference's gradient reaches."""
    errors = leaf_errors(want_g, got_g, floor, err)
    for e, name, _ in errors:
        assert e <= (rtol(name) if callable(rtol) else rtol), (name, e)
    return ([name for _, name, _ in errors],
            [name for _, name, moved in errors if moved])


def hold_loss(want, got, rtol=1e-5):
    assert abs(float(got) - float(want)) <= rtol * abs(float(want)), (
        float(got), float(want))


# ---- what was the same text in several files ----

def on_one_device(fn, in_specs, out_specs):
    """`fn` under `shard_map` on the one-device mesh, jitted."""
    return jax.jit(jax.shard_map(fn, mesh=mesh_of(), in_specs=in_specs,
                                 out_specs=out_specs))


def apply_moe(moe, params, x, router_x=None):
    """An expert FFN's `apply` on one device: (y, counters)."""
    if router_x is None:
        return on_one_device(lambda p, x: moe.apply(p, x),
                             (moe.specs(), P()), (P(), P()))(params, x)
    return on_one_device(lambda p, x, r: moe.apply(p, x, router_x=r),
                         (moe.specs(), P(), P()), (P(), P()))(
                             params, x, router_x)


def lowered_step(family, cfg, shape=(4, 256)):
    """The family's train step on one device, lowered at `shape` (gradient
    norm on; counters where the family has facts)."""
    model = build_model(family, cfg)
    kw = dict(with_counters=True) if cfg.family_facts else {}
    step = build_train_step(model, mesh_of(), OptimizerConfig(),
                            with_grad_norm=True, **kw)
    params = jax.eval_shape(model.init, jax.random.key(0))
    ids = jax.ShapeDtypeStruct(shape, np.int32)
    return step.lower(params, jax.eval_shape(init_adam_state, params), ids,
                      ids, ids)


def lowered_text(family, cfg, shape=(4, 256), debug_info=False):
    """`lowered_step`'s StableHLO: with `debug_info` as lowered, name
    stacks and all; otherwise locations stripped, the text the digests are
    of."""
    lowered = lowered_step(family, cfg, shape)
    if debug_info:
        return lowered.as_text(debug_info=True)
    return re.sub(r"loc\(.*?\)|#loc.*|metadata=\{[^}]*\}", "",
                  lowered.as_text())


class _Seen:
    def instant(self, name, **fields):
        self.fields = fields


def picked_rung(monkeypatch, family, cfg, budget_gib, b=4, t=256):
    """(model, the rung `select_remat_traced` picks it at `b` x `t` under
    `budget_gib`, the fields of the instant it writes on the tracer)."""
    from distributed_pytorch_from_scratch_tpu.obs import trace as obs_trace
    from distributed_pytorch_from_scratch_tpu.training import memory
    model = build_model(family, cfg, remat_budget_gib=budget_gib)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    count = lambda tree: sum(x.size for x in jax.tree.leaves(tree))
    seen = _Seen()
    monkeypatch.setattr(obs_trace, "_current", seen)
    memory.select_remat_traced.cache_clear()
    rung = memory.select_remat_traced(
        model, count(shapes),
        sum(count(shapes[k]) for k in model._layer_keys), b, t)
    return model, rung, seen.fields


def token_file(tmp_path):
    """The token file the train CLI's smoke reads (chip_smoke.py's)."""
    from chip_smoke import write_tokens
    tokens = tmp_path / "tokens.json"
    write_tokens(str(tokens), 503, 16, 65)
    return tokens


@functools.lru_cache(maxsize=256)
def _outputs_and_grads(fn, scalar, n):
    def both(*a):
        grads = jax.grad(lambda *b: scalar(*fn(*b)), tuple(range(n)))(*a)
        return fn(*a), grads
    return jax.jit(both)


def outputs_and_grads(fn, scalar, *args, precision="highest"):
    """(`fn(*args)`, a tuple; the gradients of `scalar(*fn(*args))` in every
    one of `args`) as ONE compiled program, under float32 products unless
    `precision` says otherwise: what a sweep's case ran eagerly, an op
    compiled and dispatched at a time. The outputs are `fn`'s own call, as
    a caller that takes no gradient makes it: where `fn` has a derivative
    written by hand, its forward rule is another function, and both are
    held. The program is kept by (`fn`, `scalar`): a sweep that hands the
    SAME two functions to cases of the same shapes (a reference that reads
    no chunk size, a rule whose cases differ in their data) compiles them
    for the first."""
    with jax.default_matmul_precision(precision):
        return _outputs_and_grads(fn, scalar, len(args))(*args)


def jitted(fn, *args):
    """`fn(*args)` as ONE compiled program under float32 products: what a
    sweep's case ran eagerly, an op compiled and dispatched at a time."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(fn)(*args)
