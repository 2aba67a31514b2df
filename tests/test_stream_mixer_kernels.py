"""The hyper-connection mixers' Pallas kernels (ops/pallas/stream_mixer.py)
under the interpreter, against the `jax.numpy` text of parallel/hyper.py.
CPU, small sizes, seeded.

* the four kernels against the text: the maps, u and X' and every gradient
  (X, y, W, alpha, b), float32 and bfloat16, a layer's mixer and an exit
  mixer, a token count that is and is not a multiple of the block;
* maps built by hand (the benchmark's `bf16_maps` control builds
  `StreamMaps(pre, post, res)` positionally) still run: `pre` by the text,
  `post` by the write kernel, whose backward then makes its own part of dX
  (a layer's mixer defers it to the read's backward);
* what the benchmark's reader needs of the calls: no `pallas_call` of the
  mixer path has 3 or 6 operands or a name that starts `flash_`, and the
  kernels' ops carry `mhc/maps`, `mhc/post` and `mhc/exit` in the lowered
  step, in the backward too;
* each joint says which path it took on the program's tracer (`mhc_joint`);
* W's three pieces are W, and two pieces are 16 bits of it.
"""

import dataclasses
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_from_scratch_tpu.obs.trace import SpanTracer
from distributed_pytorch_from_scratch_tpu.ops.pallas import (
    stream_mixer as kernels)
from distributed_pytorch_from_scratch_tpu.parallel.hyper import (StreamMaps,
                                                                 StreamMixer)

N, C = 4, 128


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Two grid steps at 256 tokens, forward and backward."""
    monkeypatch.setattr(kernels, "FWD_BLOCK", 128)
    monkeypatch.setattr(kernels, "BWD_BLOCK", 128)


def mixers(exit_only=False, **kw):
    text = StreamMixer(C, N, exit_only=exit_only, **kw)
    return text, dataclasses.replace(text, interpret=True)


def inputs(tokens, dtype, b=2):
    key = lambda i: jax.random.key(i)
    X = jax.random.normal(key(1), (N, b, tokens // b, C)).astype(dtype)
    y = jax.random.normal(key(2), X.shape[1:]).astype(dtype)
    cu = jax.random.normal(key(3), X.shape[1:])
    cx = jax.random.normal(key(4), X.shape)
    return X, y, cu, cx


def joint(mixer):
    """One mixer as the stack calls it, its sublayer's output an input:
    (params, X, y) -> (loss, (maps, u, X'))."""
    def run(p, X, y, cu, cx):
        if mixer.exit_only:
            maps = mixer.maps(p, X)
            u = mixer.exit(p, X)
            return jnp.sum(u.astype(jnp.float32) * cu), (maps[:3], u, None)
        maps = mixer.maps(p, X)
        u = mixer.pre(maps, X)
        out = mixer.post(maps, X, y)
        loss = (jnp.sum(u.astype(jnp.float32) * cu)
                + jnp.sum(out.astype(jnp.float32) * cx)
                + jnp.sum(jnp.sin(maps.post)) + jnp.sum(maps.res ** 2))
        return loss, (maps[:3], u, out)
    return jax.jit(jax.value_and_grad(run, argnums=(0, 1, 2), has_aux=True))


def close(got, want, tol, name):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert got.shape == want.shape, name
    err = np.abs(got - want).max()
    assert err <= tol * max(np.abs(want).max(), 1e-30), (name, err)


@pytest.mark.parametrize("tokens", [256, 200], ids=["whole", "ragged"])
@pytest.mark.parametrize("exit_only", [False, True], ids=["layer", "exit"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_kernels_equal_the_text(dtype, exit_only, tokens):
    """float32 within 1e-5 of the largest entry; bfloat16 within what the
    roundings allow: the kernels round a value once where the text rounds
    the cotangent of X once for each of its three uses."""
    text, kernel = mixers(exit_only)
    p = text.init(jax.random.key(0))
    args = inputs(tokens, jnp.dtype(dtype))
    with jax.default_matmul_precision("highest"):
        (want, want_aux), want_g = joint(text)(p, *args)
        (got, got_aux), got_g = joint(kernel)(p, *args)
    exact = dtype == "float32"
    tol = 1e-5 if exact else 2.0 ** -7
    close(got, want, 1e-5 if exact else 2e-3, "loss")
    for name, a, b in zip(("pre", "post", "res"), got_aux[0], want_aux[0]):
        if b is not None:
            assert a.dtype == jnp.float32
            close(a, b, 1e-5, name)
    close(got_aux[1], want_aux[1], tol, "u")
    assert got_aux[1].dtype == jnp.dtype(dtype)
    if not exit_only:
        close(got_aux[2], want_aux[2], tol, "out")
    for k in ("w", "alpha", "b"):
        close(got_g[0][k], want_g[0][k], 1e-5 if exact else 2e-2, k)
        assert np.any(np.asarray(want_g[0][k]))
    close(got_g[1], want_g[1], 1e-5 if exact else 3 * 2.0 ** -7, "dX")
    if not exit_only:
        close(got_g[2], want_g[2], tol, "dy")


def test_maps_built_by_hand_take_the_text_for_pre_and_the_kernel_for_post():
    """`benchmark/tools/mhc_control.py` replaces `maps` and builds
    `StreamMaps(pre, post, res)`: no u, no streams."""
    text, kernel = mixers()
    p = text.init(jax.random.key(0))
    X, y, *_ = inputs(256, jnp.float32)
    by_hand = StreamMaps(*text.maps(p, X))
    assert by_hand.u is None and by_hand.through is None
    close(kernel.pre(by_hand, X), text.pre(by_hand, X), 1e-6, "u")
    close(kernel.post(by_hand, X, y), text.post(by_hand, X, y), 1e-5, "out")
    # streams that came through no read joint: the write's backward makes
    # its own part of dX
    loss = lambda mixer: lambda X, y, res, post: jnp.sum(jnp.sin(mixer.post(
        StreamMaps(by_hand.pre, post, res), X, y)))
    args = (X, y, by_hand.res, by_hand.post)
    for got, want, name in zip(
            jax.grad(loss(kernel), argnums=(0, 1, 2, 3))(*args),
            jax.grad(loss(text), argnums=(0, 1, 2, 3))(*args),
            ("dX", "dy", "dH", "dpost")):
        close(got, want, 1e-5, name)
    # and the kernel path's maps carry both
    maps = kernel.maps(p, X)
    assert maps.u.shape == X.shape[1:] and maps.through[0].shape == X.shape
    assert text.maps(p, X).u is None


def test_a_width_the_kernels_do_not_hold_is_refused_by_name():
    mixer = StreamMixer(64, 4, interpret=True)
    with pytest.raises(ValueError, match="multiple of 128"):
        mixer.maps(mixer.init(jax.random.key(0)),
                   jnp.zeros((4, 1, 32, 64), jnp.float32))
    assert not kernels.holds(4, 64, jnp.dtype("float32"))
    assert kernels.holds(4, 3584, jnp.dtype("bfloat16"))
    assert not kernels.holds(6, 128, jnp.dtype("bfloat16"))     # 48 maps
    assert not kernels.holds(4, 128, jnp.dtype("float16"))
    # off the TPU the text runs unasked
    text = StreamMixer(C, N)
    assert not text._kernels(jnp.zeros((4, 1, 32, C), jnp.bfloat16))


# ---- what the benchmark's reader needs of the calls ----

def _pallas_calls(jaxpr, found):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append((str(eqn.params.get("name")
                              or eqn.params["name_and_src_info"]),
                          len(eqn.invars)))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _pallas_calls(sub, found)
    return found


@pytest.mark.parametrize("exit_only", [False, True], ids=["layer", "exit"])
def test_no_call_reads_as_a_flash_kernel(exit_only):
    """`benchmark/lib/kernels.FLASH`: a Mosaic call of 3 or 6 operands, or
    one named `flash_*`."""
    _, kernel = mixers(exit_only)
    p = kernel.init(jax.random.key(0))
    args = inputs(256, jnp.bfloat16)
    calls = _pallas_calls(jax.make_jaxpr(joint(kernel))(p, *args).jaxpr, [])
    names = sorted({re.match(r"\w+", name).group() for name, _ in calls})
    assert names == (["mhc_read_bwd", "mhc_read_fwd"] if exit_only else
                     ["mhc_read_bwd", "mhc_read_fwd", "mhc_write_bwd",
                      "mhc_write_fwd"])
    for name, operands in calls:
        assert operands not in (3, 6), (name, operands)
        assert not name.startswith("flash")
    by_name = {re.match(r"\w+", name).group(): k for name, k in calls}
    assert by_name["mhc_read_fwd"] == 4
    assert by_name["mhc_read_bwd"] == (7 if exit_only else 9)
    if not exit_only:
        assert by_name["mhc_write_fwd"] == 4 and by_name["mhc_write_bwd"] == 5


def test_the_kernels_ops_carry_the_mixers_scopes_backward_too():
    """`benchmark/lib/mhc_scopes.py` attributes an op by the last scope of
    its `op_name`: the interpreted kernels' ops, forward and backward, sit
    under `mhc/maps`, `mhc/post` and `mhc/exit` in a compiled step, and
    nothing of a kernel is left without `mhc`."""
    _, layer = mixers()
    _, leave = mixers(exit_only=True)
    p, q = layer.init(jax.random.key(0)), leave.init(jax.random.key(1))
    X, y, cu, cx = inputs(256, jnp.bfloat16)

    def loss(p, q, X):
        with jax.named_scope("loss_and_grad"):
            maps = layer.maps(p, X)
            out = layer.post(maps, X, jnp.tanh(layer.pre(maps, X)))
            return jnp.sum(leave.exit(q, out).astype(jnp.float32) * cu)

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        p, q, X).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    for scope in ("mhc/maps", "mhc/post", "mhc/exit", "mhc/sinkhorn"):
        ops = [n for n in names if re.search(rf"(^|/){scope}(/|$)", n)]
        assert any("transpose(" in n for n in ops), scope
        assert any("transpose(" not in n for n in ops), scope
    # an interpreted kernel's ops name their kernel: all of them under mhc
    kernel_ops = [n for n in names if "mhc_" in n]
    assert kernel_ops
    for n in kernel_ops:
        assert re.search(r"(^|/)mhc/(maps|post|exit)(/|$)", n), n
    for kernel, scope in (("mhc_read_fwd", "maps"), ("mhc_read_bwd", "maps"),
                          ("mhc_write_fwd", "post"),
                          ("mhc_write_bwd", "post"),
                          ("mhc_read_fwd", "exit"), ("mhc_read_bwd", "exit")):
        assert any(kernel in n and f"mhc/{scope}" in n for n in kernel_ops), (
            kernel, scope)


# ---- the counter that says the mechanism engaged ----

@pytest.mark.parametrize("path", ["kernel", "xla"])
def test_a_joint_says_its_path_on_the_tracer(path, tmp_path):
    text, kernel = mixers()
    mixer = kernel if path == "kernel" else text
    leave = dataclasses.replace(mixer, exit_only=True)
    p, q = mixer.init(jax.random.key(0)), leave.init(jax.random.key(1))
    X, y, *_ = inputs(256, jnp.bfloat16)
    tracer = SpanTracer(str(tmp_path))
    try:
        maps = mixer.maps(p, X)
        mixer.post(maps, X, mixer.pre(maps, X))
        leave.exit(q, X)
    finally:
        tracer.close()
    joints = [e["args"] for e in map(json.loads,
                                     open(tmp_path / "trace.jsonl"))
              if e["name"] == "mhc_joint"]
    assert [f["part"] for f in joints] == ["read", "write", "exit"]
    for f in joints:
        assert f["path"] == path and (f["n"], f["d"]) == (N, C)
        assert f["tokens"] == 256 and f["dtype"] == "bfloat16"
        assert f["block"] == (128 if path == "kernel" else None)


# ---- W's pieces ----

def test_three_pieces_are_w_and_each_is_exact_in_bfloat16():
    w = jax.random.normal(jax.random.key(0), (64, 24)) * jnp.exp(
        jax.random.normal(jax.random.key(1), (64, 24)) * 4)
    three = kernels._split(w, 3)
    np.testing.assert_array_equal(sum(three), w)
    for piece in three:
        np.testing.assert_array_equal(
            piece.astype(jnp.bfloat16).astype(jnp.float32), piece)
    two = kernels._split(w, 2)
    assert float(jnp.max(jnp.abs(sum(two) - w) / jnp.abs(w))) < 2.0 ** -15
    # side by side in one pass's columns, stacked in one pass's rows
    side = kernels._w_side_by_side(w.reshape(4 * 16, 24), 4, 16, 24, 3)
    assert side.shape == (4, 16, 128) and side.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        sum(side[..., k * 24:(k + 1) * 24].astype(jnp.float32)
            for k in range(3)).reshape(64, 24), w)
    assert not np.any(np.asarray(side[..., 72:], np.float32))
    stacked = kernels._wt_stacked(w.reshape(64, 24), 4, 16, 24, 3)
    assert stacked.shape == (4, 128, 16)
    np.testing.assert_array_equal(stacked[:, :24], stacked[:, 48:72])
