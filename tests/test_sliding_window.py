"""The third attention mask declaration, `ops/attention.sliding_window(W)`:
a row sees itself and the W - 1 rows before it. CPU, float32.

* the declaration against a double loop, W in {1, 3, block - 1, block,
  block + 1, >= t};
* the flash kernels under it (the Pallas interpreter) against the dense XLA
  path, forward and all three gradients, with at least three tiles a side
  so that a dead tile, a tile the window's left edge crosses, a tile wholly
  inside the band and a diagonal tile all occur; the head resident (the
  forward's row walk and the ONE backward kernel) and the gridded walks; a
  group > 1; windows that are and are not multiples of the block; sub-tile
  grain (blocks of several sub-tiles); one tile a head;
* a window off by one row fails the same comparison;
* the plan: skipped + computed sub-tiles are all of them, every live entry
  lies in a computed sub-tile, an unmasked sub-tile holds live entries
  only, and computed over live stays under a stated number;
* a window that covers the sequence is the triangle's own program text;
* what the kernels cannot plan is refused with the XLA path's name.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from family_recipe import outputs_and_grads

from distributed_pytorch_from_scratch_tpu.obs.attribution import (
    flash_tile_stats)
from distributed_pytorch_from_scratch_tpu.ops.attention import (
    CAUSAL, live_entries, mask_matrix, masked_attention,
    masked_attention_xla, sliding_window)
from distributed_pytorch_from_scratch_tpu.ops.pallas import (
    flash_attention as fa_mod)


@pytest.mark.parametrize("w", [1, 3, 127, 128, 129, 160, 500])
def test_the_declaration_is_the_double_loop(w):
    t = 160
    want = np.zeros((t, t), bool)
    for i in range(t):
        for j in range(t):
            want[i, j] = j <= i and i - j < w
    live = np.asarray(mask_matrix(sliding_window(w), t))
    np.testing.assert_array_equal(live, want)
    assert live.sum() == live_entries(sliding_window(w), t)
    assert live.any(axis=1).all()           # every row sees itself
    if w >= t:
        np.testing.assert_array_equal(live, mask_matrix(CAUSAL, t))


def test_a_window_sees_a_row_at_least():
    with pytest.raises(ValueError, match="sees itself"):
        sliding_window(0)


def _operands(t, hq, hkv, width=64):
    key = jax.random.key(0)
    shape = lambda h: (1, h, t, width)
    return (jax.random.normal(jax.random.fold_in(key, 1), shape(hq)),
            jax.random.normal(jax.random.fold_in(key, 2), shape(hkv)),
            jax.random.normal(jax.random.fold_in(key, 3), shape(hkv)),
            jax.random.normal(jax.random.fold_in(key, 4), shape(hq)))


def _walks(monkeypatch, walk):
    """`grid`: every budget shut, the gridded forward and the split
    backward. `once`: the backward's first budget shut alone, so the one
    kernel keeps its whole-row blocks once (PR 56)."""
    if walk == "grid":
        monkeypatch.setattr(fa_mod, "KV_ROW_VMEM_BYTES", 0)
        monkeypatch.setattr(fa_mod, "BWD_ROW_ONCE_VMEM_BYTES", 0)
    if walk in ("grid", "once"):
        monkeypatch.setattr(fa_mod, "BWD_ROW_VMEM_BYTES", 0)


CASES = [
    # t, W, block, query heads, key-value heads, walk
    (512, 256, 128, 4, 2, "row"), (512, 256, 128, 4, 2, "grid"),
    (512, 200, 128, 2, 1, "row"), (512, 200, 128, 2, 1, "grid"),
    (384, 64, 128, 2, 2, "row"), (384, 64, 128, 2, 2, "grid"),
    (640, 384, 128, 1, 1, "row"),
    (1536, 1024, 512, 2, 1, "row"), (1536, 640, 512, 1, 1, "grid"),
    (256, 100, 256, 2, 1, "row"),
    # a group of 7 query heads a key-value head (not a power of two: the
    # group is a sequential grid dimension of the dk/dv kernel), the
    # backward SPLIT under the window (`flash_bwd_dq_window` /
    # `flash_bwd_dkv_window`, planned by the grid's guards) and resident
    (384, 200, 128, 14, 2, "grid"), (384, 200, 128, 7, 1, "row"),
    (512, 256, 128, 7, 1, "grid"),
    # the eighth cell's forward since PR 52, in small: a group of 7, the head
    # resident, a window that reaches back over five key tiles of the six
    # (one edge tile, a loop over four whole ones, the diagonal)
    (768, 600, 128, 7, 1, "row"),
    # the eighth cell's backward since PR 56, in small: the same head over
    # the first budget, its blocks kept once; a group of 1 and two key-value
    # heads beside it, a window that is no multiple of the block
    (768, 600, 128, 7, 1, "once"), (512, 200, 128, 2, 2, "once"),
    (1536, 1024, 512, 4, 2, "once"),
]


@pytest.mark.parametrize("t,w,block,hq,hkv,walk", CASES)
def test_the_kernels_under_the_window_equal_the_dense_path(
        t, w, block, hq, hkv, walk, monkeypatch, flash_bwd_calls):
    """Forward and all three gradients. The first case a pair has a window
    of two blocks (diagonal, whole, edge, dead tiles); the second one that
    is no multiple of the block (two edge tiles, none whole); the third a
    window inside one block (the diagonal tile has a left edge too); then a
    window of three blocks (the whole tiles are a loop of two), blocks of
    several sub-tiles, and one tile a head (the fused backward); last the
    eighth cell's grouping, 7 query heads a key-value head, under the split
    backward (two key-value heads, a window that is no multiple of the
    block; and a window of two blocks) and the resident one; and that
    grouping with the head resident under a window of more than four key
    tiles; and the resident backward with its blocks kept once, whose
    call says so."""
    _walks(monkeypatch, walk)
    mask = sliding_window(w)
    q, k, v, wt = _operands(t, hq, hkv)
    kernel = lambda q, k, v: fa_mod.flash_attention(
        q, k, v, block, block, block, block, interpret=True, mask=mask)
    if walk == "once":
        assert flash_bwd_calls(kernel, q, k, v) == [
            ("flash_bwd_window", [1] * 9)]
    dense = lambda q, k, v: masked_attention_xla(q, k, v, mask)
    # (a side's output and its three gradients are one compiled program)
    weigh = lambda o: jnp.sum(o * wt)
    (o,), got = outputs_and_grads(lambda *a: (kernel(*a),), weigh, q, k, v,
                                  precision=None)
    (o_dense,), want = outputs_and_grads(lambda *a: (dense(*a),), weigh, q, k,
                                         v, precision=None)
    np.testing.assert_allclose(o, o_dense, atol=2e-5)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-4)


@pytest.mark.parametrize("off", [-1, 1])
def test_a_window_off_by_one_row_fails(off):
    t, w, block = 512, 256, 128
    q, k, v, wt = _operands(t, 2, 1)
    kernel = lambda q, k, v: fa_mod.flash_attention(
        q, k, v, block, block, block, block, interpret=True,
        mask=sliding_window(w))
    dense = lambda q, k, v: masked_attention_xla(q, k, v,
                                                 sliding_window(w + off))
    assert np.abs(np.asarray(kernel(q, k, v) - dense(q, k, v))).max() > 1e-3
    got = jax.grad(lambda *a: jnp.sum(kernel(*a) * wt), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * wt), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert np.abs(np.asarray(a - b)).max() > 1e-3


def test_the_dispatch_takes_the_declaration():
    q, k, v, _ = _operands(256, 2, 1)
    mask = sliding_window(128)
    np.testing.assert_allclose(
        masked_attention(q, k, v, mask, impl="flash_interpret"),
        masked_attention(q, k, v, mask, impl="xla"), atol=2e-5)


@pytest.mark.parametrize("t,w,block", [(512, 256, 128), (512, 200, 128),
                                       (2048, 768, 512), (2048, 1000, 1024),
                                       (8192, 2048, 1024)])
def test_the_plan_counts_what_the_window_leaves_live(t, w, block):
    """Brute force over the plan's own rectangles."""
    mask = sliding_window(w)
    n = t // block
    for backward in (False, True):
        work = total = 0
        for qb in range(n):
            for kb in range(n):
                plan = fa_mod.subtile_plan(mask, block, block, qb, kb, t, 64,
                                           backward, n)
                cells = (block // plan.sub_q) * (block // plan.sub_k)
                assert (plan.computed_unmasked + plan.computed_masked
                        + plan.skipped) == cells
                work += plan.work_elems
                total += cells
                if kb > qb or (qb - kb - 1) * block >= w:
                    assert not plan.bands       # a dead tile: never computed
                if t > 2048:
                    continue
                rows = qb * block + np.arange(block)[:, None]
                cols = kb * block + np.arange(block)[None, :]
                live = (cols <= rows) & (rows - cols < w)
                covered = np.zeros_like(live)
                for r0, nr, rects in plan.bands:
                    for c0, nc, masked in rects:
                        covered[r0:r0 + nr, c0:c0 + nc] = True
                        if not masked:
                            assert live[r0:r0 + nr, c0:c0 + nc].all()
                assert not (live & ~covered).any()
        stats = flash_tile_stats(t, block, block, head_dim=64, mask=mask,
                                 backward=backward)
        assert stats["total_tiles"] == total
        assert stats["work_elems"] == work >= stats["ideal_elems"]
        assert stats["ideal_elems"] == w * (2 * t - w + 1) // 2


def test_the_cells_shape_computes_under_a_third_over_the_live_entries():
    """8192 rows under a window of 2048 at head 128, blocks of 1024: a
    window row walks 3 key tiles where a causal row walks up to 8; the
    forward's plan computes 1.250 of the live entries, the backward's
    1.125, and a causal plan over the same rows 1.94 times the forward's."""
    mask = sliding_window(2048)
    fwd = flash_tile_stats(8192, head_dim=128, mask=mask)
    bwd = flash_tile_stats(8192, head_dim=128, mask=mask, backward=True)
    assert (fwd["block_q"], fwd["sub_q"], fwd["sub_k"]) == (1024, 256, 512)
    assert fwd["ideal_elems"] == 2048 * (16384 - 2047) // 2 == 14_681_088
    assert fwd["work_elems"] == 18_350_080 and bwd["work_elems"] == 16_515_072
    assert fwd["work_elems"] / fwd["ideal_elems"] < 1.26
    assert bwd["work_elems"] / bwd["ideal_elems"] < 1.13
    both = (fwd["work_elems"] + bwd["work_elems"]) / (2 * fwd["ideal_elems"])
    assert both < 1.35
    assert flash_tile_stats(8192, head_dim=128)["work_elems"] \
        > 1.8 * fwd["work_elems"]


def test_the_eighth_cells_shape_takes_the_resident_forward_and_backward(
        tmp_path):
    """16,384 rows under a window of 4096 at head 128 and a group of 7,
    blocks of 1024: a head's K and V, double-buffered, are 16 MiB, inside
    `KV_ROW_VMEM_BYTES` since PR 52 (the forward keeps them resident and
    asks Mosaic for the scoped VMEM: they are over `KV_ROW_SCOPED_BYTES`;
    until then the grid walked the key tiles), and what the resident
    backward would keep with two buffers a block is 117 MB, over
    `BWD_ROW_VMEM_BYTES`; kept once it is 71 MB, inside
    `BWD_ROW_ONCE_VMEM_BYTES`, so since PR 56 the backward is the ONE
    kernel too, single-buffered (the split kernels until then). Both walks
    run the SAME tile plans, which is what
    `flash_tile_stats` reports: the forward computes 1.125 of the band's
    58,722,304 live entries, the backward 1.062; the full layer's triangle
    beside it 1.031 and 1.016. The tracer says which walk each took."""
    import json

    from distributed_pytorch_from_scratch_tpu.obs.trace import SpanTracer
    t, d, group, mask = 16384, 128, 7, sliding_window(4096)
    assert fa_mod.KV_ROW_VMEM_BYTES >= fa_mod._fwd_resident_bytes(
        t, d, d, 2) == 2 * t * (d + d) * 2 == 16 * 2 ** 20 \
        > fa_mod.KV_ROW_SCOPED_BYTES
    assert fa_mod._bwd_resident_bytes(t, d, d, 2, group) == 117_440_512 \
        > fa_mod.BWD_ROW_VMEM_BYTES
    assert fa_mod._bwd_resident_bytes(t, d, d, 2, group, buffers=1) \
        == 71_303_168 <= fa_mod.BWD_ROW_ONCE_VMEM_BYTES
    fwd = flash_tile_stats(t, head_dim=d, mask=mask)
    bwd = flash_tile_stats(t, head_dim=d, mask=mask, backward=True)
    assert (fwd["block_q"], fwd["sub_q"], fwd["sub_k"]) == (1024, 256, 512)
    assert (bwd["block_q"], bwd["sub_q"], bwd["sub_k"]) == (1024, 256, 256)
    assert fwd["ideal_elems"] == 4096 * (2 * t - 4095) // 2 == 58_722_304
    assert fwd["work_elems"] == 66_060_288 and bwd["work_elems"] == 62_390_272
    full = flash_tile_stats(t, head_dim=d)
    assert full["ideal_elems"] == t * (t + 1) // 2 == 134_225_920
    assert full["work_elems"] == 138_412_032
    assert fwd["ideal_elems"] / full["ideal_elems"] == pytest.approx(0.4375,
                                                                     abs=1e-4)
    # the walks taken at this shape, as the program's tracer records them
    # (shapes only: nothing runs)
    tracer = SpanTracer(str(tmp_path))
    arg = lambda rows, w, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        (rows, t, w), dtype)
    kw = dict(t_real=t, block_q=1024, block_k=1024, hq=group, hkv=1,
              interpret=True, mask=mask)
    try:
        jax.eval_shape(lambda *a: fa_mod._fwd_call(*a, **kw),
                       arg(group, d), arg(1, d), arg(1, d))
        jax.eval_shape(
            lambda *a: fa_mod._bwd_call(*a, **kw),
            arg(group, d), arg(1, d), arg(1, d), arg(group, d),
            arg(group, 1, jnp.float32), arg(group, d))
    finally:
        tracer.close()
    events = [json.loads(line) for line in open(tmp_path / "trace.jsonl")]
    assert [(e["name"], e["args"]["walk"], e["args"]["window"],
             e["args"]["group"]) for e in events
            if e["name"].startswith("flash_")] == [
        ("flash_fwd_walk", "row", 4096, 7), ("flash_bwd_walk", "row", 4096, 7)]
    # (the backward's instant, not the file's last event: where an earlier
    # test of the same worker process ran `train()`, the compile cache's
    # listener is registered and writes this trace's own compile spans too)
    bwd_walk = [e for e in events if e["name"] == "flash_bwd_walk"][-1]["args"]
    assert (bwd_walk["buffers"], bwd_walk["resident_bytes"],
            bwd_walk["budget_bytes"]) == (1, 71_303_168,
                                          fa_mod.BWD_ROW_ONCE_VMEM_BYTES)


def test_a_window_over_the_whole_sequence_is_the_triangles_text():
    """`W` >= t takes `CAUSAL`'s plan: the same jaxpr, kernel bodies and
    names included."""
    shape = jax.ShapeDtypeStruct((4, 512, 64), jnp.float32)

    def text(mask):
        f = lambda q, k, v: jnp.sum(fa_mod.flash_attention(
            q[None], k[None], v[None], 128, 128, 128, 128, interpret=True,
            mask=mask))
        return str(jax.make_jaxpr(jax.value_and_grad(f, (0, 1, 2)))(
            shape, shape, shape))
    assert text(sliding_window(512)) == text(CAUSAL)
    assert text(sliding_window(4096)) == text(CAUSAL)
    assert text(sliding_window(256)) != text(CAUSAL)
    assert "flash_fwd_window" in text(sliding_window(256))
    assert "window" not in text(CAUSAL)


def test_the_tracer_says_which_mask_the_backward_walked(tmp_path):
    """`_bwd_call`'s instant on the program's tracer carries the mask's kind
    and the window beside the walk it took."""
    import json

    from distributed_pytorch_from_scratch_tpu.obs.trace import SpanTracer
    q, k, v, wt = _operands(512, 2, 1)
    tracer = SpanTracer(str(tmp_path))
    try:
        for mask in (sliding_window(256), CAUSAL):
            jax.grad(lambda q: jnp.sum(fa_mod.flash_attention(
                q, k, v, 128, 128, 128, 128, interpret=True,
                mask=mask) * wt))(q)
    finally:
        tracer.close()
    events = [json.loads(line)["args"] for line in
              open(tmp_path / "trace.jsonl")
              if json.loads(line)["name"] == "flash_bwd_walk"]
    assert [(e["walk"], e["mask"], e["window"]) for e in events] == [
        ("row", "sliding_window", 256), ("row", "causal", 0)]


def test_what_the_kernels_cannot_plan_is_refused():
    q = jnp.zeros((1, 1, 384, 64))
    with pytest.raises(ValueError, match="use the XLA attention"):
        fa_mod.flash_attention(q, q, q, 256, 256, 256, 256, interpret=True,
                               mask=sliding_window(128))
    with pytest.raises(ValueError, match="no t_real"):
        fa_mod.flash_attention(q, q, q, 128, 128, 128, 128, interpret=True,
                               t_real=200, mask=sliding_window(128))
