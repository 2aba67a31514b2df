"""The benchmark's two train steps, compiled for a described v5e at the rung
`remat="auto"` picks there: no chip, the chip's compiler (on-chip-measurement
guide, section 2). One file, topology in a fixture: only the worker that
runs this file loads the TPU library.

What is asserted is what the compiler would refuse on the chip (arguments +
planned temporaries over `bytes_limit`), and that the estimate the rung was
picked by leaves the snapshot's reserve free where one is held (the GPT-2
cells) and the selector's margin of the chip where none can be (the expert
cells, PR 62). The plan is an upper bound of what the runtime reserves: it
charges some of the stacks that live from the forward loop to the backward
loop twice (PERF.md section 5). The last test holds the estimate to the
chip's own counts, cell by cell, with nothing compiled.
"""

import contextlib
import io
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_pytorch_from_scratch_tpu.config import (
    MeshConfig, ModelConfig, OptimizerConfig)
from distributed_pytorch_from_scratch_tpu.models.gpt2 import GPT2Transformer
from distributed_pytorch_from_scratch_tpu.runtime.mesh import make_mesh
from distributed_pytorch_from_scratch_tpu.training import memory
from distributed_pytorch_from_scratch_tpu.training.optim import AdamState
from distributed_pytorch_from_scratch_tpu.training.train_step import (
    build_train_step)

V5E_LIMIT_GIB = 15.748          # memory_stats()["bytes_limit"] of a v5e
CELLS = {
    # cell: (widths, mesh, global batch, the rung auto picks, its chip GiB)
    "gpt2-medium.train-b12-t1024": (
        dict(attn_dim=1024, ffn_dim=4096, num_heads=16, num_layers=24),
        dict(dp=1, tp=1), 12, "ffn"),
    "gpt2-large.train-dp2-tp2": (
        dict(attn_dim=1280, ffn_dim=5120, num_heads=20, num_layers=36),
        # tp 2: the model picks sequence parallelism over the ring matmuls
        # (PR 28), whose halved layer-boundary stacks leave room for 'dots'
        dict(dp=2, tp=2), 16, "dots"),
}


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever stops the description
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def described_tpu(monkeypatch):
    """The flash guards ask `jax.default_backend()`; the target here is the
    described chip. Steered in the test, not by an option of the program.
    The persistent compile cache cannot read back what no chip compiled."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    memory.select_remat_traced.cache_clear()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


_COMPILED = {}


def compiled_step(cell, topo):
    """The cell's step compiled for the described chip, once a cell for the
    whole file: (model, compiled, its text, what the trace printed)."""
    if cell in _COMPILED:
        return _COMPILED[cell]
    widths, mesh_sizes, batch, _ = CELLS[cell]
    chips = mesh_sizes["dp"] * mesh_sizes["tp"]
    mesh = make_mesh(MeshConfig(**mesh_sizes), devices=topo.devices[:chips])
    cfg = ModelConfig(vocab_size=50257, maxlen=1024,
                      compute_dtype="bfloat16", **widths)
    model = GPT2Transformer(cfg, tp_size=mesh_sizes["tp"],
                            remat_budget_gib=V5E_LIMIT_GIB)
    assert model.remat == "auto"
    params = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        jax.eval_shape(model.init, jax.random.key(0)), model.shardings(mesh))
    scalar = NamedSharding(mesh, P())
    opt = AdamState(step=jax.ShapeDtypeStruct((), jnp.int32, sharding=scalar),
                    mu=params, nu=params)
    ids = jax.ShapeDtypeStruct((batch, 1024), jnp.int32,
                               sharding=NamedSharding(mesh, P(("dp", "ep"),
                                                              "cp")))
    step = build_train_step(model, mesh, OptimizerConfig(),
                            with_grad_norm=True)
    said = io.StringIO()
    with contextlib.redirect_stderr(said):
        compiled = step.lower(params, opt, ids, ids, ids).compile()
    _COMPILED[cell] = (model, compiled, compiled.as_text(), said.getvalue())
    return _COMPILED[cell]


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cells_step_fits_a_v5e_at_the_rung_auto_picks(cell, topo,
                                                      described_tpu):
    widths, mesh_sizes, batch, want_rung = CELLS[cell]
    chips = mesh_sizes["dp"] * mesh_sizes["tp"]
    model, compiled, text, said = compiled_step(cell, topo)
    cfg = model.cfg

    assert f"remat auto: picked '{want_rung}'" in said
    assert "tpu_custom_call" in text                     # the flash kernel
    # the ring collective matmuls' hops, asynchronous, at tp 2 and only there
    sp, overlap = model.tp_layout(1024)
    assert (sp, overlap) == ((True, "ring") if mesh_sizes["tp"] > 1
                             else (False, "off"))
    assert ("collective-permute-start" in text) == sp
    plan = compiled.memory_analysis()
    args = plan.argument_size_in_bytes / memory.GIB
    planned = args + plan.temp_size_in_bytes / memory.GIB
    assert planned < V5E_LIMIT_GIB, planned
    estimate = memory.estimate_step_gib(
        cfg, batch, 1024, want_rung, tp=mesh_sizes["tp"], world=chips,
        dp=mesh_sizes["dp"], family="gpt2", sequence_parallel=sp)
    # the estimate is of what the chip counts, which the plan bounds ...
    assert estimate < planned * 1.01, (estimate, planned)
    # ... and with one more copy of the state (the snapshot) it still fits
    assert estimate + args < V5E_LIMIT_GIB, (estimate, args)


COLLECTIVE = re.compile(
    r" (all-reduce|all-gather|all-to-all|reduce-scatter|collective-permute)"
    r"(-start)?\(")
DP_PAIRS = "replica_groups={{0,2},{1,3}}"


def computations(text):
    """name -> instruction lines, metadata cut off, of each computation."""
    out, name = {}, None
    for line in text.splitlines():
        if line.endswith("{") and "->" in line and not line.startswith(" "):
            name = line.split(" (", 1)[0].replace("ENTRY ", "")
            out[name] = []
        elif name and " = " in line:
            out[name].append(line.split(", metadata=")[0])
    return out


def test_cell_1_step_has_no_collective(topo, described_tpu):
    """dp 1 x tp 1: the dp exchange does not engage (nor anything else that
    talks to another chip)."""
    _, _, text, _ = compiled_step("gpt2-medium.train-b12-t1024", topo)
    for lines in computations(text).values():
        assert not [l for l in lines if COLLECTIVE.search(l)]


def test_cell_2_backward_body_exchanges_the_dp_gradients_under_dots(
        topo, described_tpu):
    """The layers' sum over 'dp' is no all-reduce at the end of the backward
    body any more (PR 32): each leaf's gather over the dp pairs is issued as
    a start / done pair, and dots run between the two."""
    _, _, text, said = compiled_step("gpt2-large.train-dp2-tp2", topo)
    assert "remat auto: picked 'dots'" in said
    comps = computations(text)
    # the backward body: the while body that holds the dp gathers
    bodies = [lines for lines in comps.values()
              if any(l.lstrip().startswith("%async-collective-start")
                     for l in lines)
              and any("collective-permute-start" in l for l in lines)]
    assert len(bodies) == 1
    body = bodies[0]
    assert not [l for l in body if " all-reduce(" in l and DP_PAIRS in l]
    # a gather's start names the fused computation that holds the
    # all-gather; its done is the next `async-collective-done`
    gathers = {name: lines for name, lines in comps.items()
               if any(" all-gather(" in l and DP_PAIRS in l for l in lines)}
    hidden = 0
    for i, line in enumerate(body):
        if not line.lstrip().startswith("%async-collective-start"):
            continue
        assert re.search(r"calls=(%[\w.\-]+)", line).group(1) in gathers
        done = next(j for j in range(i + 1, len(body))
                    if body[j].lstrip().startswith("%async-collective-done"))
        dots = [l for l in body[i + 1:done]
                if " convolution(" in l or "kind=kOutput" in l]
        hidden += bool(dots)
    # proj, fc, the attention projection and two of q/k/v at the least
    assert hidden >= 5, hidden


@pytest.mark.parametrize("t,d,dv,t_real,asks", [
    (4096, 192, 128, 4096, False),  # the latent-attention cell's forward
    (4096, 192, 128, 4000, False),  # a second plan for the block t_real cuts
    (8192, 64, 64, 8192, False),    # as long a row as the default limit takes
    (16384, 128, 128, 16384, True),     # the sixteen-thousand-row cell's
    (8192, 256, 256, 8192, True),       # the hybrid cell's attention layer
    (32768, 128, 128, 32768, True)])    # as long a row as the budget takes
def test_flash_forward_with_a_resident_key_row_fits_mosaics_vmem(
        topo, described_tpu, t, d, dv, t_real, asks):
    """Mosaic takes the multi-block forward that keeps a head's K and V in
    VMEM (PR 34) at `flash_blocks`' blocks: one kernel an attention, grid
    (b*h, query blocks, 1). Rows of up to 8 MiB compile inside its default
    scoped VMEM and their call names no limit; the two cells whose row is 16
    MiB (PR 52) carry the limit `_fwd_call` asks for, 40 MiB, and a row of
    32 MiB, the budget, 56."""
    from jax.sharding import SingleDeviceSharding
    from distributed_pytorch_from_scratch_tpu.ops.pallas import (
        flash_attention as fa)
    _, block_q, block_k, *_ = fa.flash_blocks(t, d, t_real=t_real)
    chip = SingleDeviceSharding(topo.devices[0])
    arg = lambda w: jax.ShapeDtypeStruct((8, t, w), jnp.bfloat16,
                                         sharding=chip)
    resident = fa._fwd_resident_bytes(t, d, dv, 2)
    assert resident <= fa.KV_ROW_VMEM_BYTES
    assert asks == (resident > fa.KV_ROW_SCOPED_BYTES)
    text = jax.jit(lambda q, k, v: fa._fwd_call(
        q, k, v, t_real=t_real, block_q=block_q, block_k=block_k, hq=1,
        hkv=1, interpret=False)).lower(
            arg(d), arg(d), arg(dv)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    call = next(line for line in text.splitlines()
                if 'custom_call_target="tpu_custom_call"' in line)
    scoped = re.search(r'"scoped_memory_configs":\[([^\]]*)\]', call).group(1)
    if asks:
        assert f'"size":"{fa._vmem_limit(resident)}"' in scoped
        assert fa._vmem_limit(resident) == resident + 24 * 2 ** 20
    else:
        assert scoped == ""


def _mosaic_body(lowered_text):
    """The one Mosaic kernel of a lowered text, its serialized body decoded."""
    import base64

    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir
    raw, = re.findall(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22',
                      lowered_text)
    with mlir.make_ir_context() as ctx:
        ctx.allow_unregistered_dialects = True
        return ir.Module.parse(base64.b64decode(raw)).operation.get_asm(
            enable_debug_info=False)


@pytest.mark.parametrize("t,d,dv,group,t_real,window,buffers,names", [
    # the latent-attention cell's head, 34 MiB resident
    (4096, 192, 128, 1, 4096, 0, 2, ["flash_bwd"]),
    # t_real cuts the third block: the cut tiles' plans beside the whole ones
    (4096, 192, 128, 1, 3000, 0, 2, ["flash_bwd"]),
    # the conv cell's group of four heads of 64 at 8k, 56 MiB: the most the
    # first budget admits among the benchmark's cells
    (8192, 64, 64, 4, 8192, 0, 2, ["flash_bwd"]),
    # the hybrid cell's group of eight heads of 256 at 8k, 96 MiB with two
    # buffers a block: kept once it is 60 (PR 56)
    (8192, 256, 256, 8, 8192, 0, 1, ["flash_bwd"]),
    # the sixteen-thousand-row cell's group of seven heads of 128: 112 MiB
    # twice, 68 once, its full layer and its window layers
    (16384, 128, 128, 7, 16384, 0, 1, ["flash_bwd"]),
    (16384, 128, 128, 7, 16384, 4096, 1, ["flash_bwd_window"]),
    # twice that row is over both budgets (224 MiB, 136 once)
    (32768, 128, 128, 7, 32768, 0, 0, ["flash_bwd_dq", "flash_bwd_dkv"])])
def test_flash_backward_with_a_resident_head_fits_the_vmem_it_asks_for(
        topo, described_tpu, t, d, dv, group, t_real, window, buffers,
        names):
    """Mosaic takes the multi-block backward that keeps the whole head in
    VMEM (PR 40) at `flash_blocks`' blocks with the scoped VMEM
    `_bwd_row_call` asks for: double-buffered at the shapes
    `BWD_ROW_VMEM_BYTES` admits,
    and with its nine whole-row blocks kept once (PR 56) at the shapes only
    `BWD_ROW_ONCE_VMEM_BYTES` does, whose call says so block by block and
    asks for the bytes as taken and the body's room; a head over both
    budgets compiles the two split kernels as before."""
    from jax.sharding import SingleDeviceSharding
    from distributed_pytorch_from_scratch_tpu.ops.attention import (
        CAUSAL, sliding_window)
    from distributed_pytorch_from_scratch_tpu.ops.pallas import (
        flash_attention as fa)
    mask = sliding_window(window) if window else CAUSAL
    *_, block_q, block_k, mask = fa.flash_blocks(t, d, mask)
    chip = SingleDeviceSharding(topo.devices[0])
    arg = lambda rows, w, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        (rows, t, w), dtype, sharding=chip)
    twice, once = (fa._bwd_resident_bytes(t, d, dv, 2, group, buffers=n)
                   for n in (2, 1))
    # `buffers` 0: over both budgets, the two split kernels
    assert buffers == (2 if twice <= fa.BWD_ROW_VMEM_BYTES else
                       1 if once <= fa.BWD_ROW_ONCE_VMEM_BYTES else 0)
    lowered = jax.jit(lambda *a: fa._bwd_call(
        *a, t_real=t_real, block_q=block_q, block_k=block_k, hq=group,
        hkv=1, interpret=False, mask=mask)).lower(
            arg(2 * group, d), arg(2, d), arg(2, dv), arg(2 * group, dv),
            arg(2 * group, 1, jnp.float32), arg(2 * group, dv))
    text = lowered.compile().as_text()
    # the compiled text lists the two split calls in the scheduler's order
    assert sorted(name.split(".")[0] for name in re.findall(
        r"%([\w.\-]+) = [^\n]*? custom-call\([^)]*\), "
        r'custom_call_target="tpu_custom_call"', text)) == sorted(names)
    if not buffers:
        return
    call = next(line for line in text.splitlines()
                if 'custom_call_target="tpu_custom_call"' in line)
    scoped = re.search(r'"scoped_memory_configs":\[([^\]]*)\]', call).group(1)
    taken = twice if buffers == 2 else once
    assert f'"size":"{fa._vmem_limit(taken)}"' in scoped
    assert fa._vmem_limit(taken) == taken + 24 * 2 ** 20 <= 92 * 2 ** 20
    # the kernel as Mosaic is handed it: nine window parameters, each with
    # the single buffer or none with it
    body = _mosaic_body(lowered.as_text())
    assert body.count("pipeline_mode<synchronous>") == (9 if buffers == 1
                                                        else 0)


SPLIT = ["flash_bwd_dkv_window", "flash_bwd_dq_window", "flash_fwd_window"]


@pytest.mark.parametrize("t,window,group,walk,names", [
    (8192, 2048, 8, "row", ["flash_bwd_window", "flash_fwd_window"]),
    (8192, 2048, 8, "grid", SPLIT),
    (16384, 4096, 7, "own", ["flash_bwd_window", "flash_fwd_window"])])
def test_the_window_kernels_compile_at_the_window_cells_shapes(
        topo, described_tpu, monkeypatch, t, window, group, walk, names):
    """Mosaic takes the flash kernels under `sliding_window(2048)` at a
    window layer's shape in the seventh cell (8192 rows, 128 / 128, bf16, a
    group of 8 query heads a key-value head, blocks of 1024): the forward
    with K and V resident and the ONE backward kernel, and the gridded
    forward with the split backward a longer row would take; and under
    `sliding_window(4096)` at the eighth cell's (16,384 rows, a group of
    7), which takes the resident forward (PR 52) and the ONE backward
    kernel, its blocks kept once (PR 56), by its OWN size, no budget
    patched. The calls carry `_window` in their names,
    which is how a device trace tells a window layer's from a full
    layer's."""
    from jax.sharding import SingleDeviceSharding
    from distributed_pytorch_from_scratch_tpu.ops.attention import (
        sliding_window)
    from distributed_pytorch_from_scratch_tpu.ops.pallas import (
        flash_attention as fa)
    if walk == "grid":
        monkeypatch.setattr(fa, "KV_ROW_VMEM_BYTES", 0)
        monkeypatch.setattr(fa, "BWD_ROW_VMEM_BYTES", 0)
        monkeypatch.setattr(fa, "BWD_ROW_ONCE_VMEM_BYTES", 0)
    d, mask = 128, sliding_window(window)
    chip = SingleDeviceSharding(topo.devices[0])
    arg = lambda rows, w, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        (rows, t, w), dtype, sharding=chip)
    kw = dict(t_real=t, block_q=1024, block_k=1024, hq=group, hkv=1,
              interpret=False, mask=mask)
    text = jax.jit(lambda q, k, v: fa._fwd_call(q, k, v, **kw)).lower(
        arg(2 * group, d), arg(2, d), arg(2, d)).compile().as_text()
    text += jax.jit(lambda *a: fa._bwd_call(*a, **kw)).lower(
        arg(2 * group, d), arg(2, d), arg(2, d), arg(2 * group, d),
        arg(2 * group, 1, jnp.float32),
        arg(2 * group, d)).compile().as_text()
    assert sorted(name.split(".")[0] for name in re.findall(
        r"%([\w.\-]+) = [^\n]*? custom-call\([^)]*\), "
        r'custom_call_target="tpu_custom_call"', text)) == names


def test_the_mixers_kernels_compile_at_the_mhc_cells_shape(topo,
                                                           described_tpu):
    """Mosaic takes the hyper-connection mixers' four kernels (PR 58) at
    cell 11's shape (4 streams, 4096 tokens, 3584 wide, bf16; a layer's
    mixer of 24 maps and the exit's of 4) at the module's blocks, within
    the VMEM each call asks for; the compiled calls carry the names and
    operand counts that keep them out of the benchmark's flash patterns
    (`^flash_`, 3 or 6 operands)."""
    from jax.sharding import SingleDeviceSharding
    from distributed_pytorch_from_scratch_tpu.ops.pallas import (
        stream_mixer as mixer)
    n, t, d = 4, 4096, 3584
    assert mixer.holds(n, d, jnp.dtype("bfloat16"))
    chip = SingleDeviceSharding(topo.devices[0])
    arg = lambda dtype, *shape: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=chip)
    bf16, f32 = jnp.bfloat16, jnp.float32
    X, row = arg(bf16, n, t, d), arg(bf16, t, d)
    maps = (arg(f32, t, 128), arg(f32, t, 128))
    calls = []
    for width, passes in ((24, True), (4, False)):
        params = (arg(f32, n * d, width), arg(f32), arg(f32, n))
        kw = dict(width=width, eps=1e-6)
        read = lambda X, *p: mixer.read_forward(X, *p, norm_eps=1e-6, **kw)
        back = lambda X, w, a, b, tok, dm, du, *through: \
            mixer.read_backward(X, w, a, b, tok, dm, du, through or None,
                                **kw)
        for fn, args in (
                (read, (X, *params)),
                (back, (X, *params, arg(f32, t, 128), arg(f32, width, t),
                        row) + ((X, maps[0]) if passes else ()))):
            text = jax.jit(fn).lower(*args).compile().as_text()
            calls += re.findall(
                r"%([\w.\-]+) = [^\n]*? custom-call\(([^)]*)\), "
                r'custom_call_target="tpu_custom_call"', text)
    for fn, args in (
            (mixer.write_forward, (X, row, *maps)),
            (lambda *a: mixer.write_backward(*a, part=False)[1:],
             (X, row, *maps, X))):
        text = jax.jit(fn).lower(*args).compile().as_text()
        calls += re.findall(
            r"%([\w.\-]+) = [^\n]*? custom-call\(([^)]*)\), "
            r'custom_call_target="tpu_custom_call"', text)
    assert [(name.split(".")[0], operands.count("%"))
            for name, operands in calls] == [
        ("mhc_read_fwd", 4), ("mhc_read_bwd", 9), ("mhc_read_fwd", 4),
        ("mhc_read_bwd", 7), ("mhc_write_fwd", 4), ("mhc_write_bwd", 5)]


def test_the_delta_rules_kernels_compile_at_the_hybrid_cells_shape(
        topo, described_tpu):
    """Mosaic takes the rule's two kernels (PR 36; since PR 38 they make a
    chunk's operands in VMEM too) at one sequence of cell 6 (32 heads, 8192
    tokens in chunks of 64, 128 / 128, bf16) at the module's blocks; the
    compiled calls carry the names and operand counts that keep them out of
    the benchmark's flash patterns (`^flash_`, 3 or 6 operands)."""
    from jax.sharding import SingleDeviceSharding
    from distributed_pytorch_from_scratch_tpu.ops.pallas import (
        delta_rule as rule)
    h, n, C, dk, dv = 32, 128, 64, 128, 128
    chip = SingleDeviceSharding(topo.devices[0])
    arg = lambda dtype, *shape: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=chip)
    bf16, f32 = jnp.bfloat16, jnp.float32
    inputs = (arg(bf16, h, n, C, dk), arg(bf16, h, n, C, dk),
              arg(bf16, h, n, C, dv), arg(f32, h, n, rule.ROWS, C),
              arg(f32, h, n, C, C))
    # one state a grid step's two chunks
    saved = (arg(f32, h, n // 2, dk, dv), arg(bf16, h, n, C, dv),
             arg(f32, h, dk, dv))
    calls = []
    for fn, args in (
            (lambda *a: rule.rule_forward(*a, residuals=True), inputs),
            (rule.rule_backward, inputs + saved)):
        text = jax.jit(fn).lower(*args).compile().as_text()
        calls += re.findall(
            r"%([\w.\-]+) = [^\n]*? custom-call\(([^)]*)\), "
            r'custom_call_target="tpu_custom_call"', text)
    assert [(name.split(".")[0], operands.count("%"))
            for name, operands in calls] == [("gdn_rule_fwd", 7),
                                             ("gdn_rule_bwd", 10)]


def test_the_channel_rules_kernels_compile_at_the_kda_cells_shape(
        topo, described_tpu):
    """Mosaic takes the three kernels of the rule with a decay a channel
    (PR 60) at cell 12's one sequence (32 heads, 4096 tokens in chunks of
    64, 128 / 128, bf16) at the module's blocks, under the names and
    operand counts that keep them out of the benchmark's flash patterns."""
    from jax.sharding import SingleDeviceSharding
    from distributed_pytorch_from_scratch_tpu.ops.pallas import kda_rule
    from distributed_pytorch_from_scratch_tpu.ops.pallas.delta_rule import (
        ROWS)
    h, n, C, dk, dv = 32, 64, 64, 128, 128
    chip = SingleDeviceSharding(topo.devices[0])
    arg = lambda dtype, *shape: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=chip)
    bf16, f32 = jnp.bfloat16, jnp.float32
    inputs = (arg(bf16, h, n, C, dk), arg(bf16, h, n, C, dk),
              arg(bf16, h, n, C, dv), arg(f32, h, n, C + ROWS, dk),
              arg(f32, h, n, C, C))
    saved = (arg(f32, h, n // kda_rule.blocks(h, n)[1], dv, dk),
             arg(bf16, h, n, C, dv), arg(f32, h, dv, dk))
    calls = []
    for fn, args in (
            (lambda k, gb: kda_rule.rule_pairs(k, gb, sub=16),
             (inputs[1], inputs[3])),
            (lambda *a: kda_rule.rule_forward(*a, sub=16, residuals=True),
             inputs),
            (lambda *a: kda_rule.rule_backward(*a, sub=16),
             inputs + saved)):
        text = jax.jit(fn).lower(*args).compile().as_text()
        calls += re.findall(
            r"%([\w.\-]+) = [^\n]*? custom-call\(([^)]*)\), "
            r'custom_call_target="tpu_custom_call"', text)
    assert [(name.split(".")[0], operands.count("%"))
            for name, operands in calls] == [
        ("kda_rule_pairs", 2), ("kda_rule_fwd", 5), ("kda_rule_bwd", 8)]


@pytest.mark.parametrize("heads,groups,chunk", [(64, 1, 256), (32, 2, 128)])
def test_the_recurrences_kernels_compile_at_the_ssm_cells_shapes(
        topo, described_tpu, heads, groups, chunk):
    """Mosaic takes the state-space recurrence's two kernels (PR 69) at cell
    15's and cell 13's one sequence (64 heads in one group at chunks of
    256; 32 heads in two groups at chunks of 128; 4096 tokens, heads of 64,
    a state of 128, bf16), reached through `ops/ssd.ssd` as the mixer calls
    it, under the names and operand counts that keep them out of the
    benchmark's flash patterns."""
    from jax.sharding import SingleDeviceSharding
    from distributed_pytorch_from_scratch_tpu.ops.ssd import ssd
    chip = SingleDeviceSharding(topo.devices[0])
    arg = lambda dtype, *shape: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=chip)
    bf16, f32 = jnp.bfloat16, jnp.float32
    args = (arg(bf16, 1, 4096, heads, 64), arg(f32, 1, 4096, heads),
            arg(f32, heads), arg(bf16, 1, 4096, groups, 128),
            arg(bf16, 1, 4096, groups, 128))
    loss = lambda *a: jnp.sum(ssd(*a, chunk)[0].astype(f32))
    text = jax.jit(jax.grad(loss, range(5))).lower(*args).compile().as_text()
    calls = re.findall(
        r"%([\w.\-]+) = [^\n]*? custom-call\(([^)]*)\), "
        r'custom_call_target="tpu_custom_call"', text)
    # (taken alone, the gradient's calls are named by their transform too:
    # `jvp_ssd_fwd_`; in a step they sit under its jitted scopes)
    assert sorted((re.search(r"ssd_(fwd|bwd)", name).group(),
                   operands.count("%")) for name, operands in calls) == [
        ("ssd_bwd", 7), ("ssd_fwd", 5)]


def test_the_selective_scans_kernels_compile_at_the_sambay_cells_shape(
        topo, described_tpu):
    """Mosaic takes the Mamba-1 scan's two kernels (PR 76) at cell 18's one
    sequence (16,384 tokens of 5120 channels over a state of 16, u in
    bf16), reached through `ops/selective_scan.selective_scan` as the mixer
    calls it, under the names and operand counts that keep them out of the
    benchmark's flash patterns; the states of every step, (16384, 5120, 16)
    float32, are nowhere in the text, and the forward keeps the state a
    chunk of 128 tokens entered with."""
    from jax.sharding import SingleDeviceSharding
    from distributed_pytorch_from_scratch_tpu.ops.selective_scan import (
        selective_scan)
    chip = SingleDeviceSharding(topo.devices[0])
    arg = lambda dtype, *shape: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=chip)
    bf16, f32 = jnp.bfloat16, jnp.float32
    args = (arg(bf16, 1, 16384, 5120), arg(f32, 1, 16384, 5120),
            arg(f32, 5120, 16), arg(f32, 1, 16384, 16),
            arg(f32, 1, 16384, 16))
    loss = lambda *a: jnp.sum(selective_scan(*a)[0])
    text = jax.jit(jax.grad(loss, range(5))).lower(*args).compile().as_text()
    calls = re.findall(
        r"%([\w.\-]+) = ([^\n]*?) custom-call\(([^)]*)\), "
        r'custom_call_target="tpu_custom_call"', text)
    assert sorted((re.search(r"sscan_(fwd|bwd)", name).group(),
                   operands.count("%")) for name, _, operands in calls) == [
        ("sscan_bwd", 7), ("sscan_fwd", 5)]
    assert any("f32[1,128,16,5120]" in out for _, out, _ in calls)
    assert "16384,5120,16]" not in text and "16384,16,5120]" not in text


def test_the_conv_moe_cells_step_fits_a_v5e_at_the_rung_auto_picks(
        topo, described_tpu):
    """The fifth cell's step (`lfm2-8b-a1b.train-ep4share-b2-t8192`: the
    conv_moe family at the published widths, 8 of 32 experts held, 2 x 8192
    tokens, bf16) compiled for the described chip at the rung `remat="auto"`
    picks there, `dots` since PR 62 (no reserve is held beside 5.68 GiB of
    state): the family's memory facts (`ffn_inputs`, `tagged_layers`: one
    dense and one attention layer of five, `layer_extra_elems_per_token`: at
    a held share of 1/4 a chunk of the dispatch is 16,384 of the 65,536
    pairs since PR 71) are
    held to the compiler's plan, and Mosaic takes the flash kernels at head
    64 under a group of 4 over several blocks a head (the forward and,
    since PR 40, ONE backward kernel with the head resident where there
    were dq and dk/dv). The chip itself counts 10.34 GiB for this step
    (PERF.md section 5, PR 71; 9.63 at the floor; 11.61 and 10.90 with the
    one chunk of all the pairs, PR 62)."""
    from distributed_pytorch_from_scratch_tpu.config import ConvMoEConfig
    from distributed_pytorch_from_scratch_tpu.models import build_model
    cfg = ModelConfig(
        attn_dim=2048, ffn_dim=7168, num_heads=32, num_kv_heads=8,
        num_layers=5, vocab_size=16384, maxlen=128000, rope_theta=1e6,
        compute_dtype="bfloat16", num_experts=32, moe_top_k=4,
        conv_moe=ConvMoEConfig(
            layer_types=("conv", "full_attention", "conv", "conv", "conv"),
            moe_intermediate_size=1792, num_dense_layers=1, experts_held=8))
    mesh = make_mesh(MeshConfig(dp=1, tp=1), devices=topo.devices[:1])
    model = build_model("conv_moe", cfg, remat_budget_gib=V5E_LIMIT_GIB)
    params = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        jax.eval_shape(model.init, jax.random.key(0)), model.shardings(mesh))
    scalar = NamedSharding(mesh, P())
    opt = AdamState(step=jax.ShapeDtypeStruct((), jnp.int32, sharding=scalar),
                    mu=params, nu=params)
    ids = jax.ShapeDtypeStruct((2, 8192), jnp.int32, sharding=NamedSharding(
        mesh, P(("dp", "ep"), "cp")))
    step = build_train_step(model, mesh, OptimizerConfig(),
                            with_grad_norm=True, with_counters=True)
    said = io.StringIO()
    with contextlib.redirect_stderr(said):
        compiled = step.lower(params, opt, ids, ids, ids).compile()
    # 5.68 GiB of state: the floor cannot take a snapshot beside it, so no
    # reserve is held and the chip's free 4.7 GiB buy the top rung
    assert "remat auto: picked 'dots'" in said.getvalue()
    assert "reserve 0.00 GiB" in said.getvalue()
    assert "reserve_held=False" in said.getvalue()
    estimate = float(re.search(r"dots=([\d.]+)GiB", said.getvalue()).group(1))
    plan = compiled.memory_analysis()
    args = plan.argument_size_in_bytes / memory.GIB
    planned = args + plan.temp_size_in_bytes / memory.GIB
    assert args == pytest.approx(507_820_288 * 12 / memory.GIB, rel=1e-3)
    assert planned < V5E_LIMIT_GIB, planned
    # the estimate is of what the chip counts, which the plan bounds, and
    # it leaves the selector's margin of the chip
    assert estimate < planned * 1.01, (estimate, planned)
    assert estimate <= memory.MARGIN * V5E_LIMIT_GIB, estimate
    kernels = set(re.findall(r"%((?:flash|ragged)[\w\-]*?)[.\d]* = ",
                             compiled.as_text()))
    assert {"flash_fwd", "flash_bwd"} <= kernels, kernels
    assert not {"flash_bwd_dq", "flash_bwd_dkv"} & kernels, kernels


@pytest.mark.parametrize("remat", ["auto", "true"])
def test_the_loop_cells_step_fits_a_v5e_at_the_rung_auto_picks(
        topo, described_tpu, remat):
    """Cell 14's step (`ouro-2.6b.train-loop4-b1-t4096`: the loop_llama
    family at the published widths, 8 layers passed 4 times, 1 x 4096
    tokens, bf16) compiled for the described chip: ONE walk of 32 layer
    applications each way (one traced copy of the layer body: one flash
    forward and ONE backward kernel in the text) and the exits as one scan
    under a checkpoint (no float32 (4, 1, 4096, 49152) tensor: one exit's
    logits at a time).

    At the floor pinned the plan's temporaries are 5.03 GiB: the backward
    walk carries the layers' float32 gradient as ONE stack and adds a layer
    application's slice in place, where the transpose of a scan of passes
    around the scan of layers held the stack twice (8.61 GiB on PR 66's
    program, whose step this case refuses; PERF.md section 6, PR 67). So
    `remat="auto"` climbs by itself beside 6.84 GiB of state, to `flash`:
    the layer's recompute runs no flash forward. What the compiler refuses
    a program by is the peak of what is live, `peak_memory_in_bytes` (it
    refused PR 66's program at `flash`, 16.33 GiB, and takes this one at
    `dots`, 15.25 of 15.75, at arguments + temporaries of 17.49), which on
    PR 66's program read 12.394 where the chip counted 12.438: the rung
    picked has to fit by it, and the estimate it was picked by may not be
    under it."""
    from distributed_pytorch_from_scratch_tpu.config import LoopLlamaConfig
    from distributed_pytorch_from_scratch_tpu.models import build_model
    cfg = ModelConfig(
        attn_dim=2048, ffn_dim=5632, num_heads=16, num_kv_heads=16,
        num_layers=8, vocab_size=49152, maxlen=65536, rope_theta=1e6,
        compute_dtype="bfloat16", loop_llama=LoopLlamaConfig(loop_steps=4))
    mesh = make_mesh(MeshConfig(dp=1, tp=1), devices=topo.devices[:1])
    model = build_model("loop_llama", cfg, **(
        dict(remat_budget_gib=V5E_LIMIT_GIB) if remat == "auto"
        else dict(remat=remat)))
    params = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        jax.eval_shape(model.init, jax.random.key(0)), model.shardings(mesh))
    scalar = NamedSharding(mesh, P())
    opt = AdamState(step=jax.ShapeDtypeStruct((), jnp.int32, sharding=scalar),
                    mu=params, nu=params)
    ids = jax.ShapeDtypeStruct((1, 4096), jnp.int32, sharding=NamedSharding(
        mesh, P(("dp", "ep"), "cp")))
    step = build_train_step(model, mesh, OptimizerConfig(),
                            with_grad_norm=True, with_counters=True)
    said = io.StringIO()
    with contextlib.redirect_stderr(said):
        compiled = step.lower(params, opt, ids, ids, ids).compile()
    plan = compiled.memory_analysis()
    args = plan.argument_size_in_bytes / memory.GIB
    assert args == pytest.approx(612_438_017 * 12 / memory.GIB, rel=1e-3)
    peak = plan.peak_memory_in_bytes / memory.GIB
    assert peak < V5E_LIMIT_GIB, peak
    text = compiled.as_text()
    assert len(re.findall(r"%flash_bwd[.\d]* = ", text)) == 1
    assert "f32[4,1,4096,49152]" not in text
    assert "f32[1,4096,49152]" in text
    forwards = len(re.findall(r"%flash_fwd[.\d]* = ", text))
    if remat == "true":
        assert plan.temp_size_in_bytes / memory.GIB <= 7.3
        assert forwards == 2                                # + recompute
        return
    assert "remat auto: picked 'flash'" in said.getvalue()
    assert "reserve_held=False" in said.getvalue()
    estimate = float(re.search(r"flash=([\d.]+)GiB",
                               said.getvalue()).group(1))
    assert peak * 0.99 < estimate <= memory.MARGIN * V5E_LIMIT_GIB, (
        estimate, peak)
    assert forwards == 1


def test_the_ssm_dense_cells_step_fits_a_v5e_at_the_rung_auto_picks(
        topo, described_tpu):
    """Cell 15's step (`granite-4.0-h-micro.train-pp4stage-b1-t4096`: the
    ssm_dense family at the published widths, nine Mamba-2 layers at all 64
    heads and chunk 256 and one attention layer, a SwiGLU in each, 1 x 4096
    tokens, bf16) compiled for the described chip: beside 8.63 GiB of
    weights and moments `remat="auto"` picks `dots` (every layer's
    `ffn_gate` / `ffn_up`, the attention layer's q, k, v and flash outputs),
    so ONE flash forward in the text; no (1, 4096, 100352) logits: the
    slice's. The chip itself counted 13.445 GiB with the recurrence as
    XLA text (PERF.md section 5, PR 68)."""
    from distributed_pytorch_from_scratch_tpu.config import SsmDenseConfig
    from distributed_pytorch_from_scratch_tpu.models import build_model
    cfg = ModelConfig(
        attn_dim=2048, ffn_dim=8192, num_heads=32, num_kv_heads=8,
        num_layers=10, vocab_size=12544, maxlen=131072,
        compute_dtype="bfloat16", ssm_dense=SsmDenseConfig(
            layer_types=("mamba",) * 5 + ("attention",) + ("mamba",) * 4,
            mamba_n_heads=64, mamba_d_head=64, mamba_d_state=128,
            embedding_multiplier=12.0, residual_multiplier=0.22,
            attention_multiplier=0.015625, logits_scaling=8.0))
    mesh = make_mesh(MeshConfig(dp=1, tp=1), devices=topo.devices[:1])
    model = build_model("ssm_dense", cfg, remat_budget_gib=V5E_LIMIT_GIB)
    params = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        jax.eval_shape(model.init, jax.random.key(0)), model.shardings(mesh))
    scalar = NamedSharding(mesh, P())
    opt = AdamState(step=jax.ShapeDtypeStruct((), jnp.int32, sharding=scalar),
                    mu=params, nu=params)
    ids = jax.ShapeDtypeStruct((1, 4096), jnp.int32, sharding=NamedSharding(
        mesh, P(("dp", "ep"), "cp")))
    step = build_train_step(model, mesh, OptimizerConfig(),
                            with_grad_norm=True, with_counters=True)
    said = io.StringIO()
    with contextlib.redirect_stderr(said):
        compiled = step.lower(params, opt, ids, ids, ids).compile()
    assert "remat auto: picked 'dots'" in said.getvalue()
    assert "reserve_held=False" in said.getvalue()
    plan = compiled.memory_analysis()
    args = plan.argument_size_in_bytes / memory.GIB
    planned = args + plan.temp_size_in_bytes / memory.GIB
    assert args == pytest.approx(772_160_448 * 12 / memory.GIB, rel=1e-3)
    assert planned < V5E_LIMIT_GIB, planned
    text = compiled.as_text()
    assert len(re.findall(r"%flash_fwd[.\d]* = ", text)) == 1
    assert len(re.findall(r"%flash_bwd[.\d]* = ", text)) == 1
    assert "f32[1,4096,12544]" in text and "100352" not in text
    # since PR 69 the recurrence is two Mosaic calls (ops/pallas/ssd.py),
    # forward, the layer's recompute (with the entering states) and the
    # transpose, under the scope the benchmark reads as `mamba/ssd`, with
    # operand counts its flash patterns pass over, and the text's float32
    # decays of 256 x 256 a head and chunk are nowhere in the step
    calls = re.findall(
        r"%([\w.\-]+) = ([^\n]*?) custom-call\(([^)]*)\), "
        r'custom_call_target="tpu_custom_call"[^\n]*?op_name="([^"]*)"',
        text)
    ssd_calls = [(name.split(".")[0], operands.count("%"), scope)
                 for name, _, operands, scope in calls
                 if name.startswith("ssd_")]
    assert sorted({(name, n) for name, n, _ in ssd_calls}) == [
        ("ssd_bwd", 7), ("ssd_fwd", 5)]
    assert all("mamba/ssd" in scope for _, _, scope in ssd_calls)
    states = [out for name, out, _, _ in calls if name.startswith("ssd_fwd")]
    assert any("f32[1,16,128,4096]" in out for out in states)
    assert not re.search(r"\[[\d,]*256,256\]", text)


def test_the_bd_moe_cells_step_compiles_for_a_v5e_at_the_rung_auto_picks(
        topo, described_tpu):
    """The sixth cell's step (`sdar-30b-a3b.train-ep8share-b2-t4096`: the
    bd_moe family at the published widths, 16 of 128 experts held, 6
    layers, 2 x 4096 data tokens = 2 x 8192 rows, bf16) compiled for the
    described chip at the rung `remat="auto"` picks there, `flash` since PR
    62 (no reserve is held beside 7.22 GiB of state): the family's memory
    facts (2L rows a sequence, the chunk of one mean share, 16,384 rows,
    logits on half the rows) leave the selector's margin, where the chip's
    own count of this step is 13.55 GiB (PERF.md section 5, PR 62; 13.46
    at the floor; the compiler's plan, 15.92, charges more than the
    runtime reserves, as in the hybrid cell),
    and Mosaic takes the flash kernels under the block-diffusion mask at
    head 128 and a group of 8 over eight blocks a head: the forward with
    the key row resident and ONE backward kernel with the head resident."""
    from distributed_pytorch_from_scratch_tpu.config import BdMoEConfig
    from distributed_pytorch_from_scratch_tpu.models import build_model
    cfg = ModelConfig(
        attn_dim=2048, ffn_dim=768, num_heads=32, num_kv_heads=4,
        num_layers=6, vocab_size=18992, maxlen=32768, rope_theta=1e6,
        compute_dtype="bfloat16", num_experts=128, moe_top_k=8,
        bd_moe=BdMoEConfig(head_dim=128, moe_intermediate_size=768,
                           block_length=4, mask_token_id=1,
                           experts_held=16))
    mesh = make_mesh(MeshConfig(dp=1, tp=1), devices=topo.devices[:1])
    model = build_model("bd_moe", cfg, remat_budget_gib=V5E_LIMIT_GIB)
    params = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        jax.eval_shape(model.init, jax.random.key(0)), model.shardings(mesh))
    scalar = NamedSharding(mesh, P())
    opt = AdamState(step=jax.ShapeDtypeStruct((), jnp.int32, sharding=scalar),
                    mu=params, nu=params)
    ids = jax.ShapeDtypeStruct((2, 4096), jnp.int32, sharding=NamedSharding(
        mesh, P(("dp", "ep"), "cp")))
    step = build_train_step(model, mesh, OptimizerConfig(),
                            with_grad_norm=True, with_counters=True)
    said = io.StringIO()
    with contextlib.redirect_stderr(said):
        compiled = step.lower(params, opt, ids, ids, ids).compile()
    # the model sizes itself by the 2 x 8192 ROWS it makes of the batch;
    # 7.22 GiB of state: no snapshot fits beside the floor, no reserve is
    # held, and the rung that keeps the flash outputs fits the margin
    assert "remat auto: picked 'flash'" in said.getvalue()
    assert "reserve_held=False" in said.getvalue()
    assert "traced b2 x t8192" in said.getvalue()
    estimate = float(re.search(r"flash=([\d.]+)GiB",
                               said.getvalue()).group(1))
    plan = compiled.memory_analysis()
    args = plan.argument_size_in_bytes / memory.GIB
    assert args == pytest.approx(645_623_296 * 12 / memory.GIB, rel=1e-3)
    planned = args + plan.temp_size_in_bytes / memory.GIB
    # what the chip counted for this step, under the estimate and the plan
    chip_gib = 13.55
    assert chip_gib < estimate <= memory.MARGIN * V5E_LIMIT_GIB, estimate
    assert chip_gib < planned, planned
    # one forward kernel a layer body: the backward's recompute runs none
    assert len(re.findall(r"%flash_fwd[.\d]* = ", compiled.as_text())) == 1
    kernels = set(re.findall(r"%((?:flash|ragged)[\w\-]*?)[.\d]* = ",
                             compiled.as_text()))
    assert {"flash_fwd", "flash_bwd"} <= kernels, kernels
    assert not {"flash_bwd_dq", "flash_bwd_dkv"} & kernels, kernels


def test_the_selection_kernels_compile_at_the_dsa_cells_shape(
        topo, described_tpu):
    """Mosaic takes the five kernels of `ops/pallas/dsa_attention.py` at the
    sixteenth cell's shape (`keye-vl-2.0-30b-a3b.train-ep8share-b1-t16384`:
    one sequence of 16,384 rows, 32 query heads over 4 key-value heads of
    128, 16 index heads of 64, bf16, blocks of 128 x 512): the selection
    with a row block's 16,384 keys in VMEM (8 MiB of int32) and the block's
    set written as one plane of 128 x 512 words, the three attention walks
    with every head of a row block a grid step and that block of words for
    their mask (no index tensor: PR 73), and the indexer's loss walk, which
    still makes its index tile, with the index key's gradient (16,384 x 64
    float32) resident. The calls
    carry the names the benchmark's scopes read, and none has the 3 or 6
    operands by which the static-mask flash metrics find their calls."""
    from jax.sharding import SingleDeviceSharding
    from distributed_pytorch_from_scratch_tpu.ops.pallas import (
        dsa_attention as K)
    b, H, Hkv, t, h, J, c = 1, 32, 4, 16384, 128, 16, 64
    chip = SingleDeviceSharding(topo.devices[0])
    arg = lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=chip)
    f32, i32 = jnp.float32, jnp.int32
    q, k = arg(b, H, t, h), arg(b, Hkv, t, h)
    qi, ki, w = arg(b, J, t, c), arg(b, t, c), arg(b, J, t, 1, dtype=f32)
    tau, cut = arg(b, t, 1, dtype=f32), arg(b, t, 1, dtype=i32)
    lse = arg(b, H, t, 1, dtype=f32)
    blocks = dict(bq=K.BLOCK_Q, bk=K.BLOCK_K, interpret=False)
    assert (K.BLOCK_Q, K.BLOCK_K) == (128, 512)
    assert K.bit_planes(t, K.BLOCK_K) == 1
    bits = arg(b, 1, t, K.BLOCK_K, dtype=i32)
    select = jax.jit(lambda *a: K.select_call(*a, 2048, **blocks))
    assert [(o.shape, o.dtype) for o in jax.eval_shape(select, qi, ki, w)][
        3:] == [(bits.shape, bits.dtype), ((b, t, 1), f32), ((b, t, 1), f32)]
    text = ""
    for call, args in (
            (select, (qi, ki, w)),
            (lambda *a: K.fwd_call(*a, **blocks), (q, k, k, bits)),
            (lambda *a: K.bwd_calls(*a, **blocks),
             (q, k, k, bits, q, lse, lse)),
            (lambda *a: K.loss_call(*a, **blocks),
             (q, k, lse, qi, ki, w, tau, cut, tau))):
        text += jax.jit(call).lower(*args).compile().as_text()
    calls = re.findall(
        r"%([\w.\-]+) = [^\n]*? custom-call\(([^)]*)\), "
        r'custom_call_target="tpu_custom_call"', text)
    found = sorted((name.split(".")[0], operands.count("%"))
                   for name, operands in calls)
    assert found == [
        ("dsa_flash_bwd_dkv", 7), ("dsa_flash_bwd_dq", 7),
        ("dsa_flash_fwd", 4), ("dsa_index_loss", 9), ("dsa_select", 4)]
    assert not {3, 6} & {operands for _, operands in found}


# ---- the estimate against the chip's own counts, cell by cell (PR 62) ----

# cell: {rung or joined set: `device.peak_hbm_gib`}, what `remat="auto"`
# picks last.
# The floor's count is the ledger's (PR 61, every cell then at its floor or
# at the rung it still picks); the picked rung's is the builder's traced
# run of PR 62 (PERF.md section 5 has the table). Cell 3 takes the snapshot
# the reserve is held for, and its count holds that copy too.
CHIP_GIB = {
    "gpt2-medium.train-b12-t1024": {"ffn": 10.845},
    "gpt2-large.train-dp2-tp2": {"dots": 10.153},
    "gpt2-medium.train-ckpt-every40": {"ffn": 14.812},
    "joyai-llm-flash.train-ep16share-b4-t4096": {"true": 14.229},
    "qwen3-next-80b-a3b.train-ep16share-b2-t8192": {"true": 14.110,
                                                    "flash": 14.110},
    # (PR 71's readings, here and in cell 10: a chunk of the dispatch is a
    # quarter of the pairs where it was all of them, 10.899 / 11.609 and
    # 13.957 / 14.459 at `flash` until then; cell 10's 0.5 GiB buy `dots`)
    "lfm2-8b-a1b.train-ep4share-b2-t8192": {"true": 9.632, "dots": 10.339},
    "sdar-30b-a3b.train-ep8share-b2-t4096": {"true": 13.461,
                                             "flash": 13.547},
    "trinity-mini.train-epshare-b2-t8192": {"true": 14.695},
    "smallthinker-21b-a3b.train-ep4share-b1-t16384": {"true": 13.366,
                                                      "flash": 14.002,
                                                      "dots": 14.538},
    "xing4-29b-a4b.train-ep8share-b1-t4096": {"true": 13.700,
                                              "flash": 14.011},
    # (PR 64's readings: the delta mixer keeps no checkpoint of its own and
    # its q, k, v carry the ladder's names, so `auto` picks `dots`)
    "ling-3-flash.train-ep64share-b1-t4096": {"true": 12.937,
                                              "flash": 12.940,
                                              "dots": 13.400},
    # (PR 67's readings: the walk of the four passes carries the layers'
    # gradient as ONE stack, so the floor fell from PR 66's 12.438 and
    # `auto` picks `flash` beside 6.84 GiB of state; `dots` would need
    # 15.64. At the floor PINNED the cell's `device.peak_hbm_gib` reads the
    # float32 reference's phase, 11.934; the step's own count there, the
    # buffers and the reserve at the window's end, is what stands here)
    "ouro-2.6b.train-loop4-b1-t4096": {"true": 10.654, "flash": 13.850},
    # (PR 68's reading at the rung `auto` picks beside 8.63 GiB of weights
    # and moments: every layer's `ffn_gate` / `ffn_up` and the one attention
    # layer's names kept. The floor counted 12.977 and the estimate reads
    # 3.2% under it there: models/ssm_dense.py says why it is not listed)
    "granite-4.0-h-micro.train-pp4stage-b1-t4096": {"dots": 13.445},
    # (PR 73's readings, what the chip held when the window ended, buffers
    # and the step's reservation, `dots` with the rung named in a scratch
    # wrapper: 14.970 and 14.071 in PR 72. The rows' sets are kept as one
    # bit a pair with the lse, 32 MiB a layer, and the backward's walks hold
    # no index tensor any more, which took more off the window than the
    # bits put on; the family's last term is set from these two. The
    # (t, t) index score is in no term: the kernels make it a tile)
    "keye-vl-2.0-30b-a3b.train-ep8share-b1-t16384": {"dots": 14.917,
                                                     "flash": 13.861},
    # (PR 76's reading at the floor, from which the family's last term is
    # set; the memory and the one layer's keys and values, kept whatever
    # the rung, are `shared_elems_per_token`. Beside 10.39 GiB of state the
    # `ffn_gate` / `ffn_up` stacks, 4.0 GiB at 16k, do not fit: since PR 77
    # `auto` PASSES OVER that group and keeps the two behind it, the flash
    # kernel's outputs in the three attention layers and their q, k, v.
    # PR 77's readings, what the chip held when the window ended: the
    # `flash` group alone named in a scratch wrapper, then what `auto`
    # picks. The flash group reads 0.79 GiB on the chip for 0.48 of named
    # stacks and q, k, v 0.16 for 0.39: PERF.md section 7)
    "phi-4-mini-flash-reasoning.train-b1-t16384": {
        "true": 12.068, "true+flash": 12.856, "true+flash+dots": 13.018},
}
SNAPSHOTS = ("gpt2-medium.train-ckpt-every40",)


@pytest.mark.parametrize("cell,rung", [
    (cell, rung) for cell, counts in CHIP_GIB.items() for rung in counts])
def test_the_estimate_is_within_a_band_of_the_chips_count(
        cell, rung, cell_step_bytes):
    """Never under the chip by more than 1% (an under-estimate is an OOM
    in a cell the driver runs), never over by more than 5%, at the floor
    and at the rung `auto` picks; and that rung IS what `auto` picks at a
    v5e's limit, with the margin of the chip left."""
    _, parts = cell_step_bytes(cell)
    at = parts(rung)
    estimate = (at["total"] + (cell in SNAPSHOTS) * at["resident"]
                ) / memory.GIB
    chip = CHIP_GIB[cell][rung]
    assert -0.01 <= estimate / chip - 1 <= 0.05, (estimate, chip)
    if rung != list(CHIP_GIB[cell])[-1]:
        return
    assert memory._pick(parts, V5E_LIMIT_GIB, None, allow_false=False,
                        verbose=True) == rung
    # (the floor is what is left where nothing fits the margin: cell 9)
    usable = memory.MARGIN * V5E_LIMIT_GIB
    if rung != "true" and cell not in SNAPSHOTS:
        assert estimate <= usable and chip <= usable, (estimate, chip)
