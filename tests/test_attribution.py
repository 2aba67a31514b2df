"""Roofline attribution (obs/attribution) + the remat memory selector
(training/memory): pure host math, so these pin the numbers the perf work
leans on — the flash tile accounting (read from the kernel's own
sub-tile plan), the suspect ranking, and the policy the 45m/gpt2 presets are
known to need.
"""

import pytest

from distributed_pytorch_from_scratch_tpu.config import model_preset
from distributed_pytorch_from_scratch_tpu.obs.attribution import (
    attribution, flash_tile_stats, format_attribution)
from distributed_pytorch_from_scratch_tpu.training.memory import (
    estimate_step_gib, hbm_budget_gib, select_remat)


# ------------------------------------------------------ flash tile stats


def test_tile_stats_single_block_walks_its_plan():
    """t=1000 at the shipped 1024x1024 default: ONE grid tile, but the
    kernel's plan walks its 128x256 sub-tiles and leaves out the 12 of 32
    wholly above the diagonal — 1.31x the causal-real work where the whole
    padded square was 2.1x. 11 of the 20 computed build a mask: the 8 the
    diagonal crosses and the 3 more of the sub-row t_real cuts."""
    s = flash_tile_stats(1000, 1024, 1024)
    assert s["t_pad"] == 1024
    assert (s["sub_q"], s["sub_k"]) == (128, 256)
    assert (s["live_tiles"], s["total_tiles"]) == (20, 32)
    assert s["masked_tiles"] == 11
    assert s["work_elems"] == 20 * 128 * 256
    assert s["ideal_elems"] == 1000 * 1001 / 2
    assert 1.30 < s["waste_ratio"] < 1.32
    full = flash_tile_stats(1024, 1024, 1024)
    assert (full["live_tiles"], full["masked_tiles"]) == (20, 8)
    assert 1.24 < full["waste_ratio"] < 1.26      # 10/16 of the square


@pytest.mark.parametrize("t,t_real,blocks,hkv", [
    (1024, None, (1024, 1024), 2), (1024, 1000, (1024, 1024), 2),
    (1024, 600, (512, 512), 1), (700, None, (128, 256), 2),
    # four and eight resident blocks a head: the looped stretch is counted
    # as the sub-tiles it runs
    (2048, None, (512, 512), 2), (2048, 1500, (256, 256), 1)])
def test_tile_stats_agree_with_kernel_cost_estimate(t, t_real, blocks, hkv):
    """One source: the FLOPs `_fwd_call` hands XLA as the kernel's
    cost_estimate are the plan's computed entries x 4 x head_dim, the same
    entries `flash_tile_stats` reports."""
    import jax
    import jax.numpy as jnp
    from distributed_pytorch_from_scratch_tpu.ops.pallas.flash_attention \
        import flash_attention

    b, h, d = 1, 2, 16
    q = jnp.zeros((b, h, t, d), jnp.float32)
    kv = jnp.zeros((b, hkv, t, d), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda q, k, v: flash_attention(
        q, k, v, block_q=blocks[0], block_k=blocks[1], t_real=t_real,
        interpret=True))(q, kv, kv)
    calls = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "custom_vjp_call"]
    inner = calls[0].params["call_jaxpr"].jaxpr.eqns
    cost = [e for e in inner if e.primitive.name == "pallas_call"][0].params[
        "cost_estimate"]
    s = flash_tile_stats(t, *blocks, t_real=t_real, head_dim=d,
                         dtype="float32")
    assert cost.flops == 4 * d * b * h * s["work_elems"]
    assert cost.transcendentals == b * h * s["work_elems"]


def test_tile_stats_small_blocks_skip_dead_tiles():
    """128-blocks at t=1024: the causal grid guard skips the upper
    triangle — 36 of 64 tiles live (sum of min(i+1, 8))."""
    s = flash_tile_stats(1024, 128, 128)
    assert (s["live_tiles"], s["total_tiles"]) == (36, 64)
    assert s["waste_ratio"] < 1.2


def test_tile_stats_brute_force_agreement():
    """The tile counter must agree with brute-force evaluation of the
    kernel's block_live predicate at a non-square block shape."""
    t, bq, bk = 700, 128, 256
    s = flash_tile_stats(t, bq, bk)
    t_pad = s["t_pad"]
    live = sum(1
               for qi in range(t_pad // bq)
               for ki in range(t_pad // bk)
               if ki * bk <= qi * bq + bq - 1
               and ki * bk < t and qi * bq < t)
    assert s["live_tiles"] == live
    assert s["work_elems"] == live * bq * bk


def test_tile_stats_t_real_cuts_pad_rows():
    """Bucketed accounting: a t=1024 buffer holding 1000 real tokens prices
    exactly like t=1000 at the same blocks (pad tiles are skipped, the
    ideal is the real causal triangle)."""
    bucketed = flash_tile_stats(1024, 256, 256, t_real=1000)
    plain = flash_tile_stats(1000, 256, 256)
    assert bucketed["work_elems"] == plain["work_elems"]
    assert bucketed["ideal_elems"] == plain["ideal_elems"]


# ------------------------------------------------------ attribution report


@pytest.fixture
def cfg45m():
    return model_preset("45m", compute_dtype="bfloat16")


def test_attribution_ranks_suspects_descending(cfg45m):
    rep = attribution(cfg45m, 32, 1000, remat="dots", spd=8,
                      block_q=1024, block_k=1024)
    est = [s["est_ms"] for s in rep["suspects"]]
    assert est == sorted(est, reverse=True)
    assert [s["rank"] for s in rep["suspects"]] == list(
        range(1, len(est) + 1))
    assert rep["analytic_step_ms"] > 0
    # at the flagship shape the tile waste must register as a real suspect
    tile = next(s for s in rep["suspects"]
                if "tile/pad waste" in s["name"])
    assert tile["est_ms"] > 1.0  # > 1 ms of the step


def test_attribution_measured_mode_computes_dispatch_and_gap(cfg45m):
    """With the round-4 measured step, the report must (a) quote shares
    against the measured basis, (b) derive the dispatch gap from
    step - amortised, and (c) surface the roofline gap — the share the
    itemised suspects cannot explain, which IS the 45m finding."""
    measured = {"step_ms": 200.0, "step_ms_spd8": 184.5}
    rep = attribution(cfg45m, 32, 1000, remat="dots", spd=8,
                      measured=measured, block_q=1024, block_k=1024)
    assert rep["step_ms_basis"] == 184.5
    assert abs(rep["dispatch_ms"] - 15.5) < 1e-9
    gap = next(s for s in rep["suspects"] if "roofline gap" in s["name"])
    assert gap["est_ms"] > 50  # most of the flagship's missing MFU
    assert gap["rank"] == 1
    total_share = sum(s["share"] for s in rep["suspects"])
    assert total_share <= 1.01  # suspects never over-explain the step


def test_gpt2_family_prices_two_matmul_ffn(cfg45m):
    """gpt2's gelu MLP is fc+proj (2 matmuls) vs llama's SwiGLU (3): at
    identical dims the gpt2 FFN phase must price exactly 2/3 of llama's."""
    from distributed_pytorch_from_scratch_tpu.obs.attribution import (
        analytic_phases)

    llama = {p.name: p for p in analytic_phases(cfg45m, 32, 1000, "dots")}
    gpt2 = {p.name: p for p in analytic_phases(cfg45m, 32, 1000, "dots",
                                               family="gpt2")}
    assert gpt2["ffn"].flops == pytest.approx(llama["ffn"].flops * 2 / 3)
    assert gpt2["qkv_proj"].flops == llama["qkv_proj"].flops


def test_attribution_remat_ordering(cfg45m):
    """remat=true must price strictly more recompute than dots, and dots
    more than false; a joined set of the ladder's groups (what `auto`
    resolves to where it passes over a group) is priced by what it keeps."""
    ms = {r: attribution(cfg45m, 32, 1000, remat=r)["analytic_step_ms"]
          for r in ("false", "dots", "true+flash+dots", "flash",
                    "true+flash", "true")}
    assert ms["false"] < ms["dots"] < ms["true+flash+dots"] \
        < ms["true+flash"] < ms["true"]
    assert ms["flash"] < ms["true+flash"]


def test_format_attribution_renders_table(cfg45m):
    measured = {"fwd_ms": 50.0, "fwdbwd_ms": 150.0, "step_ms": 200.0,
                "step_ms_spd8": 184.5}
    rep = attribution(cfg45m, 32, 1000, remat="dots", spd=8,
                      measured=measured)
    text = format_attribution(rep, measured)
    assert "rank" in text and "suspect" in text
    assert "measured" in text  # the basis line names its source
    # analytic-vs-measured bucket rows render the measured numbers
    assert "50.00" in text and "100.00" in text


def test_attribution_default_block_prices_like_tuned_blocks(cfg45m):
    """Since the kernels walk sub-tiles inside a grid block, the one-block
    default skips what 256-blocks skip: plain t=1000 at the 1024 default
    prices its attention like the bucketed t_real=1000 path at 256-blocks
    (1.31x the causal ideal both), no longer 2.1x against 1.3x — so the
    bucketed buffer's 2.4% more tokens are all that is left between them."""
    before = attribution(cfg45m, 32, 1000, remat="dots",
                         block_q=1024, block_k=1024)
    after = attribution(cfg45m, 32, 1024, remat="dots", t_real=1000,
                        block_q=256, block_k=256)
    assert (after["tile_stats"]["waste_ratio"]
            <= before["tile_stats"]["waste_ratio"] < 1.32)
    assert (before["analytic_step_ms"] < after["analytic_step_ms"]
            < 1.03 * before["analytic_step_ms"])


# ------------------------------------------------------ memory selector


def test_estimate_monotone_in_remat_policy():
    """Every rung of the ladder keeps what the rung below keeps and more
    (under tp; without it nothing tags `attn_proj`, so its rung is rung
    0's size); no remat keeps most."""
    from distributed_pytorch_from_scratch_tpu.models.transformer import (
        REMAT_RUNGS)
    for family in ("llama", "gpt2"):
        cfg = model_preset("45m")
        est = [estimate_step_gib(cfg, 32, 1000, p, family=family, tp=2,
                                 world=2)
               for p in REMAT_RUNGS + ("false",)]
        assert est == sorted(est) and len(set(est)) == len(est) and est[0] > 0
        flat = [estimate_step_gib(cfg, 32, 1000, p, family=family)
                for p in REMAT_RUNGS]
        assert flat == sorted(flat) and flat[0] == flat[1] < flat[2]


# The benchmark's two cells per rung, GiB on the fullest chip: what the chip
# counted while the cell's step ran (`memory_stats()`: peak_bytes_in_use +
# peak_bytes_reserved; my chip runs, PR 26: `.scratch/rungs.py` builds the
# step as the `train` runner does, one process a rung), and what the
# compiler planned (`memory_analysis()`: arguments + temporaries; a v5e
# described in the sandbox gives the chip's own plan to the byte, PR 26).
# The plan charges some stacks that live from the forward loop to the
# backward loop twice; the runtime reserves each once, and it is the runtime
# the estimate is held to (PERF.md section 5). Rung 0 of cell 1 is also the
# ledger's `device.peak_hbm_gib` (8.60, PR 25); the ledger's 8.09 for cell 2
# is not the step's peak but the float32 reference's temporaries (3.73 GiB
# reserved against the step's 3.06), which every rung above 0 exceeds.
MEDIUM = dict(attn_dim=1024, ffn_dim=4096, num_heads=16, num_layers=24,
              vocab_size=50257, maxlen=1024, compute_dtype="bfloat16")
LARGE = dict(attn_dim=1280, ffn_dim=5120, num_heads=20, num_layers=36,
             vocab_size=50257, maxlen=1024, compute_dtype="bfloat16")
CELL1 = dict(batch=12, seqlen=1024, family="gpt2")
CELL2 = dict(batch=16, seqlen=1024, tp=2, world=4, dp=2, family="gpt2")
#        rung: (chip GiB or None where not run, planned GiB)
CELL1_GIB = {"true": (8.598, 9.139), "attn_proj": (8.598, 9.138),
             "ffn": (10.848, 13.637), "flash": (None, 14.799),
             "dots": (13.113, 16.544)}
CELL2_GIB = {"true": (7.419, 8.386), "attn_proj": (8.090, 9.695),
             "ffn": (9.457, 11.025), "flash": (9.809, 11.365),
             "dots": (10.864, 12.425)}
# Cell 2 in the layout the model picks for itself at tp 2 since PR 28
# (sequence parallelism over the ring matmuls: every (b, t, d) stack is
# halved): the chip ran the rung `auto` picks there, 'dots' (my chip runs,
# PR 28: 10,907,819,520 bytes); the plans are of the ring programs compiled
# for a described v5e:2x2.
CELL2_SP = dict(CELL2, sequence_parallel=True)
CELL2_SP_GIB = {"true": (None, 7.651), "attn_proj": (None, 8.158),
                "ffn": (None, 10.368), "flash": (None, 10.715),
                "dots": (10.159, 11.694)}


@pytest.mark.parametrize("rung", sorted(CELL1_GIB))
@pytest.mark.parametrize("cell", ["medium-b12-tp1", "large-b8-tp2",
                                  "large-b8-tp2-sp"])
def test_estimate_is_what_the_chip_counts(cell, rung):
    """Within 1% of the chip's count where a rung was run on the chip
    (the PR's criterion is 8%), and never over the compiler's plan."""
    from distributed_pytorch_from_scratch_tpu.config import ModelConfig
    shape, kw, table = {"medium-b12-tp1": (MEDIUM, CELL1, CELL1_GIB),
                        "large-b8-tp2": (LARGE, CELL2, CELL2_GIB),
                        "large-b8-tp2-sp": (LARGE, CELL2_SP, CELL2_SP_GIB),
                        }[cell]
    chip, planned = table[rung]
    est = estimate_step_gib(ModelConfig(**shape), remat=rung, **kw)
    if chip is not None:
        assert abs(est - chip) / chip < 0.01, (est, chip)
    assert est < planned * 1.01, (est, planned)


def test_select_remat_matches_validated_configs():
    """The selector must reproduce the empirically validated picks: 45m
    b32xt1000 and gpt2-124m b8xt1024 fit a 16G chip without remat
    (bench.py's defaults, proven in round 4)."""
    bf16 = dict(compute_dtype="bfloat16")     # what those runs computed in
    assert select_remat(model_preset("45m", **bf16), 32, 1000,
                        budget_gib=16.0, verbose=False) == "false"
    assert select_remat(model_preset("gpt2-124m", **bf16), 8, 1024,
                        budget_gib=16.0, verbose=False) == "false"


def test_select_remat_picks_the_cells_rungs(capsys):
    """What the benchmark's cells get on a v5e (bytes_limit 15.748 GiB) with
    the default reserve of one more copy of the resident state (the
    program's own snapshot): the rung, and the line that says so."""
    from distributed_pytorch_from_scratch_tpu.config import ModelConfig
    limit = 15.748
    assert select_remat(ModelConfig(**MEDIUM), budget_gib=limit, **CELL1) \
        == "ffn"
    err = capsys.readouterr().err
    assert "remat auto: picked 'ffn'" in err and "reserve 3.97 GiB" in err
    # (the climb sizes each group on top of what it kept: `flash` over
    # `ffn`, then q, k, v over `ffn` alone, and neither fits)
    assert "ffn=10.79GiB" in err and "flash=11.37GiB" in err
    assert "passing over flash, dots" in err and "true+ffn+dots=" in err
    assert select_remat(ModelConfig(**LARGE), budget_gib=limit,
                        verbose=False, **CELL2) == "flash"
    # ... and in the layout the model resolves to at tp 2 (PR 28), whose
    # halved stacks leave room for the top rung
    assert select_remat(ModelConfig(**LARGE), budget_gib=limit,
                        verbose=False, **CELL2_SP) == "dots"
    # a caller that knows it takes no snapshot passes the true reserve
    assert select_remat(ModelConfig(**MEDIUM), budget_gib=limit,
                        reserve_gib=0.0, verbose=False, **CELL1) == "dots"


V5E_LIMIT_GIB = 15.748
EXPERT_CELLS = [
    "joyai-llm-flash.train-ep16share-b4-t4096",
    "qwen3-next-80b-a3b.train-ep16share-b2-t8192",
    "lfm2-8b-a1b.train-ep4share-b2-t8192",
    "sdar-30b-a3b.train-ep8share-b2-t4096",
    "trinity-mini.train-epshare-b2-t8192",
    "smallthinker-21b-a3b.train-ep4share-b1-t16384",
    "xing4-29b-a4b.train-ep8share-b1-t4096",
    "ling-3-flash.train-ep64share-b1-t4096"]


def picked(parts, capsys, reserve_gib=None):
    from distributed_pytorch_from_scratch_tpu.training.memory import _pick
    rung = _pick(parts, V5E_LIMIT_GIB, reserve_gib, allow_false=False,
                 verbose=True)
    return rung, capsys.readouterr().err


@pytest.mark.parametrize("cell", ["gpt2-medium.train-b12-t1024",
                                  "gpt2-medium.train-ckpt-every40"])
def test_the_reserve_is_held_where_the_floor_can_take_a_snapshot(
        cell, cell_step_bytes, capsys):
    """Cells 1 and 3 hold 3.97 GiB of state: the floor rung fits beside one
    more copy of it (cell 3 does take the snapshot), the copy is held back
    and the rung is the one it has been."""
    _, parts = cell_step_bytes(cell)
    rung, said = picked(parts, capsys)
    assert rung == "ffn"
    assert "reserve 3.97 GiB" in said and "reserve_held=True" in said


@pytest.mark.parametrize("cell", EXPERT_CELLS)
def test_a_reserve_no_rung_can_honour_is_not_held(cell, cell_step_bytes,
                                                  capsys):
    """A chip's share of an expert model holds 5.7 - 8.6 GiB of state: the
    floor rung does not fit the selector's margin of what one more copy
    would leave (with it the floor is over the chip itself, but for cell 7
    since PR 71: 15.52 GiB of 15.75, whose chunk of the dispatch is a
    quarter of what it was), no snapshot could be taken, and `auto` sizes
    its rung by MARGIN x the budget; a caller that names a reserve still
    gets it held."""
    from distributed_pytorch_from_scratch_tpu.training.memory import (
        GIB, MARGIN)
    _, parts = cell_step_bytes(cell)
    floor = parts("true")
    assert floor["total"] > MARGIN * (V5E_LIMIT_GIB * GIB
                                      - floor["resident"])
    rung, said = picked(parts, capsys)
    assert "reserve 0.00 GiB" in said and "reserve_held=False" in said
    assert parts(rung)["total"] / GIB <= MARGIN * V5E_LIMIT_GIB \
        or rung == "true"
    named = floor["resident"] / GIB
    rung, said = picked(parts, capsys, reserve_gib=named)
    assert rung == "true"
    assert f"reserve {named:.2f} GiB" in said and "reserve_held=True" in said


def test_select_remat_steps_down_when_tight():
    """A small budget must force the ladder down — and a hopeless one
    still returns 'true' (the ladder's floor, never an exception)."""
    cfg = model_preset("45m")
    from distributed_pytorch_from_scratch_tpu.models.transformer import (
        REMAT_RUNGS)
    assert select_remat(cfg, 32, 1000, budget_gib=10.0,
                        verbose=False) in REMAT_RUNGS
    assert select_remat(cfg, 32, 1000, budget_gib=0.1,
                        verbose=False) == "true"


def test_estimate_rejects_unknown_policy():
    with pytest.raises(ValueError, match="remat must be"):
        estimate_step_gib(model_preset("45m"), 32, 1000, "sometimes")


def test_hbm_budget_raises_without_memory_stats():
    # the CPU test mesh reports no bytes_limit: no assumed 16 GiB, an error
    with pytest.raises(ValueError, match="no memory_stats"):
        hbm_budget_gib()
    # the selector sizes nothing there and stays on rung 0, the program
    # `remat=True` has always been
    assert select_remat(model_preset("45m"), 32, 1000, verbose=False) == "true"
    # off-chip callers name the budget
    assert select_remat(model_preset("45m"), 32, 1000, budget_gib=16.0,
                        verbose=False) != "true"


def test_one_peaks_table_and_unknown_chips_raise():
    """MFU and every roofline divide by obs/attribution.CHIP_SPECS and by
    nothing else; a device_kind it does not list raises, and the CPU has
    no peak at all (MFU 'not measured', not a share of a v5e)."""
    import jax

    from distributed_pytorch_from_scratch_tpu.obs.attribution import (
        CHIP_SPECS, DEVICE_KINDS, chip_key_for, chip_specs)
    from distributed_pytorch_from_scratch_tpu.training.metrics import (
        chip_peak_flops)

    assert chip_key_for("TPU v5 lite") == "v5e"      # what the v5e answers
    assert chip_key_for("TPU v5") == "v5p"           # NOT the v5e
    assert set(DEVICE_KINDS.values()) <= set(CHIP_SPECS)
    assert chip_specs("v5e") == (197e12, 819e9)
    for kind in ("cpu", "TPU v9 mega", ""):
        with pytest.raises(ValueError, match="no peak"):
            chip_key_for(kind)
    with pytest.raises(ValueError, match="unknown chip"):
        chip_specs("v9")
    with pytest.raises(ValueError, match="unknown chip"):
        attribution(model_preset("45m"), 32, 1000, chip="v9")
    assert chip_peak_flops() is None                 # the CPU test mesh

    class FakeDevice:
        platform, device_kind = "tpu", "TPU v5 lite"

    assert chip_peak_flops(FakeDevice()) == 197e12
    FakeDevice.device_kind = "TPU v9 mega"
    with pytest.raises(ValueError, match="no peak"):
        chip_peak_flops(FakeDevice())
    assert jax.devices()[0].platform == "cpu"


def test_moe_estimate_exceeds_dense():
    dense = estimate_step_gib(model_preset("45m"), 32, 1000, "false")
    moe = estimate_step_gib(model_preset("45m-moe8"), 32, 1000, "false")
    assert moe > dense


# ------------------------------------------------------ ZeRO ladder (r12)


def _dp_records(zero_stage, dp_reduce_dtype="f32", dp_bucket_mb=25.0,
                dp=4):
    from distributed_pytorch_from_scratch_tpu.obs.attribution import (
        comm_attribution)
    comm = comm_attribution(model_preset("45m"), 32, 1000, tp=1, dp=dp,
                            dp_bucket_mb=dp_bucket_mb,
                            dp_reduce_dtype=dp_reduce_dtype,
                            zero_stage=zero_stage)
    return {r["name"]: r for r in comm["records"]}, comm


def test_zero2_reduce_scatter_priced_at_half_allreduce_bytes():
    """ISSUE 9 acceptance: comm_attribution prices the stage-2 grad
    reduce-scatter at exactly HALF the stage-1 all-reduce wire bytes, at
    every wire dtype — the halved wire is shown, not asserted."""
    for wire in ("f32", "bf16", "int8"):
        ar, _ = _dp_records(1, wire)
        rs, _ = _dp_records(2, wire)
        assert rs["DP grad reduce-scatter"]["bytes_each"] * 2 == \
            ar["DP grad reduce"]["bytes_each"], wire
    # the schedule's other half: stage 2 adds the f32 param all-gather
    rs, comm = _dp_records(2)
    assert "ZeRO-2 param all-gather" in rs
    assert comm["config"]["zero_stage"] == 2
    # bucketed RS hides under the backward; the param gather is exposed
    assert rs["DP grad reduce-scatter"]["hidden_ms"] > 0
    assert rs["ZeRO-2 param all-gather"]["exposed_ms"] > 0


def test_zero3_schedule_priced_as_per_layer_gathers():
    """Stage 3 prices NO standalone grad collective: two param all-gathers
    (fwd + the remat replay) and the gather-transpose reduce-scatter, all
    f32 and all hidden up to the adjacent compute budgets."""
    recs, comm = _dp_records(3)
    names = set(recs)
    assert "ZeRO-3 param all-gather (fwd)" in names
    assert "ZeRO-3 param all-gather (bwd remat)" in names
    assert "ZeRO-3 grad reduce-scatter (bwd)" in names
    assert not any(n.startswith("DP grad reduce") for n in names)
    # the wire dtype the DP schedule actually carries under stage 3 is f32
    assert comm["config"]["wire_dtype"] == "f32"
    # per-element the RS matches stage 2's f32 bytes (same shard walks the
    # ring), while the gathers pay f32 regardless of --dp_reduce_dtype
    rs2, _ = _dp_records(2)
    assert recs["ZeRO-3 grad reduce-scatter (bwd)"]["bytes_each"] == \
        rs2["DP grad reduce-scatter"]["bytes_each"]


def test_zero_estimate_matches_perf_doc_table():
    """The per-stage resident-state model equals the docs/PERF.md "ZeRO
    ladder" table's bytes/param column (the satellite's validation): the
    doc and the estimator must not drift apart."""
    from distributed_pytorch_from_scratch_tpu.training.memory import (
        zero_state_bytes_per_param)
    dp = 8
    assert zero_state_bytes_per_param(0, dp) == 16.0
    assert zero_state_bytes_per_param(1, dp) == 8.0 + 8.0 / dp      # 9.0
    assert zero_state_bytes_per_param(2, dp) == 4.0 + 12.0 / dp     # 5.5
    # stage 3: 16/dp resident + the gathered working set (one layer +
    # embed/head), charged at 4 bytes per gathered param
    cfg = model_preset("45m")
    P = cfg.num_params()
    nonlayer = 2 * cfg.vocab_size * cfg.attn_dim + cfg.vocab_size \
        + cfg.attn_dim
    per_layer = (P - nonlayer) / cfg.num_layers
    expect = 16.0 / dp + 4.0 * (per_layer + nonlayer) / P
    assert abs(zero_state_bytes_per_param(3, dp, cfg) - expect) < 1e-9
    # dp=1 collapses every stage to the plain 16 bytes/param
    for stage in (0, 1, 2, 3):
        assert zero_state_bytes_per_param(stage, 1, cfg) == 16.0


def test_zero1_estimate_fix_shrinks_pre_existing_overestimate():
    """The satellite's bugfix: estimate_step_gib used to ignore optimizer
    sharding entirely, so a --zero1 dp8 run was overestimated by
    8 x P x (1 - 1/dp) bytes. The stage-aware estimate must be smaller
    and the delta must be exactly the sharded-moment savings."""
    cfg = model_preset("45m")
    base = estimate_step_gib(cfg, 32, 1000, "dots")
    z1 = estimate_step_gib(cfg, 32, 1000, "dots", zero_stage=1, dp=8)
    saved = (base - z1) * 1024 ** 3
    expect = cfg.num_params() * 8.0 * (1 - 1 / 8)
    assert abs(saved - expect) / expect < 1e-6


def test_select_remat_zero3_never_picks_false():
    """Stage 3 + remat 'false' would save every gathered layer as a
    backward residual; the selector must skip it even under an infinite
    budget."""
    cfg = model_preset("45m")
    assert select_remat(cfg, 32, 1000, budget_gib=1e9, verbose=False,
                        zero_stage=3, dp=8) == "dots"
    assert select_remat(cfg, 32, 1000, budget_gib=1e9,
                        verbose=False) == "false"
