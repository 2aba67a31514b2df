"""Analytic roofline + step-time attribution: where do the milliseconds go?

VERDICT r5 #1: the flagship 45M config ran at 33.7% MFU while gpt2-124m hit
55.7% on the same chip, and nothing in the repo could say WHY. This module
answers that question without needing the chip: it prices every phase of a
train step analytically (FLOPs and HBM bytes -> a roofline ms estimate) and
ranks the known waste suspects — flash-kernel tile/padding waste at the
actual block shapes, remat recompute, dispatch amortisation, the lm_head —
so `bench.py --breakdown` can print an attribution table on CPU and
cross-check it against measured phase times and XLA's cost_analysis when a
backend is present.

Everything here is pure host math (no jax arrays, no backend init): the
tile accounting mirrors the flash kernels' `block_live` grid predicates
(ops/pallas/flash_attention.py) and the phase FLOPs mirror
`training.metrics.model_flops_per_step`'s conventions, itemised per phase.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

# THE peaks table: per-chip peak bf16 FLOP/s and HBM bandwidth (bytes/s).
# Source: Google Cloud TPU documentation, the per-version "System
# architecture" pages (v4: 275 TFLOP/s, 1228 GB/s; v5e: 197 TFLOP/s,
# 819 GB/s; v5p: 459 TFLOP/s, 2765 GB/s; v6e: 918 TFLOP/s, 1640 GB/s).
# MFU (training.metrics.chip_peak_flops) and every roofline here divide by
# these and nothing else; a chip that is not listed is an error, not a v5e.
CHIP_SPECS = {
    "v4": (275e12, 1228e9),
    "v5e": (197e12, 819e9),
    "v5p": (459e12, 2765e9),
    "v6e": (918e12, 1640e9),
}

# jax `device_kind` -> CHIP_SPECS key, spelled as the TPU backend reports
# them (both spellings per chip, as jax's own tpu_info lists them; "TPU v5
# lite" is what the v5e machine answered, PR 21).
DEVICE_KINDS = {
    "TPU v4": "v4",
    "TPU v5 lite": "v5e", "TPU v5e": "v5e",
    "TPU v5": "v5p", "TPU v5p": "v5p",
    "TPU v6 lite": "v6e", "TPU v6e": "v6e",
}

# Per-chip ICI terms: (one-way per-link ring bandwidth bytes/s, per-hop
# latency s). These are the alpha-beta model's two knobs per collective —
# nominal values from the published interconnect specs; `calibrate_ici`
# LEARNS the effective bandwidth from a measured all-reduce p50 when the
# bench took one on real hardware (the 4 MiB probe bench.py already runs),
# so the comm attribution tracks the chip actually attached rather than
# the datasheet.
ICI_SPECS = {
    "v5e": (4.5e10, 1e-6),
    "v5p": (9.0e10, 1e-6),
    "v4": (4.5e10, 1e-6),
    "v6e": (9.0e10, 1e-6),
}

ALLREDUCE_PROBE_BYTES = 4 * 2**20  # metrics.allreduce_p50_us's payload


def chip_key_for(device_kind: str) -> str:
    """CHIP_SPECS key for a jax `device_kind` string ('TPU v6 lite' ->
    'v6e'). An unlisted kind — the CPU included — raises: a number divided
    by another chip's peak is not a measurement."""
    try:
        return DEVICE_KINDS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak FLOP/s / HBM bandwidth known for device_kind "
            f"{device_kind!r}: MFU and roofline numbers cannot be produced "
            f"on it (known kinds: {sorted(DEVICE_KINDS)}; add the chip, "
            f"with its source, to obs/attribution.CHIP_SPECS)") from None


def chip_specs(chip: str, table: Optional[dict] = None):
    """(peak bf16 FLOP/s, HBM bytes/s) for a CHIP_SPECS key — or the chip's
    row of another per-chip `table` (ICI_SPECS); unknown keys raise with
    the known ones listed, never priced as some other chip."""
    table = CHIP_SPECS if table is None else table
    try:
        return table[chip]
    except KeyError:
        raise ValueError(f"unknown chip {chip!r}; expected one of "
                         f"{sorted(table)}") from None


def calibrate_ici(chip: str, n: int,
                  measured_allreduce_us: Optional[float] = None,
                  probe_bytes: int = ALLREDUCE_PROBE_BYTES):
    """(ici_bw, ici_lat) for `chip` — the ICI_SPECS entry, with the
    bandwidth term re-fit from a measured ring all-reduce p50 when one is
    available: t = 2(n-1)/n * bytes / bw + 2(n-1) * lat  =>  bw. The
    latency model (2(n-1) hops: reduce-scatter phase + all-gather phase)
    matches how `comm_attribution` prices all-reduce records, so
    re-pricing the probe collective with the fitted terms reproduces the
    measurement. This is the 'learned ICI term': one measured collective
    pins the line the whole comm attribution is priced on."""
    bw, lat = chip_specs(chip, ICI_SPECS)
    if measured_allreduce_us and n > 1:
        wire = measured_allreduce_us * 1e-6 - 2 * (n - 1) * lat
        if wire > 0:
            bw = 2 * (n - 1) / n * probe_bytes / wire
    return bw, lat


def flash_tile_stats(t: int, block_q: Optional[int] = None,
                     block_k: Optional[int] = None,
                     t_real: Optional[int] = None,
                     head_dim: int = 64,
                     dtype: str = "bfloat16", mask=None,
                     backward: bool = False) -> Dict[str, float]:
    """MXU work the fwd flash kernel performs at this (t, blocks) vs the
    causal ideal — the quantified 't=1000 -> 1024 padding waste' suspect.
    With `mask` (an `ops/attention.AttnMask` that is not the triangle) the
    same under the declared mask: the ideal is the entries it leaves live,
    the blocks the kernels' own (`flash_blocks`, asked with `block_q` /
    `block_k` for the direction read); `backward` reads the backward's plan
    (merged rectangles, its own sub-tile) at the backward's blocks. `dtype`
    selects nothing and is kept for its callers.

    Reads the kernel's own static plan (`causal_plan_stats`, the function
    the kernels walk and `_fwd_call`'s cost_estimate prices): a tile is a
    SUB-TILE of a grid block here, `live_tiles` those the plan computes
    (masked or not), work = their score elements. `waste_ratio` = work /
    ideal (1.0 = perfect causal skip; the shipped 1024x1024 grid block at
    t=1024 computes 10 of its 16 256-wide sub-tile columns: 1.25, where the
    whole square was 2.0). `t_real` < t prices the pad-aware bucketed path
    (attn_t_real).
    """
    from ..ops.attention import CAUSAL, live_entries
    from ..ops.pallas.flash_attention import flash_blocks, plan_stats
    asked = ({"bwd_block_q": block_q, "bwd_block_k": block_k} if backward
             else {"block_q": block_q, "block_k": block_k})
    t_pad, bq, bk, bbq, bbk, mask = flash_blocks(
        t, head_dim, mask or CAUSAL, t_real=t_real, **asked)
    if backward:
        bq, bk = bbq, bbk
    tr = t if t_real is None else t_real
    ideal = (tr * (tr + 1) / 2 if mask.kind == "causal"
             else live_entries(mask, t))
    plan = plan_stats(mask, t_pad, bq, bk, tr, head_dim, backward)
    live = plan["computed_unmasked"] + plan["computed_masked"]
    return {"t_pad": t_pad, "block_q": bq, "block_k": bk,
            "sub_q": plan["sub_q"], "sub_k": plan["sub_k"],
            "live_tiles": live, "total_tiles": live + plan["skipped"],
            "masked_tiles": plan["computed_masked"],
            "work_elems": plan["work_elems"], "ideal_elems": ideal,
            "waste_ratio": plan["work_elems"] / ideal}


@dataclasses.dataclass
class PhaseCost:
    """One phase's analytic price. ms_est = roofline max(compute, memory)."""

    name: str
    flops: float
    bytes: float
    note: str = ""

    def ms(self, peak_flops: float, hbm_bw: float) -> float:
        return max(self.flops / peak_flops, self.bytes / hbm_bw) * 1e3


def analytic_phases(cfg, batch: int, t: int, remat: str = "dots",
                    t_real: Optional[int] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    family: str = "llama") -> List[PhaseCost]:
    """Per-phase FLOPs + HBM bytes for ONE fwd+bwd+adam train step (global,
    all devices), itemised so shares can be compared against measured
    fwd/bwd/adam times. remat is 'false' or names REMAT_LADDER's groups (a
    rung, or a joined set). A family whose stack a step passes R times over
    the same weights (`DecoderStack.passes`) runs every layer phase R x L
    times and the head and the CE R times (an exit a pass); the embedding
    and Adam once."""
    from ..models import family_class
    R = family_class(family).passes(cfg) or 1
    d, f, L = cfg.attn_dim, cfg.ffn_dim, cfg.num_layers * R
    h, hd, kd = cfg.num_heads, cfg.head_dim, cfg.kv_dim
    v = cfg.padded_vocab_size(1)
    N = batch * t            # tokens incl. any bucket padding
    A = 2                    # activation bytes (bf16); f32 would be 4
    P = cfg.num_params()
    # the matrices that read the MLP's input plus the one that writes its
    # output: SwiGLU = gate/up/down, 3 matmuls; a fc/proj gelu MLP, 2
    ffn_mats = family_class(family).ffn_inputs + 1

    stats = flash_tile_stats(t, block_q, block_k, t_real, hd,
                             cfg.compute_dtype)
    attn_elems = batch * h * stats["work_elems"]

    fwd = [
        PhaseCost("embed", 0.0, N * d * 4 + N * 4,
                  "gather; bytes-bound"),
        PhaseCost("qkv_proj", L * 2 * N * d * (d + 2 * kd),
                  L * (N * (d + (d + 2 * kd)) * A + d * (d + 2 * kd) * A)),
        PhaseCost("attention", attn_elems * 4 * hd,
                  L * (N * (2 * d + 2 * kd) * A + N * h * 4),
                  f"{stats['live_tiles']}/{stats['total_tiles']} live "
                  f"{stats['sub_q']}x{stats['sub_k']} sub-tiles of "
                  f"{stats['block_q']}x{stats['block_k']} blocks, "
                  f"{stats['waste_ratio']:.2f}x causal-ideal work"),
        PhaseCost("wo_proj", L * 2 * N * d * d,
                  L * (2 * N * d * A + d * d * A)),
        PhaseCost("ffn", L * 2 * ffn_mats * N * d * f,
                  L * (2 * N * (d + (ffn_mats - 1) * f) * A
                       + ffn_mats * d * f * A)),
        PhaseCost("norms_rope", L * 16 * N * d, L * 6 * N * d * A,
                  "elementwise; bytes-bound"),
        PhaseCost("lm_head", R * 2 * N * d * v,
                  R * (N * d * A + N * v * 4)),
        PhaseCost("ce_loss", R * 8 * N * v, R * 2 * N * v * 4,
                  "f32 logits read+reduce"),
    ]
    # attention FLOPs scale by L too (itemised per layer above except attn)
    fwd[2] = dataclasses.replace(fwd[2], flops=fwd[2].flops * L)

    # Backward: matmul phases cost 2x forward (dgrad + wgrad); the flash
    # backward runs 5 MXU dots where the forward runs 2 (fused path) ->
    # 2.5x; elementwise ~2x. Remat adds recompute on top:
    #   'true' — the whole layer forward replays (+1x layer fwd FLOPs)
    #   'dots' — matmul outputs + flash o/lse are saved; only elementwise
    #            replays (norms/rope/silu)
    #   'false' — nothing replays
    layer_fwd_flops = sum(p.flops for p in fwd[1:6])
    layer_fwd_bytes = sum(p.bytes for p in fwd[1:6])
    # what each group of models/transformer.REMAT_LADDER takes out of the
    # replay: the attention projection, the FFN's input matmuls, the flash
    # kernel, and with q/k/v every other matmul (only the elementwise
    # replay is left at the top rung); `remat` names the groups kept, as a
    # rung's prefix or a joined set (`remat_groups`)
    from ..models.stack import remat_groups
    ffn_in = fwd[4].flops * (ffn_mats - 1) / ffn_mats
    kept = {"attn_proj": fwd[3].flops, "ffn": ffn_in, "flash": fwd[2].flops,
            "dots": fwd[1].flops + fwd[4].flops - ffn_in}
    recompute = (0.0 if str(remat) == "false" else layer_fwd_flops - sum(
        kept[group] for group in remat_groups(str(remat))))
    recompute_bytes = (layer_fwd_bytes * recompute / layer_fwd_flops
                       if layer_fwd_flops else 0.0)
    bwd_flops = (2 * (fwd[1].flops + fwd[3].flops + fwd[4].flops
                      + fwd[6].flops + fwd[7].flops)
                 + 2.5 * fwd[2].flops + 2 * fwd[5].flops)
    bwd_bytes = 2 * sum(p.bytes for p in fwd[1:])
    phases = fwd + [
        PhaseCost("backward", bwd_flops, bwd_bytes,
                  "2x matmuls, 2.5x flash kernel"),
        PhaseCost("remat_recompute", recompute, recompute_bytes,
                  f"remat={remat}"),
        PhaseCost("adam", 12 * P, 28 * P,
                  "f32 params/moments read+write; bytes-bound"),
    ]
    return phases


def ring_chunk_bytes(cfg, batch: int, t: int, tp: int) -> Dict[str, float]:
    """The ring collective-matmul chunk schedule's ppermute bytes per
    DEVICE (tp_overlap='ring'), itemised so `--introspect` can cross-check
    the HLO's collective-permute byte count against it.

    Per ring instance the wire carries (n-1) hops of one (b, t/n, d) chunk
    = (n-1)/n * b*t*d*A bytes. Per layer: fwd = 4 instances (qkv ring, wo
    reduce ring, ffn ring, down reduce ring); bwd = 6 (each ag VJP runs a
    re-gather ring + a reduce ring; each rs VJP one gather ring). The head
    adds 1 fwd + 2 bwd. Both families share the schedule (gpt2's fc/proj
    pair rings exactly like gate-up/down). NOTE for the HLO cross-check:
    the layer stack is a lax.scan, so the compiled program TEXT contains
    one layer's ring ops (executed num_layers times) — compare
    `per_layer_*` against the HLO count, not `total_bytes`."""
    A = 2 if "bf16" in str(cfg.compute_dtype) or "bfloat16" in str(
        cfg.compute_dtype) else 4
    u = (tp - 1) / tp * batch * t * cfg.attn_dim * A
    return {"unit_bytes": u,
            "per_layer_fwd_bytes": 4 * u,
            "per_layer_bwd_bytes": 6 * u,
            "head_fwd_bytes": u,
            "head_bwd_bytes": 2 * u,
            "total_bytes": cfg.num_layers * 10 * u + 3 * u}


def cp_ring_attribution(cfg, batch: int, chunk: int, context: int,
                        cp: int, chip: str = "v5e",
                        decode_steps: int = 0,
                        measured_allreduce_us: Optional[float] = None) -> Dict:
    """Price the cp-serving wire (ISSUE 18): the chunked-prefill query
    ring's ppermute hops against the per-hop attend compute they
    interleave with, plus the two small psum families (chunk reassembly,
    decode's (out, lse) combine).

    The ring moves the QUERY carry, never page data: per hop each rank
    rotates its (b, h, chunk/cp, hd) query sub-block (compute dtype), the
    f32 (o, lse) accumulators and two int32 position fields to its
    neighbour, then attends the arrived queries against its LOCAL pool
    slab (~context/cp keys). The schedule is profitable while
    `per_hop.wire_ms` < `per_hop.attend_ms` — the ratio this report
    carries — because at steady state each hop's rotation hides under the
    next hop's attend (classic ring-attention overlap); the reassembling
    psum and the decode combine are latency-bound small collectives
    either way, priced fully exposed.

    Prefill records price ONE chunk dispatch x num_layers (the layer
    stack is a scan — multiply by ceil(context/chunk) dispatches for a
    full prompt); `decode_steps` > 0 additionally prices that many
    (out, lse) combines."""
    cp = max(1, cp)
    bw, lat = calibrate_ici(chip, cp,
                            measured_allreduce_us if cp > 1 else None)
    peak_flops, _ = chip_specs(chip)
    A = 2 if "bf16" in str(cfg.compute_dtype) or "bfloat16" in str(
        cfg.compute_dtype) else 4
    L, h, hd = cfg.num_layers, cfg.num_heads, cfg.head_dim
    cws = max(1, chunk // cp)  # per-rank query sub-block width
    # per-hop carry: compute-dtype query sub-block + f32 (o, lse)
    # accumulators + int32 positions/offset
    hop_bytes = (batch * h * cws * hd * (A + 4)   # qh + o
                 + batch * h * cws * 4            # lse
                 + batch * cws * 4 + 4)           # qph + off
    # per-hop attend: cws queries vs the local slab, qk + av matmuls
    attend_flops = 4 * batch * h * cws * max(1, context // cp) * hd
    hop_ms = (hop_bytes / bw + lat) * 1e3
    attend_ms = attend_flops / peak_flops * 1e3

    records = []

    def add(name, kind, count, nbytes, hops, budget_ms, note=""):
        total = count * (nbytes / bw + hops * lat) * 1e3
        hidden = min(total, budget_ms) if budget_ms > 0 else 0.0
        records.append({
            "name": name, "kind": kind, "count": count,
            "bytes_each": nbytes, "serialized_ms": total,
            "hidden_ms": hidden, "exposed_ms": total - hidden, "note": note})

    if cp > 1:
        ratio = hop_ms / attend_ms if attend_ms > 0 else float("inf")
        add("cp prefill query ring", "collective-permute",
            L * (cp - 1), hop_bytes, 1, L * (cp - 1) * attend_ms,
            f"per-hop carry {hop_bytes / 1e3:.1f} kB vs "
            f"{attend_flops / 1e9:.3f} GFLOP attend "
            f"(wire/compute {ratio:.2f}): hops hide under the next "
            f"hop's attend while the ratio stays < 1")
        add("cp prefill chunk reassembly", "all-reduce", L,
            2 * (cp - 1) / cp * batch * h * chunk * hd * 4,
            2 * (cp - 1), 0.0,
            "psum of the rotated (out) sub-blocks back into chunk order; "
            "small and latency-bound")
        if decode_steps > 0:
            add("cp decode (out, lse) combine", "all-reduce",
                decode_steps * L,
                2 * (cp - 1) / cp * (batch * h * hd * 4 + 2 * batch * h * 4),
                2 * (cp - 1), 0.0,
                "per-step psums of the per-rank partial output and softmax "
                "weights; pure latency")

    total = sum(r["serialized_ms"] for r in records)
    hidden = sum(r["hidden_ms"] for r in records)
    return {"records": records,
            "comm_total_ms": total,
            "comm_hidden_ms": hidden,
            "comm_exposed_ms": total - hidden,
            "per_hop": {"wire_bytes": int(hop_bytes), "wire_ms": hop_ms,
                        "attend_flops": int(attend_flops),
                        "attend_ms": attend_ms,
                        "wire_to_compute": (hop_ms / attend_ms
                                            if attend_ms > 0 else None)},
            "config": {"cp": cp, "chunk": chunk, "context": context,
                       "decode_steps": decode_steps, "chip": chip}}


def comm_attribution(cfg, batch: int, t: int, tp: int = 1, sp: bool = False,
                     tp_overlap: str = "off", dp: int = 1,
                     dp_bucket_mb: float = 0.0, dp_reduce_dtype: str = "f32",
                     chip: str = "v5e", family: str = "llama",
                     remat: str = "dots",
                     measured_allreduce_us: Optional[float] = None,
                     phase_ms: Optional[Dict[str, float]] = None,
                     zero_stage: int = 0, cp: int = 1,
                     cp_prefill_chunk: int = 0,
                     cp_context: int = 0) -> Dict:
    """Per-collective comm attribution with an overlap model: how many ms
    of ICI time the step spends, and how much of it HIDES under the matmul
    each collective is (or could be) fused with.

    Each record prices serialized_ms = bytes/ici_bw + hops*lat from the
    learned ICI terms (`calibrate_ici`), then splits hidden vs exposed:

    * tp act collectives, tp_overlap='ring' — hidden up to the ms of the
      matmul sharing the ring (ag_matmul/matmul_rs overlap exactly that
      pair); 'ring_q' additionally HALVES the priced chunk bytes (int8
      codes + per-row scales replace the bf16 payload); 'off' — the
      monolithic collective serialises fully.
    * DP grad reduce, dp_bucket_mb > 0 — buckets issue during the
      backward, hidden up to the backward's compute ms; 0 — the
      end-of-step blob is fully exposed. The WIRE dtype prices the bytes:
      bf16 halves them, int8 quarters them (the quantized ring's scale
      overhead, 4/WIRE_GROUP < 1%, is deliberately ignored) — a record
      that kept pricing the compute dtype would silently misreport the
      quantized wire as hidden/exposed ms it no longer spends.
    * `zero_stage` reshapes the DP schedule (training/zero.py). <= 1: one
      grad ALL-REDUCE, 2(dp-1)/dp x P x wire bytes. 2: a grad
      REDUCE-SCATTER at HALF those bytes ((dp-1)/dp x P — each rank
      receives only its shard) plus the end-of-step f32 param all-gather
      XLA inserts for the replicated params. 3: no explicit grad
      collective at all — per-layer param all-gathers (fwd, and again in
      the remat'd backward) whose TRANSPOSE is the grad reduce-scatter,
      all f32 ppermute rings hidden up to the adjacent compute. A record
      that kept pricing the stage-1 all-reduce would assert the halved
      wire instead of showing it.

    `phase_ms` (name -> analytic ms from `analytic_phases`) supplies the
    overlap budgets; computed here when omitted.
    """
    # the 4 MiB probe (`metrics.allreduce_p50_us`) rings over the tp axis,
    # so the re-fit must solve for n = tp; the fitted per-link bandwidth
    # then prices every axis's collectives
    bw, lat = calibrate_ici(chip, tp,
                            measured_allreduce_us if tp > 1 else None)
    if phase_ms is None:
        peak_flops, hbm_bw = chip_specs(chip)
        world = max(1, tp * dp)
        phases = analytic_phases(cfg, batch, t, remat, family=family)
        phase_ms = {p.name: p.ms(peak_flops * world, hbm_bw * world)
                    for p in phases}

    A = 2  # bf16 activation bytes, matching analytic_phases
    L = cfg.num_layers
    act = batch * t * cfg.attn_dim * A  # one full layer-boundary activation

    def ms_of(nbytes: float, hops: int) -> float:
        return (nbytes / bw + hops * lat) * 1e3

    records = []

    def add(name, kind, count, nbytes, hops, budget_ms, note=""):
        total = count * ms_of(nbytes, hops)
        hidden = min(total, budget_ms) if budget_ms > 0 else 0.0
        records.append({
            "name": name, "kind": kind, "count": count,
            "bytes_each": nbytes, "serialized_ms": total,
            "hidden_ms": hidden, "exposed_ms": total - hidden, "note": note})

    if tp > 1:
        ring = tp_overlap in ("ring", "ring_q")
        # ring_q: int8 codes on every hop — half the bf16 activation
        # bytes (per-row scales add 4/head_dim-ish; ignored like the DP
        # wire's group scales)
        wire_scale = 0.5 if tp_overlap == "ring_q" else 1.0
        shard = (tp - 1) / tp * act * wire_scale  # ag / rs wire bytes
        ar = 2 * (tp - 1) / tp * act    # all-reduce wire bytes (non-ring)
        hops = tp - 1
        # budgets: the matmul each collective's ring is fused with (fwd),
        # and its ~2x backward counterpart for the conjugate direction
        fwd_note = ("ring: hops hide under the partial dots"
                    + (", int8 payloads" if tp_overlap == "ring_q" else "")
                    if ring else "monolithic: fully exposed")
        if sp:
            # ring-mode counts follow `ring_chunk_bytes`'s chunk schedule:
            # each ag VJP runs TWO reverse rings (re-gather + reduce) where
            # the monolithic transpose is one conjugate collective, so the
            # ring moves MORE chunk-instances per layer (4 fwd + 6 bwd vs
            # 4 + 4) — all of them overlappable, but priced honestly
            add("qkv all-gather (fwd+bwd)", "all-gather",
                (3 if ring else 2) * L, shard, hops,
                (phase_ms.get("qkv_proj", 0) * 3 if ring else 0), fwd_note)
            add("wo reduce-scatter (fwd+bwd)", "reduce-scatter", 2 * L,
                shard, hops,
                (phase_ms.get("wo_proj", 0) * 3 if ring else 0), fwd_note)
            add("ffn all-gather+reduce-scatter (fwd+bwd)", "all-gather",
                (5 if ring else 4) * L, shard, hops,
                (phase_ms.get("ffn", 0) * 3 if ring else 0), fwd_note)
            add("lm_head all-gather (fwd+bwd)", "all-gather",
                3 if ring else 2, shard, hops,
                (phase_ms.get("lm_head", 0) * 3 if ring else 0), fwd_note)
            add("embed reduce-scatter (fwd+bwd)", "reduce-scatter", 2,
                shard, hops, 0.0, "bytes-bound producer; not ringed")
        else:
            add("per-sublayer all-reduce (fwd+bwd)", "all-reduce", 4 * L,
                ar, 2 * hops, 0.0,
                "no SP: monolithic psum per sublayer per direction")
            add("lm_head input all-reduce (bwd)", "all-reduce", 1, ar,
                2 * hops, 0.0, "copy_to transpose")
        # vocab-parallel CE scalar-field psums: two (b, t) f32 fields
        add("CE scalar psums (fwd+bwd)", "all-reduce", 2,
            2 * (tp - 1) / tp * batch * t * 4, 2 * hops, 0.0,
            "tiny; never worth overlapping")

    if dp > 1:
        P_count = cfg.num_params()
        wire_itemsize = {"bf16": 2, "bfloat16": 2,
                         "int8": 1}.get(dp_reduce_dtype, 4)
        shard_bytes = (dp - 1) / dp * P_count  # RS or AG wire, per element
        bucketed = dp_bucket_mb > 0
        bwd_budget = phase_ms.get("backward", 0.0)
        if zero_stage >= 3:
            # ZeRO-3: params gather per layer inside the scan (fwd, and
            # again in the remat'd backward replay); the gathers'
            # transposes ARE the grad reduce-scatter. All three rings are
            # f32 (params/cotangents), per-layer, overlappable.
            fwd_budget = sum(phase_ms.get(n, 0.0)
                             for n in ("qkv_proj", "wo_proj", "ffn"))
            add("ZeRO-3 param all-gather (fwd)", "all-gather", 1,
                shard_bytes * 4, dp - 1, fwd_budget,
                "per-layer ring inside the scan: hops hide under the "
                "layer's matmuls")
            add("ZeRO-3 param all-gather (bwd remat)", "all-gather", 1,
                shard_bytes * 4, dp - 1, bwd_budget,
                "the remat replay re-gathers each layer during the "
                "backward")
            add("ZeRO-3 grad reduce-scatter (bwd)", "reduce-scatter", 1,
                shard_bytes * 4, dp - 1, bwd_budget,
                "the gather's transpose: each rank receives only its "
                "dp-summed shard (f32 wire)")
        elif zero_stage == 2:
            note = (f"bucketed ({dp_bucket_mb:g} MiB, {dp_reduce_dtype} "
                    f"wire): half the all-reduce bytes — each rank "
                    f"receives only its 1/dp grad shard"
                    if bucketed else
                    f"{dp_reduce_dtype} wire; half the all-reduce bytes")
            add("DP grad reduce-scatter", "reduce-scatter", 1,
                shard_bytes * wire_itemsize, dp - 1,
                bwd_budget if bucketed else 0.0, note)
            add("ZeRO-2 param all-gather", "all-gather", 1,
                shard_bytes * 4, dp - 1, 0.0,
                "end-of-step gather of the freshly updated params (f32); "
                "--zero 3 gathers per-layer under compute instead")
        else:
            nbytes = 2 * shard_bytes * wire_itemsize
            budget = bwd_budget if bucketed else 0.0
            note = (f"bucketed ({dp_bucket_mb:g} MiB, {dp_reduce_dtype} "
                    f"wire): buckets overlap the remaining backward"
                    if bucketed else
                    "priced as one exposed all-reduce of the tree; the "
                    "default step gathers the layers' part leaf by leaf "
                    "under the backward (ops/overlap.exchange_grads), "
                    "which at dp 2 moves these bytes")
            add("DP grad reduce", "all-reduce", 1, nbytes, 2 * (dp - 1),
                budget, note)

    if cp > 1 and cp_prefill_chunk > 0:
        # serving-side cp ring (ISSUE 18): priced by cp_ring_attribution
        # and folded into the same record table so one report covers the
        # whole wire
        ring = cp_ring_attribution(
            cfg, batch, cp_prefill_chunk,
            max(cp_context, cp_prefill_chunk), cp, chip=chip,
            measured_allreduce_us=measured_allreduce_us)
        records.extend(ring["records"])

    total = sum(r["serialized_ms"] for r in records)
    hidden = sum(r["hidden_ms"] for r in records)
    return {"records": records,
            "comm_total_ms": total,
            "comm_hidden_ms": hidden,
            "comm_exposed_ms": total - hidden,
            "ici": {"bw_bytes_per_s": bw, "latency_s": lat,
                    "calibrated": bool(measured_allreduce_us)},
            "config": {"tp": tp, "sp": sp, "tp_overlap": tp_overlap,
                       "dp": dp, "dp_bucket_mb": dp_bucket_mb,
                       "dp_reduce_dtype": dp_reduce_dtype,
                       # the ZeRO stage the DP schedule was priced at
                       # (ISSUE 9): <=1 all-reduce, 2 RS+param-AG, 3
                       # per-layer AG + transpose RS
                       "zero_stage": zero_stage,
                       # the attributable wire dtypes (ISSUE 8): what the
                       # DP reduce and the tp ring payloads actually carry
                       "wire_dtype": (dp_reduce_dtype if zero_stage < 3
                                      else "f32"),
                       "tp_wire_dtype": ("int8" if tp_overlap == "ring_q"
                                         else "bf16"),
                       # serving-side cp ring inputs (ISSUE 18); 1/0 when
                       # the report prices a pure training step
                       "cp": cp, "cp_prefill_chunk": cp_prefill_chunk,
                       "cp_context": cp_context}}


def attribution(cfg, batch: int, t: int, remat: str = "dots", spd: int = 8,
                t_real: Optional[int] = None,
                block_q: Optional[int] = None,
                block_k: Optional[int] = None,
                measured: Optional[Dict[str, float]] = None,
                chip: str = "v5e", world: int = 1,
                family: str = "llama", tp: int = 1, sp: bool = False,
                tp_overlap: str = "off", dp: int = 1,
                dp_bucket_mb: float = 0.0, dp_reduce_dtype: str = "f32",
                measured_allreduce_us: Optional[float] = None,
                zero_stage: int = 0) -> Dict:
    """The full report structure: analytic phase table, fwd/bwd/adam bucket
    sums, the per-collective COMM attribution (serialized vs hidden vs
    exposed ICI ms under the configured overlap knobs), ranked waste
    suspects, and (when `measured` carries bench.py --breakdown
    components) analytic-vs-measured share columns.

    measured keys (all optional, ms): fwd_ms, fwdbwd_ms, step_ms,
    h2d_ms, and any 'step_ms_spdN'.
    """
    peak_flops, hbm_bw = chip_specs(chip)
    peak_flops *= world
    hbm_bw *= world
    phases = analytic_phases(cfg, batch, t, remat, t_real, block_q, block_k,
                             family)
    by = {p.name: p for p in phases}
    ms = {p.name: p.ms(peak_flops, hbm_bw) for p in phases}
    comm = comm_attribution(cfg, batch, t_real or t, tp=tp, sp=sp,
                            tp_overlap=tp_overlap, dp=dp,
                            dp_bucket_mb=dp_bucket_mb,
                            dp_reduce_dtype=dp_reduce_dtype, chip=chip,
                            family=family, remat=remat,
                            measured_allreduce_us=measured_allreduce_us,
                            phase_ms=ms, zero_stage=zero_stage)
    fwd_names = ["embed", "qkv_proj", "attention", "wo_proj", "ffn",
                 "norms_rope", "lm_head", "ce_loss"]
    buckets = {
        "fwd_ms": sum(ms[n] for n in fwd_names),
        "bwd_ms": ms["backward"] + ms["remat_recompute"],
        "adam_ms": ms["adam"],
    }
    analytic_step = sum(buckets.values())

    measured = measured or {}
    spd_keys = [k for k in measured if k.startswith("step_ms_spd")]
    measured_amortised = measured.get(spd_keys[0]) if spd_keys else None
    measured_step = measured.get("step_ms")
    dispatch_ms = (measured_step - measured_amortised
                   if measured_step and measured_amortised else None)
    # the yardstick every suspect's share is quoted against
    step_ms = measured_amortised or measured_step or analytic_step

    stats = flash_tile_stats(t, block_q, block_k, t_real, cfg.head_dim,
                             cfg.compute_dtype)
    attn_ms = ms["attention"] * (1 + 2.5)  # fwd + its share of backward
    waste = stats["waste_ratio"]
    suspects = [{
        "name": "attention tile/pad waste",
        "est_ms": attn_ms * (1 - 1 / waste),
        "note": (f"t={t_real or t}->t_pad {stats['t_pad']} @ "
                 f"{stats['block_q']}x{stats['block_k']} blocks: "
                 f"{waste:.2f}x causal-ideal MXU work (fix: bucketing/"
                 f"attn_t_real)"),
    }, {
        "name": "remat recompute",
        "est_ms": ms["remat_recompute"],
        "note": f"remat={remat} (fix: --remat auto picks false when "
                f"activations fit)",
    }, {
        "name": "dispatch overhead",
        "est_ms": dispatch_ms if dispatch_ms is not None else 0.0,
        "note": (f"measured step - spd-amortised step at spd={spd}"
                 if dispatch_ms is not None else
                 f"unmeasured (needs --breakdown on a backend); spd={spd} "
                 f"amortises host round-trips"),
    }, {
        "name": "lm_head+CE (vocab %d)" % cfg.vocab_size,
        "est_ms": ms["lm_head"] + ms["ce_loss"],
        "note": "unsharded head pass + f32 CE over the full vocab",
    }, {
        "name": "optimizer (bytes-bound)",
        "est_ms": ms["adam"],
        "note": "28 bytes/param HBM traffic",
    }]
    if comm["comm_total_ms"] > 0:
        cfg_note = comm["config"]
        suspects.append({
            "name": "exposed collective comm",
            "est_ms": comm["comm_exposed_ms"],
            "note": (f"{comm['comm_total_ms']:.2f} ms ICI total, "
                     f"{comm['comm_hidden_ms']:.2f} hidden under compute "
                     f"(tp_overlap={cfg_note['tp_overlap']}, "
                     f"dp_bucket={cfg_note['dp_bucket_mb']:g}MiB); fix: "
                     f"--tp_overlap ring / --dp_reduce_bucket_mb"),
        })
    if step_ms > analytic_step:
        # The most important row when a measurement exists: whatever the
        # itemised suspects do NOT cover. A large value here means the gap
        # is kernel efficiency / launch overhead / pipeline stalls — small
        # matmuls far off peak — not algorithmic waste; --breakdown's
        # fwd/bwd/adam splits localise which phase is off its roofline.
        gap = step_ms - analytic_step - (dispatch_ms or 0.0)
        if gap > 0:
            suspects.append({
                "name": "roofline gap (kernel efficiency)",
                "est_ms": gap,
                "note": ("measured minus analytic roofline: time the "
                         "itemised suspects cannot explain — small-matmul "
                         "MXU underutilisation and per-kernel overhead at "
                         f"d={cfg.attn_dim}"),
            })
    suspects.sort(key=lambda s: -s["est_ms"])
    for rank, s in enumerate(suspects, 1):
        s["rank"] = rank
        s["share"] = s["est_ms"] / step_ms if step_ms else 0.0

    return {"phases": [dataclasses.asdict(p) | {"ms_est": ms[p.name]}
                       for p in phases],
            "comm": comm,
            "buckets": buckets,
            "analytic_step_ms": analytic_step,
            "measured_step_ms": measured_step,
            "measured_amortised_ms": measured_amortised,
            "dispatch_ms": dispatch_ms,
            "step_ms_basis": step_ms,
            "tile_stats": stats,
            "suspects": suspects,
            "chip": chip, "world": world,
            "assumptions": (f"{chip} roofline ({peak_flops/1e12:.0f} "
                            f"TFLOP/s, {hbm_bw/1e9:.0f} GB/s) x {world} "
                            f"device(s); bf16 activations, f32 optimizer")}


def format_attribution(report: Dict,
                       measured: Optional[Dict[str, float]] = None) -> str:
    """Human table: ranked suspects + analytic-vs-measured bucket shares."""
    lines = ["step-time attribution (" + report["assumptions"] + ")"]
    basis = report["step_ms_basis"]
    src = ("measured" if report.get("measured_amortised_ms")
           or report.get("measured_step_ms") else "analytic")
    lines.append(f"  step basis: {basis:.1f} ms ({src})")

    measured = measured or {}
    mfwd = measured.get("fwd_ms")
    mbwd = (measured["fwdbwd_ms"] - measured["fwd_ms"]
            if "fwdbwd_ms" in measured and "fwd_ms" in measured else None)
    madam = (measured["step_ms"] - measured["fwdbwd_ms"]
             if "step_ms" in measured and "fwdbwd_ms" in measured else None)
    b = report["buckets"]
    lines.append("  bucket       analytic_ms   measured_ms")
    for name, analytic, meas in [("fwd", b["fwd_ms"], mfwd),
                                 ("bwd(+remat)", b["bwd_ms"], mbwd),
                                 ("adam", b["adam_ms"], madam)]:
        m = f"{meas:11.2f}" if meas is not None else "          —"
        lines.append(f"  {name:<12} {analytic:11.2f}   {m}")

    comm = report.get("comm") or {}
    if comm.get("comm_total_ms"):
        ici = comm["ici"]
        src = "calibrated" if ici["calibrated"] else "nominal"
        lines.append(
            f"  comm hidden / exposed: {comm['comm_hidden_ms']:.2f} / "
            f"{comm['comm_exposed_ms']:.2f} ms "
            f"(of {comm['comm_total_ms']:.2f} ms ICI, "
            f"{src} {ici['bw_bytes_per_s']/1e9:.0f} GB/s + "
            f"{ici['latency_s']*1e6:.1f}us/hop; "
            f"tp_overlap={comm['config']['tp_overlap']}, "
            f"dp_bucket={comm['config']['dp_bucket_mb']:g}MiB)")
        for r in comm["records"]:
            lines.append(
                f"    {r['name']:<38} x{r['count']:<3} "
                f"{r['serialized_ms']:6.2f} ms  hidden {r['hidden_ms']:6.2f}"
                f"  exposed {r['exposed_ms']:6.2f}  {r['note']}")

    lines.append("  rank  suspect                        est_ms  share  note")
    for s in report["suspects"]:
        lines.append(f"  {s['rank']:>4}  {s['name']:<29} {s['est_ms']:7.2f}"
                     f"  {s['share']*100:4.1f}%  {s['note']}")
    return "\n".join(lines)


# -- paged-decode roofline (ISSUE 14) ------------------------------------

def paged_decode_hbm_bytes(cfg, slots: int, max_pages: int, page_size: int,
                           kv_dtype=None, paged_attn: str = "gather",
                           decode_weight_dtype=None,
                           live_tokens: Optional[int] = None,
                           cp: int = 1) -> Dict:
    """Analytic HBM bytes ONE paged decode dispatch moves, itemised so the
    gather-vs-pallas A/B can assert the win instead of claiming it.

    The decode step at serving scale is bytes-bound; per dispatch it must
    move (a) the weights (int8 when `decode_weight_dtype='int8'` — the PR
    8 floor) and (b) the K/V context. How (b) is priced depends on the
    attend impl:

    * `'gather'` — `_gather_page_view` materializes the dense logical
      view per layer: the pool pages are READ (at their storage dtype),
      the dequantized compute-dtype view is WRITTEN to HBM, and the
      attend READS it back. The write+read of that view is
      `gather_copy_bytes` — pure overhead the kernel exists to kill —
      and the view spans the FULL (slots, max_pages*page_size) dense
      shape whatever the cursors say (the gather cannot skip).
    * `'pallas'` — the kernel streams pages pool->VMEM once;
      `gather_copy_bytes` is exactly 0, and the cursor-mask block skip
      bounds the pool read by the LIVE context (`live_tokens`, page-
      rounded) instead of the dense span.

    Returns {weight_bytes, kv_pool_read_bytes, gather_copy_bytes,
    total_bytes, paged_attn, cp}: `total = weight + pool_read +
    gather_copy`, so `total(gather) - total(pallas)` at equal live
    context is the gather-copy elimination plus the dead-page skip.

    `cp` > 1 (ISSUE 18) reports PER-CHIP bytes: each cp rank's page-table
    view spans only its max_pages/cp slab columns, so the dense span (and
    the live context a pallas read walks) divides by cp — the ~1/cp
    per-chip KV traffic the cp shard exists to buy. Weights replicate
    over cp, so `weight_bytes` does not divide."""
    if paged_attn not in ("gather", "pallas"):
        raise ValueError(f"paged_attn must be 'gather'/'pallas', got "
                         f"{paged_attn!r}")
    cp = max(1, cp)
    L, kvh, hd = cfg.num_layers, cfg.kv_heads, cfg.head_dim
    compute_itemsize = 2 if "bf16" in str(cfg.compute_dtype) or (
        "bfloat16" in str(cfg.compute_dtype)) else 4
    # stored bytes per token position (K+V, all layers): int8 pages carry
    # codes + one f32 scale per head-vector (kv_manager.kv_token_bytes)
    if kv_dtype in ("int8", "s8"):
        stored_per_tok = 2 * L * kvh * (hd + 4)
    else:
        stored_per_tok = 2 * L * kvh * hd * compute_itemsize
    view_per_tok = 2 * L * kvh * hd * compute_itemsize  # dequantized view
    dense_span = slots * (max_pages // cp) * page_size
    if paged_attn == "gather" or live_tokens is None:
        read_span = dense_span
    else:
        # block-granular skip: each rank's ~1/cp share of the live
        # context rounds up to whole local pages
        live_local = -(-int(live_tokens) // cp)
        read_span = min(dense_span,
                        -(-live_local // page_size) * page_size)
    weight_itemsize = 1 if decode_weight_dtype in ("int8", "s8") else (
        compute_itemsize)
    weight_bytes = cfg.num_params() * weight_itemsize
    pool_read = read_span * stored_per_tok
    gather_copy = 2 * dense_span * view_per_tok if paged_attn == "gather" \
        else 0
    return {
        "paged_attn": paged_attn,
        "cp": cp,
        "weight_bytes": int(weight_bytes),
        "kv_pool_read_bytes": int(pool_read),
        "gather_copy_bytes": int(gather_copy),
        "total_bytes": int(weight_bytes + pool_read + gather_copy),
    }


# -- checkable collective schedule (ISSUE 11) ----------------------------

def expected_collectives(tp: int = 1, sp: bool = False,
                         tp_overlap: str = "off", dp: int = 1,
                         dp_bucket_mb: float = 0.0,
                         dp_reduce_dtype: str = "f32",
                         zero_stage: int = 0,
                         serving: bool = False,
                         kind: Optional[str] = None,
                         cp: int = 1) -> Dict:
    """The schedule `comm_attribution` prices, as a CHECKABLE contract
    over a compiled program's collective inventory: (mesh axis, HLO op)
    pairs that must be present (`require`), may be present (`allow`), and
    must NOT be present (`forbid`), each with the wire dtypes the priced
    schedule carries. `analysis/contracts.check_collective_inventory`
    asserts a lowered program against this — so when a refactor changes
    the wire (a new collective, a dtype fallback, a gather that stopped
    ringing), the contract fails INSTEAD of the attribution silently
    mispricing it.

    The mapping from priced records to physical ops: monolithic psums are
    `all-reduce`; SP's boundary collectives are `all-gather` /
    `reduce-scatter`; every hand-rolled ring (ring/ring_q tp overlap, the
    quantized DP wire, ZeRO-3's per-layer gathers and their transposes)
    is `collective-permute`. Axes: 'dp'/'tp' are the mesh axes; 'all' is
    a reduction spanning the whole mesh (SP-replicated leaf grads, the
    loss mean); XLA-derived entries (the ZeRO-1/2 param all-gather, the
    all-to-all it may rewrite SP gathers into) are included and marked —
    they are part of the stage's schedule even though the pricing
    attributes them to other records.

    `dp_bucket_mb` (program configs pass through verbatim) changes
    collective COUNTS and overlap, and since PR 32 one entry: without it
    (and below ZeRO-2) the default step sums the layers' gradients by typed
    all-gathers over dp, which the hand-reduced builders do not have.
    """
    require: Dict[tuple, dict] = {}
    allow: Dict[tuple, str] = {}
    forbid: Dict[tuple, str] = {}
    wide = {"f32", "bf16", "f16"}

    if tp > 1:
        if sp:
            require[("tp", "all-gather")] = {
                "dtypes": wide,
                "note": "SP boundary gathers (qkv/ffn/lm_head records)"}
            require[("tp", "reduce-scatter")] = {
                "dtypes": wide,
                "note": "SP boundary scatters (wo/ffn/embed records)"}
            require[("tp", "all-reduce")] = {
                "dtypes": wide,
                "note": "CE scalar-field psums (+ small SP residuals)"}
            allow[("tp", "all-to-all")] = (
                "XLA rewrites some SP gather+slice patterns into "
                "all-to-all; same bytes, priced under the gather records")
        else:
            require[("tp", "all-reduce")] = {
                "dtypes": wide,
                "note": "monolithic per-sublayer psums (no-SP schedule)"}
            allow[("tp", "all-gather")] = "XLA-derived activation gathers"
            allow[("tp", "reduce-scatter")] = "XLA-derived scatters"
            allow[("tp", "all-to-all")] = "XLA-derived rewrites"
        if tp_overlap in ("ring", "ring_q"):
            require[("tp", "collective-permute")] = {
                "dtypes": ({"s8"} | wide if tp_overlap == "ring_q"
                           else wide),
                "note": f"the {tp_overlap} collective-matmul rings"}
        allow[("all", "all-reduce")] = (
            "whole-mesh sums: the loss mean and SP-replicated leaf grads "
            "(dp x tp groups)")

    if dp > 1 and not serving:
        int8 = dp_reduce_dtype in ("int8", "s8")
        if zero_stage >= 3:
            require[("dp", "collective-permute")] = {
                "dtypes": {"f32"},
                "note": "ZeRO-3 per-layer gather rings + their "
                        "reduce-scatter transposes (f32 by contract)"}
            forbid[("dp", "all-gather")] = (
                "a dp all-gather in a ZeRO-3 program is the whole-tree "
                "param materialisation the stage exists to eliminate")
            allow[("dp", "all-reduce")] = (
                "residual psums for leaves too small to shard")
        elif zero_stage == 2:
            if int8:
                require[("dp", "collective-permute")] = {
                    "dtypes": {"s8"},
                    "note": "quantized reduce-scatter ring (int8 codes; "
                            "f32 group scales ride below the sidecar "
                            "threshold)"}
            else:
                require[("dp", "reduce-scatter")] = {
                    "dtypes": wide,
                    "note": "stage-2 bucketed grad reduce-scatter (half "
                            "the all-reduce bytes)"}
            require[("dp", "all-gather")] = {
                "dtypes": {"f32"},
                "note": "the end-of-step param all-gather XLA inserts "
                        "for the replicated out_sharding (priced as "
                        "'ZeRO-2 param all-gather')"}
            allow[("dp", "all-reduce")] = (
                "residual psums for unscatterable leaves")
        else:
            if int8:
                require[("dp", "collective-permute")] = {
                    "dtypes": {"s8"},
                    "note": "quantized DP all-reduce ring (EQuARX "
                            "schedule: int8 codes, f32 sidecar scales)"}
                allow[("all", "collective-permute")] = (
                    "the quantized ring over combined (dp x tp) groups "
                    "for SP-replicated leaves")
                allow[("tp", "collective-permute")] = (
                    "the quantized ring's tp leg for SP-replicated "
                    "leaves (their grads reduce over dp AND tp)")
                allow[("dp", "all-reduce")] = (
                    "small-leaf / scalar residuals")
            else:
                require[("dp", "all-reduce")] = {
                    "dtypes": wide,
                    "note": "the DP grad reduce (bucketed or whole-tree)"}
                if not dp_bucket_mb and zero_stage <= 1:
                    require[("dp", "all-gather")] = {
                        "dtypes": wide,
                        "note": "the default step's exchange of the "
                                "layers' weight cotangents (ops/overlap."
                                "exchange_grads): a typed gather a leaf, "
                                "at dp 2 the bytes of the all-reduce that "
                                "'DP grad reduce' prices; the non-layer "
                                "leaves stay psums"}
            if zero_stage == 1:
                require[("dp", "all-gather")] = {
                    "dtypes": {"f32"},
                    "note": "stage-1 param gather from the dp-sharded "
                            "moment update (XLA-derived schedule)"}
        allow[("all", "all-reduce")] = (
            "whole-mesh sums (loss mean, SP-replicated leaf grads)")

    if serving and tp > 1:
        # inference programs: row-parallel psums on tp; gathers allowed
        # (vocab-parallel logits, page views); nothing on dp. All
        # serving kinds (decode / prefill_chunk / spec_verify) share one
        # schedule for BOTH paged-attention impls: the Pallas kernel
        # (ISSUE 14) changes only local HBM traffic, never the wire —
        # graftcheck's collective-inventory contract asserts the pallas
        # programs against this same schedule, so a kernel revision that
        # grew a collective would fail there. When the wires genuinely
        # diverge some day, differentiate on `kind` HERE so the contract
        # tightens with the implementation.
        require[("tp", "all-reduce")] = {
            "dtypes": wide | {"s32", "u32"},
            "note": f"row-parallel output psums + fused-sampler argmax "
                    f"reductions ({kind or 'serving'} dispatch)"}
        allow[("tp", "all-gather")] = "vocab/head gathers"
        allow[("tp", "reduce-scatter")] = "XLA-derived scatters"
        allow[("tp", "all-to-all")] = "XLA-derived rewrites"
        allow[("tp", "collective-permute")] = "XLA-derived rotations"

    if serving and cp > 1:
        # cp-sharded paged serving (ISSUE 18): decode combines the
        # per-rank partial (out, lse) with small cp psums; chunked
        # prefill (and its speculative-verify twin) ADDITIONALLY rings
        # the query carry around cp before one reassembling psum. Page
        # DATA never crosses the wire — the byte-threshold canary
        # (analysis/contracts.check_cp_no_page_gather) forbids
        # pool-sized cp gathers the way the ZeRO-3 rule forbids
        # whole-tree dp gathers; this inventory only admits small
        # XLA-derived gathers (psum rewrites, sampler plumbing).
        require[("cp", "all-reduce")] = {
            "dtypes": wide,
            "note": "the (out, lse) combine psums (decode) / the chunk "
                    "reassembly psum (prefill ring)"}
        if kind in ("prefill_chunk", "spec_verify"):
            require[("cp", "collective-permute")] = {
                "dtypes": wide | {"s32", "u32"},
                "note": "the prefill query ring: per-hop rotation of the "
                        "(qh, qph, o, lse, off) carry around cp"}
        else:
            allow[("cp", "collective-permute")] = (
                "XLA-derived rotations (decode itself combines with "
                "psums only)")
        allow[("cp", "all-gather")] = (
            "small XLA-derived gathers (psum rewrites / sampler "
            "plumbing); pool-sized page gathers are the byte-threshold "
            "canary's job, not this inventory's")
        allow[("cp", "reduce-scatter")] = "XLA-derived scatters"
        allow[("cp", "all-to-all")] = "XLA-derived rewrites"

    return {"require": require, "allow": allow, "forbid": forbid}


# -- cross-rank skew attribution (ISSUE 10) ------------------------------

DCN_BANDWIDTH = 25e9   # bytes/s per host NIC, the cross-host default


def kv_transfer_attribution(pages: int, page_bytes_each: int,
                            chip: str = "v5e", link: str = "ici",
                            measured_ms: Optional[float] = None) -> Dict:
    """Price one disaggregated prefill->decode KV page handoff (ISSUE
    19) in the comm-attribution record shape: bytes on the wire are
    EXACTLY pages x page_bytes (the transfer ships whole pages —
    bench.py --fleet asserts its measured per-request bytes against
    this), serialized over the chosen link's alpha-beta terms. `link`:
    'ici' (same-pod reshard, ICI_SPECS bandwidth) or 'dcn' (cross-host,
    the DCN_BANDWIDTH NIC default). `measured_ms` (the transfer span
    from obs.reqtrace's handoff gap, or serving.transfer's in-process
    clock) rides along so reports show expected vs observed; the wire
    is never overlapped with compute — a handoff serializes the
    request's path — so exposed == serialized."""
    if pages < 0 or page_bytes_each < 0:
        raise ValueError(f"pages/page_bytes must be >= 0, got "
                         f"{pages}/{page_bytes_each}")
    if link not in ("ici", "dcn"):
        raise ValueError(f"link must be 'ici' or 'dcn', got {link!r}")
    ici_bw, lat = chip_specs(chip, ICI_SPECS)
    bw = ici_bw if link == "ici" else DCN_BANDWIDTH
    nbytes = pages * page_bytes_each
    ms = (nbytes / bw + lat) * 1e3
    rec = {
        "name": "kv_page_transfer", "kind": "handoff", "count": 1,
        "bytes_each": nbytes, "serialized_ms": round(ms, 6),
        "hidden_ms": 0.0, "exposed_ms": round(ms, 6),
        "note": f"{pages} pages x {page_bytes_each} B over {link} "
                f"({chip}): the prefill->decode page stream",
        "pages": pages, "page_bytes": page_bytes_each, "link": link,
    }
    if measured_ms is not None:
        rec["measured_ms"] = round(float(measured_ms), 3)
    return rec


def rank_skew(records: List[Dict], tol: float = 0.20) -> Optional[Dict]:
    """Rank cross-rank straggler suspects from per-process phase timings.

    `records` are `rank_phase_stats` events (one per process per run:
    obs/observer.py emits them at close from the goodput buckets, and the
    proc-tagged metrics*.jsonl filenames keep them separable). The failure
    mode this catches is the one ZeRO-3's per-layer gathers and the ring
    overlap are most sensitive to: every collective runs at the pace of
    the SLOWEST rank, so one rank stuck in `data_wait` (a slow host input
    pipeline) or `h2d` (a sick PCIe link) taxes the whole mesh — and an
    aggregate goodput number cannot say WHICH rank.

    Returns None with < 2 records (nothing to compare). Otherwise:
      * per-phase mean/max across ranks and `skew` = max/mean - 1,
      * `suspects`: (process, phase) pairs whose time exceeds the phase
        mean by more than `tol`, ranked by absolute excess seconds (the
        wall-clock the mesh pays for that rank), and
      * `persistent`: processes that are the worst rank in >= 2 phases
        with skew past `tol` — a rank slow across phases is a sick HOST,
        not a noisy measurement.
    """
    by_proc = {}
    for r in records:
        by_proc[int(r["process"])] = {k: float(v)
                                      for k, v in r["phases_s"].items()}
    if len(by_proc) < 2:
        # DISTINCT ranks, not records: two single-process runs in one
        # dir (a re-run staged script) must not render a fake one-rank
        # "cross-rank" table with every skew at 0%
        return None
    phases = sorted({p for ph in by_proc.values() for p in ph})
    out_phases, suspects, worst_count = {}, [], {}
    for phase in phases:
        vals = {proc: ph.get(phase, 0.0) for proc, ph in by_proc.items()}
        mean = sum(vals.values()) / len(vals)
        max_proc = max(vals, key=lambda p: vals[p])
        mx = vals[max_proc]
        skew = (mx / mean - 1.0) if mean > 0 else 0.0
        out_phases[phase] = {"mean_s": round(mean, 6),
                             "max_s": round(mx, 6),
                             "max_process": max_proc,
                             "skew": round(skew, 4)}
        if mean <= 0:
            continue
        if skew > tol:
            worst_count[max_proc] = worst_count.get(max_proc, 0) + 1
        for proc, v in vals.items():
            if v > mean * (1.0 + tol):
                suspects.append({"process": proc, "phase": phase,
                                 "excess_s": round(v - mean, 6),
                                 "ratio": round(v / mean, 4)})
    suspects.sort(key=lambda s: -s["excess_s"])
    return {
        "ranks": len(by_proc),
        "tol": tol,
        "phases": out_phases,
        "suspects": suspects,
        "persistent": sorted(p for p, c in worst_count.items() if c >= 2),
    }
