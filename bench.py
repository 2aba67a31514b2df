"""Benchmark harness. Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Default run (what the driver executes): training throughput of the
reference-scale GPT (45M params, `/root/reference/constants.py:9-17`) at the
reference's experiment scale (batch 32, seqlen 1000, bf16 — `train.py:41`,
`recipe.sh`) on the available device(s): TP over all local chips (1 chip
under the bench driver).

Flags cover the other BASELINE.md configs:
    --model {45m,gpt2-124m,gpt2-355m,tiny,45m-moe8}   model preset
    --family {llama,gpt2}          model family at the preset shape
    --remat {true,dots,false}      rematerialisation policy
                                   (default false; dots for gpt2-355m)
    --batch N --seqlen N           override the experiment shape
    --dp N --tp N                  mesh axes (world = dp*tp must match chips)
    --steps_per_dispatch N         optimizer steps per device dispatch
                                   (train.py's scanned megabatch mode)
    --decode                       KV-cache generation throughput instead of
                                   training (vs_baseline = per-stream speedup
                                   over reference-semantics recompute)
    --breakdown                    step-time accounting (H2D/fwd/bwd/adam/
                                   dispatch components)

vs_baseline: the reference publishes no numbers (BASELINE.md), so the
driver-assigned north star is used — MFU >= 30% on TPU. vs_baseline is
measured_MFU / 0.30 (1.0 == target met).

Extra diagnostics (tp all-reduce p50 latency, MFU, memory) go to stderr.
"""

import argparse
import dataclasses
import json
import os
import sys
import time

import jax
import jax.numpy as jnp

from distributed_pytorch_from_scratch_tpu import (MeshConfig, Transformer,
                                                  make_mesh)
from distributed_pytorch_from_scratch_tpu import models
from distributed_pytorch_from_scratch_tpu.config import (IGNORE_INDEX,
                                                         REMAT_CHOICES,
                                                         OptimizerConfig,
                                                         model_preset)
from distributed_pytorch_from_scratch_tpu.obs.runindex import run_stamp
from distributed_pytorch_from_scratch_tpu.runtime.compile_cache import (
    enable_compile_cache)
from distributed_pytorch_from_scratch_tpu.training.optim import init_adam_state
from distributed_pytorch_from_scratch_tpu.training.metrics import (
    ProfilerTrace, allreduce_p50_us, chip_peak_flops, device_memory_gib,
    model_flops_per_step)
from distributed_pytorch_from_scratch_tpu.training.train_step import (
    build_train_step, build_train_step_multi)

def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--model", default="45m",
                   choices=["45m", "gpt2-124m", "gpt2-355m", "tiny", "45m-moe8"])
    p.add_argument("--family", default="llama", choices=list(models.FAMILIES),
                   help="model family; 'gpt2' benches GPT2Transformer "
                        "(LayerNorm/GELU/learned positions/tied head) at "
                        "the chosen preset shape")
    # Default "false": no recompute at all — the fastest config whenever
    # the activations fit; the 45m/gpt2-124m bench shapes fit a 16G chip
    # without remat, gpt2-355m needs "dots" (resolved post-parse). A config
    # that does not fit is a failed run, not a quieter config. "auto" picks
    # the fastest policy whose activation-memory ESTIMATE fits the chip
    # (training/memory.select_remat).
    p.add_argument("--remat", default=None,
                   choices=sorted(REMAT_CHOICES) + ["auto"],
                   help="default: false (dots for gpt2-355m); 'auto' = "
                        "fastest policy the memory estimate says fits")
    p.add_argument("--batch", type=int, default=None,
                   help="default: 32 (reference train.py:41), 8 for "
                        "gpt2-124m, 4 for gpt2-355m")
    p.add_argument("--seqlen", type=int, default=None,
                   help="default: model maxlen (1000 for 45m)")
    p.add_argument("--dp", type=int, default=1)
    p.add_argument("--tp", type=int, default=0,
                   help="0 = all remaining local chips")
    p.add_argument("--sequence_parallel", action="store_true",
                   help="Megatron SP over tp (reduce-scatter/all-gather "
                        "instead of all-reduce); needed for --tp_overlap")
    p.add_argument("--tp_overlap", default="off",
                   choices=["off", "ring", "ring_q"],
                   help="'ring' = ring-decomposed collective matmuls for "
                        "the SP tp collectives (ops/overlap.py); 'ring_q' "
                        "= the same rings with int8 ppermute payloads "
                        "(half the bf16 chunk bytes; bounds pinned in "
                        "tests/test_quant.py); the breakdown/attribution "
                        "then reports the comm the ring hides. Requires "
                        "--sequence_parallel")
    p.add_argument("--zero", type=int, choices=[0, 1, 2, 3], default=0,
                   help="ZeRO stage over dp (training/zero.py): 1 shards "
                        "the Adam moments, 2 also reduce-scatters the "
                        "grads (half the DP wire bytes; implies the "
                        "bucketed reducer) with one param all-gather per "
                        "step, 3 also shards the params with per-layer "
                        "gather-on-demand (peak param HBM full/dp + one "
                        "layer). The record carries zero_stage + the "
                        "measured param_bytes_per_device. Stages 2/3: "
                        "dense presets, SP whenever tp > 1; stage 3 needs "
                        "remat (defaults to dots) and an f32 wire")
    p.add_argument("--dp_reduce_bucket_mb", type=float, default=0.0,
                   help="bucketed DP grad reduction: one psum per <= N-MiB "
                        "bucket (overlappable with the backward) instead "
                        "of the end-of-step whole-tree blob; 0 = off")
    p.add_argument("--dp_reduce_dtype", default="f32",
                   choices=["f32", "bf16", "int8"],
                   help="wire dtype for the bucketed DP reduce (bf16 "
                        "halves the reduction bytes; int8 quarters them "
                        "via the EQuARX-style block-scaled ring, "
                        "ops/overlap.quantized_allreduce; f32 master "
                        "accumulate untouched either way)")
    p.add_argument("--iters", type=int, default=8)
    # The product training mode this measures: train.py --steps_per_dispatch
    # runs N optimizer steps per device dispatch (lax.scan over a stacked
    # megabatch, training/train_step.py:build_train_step_multi), amortising
    # the host->device round-trip N-fold. 1 = the reference-style
    # one-dispatch-per-step loop.
    p.add_argument("--steps_per_dispatch", type=int, default=8)
    p.add_argument("--breakdown", action="store_true",
                   help="step-time accounting instead of a throughput "
                        "number: separately time H2D, forward, "
                        "forward+backward, the full optimizer step, and "
                        "the scanned multi-step program, report the "
                        "derived bwd/adam/dispatch components, and emit "
                        "the ranked roofline ATTRIBUTION table (analytic "
                        "vs measured phase shares — answers 'where do the "
                        "step milliseconds go')")
    p.add_argument("--analytic", action="store_true",
                   help="--breakdown without any device timing: the pure "
                        "roofline attribution report (obs/attribution), "
                        "runnable on CPU at the flagship 45m shape in "
                        "milliseconds")
    p.add_argument("--seq_bucket", type=int, default=0,
                   help="pad-aware sequence bucketing: round the sequence "
                        "up to a multiple of N (cleanly tiled matmuls), "
                        "tell attention the REAL length (attn_t_real — "
                        "kernels skip the pad tiles) and mask the pad "
                        "targets in the CE; tokens/sec and MFU count REAL "
                        "tokens only. 0 = off. The 45m fast-path line uses "
                        "128 (t=1000 -> 1024)")
    p.add_argument("--introspect", action="store_true",
                   help="AOT-compile the benched program once more and "
                        "print its cost analysis to stderr (XLA FLOPs vs "
                        "the hand-rolled estimate, bytes accessed, peak "
                        "HBM, per-collective comm bytes — obs/introspect); "
                        "adds one compile to the bench run")
    p.add_argument("--decode", action="store_true",
                   help="bench GENERATION throughput instead of training: "
                        "KV-cache batched decode (models/decode.py) vs the "
                        "reference-semantics full-recompute loop "
                        "(/root/reference/test.py:141-161 recomputes the "
                        "whole prefix per token); vs_baseline = the speedup")
    p.add_argument("--prompt_len", type=int, default=64,
                   help="--decode/--serving: tokens per prompt (serving "
                        "draws lengths in [prompt_len/2, prompt_len])")
    p.add_argument("--gen_tokens", type=int, default=128,
                   help="--decode/--serving: generation budget per prompt")
    p.add_argument("--serving", action="store_true",
                   help="bench CONTINUOUS-BATCHING serving throughput "
                        "(serving/engine.py): a burst of --serve_requests "
                        "mixed-length requests through the slot-based "
                        "engine vs the same set decoded by one-shot "
                        "GreedyDecoder batches (vs_baseline = the "
                        "continuous-batching speedup); also reports "
                        "TTFT/TPOT p50/p95 and slot occupancy")
    p.add_argument("--slots", type=int, default=8,
                   help="--serving: KV-pool slots (= the one-shot "
                        "baseline's batch size, so the comparison is "
                        "concurrency-controlled; also fixes the paged "
                        "engine's page budget: slots x buf_len tokens)")
    p.add_argument("--serve_requests", type=int, default=24,
                   help="--serving: requests in the burst")
    p.add_argument("--page_size", type=int, default=64,
                   help="--serving: paged-engine KV page size (tokens)")
    p.add_argument("--prefill_chunk", type=int, default=128,
                   help="--serving: paged-engine prefill chunk (positions "
                        "per dispatch interleaved into the decode loop)")
    p.add_argument("--kv_dtype", default="native",
                   choices=["native", "int8"],
                   help="--serving: paged/speculative KV-page storage "
                        "dtype. 'int8' stores block-scaled codes "
                        "(kv_manager.PagedKVPool) and the equal-HBM "
                        "budget math grants the pool ~2x the pages at the "
                        "same bytes — the record carries kv_dtype + the "
                        "granted capacity ratio")
    p.add_argument("--decode_weight_dtype", default="native",
                   choices=["native", "int8"],
                   help="--serving: weight-only int8 decode weights for "
                        "the paged/speculative arms (dequant-on-use "
                        "inside the decode/prefill programs; "
                        "ops/quant.quantize_decode_params)")
    p.add_argument("--paged_attn", default="gather",
                   choices=["gather", "pallas"],
                   help="--serving: the paged arms' attend impl. "
                        "'pallas' walks the page table in place "
                        "(ops/pallas/paged_attention.py) AND adds a "
                        "gather-impl arm at the SAME page-byte budget, "
                        "so the record carries the A/B "
                        "(pallas_vs_gather, both TTFT/TPOT p95) plus "
                        "attribution's decode HBM bytes/step before and "
                        "after the gather copy. Non-TPU backends fall "
                        "back to gather with a one-time warning")
    p.add_argument("--cp", type=int, default=1,
                   help="--serving: context-parallel shards for the PAGED "
                        "arm (ISSUE 18). The KV page pool shards over the "
                        "'cp' mesh axis (per-chip KV bytes ~1/cp at equal "
                        "context), chunked prefill rings the query chunk "
                        "around cp, decode combines per-rank (out, lse) "
                        "partials; greedy output token-identical to cp=1. "
                        "cp > 1 adds a cp=1 arm at the SAME page-byte "
                        "budget (record: cp_vs_cp1). The speculative "
                        "drafter stays cp=1")
    p.add_argument("--trace_requests", action="store_true",
                   help="--serving: per-request span timelines on the "
                        "paged arm (obs/reqtrace.py) — request_trace "
                        "events + the k-worst exemplar timelines land in "
                        "--obs_dir so an SLO-tail number is explainable, "
                        "not just reported")
    p.add_argument("--flight_records", action="store_true",
                   help="--serving: anomaly flight recorder on the paged "
                        "arm (obs/flight.py) — PoolExhausted preemptions "
                        "dump flightdump_*.json to --obs_dir")
    p.add_argument("--obs_dir", default="bench_obs",
                   help="--trace_requests/--flight_records/--metrics_port "
                        "output dir (metrics.jsonl + trace + flight dumps)")
    p.add_argument("--metrics_port", type=int, default=None,
                   help="--serving: live telemetry exporter on the paged "
                        "arm (obs/telemetry.py) — gauges/counters at "
                        "http://127.0.0.1:PORT/metrics.json and /metrics; "
                        "0 = ephemeral; telemetry_snapshot events mirror "
                        "into --obs_dir")
    p.add_argument("--rollup_interval", type=float, default=1.0,
                   help="--metrics_port: seconds between "
                        "telemetry_snapshot events")
    p.add_argument("--profile_on_anomaly", type=int, default=0,
                   metavar="STEPS",
                   help="--serving: arm a bounded jax.profiler window of "
                        "N decode steps when a flight dump fires, cross-"
                        "linked from the dump; needs --flight_records")
    p.add_argument("--profile_every", type=int, default=0, metavar="N",
                   help="--serving: duty-cycled MEASURED attribution on "
                        "the paged arm (training/metrics."
                        "DutyCycleProfiler): every N decode steps capture "
                        "a --profile_window-step jax.profiler window, "
                        "parse it (obs/profparse), land "
                        "profile_attribution events in --obs_dir and "
                        "carry measured_vs_analytic in the record; 0 = "
                        "off")
    p.add_argument("--profile_window", type=int, default=4, metavar="W",
                   help="--profile_every: decode steps per capture "
                        "window (must be <= N)")
    p.add_argument("--profile_budget_mb", type=float, default=64.0,
                   help="--profile_every: total on-disk capture budget; "
                        "exhaustion stops sampling between windows, "
                        "never mid-window")
    p.add_argument("--control", choices=["off", "advise"], default="off",
                   help="--serving + --profile_every: run the obs v5 "
                        "drift advisor in ADVISE mode over the paged "
                        "arm's duty reconciles — tuning_decision ledger "
                        "events land in --obs_dir and the record carries "
                        "the summary. 'act' is deliberately absent: a "
                        "bench record must measure ONE fixed config, not "
                        "a config that moved mid-measurement")
    p.add_argument("--capture_profile", action="store_true",
                   help="--breakdown: capture the scanned multi-step "
                        "program under a jax.profiler window "
                        "(training/metrics.ProfilerTrace into --obs_dir), "
                        "parse it (obs/profparse) and attach the "
                        "measured-vs-analytic reconcile to the record "
                        "(measured_vs_analytic) — the analytic roofline "
                        "checked against the device timeline, not just "
                        "asserted")
    p.add_argument("--speculate", type=int, default=0, metavar="K",
                   help="--serving: add a SPECULATIVE arm to the A/B — a "
                        "'tiny'-preset drafter proposes K tokens per round, "
                        "the target verifies them in one dispatch "
                        "(serving/speculative.py). Equal-HBM: the drafter's "
                        "pages are paid for by SHRINKING the target page "
                        "pool below the slot engine's budget. The record "
                        "gains vs_paged (speedup over the non-speculative "
                        "paged arm) + accepted_tokens_per_dispatch")
    p.add_argument("--fleet", action="store_true",
                   help="bench the SERVING FLEET (ISSUE 19): "
                        "--fleet_replicas PagedEngine replicas behind the "
                        "prefix-cache-aware FleetRouter vs ONE engine at "
                        "equal total HBM (slots x replicas), PLUS a "
                        "disaggregated prefill/decode arm (KV pages "
                        "streamed over serving/transfer.py) vs the same "
                        "engine colocated. The record carries "
                        "fleet_tokens_per_sec, per-class fleet SLO "
                        "attainment, disagg-vs-colocated TTFT/TPOT p95, "
                        "and the transfer wire (pages, bytes = pages x "
                        "page_bytes asserted, transfer_ms p95, priced by "
                        "obs/attribution.kv_transfer_attribution)")
    p.add_argument("--fleet_replicas", type=int, default=2,
                   help="--fleet: replicas behind the router (the equal-"
                        "HBM baseline gets slots x this)")
    p.add_argument("--reshard", action="store_true",
                   help="bench the RESHARD pass (ISSUE 20): save one "
                        "stamped checkpoint at the CURRENT layout (dp x "
                        "tp at --zero), reshard it file->file onto "
                        "--reshard_tp, validate the output shard set, and "
                        "record reshard_ms / reshard_bytes_moved / plan "
                        "op counts / peak host bytes (bounded by the "
                        "largest single leaf, asserted). Two identical "
                        "lines gate each other via check_bench_regression")
    p.add_argument("--reshard_tp", type=int, default=0,
                   help="--reshard: target tp width (default "
                        "max(1, tp // 2))")
    args = p.parse_args(argv)
    if args.serving and (args.decode or args.breakdown):
        p.error("--serving excludes --decode/--breakdown")
    if args.fleet and (args.serving or args.decode or args.breakdown):
        p.error("--fleet excludes --serving/--decode/--breakdown (it IS "
                "a serving bench — the fleet-level one)")
    if args.fleet and args.fleet_replicas < 1:
        p.error(f"--fleet_replicas must be >= 1, got "
                f"{args.fleet_replicas}")
    if args.fleet and args.cp > 1:
        p.error("--fleet composes with cp inside each replica via "
                "--serving --cp; the fleet A/B keeps replicas cp=1")
    if args.reshard and (args.serving or args.decode or args.breakdown
                         or args.fleet):
        p.error("--reshard excludes --serving/--decode/--breakdown/"
                "--fleet (it benches the checkpoint redistribution pass, "
                "not a model program)")
    if args.reshard and args.cp > 1:
        p.error("--reshard keeps cp=1 (checkpoint layouts stamp dp/tp; "
                "cp is a serving-time axis)")
    if args.reshard_tp and not args.reshard:
        p.error("--reshard_tp is a --reshard knob")
    if args.reshard and args.reshard_tp < 0:
        p.error(f"--reshard_tp must be >= 0 (0 = tp // 2), got "
                f"{args.reshard_tp}")
    if args.speculate and not args.serving:
        p.error("--speculate is a --serving mode")
    if args.kv_dtype != "native" and not (args.serving or args.fleet):
        p.error("--kv_dtype is a --serving/--fleet knob (the paged KV "
                "pool)")
    if args.paged_attn != "gather" and not args.serving:
        p.error("--paged_attn is a --serving knob (the paged engine's "
                "attend impl; training has no page table)")
    if (args.trace_requests or args.flight_records) and not args.serving:
        p.error("--trace_requests/--flight_records are --serving knobs "
                "(training runs get them from train.py's observer)")
    if args.metrics_port is not None and not args.serving:
        p.error("--metrics_port is a --serving knob here (training runs "
                "get the exporter from train.py)")
    if args.metrics_port is not None:
        if args.metrics_port < 0:
            p.error(f"--metrics_port must be >= 0 (0 = ephemeral), got "
                    f"{args.metrics_port}")
        if args.rollup_interval <= 0:
            p.error("--rollup_interval must be > 0 (seconds between "
                    "telemetry_snapshot events)")
    if args.profile_on_anomaly and not args.flight_records:
        p.error("--profile_on_anomaly arms on flight-dump triggers; add "
                "--flight_records (and --serving)")
    if args.profile_every:
        if not args.serving:
            p.error("--profile_every is a --serving knob here (training "
                    "runs get the duty profiler from train.py)")
        if args.profile_on_anomaly:
            p.error("--profile_every excludes --profile_on_anomaly (both "
                    "drive the one-capture-at-a-time device profiler)")
        if not args.obs_dir:
            p.error("--profile_every needs a metrics dir: captures and "
                    "the parsed profile_attribution events land in "
                    "--obs_dir (point it somewhere writable)")
        if not 1 <= args.profile_window <= args.profile_every:
            p.error(f"--profile_window must be in [1, --profile_every], "
                    f"got window {args.profile_window} with every "
                    f"{args.profile_every}")
        if args.profile_budget_mb <= 0:
            p.error(f"--profile_budget_mb must be > 0, got "
                    f"{args.profile_budget_mb}")
    if args.control != "off" and not args.profile_every:
        p.error("--control advise rides the duty profiler's measured "
                "reconciles; add --profile_every N (a --serving knob)")
    if args.capture_profile:
        if not args.breakdown:
            p.error("--capture_profile is a --breakdown knob (the "
                    "serving arms use --profile_every)")
        if args.analytic:
            p.error("--capture_profile needs device timing; drop "
                    "--analytic (the analytic report is what the capture "
                    "is reconciled AGAINST)")
        if not args.obs_dir:
            p.error("--capture_profile needs --obs_dir (the capture "
                    "lands there)")
    if args.decode_weight_dtype != "native" and not args.serving:
        p.error("--decode_weight_dtype is a --serving knob")
    if args.cp < 1:
        p.error(f"--cp must be >= 1, got {args.cp}")
    if args.cp > 1 and not args.serving:
        p.error("--cp is a --serving knob (only the paged engine's KV "
                "pool shards over 'cp'; training context parallel is "
                "train.py's --cp_size)")
    if args.remat is None:
        # zero 3 pairs with remat: without it the gathered layer weights
        # would be saved as backward residuals (full replica again)
        args.remat = ("dots" if args.model == "gpt2-355m" or args.zero == 3
                      else "false")
    if args.zero == 3 and args.remat == "false":
        p.error("--zero 3 needs remat (dots/true/auto): without remat, "
                "autodiff saves every layer's gathered weights as "
                "backward residuals, recreating the full param replica")
    if args.zero == 3 and args.dp_reduce_dtype != "f32":
        p.error(f"--dp_reduce_dtype {args.dp_reduce_dtype} with --zero 3: "
                f"the ZeRO-3 grad reduce-scatter rides the parameter "
                f"all-gather's transpose (f32 ppermute ring) — the "
                f"compressed wire applies to --zero 2")
    if args.zero >= 2 and args.model.endswith("-moe8"):
        p.error(f"--zero {args.zero} does not compose with MoE presets "
                f"(expert grads are ep-sharded, not batch-replicated); "
                f"--zero 1 shards MoE moments fine")
    if args.zero and (args.serving or args.decode or args.fleet):
        p.error("--zero is a training knob; it does not apply to "
                "--serving/--decode/--fleet (any stage would be silently "
                "ignored)")
    if args.analytic and not args.breakdown:
        p.error("--analytic is a --breakdown mode")
    if args.analytic and args.remat == "auto":
        p.error("--analytic needs an explicit --remat (auto resolves "
                "against the attached chip's memory; --analytic runs "
                "without a backend)")
    if args.tp_overlap in ("ring", "ring_q") and not args.sequence_parallel:
        p.error(f"--tp_overlap {args.tp_overlap} requires "
                f"--sequence_parallel (the ring decomposes the SP "
                f"all-gather/reduce-scatter pair)")
    if (args.dp_reduce_dtype != "f32" and not args.dp_reduce_bucket_mb
            and args.zero != 2):
        p.error(f"--dp_reduce_dtype {args.dp_reduce_dtype} needs "
                f"--dp_reduce_bucket_mb > 0 (the compressed wire rides "
                f"the bucketed reducer; --zero 2 implies it)")
    if args.dp_reduce_bucket_mb and args.model.endswith("-moe8"):
        p.error("--dp_reduce_bucket_mb does not compose with MoE presets "
                "(expert grads are ep-sharded, not batch-replicated)")
    if args.seq_bucket and (args.seq_bucket < 1 or args.seq_bucket % 128):
        p.error(f"--seq_bucket must be a positive multiple of 128 (the TPU "
                f"lane width), got {args.seq_bucket}")
    return args


def build_model(args, cfg, tp: int, remat: str = None, attn_impl: str = "auto",
                attn_t_real: int = None, cp: int = 1):
    """The one keyword list shared by the training/decode/breakdown paths
    (three copies had already diverged once)."""
    kw = dict(tp_size=tp, cp_size=cp, attn_impl=attn_impl,
              attn_t_real=attn_t_real,
              sequence_parallel=args.sequence_parallel,
              tp_overlap=args.tp_overlap)
    if remat is not None:
        # a key of REMAT_CHOICES, or the ladder rung `--remat auto` chose
        kw["remat"] = REMAT_CHOICES.get(remat, remat)
    return models.build_model(args.family, cfg, **kw)


def dp_reduce_kwargs(args):
    """Step-builder kwargs for the bucketed DP grad reduce + ZeRO flags."""
    wire = {"bf16": jnp.bfloat16, "int8": jnp.int8}.get(
        args.dp_reduce_dtype)
    return dict(dp_reduce_bucket_mb=args.dp_reduce_bucket_mb,
                dp_reduce_dtype=wire, zero=args.zero)


def zero_state_put(args, model, mesh, params):
    """(params_on_device, moment_shardings | None) at the --zero stage's
    RESTING layouts (training/zero.py): stage 3 puts params dp-sharded
    (the forward gathers per layer), stages 1/2 dp-shard the moments."""
    if args.zero >= 3:
        from distributed_pytorch_from_scratch_tpu.training.zero import (
            zero3_shardings)
        sh = zero3_shardings(model, mesh)
        return jax.device_put(params, sh), sh
    params = jax.device_put(params, model.shardings(mesh))
    if args.zero >= 1:
        from distributed_pytorch_from_scratch_tpu.training.zero import (
            zero1_moment_shardings)
        return params, zero1_moment_shardings(model, mesh)
    return params, None


def put_opt_state(opt_state, mesh, moment_sh):
    """device_put the Adam state at the ZeRO moment layout (no-op when the
    stage keeps moments on the param shardings)."""
    if moment_sh is None:
        return opt_state
    from jax.sharding import NamedSharding, PartitionSpec
    return jax.device_put(opt_state, opt_state.__class__(
        step=NamedSharding(mesh, PartitionSpec()),
        mu=moment_sh, nu=moment_sh))


def param_bytes_per_device(params) -> int:
    """MEASURED resident param bytes per mesh device (sums every leaf's
    addressable shards — a replicated leaf counts once per device, a
    dp-sharded one 1/dp as much — divided by the devices actually holding
    shards, NOT jax.local_device_count(): a dp2 mesh on an 8-device host
    must not report 1/8th). The record field the ZeRO-3 memory claim is
    pinned on rather than asserted."""
    leaves = jax.tree.leaves(params)
    total = sum(sum(s.data.nbytes for s in leaf.addressable_shards)
                for leaf in leaves)
    devices = {s.device for leaf in leaves for s in leaf.addressable_shards}
    return int(total // max(len(devices), 1))


def bucket_shape(args, cfg):
    """(t_real, t_pad): the real sequence length and the bucket-padded
    buffer length actually dispatched (equal when bucketing is off)."""
    t_real = args.seqlen or cfg.maxlen
    if not args.seq_bucket:
        return t_real, t_real
    pad = (t_real + args.seq_bucket - 1) // args.seq_bucket * args.seq_bucket
    return t_real, pad


def make_batch(cfg, B, t_real, t_pad, seed=1):
    """(ids, tgt, pos) for one step; bucket-pad rows carry IGNORE_INDEX
    targets so the CE masks them, exactly like the train loop's bucketing."""
    key = jax.random.key(seed)
    ids = jax.random.randint(key, (B, t_real), 0, cfg.vocab_size)
    tgt = jnp.roll(ids, -1, axis=1)
    if t_pad > t_real:
        ids = jnp.pad(ids, ((0, 0), (0, t_pad - t_real)))
        tgt = jnp.pad(tgt, ((0, 0), (0, t_pad - t_real)),
                      constant_values=IGNORE_INDEX)
    pos = jnp.tile(jnp.arange(t_pad, dtype=jnp.int32)[None, :], (B, 1))
    return ids, tgt, pos


def chip_key() -> str:
    """attribution's roofline key for the attached chip; an accelerator the
    peaks table does not list raises. With no accelerator (the CPU-runnable
    analytic reports) the roofline is priced for a v5e, and the report's
    header says which chip it priced."""
    from distributed_pytorch_from_scratch_tpu.obs.attribution import (
        chip_key_for)
    dev = jax.devices()[0]
    return "v5e" if dev.platform == "cpu" else chip_key_for(dev.device_kind)


def mfu_of(flops: float, secs: float, world: int):
    """Model FLOP/s utilisation, or None where there is no chip peak to
    divide by (the CPU backend): printed as 'not measured', never as a
    share of some TPU's peak."""
    peak = chip_peak_flops()
    return flops / secs / (peak * world) if peak else None


def mfu_str(mfu) -> str:
    return f"{mfu * 100:.1f}%" if mfu is not None else "not measured"


def default_batch(args) -> int:
    """b8 for gpt2-124m (validated to fit 16G without remat), b4 for
    gpt2-355m (fits WITH remat), b32 (the reference's experiment batch)
    otherwise."""
    if args.batch:
        return args.batch
    return {"gpt2-124m": 8, "gpt2-355m": 4}.get(args.model, 32)


def run_decode_bench(args, mesh, cfg, tp: int) -> None:
    """Generation throughput, KV-cache vs reference-semantics recompute.

    Params are fresh random inits (throughput does not depend on the
    values); prompts are random ids. Both paths produce tokens until EOS or
    the budget — actual produced counts are used, so chance early-EOS rows
    do not inflate the rate."""
    from distributed_pytorch_from_scratch_tpu.evaluate import (
        make_greedy_decoder)
    from distributed_pytorch_from_scratch_tpu.models.decode import (
        GreedyDecoder)

    if args.prompt_len + args.gen_tokens + 2 > cfg.maxlen:
        # same hazard the training path fixes up for --seqlen: positions
        # past the RoPE/position table would clip to its last row and the
        # bench would silently measure a degenerate model
        cfg = dataclasses.replace(
            cfg, maxlen=args.prompt_len + args.gen_tokens + 2)
    model = build_model(args, cfg, tp)
    params = jax.device_put(model.init(jax.random.key(0)),
                            model.shardings(mesh))
    B = args.batch or 8
    plen, gen = args.prompt_len, args.gen_tokens
    if plen <= 0 or gen <= 0:
        raise SystemExit("--decode needs --prompt_len and --gen_tokens >= 1")
    buf_len = plen + gen + 2
    eos = 1  # the shipped tokenizer's EOS (tokenizer/tokenizer.json)
    import numpy as np
    rng = jax.random.randint(jax.random.key(1), (B, plen), 3, cfg.vocab_size)
    prompts = np.asarray(rng).tolist()  # one device->host transfer

    decoder = GreedyDecoder(model, mesh, buf_len)
    t0 = time.time()
    decoder.decode_batch(params, prompts, eos, plen + gen)  # compile
    compile_s = time.time() - t0
    t0 = time.time()
    gens = decoder.decode_batch(params, prompts, eos, plen + gen)
    kv_s = time.time() - t0
    kv_tokens = sum(len(g) for g in gens)
    kv_rate = kv_tokens / kv_s          # aggregate over the B streams
    kv_rate_stream = kv_rate / B        # per-stream: the batching win removed

    # Reference semantics: one dispatch per token, full-prefix recompute
    # (evaluate.py --no_kv_cache; /root/reference/test.py:141-161 decodes
    # one prompt at a time). ADVICE r4: probe over the FULL generation
    # budget, not the first 16 tokens — recompute cost grows with the
    # prefix, so a short early probe flattered the baseline; and compare
    # per-stream so the headline isn't mostly a batching win.
    step = make_greedy_decoder(model, mesh, buf_len)
    buf = np.full((1, buf_len), eos, np.int32)
    buf[0, :plen] = prompts[0]
    int(step(params, jnp.asarray(buf), plen))  # compile
    probe_steps = gen
    cur = plen
    t0 = time.time()
    for _ in range(probe_steps):
        nxt = int(step(params, jnp.asarray(buf), cur))
        buf[0, cur] = nxt
        cur += 1
    ref_per_token = (time.time() - t0) / probe_steps
    ref_rate = 1.0 / ref_per_token  # one prompt at a time, like test.py

    print(f"bench[decode {args.model} {args.family}]: b{B} prompt{plen} "
          f"gen{gen}, compile {compile_s:.1f}s, kv-cache "
          f"{kv_tokens} tokens in {kv_s*1000:.0f}ms ({kv_rate:.0f} tok/s "
          f"aggregate, {kv_rate_stream:.0f} tok/s/stream); "
          f"reference-semantics recompute {ref_per_token*1000:.1f}ms/token "
          f"({ref_rate:.0f} tok/s, measured over the full {probe_steps}-token "
          f"budget)", file=sys.stderr)
    print(json.dumps({
        "metric": (f"decode tokens/sec ({args.model} {args.family}, "
                   f"kv-cache batched, b{B}, prompt{plen}, gen{gen}; "
                   f"vs_baseline = PER-STREAM speedup over the reference's "
                   f"full-recompute per-token decode; batching adds "
                   f"another x{B} aggregate)"),
        "value": round(kv_rate, 1),
        "unit": "tokens/sec",
        "vs_baseline": round(kv_rate_stream / ref_rate, 2),
        "batch": B,
        "probe_steps": probe_steps,
        "kv_rate_per_stream": round(kv_rate_stream, 1),
        "ref_recompute_rate": round(ref_rate, 1),
        **run_stamp(vars(args)),
    }))


def run_serving_bench(args, mesh, cfg, tp: int) -> None:
    """Serving A/B: PAGED engine vs the PR 5 slot engine at EQUAL HBM
    budget, both vs one-shot batch decode.

    The same long/short INTERLEAVED burst (alternating prompt_len/4 and
    prompt_len prompts — the head-of-line-prefill stress) goes through:

    (a) the paged engine (serving v2): page budget = slots x buf_len
        tokens — the SAME bytes the slot engine spends — but leased as
        pages, so short requests admit past the slot count, long prompts
        prefill in chunks, and identical prefixes share pages;
    (b) the slot engine at --slots rows of buf_len (PR 5's shape);
    (c) one-shot GreedyDecoder batches of --slots rows (the
        pre-serving baseline; every batch pads to its slowest row).

    vs_baseline = paged / one-shot aggregate tokens/s; `paged_vs_slot`
    and the per-engine TTFT p95 + max sustained concurrency are the A/B
    the page table exists to win. Random init + random-id prompts (cost
    depends on shapes, not values); first-touch compiles are included in
    every side's wall."""
    import numpy as np

    from distributed_pytorch_from_scratch_tpu.models.decode import (
        GreedyDecoder)
    from distributed_pytorch_from_scratch_tpu.serving.engine import (
        ContinuousBatchingEngine, PagedEngine)
    from distributed_pytorch_from_scratch_tpu.serving.loadgen import (
        run_loadgen, synthetic_requests)

    plen, gen = args.prompt_len, args.gen_tokens
    if plen < 3 or gen <= 0:
        # loadgen prompts need >= 3 ids (the BOS/EOS/UNK convention floor)
        raise SystemExit("--serving needs --prompt_len >= 3 and "
                         "--gen_tokens >= 1")
    if plen + gen + 2 > cfg.maxlen:
        cfg = dataclasses.replace(cfg, maxlen=plen + gen + 2)
    model = build_model(args, cfg, tp, cp=args.cp)
    params = jax.device_put(model.init(jax.random.key(0)),
                            model.shardings(mesh))
    buf_len = plen + gen + 2
    eos = 1  # the shipped tokenizer's EOS (tokenizer/tokenizer.json)

    def burst():
        # fresh Request objects each time — engines mutate them
        return synthetic_requests(
            args.serve_requests, max(3, plen // 4), plen, gen,
            cfg.vocab_size, seed=2, arrival="burst", interleave=True)

    # (a) paged at the slot engine's HBM budget. FLOOR division: the slot
    # engine owns slots x buf_len token positions, and rounding the page
    # count UP would hand the paged side up to page_size-1 extra tokens
    # per slot — the A/B must pay paging's tail-page fragmentation out of
    # the SAME bytes, not out of extra budget. (Clamped so one worst-case
    # request still fits, else --slots 1 would refuse every submit.)
    # --kv_dtype int8: the SAME byte budget buys ~2x the pages (int8
    # codes + per-head-vector scales priced honestly by page_bytes) —
    # the record carries kv_dtype + the granted capacity ratio so the
    # r11 numbers are attributable to the knob, not to extra budget.
    from distributed_pytorch_from_scratch_tpu.serving.kv_manager import (
        kv_token_bytes, page_bytes)
    kv_dtype = None if args.kv_dtype == "native" else args.kv_dtype
    wdtype = (None if args.decode_weight_dtype == "native"
              else args.decode_weight_dtype)
    budget_bytes = args.slots * buf_len * kv_token_bytes(cfg)
    num_pages = max(-(-buf_len // args.page_size),
                    int(budget_bytes
                        // page_bytes(cfg, args.page_size, kv_dtype)))
    native_pages = max(-(-buf_len // args.page_size),
                       (args.slots * buf_len) // args.page_size)
    kv_capacity_ratio = round(num_pages / max(native_pages, 1), 3)
    # observability on the PAGED arm (the headline engine): per-request
    # timelines + flight ring under --obs_dir, refused loudly when the
    # dir cannot take writes (a silently traceless traced bench is worse
    # than none)
    obs_tracer = obs_writer = obs_rt = obs_flight = None
    obs_telemetry = obs_profiler = obs_duty = obs_advisor = None
    if args.trace_requests or args.flight_records \
            or args.metrics_port is not None or args.profile_every:
        from distributed_pytorch_from_scratch_tpu.obs import (
            FlightRecorder, RequestTracer, SpanTracer, TelemetryExporter)
        from distributed_pytorch_from_scratch_tpu.serving.serve import (
            require_writable_dir)
        from distributed_pytorch_from_scratch_tpu.training.metrics import (
            AnomalyProfiler, DutyCycleProfiler, MetricsWriter)
        require_writable_dir(
            args.obs_dir,
            "--trace_requests/--flight_records/--metrics_port/"
            "--profile_every")
        obs_tracer = SpanTracer(args.obs_dir, process_name="bench-serving")
        obs_writer = MetricsWriter(args.obs_dir, process_index=0)
        if args.metrics_port is not None:
            obs_telemetry = TelemetryExporter(
                writer=obs_writer, rollup_interval=args.rollup_interval)
            port = obs_telemetry.start(args.metrics_port)
            print(f"telemetry exporter: http://127.0.0.1:{port}"
                  f"/metrics.json", file=sys.stderr)
        if args.flight_records:
            if args.profile_on_anomaly:
                obs_profiler = AnomalyProfiler(
                    args.obs_dir, window_steps=args.profile_on_anomaly,
                    writer=obs_writer)
            obs_flight = FlightRecorder(args.obs_dir,
                                        profiler=obs_profiler)
        if args.trace_requests:
            obs_rt = RequestTracer(writer=obs_writer, tracer=obs_tracer,
                                   flight=obs_flight)
        if args.profile_every:
            obs_duty = DutyCycleProfiler(
                args.obs_dir, args.profile_every, args.profile_window,
                args.profile_budget_mb, writer=obs_writer)
    try:
        paged = PagedEngine(
            model, mesh, params, num_slots=args.serve_requests,
            buf_len=buf_len, eos_id=eos, page_size=args.page_size,
            num_pages=num_pages, prefill_chunk=args.prefill_chunk,
            kv_dtype=kv_dtype, decode_weight_dtype=wdtype,
            paged_attn_impl=args.paged_attn,
            tracer=obs_tracer, writer=obs_writer,
            request_tracer=obs_rt, flight=obs_flight,
            telemetry=obs_telemetry, duty_profiler=obs_duty)
        # the impl the engine actually built (a non-TPU backend downgrades
        # 'pallas' to 'gather' with a warning — the record must not lie)
        paged_attn = paged.paged_attn_impl
        if args.control != "off" and obs_duty is not None:
            # obs v5 ADVISE-mode drift advisor on the paged arm: the duty
            # hook below fires between capture windows (the registered
            # safe point); advise never mutates, so the record still
            # measures exactly the configured engine
            from distributed_pytorch_from_scratch_tpu.obs.control import (
                RetuneAdvisor, control_safe_point)
            obs_advisor = RetuneAdvisor(args.control, writer=obs_writer,
                                        telemetry=obs_telemetry)
            obs_advisor.register_knob(
                "prefill_chunk", lambda: paged.prefill_chunk, lo=1)

            @control_safe_point
            def _bench_on_attribution(fields):
                obs_advisor.observe_attribution(fields)
                obs_advisor.apply_decisions()

            obs_duty.on_attribution = _bench_on_attribution
        paged_summary = run_loadgen(paged, burst())
        paged_rate = paged_summary["tokens_per_sec"]
    finally:
        # a mid-run failure is exactly when the trace matters: finalise
        # trace.json + flush the events before the exception propagates
        # (profilers -> exporter -> tracer -> writer, the serve.py order)
        if obs_profiler is not None:
            obs_profiler.close()
        if obs_duty is not None:
            obs_duty.close()
        if obs_advisor is not None:  # after duty: its close() may feed
            obs_advisor.close()      # the advisor one last reconcile
        if obs_telemetry is not None:
            obs_telemetry.close()
        if obs_tracer is not None:
            obs_tracer.close()
        if obs_writer is not None:
            obs_writer.close()

    # (a'') the gather-impl arm of the kernel A/B (ISSUE 14): when
    # --paged_attn pallas was asked for, rerun the SAME burst through an
    # otherwise-identical engine on the gather impl at the SAME page-byte
    # budget, and price both impls' decode dispatch analytically
    # (obs/attribution.paged_decode_hbm_bytes) so the record carries the
    # gather-copy elimination as numbers, not claims.
    from distributed_pytorch_from_scratch_tpu.obs.attribution import (
        paged_decode_hbm_bytes)
    max_pages_per_slot = -(-buf_len // args.page_size)
    hbm_kw = dict(slots=args.serve_requests,
                  max_pages=max_pages_per_slot, page_size=args.page_size,
                  kv_dtype=kv_dtype, decode_weight_dtype=wdtype,
                  live_tokens=args.serve_requests * (plen + gen // 2),
                  cp=args.cp)
    decode_hbm = {impl: paged_decode_hbm_bytes(cfg, paged_attn=impl,
                                               **hbm_kw)
                  for impl in ("gather", "pallas")}

    # ISSUE 18: prefill latency per prompt token (queue wait excluded) —
    # the number the cp query ring must hold flat-or-better while
    # per-chip KV bytes shrink ~1/cp; check_bench_regression gates it
    # directionally (up = fail). TTFT minus queue wait still includes the
    # decode dispatches interleaved into the chunked prefill — that IS
    # the serving prefill cost, not a kernel microbenchmark.
    def _prefill_ms_per_token(eng):
        done = [r for r in eng.completed if r.ttft_s]
        toks = sum(len(r.prompt) for r in done)
        return round(sum(r.ttft_s - (r.queue_wait_s or 0.0)
                         for r in done) * 1e3 / max(toks, 1), 4)

    prefill_ms_per_token = _prefill_ms_per_token(paged)

    # ISSUE 15: measured attribution on the paged arm — the duty
    # profiler's last finished capture parsed and reconciled against the
    # decode roofline the record already prices analytically (the byte
    # model above over the chip's HBM bandwidth). The regression gate
    # treats the measured per-phase / comm ms directionally (up = fail).
    measured_vs_analytic = None
    if obs_duty is not None and obs_duty.captures:
        from distributed_pytorch_from_scratch_tpu.obs import profparse
        from distributed_pytorch_from_scratch_tpu.obs.attribution import (
            CHIP_SPECS)
        try:
            measured = profparse.parse_capture(obs_duty.captures[-1])
        except (ValueError, OSError) as e:
            measured = None
            print(f"bench[serving]: duty capture unparseable "
                  f"({type(e).__name__}: {e}) — record carries no "
                  f"measured_vs_analytic", file=sys.stderr)
        if measured is not None:
            _, hbm_bw = CHIP_SPECS[chip_key()]
            roofline_ms = (decode_hbm[paged_attn]["total_bytes"]
                           / hbm_bw * 1e3)
            analytic_rep = {
                "phases": [{"name": "compute",
                            "ms": round(roofline_ms, 4)}],
                "total_ms": round(roofline_ms, 4)}
            # the dispatches the LAST capture actually covered (a
            # close()-truncated window is shorter than the configured W)
            steps = (obs_duty.capture_steps[-1]
                     if obs_duty.capture_steps else obs_duty.window)
            measured_vs_analytic = {
                "capture": obs_duty.captures[-1],
                "analytic_decode_roofline_ms": round(roofline_ms, 4),
                **profparse.reconcile(measured, analytic_rep,
                                      steps=steps)}
            print(f"bench[serving]: measured decode "
                  f"{measured_vs_analytic['measured_step_ms']:.2f} ms/step"
                  f" vs analytic roofline {roofline_ms:.2f} ms "
                  f"(measured comm "
                  f"{measured_vs_analytic['comm_ms']:.2f} ms/step)",
                  file=sys.stderr)

    gather_summary = None
    if args.paged_attn == "pallas":
        # the gather arm runs WITHOUT the obs hooks (those closed with
        # the paged arm above, whose record they annotate) — so when obs
        # flags are combined with the A/B, the pallas arm alone pays the
        # tracing cost and the ratio is not a clean kernel comparison;
        # say so rather than let the skew pass as a kernel result (the
        # staged r15 A/B lines run obs-free for exactly this reason)
        if obs_tracer is not None or obs_writer is not None:
            print("bench[serving]: NOTE pallas_vs_gather includes "
                  "observability overhead on the pallas arm only "
                  "(--trace_requests/--flight_records/--metrics_port "
                  "attach to the headline arm); rerun without obs flags "
                  "for a clean kernel A/B", file=sys.stderr)
        gather_eng = PagedEngine(
            model, mesh, params, num_slots=args.serve_requests,
            buf_len=buf_len, eos_id=eos, page_size=args.page_size,
            num_pages=num_pages, prefill_chunk=args.prefill_chunk,
            kv_dtype=kv_dtype, decode_weight_dtype=wdtype,
            paged_attn_impl="gather")
        gather_summary = run_loadgen(gather_eng, burst())

    # (a''') the cp=1 arm of the long-context A/B (ISSUE 18): when --cp
    # shards the page pool, rerun the SAME burst through a cp=1 engine at
    # the SAME page-byte budget (num_pages unchanged — equal TOTAL pool
    # bytes, so the ratio isolates the ring + combine overhead from any
    # capacity effect). The record carries cp_vs_cp1 plus both sides'
    # per-chip pool bytes, and the 1/cp per-chip shrink is ASSERTED (the
    # bound 1/cp + 0.05 covers the per-rank scratch page), not narrated.
    # the slot/one-shot baselines below always run cp=1 (the slot
    # engine's per-slot caches replicate over cp — it refuses a cp>1
    # model — and the one-shot batch decoder needs no page pool to
    # shard); at cp>1 they reuse the cp=1 arm's model/mesh/params
    slot_model, slot_mesh, slot_params = model, mesh, params
    cp1_rec = {}
    if args.cp > 1:
        def _pool_bytes_per_chip(eng):
            # page data only (pool.ks/vs); the tp head-axis sharding
            # divides both sides equally, so it cancels in the ratio
            total = sum(x.nbytes for x in
                        jax.tree.leaves((eng.pool.ks, eng.pool.vs)))
            return total // (max(1, eng.pool.cp) * tp)

        mesh1 = make_mesh(MeshConfig(dp=1, tp=tp))
        model1 = build_model(args, cfg, tp)
        params1 = jax.device_put(model1.init(jax.random.key(0)),
                                 model1.shardings(mesh1))
        cp1_eng = PagedEngine(
            model1, mesh1, params1, num_slots=args.serve_requests,
            buf_len=buf_len, eos_id=eos, page_size=args.page_size,
            num_pages=num_pages, prefill_chunk=args.prefill_chunk,
            kv_dtype=kv_dtype, decode_weight_dtype=wdtype,
            paged_attn_impl=args.paged_attn)
        cp1_summary = run_loadgen(cp1_eng, burst())
        slot_model, slot_mesh, slot_params = model1, mesh1, params1
        chip_cp = _pool_bytes_per_chip(paged)
        chip_cp1 = _pool_bytes_per_chip(cp1_eng)
        bytes_ratio = chip_cp / max(chip_cp1, 1)
        bound = 1.0 / args.cp + 0.05
        if bytes_ratio > bound:
            raise SystemExit(
                f"bench[serving]: per-chip KV-pool bytes at cp={args.cp} "
                f"are {bytes_ratio:.3f}x the cp=1 pool at equal "
                f"page-byte budget (bound {bound:.2f}) — the cp sharding "
                f"is not delivering its 1/cp ({chip_cp} vs {chip_cp1} "
                f"bytes)")
        cp1_rec = {"cp_vs_cp1": {
            "tokens_per_sec_ratio": round(
                paged_rate / max(cp1_summary["tokens_per_sec"], 1e-9), 3),
            "cp1_rate": cp1_summary["tokens_per_sec"],
            "cp1_ttft_ms_p95": cp1_summary["ttft_ms_p95"],
            "cp1_tpot_ms_p95": cp1_summary["tpot_ms_p95"],
            "cp1_prefill_ms_per_token": _prefill_ms_per_token(cp1_eng),
            "kv_pool_bytes_per_chip": chip_cp,
            "cp1_kv_pool_bytes_per_chip": chip_cp1,
            "pool_bytes_per_chip_ratio": round(bytes_ratio, 4),
        }}
        print(f"bench[serving]: cp={args.cp} {paged_rate:.0f} tok/s vs "
              f"cp=1 {cp1_summary['tokens_per_sec']:.0f} tok/s at equal "
              f"page-byte budget; per-chip pool bytes "
              f"{chip_cp / 1e6:.1f} MB vs {chip_cp1 / 1e6:.1f} MB "
              f"({bytes_ratio:.2f}x, bound {bound:.2f})", file=sys.stderr)

    # (a') the speculative arm at the SAME byte budget: the drafter's pages
    # buy acceptance, not capacity, so they are paid for by SHRINKING the
    # target pool — budget_bytes = slots x buf_len target-token bytes,
    # minus the drafter pool's bytes, floored to target pages. (Clamped so
    # one worst-case request still fits each pool.)
    spec_summary = None
    spec_pages = {}
    if args.speculate:
        from distributed_pytorch_from_scratch_tpu.config import model_preset
        from distributed_pytorch_from_scratch_tpu.models.transformer import (
            Transformer as _LlamaTransformer)
        from distributed_pytorch_from_scratch_tpu.serving.speculative import (
            SpeculativeEngine)

        # drafter: the 'tiny' preset at the target's vocab. Always the
        # RoPE llama family — no learned-position cap to fight, and the
        # verify step only needs a shared vocabulary, not a shared family.
        dcfg = model_preset("tiny", vocab_size=cfg.vocab_size,
                            maxlen=cfg.maxlen,
                            compute_dtype=cfg.compute_dtype)
        dmodel = _LlamaTransformer(dcfg, tp_size=tp)
        dparams = jax.device_put(dmodel.init(jax.random.key(3)),
                                 dmodel.shardings(mesh))
        k = args.speculate
        ps = args.page_size
        d_max_pages = -(-(buf_len + k + 1) // ps)
        d_pages = args.serve_requests * d_max_pages
        # both pools price at THEIR storage dtype (int8 drafter pages are
        # cheaper too — the knob shifts the whole budget split)
        d_bytes = d_pages * page_bytes(dcfg, ps, kv_dtype)
        t_pages = max(-(-buf_len // ps),
                      int((budget_bytes - d_bytes)
                          // page_bytes(cfg, ps, kv_dtype)))
        spec_pages = {"target_pages": t_pages, "drafter_pages": d_pages,
                      "drafter_budget_share": round(
                          d_bytes / max(budget_bytes, 1), 4)}
        spec = SpeculativeEngine(
            model, mesh, params, dmodel, dparams,
            num_slots=args.serve_requests, buf_len=buf_len, eos_id=eos,
            speculate_k=k, drafter_pages=d_pages, page_size=ps,
            num_pages=t_pages, prefill_chunk=args.prefill_chunk,
            kv_dtype=kv_dtype, decode_weight_dtype=wdtype,
            paged_attn_impl=args.paged_attn)
        spec_summary = run_loadgen(spec, burst())

    # (b) the PR 5 slot engine
    engine = ContinuousBatchingEngine(
        slot_model, slot_mesh, slot_params, num_slots=args.slots,
        buf_len=buf_len, eos_id=eos, prefill_bucket=128)
    summary = run_loadgen(engine, burst())
    serve_rate = summary["tokens_per_sec"]

    # (c) one-shot baseline: the same prompts in GreedyDecoder batches of
    # --slots (the final ragged batch repeats its last prompt to keep one
    # compiled shape; pad-row outputs are not counted)
    dec = GreedyDecoder(slot_model, slot_mesh, buf_len)
    prompts = [r.prompt for r in burst()]
    B = args.slots
    t0 = time.time()
    oneshot_tokens = 0
    for i in range(0, len(prompts), B):
        chunk = prompts[i:i + B]
        real = len(chunk)
        chunk = chunk + [chunk[-1]] * (B - real)
        limits = np.asarray([len(p) + gen for p in chunk], np.int32)
        gens = dec.decode_batch(slot_params, chunk, eos,
                                max_total_len=limits)
        oneshot_tokens += sum(len(g) for g in gens[:real])
    oneshot_s = time.time() - t0
    oneshot_rate = oneshot_tokens / max(oneshot_s, 1e-9)

    fmt = lambda v: "-" if v is None else f"{v:.0f}"
    kernel_line = ""
    if gather_summary is not None:
        kernel_line = (
            f" vs GATHER impl {gather_summary['tokens_per_sec']:.0f} "
            f"tok/s (TTFT p95 {fmt(gather_summary['ttft_ms_p95'])}ms)")
    hbm_g, hbm_p = decode_hbm["gather"], decode_hbm["pallas"]
    saved_pct = (1 - hbm_p["total_bytes"]
                 / max(hbm_g["total_bytes"], 1)) * 100
    print(f"bench[serving]: decode HBM bytes/step — gather "
          f"{hbm_g['total_bytes']/1e6:.1f} MB (gather copy "
          f"{hbm_g['gather_copy_bytes']/1e6:.1f} MB) vs pallas "
          f"{hbm_p['total_bytes']/1e6:.1f} MB ({saved_pct:.0f}% "
          f"eliminated; running impl: {paged_attn})", file=sys.stderr)
    spec_line = ""
    if spec_summary is not None:
        spec_line = (
            f" vs SPECULATIVE k={args.speculate} "
            f"{spec_summary['tokens_per_sec']:.0f} tok/s "
            f"({spec_summary['accepted_tokens_per_dispatch']:.2f} "
            f"tok/dispatch, acceptance "
            f"{100 * spec_summary['acceptance_rate']:.0f}%, "
            f"{spec_pages['target_pages']}+{spec_pages['drafter_pages']} "
            f"target+drafter pages = "
            f"{100 * spec_pages['drafter_budget_share']:.1f}% of budget "
            f"on the drafter)")
    print(f"bench[serving {args.model} {args.family}]: "
          f"{args.serve_requests}-request long/short interleave — paged "
          f"{paged_rate:.0f} tok/s (TTFT p95 "
          f"{fmt(paged_summary['ttft_ms_p95'])}ms, max live "
          f"{paged_summary['max_live']}, kv util "
          f"{paged_summary['kv_util_mean']:.2f}, prefix hits "
          f"{100 * paged_summary['prefix_hit_rate']:.0f}%, "
          f"{paged_summary['preemptions']} preempted)" + spec_line
          + kernel_line +
          f" vs slot "
          f"{serve_rate:.0f} tok/s (TTFT p95 "
          f"{fmt(summary['ttft_ms_p95'])}ms, {args.slots} slots) vs "
          f"one-shot {oneshot_rate:.0f} tok/s "
          f"({oneshot_tokens} tokens in {oneshot_s*1000:.0f}ms); equal "
          f"HBM budget: {num_pages} pages x {args.page_size} "
          f"({args.kv_dtype} KV, x{kv_capacity_ratio} vs native) = "
          f"{args.slots} slots x {buf_len}", file=sys.stderr)
    rec_value = paged_rate
    spec_rec = {}
    if spec_summary is not None:
        # the speculative arm is the headline when requested; vs_paged is
        # ITS A/B (the non-speculative paged engine at equal HBM)
        rec_value = spec_summary["tokens_per_sec"]
        spec_rec = {
            "vs_paged": round(spec_summary["tokens_per_sec"]
                              / max(paged_rate, 1e-9), 3),
            "speculate_k": args.speculate,
            "accepted_tokens_per_dispatch":
                spec_summary["accepted_tokens_per_dispatch"],
            "acceptance_rate": spec_summary["acceptance_rate"],
            "acceptance_rate_by_position":
                spec_summary["acceptance_rate_by_position"],
            "spec_rounds": spec_summary["spec_rounds"],
            "spec_ttft_ms_p95": spec_summary["ttft_ms_p95"],
            "spec_tpot_ms_p95": spec_summary["tpot_ms_p95"],
            "drafter_ms_total": spec_summary["drafter_ms_total"],
            "target_ms_total": spec_summary["target_ms_total"],
            **spec_pages,
        }
    print(json.dumps({
        "metric": (f"serving tokens/sec ({args.model} {args.family}, "
                   + (f"SPECULATIVE k={args.speculate} (tiny drafter, "
                      f"drafter pages inside the budget) over "
                      if args.speculate else "")
                   + f"PAGED at {num_pages}x{args.page_size}-token pages = "
                   + (f"{paged_attn} attn, " if paged_attn != "gather"
                      else "")
                   + (f"cp{args.cp} page shard, " if args.cp > 1 else "")
                   + f"slots{args.slots} HBM, {args.serve_requests}-request "
                   f"long/short burst, prompt {max(3, plen // 4)}/{plen}, "
                   f"gen {gen}; vs_baseline = speedup over one-shot "
                   f"b{args.slots} GreedyDecoder batches; paged_vs_slot = "
                   f"A/B against the slot engine at equal HBM"
                   + ("; vs_paged = speculative / plain paged"
                      if args.speculate else "") + ")"),
        "value": round(rec_value, 1),
        "unit": "tokens/sec (serving)",
        "vs_baseline": round(rec_value / max(oneshot_rate, 1e-9), 3),
        "paged_vs_slot": round(paged_rate / max(serve_rate, 1e-9), 3),
        "paged_rate": round(paged_rate, 1),
        "oneshot_rate": round(oneshot_rate, 1),
        # quantization attribution (ISSUE 8): what the pages/weights
        # carried and how many pages the byte budget granted vs native
        "kv_dtype": args.kv_dtype,
        "decode_weight_dtype": args.decode_weight_dtype,
        "num_pages": num_pages,
        "kv_capacity_ratio": kv_capacity_ratio,
        # ISSUE 18: the resolved cp + per-chip page count; at cp > 1
        # prefill_ms_per_token is the number the query ring must hold
        # flat-or-better and cp_vs_cp1 the equal-page-byte-budget A/B
        # (per-chip pool bytes asserted <= 1/cp + 0.05 of the cp=1 arm)
        "cp": args.cp,
        "pages_per_rank": paged.pool.pages_per_rank,
        "prefill_ms_per_token": prefill_ms_per_token,
        **cp1_rec,
        # paged-attention kernel A/B (ISSUE 14): the impl that actually
        # ran, the analytic decode-dispatch HBM bytes for BOTH impls
        # (obs/attribution.paged_decode_hbm_bytes — the gather-copy
        # elimination as an asserted number), and, when the pallas arm
        # ran, the gather arm at the same budget. The regression gate
        # treats decode_hbm_bytes_per_step directionally (up = fail).
        "paged_attn": paged_attn,
        "decode_hbm_bytes_per_step": decode_hbm[paged_attn]["total_bytes"],
        "decode_hbm_bytes_gather": decode_hbm["gather"]["total_bytes"],
        "decode_hbm_bytes_pallas": decode_hbm["pallas"]["total_bytes"],
        "gather_copy_bytes_per_step":
            decode_hbm["gather"]["gather_copy_bytes"],
        **({"pallas_vs_gather": round(
                paged_rate / max(gather_summary["tokens_per_sec"], 1e-9),
                3),
            "gather_rate": round(gather_summary["tokens_per_sec"], 1),
            "gather_ttft_ms_p95": gather_summary["ttft_ms_p95"],
            "gather_tpot_ms_p95": gather_summary["tpot_ms_p95"]}
           if gather_summary is not None else {}),
        # ISSUE 10: where the per-request timelines / flight dumps landed
        **({"obs_dir": args.obs_dir}
           if (args.trace_requests or args.flight_records
               or args.metrics_port is not None) else {}),
        **({"worst_ttft_rids": paged_summary["worst_ttft_rids"]}
           if "worst_ttft_rids" in paged_summary else {}),
        **({"flight_dumps": list(obs_flight.dumps)}
           if obs_flight is not None else {}),
        # ISSUE 12: the live endpoint + anomaly captures, when armed
        **({"metrics_port": obs_telemetry.port,
            "telemetry_snapshots": obs_telemetry.snapshots}
           if obs_telemetry is not None else {}),
        **({"anomaly_profiles": list(obs_profiler.captures)}
           if obs_profiler is not None else {}),
        # ISSUE 15: the duty-profiled arm's capture accounting rides
        # UNCONDITIONALLY when the duty profiler ran (an unparseable
        # capture must not make the record look like --profile_every 0);
        # the reconcile itself only when the last capture parsed —
        # gated directionally by check_bench_regression
        **({"profile_captures": list(obs_duty.captures),
            "profile_attributions": obs_duty.attributions,
            "profile_windows_skipped": obs_duty.windows_skipped}
           if obs_duty is not None else {}),
        **({"measured_vs_analytic": measured_vs_analytic}
           if measured_vs_analytic is not None else {}),
        # ISSUE 16: the advise-mode ledger summary (absent when off —
        # the zero-cost off-state the tests pin)
        **({"control": args.control, "tuning": obs_advisor.summary()}
           if obs_advisor is not None else {}),
        **spec_rec,
        "ttft_ms_p50": paged_summary["ttft_ms_p50"],
        "ttft_ms_p95": paged_summary["ttft_ms_p95"],
        "tpot_ms_p50": paged_summary["tpot_ms_p50"],
        "tpot_ms_p95": paged_summary["tpot_ms_p95"],
        "queue_wait_ms_p95": paged_summary["queue_wait_ms_p95"],
        "max_live": paged_summary["max_live"],
        "kv_util_mean": paged_summary["kv_util_mean"],
        "prefix_hit_rate": paged_summary["prefix_hit_rate"],
        "preemptions": paged_summary["preemptions"],
        "slo_attainment": paged_summary.get("slo_attainment"),
        "slot_engine": {
            "tokens_per_sec": round(serve_rate, 1),
            "slots": args.slots,
            "ttft_ms_p95": summary["ttft_ms_p95"],
            "queue_wait_ms_p95": summary["queue_wait_ms_p95"],
            "slot_occupancy_mean": summary["slot_occupancy_mean"],
        },
        **run_stamp(vars(args)),
    }))


def run_fleet_bench(args, mesh, cfg, tp: int) -> None:
    """Serving fleet A/B (ISSUE 19): is the router worth its hop, and
    when does disaggregation win?

    The same shared-prefix mixed-class burst goes through:

    (a) --fleet_replicas PagedEngine replicas behind the prefix-cache-
        aware FleetRouter (serving/router.py) — each replica at --slots
        and the per-replica page budget;
    (b) ONE PagedEngine at slots x replicas and pages x replicas — the
        SAME total HBM in one pool (vs_baseline = fleet / single; the
        single engine shares every prefix in one index, so the router's
        job is to lose as little of that as possible while it buys
        blast-radius isolation and per-replica restart);
    (c) disaggregated prefill/decode: a prefill-only engine streams
        each request's KV pages to a decode engine over the KVPG wire
        (serving/transfer.py), vs (d) the SAME single engine colocated
        — disagg_vs_colocated prices the handoff against the prefill/
        decode interference it removes.

    The record carries fleet_tokens_per_sec + per-class fleet SLO
    attainment (obs/telemetry.fleet_slo_attainment over the replicas'
    counters), router dispatch p50/p95, disagg-vs-colocated TTFT/TPOT
    p95, and the transfer wire: transferred pages, bytes-per-request
    (asserted = pages x page_bytes — the framing rides separately as
    transferred_bytes), transfer_ms p95, and the analytic pricing
    (obs/attribution.kv_transfer_attribution at the DCN rate — a fleet
    crosses hosts even though this bench runs in-process). Random init,
    random-id prompts; compiles included in every arm's wall."""
    from distributed_pytorch_from_scratch_tpu.obs.attribution import (
        kv_transfer_attribution)
    from distributed_pytorch_from_scratch_tpu.serving.engine import (
        PagedEngine)
    from distributed_pytorch_from_scratch_tpu.serving.kv_manager import (
        kv_token_bytes, page_bytes)
    from distributed_pytorch_from_scratch_tpu.serving.loadgen import (
        _pctl, run_fleet_loadgen, run_loadgen, synthetic_requests)
    from distributed_pytorch_from_scratch_tpu.serving.router import (
        FleetRouter)
    from distributed_pytorch_from_scratch_tpu.serving.scheduler import (
        parse_slo_classes)
    from distributed_pytorch_from_scratch_tpu.serving.transfer import (
        run_disaggregated)

    plen, gen = args.prompt_len, args.gen_tokens
    if plen < 3 or gen <= 0:
        raise SystemExit("--fleet needs --prompt_len >= 3 and "
                         "--gen_tokens >= 1")
    spl = args.page_size            # one full shared page to route on
    buf_len = spl + plen + gen + 2
    if buf_len > cfg.maxlen:
        cfg = dataclasses.replace(cfg, maxlen=buf_len)
    model = build_model(args, cfg, tp)
    params = jax.device_put(model.init(jax.random.key(0)),
                            model.shardings(mesh))
    eos = 1
    R = args.fleet_replicas
    kv_dtype = None if args.kv_dtype == "native" else args.kv_dtype
    pb = page_bytes(cfg, args.page_size, kv_dtype)
    budget_bytes = args.slots * buf_len * kv_token_bytes(cfg)
    pages_each = max(-(-buf_len // args.page_size),
                     int(budget_bytes // pb))
    mix = parse_slo_classes("interactive=1,standard=1")

    def burst():
        # fresh Request objects each arm — engines mutate them
        return synthetic_requests(
            args.serve_requests, max(3, plen // 4), plen, gen,
            cfg.vocab_size, seed=2, arrival="burst", class_mix=mix,
            tenants=2, shared_prefix_len=spl, interleave=True)

    def engine(slots, pages, prefill_only=False):
        return PagedEngine(
            model, mesh, params, num_slots=slots, buf_len=buf_len,
            eos_id=eos, page_size=args.page_size, num_pages=pages,
            prefill_chunk=args.prefill_chunk, kv_dtype=kv_dtype,
            slo_classes=mix, prefill_only=prefill_only)

    # (a) the fleet behind the router
    router = FleetRouter([engine(args.slots, pages_each)
                          for _ in range(R)])
    fleet = run_fleet_loadgen(router, burst())
    fleet_rate = fleet["fleet_tokens_per_sec"]
    print(f"fleet x{R}: {fleet_rate:.1f} tok/s, dispatch p50 "
          f"{fleet['dispatch_ms_p50']} ms", file=sys.stderr)

    # (b) one engine, same total HBM
    single = run_loadgen(engine(args.slots * R, pages_each * R), burst())
    single_rate = single["tokens_per_sec"]
    print(f"single slots x{R}: {single_rate:.1f} tok/s", file=sys.stderr)

    # (c) disaggregated prefill/decode over the page stream
    disagg = run_disaggregated(engine(args.slots, pages_each,
                                      prefill_only=True),
                               engine(args.slots, pages_each), burst())
    done = disagg["completed"]
    ms = 1e3
    disagg_gen = sum(len(r.tokens) for r in done)
    disagg_rate = disagg_gen / max(disagg["wall_s"], 1e-9)
    disagg_ttft = _pctl([r.ttft_s and r.ttft_s * ms for r in done], 95)
    disagg_tpot = _pctl([r.tpot_s and r.tpot_s * ms for r in done], 95)
    print(f"disagg: {disagg_rate:.1f} tok/s, transfer p95 "
          f"{disagg['transfer_ms_p95']} ms", file=sys.stderr)

    # (d) colocated comparator: one replica-sized engine doing both
    coloc = run_loadgen(engine(args.slots, pages_each), burst())
    coloc_rate = coloc["tokens_per_sec"]

    # the wire, asserted: the priced bytes ARE pages x page_bytes (the
    # JSON framing rides separately in transferred_bytes)
    pricing = kv_transfer_attribution(disagg["transferred_pages"], pb,
                                      link="dcn",
                                      measured_ms=disagg["transfer_ms_p50"])
    assert pricing["bytes_each"] == disagg["transferred_pages"] * pb, \
        (pricing["bytes_each"], disagg["transferred_pages"], pb)
    kv_bytes_per_req = round(disagg["transferred_pages"] * pb
                             / max(len(done), 1), 1)

    slo = fleet.get("fleet_slo_attainment") or {}
    slo_min = min((v["attained"] for v in slo.values()), default=None)
    print(json.dumps({
        "metric": (f"serving fleet tokens/sec ({args.model} "
                   f"{args.family}, {R}x PagedEngine slots{args.slots} "
                   f"behind the prefix-aware router; vs_baseline = fleet "
                   f"/ ONE engine at slots{args.slots * R} equal total "
                   f"HBM; disagg_vs_colocated = prefill/decode split "
                   f"over the KV page stream / the same one-replica "
                   f"engine colocated; {args.serve_requests}-request "
                   f"long/short burst, {spl}-token shared prefix, "
                   f"prompt {max(3, plen // 4)}/{plen}, gen {gen})"),
        "value": round(fleet_rate, 1),
        "unit": "tokens/sec (fleet)",
        "fleet_replicas": R,
        "fleet_tokens_per_sec": round(fleet_rate, 1),
        "vs_baseline": round(fleet_rate / max(single_rate, 1e-9), 3),
        "single_rate": round(single_rate, 1),
        "dispatch_ms_p50": fleet["dispatch_ms_p50"],
        "dispatch_ms_p95": fleet["dispatch_ms_p95"],
        "session_spills": fleet["session_spills"],
        "rejected": fleet["rejected"],
        "ttft_ms_p95": fleet["ttft_ms_p95"],
        "tpot_ms_p95": fleet["tpot_ms_p95"],
        "per_replica": fleet["per_replica"],
        "fleet_slo_attainment": slo,
        "fleet_slo_attainment_min": slo_min,
        "kv_dtype": args.kv_dtype,
        "num_pages": pages_each,
        "page_bytes": pb,
        # the disagg A/B + the wire it pays for
        "disagg_rate": round(disagg_rate, 1),
        "coloc_rate": round(coloc_rate, 1),
        "disagg_vs_colocated": round(disagg_rate / max(coloc_rate, 1e-9),
                                     3),
        "disagg_ttft_ms_p95": disagg_ttft,
        "coloc_ttft_ms_p95": coloc["ttft_ms_p95"],
        "disagg_tpot_ms_p95": disagg_tpot,
        "coloc_tpot_ms_p95": coloc["tpot_ms_p95"],
        "transfer_ms_p50": disagg["transfer_ms_p50"],
        "transfer_ms_p95": disagg["transfer_ms_p95"],
        "transferred_pages": disagg["transferred_pages"],
        "transferred_bytes": disagg["transferred_bytes"],
        "transfer_bytes_per_request": kv_bytes_per_req,
        "transfer_attribution": pricing,
        **run_stamp(vars(args)),
    }))


def run_breakdown(args, mesh, cfg, tp: int) -> None:
    """Where does the step time go? (VERDICT r4 #3 / r5 #1.)

    Times, with a device->host sync after each: the batch H2D transfer,
    a jitted forward (loss only), a jitted forward+backward (grads, no
    update), the full single-step train program, and the scanned
    steps_per_dispatch-step program. Derived components: bwd = fwdbwd-fwd,
    adam = step-fwdbwd, dispatch = step - scanned-per-step: the per-dispatch
    host cost steps_per_dispatch exists to amortise.

    On top of the measured components, the roofline ATTRIBUTION report
    (obs/attribution) prices every phase analytically and ranks the waste
    suspects — pad/tile waste at the active flash blocks, remat recompute,
    dispatch, the head — against the measured step. `--analytic` emits
    that report alone, with no device timing at all (CPU-runnable at the
    flagship shape)."""
    import numpy as np

    from distributed_pytorch_from_scratch_tpu.obs.attribution import (
        attribution, format_attribution)

    spd = max(2, args.steps_per_dispatch)
    B = default_batch(args)
    T, T_pad = bucket_shape(args, cfg)
    world = args.dp * tp

    # zero 2's grad wire IS the bucketed reduce-scatter: price it at the
    # default bucket when the flag was left 0 (matching the step builder)
    dp_bucket_mb = args.dp_reduce_bucket_mb
    if args.zero == 2 and not dp_bucket_mb:
        dp_bucket_mb = 25.0

    def emit(measured=None, comp=None, allreduce_us=None):
        report = attribution(
            cfg, B, T_pad, remat=args.remat, spd=spd,
            t_real=T if T_pad > T else None,
            measured=measured, chip=chip_key(), world=world,
            family=args.family, tp=tp, sp=args.sequence_parallel,
            tp_overlap=args.tp_overlap, dp=args.dp,
            dp_bucket_mb=dp_bucket_mb,
            dp_reduce_dtype=args.dp_reduce_dtype,
            measured_allreduce_us=allreduce_us,
            zero_stage=args.zero)
        print(format_attribution(report, measured), file=sys.stderr)
        return report

    if args.analytic:
        report = emit()
        shape = f"b{B}xt{T}" + (f"->t{T_pad}" if T_pad > T else "")
        comm = report["comm"]
        print(json.dumps({
            "metric": (f"step-time attribution ({args.model} {args.family}, "
                       f"{shape}, remat={args.remat}, tp={tp}, "
                       f"sp={args.sequence_parallel}, "
                       f"tp_overlap={args.tp_overlap}, "
                       f"ANALYTIC {report['chip']} roofline — no device "
                       f"timing; value = analytic step ms, vs_baseline = "
                       f"top suspect's share of the step"),
            "value": round(report["analytic_step_ms"], 2),
            "unit": "ms/step (analytic)",
            "vs_baseline": round(report["suspects"][0]["share"], 4),
            # r11 attribution: the wire dtypes the comm was PRICED at
            "wire_dtype": args.dp_reduce_dtype,
            "tp_overlap": args.tp_overlap,
            # r12: the DP schedule the comm was priced at (AR vs RS+AG)
            "zero_stage": args.zero,
            "comm": {
                "total_ms": round(comm["comm_total_ms"], 3),
                "hidden_ms": round(comm["comm_hidden_ms"], 3),
                "exposed_ms": round(comm["comm_exposed_ms"], 3),
            },
            "suspects": [{k: (round(v, 3) if isinstance(v, float) else v)
                          for k, v in s.items()}
                         for s in report["suspects"]],
            **run_stamp(vars(args)),
        }))
        return

    if T > cfg.maxlen:
        # same RoPE/position-table hazard the training path fixes up: past
        # maxlen every position clips to the last row and the breakdown
        # would silently time a degenerate model
        cfg = dataclasses.replace(cfg, maxlen=T)
    model = build_model(args, cfg, tp, remat=args.remat,
                        attn_t_real=T if T_pad > T else None)
    params, moment_sh = zero_state_put(args, model, mesh,
                                       model.init(jax.random.key(0)))
    pbpd = param_bytes_per_device(params)
    flops = model_flops_per_step(cfg, B, T,
                                 num_params=model.num_params(cfg))
    ocfg = OptimizerConfig()
    host_ids = np.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (B, T_pad), dtype=np.int32))
    ids, tgt, pos = make_batch(cfg, B, T, T_pad)

    iters = args.iters

    def timed(fn, sync, warm=2):
        for _ in range(warm):
            sync(fn())
        t0 = time.time()
        for _ in range(iters):
            out = fn()
        sync(out)
        return (time.time() - t0) / iters

    h2d_s = timed(lambda: jax.device_put(host_ids),
                  lambda x: x.block_until_ready())

    loss_fn = jax.jit(model.make_loss(mesh))
    fwd_s = timed(lambda: loss_fn(params, ids, tgt, pos),
                  lambda x: float(x))

    grad_fn = jax.jit(jax.value_and_grad(model.make_loss(mesh)))
    fwdbwd_s = timed(lambda: grad_fn(params, ids, tgt, pos),
                     lambda x: float(x[0]))

    introspection = None
    if args.introspect:
        # cross-check the analytic FLOPs against XLA's own cost model for
        # the fwd+bwd program (the attribution's ground-truth anchor).
        # Runs HERE, before the donating step programs consume `params`.
        from distributed_pytorch_from_scratch_tpu.obs import (
            analyze_compiled, format_analysis)
        try:
            analysis = analyze_compiled(
                grad_fn.lower(params, ids, tgt, pos).compile())
            introspection = format_analysis(
                analysis, model_flops=flops / (args.dp * tp))
            if args.tp_overlap == "ring" and tp > 1:
                # cross-check the HLO's collective-permute bytes against
                # the ring's chunk schedule: the scanned layer body holds
                # ONE layer's ring ops in the program text, so the
                # comparable number is the per-layer fwd+bwd chunk bytes
                # (+ the unscanned head rings)
                from distributed_pytorch_from_scratch_tpu.obs.attribution \
                    import ring_chunk_bytes
                sched = ring_chunk_bytes(cfg, B, T_pad, tp)
                expect = (sched["per_layer_fwd_bytes"]
                          + sched["per_layer_bwd_bytes"]
                          + sched["head_fwd_bytes"]
                          + sched["head_bwd_bytes"])
                hlo_cp = analysis.get("collectives", {}).get(
                    "collective-permute", {"count": 0, "bytes": 0})
                introspection += (
                    f"; ring chunk schedule expects "
                    f"{expect / 2**20:.1f} MiB of collective-permute in "
                    f"the program text (per-layer body + head), HLO has "
                    f"x{hlo_cp['count']} ({hlo_cp['bytes'] / 2**20:.1f} "
                    f"MiB)")
        except Exception as e:  # noqa: BLE001 — diagnostics must not kill
            introspection = (f"unavailable: {type(e).__name__}: "
                             f"{str(e)[:200]}")

    # full step programs donate params/opt_state: thread them through
    opt_state = put_opt_state(init_adam_state(params), mesh, moment_sh)
    step_fn = build_train_step(model, mesh, ocfg, moment_shardings=moment_sh,
                               **dp_reduce_kwargs(args))
    state = [params, opt_state]

    def one_step():
        state[0], state[1], loss = step_fn(state[0], state[1], ids, tgt, pos)
        return loss

    step_s = timed(one_step, lambda x: float(jnp.sum(x)))

    ids_n, tgt_n, pos_n = (jnp.tile(x[None], (spd, 1, 1))
                           for x in (ids, tgt, pos))
    multi_fn = build_train_step_multi(model, mesh, ocfg,
                                      moment_shardings=moment_sh,
                                      **dp_reduce_kwargs(args))
    # fresh state: the donated buffers above were consumed
    params2, _ = zero_state_put(args, model, mesh,
                                model.init(jax.random.key(0)))
    state = [params2, put_opt_state(init_adam_state(params2), mesh,
                                    moment_sh)]

    def multi_step():
        state[0], state[1], loss = multi_fn(state[0], state[1], ids_n,
                                            tgt_n, pos_n)
        return loss

    multi_s = timed(multi_step, lambda x: float(jnp.sum(x))) / spd

    # ISSUE 15: capture the (already warm) scanned program under a real
    # jax.profiler window so the analytic roofline below is CHECKED
    # against a device timeline, not just printed next to wall clocks.
    # Two dispatches = 2 x spd profiled steps; ProfilerTrace owns the
    # start/stop (the profiler-discipline contract).
    capture_dir = None
    capture_steps = 0
    if args.capture_profile:
        from distributed_pytorch_from_scratch_tpu.serving.serve import (
            require_writable_dir)
        require_writable_dir(args.obs_dir, "--capture_profile")
        cap_root = os.path.join(args.obs_dir, "profile_breakdown")
        cap_trace = ProfilerTrace(cap_root, start_step=0, num_steps=2)
        cap_trace.maybe_start(0)
        multi_step()
        loss = multi_step()
        cap_trace.maybe_stop(2, sync=loss)
        capture_dir = cap_trace.log_dir
        capture_steps = 2 * spd

    comp = {
        "h2d_ms": round(h2d_s * 1e3, 2),
        "fwd_ms": round(fwd_s * 1e3, 2),
        "fwdbwd_ms": round(fwdbwd_s * 1e3, 2),
        "step_ms": round(step_s * 1e3, 2),
        f"step_ms_spd{spd}": round(multi_s * 1e3, 2),
        "derived_bwd_ms": round((fwdbwd_s - fwd_s) * 1e3, 2),
        "derived_adam_ms": round((step_s - fwdbwd_s) * 1e3, 2),
        "derived_dispatch_ms": round((step_s - multi_s) * 1e3, 2),
    }
    mfu_spd = mfu_of(flops, multi_s, world)
    shape_note = f"b{B}xt{T}" + (f"->t{T_pad}" if T_pad > T else "")
    print(f"bench[breakdown {args.model}, remat={args.remat}, {shape_note}, "
          f"world={world}]: "
          + ", ".join(f"{k}={v}" for k, v in comp.items())
          + f"; MFU at spd{spd} {mfu_str(mfu_spd)}", file=sys.stderr)

    if introspection is not None:
        print(f"breakdown introspection (fwd+bwd program): {introspection}",
              file=sys.stderr)

    # the 4 MiB tp all-reduce p50 calibrates the comm attribution's ICI
    # bandwidth term (obs/attribution.calibrate_ici) — measured on THIS
    # chip session, so the hidden/exposed split tracks the attached
    # hardware rather than the datasheet
    p50_us = allreduce_p50_us(mesh, "tp") if tp > 1 else None
    report = emit(measured=comp, allreduce_us=p50_us)

    # parse the capture and reconcile it against the attribution report
    # just emitted (ISSUE 15): per-phase drift, worst "model is wrong
    # here" suspects, and the gate-checkable measured ms
    measured_vs_analytic = None
    if capture_dir is not None:
        from distributed_pytorch_from_scratch_tpu.obs import profparse
        try:
            measured = profparse.parse_capture(capture_dir)
        except (ValueError, OSError) as e:
            print(f"bench[breakdown]: capture unparseable "
                  f"({type(e).__name__}: {e}) — record carries no "
                  f"measured_vs_analytic", file=sys.stderr)
        else:
            rec = profparse.reconcile(
                measured, profparse.analytic_phase_report(report),
                steps=capture_steps)
            measured_vs_analytic = {"capture": capture_dir, **rec}
            print("bench[breakdown] measured vs analytic:\n"
                  + profparse.format_reconcile(rec), file=sys.stderr)

    print(json.dumps({
        "metric": (f"step-time breakdown ({args.model}, bf16, {shape_note}, "
                   f"remat={args.remat}; value = single-dispatch step ms, "
                   f"vs_baseline = dispatch-amortisation gain "
                   f"step_ms / step_ms_spd{spd})"),
        "value": comp["step_ms"],
        "unit": "ms/step",
        "vs_baseline": round(step_s / multi_s, 3),
        "components": comp,
        "wire_dtype": args.dp_reduce_dtype,
        "zero_stage": args.zero,
        "param_bytes_per_device": pbpd,
        # ISSUE 15: the profiled-window reconcile (when captured); the
        # regression gate treats its per-phase / comm ms directionally
        **({"measured_vs_analytic": measured_vs_analytic}
           if measured_vs_analytic is not None else {}),
        "attribution": {
            "analytic_step_ms": round(report["analytic_step_ms"], 2),
            "chip": report["chip"],
            "comm": {
                "total_ms": round(report["comm"]["comm_total_ms"], 3),
                "hidden_ms": round(report["comm"]["comm_hidden_ms"], 3),
                "exposed_ms": round(report["comm"]["comm_exposed_ms"], 3),
            },
            "suspects": [{k: (round(v, 3) if isinstance(v, float) else v)
                          for k, v in s.items()}
                         for s in report["suspects"]],
        },
        **run_stamp(vars(args)),
    }))


def run_reshard_bench(args, mesh, cfg, tp: int) -> None:
    """Reshard pass timing (ISSUE 20): save one stamped checkpoint at the
    current layout (dp x tp at --zero, moments included), reshard it
    file->file onto --reshard_tp, and record the plan + movement facts.
    The streamed executor's law is ASSERTED here too: peak host bytes
    never exceed the largest single leaf. Two identical invocations gate
    each other in CI through check_bench_regression's reshard_ms
    (latency-directional) and reshard_bytes_moved (bytes-directional)
    fields."""
    import shutil
    import tempfile

    from distributed_pytorch_from_scratch_tpu.reshard import (
        HostMeter, make_layout, reshard_checkpoint)
    from distributed_pytorch_from_scratch_tpu.training.checkpoint import (
        save_checkpoint, validate_checkpoint)
    from distributed_pytorch_from_scratch_tpu.training.zero import (
        zero3_shardings)

    dst_tp = args.reshard_tp or max(1, tp // 2)
    model = Transformer(cfg, tp_size=tp,
                        sequence_parallel=args.sequence_parallel and tp > 1)
    sh = (zero3_shardings(model, mesh) if args.zero >= 3
          else model.shardings(mesh))
    params = jax.device_put(model.init(jax.random.key(0)), sh)
    opt = init_adam_state(params)
    work = tempfile.mkdtemp(prefix="bench_reshard_")
    try:
        src = os.path.join(work, "src")
        save_checkpoint(src, 0, 0.0, model.to_canonical(params),
                        model.canonical_specs(), tp, opt_state=opt,
                        zero_stage=args.zero, mesh_axes=mesh)
        dst_layout = make_layout((("tp", dst_tp),),
                                 model.canonical_specs(), zero_stage=0)
        meter = HostMeter()
        echo = lambda *a: print("bench[reshard]:", *a, file=sys.stderr)
        t0 = time.perf_counter()
        paths, plan, info = reshard_checkpoint(
            src, 0, os.path.join(work, "dst"), dst_layout, meter=meter,
            echo=echo)
        wall_ms = (time.perf_counter() - t0) * 1e3
        tp_out, _ = validate_checkpoint(os.path.join(work, "dst"), 0)
        assert tp_out == dst_tp, (tp_out, dst_tp)
        assert meter.peak <= info["max_leaf_bytes"], \
            (meter.peak, info["max_leaf_bytes"])
    finally:
        shutil.rmtree(work, ignore_errors=True)
    print(f"bench[reshard {args.model}]: {info['src']} -> {info['dst']}, "
          f"{len(paths)} shard(s), {info['bytes_moved']} B moved "
          f"({info['ops']}), peak host {meter.peak} B <= largest leaf "
          f"{info['max_leaf_bytes']} B, {wall_ms:.0f} ms", file=sys.stderr)
    print(json.dumps({
        "metric": (f"reshard wall ms ({args.model}, {info['src']} -> "
                   f"{info['dst']}, moments included, streamed "
                   f"leaf-at-a-time)"),
        "value": round(wall_ms, 1),
        "unit": "ms",
        "reshard_ms": round(wall_ms, 1),
        "reshard_bytes_moved": info["bytes_moved"],
        "plan_ops": info["ops"],
        "n_leaves": info["n_leaves"],
        "peak_host_bytes": meter.peak,
        "max_leaf_bytes": info["max_leaf_bytes"],
        "files": len(paths),
        **run_stamp(vars(args)),
    }))


def main(argv=None):
    args = parse_args(argv)
    enable_compile_cache()
    # no backend is a failed run: jax raises here and the exit is non-zero
    n_dev = jax.device_count()
    # off-TPU the kernel cannot be compiled and nothing stands in for it
    from distributed_pytorch_from_scratch_tpu.ops.pallas.paged_attention \
        import check_paged_attn_impl
    check_paged_attn_impl(args.paged_attn)
    tp = args.tp or max(1, n_dev // (args.dp * args.cp))
    if args.dp_reduce_bucket_mb and tp > 1 and not args.sequence_parallel:
        # fail HERE with the same clean message train.py gives
        raise SystemExit("--dp_reduce_bucket_mb with tp > 1 needs "
                         "--sequence_parallel (the non-SP path all-reduces "
                         "inside every row-parallel layer; see "
                         "training/zero.build_bucketed_grad_fn)")
    if args.zero >= 2 and tp > 1 and not args.sequence_parallel:
        raise SystemExit(f"--zero {args.zero} with tp > 1 needs "
                         f"--sequence_parallel (the stage-2/3 grad paths "
                         f"ride the bucketed reducer's per-leaf cotangent "
                         f"bookkeeping; see training/zero.py)")
    cfg = model_preset(args.model, compute_dtype="bfloat16")
    if args.seq_bucket and cfg.num_experts:
        raise SystemExit("--seq_bucket does not compose with MoE presets: "
                         "the router sees every position, so pad tokens "
                         "would claim expert-capacity slots and inflate "
                         "the aux losses")
    if args.breakdown and args.analytic:
        # pure host math — no mesh, so `--tp 4 --analytic` prices a 4-chip
        # overlapped config from a 1-chip (or CPU) box
        return run_breakdown(args, None, cfg, tp)
    mesh = make_mesh(MeshConfig(dp=args.dp, cp=args.cp, tp=tp))
    if args.remat == "auto":
        from distributed_pytorch_from_scratch_tpu.training.memory import (
            select_remat)
        args.remat = select_remat(cfg, default_batch(args),
                                  args.seqlen or cfg.maxlen,
                                  tp=tp, world=args.dp * tp,
                                  zero_stage=args.zero, dp=args.dp,
                                  family=args.family)
    if (args.decode or args.breakdown or args.serving or args.fleet
            or args.reshard):
        if args.introspect and (args.decode or args.serving or args.fleet):
            print("bench: --introspect does not apply to --decode/"
                  "--serving/--fleet; ignoring it", file=sys.stderr)
        if args.reshard:
            return run_reshard_bench(args, mesh, cfg, tp)
        if args.fleet:
            return run_fleet_bench(args, mesh, cfg, tp)
        if args.serving:
            return run_serving_bench(args, mesh, cfg, tp)
        if args.decode:
            return run_decode_bench(args, mesh, cfg, tp)
        return run_breakdown(args, mesh, cfg, tp)
    ocfg = OptimizerConfig()
    spd = max(1, args.steps_per_dispatch)

    B = default_batch(args)
    T, T_pad = bucket_shape(args, cfg)
    if T > cfg.maxlen:
        # long-context bench lines (e.g. --seqlen 8192 on the 45m preset):
        # the RoPE/position tables must cover T or every position past
        # maxlen clips to the last row (ops/rope.py clip-mode indexing).
        # Bucket padding is NOT included — pad rows are masked, so their
        # clipped positions never matter.
        cfg = dataclasses.replace(cfg, maxlen=T)
    ids, tgt, pos = make_batch(cfg, B, T, T_pad)
    if spd > 1:
        # same batch content each scanned step: throughput-identical to a
        # real stream (shapes are what matter), one H2D instead of N
        ids, tgt, pos = (jnp.tile(x[None], (spd, 1, 1)) for x in (ids, tgt, pos))

    pbpd = [None]  # measured resident param bytes/device (ZeRO record)

    def build(remat, attn_impl):
        model = build_model(args, cfg, tp, remat=remat, attn_impl=attn_impl,
                            attn_t_real=T if T_pad > T else None)
        params, moment_sh = zero_state_put(args, model, mesh,
                                           model.init(jax.random.key(0)))
        pbpd[0] = param_bytes_per_device(params)
        opt_state = put_opt_state(init_adam_state(params), mesh, moment_sh)
        builder = build_train_step_multi if spd > 1 else build_train_step
        return params, opt_state, builder(model, mesh, ocfg,
                                          moment_shardings=moment_sh,
                                          **dp_reduce_kwargs(args))

    # The requested config or a failed run: a kernel that does not compile
    # or a config that does not fit raises, and the exit is non-zero. A
    # number for some other config is never printed under this one's name.
    remat_used, attn_used = args.remat, "auto"
    params, opt_state, step_fn = build(remat_used, attn_used)

    def run_once():
        nonlocal params, opt_state
        params, opt_state, loss = step_fn(params, opt_state, ids, tgt, pos)
        return loss

    # every timed region ends in a device->host copy of the loss; the first
    # two dispatches are excluded (the second recompiles once, when donated
    # output layouts replace device_put's)
    t0 = time.time()
    loss = run_once()
    float(jnp.sum(loss))
    compile_s = time.time() - t0

    warm, iters = 2, args.iters
    for _ in range(warm):
        loss = run_once()
        float(jnp.sum(loss))
    t0 = time.time()
    for _ in range(iters):
        loss = run_once()
    loss = jnp.mean(loss)
    float(loss)
    step_s = (time.time() - t0) / (iters * spd)

    world = args.dp * tp
    tokens_per_sec_per_chip = B * T / step_s / world

    flops_per_step = model_flops_per_step(
        cfg, B, T, num_params=models.family_class(args.family).num_params(cfg))
    mfu = mfu_of(flops_per_step, step_s, world)

    if args.introspect:
        from distributed_pytorch_from_scratch_tpu.obs import (
            analyze_compiled, format_analysis)
        try:
            analysis = analyze_compiled(
                step_fn.lower(params, opt_state, ids, tgt, pos).compile())
            # per-device SPMD program, x spd scanned steps
            expected = flops_per_step * spd / world
            print("bench introspection: "
                  + format_analysis(analysis, model_flops=expected),
                  file=sys.stderr)
        except Exception as e:  # noqa: BLE001 — diagnostics must not kill
            print(f"bench introspection unavailable: "
                  f"{type(e).__name__}: {str(e)[:200]}", file=sys.stderr)

    p50 = allreduce_p50_us(mesh, "tp") if tp > 1 else None

    # BASELINE config 4 note: the vocab-parallel CE (the train step's default
    # loss mode) never materialises the full (B, T, V) logits; the f32 gather
    # it avoids at this config would be:
    vp = cfg.padded_vocab_size(tp)
    print(f"bench: vocab-parallel CE avoids a {B}x{T}x{vp} f32 logits "
          f"gather ({B * T * vp * 4 / 2**30:.2f} GiB at this config; "
          f"tested in tests/test_large_vocab.py)", file=sys.stderr)

    # None = no memory_stats on this backend: print 'n/a', never a fake
    # 0.00 GiB watermark (ISSUE 15 silent-zero fix)
    mem = device_memory_gib()
    mem_s = f"{mem:.2f}GiB" if mem is not None else "n/a"
    print(f"bench[{args.model}, remat={remat_used}, attn={attn_used}]: "
          f"{world} device(s) "
          f"[{jax.devices()[0].device_kind}], compile {compile_s:.1f}s, "
          f"step {step_s*1000:.1f}ms, loss {float(loss):.4f}, "
          f"MFU {mfu_str(mfu)}, mem {mem_s}"
          + (f", tp all-reduce p50 {p50:.0f}us (4MiB)" if p50 else ""),
          file=sys.stderr)

    bucket_note = (f", seq_bucket t{T}->t{T_pad} (real tokens counted)"
                   if T_pad > T else "")
    overlap_note = ""
    if args.sequence_parallel:
        overlap_note = f", sp, tp_overlap={args.tp_overlap}"
    if args.dp_reduce_bucket_mb:
        overlap_note += (f", dp_reduce_bucket={args.dp_reduce_bucket_mb:g}MiB"
                         f" {args.dp_reduce_dtype}")
    if args.zero:
        overlap_note += f", zero={args.zero}"
    print(json.dumps({
        "metric": (f"tokens/sec/chip ({args.model} {args.family}, bf16, b{B}xt{T}, "
                   f"dp={args.dp}, tp={tp}, remat={remat_used}, "
                   f"attn={attn_used}, steps_per_dispatch={spd}"
                   f"{bucket_note}{overlap_note})"),
        "value": round(tokens_per_sec_per_chip, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": round(mfu / 0.30, 4) if mfu is not None else None,
        # r12: which ZeRO stage trained and what it actually left resident
        # per device — the memory claim is measured, not asserted
        "zero_stage": args.zero,
        "param_bytes_per_device": pbpd[0],
        **run_stamp(vars(args)),
    }))


if __name__ == "__main__":
    main()
