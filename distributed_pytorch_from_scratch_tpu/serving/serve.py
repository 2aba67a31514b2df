"""Offline serving benchmark CLI: loadgen -> continuous-batching engine.

`python -m distributed_pytorch_from_scratch_tpu.serving.serve \
     --ckpt_dir ... --tokenizer_path ... --rate 4 --num_requests 64`

Drives the continuous-batching engine (serving/engine.py) with a synthetic
Poisson/burst arrival stream (or a replayed trace) and reports the serving
metrics — TTFT / TPOT / queue-wait p50/p95, slot occupancy, tokens/s — as:

* ONE machine-readable JSON line on stdout (the bench.py convention),
* `serving_summary` + per-request `serve_request` MetricsWriter events and
  Chrome-trace spans (prefill / decode_step per dispatch) under --log_dir,
  so `scripts/summarize_run.py` and the Perfetto timeline render a serving
  run exactly like a training run.

`--random_init` serves fresh random weights at the flag shape (throughput
and latency depend on shapes, not values — checkpoint-free benchmarking,
the bench.py --decode convention). `--dry_run` shrinks everything to a
tiny CPU-runnable smoke (tier-1 coverage: the CLI surface cannot rot on
images without chips).
"""

from __future__ import annotations

import argparse
import json
import sys

import dataclasses

from ..cli import add_model_shape_args, build_model_config
from ..obs.runindex import run_stamp
from ..config import (BOS_TOKEN, EOS_TOKEN, MODEL_PRESETS, MeshConfig,
                      ModelConfig, model_preset)
from ..models import FAMILIES, build_model
from ..ops.attention import resolve_attention_impl
from ..runtime.compile_cache import compile_cache_stats, enable_compile_cache
from ..runtime.mesh import make_mesh

_DRY_CFG = ModelConfig(attn_dim=32, ffn_dim=64, num_heads=4, num_layers=2,
                       vocab_size=64, maxlen=64)
# dry-run drafter: even smaller than the dry target, so the smoke actually
# exercises the drafter-cheaper-than-target shape the feature assumes
_DRY_DRAFTER_CFG = ModelConfig(attn_dim=16, ffn_dim=32, num_heads=2,
                               num_layers=1, vocab_size=64, maxlen=64)


def get_serve_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__)
    g = p.add_argument_group("model")
    g.add_argument("--ckpt_dir", default=None,
                   help="serve this checkpoint (validated complete before "
                        "assembly); omit with --random_init/--dry_run")
    g.add_argument("--iter", type=int, default=None,
                   help="checkpoint iteration (default: latest)")
    g.add_argument("--random_init", action="store_true",
                   help="serve fresh random weights at the flag shape "
                        "(checkpoint-free load benchmarking)")
    g.add_argument("--tokenizer_path", "-t", default=None,
                   help="supplies vocab_size and the real EOS id; omit to "
                        "use --vocab_size and EOS id 1 (the shipped "
                        "tokenizer's convention)")
    g.add_argument("--vocab_size", type=int, default=1024,
                   help="vocab for --random_init without a tokenizer")
    g.add_argument("--family", choices=list(FAMILIES), default="llama")
    g.add_argument("--tp_size", type=int, default=1)
    add_model_shape_args(g)

    g = p.add_argument_group("engine")
    g.add_argument("--slots", type=int, default=8,
                   help="KV-pool slots = max concurrently decoding requests")
    g.add_argument("--buf_len", type=int, default=0,
                   help="per-slot cache length (0 = longest prompt + "
                        "--max_new_tokens + 2)")
    g.add_argument("--max_new_tokens", type=int, default=64)
    g.add_argument("--prefill_bucket", type=int, default=64,
                   help="prefill width bucket (prompts pad to a multiple "
                        "of this, not to the full buffer); 0 = off")
    g.add_argument("--max_prefill_batch", type=int, default=4,
                   help="max prompts per prefill dispatch (same-bucket "
                        "FIFO neighbours ride together)")
    g.add_argument("--queue_limit", type=int, default=0,
                   help="backpressure: max waiting requests (arrivals past "
                        "it are rejected and counted); 0 = unbounded")
    g.add_argument("--temperature", type=float, default=0.0,
                   help="0 = greedy; > 0 samples with per-request seeds")
    g.add_argument("--decode_top_k", type=int, default=0)
    g.add_argument("--decode_top_p", type=float, default=0.0)
    g.add_argument("--decode_weight_dtype", choices=["native", "int8"],
                   default="native",
                   help="'int8' serves weight-only-quantized decode "
                        "weights (per-output-channel scales, dequant-on-"
                        "use inside the compiled programs — cuts the "
                        "weight-read HBM floor; ops/quant.py). Works for "
                        "both engines")

    g = p.add_argument_group("paged engine (serving v2)")
    g.add_argument("--paged", action="store_true",
                   help="serve through the PAGED engine: page-table KV "
                        "cache with COW prefix reuse, chunked prefill, and "
                        "the SLO-aware scheduler (docs/SERVING.md v2)")
    g.add_argument("--page_size", type=int, default=64,
                   help="--paged: tokens per KV page")
    g.add_argument("--kv_dtype", choices=["native", "int8"],
                   default="native",
                   help="--paged: KV-page storage dtype. 'int8' stores "
                        "block-scaled codes + per-head-vector scales "
                        "(~2x the tokens per HBM byte at hd 64; greedy "
                        "quality pinned in tests/test_quant.py); the "
                        "speculative drafter pool inherits it")
    g.add_argument("--paged_attn", choices=["gather", "pallas"],
                   default="gather",
                   help="--paged: the attend over the page table. "
                        "'gather' materializes the dense page view per "
                        "step (the oracle); 'pallas' walks the "
                        "(slots, max_pages) table in place on TPU "
                        "(ops/pallas/paged_attention.py — no per-step "
                        "HBM copy of the context, int8 dequant fused "
                        "into the block loop). Token-identical greedy "
                        "output by contract; refused on a non-TPU "
                        "backend (the kernel is compiled by Mosaic)")
    g.add_argument("--num_pages", type=int, default=0,
                   help="--paged: page-pool HBM budget in pages (0 = "
                        "slots x ceil(buf_len/page_size), i.e. no "
                        "oversubscription — raise slots past the pool to "
                        "oversubscribe)")
    g.add_argument("--cp", type=int, default=1,
                   help="--paged: context-parallel ranks; the page pool "
                        "shards over the 'cp' mesh axis (each rank owns "
                        "1/cp of the pages, so per-chip KV bytes shrink "
                        "~1/cp at equal context), chunked prefill rings "
                        "the query chunk around cp, and decode combines "
                        "per-rank partial (out, lse). Greedy output is "
                        "token-identical to cp=1 (docs/SERVING.md, "
                        "ISSUE 18). The speculative drafter stays cp=1")
    g.add_argument("--prefill_chunk", type=int, default=128,
                   help="--paged: prefill positions per chunk; a live "
                        "stream's decode never stalls by more than one "
                        "chunk")
    g.add_argument("--slo_classes", default="interactive=0.25,standard=1.0,"
                                            "batch=8.0",
                   help="--paged: TTFT deadline classes, name=seconds "
                        "pairs (scheduler.parse_slo_classes)")
    g.add_argument("--default_class", default="standard",
                   help="--paged: class for requests that name none")
    g.add_argument("--class_mix", default="",
                   help="loadgen: draw request classes by weight, e.g. "
                        "'interactive=1,batch=1' (empty = default class)")
    g.add_argument("--tenants", type=int, default=1,
                   help="loadgen: spread requests over N tenants "
                        "(the fair-queuing axis)")
    g.add_argument("--shared_prefix_len", type=int, default=0,
                   help="loadgen: prepend one common random prefix of N "
                        "tokens to every prompt (system-prompt stand-in; "
                        "feeds the COW prefix cache)")
    g.add_argument("--interleave", action="store_true",
                   help="loadgen: alternate short/long prompts "
                        "(prompt_len_min / prompt_len_max) instead of "
                        "uniform lengths — the head-of-line stress")

    g = p.add_argument_group("speculative decoding (--paged only)")
    g.add_argument("--speculate", type=int, default=0, metavar="K",
                   help="draft K tokens per round with the drafter model "
                        "and verify them in ONE target dispatch (exact "
                        "rejection sampling — greedy output is token-"
                        "identical to the plain paged engine); 0 = off")
    g.add_argument("--drafter_model", choices=sorted(MODEL_PRESETS),
                   default="tiny",
                   help="drafter shape preset (vocab is forced to the "
                        "target's; ROADMAP's cheap-drafter default is "
                        "'tiny')")
    g.add_argument("--drafter_ckpt_dir", default=None,
                   help="load drafter weights from this checkpoint "
                        "(default: random init — fine for latency "
                        "benchmarks, useless acceptance on real text)")
    g.add_argument("--drafter_iter", type=int, default=None,
                   help="drafter checkpoint iteration (default: latest)")
    g.add_argument("--drafter_pages", type=int, default=0,
                   help="drafter page-pool budget in pages (0 = every "
                        "slot can hold its full drafter row); counts "
                        "against the serving HBM budget in bench A/Bs")
    g.add_argument("--debug_host_sampler", action="store_true",
                   help="ABLATION: switch to host-side sampling "
                        "(materialises full-vocab logits on the host every "
                        "step) — prices the host-round-trip cost the fused "
                        "in-program sampler (the production path since the "
                        "engines shipped) avoids; excludes --speculate")

    g = p.add_argument_group("loadgen")
    g.add_argument("--num_requests", type=int, default=32)
    g.add_argument("--rate", type=float, default=4.0,
                   help="poisson arrival rate, requests/second")
    g.add_argument("--arrival", choices=["poisson", "burst", "replay"],
                   default="poisson")
    g.add_argument("--replay", default=None,
                   help="jsonl trace for --arrival replay (loadgen.py "
                        "schema)")
    g.add_argument("--prompt_len_min", type=int, default=8)
    g.add_argument("--prompt_len_max", type=int, default=64)
    g.add_argument("--seed", type=int, default=0)

    g = p.add_argument_group("observability")
    g.add_argument("--trace_requests", action="store_true",
                   help="per-request span timelines (obs/reqtrace.py): "
                        "every request emits a request_trace event + a "
                        "Chrome-trace track under --log_dir, and the "
                        "summary carries the k-worst-TTFT/TPOT exemplars "
                        "WITH their timelines (docs/OBSERVABILITY.md)")
    g.add_argument("--flight_records", action="store_true",
                   help="anomaly flight recorder (obs/flight.py): pool "
                        "stats + scheduler decisions ring-buffered; "
                        "PoolExhausted preemptions and SLO-attainment "
                        "collapses dump flightdump_*.json to --log_dir")
    g.add_argument("--flight_ring", type=int, default=512,
                   help="--flight_records: ring capacity (events); "
                        "0 disables the recorder (train.py semantics)")
    g.add_argument("--metrics_port", type=int, default=None,
                   help="live telemetry exporter (obs/telemetry.py): "
                        "serve gauges/counters at http://127.0.0.1:PORT"
                        "/metrics.json (JSON) and /metrics (Prometheus "
                        "text); 0 = ephemeral (the bound port is printed "
                        "and lands in the summary record). A busy port "
                        "refuses loudly up front")
    g.add_argument("--rollup_interval", type=float, default=1.0,
                   help="--metrics_port: seconds between "
                        "telemetry_snapshot events mirrored into "
                        "metrics.jsonl (the fleet collector's food)")
    g.add_argument("--profile_on_anomaly", type=int, default=0,
                   metavar="STEPS",
                   help="arm a bounded jax.profiler window of N decode "
                        "steps when a flight dump fires (PoolExhausted "
                        "preemption, SLO collapse), cross-linked from "
                        "the dump's 'profile' field; needs "
                        "--flight_records; 0 = off")
    g.add_argument("--profile_every", type=int, default=0, metavar="N",
                   help="duty-cycled MEASURED attribution "
                        "(training/metrics.DutyCycleProfiler): every N "
                        "decode steps capture a --profile_window-step "
                        "jax.profiler window, parse it (obs/profparse) "
                        "and land a profile_attribution event in the "
                        "--log_dir metrics chain; 0 = off (exactly zero "
                        "cost: no captures, no events)")
    g.add_argument("--profile_window", type=int, default=4, metavar="W",
                   help="--profile_every: decode steps per capture "
                        "window (must be <= N)")
    g.add_argument("--profile_budget_mb", type=float, default=64.0,
                   help="--profile_every: total on-disk capture budget; "
                        "exhaustion stops sampling BETWEEN windows "
                        "(never mid-window), counted in the summary")
    g.add_argument("--metrics_max_mb", type=float, default=0.0,
                   help="rotate metrics.jsonl past N MiB (-> "
                        "metrics.001.jsonl ... via schema-valid "
                        "'rotated' continuation events; consumers "
                        "follow the chain); 0 = unbounded")
    g.add_argument("--control", choices=["off", "advise", "act"],
                   default="off",
                   help="the obs v5 control plane (obs/control.py, "
                        "serving/controller.py): 'advise' computes SLO/"
                        "admission and drift-retune decisions and lands "
                        "them in the decision ledger with applied=false "
                        "(nothing mutates); 'act' additionally moves the "
                        "knobs — prefill chunk, admission limit, "
                        "speculation K, pages_per_block — at registered "
                        "safe points only. 'off' (default) is zero-cost: "
                        "no advisor, no events, no record fields")
    g.add_argument("--control_interval", type=int, default=32,
                   help="--control: decode steps between SLO-controller "
                        "evaluations (the adaptation + cooldown window)")
    g.add_argument("--control_force", action="store_true",
                   help="--control act: let an online pages_per_block "
                        "retune overwrite a SWEPT block-cache entry "
                        "(default: the write is refused and the decision "
                        "lands applied=false with the refusal — online "
                        "never silently shadows a sweep)")

    g = p.add_argument_group("other")
    g.add_argument("--log_dir", default="serve_logs",
                   help="obs output: trace.jsonl/trace.json spans + "
                        "metrics.jsonl events")
    g.add_argument("--dry_run", action="store_true",
                   help="tiny random-init model + a 6-request burst on CPU "
                        "— the tier-1 smoke; ignores --ckpt_dir")
    args = p.parse_args(argv)
    if (args.decode_top_k or args.decode_top_p) and not args.temperature:
        p.error("--decode_top_k/--decode_top_p need --temperature > 0")
    if args.cp < 1:
        p.error(f"--cp must be >= 1, got {args.cp}")
    # class/tenant mixes and the page budget only matter to the paged
    # engine; a silent no-op would misreport what the run measured
    if not args.paged:
        if args.num_pages:
            p.error("--num_pages is a --paged knob")
        if args.kv_dtype != "native":
            p.error("--kv_dtype is a --paged knob (the slot pool stores "
                    "the compute dtype; only PagedKVPool quantizes)")
        if args.paged_attn != "gather":
            p.error("--paged_attn is a --paged knob (the slot engine has "
                    "no page table to walk)")
        if args.cp != 1:
            p.error("--cp is a --paged knob (only the page pool shards "
                    "over cp; the slot engine replicates its caches — "
                    "add --paged for long-context cp serving)")
        if args.class_mix:
            p.error("--class_mix needs --paged (the FIFO engine has no "
                    "SLO classes)")
        if args.tenants != 1:
            p.error("--tenants needs --paged (the FIFO engine ignores "
                    "tenants — the run would measure nothing fair)")
    if args.speculate:
        if not args.paged:
            p.error("--speculate runs over the paged cache; add --paged")
        if args.debug_host_sampler:
            p.error("--debug_host_sampler is the NON-speculative ablation "
                    "knob (a speculative round never materialises host "
                    "logits); drop --speculate to measure it")
        if args.drafter_iter is not None and not args.drafter_ckpt_dir:
            p.error("--drafter_iter needs --drafter_ckpt_dir (without one "
                    "the drafter is random-init and the iter is ignored)")
    elif (args.drafter_ckpt_dir or args.drafter_pages
          or args.drafter_iter is not None):
        p.error("--drafter_ckpt_dir/--drafter_iter/--drafter_pages need "
                "--speculate K")
    if args.arrival == "replay" and not args.replay and not args.dry_run:
        p.error("--arrival replay needs --replay PATH")
    if args.profile_on_anomaly and not args.flight_records:
        p.error("--profile_on_anomaly arms on flight-dump triggers; add "
                "--flight_records")
    if args.profile_every:
        if args.profile_on_anomaly:
            p.error("--profile_every excludes --profile_on_anomaly (both "
                    "drive the one-capture-at-a-time device profiler; "
                    "pick the duty cycle or the anomaly trigger)")
        if not args.log_dir:
            p.error("--profile_every needs a metrics dir: the parsed "
                    "profile_attribution events land in --log_dir's "
                    "metrics chain (point --log_dir somewhere writable)")
        if not 1 <= args.profile_window <= args.profile_every:
            p.error(f"--profile_window must be in [1, --profile_every] "
                    f"(a window longer than the duty period would re-arm "
                    f"mid-capture), got window {args.profile_window} with "
                    f"every {args.profile_every}")
        if args.profile_budget_mb <= 0:
            p.error(f"--profile_budget_mb must be > 0, got "
                    f"{args.profile_budget_mb}")
    if args.control != "off":
        if not args.paged:
            p.error("--control drives the paged engine's scheduler "
                    "admission and prefill chunking (the slot engine has "
                    "none of those knobs); add --paged")
        if args.control_interval < 1:
            p.error(f"--control_interval must be >= 1, got "
                    f"{args.control_interval}")
    if args.control_force and args.control != "act":
        p.error("--control_force needs --control act (only act mode "
                "writes the block cache; nothing can shadow a swept "
                "entry otherwise)")
    if args.metrics_port is not None and args.metrics_port < 0:
        p.error(f"--metrics_port must be >= 0 (0 = ephemeral), got "
                f"{args.metrics_port}")
    if args.metrics_port is not None and args.rollup_interval <= 0:
        p.error("--rollup_interval must be > 0 (seconds between "
                "telemetry_snapshot events)")
    if not args.dry_run and not args.random_init and not args.ckpt_dir:
        p.error("pick a weight source: --ckpt_dir, --random_init, or "
                "--dry_run")
    return args


def require_writable_dir(path: str, why: str) -> None:
    """Loud up-front refusal when an obs output dir cannot take writes:
    a traced run that silently drops its timelines is worse than no run
    (the flags' whole point is the post-mortem artifact)."""
    import os

    try:
        os.makedirs(path, exist_ok=True)
        probe = os.path.join(path, ".obs_write_probe")
        with open(probe, "w") as f:
            f.write("ok")
        os.remove(probe)
    except OSError as e:
        raise SystemExit(
            f"{why}: trace output dir {path!r} is not writable "
            f"({type(e).__name__}: {e}) — point --log_dir at a writable "
            f"directory or drop the flag")


def _load_params(args, model, mesh):
    import jax

    if args.random_init or args.dry_run or not args.ckpt_dir:
        return jax.device_put(model.init(jax.random.key(args.seed)),
                              model.shardings(mesh))
    from ..training.checkpoint import latest_step, load_checkpoint
    step = args.iter if args.iter is not None else latest_step(args.ckpt_dir)
    if step is None:
        raise SystemExit(f"no checkpoints found in {args.ckpt_dir}")
    # load_checkpoint refuses an incomplete shard set up front with the
    # missing-rank list (training/checkpoint.validate_checkpoint) — no
    # KeyError mid-assemble, no separate pre-check needed
    template = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    params, _, _ = load_checkpoint(args.ckpt_dir, step, template,
                                   model.specs())
    print(f"serving checkpoint iter {step} from {args.ckpt_dir}",
          file=sys.stderr)
    return jax.device_put(params, model.shardings(mesh))


def _build_drafter(args, vocab_size: int, mesh, family: str):
    """Drafter model + params for --speculate: the named preset reshaped to
    the TARGET's vocab (the verify step compares distributions over one
    vocabulary), weights from --drafter_ckpt_dir or random init."""
    import jax

    if args.dry_run:
        dcfg = _DRY_DRAFTER_CFG
    else:
        dcfg = model_preset(args.drafter_model)
    dcfg = dataclasses.replace(
        dcfg, vocab_size=vocab_size,
        compute_dtype="bfloat16" if getattr(args, "bf16", True) and
        not args.dry_run else "float32")
    dmodel = build_model(family, dcfg, tp_size=args.tp_size)
    if args.drafter_ckpt_dir:
        from ..training.checkpoint import latest_step, load_checkpoint
        step = (args.drafter_iter if args.drafter_iter is not None
                else latest_step(args.drafter_ckpt_dir))
        if step is None:
            raise SystemExit(
                f"no drafter checkpoints found in {args.drafter_ckpt_dir}")
        template = jax.eval_shape(lambda: dmodel.init(jax.random.key(0)))
        dparams, _, _ = load_checkpoint(args.drafter_ckpt_dir, step,
                                        template, dmodel.specs())
        print(f"drafter checkpoint iter {step} from {args.drafter_ckpt_dir}",
              file=sys.stderr)
    else:
        dparams = dmodel.init(jax.random.key(args.seed + 1))
    return dmodel, jax.device_put(dparams, dmodel.shardings(mesh))


def serve(args: argparse.Namespace) -> dict:
    import time as _time

    from ..obs import (FlightRecorder, RequestTracer, SpanTracer,
                       TelemetryExporter)
    from ..training.metrics import AnomalyProfiler, MetricsWriter
    from .engine import ContinuousBatchingEngine
    from .loadgen import replay_requests, run_loadgen, synthetic_requests

    import jax

    # refuse a kernel this backend cannot compile before any weights load:
    # it never degrades to gather
    from ..ops.pallas.paged_attention import check_paged_attn_impl
    check_paged_attn_impl(args.paged_attn)

    if args.trace_requests or args.flight_records \
            or args.metrics_port is not None or args.profile_every:
        require_writable_dir(
            args.log_dir,
            "--trace_requests/--flight_records/--metrics_port/"
            "--profile_every")

    eos_id = 1  # the shipped tokenizer's EOS (tokenizer/tokenizer.json)
    vocab_size = args.vocab_size
    if args.tokenizer_path:
        from tokenizers import Tokenizer as HFTokenizer
        tok = HFTokenizer.from_file(args.tokenizer_path)
        vocab_size = tok.get_vocab_size()
        eos_id = tok.token_to_id(EOS_TOKEN)
        if eos_id is None or tok.token_to_id(BOS_TOKEN) is None:
            raise SystemExit(f"tokenizer {args.tokenizer_path} lacks the "
                             f"{BOS_TOKEN}/{EOS_TOKEN} specials")

    if args.dry_run:
        cfg = _DRY_CFG
        vocab_size = cfg.vocab_size
        args.slots, args.max_prefill_batch = 4, 2
        args.num_requests, args.arrival = 6, "burst"
        args.prompt_len_min, args.prompt_len_max = 4, 12
        args.max_new_tokens = min(args.max_new_tokens, 8)
        args.buf_len, args.prefill_bucket = 24, 8
        if args.paged:       # tiny pages so the smoke crosses boundaries
            args.page_size, args.prefill_chunk = 8, 8
            args.num_pages = 0
            if not args.class_mix:
                args.class_mix = "interactive=1,standard=1"
            args.shared_prefix_len = max(args.shared_prefix_len, 4)
    else:
        cfg = build_model_config(args, vocab_size)

    mesh = make_mesh(MeshConfig(tp=args.tp_size, cp=args.cp))
    model = build_model(args.family, cfg, tp_size=args.tp_size,
                        cp_size=args.cp)
    params = _load_params(args, model, mesh)

    if args.arrival == "replay" and args.replay:
        requests = replay_requests(args.replay)
    else:
        from .scheduler import parse_slo_classes
        mix = parse_slo_classes(args.class_mix) if args.class_mix else None
        requests = synthetic_requests(
            args.num_requests, args.prompt_len_min, args.prompt_len_max,
            args.max_new_tokens, vocab_size, seed=args.seed,
            rate=args.rate, arrival=args.arrival, class_mix=mix,
            tenants=args.tenants,
            shared_prefix_len=args.shared_prefix_len,
            interleave=args.interleave)
    longest = max(len(r.prompt) for r in requests)
    buf_len = args.buf_len or (longest + args.max_new_tokens + 2)
    cap = getattr(model, "max_decode_positions", None)
    if cap is not None and buf_len > cap:
        if cap < longest + 2:
            raise SystemExit(f"prompts need {longest + 2} positions but the "
                             f"model's position table has {cap}")
        print(f"Warning: clamping serve buffer {buf_len} -> {cap} (learned "
              f"position table size)", file=sys.stderr)
        buf_len = cap

    tracer = SpanTracer(args.log_dir, process_name="serve")
    writer = MetricsWriter(args.log_dir, process_index=0,
                           max_bytes=int(args.metrics_max_mb * 2**20))
    # live telemetry exporter (ISSUE 12): starts BEFORE the engine so a
    # hung prefill is still scrapeable; a busy port dies loudly here
    telemetry = None
    if args.metrics_port is not None:
        telemetry = TelemetryExporter(
            writer=writer, rollup_interval=args.rollup_interval)
        port = telemetry.start(args.metrics_port)
        print(f"telemetry exporter: http://127.0.0.1:{port}/metrics.json "
              f"(Prometheus text at /metrics)", file=sys.stderr)
    elif args.control != "off":
        # headless registry (no HTTP endpoint): controller decisions
        # cross-link a telemetry_snapshot emitted at decision time, so
        # the control plane needs the registry even without --metrics_port
        telemetry = TelemetryExporter(writer=writer)
    profiler = (AnomalyProfiler(args.log_dir,
                                window_steps=args.profile_on_anomaly,
                                writer=writer)
                if args.profile_on_anomaly and args.flight_ring > 0
                else None)
    duty = None
    if args.profile_every:
        from ..training.metrics import DutyCycleProfiler
        duty = DutyCycleProfiler(args.log_dir, args.profile_every,
                                 args.profile_window,
                                 args.profile_budget_mb, writer=writer)
    flight = (FlightRecorder(args.log_dir, maxlen=args.flight_ring,
                             profiler=profiler)
              if args.flight_records and args.flight_ring > 0 else None)
    rt = (RequestTracer(writer=writer, tracer=tracer, flight=flight,
                        clock=_time.monotonic)
          if args.trace_requests else None)
    controller = advisor = None
    try:
        kv_dtype = None if args.kv_dtype == "native" else args.kv_dtype
        wdtype = (None if args.decode_weight_dtype == "native"
                  else args.decode_weight_dtype)
        if args.paged:
            from .scheduler import parse_slo_classes
            paged_kw = dict(
                num_slots=args.slots, buf_len=buf_len, eos_id=eos_id,
                page_size=args.page_size, num_pages=args.num_pages,
                prefill_chunk=args.prefill_chunk,
                temperature=args.temperature, top_k=args.decode_top_k,
                top_p=args.decode_top_p, kv_dtype=kv_dtype,
                decode_weight_dtype=wdtype,
                paged_attn_impl=args.paged_attn,
                slo_classes=parse_slo_classes(args.slo_classes),
                default_class=args.default_class,
                max_queue=args.queue_limit, tracer=tracer, writer=writer,
                request_tracer=rt, flight=flight, telemetry=telemetry,
                duty_profiler=duty)
            if args.speculate:
                from .speculative import SpeculativeEngine
                dmodel, dparams = _build_drafter(args, cfg.vocab_size, mesh,
                                                 args.family)
                engine = SpeculativeEngine(
                    model, mesh, params, dmodel, dparams,
                    speculate_k=args.speculate,
                    drafter_pages=args.drafter_pages, **paged_kw)
            else:
                from .engine import PagedEngine
                engine = PagedEngine(
                    model, mesh, params,
                    debug_host_sampler=args.debug_host_sampler, **paged_kw)
        else:
            engine = ContinuousBatchingEngine(
                model, mesh, params, num_slots=args.slots, buf_len=buf_len,
                eos_id=eos_id, temperature=args.temperature,
                top_k=args.decode_top_k, top_p=args.decode_top_p,
                prefill_bucket=args.prefill_bucket,
                max_prefill_batch=args.max_prefill_batch,
                max_queue=args.queue_limit,
                debug_host_sampler=args.debug_host_sampler,
                decode_weight_dtype=wdtype,
                tracer=tracer, writer=writer,
                request_tracer=rt, flight=flight, telemetry=telemetry,
                duty_profiler=duty)
        if args.control != "off":
            from ..obs.control import RetuneAdvisor, control_safe_point
            from .controller import SLOController
            controller = SLOController(engine, args.control, writer=writer,
                                       telemetry=telemetry,
                                       interval=args.control_interval)
            # the engine's decorated _control_tick (its host-side decode
            # tick) is the safe point that drives tick()+apply_decisions()
            engine.controller = controller
            if duty is not None:
                # drift-driven retuning rides the duty profiler: the
                # on_attribution hook fires BETWEEN capture windows (a
                # registered safe point), with the parsed reconcile
                advisor = RetuneAdvisor(args.control, writer=writer,
                                        telemetry=telemetry)
                advisor.register_knob(
                    "prefill_chunk",
                    lambda: engine.prefill_chunk,
                    lambda v: setattr(engine, "prefill_chunk", int(v)),
                    lo=1)
                if args.speculate:
                    advisor.register_knob(
                        "speculate_k", lambda: engine.k,
                        lambda v: setattr(engine, "k", int(v)), lo=1)
                last_capture = {"id": None}
                if args.paged_attn == "pallas":
                    from ..ops.pallas.paged_attention import (
                        PagedBlockConfig, get_paged_block_config,
                        record_online_paged_config)
                    hd = cfg.attn_dim // cfg.num_heads
                    kvd = (None if args.kv_dtype == "native"
                           else args.kv_dtype)
                    advisor.register_knob(
                        "pages_per_block",
                        lambda: get_paged_block_config(
                            args.page_size, hd, kvd).pages_per_block,
                        lambda v: record_online_paged_config(
                            args.page_size, hd, kvd,
                            PagedBlockConfig(int(v)),
                            capture=last_capture["id"],
                            force=args.control_force),
                        lo=1)

                @control_safe_point
                def _on_attribution(fields):
                    # between capture windows: observe, then actuate —
                    # the decoration is the graftcheck registration
                    last_capture["id"] = (fields or {}).get("capture")
                    advisor.observe_attribution(fields)
                    from ..training.metrics import hbm_watermarks
                    marks = hbm_watermarks()
                    advisor.observe_hbm({"devices": marks or [],
                                         "available": marks is not None})
                    advisor.apply_decisions()

                duty.on_attribution = _on_attribution
        summary = run_loadgen(engine, requests)
    finally:
        # profiler before exporter before writer: an open capture window
        # finalises (and parses into its profile_attribution event), the
        # exporter's LAST snapshot event lands, then the jsonl stream
        # closes
        if profiler is not None:
            profiler.close()
        if duty is not None:
            duty.close()
        # control plane after the duty profiler (its close() can finalise
        # a window and hand the advisor one last reconcile) and before
        # the exporter/writer (ledger flushes are events)
        if advisor is not None:
            advisor.close()
        if controller is not None:
            controller.close()
        if telemetry is not None:
            telemetry.close()
        path = tracer.close()
        writer.close()
    fmt = lambda v: "-" if v is None else f"{v:.1f}"
    print(f"serve[{args.family} tp{args.tp_size}]: {summary['completed']}/"
          f"{summary['requests']} requests ({summary['rejected']} rejected) "
          f"in {summary['wall_s']:.1f}s — "
          f"{summary['tokens_per_sec']:.0f} tok/s, occupancy "
          f"{summary['slot_occupancy_mean']:.2f}, TTFT p50/p95 "
          f"{fmt(summary['ttft_ms_p50'])}/{fmt(summary['ttft_ms_p95'])}ms, "
          f"TPOT p50/p95 {fmt(summary['tpot_ms_p50'])}/"
          f"{fmt(summary['tpot_ms_p95'])}ms, queue p50/p95 "
          f"{fmt(summary['queue_wait_ms_p50'])}/"
          f"{fmt(summary['queue_wait_ms_p95'])}ms"
          + (f"; pad waste eliminated "
             f"{100 * summary['prefill_pad_waste_eliminated']:.0f}%"
             if summary["prefill_pad_waste_eliminated"] > 0 else "")
          + (f"; kv util {summary['kv_util_mean']:.2f}, prefix hits "
             f"{100 * summary['prefix_hit_rate']:.0f}%, "
             f"{summary['preemptions']} preempted"
             if "kv_util_mean" in summary else "")
          + (f"; spec k={summary['speculate_k']}: "
             f"{summary['accepted_tokens_per_dispatch']:.2f} tok/dispatch, "
             f"acceptance {100 * summary['acceptance_rate']:.0f}%"
             if "speculate_k" in summary else "")
          + (f"; trace {path}" if path else ""), file=sys.stderr)
    rec = {
        "metric": (f"serving tokens/sec ({args.family}, tp={args.tp_size}, "
                   + ("paged, " if args.paged else "")
                   + (f"speculate k={args.speculate} "
                      f"({args.drafter_model} drafter), "
                      if args.speculate else "")
                   + ("HOST-sampler ablation, "
                      if args.debug_host_sampler else "")
                   + f"slots={args.slots}, {args.arrival} arrivals"
                   + (f" @{args.rate:g}/s" if args.arrival == "poisson"
                      else "") + ")"),
        "value": summary["tokens_per_sec"],
        "unit": "tokens/sec (serving)",
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "device_count": jax.device_count(),
        "attn_impl": resolve_attention_impl(model.attn_impl),  # prefill
        "compile_cache": compile_cache_stats(),
        **{k: summary[k] for k in (
            "requests", "completed", "rejected", "invalid", "wall_s",
            "generated_tokens", "tokens_digest",
            "slot_occupancy_mean", "ttft_ms_p50", "ttft_ms_p95",
            "tpot_ms_p50", "tpot_ms_p95", "queue_wait_ms_p50",
            "queue_wait_ms_p95", "prefill_pad_waste_eliminated")},
    }
    for k in ("kv_dtype", "paged_attn", "cp", "pages_per_rank", "num_pages",
              "kv_util_mean", "kv_fragmentation_mean", "prefix_hit_rate",
              "cow_copies", "preemptions", "max_live",
              "max_interleaved_prefill_positions", "slo_attainment",
              "speculate_k", "spec_rounds", "accepted_tokens_per_dispatch",
              "acceptance_rate", "acceptance_rate_by_position",
              "rounds_per_request", "drafter_ms_total", "target_ms_total",
              "worst_ttft_rids", "worst_tpot_rids"):
        if k in summary:
            rec[k] = summary[k]
    if args.debug_host_sampler:
        rec["debug_host_sampler"] = True
    if args.decode_weight_dtype != "native":
        rec["decode_weight_dtype"] = args.decode_weight_dtype
    if args.trace_requests:
        rec["trace_requests"] = True
    if telemetry is not None and telemetry.port is not None:
        rec["metrics_port"] = telemetry.port
    if telemetry is not None:
        rec["telemetry_snapshots"] = telemetry.snapshots
    if controller is not None:
        rec["control"] = args.control
        rec["controller"] = controller.summary()
    if advisor is not None:
        rec["tuning"] = advisor.summary()
    if flight is not None:
        rec["flight_dumps"] = list(flight.dumps)
        for d in flight.dumps:
            print(f"flight dump written: {d}", file=sys.stderr)
    if profiler is not None:
        rec["anomaly_profiles"] = list(profiler.captures)
        rec["profile_attributions"] = profiler.attributions
        for d in profiler.captures:
            print(f"anomaly profile captured: {d}", file=sys.stderr)
    if duty is not None:
        rec["profile_captures"] = list(duty.captures)
        rec["profile_attributions"] = duty.attributions
        rec["profile_windows_skipped"] = duty.windows_skipped
        print(f"duty profiler: {len(duty.captures)} capture(s), "
              f"{duty.attributions} attributed, "
              f"{duty.bytes_used / 2**20:.1f} MiB used"
              + (f", {duty.windows_skipped} window(s) skipped after "
                 f"budget exhaustion" if duty.windows_skipped else ""),
              file=sys.stderr)
    # ISSUE 17: provenance stamp (config fingerprint + git rev) — the
    # run-forensics join key every summary record carries uniformly
    rec.update(run_stamp(vars(args)))
    print(json.dumps(rec))
    return summary


def main(argv=None) -> dict:
    enable_compile_cache()
    return serve(get_serve_args(argv))


if __name__ == "__main__":
    main()
