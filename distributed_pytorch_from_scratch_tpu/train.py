"""Training entry point.

`python -m distributed_pytorch_from_scratch_tpu.train --tp_size N --data_path tokens.json ...`

Capability parity with `/root/reference/train.py` (flags `train.py:25-52`,
loop `train.py:55-146`), TPU-native:

* no `mp.spawn`/NCCL rendezvous — one process drives all visible chips via a
  ('dp','tp') mesh (`--dp_size` is the BASELINE config-5 extension; the
  reference is TP-only);
* dtype is an explicit flag (`--bf16`), not the DTYPE env var;
* the step is one donated jitted XLA program (see training/train_step.py);
* checkpoints carry optimizer state, so `--resume` continues exactly — the
  reference can only save (`train.py:121-133`), never resume;
* same logging surface: avg CE loss, lr, device memory, checkpoint filenames
  with iter/loss metadata, retention pruning.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import signal
import threading
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import multihost_utils

from .config import (IGNORE_INDEX, MODEL_PRESETS, REMAT_CHOICES, MeshConfig,
                     ModelConfig, OptimizerConfig, model_preset)
from .data.dataset import get_dataloader
from .data.prefetch import Prefetcher, stack_window, window_stream
from .models import FAMILIES, build_model, facts_family, family_class
from .obs import TrainObserver, analyze_compiled, format_analysis
from .obs.runindex import run_stamp
from .ops.attention import resolve_attention_impl
from .runtime import compile_cache
from .runtime.compile_cache import compile_cache_stats, enable_compile_cache
from .runtime.mesh import (batch_feeder, init_multihost, make_mesh,
                           process_info)
from .training.checkpoint import (AsyncCheckpointer, latest_step,
                                  load_checkpoint, map_moments)
from .training.metrics import (MetricsWriter, ProfilerTrace,
                               bd_counters_summary, dsa_counters_summary,
                               loop_counters_summary,
                               mixer_counters_summary, moe_counters_summary,
                               chip_peak_flops, device_memory_gib,
                               hbm_watermarks, model_flops_per_step,
                               param_bytes_by_device, publish_hbm)
from .training.optim import init_adam_state, schedule_lr
from .training.train_step import (build_grad_accum_step, build_train_step,
                                  build_train_step_multi, resolve_zero_stage)
from .training.zero import zero1_moment_shardings


# `recompile` events the record `train()` returns carries in full (the count
# is of all of them; metrics.jsonl has every one)
RECOMPILES_KEPT = 16


def get_train_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__)

    g = p.add_argument_group("distributed")
    g.add_argument("--tp_size", type=int, default=1)
    g.add_argument("--dp_size", type=int, default=1)
    g.add_argument("--cp_size", type=int, default=1,
                   help="context-parallel (sequence) axis size")
    g.add_argument("--cp_impl", choices=["ring", "ulysses"], default="ring")
    g.add_argument("--cp_layout", choices=["contiguous", "zigzag"],
                   default="contiguous",
                   help="zigzag: each cp shard gets an equally early+late "
                        "pair of sequence sub-chunks, balancing causal ring "
                        "work ~2x (ring impl only; needs maxlen %% (2*cp)==0)")
    g.add_argument("--sequence_parallel", nargs="?", const="on",
                   choices=["auto", "on", "off"], default="auto",
                   help="override of a default the program picks. "
                        "Megatron-style SP shards inter-block activations "
                        "over the tp axis (reduce-scatter/all-gather instead "
                        "of all-reduce). 'auto' (default): on when tp > 1, "
                        "the model is dense and the (cp-local) sequence "
                        "length divides by tp, off otherwise "
                        "(models/transformer.resolve_tp_layout); the bare "
                        "flag means 'on'")
    g.add_argument("--tp_overlap", choices=["auto", "off", "ring", "ring_q"],
                   default="auto",
                   help="override of a default the program picks. 'ring' "
                        "decomposes the SP tp collectives into ring "
                        "collective matmuls (ops/overlap.py): each ppermute "
                        "hop hides under the partial dot of the chunk in "
                        "hand, fwd and bwd; 'ring_q' puts int8 codes + "
                        "per-row scales on every hop (half the bf16 chunk "
                        "bytes; pinned bounds in tests/test_quant.py); both "
                        "need sequence parallelism. 'off' is the monolithic "
                        "path. 'auto' (default): 'ring' wherever sequence "
                        "parallelism is on by default and --pp_size is 1")
    g.add_argument("--zero", type=int, choices=[0, 1, 2, 3], default=None,
                   help="ZeRO stage over the dp axis (training/zero.py): "
                        "1 shards the Adam moments (2/dp optimizer memory); "
                        "2 also reduce-SCATTERS the grads (half the DP wire "
                        "bytes at identical buckets — implies the bucketed "
                        "reducer; --dp_reduce_dtype int8 rides the "
                        "quantized ring's reduce-scatter half) with one "
                        "param all-gather per step; 3 also shards the "
                        "PARAMS, gathered per layer on demand in fwd/bwd "
                        "(peak param HBM full/dp + one layer — the unlock "
                        "for models whose replica exceeds HBM x tp). "
                        "Stages 2/3: dense models, --pp_size 1, and "
                        "sequence parallelism whenever tp > 1 (the "
                        "default there); stage 3 "
                        "needs remat (dots/true/auto) and an f32 "
                        "--dp_reduce_dtype")
    g.add_argument("--zero1", action="store_true",
                   help="alias for --zero 1 (the PR 4-era flag): shard "
                        "Adam moments over the dp axis")
    g.add_argument("--dp_reduce_bucket_mb", type=float, default=0.0,
                   help="bucketed DP/ZeRO-1 gradient reduction: issue one "
                        "psum per <= N-MiB bucket (overlappable with the "
                        "remaining backward) instead of the end-of-step "
                        "whole-tree blob; 0 = off (the default transpose-"
                        "derived reducer). Dense models, --pp_size 1")
    g.add_argument("--dp_reduce_dtype", choices=["f32", "bf16", "int8"],
                   default="f32",
                   help="wire dtype for the bucketed DP grad reduce: 'bf16' "
                        "halves the reduction bytes, 'int8' quarters them "
                        "via the EQuARX-style block-scaled quantized ring "
                        "(ops/overlap.quantized_allreduce; f32 master "
                        "accumulate either way). Needs "
                        "--dp_reduce_bucket_mb > 0")
    g.add_argument("--ep_size", type=int, default=1,
                   help="expert-parallel axis size (MoE: experts shard over "
                        "'ep'; requires --num_experts; 'ep' also shards the "
                        "batch for the dense sublayers)")
    g.add_argument("--pp_size", type=int, default=1,
                   help="pipeline-parallel axis size: layers shard into "
                        "pp stages, microbatches flow through a GPipe "
                        "schedule (both model families)")
    g.add_argument("--pp_microbatches", type=int, default=0,
                   help="microbatches per pipeline step (default pp_size; "
                        "more microbatches = smaller bubble fraction "
                        "(pp-1)/(m+pp-1) but smaller per-microbatch work)")
    g.add_argument("--pp_remat_steps", action="store_true",
                   help="rematerialise each pipeline step: backward "
                        "residuals shrink to the (mb, t, d) step carries "
                        "(the 1F1B-style memory cut) for ~33%% recompute")
    g.add_argument("--pp_schedule", choices=["gpipe", "interleaved"],
                   default="gpipe",
                   help="'interleaved' = Megatron-style virtual stages: "
                        "each device owns pp_virtual round-robin layer "
                        "blocks and microbatches circulate the ring "
                        "pp_virtual times — bubble drops from "
                        "(pp-1)/(m+pp-1) to (pp-1)/(pp_virtual*m+pp-1) at "
                        "the cost of pp_virtual x more ppermute hops")
    g.add_argument("--pp_virtual", type=int, default=2,
                   help="virtual stages per device for "
                        "--pp_schedule interleaved (num_layers must "
                        "divide by pp_size*pp_virtual)")

    g = p.add_argument_group("training")
    g.add_argument("--lr", type=float, default=3e-4)
    g.add_argument("--clip_grad_norm", type=float, default=None,
                   help="global-norm gradient clipping (torch "
                        "clip_grad_norm_ semantics); off by default like "
                        "the reference")
    g.add_argument("--warmup_steps", type=int, default=2000)
    g.add_argument("--weight_decay", type=float, default=0.0,
                   help="decoupled weight decay (torch.optim.AdamW "
                        "semantics); 0 = plain Adam, the reference's setup")
    g.add_argument("--lr_schedule", choices=["onecycle", "cosine"],
                   default="onecycle",
                   help="'onecycle' = reference parity (torch OneCycleLR "
                        "incl. beta1 cycling); 'cosine' = linear warmup + "
                        "cosine decay to --cosine_min_ratio x lr, beta1 "
                        "fixed")
    g.add_argument("--cosine_min_ratio", type=float, default=0.1,
                   help="--lr_schedule cosine: final lr as a fraction of "
                        "--lr")
    g.add_argument("--max_steps", type=int, default=20000)
    g.add_argument("--log_interval", type=int, default=100)
    g.add_argument("--save_interval", type=int, default=1000)
    g.add_argument("--save_dir", type=str, default="./checkpoints")
    # keep the reference's (misspelled) flag name as an alias, train.py:40
    g.add_argument("--reserve_last_n_ckpts", "--reserv_last_n_ckpts",
                   type=int, default=-1)
    g.add_argument("--batch_size", "-b", type=int, default=32)
    g.add_argument("--bf16", action="store_true",
                   help="bf16 matmuls/activations (params and loss stay f32)")
    g.add_argument("--loss_mode", choices=["vocab_parallel", "gather"],
                   default="vocab_parallel")
    g.add_argument("--resume", action="store_true",
                   help="resume from the latest checkpoint in --save_dir")
    g.add_argument("--steps_per_dispatch", type=int, default=1,
                   help="run N optimizer steps per device dispatch "
                        "(lax.scan over a stacked megabatch): bitwise the "
                        "same training, N-fold fewer host round-trips; "
                        "logs/saves land on dispatch boundaries")
    g.add_argument("--grad_accum", type=int, default=1,
                   help="gradient accumulation: each optimizer step averages "
                        "the grads of N microbatches (effective batch "
                        "N*batch_size at one microbatch's activation "
                        "memory); exclusive with --steps_per_dispatch > 1")

    g = p.add_argument_group("model")
    g.add_argument("--family", choices=list(FAMILIES), default="llama",
                   help="model family: 'llama' = the reference architecture "
                        "(RoPE/RMSNorm/SwiGLU), 'gpt2' = LayerNorm/GELU/"
                        "learned positions/tied embeddings (models/gpt2.py; "
                        "composes with dp/tp/cp/SP/pp/ep like llama — GQA "
                        "is the one llama-only feature); 'mla_moe', "
                        "'gdn_moe', 'conv_moe' and 'bd_moe' each go with "
                        "their own preset (--model tiny-mla-moe | "
                        "tiny-gdn-moe | tiny-conv-moe | tiny-bd-moe: latent "
                        "attention + experts; Gated DeltaNet + gated "
                        "attention + experts; gated short convolutions + "
                        "GQA with q/k norms + experts, a tied head; a GQA "
                        "expert decoder trained by BLOCK DIFFUSION: the "
                        "step noises each sequence from (--random_seed, "
                        "step, the batch), runs [noised ; clean] under the "
                        "block-diffusion attention mask and weights the "
                        "masked positions' CE by 1/p; tokens/s count data "
                        "tokens; the later families, 'kda_mla_moe', "
                        "'ssm_moe', the dense 'ssm_dense' and 'sambay', 'dsa_moe' "
                        "(every layer chooses its keys and carries a loss "
                        "of its own; dp only) among them, each with --model "
                        "tiny-<family>: README) and "
                        "train under dp/tp/ZeRO 1 only: "
                        "pp/cp/ep > 1, SP, ZeRO 2/3, decode and serving "
                        "refuse them with a message")
    g.add_argument("--model", choices=sorted(MODEL_PRESETS), default=None,
                   help="named shape preset (BASELINE configs: '45m' is the "
                        "reference shape, 'gpt2-124m' is config 3); explicit "
                        "dim flags below override preset fields")
    g.add_argument("--attn_dim", type=int, default=None)
    g.add_argument("--ffn_dim", type=int, default=None)
    g.add_argument("--num_heads", type=int, default=None)
    g.add_argument("--num_kv_heads", type=int, default=None,
                   help="grouped-query attention: K/V heads shared across "
                        "query-head groups (llama family; default = "
                        "num_heads, i.e. plain MHA like the reference)")
    g.add_argument("--num_layers", type=int, default=None)
    g.add_argument("--maxlen", type=int, default=None)
    g.add_argument("--num_experts", type=int, default=None,
                   help="Mixture-of-Experts: swap every layer's FFN for N "
                        "routed experts (both families; default 0 = dense "
                        "FFN like the reference)")
    g.add_argument("--moe_top_k", type=int, default=None,
                   help="experts activated per token (default 2)")
    g.add_argument("--moe_capacity_factor", type=float, default=None,
                   help="per-expert slot headroom; overflow tokens fall "
                        "through the residual (default 2.0)")
    g.add_argument("--remat", choices=sorted(REMAT_CHOICES) + ["auto"],
                   default="auto",
                   help="per-layer rematerialisation: 'auto' (default) = "
                        "keep every group of the layer's named residuals "
                        "(models/transformer.REMAT_LADDER, asked in its "
                        "order) that the step's memory estimate says the "
                        "chip has room for, passing over a group that does "
                        "not fit (training/memory.select_remat; rung 0 on a "
                        "backend with no memory_stats); 'true' = rung 0, "
                        "recompute everything; 'dots' = the top rung, "
                        "every matmul output kept; 'false' = no remat")
    g.add_argument("--seq_bucket", type=int, default=0,
                   help="pad-aware sequence bucketing: pad each batch's "
                        "sequence dim up to a multiple of N (cleanly "
                        "tiled matmuls; 128 = the TPU lane width), tell "
                        "attention the real maxlen (pad tiles are "
                        "skipped, attn_t_real) and mask the pad targets "
                        "in the CE (IGNORE_INDEX). 0 = off; needs "
                        "--cp_size 1")

    g = p.add_argument_group("data")
    g.add_argument("--data_path", "-d", type=str, required=True)
    g.add_argument("--data_mode", choices=["docs", "packed"], default="docs",
                   help="'docs' = one document per row, padded to maxlen "
                        "(reference semantics, dataset.py:40-55); 'packed' "
                        "= concatenate shuffled BOS/EOS-framed documents "
                        "and cut fixed (batch, maxlen) chunks — zero "
                        "padding compute (classic GPT packing; documents "
                        "may span rows and attention may cross doc "
                        "boundaries within a row)")

    g = p.add_argument_group("observability")
    g.add_argument("--no_trace", action="store_true",
                   help="disable the host step-timeline tracer (on by "
                        "default; writes trace.jsonl + Perfetto-loadable "
                        "trace.json to the logs dir — docs/OBSERVABILITY.md)")
    g.add_argument("--no_sentinel", action="store_true",
                   help="disable the training-health sentinel (non-finite "
                        "loss/grad-norm halts with a state dump; loss "
                        "spikes are flagged)")
    g.add_argument("--sentinel_spike_factor", type=float, default=3.0,
                   help="flag a loss spike when interval loss > factor x "
                        "EMA (<= 0 disables spike detection only)")
    g.add_argument("--watchdog_secs", type=float, default=300.0,
                   help="hang watchdog: log a loud per-process report when "
                        "no dispatch completes for this many seconds "
                        "(0 disables)")
    g.add_argument("--flight_ring", type=int, default=256,
                   help="anomaly flight recorder: keep the last N spans/"
                        "heartbeats in a ring that sentinel halts and "
                        "watchdog stalls dump as flightdump_*.json "
                        "(docs/OBSERVABILITY.md; 0 disables)")
    g.add_argument("--metrics_port", type=int, default=None,
                   help="live telemetry exporter (obs/telemetry.py): step "
                        "time, tokens/s, MFU, goodput buckets at "
                        "http://127.0.0.1:PORT/metrics.json and /metrics "
                        "(Prometheus text). Multi-process runs bind "
                        "PORT + process_index; 0 = ephemeral. A busy "
                        "port refuses loudly up front")
    g.add_argument("--rollup_interval", type=float, default=5.0,
                   help="--metrics_port: seconds between "
                        "telemetry_snapshot events mirrored into "
                        "metrics.jsonl (the fleet collector's food)")
    g.add_argument("--profile_on_anomaly", type=int, default=0,
                   metavar="STEPS",
                   help="arm a bounded jax.profiler window of N dispatches "
                        "when a flight dump fires (sentinel halt, watchdog "
                        "stall), cross-linked from the dump's 'profile' "
                        "field; needs --flight_ring > 0; 0 = off")
    g.add_argument("--profile_every", type=int, default=0, metavar="N",
                   help="duty-cycled MEASURED attribution "
                        "(training/metrics.DutyCycleProfiler): every N "
                        "dispatches capture a --profile_window-dispatch "
                        "jax.profiler window, parse it (obs/profparse) "
                        "and land a versioned profile_attribution event "
                        "with the measured-vs-analytic reconcile; 0 = off "
                        "(exactly zero cost: no captures, no events)")
    g.add_argument("--profile_window", type=int, default=4, metavar="W",
                   help="--profile_every: dispatches per capture window "
                        "(must be <= N — a window longer than the duty "
                        "period would re-arm mid-capture)")
    g.add_argument("--profile_budget_mb", type=float, default=64.0,
                   help="--profile_every: total on-disk capture budget; "
                        "once exhausted, sampling stops BETWEEN windows "
                        "(never mid-window) with a logged skip counter")
    g.add_argument("--control", choices=["off", "advise", "act"],
                   default="off",
                   help="the obs v5 control plane (obs/control.py): the "
                        "drift advisor consumes each duty-cycled "
                        "measured-vs-analytic reconcile and the live HBM "
                        "watermarks and lands versioned tuning_decision "
                        "ledger events. 'advise' records without acting; "
                        "'act' applies at safe points — the training "
                        "knob (dp bucket MiB) is init-boundary, so its "
                        "decisions land applied=false and take effect at "
                        "the next launch. 'off' (default) is zero-cost: "
                        "no advisor, no events, no record fields")

    g = p.add_argument_group("other")
    g.add_argument("--random_seed", type=int, default=0)
    g.add_argument("--profile_steps", type=int, default=0,
                   help="trace N steps with jax.profiler (written to "
                        "SAVE_DIR/logs/profile; view in TensorBoard/xprof)")
    g.add_argument("--debug_nans", action="store_true",
                   help="jax.config.debug_nans: fail fast on the first "
                        "non-finite value (the functional analogue of a "
                        "sanitizer — SURVEY §5.2)")
    g.add_argument("--coordinator", type=str, default=None,
                   help="multi-host DCN rendezvous address host:port "
                        "(or set COORDINATOR_ADDRESS); omit on a single "
                        "host — the reference's --master_addr/--master_port "
                        "equivalent, /root/reference/train.py:30-31")
    g.add_argument("--num_processes", type=int, default=None,
                   help="multi-host: total process count (TPU pods "
                        "autodetect this; needed for CPU multi-process runs)")
    g.add_argument("--process_id", type=int, default=None,
                   help="multi-host: this process's id (see --num_processes)")
    args = p.parse_args(argv)
    if args.metrics_port is not None:
        # the serve.py refusals, verbatim: a run whose snapshot mirror
        # silently never starts is the traceless-run failure mode
        if args.metrics_port < 0:
            p.error(f"--metrics_port must be >= 0 (0 = ephemeral), got "
                    f"{args.metrics_port}")
        if args.rollup_interval <= 0:
            p.error("--rollup_interval must be > 0 (seconds between "
                    "telemetry_snapshot events)")
    if args.profile_every:
        # one jax.profiler capture at a time (ProfilerTrace's window
        # mechanics): the duty sampler cannot share the device profiler
        # with the fixed-window or anomaly-armed modes
        if args.profile_steps:
            p.error("--profile_every excludes --profile_steps (one "
                    "jax.profiler capture window at a time; the duty "
                    "sampler subsumes the fixed window)")
        if args.profile_on_anomaly:
            p.error("--profile_every excludes --profile_on_anomaly (both "
                    "drive the one-capture-at-a-time device profiler; "
                    "pick the duty cycle or the anomaly trigger)")
        if not 1 <= args.profile_window <= args.profile_every:
            p.error(f"--profile_window must be in [1, --profile_every] "
                    f"(a window longer than the duty period would re-arm "
                    f"mid-capture), got window {args.profile_window} with "
                    f"every {args.profile_every}")
        if args.profile_budget_mb <= 0:
            p.error(f"--profile_budget_mb must be > 0, got "
                    f"{args.profile_budget_mb}")
    if args.control != "off" and not args.profile_every:
        p.error("--control feeds on the duty profiler's measured "
                "reconciles (drift is what drives retuning); add "
                "--profile_every N")
    return args


def _bucket_window(window: dict, t_pad: int) -> dict:
    """Pad a host batch window's sequence dim up to `t_pad` (sequence
    bucketing): ids pad with 0 (any valid token — masked), targets with
    IGNORE_INDEX (the CE mask), positions extend edge-wise (clipped by the
    rope table, and masked anyway). Works on (B, T) and stacked (N, B, T)
    windows alike."""
    def pad(a, fill=None):
        extra = t_pad - a.shape[-1]
        if extra <= 0:
            return a
        width = [(0, 0)] * (a.ndim - 1) + [(0, extra)]
        if fill is None:
            return np.pad(a, width, mode="edge")
        return np.pad(a, width, constant_values=fill)

    return {"input_ids": pad(window["input_ids"], 0),
            "target_ids": pad(window["target_ids"], IGNORE_INDEX),
            "position_ids": pad(window["position_ids"])}


class _ShutdownFlag:
    """Preemption-safe shutdown: SIGTERM/SIGINT set a flag the train loop
    polls each step, so it saves a final checkpoint and exits cleanly.

    This is the failure-recovery story the reference lacks entirely
    (`mp.spawn(join=True)` — any signal just kills the job, SURVEY §5.3);
    on preemptible TPU VMs the eviction notice arrives as SIGTERM, making
    this the idiomatic TPU equivalent of elastic-training hooks. Handlers
    are only installed on the main thread (signal.signal raises elsewhere)
    and restored on exit so embedding callers (tests) are unaffected.
    """

    def __init__(self):
        self.requested = False
        self._installed = []
        if threading.current_thread() is threading.main_thread():
            for sig in (signal.SIGTERM, signal.SIGINT):
                prev = signal.signal(sig, self._handle)
                self._installed.append((sig, prev))

    def _handle(self, signum, frame):
        self.requested = True
        # Graceful shutdown can take a full train step + checkpoint write;
        # restore the previous handlers immediately so a SECOND signal
        # force-quits instead of being swallowed.
        self.restore()

    def restore(self):
        while self._installed:
            sig, prev = self._installed.pop()
            signal.signal(sig, prev)


def train(args: argparse.Namespace,
          stop: Optional[Callable[[int], bool]] = None) -> dict:
    """`stop`: for a caller that runs `train()` in its own process and wants
    it to end before `--max_steps`: polled with the step count once a window
    of batches, where the shutdown signals are polled; true ends the run as
    reaching `--max_steps` ends it (the record is returned)."""
    # the timeline's zero and the start of `setup.backend`: the observer
    # that books it needs the process index, and so the backend, first
    t_train = time.perf_counter()
    if args.debug_nans:
        jax.config.update("jax_debug_nans", True)
    # Multi-host rendezvous before any backend use (no-op on single host;
    # tests/test_multihost.py drives the underlying init across processes).
    init_multihost(getattr(args, "coordinator", None),
                   num_processes=args.num_processes,
                   process_id=args.process_id)
    nproc = jax.process_count()
    is_main = jax.process_index() == 0
    mesh_cfg = MeshConfig(dp=args.dp_size, tp=args.tp_size, cp=args.cp_size,
                          ep=args.ep_size, pp=args.pp_size)
    if mesh_cfg.world_size > jax.device_count():
        raise SystemExit(
            f"mesh {args.dp_size}x{args.pp_size}x{args.cp_size}x"
            f"{args.ep_size}x{args.tp_size} needs {mesh_cfg.world_size} "
            f"devices; only {jax.device_count()} visible "
            f"({jax.devices()[0].platform}). For CPU testing set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=N")
    # model shape: preset fields, overridden by any explicit dim flag
    # (reference shape = the '45m' preset = /root/reference/constants.py:9-17)
    preset = model_preset(args.model) if args.model else ModelConfig()
    pick = lambda flag, dflt: dflt if flag is None else flag
    maxlen = pick(args.maxlen, preset.maxlen)

    if maxlen % args.cp_size != 0:
        raise SystemExit(f"--maxlen {maxlen} must be divisible by "
                         f"--cp_size {args.cp_size} (sequence is sharded "
                         f"over the 'cp' mesh axis)")
    if args.batch_size % (args.dp_size * args.ep_size) != 0:
        raise SystemExit(f"--batch_size {args.batch_size} must be divisible "
                         f"by dp_size*ep_size "
                         f"{args.dp_size * args.ep_size} (the batch shards "
                         f"over both axes)")
    mesh = make_mesh(mesh_cfg)
    t_mesh = time.perf_counter()

    # One metrics/trace dir per process in multi-host runs (the reference
    # keeps one TB dir per rank, `/root/reference/train.py:85`); TB event
    # files and profiler traces from two writers in one dir clobber.
    # Created before model/data setup: the observer's timeline covers the
    # set-up phase by phase (`setup.*`, one after another up to the loop).
    proc_idx = process_info()[0]
    logs_dir = os.path.join(args.save_dir, "logs") if nproc == 1 else \
        os.path.join(args.save_dir, "logs", f"proc{proc_idx}")
    writer = MetricsWriter(logs_dir, process_index=proc_idx)
    # live telemetry (ISSUE 12): per-process exporter endpoint — process i
    # binds base+i so a multi-host launch script can compute every
    # replica's scrape target from one flag; dies loudly on a busy port
    telemetry = None
    if args.metrics_port is not None:
        from .obs import TelemetryExporter
        telemetry = TelemetryExporter(
            writer=writer, process_index=proc_idx,
            rollup_interval=args.rollup_interval)
        base = args.metrics_port
        bound = telemetry.start(base + proc_idx if base else 0)
        print(f"telemetry exporter[p{proc_idx}]: "
              f"http://127.0.0.1:{bound}/metrics.json")
    observer = TrainObserver(
        logs_dir, writer=writer, trace=not args.no_trace,
        watchdog_secs=args.watchdog_secs, sentinel=not args.no_sentinel,
        spike_factor=args.sentinel_spike_factor,
        process_index=proc_idx, flight_ring=args.flight_ring,
        profile_on_anomaly=args.profile_on_anomaly, started=t_train)
    # after the fact: the rendezvous and the first touch of the devices,
    # then what it took to have the observer (the metrics writer's
    # tensorboardX import is most of it, seconds where that imports torch)
    observer.span_done("setup", "setup.backend", t_train, t_mesh)
    observer.span_done("setup", "setup.logs", t_mesh, time.perf_counter())
    setup = observer.sequence("setup")
    duty = None  # DutyCycleProfiler, built once the model shape is known
    advisor = None  # RetuneAdvisor (obs v5), rides the duty profiler

    try:
        setup.enter("setup.data")
        dataloader = get_dataloader(args.data_path, args.batch_size,
                                    IGNORE_INDEX, split="train",
                                    maxlen=maxlen, shuffle=True,
                                    seed=args.random_seed,
                                    data_mode=args.data_mode)
        vocab_size = dataloader.dataset.vocab_size
        if args.data_mode == "docs":
            from .data.native import native_status
            print(f"data: {len(dataloader.dataset)} documents, collate = "
                  f"{native_status()}")
        setup.enter("setup.model")
        # the preset with the flags laid over it: what no flag names (the
        # mla_moe family's `latent_moe`, rope_theta) stays the preset's
        cfg = dataclasses.replace(
            preset,
            attn_dim=pick(args.attn_dim, preset.attn_dim),
            ffn_dim=pick(args.ffn_dim, preset.ffn_dim),
            num_heads=pick(args.num_heads, preset.num_heads),
            num_kv_heads=pick(args.num_kv_heads, preset.num_kv_heads),
            num_layers=pick(args.num_layers, preset.num_layers),
            num_experts=pick(args.num_experts, preset.num_experts),
            moe_top_k=pick(args.moe_top_k, preset.moe_top_k),
            moe_capacity_factor=pick(args.moe_capacity_factor,
                                     preset.moe_capacity_factor),
            vocab_size=vocab_size, maxlen=maxlen,
            compute_dtype="bfloat16" if args.bf16 else "float32")
        needs = family_class(args.family).config_extra
        carries = cfg.family_facts
        # (two families may read one field: `facts_family` says whose the
        # preset's facts are)
        owner = facts_family(cfg) if carries else None
        if needs != carries or (owner
                                and owner is not family_class(args.family)):
            pairs = ", ".join(
                f"--family {name} --model {preset}"
                for name, cls in FAMILIES.items() if cls.config_extra
                for preset, c in MODEL_PRESETS.items()
                if c.family_facts and facts_family(c) is cls)
            raise SystemExit(
                f"--family {args.family} reads the config field {needs!r} "
                f"and --model {args.model} carries {carries!r}"
                + (f" for --family {owner.family}" if owner else "")
                + ": a family "
                f"with facts of its own goes with a preset that has them "
                f"({pairs}), and such a preset with no other family")
        # ZeRO stage: explicit --zero wins; --zero1 is the stage-1 alias
        # (the precedence rule lives in training/train_step.py)
        zero_stage = resolve_zero_stage(args.zero, args.zero1)
        t_bucket = 0
        if args.seq_bucket:
            if args.seq_bucket < 1 or args.seq_bucket % 128:
                raise SystemExit(
                    f"--seq_bucket must be a positive multiple of 128 (the "
                    f"TPU lane width), got {args.seq_bucket}")
            if args.cp_size > 1:
                raise SystemExit("--seq_bucket needs --cp_size 1 (the "
                                 "ring/ulysses paths shard the sequence "
                                 "and mask by global positions)")
            if cfg.num_experts:
                raise SystemExit(
                    "--seq_bucket does not compose with MoE: the router "
                    "sees every position, so pad tokens would claim "
                    "expert-capacity slots and inflate the aux losses")
            t_bucket = (-(-maxlen // args.seq_bucket)) * args.seq_bucket
            if t_bucket == maxlen:
                t_bucket = 0  # already aligned: nothing to pad
            else:
                print(f"seq bucketing: dispatching t={maxlen} batches in "
                      f"t={t_bucket} buffers (attention skips the pad "
                      f"tiles; CE masks the pad targets; tok/s and MFU "
                      f"count real tokens)")
        attn_t_real = maxlen if t_bucket else None
        # --sequence_parallel / --tp_overlap override a default the program
        # picks: the model resolves it per trace, and the same resolver
        # answers here for the memory estimate, ZeRO's scope and the
        # analytic report, so all of them see the layout the step runs
        from .models.stack import resolve_dp_reduce
        from .models.transformer import resolve_tp_layout
        sp_arg = {"auto": "auto", "on": True,
                  "off": False}[args.sequence_parallel]
        sp, tp_overlap = resolve_tp_layout(
            sp_arg, args.tp_overlap, tp_size=args.tp_size,
            t_local=(t_bucket or maxlen) // args.cp_size,
            dense=not cfg.num_experts, pp_size=args.pp_size)
        # who sums the layers' gradients over dp: a hand-reduced builder
        # where one was asked for, else what the layer body picks per trace
        dp_reduce = (f"zero{zero_stage}" if zero_stage >= 2
                     else "bucketed" if args.dp_reduce_bucket_mb
                     else resolve_dp_reduce(dp_size=args.dp_size,
                                            dense=not cfg.num_experts,
                                            pp_size=args.pp_size))
        remat_key = args.remat
        if remat_key == "auto":
            from .training.memory import select_remat
            remat_key = select_remat(
                cfg, args.batch_size, maxlen, tp=args.tp_size,
                world=mesh_cfg.world_size, zero_stage=zero_stage,
                dp=args.dp_size, family=args.family,
                sequence_parallel=sp)
        if zero_stage == 3 and args.dp_reduce_dtype != "f32":
            # before the generic needs-a-bucket check: adding a bucket
            # would not make a compressed wire apply to stage 3
            raise SystemExit(
                f"--dp_reduce_dtype {args.dp_reduce_dtype} with --zero 3: "
                f"the ZeRO-3 grad reduce-scatter rides the parameter "
                f"all-gather's transpose (an f32 ppermute ring), so the "
                f"compressed wire would silently not apply — use it with "
                f"--zero 2, whose bucketed reduce-scatter carries the "
                f"{args.dp_reduce_dtype} payload")
        if (args.dp_reduce_dtype != "f32" and not args.dp_reduce_bucket_mb
                and zero_stage != 2):
            raise SystemExit(f"--dp_reduce_dtype {args.dp_reduce_dtype} "
                             f"needs --dp_reduce_bucket_mb > 0 (the "
                             f"compressed wire is a property of the "
                             f"bucketed reducer; --zero 2 implies it)")
        if args.dp_reduce_bucket_mb and args.pp_size > 1:
            raise SystemExit("--dp_reduce_bucket_mb needs --pp_size 1 "
                             "(pp-replicated leaves' reduction axes depend "
                             "on the pipeline head layout)")
        if args.dp_reduce_bucket_mb and cfg.num_experts:
            raise SystemExit("--dp_reduce_bucket_mb does not compose with "
                             "MoE (expert grads are ep-sharded, not "
                             "batch-replicated)")
        if zero_stage >= 2:
            # the stage-2/3 grad paths ride the bucketed reducer's scope
            # (training/zero.py) — refuse HERE with actionable messages
            # instead of a ValueError mid-build
            if cfg.num_experts:
                raise SystemExit(
                    f"--zero {zero_stage} does not compose with MoE: expert "
                    f"grads are ep-sharded, not batch-replicated — use "
                    f"--zero 1 (moment sharding only) for MoE runs")
            if args.pp_size > 1:
                raise SystemExit(
                    f"--zero {zero_stage} needs --pp_size 1: non-layer "
                    f"params are pp-replicated and their reduction axes "
                    f"depend on the pipeline head layout — use --zero 1 "
                    f"under pp")
            if args.tp_size > 1 and not sp:
                raise SystemExit(
                    f"--zero {zero_stage} with --tp_size {args.tp_size} "
                    f"needs sequence parallelism (the default where the "
                    f"sequence length divides by tp): the non-SP path "
                    f"all-reduces inside every row-parallel layer, so "
                    f"per-shard cotangent bookkeeping is depth-dependent "
                    f"(turn SP on, or drop to --zero 1)")
        if zero_stage == 3 and remat_key == "false":
            raise SystemExit(
                "--zero 3 needs rematerialisation (--remat dots/true/"
                "auto): without remat, autodiff saves every layer's "
                "GATHERED weights as backward residuals, recreating the "
                "full param replica the stage exists to eliminate")
        if zero_stage == 2 and not args.dp_reduce_bucket_mb:
            print("zero 2: grads reduce-scatter in 25 MiB buckets "
                  "(--dp_reduce_bucket_mb to tune)")
        model = build_model(
            args.family, cfg, tp_size=args.tp_size,
            cp_size=args.cp_size, cp_impl=args.cp_impl,
            cp_layout=args.cp_layout,
            sequence_parallel=sp_arg,
            tp_overlap=args.tp_overlap,
            ep_size=args.ep_size, pp_size=args.pp_size,
            pp_microbatches=args.pp_microbatches,
            pp_remat_steps=args.pp_remat_steps,
            pp_schedule=args.pp_schedule,
            pp_virtual=args.pp_virtual,
            remat=REMAT_CHOICES.get(remat_key, remat_key),
            attn_t_real=attn_t_real,
            # a family that draws noise inside its step draws it from the
            # run's seed
            **({"noise_seed": args.random_seed}
               if family_class(args.family).draws_noise else {}))
        ocfg = OptimizerConfig(lr=args.lr, warmup_steps=args.warmup_steps,
                               max_steps=args.max_steps,
                               clip_grad_norm=args.clip_grad_norm,
                               weight_decay=args.weight_decay,
                               lr_schedule=args.lr_schedule,
                               cosine_min_ratio=args.cosine_min_ratio)

        # the weights: made here, or read back inside the `restore` spans
        setup.enter("setup.init")
        params = model.init(jax.random.key(args.random_seed))
        # count from the actual pytree: exact for every family (cfg.num_params()
        # hardcodes the llama layout — untied head, SwiGLU, no position table)
        n_params = sum(int(x.size) for x in jax.tree.leaves(params))
        moe_note = (f", {cfg.num_experts} experts (top-{cfg.moe_top_k})"
                    if cfg.num_experts else "")
        dev0 = jax.devices()[0]
        attn_impl = resolve_attention_impl(model.attn_impl)
        # what one chip puts on the dp wire for one layer: its shard of the
        # layer's leaves in the compute dtype (ops/overlap.exchange_grads)
        dp_layer_bytes = None
        if dp_reduce == "exchange":
            dp_layer_bytes = sum(
                math.prod(sh.shard_shape(x.shape)[1:])
                for x, sh in zip(
                    jax.tree.leaves(params["layers"]),
                    jax.tree.leaves(model.shardings(mesh)["layers"]))
            ) * jnp.dtype(cfg.compute_dtype).itemsize
        print(f"model[{args.family}]: {n_params/1e6:.2f}M params{moe_note}, "
              f"vocab={vocab_size}, "
              f"mesh=dp{args.dp_size} x pp{args.pp_size} x cp{args.cp_size} x "
              f"ep{args.ep_size} x tp{args.tp_size}, "
              f"compute={cfg.compute_dtype}, attn={attn_impl}"
              + (f", sp={'on' if sp else 'off'}, tp_overlap={tp_overlap}"
                 if args.tp_size > 1 else "")
              + (f", dp_reduce={dp_reduce}"
                 + (f" ({dp_layer_bytes / 1e6:.1f} MB a layer)"
                    if dp_layer_bytes else "")
                 if args.dp_size > 1 else "")
              + (f", zero={zero_stage}" if zero_stage else "")
              + f" on {jax.device_count()} x {dev0.platform} "
                f"[{dev0.device_kind}]")
        opt_state = None  # a resume below may bring the moments
        start_step = 0
        if args.resume:
            if nproc > 1:
                # Only process 0's host is assumed to hold the checkpoint files
                # (it is the only writer — see gather_to_host). It loads and
                # broadcasts host trees; every process supplies its freshly
                # initialised tree as the shape/dtype template.
                last = latest_step(args.save_dir) if is_main else None
                last = int(multihost_utils.broadcast_one_to_all(
                    np.int64(-1 if last is None else last)))
                if last >= 0:
                    # Elastic restarts are single-process only: detect a
                    # layout mismatch on the loading process, agree on it
                    # everywhere, and refuse LOUDLY with the offline fix
                    # (a half-elastic broadcast would feed every process a
                    # tree its mesh does not own).
                    mismatch = 0
                    if is_main:
                        from .reshard import (layouts_equal, make_layout,
                                              resolve_source_layout)
                        src_lay, _ = resolve_source_layout(
                            args.save_dir, last,
                            specs=model.canonical_specs())
                        dst_lay = make_layout(mesh, model.canonical_specs(),
                                              zero_stage=zero_stage)
                        mismatch = 0 if layouts_equal(src_lay, dst_lay) \
                            else 1
                    mismatch = int(multihost_utils.broadcast_one_to_all(
                        np.int64(mismatch)))
                    if mismatch:
                        raise SystemExit(
                            f"--resume mesh mismatch: the checkpoint at "
                            f"{args.save_dir} iter {last} was saved under "
                            f"a different layout than this "
                            f"{nproc}-process run's mesh. In-process "
                            f"elastic resharding is single-process only; "
                            f"reshard the files offline first: python "
                            f"scripts/reshard_ckpt.py --src "
                            f"{args.save_dir} --dst <dir> --tp "
                            f"{args.tp_size} --dp {args.dp_size} --zero "
                            f"{zero_stage} --model <preset>")
                    tmpl_p = model.to_canonical(params)
                    tmpl_o = map_moments(init_adam_state(params),
                                         model.to_canonical)
                    if is_main:
                        with observer.span("checkpoint", "restore", step=last):
                            ck_p, ck_o, start_step = load_checkpoint(
                                args.save_dir, last, tmpl_p,
                                model.canonical_specs(), with_opt=True)
                        if ck_o is None:
                            ck_o = tmpl_o
                    else:
                        ck_p, ck_o, start_step = tmpl_p, tmpl_o, 0
                    ck_p, ck_o = multihost_utils.broadcast_one_to_all((ck_p, ck_o))
                    start_step = int(multihost_utils.broadcast_one_to_all(
                        np.int64(start_step)))
                    params = model.from_canonical(ck_p)
                    opt_state = map_moments(ck_o, model.from_canonical)
                    print(f"resumed from iter {start_step} in {args.save_dir} "
                          f"(broadcast from process 0)")
            else:
                last = latest_step(args.save_dir)
                if last is not None:
                    from .reshard import (layouts_equal, make_layout,
                                          resolve_source_layout)
                    src_lay, _ = resolve_source_layout(
                        args.save_dir, last, specs=model.canonical_specs())
                    dst_lay = make_layout(mesh, model.canonical_specs(),
                                          zero_stage=zero_stage)
                    if layouts_equal(src_lay, dst_lay):
                        with observer.span("checkpoint", "restore",
                                           step=last):
                            params, opt_state, start_step = load_checkpoint(
                                args.save_dir, last,
                                model.to_canonical(params),
                                model.canonical_specs(), with_opt=True)
                        params = model.from_canonical(params)
                        if opt_state is not None:
                            opt_state = map_moments(opt_state,
                                                    model.from_canonical)
                        print(f"resumed from iter {start_step} in "
                              f"{args.save_dir}")
                    else:
                        # ELASTIC restart: the checkpoint's mesh is not this
                        # run's mesh. Route through the reshard plan — each
                        # leaf stream-assembles once on the host and lands
                        # directly on its TARGET sharding (ZeRO ownership
                        # re-derives on this mesh via the same _zero_dim
                        # rule the optimizer uses), then record the lineage
                        # for run forensics.
                        if model._interleaved:
                            raise SystemExit(
                                "--resume across meshes with interleaved "
                                "pipeline stages is not supported: the "
                                "on-device tree is a permutation of the "
                                "canonical checkpoint tree (from_canonical "
                                "is layout-dependent) — resume on the "
                                "saving mesh, or use a non-interleaved "
                                "schedule")
                        from .reshard import HostMeter, stream_load
                        if zero_stage >= 3:
                            from .training.zero import zero3_shardings
                            p_sh = zero3_shardings(model, mesh)
                        else:
                            p_sh = model.shardings(mesh)
                        m_sh = (zero1_moment_shardings(model, mesh)
                                if zero_stage in (1, 2) else p_sh)
                        meter = HostMeter()
                        with observer.span("checkpoint", "reshard_restore",
                                           step=last):
                            params, ck_o, start_step, info = stream_load(
                                args.save_dir, last,
                                model.to_canonical(params),
                                model.canonical_specs(), dst_lay, p_sh,
                                moment_shardings=m_sh, with_opt=True,
                                meter=meter)
                        opt_state = ck_o
                        writer.event(
                            "reshard_event", src_layout=info["src"],
                            dst_layout=info["dst"],
                            bytes_moved=info["bytes_moved"],
                            plan_ops=info["ops"], wall_ms=info["wall_ms"],
                            step=start_step,
                            peak_host_bytes=meter.peak)
                        print(f"elastic resume: iter {start_step} "
                              f"resharded {info['src']} -> {info['dst']} "
                              f"({info['bytes_moved']} bytes moved, "
                              f"{info['wall_ms']} ms)")

        if zero_stage >= 3:
            # ZeRO-3: params REST dp-sharded (zero3_specs); the step's
            # forward gathers each layer on demand. Moments share the
            # layout, so the Adam update is fully local per shard.
            from .training.zero import zero3_shardings
            shardings = zero3_shardings(model, mesh)
        else:
            shardings = model.shardings(mesh)
        params = jax.device_put(params, shardings)
        setup.enter("setup.opt_state")
        if opt_state is None:
            opt_state = init_adam_state(params)
        moment_sh = (zero1_moment_shardings(model, mesh)
                     if zero_stage in (1, 2) else shardings)
        opt_state = jax.device_put(
            opt_state, opt_state.__class__(
                step=jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
                mu=moment_sh, nu=moment_sh))

        setup.enter("setup.build_step")
        spd = max(1, args.steps_per_dispatch)
        accum = max(1, args.grad_accum)
        if accum > 1 and spd > 1:
            raise SystemExit("--grad_accum and --steps_per_dispatch > 1 "
                             "are mutually exclusive")
        if spd > 1 and args.max_steps % spd != 0:
            print(f"note: --max_steps {args.max_steps} is not a multiple of "
                  f"--steps_per_dispatch {spd}: the final "
                  f"{args.max_steps % spd}-step tail triggers a one-time XLA "
                  f"recompile (pick a divisible pair to avoid it)")
        builder_kwargs = dict(zero=zero_stage,
                              moment_shardings=(moment_sh if zero_stage
                                                else None),
                              with_grad_norm=True,
                              dp_reduce_bucket_mb=args.dp_reduce_bucket_mb,
                              dp_reduce_dtype={"bf16": jnp.bfloat16,
                                               "int8": jnp.int8}.get(
                                                   args.dp_reduce_dtype))
        if accum > 1:
            step_fn = build_grad_accum_step(model, mesh, ocfg, args.loss_mode,
                                            **builder_kwargs)
        elif spd > 1:
            step_fn = build_train_step_multi(model, mesh, ocfg, args.loss_mode,
                                             **builder_kwargs)
        else:
            # a family whose loss counts things (the mla_moe, gdn_moe and conv_moe
            # families' routers) returns them with the plain step; logged
            # below at the log interval, fetched with the loss
            with_counters = (cfg.family_facts is not None and zero_stage < 2
                             and not args.dp_reduce_bucket_mb)
            step_fn = build_train_step(model, mesh, ocfg, args.loss_mode,
                                       with_counters=with_counters,
                                       **builder_kwargs)

        # single-process: jnp.asarray; multi-host: global-array assembly from
        # per-process shards (every process iterates the identical dataloader)
        # (the "h2d" span is the feeder's own: runtime/mesh.py)
        feed = batch_feeder(mesh, tracer=observer.loop_spans)
        # profile a window shortly after start so compile+layout churn is over
        profiler = ProfilerTrace(logs_dir, start_step=start_step + 3,
                                 num_steps=args.profile_steps,
                                 tracer=observer.loop_spans)
        if args.profile_every:
            # duty-cycled measured attribution (ISSUE 15): the analytic
            # phase report this run is priced with rides along, so every
            # parsed capture lands a full measured-vs-analytic reconcile
            from .obs.attribution import attribution as _attr, chip_key_for
            from .obs.profparse import analytic_phase_report
            from .training.metrics import DutyCycleProfiler
            chip = chip_key_for(jax.local_devices()[0].device_kind)
            analytic = analytic_phase_report(_attr(
                cfg, args.batch_size, maxlen, remat=remat_key,
                family=args.family, tp=args.tp_size,
                sp=sp, tp_overlap=tp_overlap,
                dp=args.dp_size, dp_bucket_mb=args.dp_reduce_bucket_mb,
                dp_reduce_dtype=args.dp_reduce_dtype, chip=chip,
                world=mesh_cfg.world_size, zero_stage=zero_stage))
            duty = DutyCycleProfiler(
                logs_dir, args.profile_every, args.profile_window,
                args.profile_budget_mb, writer=writer, analytic=analytic)
            if args.control != "off":
                # drift-driven retuning (ISSUE 16): every parsed capture's
                # reconcile feeds the advisor BETWEEN windows — the hook
                # below is the registered safe point. dp bucket MiB is
                # baked into the compiled step, so it is an init-boundary
                # knob (no setter): act-mode decisions are recorded and
                # land at the next launch
                from .obs.control import RetuneAdvisor, control_safe_point
                advisor = RetuneAdvisor(args.control, writer=writer,
                                        telemetry=telemetry)
                advisor.register_knob(
                    "dp_bucket_mb", lambda: args.dp_reduce_bucket_mb,
                    integer=False)

                @control_safe_point
                def _on_attribution(fields):
                    advisor.observe_attribution(fields)
                    advisor.apply_decisions()

                duty.on_attribution = _on_attribution
        flops_step = model_flops_per_step(
            cfg, args.batch_size, maxlen, num_params=model.num_params(cfg))
        # None on the CPU backend: there is no peak to divide by, so MFU
        # is "not measured" there; an unrecognised accelerator raises
        peak_chip = chip_peak_flops()
        peak_flops = peak_chip * mesh_cfg.world_size if peak_chip else None

        # The steady-shape program is AOT-compiled explicitly (under a traced
        # "compile" span) and introspected once — cost_analysis FLOPs, bytes,
        # per-collective comm, peak HBM — then called directly each dispatch.
        # A compile failure (a Mosaic rejection lands here first) raises.
        # Odd shapes (the max_steps tail window) go through the jit wrapper:
        # the "step" span that recompiles is as long as the build, and the
        # build itself is a `recompile` event (below) and, on the timeline,
        # `compile.*` spans inside that "step" (runtime/compile_cache.py).
        aot = {"shape": None, "fn": None, "compile_s": None,
               "collectives": None}
        # The step function built again once its steady program is in hand,
        # with the step the loop was at. The eager ops around a dispatch
        # (`jnp.sum` over a shorter window's losses, the first log interval's
        # schedule) are not the loop's program: the table of
        # `compile_cache_stats()` and the timeline have them.
        recompiles = []

        def on_program(program):
            if (aot["compile_s"] is not None
                    and program["fun"] == step_fn.__name__):
                recompiles.append({"step": n, **program})
                writer.event("recompile", **recompiles[-1])
                observer.instant("recompile", **recompiles[-1])

        def run_step(p, o, ids, tgt, pos, steps_in, step_no):
            # pin only the STEADY shape: a shrunk tail / partial epoch-end
            # window (spd mode) must not claim the AOT slot, or the
            # introspection would describe a program the run barely
            # executes and every full window would miss the cache
            steady = accum > 1 or steps_in == spd
            if aot["shape"] is None and steady:
                aot["shape"] = ids.shape
                t_compile = time.time()
                with observer.span("compile", step=step_no):
                    aot["fn"] = step_fn.lower(p, o, ids, tgt, pos).compile()
                aot["compile_s"] = time.time() - t_compile
                analysis = analyze_compiled(aot["fn"])
                aot["collectives"] = analysis["collectives"]
                # SPMD HLO is per-device: the hand-rolled global estimate
                # spreads over world_size devices (and x steps_in for the
                # scanned/accumulated multi-batch programs)
                expected = flops_step * steps_in / mesh_cfg.world_size
                observer.report_compiled(analysis, flops_step,
                                         steps_in_program=steps_in,
                                         expected_flops=expected,
                                         step=step_no)
                if is_main:
                    print(format_analysis(analysis, model_flops=expected))
            fn = aot["fn"] if (aot["fn"] is not None
                               and ids.shape == aot["shape"]) else step_fn
            with observer.span("step", step=step_no):
                try:
                    return fn(p, o, ids, tgt, pos)
                except (TypeError, ValueError):
                    if fn is step_fn:
                        raise
                    # AOT input validation (shape/layout/sharding mismatch)
                    # surfaces before execution — nothing donated yet — so
                    # downgrading to the jit wrapper, which reshards freely,
                    # is safe
                    aot["fn"] = None
                    return step_fn(p, o, ids, tgt, pos)

        # with accumulation one optimizer step consumes `accum` batches
        steps_per_epoch = len(dataloader) // accum
        if steps_per_epoch == 0:
            if args.data_mode == "packed":
                raise SystemExit(
                    f"packed corpus yields {len(dataloader)} chunks of "
                    f"batch_size*maxlen = {args.batch_size * maxlen} tokens but "
                    f"one optimizer step needs {accum} chunk(s) (grad_accum): "
                    f"zero steps per epoch — reduce --batch_size/--maxlen/"
                    f"--grad_accum")
            raise SystemExit(
                f"dataset has {len(dataloader.dataset)} sequences but one "
                f"optimizer step needs {args.batch_size * accum} "
                f"(batch_size x grad_accum, drop_last): zero steps per epoch — "
                f"reduce --batch_size/--grad_accum")
        max_epoch = math.ceil(args.max_steps / steps_per_epoch)
        # resume continues the data stream too: same seeded per-epoch order,
        # skipping the batches already consumed
        start_epoch = start_step // steps_per_epoch
        skip_batches = (start_step % steps_per_epoch) * accum
        # accumulate the loss on-device; a float() sync every step would
        # serialize host dispatch with device execution
        accum_loss, n = jnp.zeros((), jnp.float32), start_step
        # (summed loss, steps) of the first and the newest dispatch: device
        # values, read once for the summary record
        first_loss = last_loss = first_gnorm = None
        # the sentinel piggybacks on the logging-interval sync: last dispatch's
        # on-device grad norm + the per-interval mean loss, no extra D2H
        last_gnorm = None
        last_counters = None
        last_cum, last_log_n = 0.0, start_step
        t_start, tokens_since, steps_since = time.time(), 0, 0
        useful_since = 0  # non-IGNORE_INDEX targets: real tokens vs padding
        done = False
        shutdown = _ShutdownFlag()

        _last_poll = [None]

        def shutdown_agreed(step=None) -> bool:
            """Cross-host-consistent shutdown decision. A multi-host save runs a
            collective in multi-host mode, so acting on a process-local signal
            would send one process into an all-gather the others never enter
            (deadlock). Every process contributes its local flag and the
            MAX (any-of) is what all of them act on — same collective cost as
            a broadcast, but a SIGTERM delivered to only one host (some
            schedulers signal a single rank) still wins a shutdown checkpoint
            everywhere (ADVICE r4). The gather blocks on device_get, so inside
            the loop (`step` given) it runs only once per log_interval steps:
            preemption reaction lags up to that many steps, and host dispatch
            stays async in between."""
            if nproc == 1:
                return shutdown.requested
            if step is not None:
                if (_last_poll[0] is not None
                        and step - _last_poll[0] < args.log_interval):
                    return False
                _last_poll[0] = step
            return bool(np.max(multihost_utils.process_allgather(
                np.int32(shutdown.requested))))
        replicate_fn = []  # lazily-built jitted all-gather for multi-host saves

        def gather_to_host(save_params, save_opt):
            """AsyncCheckpointer's multi-host hook. Cross-host shards are
            not addressable from this process, so `jax.device_get` inside
            the writer would fail. All-gather to every host (XLA collective
            — all processes must participate), then only process 0 touches
            the filesystem. Params and the two Adam moments gather
            SEQUENTIALLY and land in host RAM one at a time, so peak extra
            device memory is one param-tree — still O(full model) per
            device transiently, which under --zero1 means saves need that
            much headroom (per-host shard files would remove even that;
            not needed at this framework's scales)."""
            if not replicate_fn:
                replicate_fn.append(jax.jit(
                    lambda t: t, out_shardings=jax.tree.map(
                        lambda _: jax.sharding.NamedSharding(
                            mesh, jax.sharding.PartitionSpec()),
                        save_params)))

            def gather_host(tree):
                rep = replicate_fn[0](tree)
                if is_main:
                    return jax.device_get(rep)
                jax.block_until_ready(rep)  # serialize; buffers free on drop
                return None

            host_p = gather_host(save_params)
            host_mu = gather_host(save_opt.mu)
            host_nu = gather_host(save_opt.nu)
            if not is_main:
                return None
            return host_p, save_opt.__class__(
                step=np.asarray(int(jax.device_get(save_opt.step)), np.int32),
                mu=host_mu, nu=host_nu)

        def print_saved(step, paths):
            print(f"saved checkpoint iter {step}: {paths[0]}" +
                  (f" (+{len(paths)-1} shards)" if len(paths) > 1 else ""))

        # the periodic save: loss sync, at most one async write in flight,
        # snapshot, writer thread — and their spans (training/checkpoint.py)
        checkpointer = AsyncCheckpointer(
            args.save_dir, model, args.tp_size, start_step=start_step,
            reserve_last_n=args.reserve_last_n_ckpts, zero_stage=zero_stage,
            mesh_axes=mesh, tracer=observer.loop_spans,
            gather=gather_to_host if nproc > 1 else None,
            on_saved=print_saved)

        def shutdown_save(step):
            """Shared by both shutdown exits (per-batch poll and post-loop)."""
            if step > checkpointer.last_saved:
                checkpointer.save(step, accum_loss, params, opt_state)
            print(f"shutdown requested: checkpointed at step {step}; "
                  f"restart with --resume to continue")

        multi = accum > 1 or spd > 1
        host_dispatches = 0
        prefetcher = None  # closed in the finally on ANY exit (thread cleanup)
        recompiles_logged = 0
        compile_cache.subscribe(on_program)
        setup.end()
        try:
            for epoch in range(start_epoch, max_epoch):
                # One background thread assembles the NEXT dispatch's window
                # (C++ collate + the spd/accum megabatch np.stack) while the
                # device executes the current one; the main thread's per-
                # dispatch host cost collapses to a queue pop (VERDICT r2
                # weak #6). Windows are per-epoch: a partial spd window at the
                # epoch boundary simply dispatches smaller (same math, batch n
                # -> step n mapping unchanged), and a partial accum group is
                # dropped below, exactly like the pre-prefetch loop.
                prefetcher = Prefetcher(
                    window_stream(dataloader.epoch(epoch),
                                  accum if accum > 1 else spd,
                                  skip=skip_batches if epoch == start_epoch
                                  else 0),
                    depth=2,
                    transform=stack_window if multi else (lambda bufs: bufs[0]),
                    tracer=observer.loop_spans)
                while True:
                    try:
                        # (the "data_wait" span is the prefetcher's own)
                        window = prefetcher.pull(step=n)
                    except StopIteration:
                        break
                    # Shutdown poll once per WINDOW: buffered/prefetched batches
                    # were never trained on, so dropping them loses nothing —
                    # resume re-reads them. Dispatch is async, so a signal
                    # arriving mid-execution is caught here before the next
                    # dispatch launches.
                    if shutdown_agreed(n):
                        prefetcher.close()
                        shutdown_save(n)
                        done = True
                        break
                    if stop is not None and stop(n):
                        # the caller's stop: the run ends here as it ends
                        # at --max_steps (no shutdown checkpoint)
                        done = True
                        break
                    if accum > 1 and window["input_ids"].shape[0] < accum:
                        # partial accumulation group at the epoch end: drop it
                        # (drop_last at the optimizer-step level) so every epoch
                        # performs exactly steps_per_epoch steps — the resume
                        # math (start_epoch/skip_batches) relies on that
                        continue
                    prev_n = n
                    if args.profile_steps:
                        profiler.maybe_start(n)
                    if multi:
                        rem = args.max_steps - n
                        if accum == 1 and window["input_ids"].shape[0] > rem:
                            # shrink the final window so the run ends exactly on
                            # max_steps (one-time recompile at the tail shape)
                            window = {k: v[:rem] for k, v in window.items()}
                        steps_in = window["input_ids"].shape[0] if accum == 1 \
                            else accum
                    else:
                        steps_in = 1
                    # bucket-pad the dispatched buffers only; `window`
                    # keeps the real shape for the token accounting below
                    w_feed = (_bucket_window(window, t_bucket) if t_bucket
                              else window)
                    ids, tgt, pos = feed(w_feed["input_ids"],
                                         w_feed["target_ids"],
                                         w_feed["position_ids"], step=n)
                    params, opt_state, out = run_step(params, opt_state, ids,
                                                      tgt, pos, steps_in, n)
                    if multi:
                        losses, gnorms = out
                        # accumulation: `losses` is already the one step's mean
                        loss = losses if accum > 1 else jnp.sum(losses)
                        last_gnorm = gnorms if accum > 1 else gnorms[-1]
                    else:
                        loss, last_gnorm, *rest = out
                        last_counters = rest[0] if rest else None
                    n += 1 if accum > 1 else steps_in
                    tokens_since += window["input_ids"].size
                    useful_since += int((window["target_ids"]
                                         != IGNORE_INDEX).sum())
                    steps_since += steps_in
                    observer.heartbeat(n, tokens=window["input_ids"].size,
                                       steps=steps_in, sync=loss)
                    if duty is not None:
                        # the duty window's start/stop boundaries; `loss`
                        # is this dispatch's device value (stop barrier)
                        duty.tick(n, sync=loss)
                    host_dispatches += 1
                    if args.profile_steps:
                        profiler.maybe_stop(n, sync=loss)
                    accum_loss = accum_loss + loss
                    last_loss = (loss, n - prev_n)
                    if first_loss is None:
                        first_loss = last_loss
                        # the norm of the run's first gradient
                        first_gnorm = (gnorms[0] if spd > 1 else last_gnorm)
                    if n // args.log_interval > prev_n // args.log_interval:
                        # the one blocking D2H of the interval: cumulative loss
                        # + last dispatch's grad norm ride the same sync.
                        # The schedule's value is asked for first, inside the
                        # span: its eager one-op programs queue behind the
                        # interval's steps and the host's dispatch of them
                        # waits for the device, so the wait begins at this
                        # line (ahead of the span it read 1 ms and the loop
                        # stood 2.6 s in no span; behind the sync the same
                        # programs run on an idle device, 11 ms an interval:
                        # PERF.md section 6, PR 70)
                        with observer.span("step", "device_sync", step=n):
                            lr, _ = schedule_lr(ocfg, jnp.asarray(n - 1))
                            cum = float(accum_loss)
                            gnorm = (float(last_gnorm)
                                     if last_gnorm is not None else None)
                        # when the interval's last step was done: the
                        # interval record's `ts`, timeline on or off
                        synced = time.time()
                        # the interval's host work, the device idle under it
                        with observer.span("log", step=n) as found:
                            built = compile_cache_stats()["programs"]
                            avg = cum / (n - start_step)
                            interval_loss = ((cum - last_cum)
                                             / max(n - last_log_n, 1))
                            dt = time.time() - t_start
                            tps = tokens_since / max(dt, 1e-9)
                            useful = useful_since / max(tokens_since, 1)
                            mfu = ((flops_step * steps_since) / max(dt, 1e-9)
                                   / peak_flops if peak_flops else None)
                            mfu_s = (f"MFU {mfu*100:.1f}%" if mfu is not None
                                     else "MFU not measured (no chip peak for "
                                          "the cpu backend)")
                            # None = the backend reports no memory_stats
                            # (CPU): say so loudly; a 0.00 GiB watermark here
                            # misread as "no HBM used" on every chip-less box
                            # (ISSUE 15)
                            mem = device_memory_gib()
                            mem_s = (f"{mem:.2f} GiB" if mem is not None
                                     else "n/a (no memory stats)")
                            rebuilt = recompiles[recompiles_logged:]
                            recompiles_logged += len(rebuilt)
                            at = ", ".join(str(r["step"]) for r in rebuilt)
                            print(f"step {n}/{args.max_steps} -> "
                                  f"avg loss {avg:.4f}, "
                                  f"lr {float(lr):.8f}, {tps/1e3:.1f}k tok/s "
                                  f"({useful*100:.0f}% useful), "
                                  f"{mfu_s}, mem {mem_s}"
                                  + (f", {len(rebuilt)} recompile(s) at step "
                                     f"{at}" if rebuilt else ""))
                            writer.scalar("train/ce_loss", avg, n, ts=synced)
                            writer.scalar("train/lr", float(lr), n)
                            writer.scalar("train/tokens_per_sec", tps, n)
                            writer.scalar("train/useful_token_frac", useful, n)
                            if mfu is not None:  # never export a fake 0
                                writer.scalar("train/mfu", mfu, n)
                            if mem is not None:  # never export a fake 0
                                writer.scalar("device_memory_gib", mem, n)
                            # live HBM watermarks (ISSUE 15): per-device
                            # gauges + one hbm_watermark event per interval
                            # ('unavailable' exported loudly on CPU)
                            marks = publish_hbm(telemetry=telemetry,
                                                writer=writer, step=n,
                                                event=True)
                            if advisor is not None:
                                # proposals only — actuation stays at the
                                # on_attribution safe point (or close())
                                advisor.observe_hbm(
                                    {"devices": marks or [],
                                     "available": marks is not None})
                            if gnorm is not None:
                                writer.scalar("train/grad_norm", gnorm, n)
                            if (last_counters is not None
                                    and "loss_exit" in last_counters):
                                # a stack passed R times: its exits and gate
                                loop = loop_counters_summary(
                                    jax.device_get(last_counters))
                                print("  " + ", ".join(
                                    f"{k} {v:.4g}" for k, v in loop.items()))
                                writer.event("loop_counters", step=n, **loop)
                            elif (last_counters is not None
                                    and "routed" not in last_counters):
                                # a dense family whose mixers count
                                mixers = mixer_counters_summary(
                                    jax.device_get(last_counters))
                                print("  " + ", ".join(
                                    f"{k} {v:.4g}" for k, v in mixers.items()))
                                writer.event("mixer_counters", step=n,
                                             **mixers)
                            elif last_counters is not None:
                                moe = moe_counters_summary(
                                    jax.device_get(last_counters), cfg,
                                    window["input_ids"].size)
                                print("  " + ", ".join(
                                    f"{k} {v:.4g}" for k, v in moe.items()))
                                writer.event("moe_counters", step=n, **moe)
                                if "masked" in last_counters:
                                    bd = bd_counters_summary(
                                        jax.device_get(last_counters))
                                    print("  " + ", ".join(
                                        f"{k} {v:.4g}" for k, v in bd.items()))
                                    writer.event("bd_counters", step=n, **bd)
                                if "dsa_kept" in last_counters:
                                    dsa = dsa_counters_summary(
                                        jax.device_get(last_counters))
                                    print("  " + ", ".join(
                                        f"{k} {v:.4g}"
                                        for k, v in dsa.items()))
                                    writer.event("dsa_counters", step=n,
                                                 **dsa)
                            if telemetry is not None:
                                # same numbers the log line prints — the live
                                # endpoint view; the goodput buckets ride too
                                # (a dict copy per log interval, not per step)
                                telemetry.gauge("train/tokens_per_sec", tps)
                                if mfu is not None:
                                    telemetry.gauge("train/mfu", mfu)
                                telemetry.gauge("train/loss_avg", avg)
                                telemetry.gauge(
                                    "train/step_time_ms",
                                    1e3 * dt / max(steps_since, 1))
                                telemetry.counter("train/step", n)
                                gsum = observer.goodput.summary()
                                telemetry.gauge("train/goodput",
                                                gsum["goodput"])
                                for b, v in gsum["buckets_s"].items():
                                    telemetry.gauge(f"train/bucket_s/{b}", v)
                            last_cum, last_log_n = cum, n
                            t_start, tokens_since, steps_since = (
                                time.time(), 0, 0)
                            useful_since = 0
                            # programs built inside the interval (the eager
                            # one-op ones of a first interval; 0 in a steady
                            # one)
                            found["programs"] = (
                                compile_cache_stats()["programs"] - built)
                            # after the metrics land on disk: a non-finite
                            # interval raises TrainingHealthError through the
                            # finally below
                            observer.check_health(n, interval_loss, gnorm)
                    if n // args.save_interval > prev_n // args.save_interval:
                        checkpointer.save(n, accum_loss, params, opt_state)
                    if n >= args.max_steps:
                        done = True
                        break
                prefetcher.close()
                print(f"epoch {epoch + 1}/{max_epoch} finished")
                if done:
                    break
            # A signal that lands during the run's FINAL dispatch exits the loop
            # via the max_steps break without passing the per-batch poll — it
            # must still checkpoint the trained state (the pre-multi-dispatch
            # code polled after every step and caught this window). The
            # n > last_saved guard keeps a signal the poll already handled from
            # printing the shutdown message twice.
            if n > checkpointer.last_saved and shutdown_agreed():
                shutdown_save(n)
        finally:
            # On ANY exit (including a raising step): stop the prefetch thread
            # (else it busy-polls its full queue forever), let the in-flight
            # async write finish so no truncated npz is left behind, and put the
            # previous signal handlers back so embedding callers keep Ctrl-C.
            # The observer closes here too, so a sentinel halt still leaves a
            # complete trace.json + goodput summary behind; the writer closes
            # last (the observer logs its summary through it).
            compile_cache.unsubscribe(on_program)
            if prefetcher is not None:
                prefetcher.close()
            shutdown.restore()
            checkpointer.join()
            # duty profiler before the observer/writer: an open capture
            # window finalises + parses into its profile_attribution
            # event while the jsonl stream is still writable
            if duty is not None:
                duty.close()
                if duty.captures or duty.windows_skipped:
                    print(f"duty profiler: {len(duty.captures)} capture(s) "
                          f"({duty.attributions} attributed, "
                          f"{duty.bytes_used / 2**20:.1f} MiB of "
                          f"{duty.budget_bytes / 2**20:.0f} MiB budget"
                          + (f", {duty.windows_skipped} window(s) skipped "
                             f"after budget exhaustion"
                             if duty.windows_skipped else "") + ")")
            # advisor after the duty profiler (whose close() can hand it
            # one last reconcile), before the writer its ledger lands in
            if advisor is not None:
                advisor.close()
                s = advisor.summary()
                if s["decisions"]:
                    print(f"control[{s['mode']}]: {s['decisions']} "
                          f"decision(s), {s['applied']} applied, last "
                          f"knob {s['last_knob']}")
            observer.close(print_summary=is_main)
            # exporter after the observer (its final snapshot is the
            # run's last registry state), before the writer it mirrors to
            if telemetry is not None:
                telemetry.close()
            writer.close()

        final_avg = float(accum_loss) / max(n - start_step, 1)
        profiler.close(sync=accum_loss)
        if host_dispatches:
            # the `data_wait` spans' bucket: one source for one number
            host_wait = observer.goodput.bucket("data_wait")
            print(f"input pipeline: host waited "
                  f"{1e3 * host_wait / host_dispatches:.2f} ms/dispatch for "
                  f"data ({host_dispatches} dispatches; collate+stack ran on "
                  f"the prefetch thread)")
        print(f"training finished at step {n}, avg loss {final_avg:.4f}")
        # ISSUE 17: provenance stamp — the run-forensics join key every
        # summary record carries uniformly (bench/serve/train)
        out = {"steps": n, "avg_loss": final_avg,
               "first_loss": (float(first_loss[0]) / first_loss[1]
                              if first_loss else None),
               "last_loss": (float(last_loss[0]) / last_loss[1]
                             if last_loss else None),
               "first_grad_norm": (float(first_gnorm)
                                   if first_gnorm is not None else None),
               "platform": dev0.platform, "device_kind": dev0.device_kind,
               "device_count": jax.device_count(),
               "mesh": {"dp": args.dp_size, "pp": args.pp_size,
                        "cp": args.cp_size, "ep": args.ep_size,
                        "tp": args.tp_size},
               "attn_impl": attn_impl,
               "sequence_parallel": sp, "tp_overlap": tp_overlap,
               "dp_reduce": dp_reduce,
               "dp_reduce_layer_bytes": dp_layer_bytes,
               "peak_flops_per_chip": peak_chip,
               "compile_s": aot["compile_s"],
               "collectives": aot["collectives"],
               "param_bytes_by_device": param_bytes_by_device(params),
               "hbm": hbm_watermarks(),
               "compile_cache": compile_cache_stats(),
               "recompiles": {"count": len(recompiles),
                              "events": recompiles[:RECOMPILES_KEPT]},
               **run_stamp(vars(args))}
        if advisor is not None:  # zero-cost off: no field when off
            out["control"] = advisor.summary()
        return out
    except BaseException:
        # Exceptions BEFORE the loop's own try/finally (bad data path,
        # validation SystemExits, model-init failures) must not leak the
        # watchdog thread or the open trace/metrics handles when train()
        # is embedded (tests call it repeatedly). Both closes are
        # idempotent, so the happy path's finally running first is fine.
        setup.end()  # the phase that raised is on the timeline as far as it got
        if duty is not None:
            duty.close()
        if advisor is not None:
            advisor.close()
        observer.close(print_summary=False)
        if telemetry is not None:
            telemetry.close()
        writer.close()
        raise


def main(argv=None):
    enable_compile_cache()
    # the summary record is the last stdout line, as serve prints its own
    print(json.dumps(train(get_train_args(argv))))


if __name__ == "__main__":
    main()
