#!/usr/bin/env python
"""serve_fleet — multi-replica serving front door (serving fleet v1, ISSUE 19).

Spawns N in-process `PagedEngine` replicas of one checkpoint behind a
`FleetRouter` (prefix-cache-aware scored dispatch, session affinity,
loud spill) and drives them with loadgen's arrival machinery; or, with
--disagg, splits prefill and decode onto separate engines joined by the
KV page stream (serving/transfer.py) — optionally at different tp
widths (--prefill_tp), the head reshard happening in the page
export/import.

Usage:
    python scripts/serve_fleet.py --dry_run                  # CPU smoke
    python scripts/serve_fleet.py --dry_run --disagg
    python scripts/serve_fleet.py --replicas 2 --num_requests 64 \
        --random_init --log_dir runs/r20/serve_logs
    python scripts/serve_fleet.py --ckpt_dir ckpts --replicas 4 \
        --class_mix interactive=2,standard=6 --tenants 4

Each replica writes its own metrics stream (proc-tagged jsonl) under
--log_dir, so `obs_top`/`FleetCollector` fold the fleet exactly as they
would a multi-host one; one JSON record lands on stdout (run_stamp'd,
the bench/serve convention) and a human summary on stderr.
"""

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    g = p.add_argument_group("fleet")
    g.add_argument("--replicas", type=int, default=2,
                   help="PagedEngine replicas behind the router")
    g.add_argument("--prefix_weight", type=float, default=4.0,
                   help="dispatch-score weight on predicted prefix hit")
    g.add_argument("--load_weight", type=float, default=1.0,
                   help="dispatch-score weight on live+queued load")
    g.add_argument("--pool_weight", type=float, default=1.0,
                   help="dispatch-score weight on pool pressure")
    g.add_argument("--disagg", action="store_true",
                   help="disaggregate: prefill engine -> KV page stream "
                        "-> decode engine (replaces the router fleet)")
    g.add_argument("--prefill_tp", type=int, default=0,
                   help="tp width of the --disagg prefill engine "
                        "(0 = same as --tp_size; the page stream "
                        "reshards heads)")
    g.add_argument("--restart_tp", type=int, default=0,
                   help="mid-run, restart one replica at this tp width: "
                        "half the workload runs, the replica's live "
                        "params reshard through the planner (reshard/), "
                        "and the rest runs against the heterogeneous "
                        "fleet (0 = off)")
    g.add_argument("--restart_replica", default="r0",
                   help="replica name --restart_tp restarts")
    g = p.add_argument_group("model")
    g.add_argument("--model", default="flagship-45m",
                   help="model preset (see config.model_preset)")
    g.add_argument("--ckpt_dir", default=None,
                   help="checkpoint dir every replica serves; omit with "
                        "--random_init/--dry_run")
    g.add_argument("--iter", type=int, default=None,
                   help="checkpoint step (default: latest)")
    g.add_argument("--random_init", action="store_true",
                   help="serve random weights (pipeline checks)")
    g.add_argument("--tp_size", type=int, default=1,
                   help="tensor-parallel width per replica")
    g = p.add_argument_group("engine")
    g.add_argument("--slots", type=int, default=8,
                   help="decode slots per replica")
    g.add_argument("--buf_len", type=int, default=0,
                   help="per-request token buffer (0 = fit the workload)")
    g.add_argument("--page_size", type=int, default=64,
                   help="tokens per KV page")
    g.add_argument("--num_pages", type=int, default=0,
                   help="pool pages per replica (0 = slots * max_pages)")
    g.add_argument("--prefill_chunk", type=int, default=128,
                   help="max prefill positions interleaved per step")
    g.add_argument("--kv_dtype", choices=["native", "int8"],
                   default="native", help="KV page storage dtype")
    g.add_argument("--class_mix", default=None,
                   help="SLO class mix, e.g. interactive=2,standard=6")
    g.add_argument("--max_queue", type=int, default=0,
                   help="per-replica queue bound (0 = unbounded; bounded "
                        "queues exercise affinity spill)")
    g = p.add_argument_group("loadgen")
    g.add_argument("--num_requests", type=int, default=32,
                   help="synthetic request count")
    g.add_argument("--arrival", choices=["poisson", "burst"],
                   default="poisson", help="arrival process")
    g.add_argument("--rate", type=float, default=8.0,
                   help="mean arrivals/sec (poisson)")
    g.add_argument("--prompt_len_min", type=int, default=8,
                   help="min synthetic prompt length")
    g.add_argument("--prompt_len_max", type=int, default=64,
                   help="max synthetic prompt length")
    g.add_argument("--max_new_tokens", type=int, default=32,
                   help="generation budget per request")
    g.add_argument("--tenants", type=int, default=2,
                   help="tenant count (tenant = session affinity key)")
    g.add_argument("--shared_prefix_len", type=int, default=16,
                   help="tokens of shared system prefix (prefix-cache "
                        "routing needs shared pages to find)")
    g.add_argument("--seed", type=int, default=0,
                   help="workload + init seed")
    g = p.add_argument_group("observability")
    g.add_argument("--log_dir", default="serve_logs",
                   help="metrics/trace output dir (per-replica streams)")
    g.add_argument("--trace_requests", action="store_true",
                   help="per-request timelines on every hop "
                        "(router + replicas; request_trace events)")
    g = p.add_argument_group("other")
    g.add_argument("--dry_run", action="store_true",
                   help="tiny config + tiny workload CPU smoke")
    args = p.parse_args(argv)
    if args.replicas < 1:
        p.error("--replicas must be >= 1")
    if args.prefill_tp and not args.disagg:
        p.error("--prefill_tp is a --disagg knob (the router fleet's "
                "replicas share --tp_size)")
    if args.restart_tp and args.disagg:
        p.error("--restart_tp restarts a router-fleet replica; it does "
                "not compose with --disagg")
    if args.restart_tp < 0:
        p.error("--restart_tp must be >= 0")
    if not args.dry_run and not args.random_init and not args.ckpt_dir:
        p.error("need --ckpt_dir, or --random_init, or --dry_run")
    return args


def _load_params(args, model, mesh):
    import jax

    if args.random_init or args.dry_run or not args.ckpt_dir:
        return jax.device_put(model.init(jax.random.key(args.seed)),
                              model.shardings(mesh))
    from distributed_pytorch_from_scratch_tpu.training.checkpoint import (
        latest_step, load_checkpoint)
    step = args.iter if args.iter is not None else latest_step(args.ckpt_dir)
    if step is None:
        raise SystemExit(f"no checkpoints found in {args.ckpt_dir}")
    template = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    params, _, _ = load_checkpoint(args.ckpt_dir, step, template,
                                   model.specs())
    print(f"fleet serving checkpoint iter {step} from {args.ckpt_dir}",
          file=sys.stderr)
    return jax.device_put(params, model.shardings(mesh))


class _DeviceSlices:
    """Hands every engine of the fleet its own run of devices, in order.
    Without it each replica's `make_mesh` took `jax.devices()[:tp]`, and on
    a four-chip host four "replicas" shared chip 0. When the devices run
    out (two replicas on one chip) the walk starts over at device 0 and
    says so: sharing is then the only option, and it is reported."""

    def __init__(self, devices):
        self.devices = list(devices)
        self.next = 0

    def take(self, n: int):
        if n > len(self.devices):
            raise SystemExit(f"an engine at tp{n} needs {n} devices; only "
                             f"{len(self.devices)} visible")
        if self.next + n > len(self.devices):
            print(f"serve_fleet: {len(self.devices)} device(s) cannot give "
                  f"every engine its own tp{n} slice; engines from here on "
                  f"share devices, starting again at device 0",
                  file=sys.stderr)
            self.next = 0
        out = self.devices[self.next:self.next + n]
        self.next += n
        return out


def _build_engine(args, cfg, tp, process_index, writer, rt, telemetry,
                  buf_len, devices, prefill_only=False, params=None):
    from distributed_pytorch_from_scratch_tpu.config import MeshConfig
    from distributed_pytorch_from_scratch_tpu.models.transformer import (
        Transformer)
    from distributed_pytorch_from_scratch_tpu.runtime.mesh import make_mesh
    from distributed_pytorch_from_scratch_tpu.serving.engine import (
        PagedEngine)
    from distributed_pytorch_from_scratch_tpu.serving.scheduler import (
        parse_slo_classes)

    mesh = make_mesh(MeshConfig(dp=1, tp=tp), devices=devices)
    model = Transformer(cfg, tp_size=tp)
    if params is None:
        params = _load_params(args, model, mesh)
    classes = parse_slo_classes(args.class_mix) if args.class_mix else None
    return PagedEngine(
        model, mesh, params, num_slots=args.slots, buf_len=buf_len,
        eos_id=1, page_size=args.page_size, num_pages=args.num_pages,
        prefill_chunk=args.prefill_chunk,
        kv_dtype=None if args.kv_dtype == "native" else args.kv_dtype,
        slo_classes=classes, max_queue=args.max_queue, writer=writer,
        request_tracer=rt, telemetry=telemetry,
        prefill_only=prefill_only)


def _reshard_restart(args, cfg, router, buf_len, obs_for, slices):
    """Restart --restart_replica at --restart_tp: plan the layout change,
    reshard the LIVE replica's params per leaf (device→device — the
    checkpoint never re-reads), attach the new engine under the old name.
    Returns the reshard info dict the replica_restart event carries."""
    import time

    import jax

    from distributed_pytorch_from_scratch_tpu.models.transformer import (
        Transformer)
    from distributed_pytorch_from_scratch_tpu.reshard import (
        make_layout, plan_reshard, reshard_params)
    from distributed_pytorch_from_scratch_tpu.runtime.mesh import make_mesh
    from distributed_pytorch_from_scratch_tpu.config import MeshConfig
    from distributed_pytorch_from_scratch_tpu.training.checkpoint import (
        _flatten)

    name, new_tp = args.restart_replica, args.restart_tp
    old = router._engine(name)
    old_tp = old.model.tp_size
    if cfg.padded_vocab_size(old_tp) != cfg.padded_vocab_size(new_tp):
        raise SystemExit(
            f"--restart_tp {new_tp}: vocab padding differs between tp"
            f"{old_tp} ({cfg.padded_vocab_size(old_tp)}) and tp{new_tp} "
            f"({cfg.padded_vocab_size(new_tp)}) — the live trees have "
            f"different shapes; restart from a checkpoint instead")
    model = Transformer(cfg, tp_size=new_tp)
    flat = _flatten(old._params_in, "param")
    plan = plan_reshard(
        sorted(flat), {k: tuple(v.shape) for k, v in flat.items()},
        {k: v.dtype.itemsize for k, v in flat.items()},
        make_layout((("tp", old_tp),), old.model.specs()),
        make_layout((("tp", new_tp),), model.specs()))
    t0 = time.perf_counter()
    devices = slices.take(new_tp)
    mesh = make_mesh(MeshConfig(dp=1, tp=new_tp), devices=devices)
    params = reshard_params(old._params_in, mesh, model.specs())
    jax.block_until_ready(params)
    info = dict(plan.summary(),
                wall_ms=round((time.perf_counter() - t0) * 1e3, 3),
                devices=[d.id for d in devices])
    w, rt, tel = obs_for(args.replicas + 1)
    eng = _build_engine(args, cfg, new_tp, args.replicas + 1, w, rt, tel,
                        buf_len, devices, params=params)
    router.replace_replica(name, eng, reshard=info)
    print(f"replica {name} restarted at tp{new_tp}: "
          f"{info['src']} -> {info['dst']}, {info['bytes_moved']} bytes, "
          f"{info['wall_ms']} ms", file=sys.stderr)
    return info


def main(argv=None) -> dict:
    args = parse_args(argv)
    from distributed_pytorch_from_scratch_tpu.runtime.compile_cache import (
        enable_compile_cache)
    enable_compile_cache()
    if args.dry_run:
        args.replicas = min(args.replicas, 2)
        args.num_requests, args.arrival = 8, "burst"
        args.prompt_len_min, args.prompt_len_max = 4, 12
        args.max_new_tokens = min(args.max_new_tokens, 8)
        args.slots, args.buf_len = 4, 0        # buf_len auto-fits below
        args.page_size, args.prefill_chunk = 8, 8
        args.shared_prefix_len = 8             # one full shared page
        if not args.class_mix:
            args.class_mix = "interactive=1,standard=1"

    from distributed_pytorch_from_scratch_tpu.config import (ModelConfig,
                                                             model_preset)
    from distributed_pytorch_from_scratch_tpu.obs import (RequestTracer,
                                                          TelemetryExporter)
    from distributed_pytorch_from_scratch_tpu.obs.runindex import run_stamp
    from distributed_pytorch_from_scratch_tpu.serving.kv_manager import (
        page_bytes)
    from distributed_pytorch_from_scratch_tpu.serving.loadgen import (
        run_fleet_loadgen, synthetic_requests)
    from distributed_pytorch_from_scratch_tpu.serving.router import (
        FleetRouter)
    from distributed_pytorch_from_scratch_tpu.serving.scheduler import (
        parse_slo_classes)
    from distributed_pytorch_from_scratch_tpu.training.metrics import (
        MetricsWriter)

    if args.dry_run:
        cfg = ModelConfig(attn_dim=32, ffn_dim=64, num_heads=4,
                          num_layers=2, vocab_size=64, maxlen=64)
    else:
        cfg = model_preset(args.model, compute_dtype="bfloat16")

    mix = parse_slo_classes(args.class_mix) if args.class_mix else None
    requests = synthetic_requests(
        args.num_requests, args.prompt_len_min, args.prompt_len_max,
        args.max_new_tokens, cfg.vocab_size, seed=args.seed,
        rate=args.rate, arrival=args.arrival, class_mix=mix,
        tenants=args.tenants, shared_prefix_len=args.shared_prefix_len)
    longest = max(len(r.prompt) for r in requests)
    buf_len = args.buf_len or (longest + args.max_new_tokens + 2)

    os.makedirs(args.log_dir, exist_ok=True)
    writers, tracers, exporters = [], [], []

    def obs_for(process_index):
        w = MetricsWriter(args.log_dir, process_index=process_index)
        writers.append(w)
        rt = (RequestTracer(writer=w, process_index=process_index)
              if args.trace_requests else None)
        if rt is not None:
            tracers.append(rt)
        tel = TelemetryExporter(writer=w, process_index=process_index)
        exporters.append(tel)
        return w, rt, tel

    import jax
    slices = _DeviceSlices(jax.devices())
    engine_devices = {}

    def build(name, tp, process_index, w, rt, tel, **kw):
        devices = slices.take(tp)
        engine_devices[name] = [d.id for d in devices]
        return _build_engine(args, cfg, tp, process_index, w, rt, tel,
                             buf_len, devices, **kw)

    try:
        if args.disagg:
            from distributed_pytorch_from_scratch_tpu.obs.attribution import (
                kv_transfer_attribution)
            from distributed_pytorch_from_scratch_tpu.serving.transfer import (
                run_disaggregated)
            wp, rtp, telp = obs_for(1)
            wd, rtd, teld = obs_for(2)
            ptp = args.prefill_tp or args.tp_size
            pre = build("prefill", ptp, 1, wp, rtp, telp, prefill_only=True)
            dec = build("decode", args.tp_size, 2, wd, rtd, teld)
            summary = run_disaggregated(pre, dec, requests)
            done = summary.pop("completed")
            pb = page_bytes(cfg, args.page_size,
                            None if args.kv_dtype == "native"
                            else args.kv_dtype)
            summary.update({
                "mode": "disagg", "prefill_tp": ptp,
                "decode_tp": args.tp_size,
                "completed": len(done),
                "generated_tokens": sum(len(r.tokens) for r in done),
                "page_bytes": pb,
                "transfer_pricing": kv_transfer_attribution(
                    summary["transferred_pages"], pb,
                    measured_ms=summary["transfer_ms_p50"]),
            })
            metric = "serve_fleet --disagg"
        else:
            wr, rtr, telr = obs_for(0)
            replicas = []
            for i in range(args.replicas):
                w, rt, tel = obs_for(i + 1)
                replicas.append((f"r{i}", build(f"r{i}", args.tp_size,
                                                i + 1, w, rt, tel)))
            router = FleetRouter(replicas,
                                 prefix_weight=args.prefix_weight,
                                 load_weight=args.load_weight,
                                 pool_weight=args.pool_weight,
                                 writer=wr, telemetry=telr,
                                 request_tracer=rtr)
            if args.restart_tp:
                # two waves around the heterogeneous restart: the second
                # wave runs against a fleet whose restarted replica is a
                # DIFFERENT width, which is the thing being proven
                half = max(1, len(requests) // 2)
                wave_a = run_fleet_loadgen(router, requests[:half])
                restart = _reshard_restart(args, cfg, router, buf_len,
                                           obs_for, slices)
                summary = run_fleet_loadgen(router, requests[half:])
                summary["completed"] = (summary.get("completed", 0)
                                        + wave_a.get("completed", 0))
                summary["wave_a_completed"] = wave_a.get("completed", 0)
                summary["restart"] = restart
                summary["mode"] = "fleet+restart"
                metric = (f"serve_fleet x{args.replicas} "
                          f"restart@tp{args.restart_tp}")
            else:
                summary = run_fleet_loadgen(router, requests)
                summary["mode"] = "fleet"
                metric = f"serve_fleet x{args.replicas}"
    finally:
        for tel in exporters:
            tel.close()
        for w in writers:
            w.close()

    summary["engine_devices"] = engine_devices
    rec = {"metric": metric, "value":
           summary.get("fleet_tokens_per_sec",
                       summary.get("transferred_pages", 0)),
           "unit": "tokens/sec (fleet)" if not args.disagg
           else "pages transferred", **summary}
    rec.update(run_stamp(vars(args)))
    print(json.dumps(rec))
    keys = ("completed", "rejected", "fleet_tokens_per_sec",
            "dispatch_ms_p50", "session_spills", "ttft_ms_p95",
            "tpot_ms_p95", "transfer_ms_p95", "bytes_per_request")
    human = ", ".join(f"{k}={summary[k]}" for k in keys if k in summary)
    print(f"serve_fleet [{summary['mode']}]: {human}", file=sys.stderr)
    return summary


if __name__ == "__main__":
    main()
