"""Continuous-batching inference engine over the slot-granular KV pool.

The one-shot decoder (`models/decode.GreedyDecoder`) fuses prefill + the
whole generation loop into a single dispatch: perfect for a fixed prompt
set, useless for serving — the batch pads to the slowest prompt and no new
request can enter until every row retires. This engine inverts the control
flow: the HOST drives a loop of small compiled programs, so between any two
decode steps it can retire finished slots and prefill queued prompts into
the freed cache rows. The device programs are built from the SAME lowering
functions the fused decoder uses (`models/decode._prefill`, `_decode_one`,
`make_token_sampler`), which is why continuous-batched greedy output is
token-identical to per-prompt `GreedyDecoder` decode (pinned in
tests/test_serving.py).

Two compiled programs, both donating the pool so slot writes are in place:

* **prefill** (one variant per (batch, width) bucket): runs the causal
  full-buffer forward over a bucket-padded prompt buffer, scatters the
  per-layer K/V into the target slots' cache rows, and samples each row's
  first token. Under causal attention the buffer width changes cost only,
  never values, so length-bucketing (scheduler.py) is free correctness-wise.
* **step** (one variant total): advances ALL slots one token — each row
  writes its pending token's K/V at its OWN cursor (`_decode_one`'s per-row
  scatter), attends over its prefix, and samples its next token. Free/dead
  slots compute garbage that flows only into garbage: their rows are
  overwritten by the next prefill before anything can attend to them (the
  same argument as the pipeline bubble steps, models/transformer.py).

Step loop (host): retire -> admit (scheduler FIFO groups -> prefill) ->
one decode dispatch. TTFT/TPOT/queue-wait are measured per request and
emitted through obs/ (SpanTracer spans + MetricsWriter events) so a serving
run renders in the same Chrome trace / summary pipeline as training.
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..models.decode import (require_decodable, _decode_one, _paged_decode_one,
                             _paged_prefill_chunk, _prefill,
                             host_sample_tokens, make_token_sampler,
                             rope_tables)
from ..config import resolve_dtype
from ..obs.control import control_safe_point
from ..ops.quant import dequantize_decode_params, quantize_decode_params
from .kv_manager import (KVCachePool, POOL_SPEC, PagedKVPool, PoolExhausted)
from .scheduler import FIFOScheduler, SLOScheduler


# ISSUE 15: HBM watermark cadences — gauges refresh every N decode steps
# (a handful of host memory_stats() calls: cheap, but not per-step free;
# the exporter overhead pin covers the gauge path), events land every M so
# a metrics chain carries a bounded watermark series, plus the first step
# so short runs still record one.
_HBM_GAUGE_EVERY = 10
_HBM_EVENT_EVERY = 100


def _publish_hbm_plane(engine, pool_bytes=None) -> None:
    """Shared per-engine HBM watermark publication (ISSUE 15): live
    gauges into the exporter, `hbm_watermark` events into the metrics
    chain, both on their cadence. `pool_bytes` is the paged pool's
    ACCOUNTED page bytes — the pool-vs-device cross-check gauge."""
    step = engine.decode_steps
    gauge = engine.telemetry is not None and (
        step == 1 or step % _HBM_GAUGE_EVERY == 0)
    event = engine.writer is not None and (
        step == 1 or step % _HBM_EVENT_EVERY == 0)
    if not (gauge or event):
        return
    from ..training.metrics import publish_hbm
    publish_hbm(telemetry=engine.telemetry if gauge else None,
                writer=engine.writer if event else None, step=step,
                pool_accounted_bytes=pool_bytes, event=event)


def _setup_decode_weights(engine, model, mesh, params, decode_weight_dtype):
    """Shared weight-dtype plumbing for every engine: `engine._params_in`
    is what the compiled programs take (int8 codes + per-output-channel
    scales when decode_weight_dtype='int8'), `engine._pspec` its matching
    spec tree, and `engine._deq(params)` the inside-program prologue that
    hands the decode/prefill lowerings ordinary dense weights (dequant-on-
    use: XLA fuses the int8->f32 convert into the consuming matmul, so
    the weights' HBM traffic — the decode latency floor at small models —
    is int8). Sampling, caches, and every token produced stay governed by
    the engines' usual contracts; weight rounding shifts logits by a
    bounded amount (pinned in tests/test_quant.py)."""
    require_decodable(model)
    if decode_weight_dtype in (None, "native"):
        engine._params_in = params
        engine._pspec = model.specs()
        engine._deq = lambda p: p
    elif decode_weight_dtype in ("int8", jnp.int8):
        engine._params_in, engine._pspec = quantize_decode_params(
            params, model.specs(), mesh)
        engine._deq = dequantize_decode_params
    else:
        raise ValueError(f"decode_weight_dtype must be None/'native'/"
                         f"'int8', got {decode_weight_dtype!r}")


@dataclass
class Request:
    """One generation request. `tokens` fills with the generated ids (EOS
    excluded, like GreedyDecoder.decode); the *_t fields are engine-clock
    samples for the serving metrics. `tenant`/`slo_class` drive the paged
    engine's SLO scheduler (the FIFO scheduler ignores them)."""

    rid: int
    prompt: List[int]
    max_new: int
    seed: int = 0
    arrival: float = 0.0                 # loadgen's planned arrival offset
    tenant: str = "default"              # fair-queuing bucket (SLOScheduler)
    slo_class: Optional[str] = None      # TTFT deadline class (None=default)
    trace_id: Optional[str] = None       # per-request trace (obs/reqtrace)
    trace_ctx: Optional[dict] = None     # wire TraceContext from another
    #                                      process (obs/reqtrace, ISSUE 12):
    #                                      submit CONTINUES that trace
    tokens: List[int] = field(default_factory=list)
    submit_t: Optional[float] = None     # entered the admission queue
    admit_t: Optional[float] = None      # left the queue (prefill dispatch)
    first_token_t: Optional[float] = None
    finish_t: Optional[float] = None
    prompt_len: int = 0
    limit: int = 0
    deadline_t: Optional[float] = None   # submit_t + class TTFT budget
    preemptions: int = 0                 # times evicted and re-queued

    # -- derived metrics (seconds; None until the request finishes) ------
    @property
    def queue_wait_s(self) -> Optional[float]:
        if self.submit_t is None or self.admit_t is None:
            return None
        return self.admit_t - self.submit_t

    @property
    def ttft_s(self) -> Optional[float]:
        if self.submit_t is None or self.first_token_t is None:
            return None
        return self.first_token_t - self.submit_t

    @property
    def tpot_s(self) -> Optional[float]:
        """Time per output token AFTER the first (the decode-loop rate);
        None with < 2 tokens."""
        if (self.first_token_t is None or self.finish_t is None
                or len(self.tokens) < 2):
            return None
        return (self.finish_t - self.first_token_t) / (len(self.tokens) - 1)


def _wire_ctx(req: Request):
    """Deserialize a request's cross-process trace handoff, if any."""
    if req.trace_ctx is None:
        return None
    from ..obs.reqtrace import TraceContext
    return TraceContext.from_wire(req.trace_ctx)


def decode_prompts(engine: "ContinuousBatchingEngine", prompts,
                   max_new, base_seed: int = 0) -> List[List[int]]:
    """Batch-CLI convenience shared by generate.py and evaluate.py: submit
    `prompts` FIFO with per-request seeds base_seed+i, drain the engine,
    and return the generated ids in PROMPT order. `max_new` is an int
    (shared budget) or a per-prompt sequence."""
    budgets = ([max_new] * len(prompts) if isinstance(max_new, int)
               else list(max_new))
    for i, pr in enumerate(prompts):
        engine.submit(Request(rid=i, prompt=pr, max_new=budgets[i],
                              seed=base_seed + i))
    engine.run_to_completion()
    return [r.tokens for r in sorted(engine.completed, key=lambda r: r.rid)]


def _pow2_at_most(n: int, cap: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return min(p, cap) if cap else p


def _chunk_maps(ids, s: int, n: int, cw: int, ps: int, eos_id: int,
                scratch_page: int, tbl_row):
    """Host-side destination maps for one prefill chunk: the (1, cw) token
    buffer eos-padded past n, and per-position destination page/offset.
    Real positions land in `tbl_row`'s pages at (s+i)//ps, (s+i)%ps; pad
    positions write the scratch page at distinct offsets so the scatter
    never collides with live rows. Shared by the target engine's
    `_dispatch_chunk` and the drafter's `_drafter_prefill` — the pad-offset
    convention must stay identical on both sides."""
    buf = np.full((1, cw), eos_id, np.int32)
    buf[0, :n] = ids[s:s + n]
    dstp = np.full((1, cw), scratch_page, np.int32)
    dsto = np.zeros((1, cw), np.int32)
    for i in range(cw):
        if i < n:
            dstp[0, i] = tbl_row[(s + i) // ps]
            dsto[0, i] = (s + i) % ps
        else:
            dsto[0, i] = i % ps
    return buf, dstp, dsto


class ContinuousBatchingEngine:
    """Slot-based continuous batching over a TP-sharded KV pool.

    Sampling knobs are build-time constants (one compiled step serves every
    request, like GreedyDecoder); randomness is PER REQUEST via its seed
    (`make_token_sampler`'s fold-in schedule), so a request's sampled tokens
    reproduce regardless of arrival order, slot placement, or batch mix.
    """

    def __init__(self, model, mesh: Mesh, params, num_slots: int,
                 buf_len: int, eos_id: int, temperature: float = 0.0,
                 top_k: int = 0, top_p: float = 0.0,
                 prefill_bucket: int = 64, max_prefill_batch: int = 4,
                 max_queue: int = 0, debug_host_sampler: bool = False,
                 decode_weight_dtype=None,
                 tracer=None, writer=None, request_tracer=None,
                 flight=None, telemetry=None, duty_profiler=None,
                 clock=time.monotonic):
        if getattr(model, "cp_size", 1) > 1:
            raise ValueError(
                "the slot engine's per-slot caches are replicated over cp; "
                "long-context cp serving is the PAGED engine's job "
                f"(--paged with --cp {model.cp_size}, ISSUE 18) — use "
                "PagedEngine, or rebuild the model at cp=1")
        cap = getattr(model, "max_decode_positions", None)
        if cap is not None and buf_len > cap:
            raise ValueError(
                f"buf_len {buf_len} exceeds the model's learned position "
                f"table ({cap}); clamp the buffer or retrain with a larger "
                f"maxlen")
        if max_prefill_batch < 1:
            raise ValueError(f"max_prefill_batch must be >= 1, got "
                             f"{max_prefill_batch}")
        self.model = model
        self.mesh = mesh
        self.params = params
        self.buf_len = buf_len
        self.eos_id = int(eos_id)
        self.max_prefill_batch = max_prefill_batch
        self._clock = clock
        self.tracer = tracer
        self.writer = writer
        self.rt = request_tracer        # obs.reqtrace.RequestTracer | None
        self.flight = flight            # obs.flight.FlightRecorder | None
        self.telemetry = telemetry      # obs.telemetry.TelemetryExporter
        # ISSUE 15: optional training.metrics.DutyCycleProfiler — ticked
        # once per decode step from the host loop (the thread owning the
        # device queue), exactly like the flight recorder's anomaly tick
        self.duty_profiler = duty_profiler
        self._dtype = resolve_dtype(model.cfg.compute_dtype)
        self._table_len = max(model.cfg.maxlen, buf_len)
        # sampling knobs kept on the engine: the fused in-program sampler
        # stays the only production path; debug_host_sampler switches to
        # host-side full-vocab sampling for the equivalence tests and the
        # r10 cost ablation
        self._temperature, self._top_k, self._top_p = temperature, top_k, top_p
        self._debug_host_sampler = debug_host_sampler
        self._sample = make_token_sampler(model, temperature=temperature,
                                          top_k=top_k, top_p=top_p)
        _setup_decode_weights(self, model, mesh, params, decode_weight_dtype)
        self.pool = KVCachePool(model, mesh, num_slots, buf_len)
        self.scheduler = FIFOScheduler(buf_len, prefill_bucket=prefill_bucket,
                                       max_queue=max_queue, clock=clock,
                                       flight=flight)
        n = num_slots + 1  # + the scratch row (kv_manager.py)
        self._tokens = np.zeros(n, np.int32)
        self._pos = np.zeros(n, np.int32)
        self._seeds = np.zeros(n, np.uint32)
        self._slot_req: Dict[int, Request] = {}
        self._step_fn = self._build_step(n)
        self._prefill_fns: Dict[tuple, object] = {}
        self.completed: List[Request] = []
        # -- aggregate stats ---------------------------------------------
        self.decode_steps = 0
        self.generated_tokens = 0
        self._occupancy_sum = 0.0
        self.prefill_positions = 0            # Σ nb * width dispatched
        self.prefill_positions_monolithic = 0  # Σ rows * buf_len (no bucket)
        self.prompt_tokens = 0

    # -- compiled programs ----------------------------------------------
    def _tables(self):
        if not self.model.uses_rope:
            return None, None
        return rope_tables(self._table_len, self.model.cfg.head_dim,
                           self.model.cfg.rope_theta)

    def _build_step(self, n: int):
        model, buf_len, dtype = self.model, self.buf_len, self._dtype
        debug = self._debug_host_sampler

        def shard_fn(params, pool_k, pool_v, tokens, pos, seeds):
            params = self._deq(params)   # int8 decode weights dequant here
            cos_t, sin_t = self._tables()
            pool_k, pool_v, logits = _decode_one(
                model, params, pool_k, pool_v, tokens, pos, buf_len,
                cos_t, sin_t, dtype)
            if debug:
                # ablation: hand the LOCAL vocab shards back (the
                # out_specs concatenation materialises full-vocab logits
                # for the host) instead of sampling in-program
                return pool_k, pool_v, logits.astype(jnp.float32)
            tok = self._sample(logits, seeds, pos + 1)
            return pool_k, pool_v, tok

        fn = jax.shard_map(
            shard_fn, mesh=self.mesh,
            in_specs=(self._pspec, POOL_SPEC, POOL_SPEC, P(None), P(None),
                      P(None)),
            out_specs=(POOL_SPEC, POOL_SPEC,
                       P(None, "tp") if debug else P(None)))
        return jax.jit(fn, donate_argnums=(1, 2))

    def _build_prefill(self, nb: int, width: int):
        model, dtype = self.model, self._dtype

        def shard_fn(params, pool_k, pool_v, buf, prompt_len, slots, seeds):
            params = self._deq(params)
            cos_t, sin_t = self._tables()
            ks, vs, logits = _prefill(model, params, buf, prompt_len,
                                      cos_t, sin_t, dtype)
            # scatter the (L, nb, kvh, width, hd) prefill caches into the
            # target slots' first `width` rows; rows past the prompt are
            # re-written by decode steps before any query attends to them
            pool_k = pool_k.at[:, slots, :, :width, :].set(
                ks.astype(pool_k.dtype))
            pool_v = pool_v.at[:, slots, :, :width, :].set(
                vs.astype(pool_v.dtype))
            tok = self._sample(logits, seeds, prompt_len)
            return pool_k, pool_v, tok

        fn = jax.shard_map(
            shard_fn, mesh=self.mesh,
            in_specs=(self._pspec, POOL_SPEC, POOL_SPEC, P(None, None),
                      P(None), P(None), P(None)),
            out_specs=(POOL_SPEC, POOL_SPEC, P(None)))
        return jax.jit(fn, donate_argnums=(1, 2))

    # -- request intake --------------------------------------------------
    def submit(self, req: Request) -> None:
        """FIFO enqueue (raises scheduler.QueueFull past the backpressure
        bound). An accepted request opens its trace timeline at submit_t
        (rejected ones never get one — they have no life to explain); a
        `trace_ctx` handed over from another process CONTINUES that
        trace instead (obs/reqtrace.TraceContext)."""
        self.scheduler.submit(req)
        if self.rt is not None:
            self.rt.begin(req, ctx=_wire_ctx(req))

    def has_work(self) -> bool:
        return bool(self.scheduler.pending or self._slot_req)

    @property
    def live_requests(self) -> int:
        return len(self._slot_req)

    # -- the continuous-batching loop ------------------------------------
    def step(self) -> List[Request]:
        """One engine iteration: admit queued prompts into free slots
        (bucket-grouped prefills), then advance every live slot one token.
        Returns the requests that finished during this iteration."""
        done: List[Request] = []
        self._admit(done)
        if self._slot_req:
            self._decode(done)
        return done

    def run_to_completion(self) -> List[Request]:
        """Drain the queue and all live slots; returns all completions in
        finish order."""
        out: List[Request] = []
        while self.has_work():
            out.extend(self.step())
        return out

    # -- internals --------------------------------------------------------
    def _span(self, name, **args):
        if self.tracer is not None:
            return self.tracer.span(name, cat="serve", **args)
        import contextlib
        return contextlib.nullcontext()

    def _admit(self, done: List[Request]) -> None:
        while self.scheduler.pending and self.pool.free_slots:
            group = self.scheduler.take_batch(
                min(self.pool.free_slots, self.max_prefill_batch))
            if not group:
                break
            now = self._clock()
            ready = []
            for req in group:
                req.admit_t = now
                req.prompt_len = len(req.prompt)
                req.limit = min(req.prompt_len + req.max_new, self.buf_len)
                self.prompt_tokens += req.prompt_len
                if self.rt is not None:
                    self.rt.mark(req, "queued", now)
                if req.limit <= req.prompt_len:   # max_new == 0
                    req.finish_t = now
                    self._complete(req, done)
                else:
                    ready.append(req)
            if not ready:
                continue
            self._prefill_group(ready, done)

    def _prefill_group(self, ready: List[Request], done: List[Request]):
        width = self.scheduler.group_width(ready)
        nb = _pow2_at_most(len(ready), self.max_prefill_batch)
        slots = self.pool.alloc_many(len(ready))
        buf = np.full((nb, width), self.eos_id, np.int32)
        plens = np.ones(nb, np.int32)          # pad rows: 1-token dummy
        slot_idx = np.full(nb, self.pool.scratch_slot, np.int32)
        seeds = np.zeros(nb, np.uint32)
        for i, req in enumerate(ready):
            buf[i, : req.prompt_len] = req.prompt
            plens[i] = req.prompt_len
            slot_idx[i] = slots[i]
            seeds[i] = np.uint32(req.seed)
        key = (nb, width)
        if key not in self._prefill_fns:
            self._prefill_fns[key] = self._build_prefill(nb, width)
        with self._span("prefill", rows=len(ready), nb=nb, width=width):
            ks, vs, tok = self._prefill_fns[key](
                self._params_in, self.pool.ks, self.pool.vs, jnp.asarray(buf),
                jnp.asarray(plens), jnp.asarray(slot_idx),
                jnp.asarray(seeds))
            self.pool.adopt(ks, vs)
            tok = np.asarray(tok)
        self.prefill_positions += nb * width
        self.prefill_positions_monolithic += len(ready) * self.buf_len
        now = self._clock()
        for i, req in enumerate(ready):
            req.first_token_t = now
            if self.rt is not None:
                self.rt.mark(req, "prefill", now, positions=req.prompt_len)
            first = int(tok[i])
            if first == self.eos_id:              # 0 generated tokens
                req.finish_t = now
                self.pool.free(slots[i])
                self._complete(req, done)
                continue
            slot = slots[i]
            self._slot_req[slot] = req
            self._tokens[slot] = first
            self._pos[slot] = req.prompt_len
            self._seeds[slot] = np.uint32(req.seed)

    def _decode(self, done: List[Request]) -> None:
        with self._span("decode_step", live=len(self._slot_req)):
            ks, vs, tok = self._step_fn(
                self._params_in, self.pool.ks, self.pool.vs,
                jnp.asarray(self._tokens), jnp.asarray(self._pos),
                jnp.asarray(self._seeds))
            self.pool.adopt(ks, vs)
            if self._debug_host_sampler:
                # `tok` is the (b, vocab_padded) full-vocab logits — the
                # per-step host transfer the fused path avoids by design
                tok = host_sample_tokens(
                    self.model, np.asarray(tok), self._seeds, self._pos + 1,
                    self._temperature, self._top_k, self._top_p)
            else:
                tok = np.asarray(tok)
        now = self._clock()
        self.decode_steps += 1
        self._occupancy_sum += self.pool.occupancy
        if self.tracer is not None:
            self.tracer.counter("slots_live", len(self._slot_req))
        if self.flight is not None:
            self.flight.record("pool_stats", live=len(self._slot_req),
                               free_slots=self.pool.free_slots,
                               queued=self.scheduler.pending)
            # `tok` is host-side already (the np.asarray above), so this
            # step's device work is done — safe profiler stop barrier
            self.flight.tick(self.decode_steps)
        if self.duty_profiler is not None:
            # same safe point: device work for this step is host-side
            self.duty_profiler.tick(self.decode_steps)
        if self.telemetry is not None:
            tel = self.telemetry
            tel.gauge("serve/live", len(self._slot_req))
            tel.gauge("serve/queue_depth", self.scheduler.pending)
            tel.rate("serve/tokens_per_sec", self.generated_tokens)
            tel.counter("serve/decode_steps", self.decode_steps)
        _publish_hbm_plane(self)
        for slot, req in list(self._slot_req.items()):
            # the pending token was written at `pos` by this dispatch: it
            # is now part of the output (mirrors make_generate's buf write)
            if self.rt is not None:
                self.rt.mark(req, "decode", now)
            req.tokens.append(int(self._tokens[slot]))
            self.generated_tokens += 1
            cand = int(tok[slot])
            self._pos[slot] += 1
            gen = len(req.tokens)
            if cand == self.eos_id or req.prompt_len + gen >= req.limit:
                req.finish_t = now
                del self._slot_req[slot]
                self.pool.free(slot)
                self._complete(req, done)
            else:
                self._tokens[slot] = cand

    def _complete(self, req: Request, done: List[Request]) -> None:
        self.completed.append(req)
        done.append(req)
        if self.rt is not None:
            self.rt.retire(req)
        if self.writer is not None:
            ms = lambda s: None if s is None else round(s * 1e3, 3)
            self.writer.event(
                "serve_request", rid=req.rid, prompt_len=req.prompt_len,
                generated=len(req.tokens), trace_id=req.trace_id,
                queue_wait_ms=ms(req.queue_wait_s), ttft_ms=ms(req.ttft_s),
                tpot_ms=ms(req.tpot_s))

    # -- aggregate view ---------------------------------------------------
    def stats(self) -> dict:
        occ = (self._occupancy_sum / self.decode_steps
               if self.decode_steps else 0.0)
        mono = max(self.prefill_positions_monolithic, 1)
        return {
            "decode_steps": self.decode_steps,
            "generated_tokens": self.generated_tokens,
            "prompt_tokens": self.prompt_tokens,
            "completed": len(self.completed),
            "rejected": self.scheduler.rejected,
            "slot_occupancy_mean": round(occ, 4),
            "prefill_positions": self.prefill_positions,
            # share of the monolithic full-buffer prefill cost that
            # length-bucketing removed (generate.py logs this). Can go
            # NEGATIVE when bucketing is off but pow2 batch-padding added
            # rows — callers gate their print on > 0
            "prefill_pad_waste_eliminated": round(
                1.0 - self.prefill_positions / mono, 4)
            if self.prefill_positions_monolithic else 0.0,
        }


@dataclass
class _PrefillState:
    """Host-side cursor of an in-flight (chunked) prefill: `ids` is the
    full token prefix to materialise (prompt, plus any tokens a preempted
    request had already generated — the resume-through-prefill path),
    `s` the next position to process, `keys` the page-aligned prefix-index
    chain keys for registration."""

    req: Request
    ids: List[int]
    s: int
    keys: List[object] = field(default_factory=list)


class PagedEngine:
    """Continuous batching over a PAGED KV cache (serving v2, ISSUE 6).

    Same host-driven loop as `ContinuousBatchingEngine` — retire, admit,
    one decode dispatch — but the cache is a pool of fixed-size PAGES
    (`kv_manager.PagedKVPool`) indexed through a shape-stable
    `(slots, max_pages)` page table, which buys three things the slot
    engine cannot do:

    * **capacity = live tokens, not worst-case rows**: a slot leases pages
      as its cursor grows, so a mixed-length burst fits in the same HBM
      budget that the slot engine spends on `slots x buf_len` whatever the
      prompts actually are (`num_pages` is the budget; oversubscribing
      slots past it is the point).
    * **copy-on-write prefix reuse**: identical prompt prefixes (system
      prompts, few-shot headers) prefill ONCE — later arrivals reference
      the donor's pages through the pool's prefix index and only
      materialise a private copy when they WRITE into a shared page.
    * **chunked prefill**: a long prompt prefills `prefill_chunk` tokens
      at a time, interleaved into the decode loop, so a live stream's
      TPOT never stalls by more than one chunk
      (`max_interleaved_prefill_positions` in stats() is the measured
      bound).

    Admission is `scheduler.SLOScheduler` (TTFT deadline classes,
    per-tenant fairness, overdue-EDF rescue); when an overdue request
    cannot be admitted — or a live slot cannot grow a page — a victim from
    a looser deadline class (most generated tokens first: the most
    over-budget work) is PREEMPTED: its pages are freed, and it re-enters
    the queue with its generated prefix re-admitted through the COW path
    (greedy decode restarted from prompt+generated is token-identical to
    the uninterrupted run — per-position math depends only on the prefix).

    Token-identity contract: greedy paged output equals the slot engine's
    (and per-prompt GreedyDecoder's) for every request, across page
    sizes, arrival orders, COW sharing, chunking, and preemption — the
    decode/chunk lowerings reuse `_decode_one`'s attend math over a
    gathered page view (`models/decode._paged_decode_one`,
    `_paged_prefill_chunk`), pinned in tests/test_serving_paged.py."""

    def __init__(self, model, mesh: Mesh, params, num_slots: int,
                 buf_len: int, eos_id: int, page_size: int = 64,
                 num_pages: int = 0, prefill_chunk: int = 128,
                 temperature: float = 0.0, top_k: int = 0, top_p: float = 0.0,
                 slo_classes=None, default_class: str = "standard",
                 max_queue: int = 0, debug_host_sampler: bool = False,
                 kv_dtype=None, decode_weight_dtype=None,
                 paged_attn_impl: str = "gather",
                 paged_attn_interpret: bool = False,
                 tracer=None, writer=None, request_tracer=None,
                 flight=None, telemetry=None, duty_profiler=None,
                 controller=None, clock=time.monotonic,
                 prefill_only: bool = False):
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got "
                             f"{prefill_chunk}")
        # cp-sharded serving (ISSUE 18): the pool's page dim shards over
        # the 'cp' mesh axis; the host keeps ONE global page table and
        # rank-global accounting, and the compiled programs translate to
        # local slabs per rank. Page-table column j belongs to cp rank
        # j // (max_pages/cp), so max_pages rounds up to a cp multiple.
        self.cp = max(1, int(getattr(model, "cp_size", 1)))
        # the logical per-request buffer rounds UP to whole pages; the
        # dense gathered view is max_pages * page_size wide
        self.page_size = page_size
        pages = -(-buf_len // page_size)
        self.max_pages = self.cp * -(-pages // self.cp)
        self.buf_len = self.max_pages * page_size
        self._mpp = self.max_pages // self.cp   # page-table cols per cp rank
        cap = getattr(model, "max_decode_positions", None)
        if cap is not None and self.buf_len > cap:
            raise ValueError(
                f"buf_len {self.buf_len} ({self.max_pages} pages of "
                f"{page_size}) exceeds the model's learned position table "
                f"({cap}); clamp the buffer or retrain with a larger maxlen")
        if not num_pages:
            num_pages = num_slots * self.max_pages  # no oversubscription
        # the pool splits its pages into equal per-rank slabs (cp=1: one)
        num_pages = self.cp * -(-num_pages // self.cp)
        self.model = model
        self.mesh = mesh
        self.params = params
        self.num_slots = num_slots
        self.eos_id = int(eos_id)
        self.prefill_chunk = prefill_chunk
        self._clock = clock
        self.tracer = tracer
        self.writer = writer
        self.rt = request_tracer        # obs.reqtrace.RequestTracer | None
        self.flight = flight            # obs.flight.FlightRecorder | None
        self.telemetry = telemetry      # obs.telemetry.TelemetryExporter
        # ISSUE 15: optional training.metrics.DutyCycleProfiler — ticked
        # once per decode step on the host loop (the flight recorder's
        # anomaly-tick contract)
        self.duty_profiler = duty_profiler
        # ISSUE 16: optional serving.controller.SLOController — observed
        # and actuated only from _control_tick (the registered safe point)
        self.controller = controller
        # online per-class SLO accounting (ISSUE 12): {class: [completed,
        # hit]}, updated at every _complete — feeds the live exporter
        # gauges AND the in-run attainment-collapse flight trigger (the
        # post-run loadgen check can only dump after the damage is done)
        self._slo_counts: Dict[str, list] = {}
        self.slo_collapsed: set = set()
        self._dtype = resolve_dtype(model.cfg.compute_dtype)
        self._table_len = max(model.cfg.maxlen, self.buf_len)
        # fused in-program sampling is the only production path; the knobs
        # stay on the engine for the host-debug sampler and the speculative
        # subclass (serving/speculative.py reuses them for draft + verify)
        self._temperature, self._top_k, self._top_p = temperature, top_k, top_p
        self._debug_host_sampler = debug_host_sampler
        self._sample = make_token_sampler(model, temperature=temperature,
                                          top_k=top_k, top_p=top_p)
        _setup_decode_weights(self, model, mesh, params, decode_weight_dtype)
        # paged-attention impl (ISSUE 14): 'gather' materializes the dense
        # page view (the oracle); 'pallas' walks the page table in place.
        # Checked ONCE here: 'pallas' off-TPU without the interpreter
        # opt-in raises, so what stats()/the record report is what ran.
        from ..ops.pallas.paged_attention import check_paged_attn_impl
        self.paged_attn_impl = check_paged_attn_impl(
            paged_attn_impl, interpret=paged_attn_interpret)
        self._paged_attn_interpret = bool(paged_attn_interpret)
        # int8 pages: codes + per-head-vector scales through the SAME
        # lease/COW/free accounting (kv_manager.PagedKVPool docstring)
        self.kv_dtype = kv_dtype
        self.pool = PagedKVPool(model, mesh, num_pages, page_size,
                                kv_dtype=kv_dtype, flight=flight)
        # ISSUE 15: bytes one leased page costs, for the pool-vs-device
        # HBM cross-check gauge (accounted pool bytes / measured
        # bytes_in_use)
        from .kv_manager import page_bytes
        self._page_bytes_each = page_bytes(model.cfg, page_size, kv_dtype)
        self.scheduler = SLOScheduler(self.buf_len, classes=slo_classes,
                                      default_class=default_class,
                                      max_queue=max_queue, clock=clock,
                                      flight=flight)
        self._free_slots = deque(range(num_slots))
        # (slots, max_pages) page table; free rows aim at the scratch page
        self._tbl = np.full((num_slots, self.max_pages),
                            self.pool.scratch_page, np.int32)
        self._tokens = np.zeros(num_slots, np.int32)
        self._pos = np.zeros(num_slots, np.int32)
        self._seeds = np.zeros(num_slots, np.uint32)
        self._slot_req: Dict[int, Request] = {}
        self._prefilling: Dict[int, _PrefillState] = {}
        self._step_fn = self._build_step()
        self._chunk_fns: Dict[int, object] = {}
        self.completed: List[Request] = []
        # disaggregated serving (ISSUE 19): a prefill_only engine never
        # decodes — finished prefills park in `handoffs` (page refs held
        # by the ledger) until the caller streams them out and calls
        # finish_handoff; a decode engine adopts them via admit_prefilled
        self.prefill_only = bool(prefill_only)
        self.handoffs: deque = deque()
        self.handoffs_staged = 0
        self.pages_exported = 0
        self.pages_imported = 0
        # -- aggregate stats ---------------------------------------------
        self.decode_steps = 0
        self.generated_tokens = 0
        self.prompt_tokens = 0
        self.prefill_positions = 0          # positions actually dispatched
        self.prefill_token_demand = 0       # Σ len(ids) at admissions
        self.prefix_hit_tokens = 0          # positions served from shared pages
        self.preemptions = 0
        self.max_live = 0
        self.max_interleaved_prefill = 0    # the chunk stall bound, measured
        self._occupancy_sum = 0.0
        self._kv_util_sum = 0.0
        self._pages_used_sum = 0

    # -- compiled programs ------------------------------------------------
    def _tables(self):
        if not self.model.uses_rope:
            return None, None
        return rope_tables(self._table_len, self.model.cfg.head_dim,
                           self.model.cfg.rope_theta)

    @property
    def _check_vma(self) -> bool:
        """shard_map's varying-axes check for the paged programs: on,
        except for the Pallas INTERPRETER under cp > 1 (CPU tests only).
        Discharged to a jaxpr, the kernel's page walk dynamic_slices with
        the cp-varying position base against unvarying operands, which the
        vma typing rejects ("...as a temporary workaround pass
        check_vma=False", jax says). Mosaic-compiled kernels never
        discharge, so on TPU the check stays on."""
        return not (self._paged_attn_interpret and self.cp > 1)

    def _build_step(self):
        model, ps, dtype = self.model, self.page_size, self._dtype
        debug = self._debug_host_sampler
        impl, interp = self.paged_attn_impl, self._paged_attn_interpret
        cp = self.cp
        pspec = self.pool.pspec   # POOL_SPEC / CP_POOL_SPEC, or (codes, sc)

        def shard_fn(params, pool_k, pool_v, tokens, pos, seeds, tbl):
            params = self._deq(params)   # int8 decode weights dequant here
            cos_t, sin_t = self._tables()
            pool_k, pool_v, logits = _paged_decode_one(
                model, params, pool_k, pool_v, tokens, pos, tbl, ps,
                cos_t, sin_t, dtype, attn_impl=impl,
                attn_interpret=interp, cp=cp)
            if debug:
                return pool_k, pool_v, logits.astype(jnp.float32)
            tok = self._sample(logits, seeds, pos + 1)
            return pool_k, pool_v, tok

        fn = jax.shard_map(
            shard_fn, mesh=self.mesh,
            in_specs=(self._pspec, pspec, pspec, P(None), P(None),
                      P(None), P(None, None)),
            out_specs=(pspec, pspec,
                       P(None, "tp") if debug else P(None)),
            check_vma=self._check_vma)
        return jax.jit(fn, donate_argnums=(1, 2))

    def _build_chunk(self, cw: int):
        model, ps, dtype = self.model, self.page_size, self._dtype
        impl, interp = self.paged_attn_impl, self._paged_attn_interpret
        cp = self.cp
        pspec = self.pool.pspec

        def shard_fn(params, pool_k, pool_v, chunk, start, qlen, tbl,
                     dstp, dsto, seeds):
            params = self._deq(params)
            cos_t, sin_t = self._tables()
            pool_k, pool_v, logits = _paged_prefill_chunk(
                model, params, pool_k, pool_v, chunk, start, qlen, tbl,
                dstp, dsto, ps, cos_t, sin_t, dtype, attn_impl=impl,
                attn_interpret=interp, cp=cp)
            tok = self._sample(logits, seeds, start + qlen)
            return pool_k, pool_v, tok

        fn = jax.shard_map(
            shard_fn, mesh=self.mesh,
            in_specs=(self._pspec, pspec, pspec, P(None, None),
                      P(None), P(None), P(None, None), P(None, None),
                      P(None, None), P(None)),
            out_specs=(pspec, pspec, P(None)),
            check_vma=self._check_vma)
        return jax.jit(fn, donate_argnums=(1, 2))

    # -- request intake ---------------------------------------------------
    def submit(self, req: Request) -> None:
        """Enqueue through the SLO scheduler (QueueFull past the
        backpressure bound). Refuses up front a request whose WORST-CASE
        private footprint cannot fit the page pool — admitted, it would
        deadlock preemption once it became the only live request."""
        need = -(-min(len(req.prompt) + req.max_new, self.buf_len)
                 // self.page_size)
        if need > self.pool.num_pages:
            raise ValueError(
                f"request {req.rid}: needs up to {need} pages "
                f"({len(req.prompt)}+{req.max_new} tokens / page_size "
                f"{self.page_size}) but the pool has {self.pool.num_pages} "
                f"— raise --num_pages or lower the budget")
        # cp>1: ownership is positional (column j -> rank j//mpp), so the
        # worst case drawn from ONE rank's slab is min(need, mpp) pages
        if min(need, self._mpp) > self.pool.pages_per_rank:
            raise ValueError(
                f"request {req.rid}: needs up to {min(need, self._mpp)} "
                f"pages from one cp rank's slab ({need} total over cp="
                f"{self.cp}) but each slab holds "
                f"{self.pool.pages_per_rank} — raise --num_pages or lower "
                f"the budget")
        self.scheduler.submit(req)
        if self.rt is not None:
            self.rt.begin(req, ctx=_wire_ctx(req))

    def has_work(self) -> bool:
        return bool(self.scheduler.pending or self._slot_req
                    or self._prefilling)

    @property
    def live_requests(self) -> int:
        return len(self._slot_req) + len(self._prefilling)

    # -- the engine loop --------------------------------------------------
    def step(self) -> List[Request]:
        """One iteration: admit (slots + shared-prefix match), pump AT MOST
        one chunk of prefill while streams are live (the TPOT stall
        bound), then advance every live slot one token."""
        done: List[Request] = []
        self._admit(done)
        self._pump_prefill(done)
        if self._slot_req:
            self._decode(done)
        self.max_live = max(self.max_live, self.live_requests)
        return done

    def run_to_completion(self) -> List[Request]:
        out: List[Request] = []
        while self.has_work():
            out.extend(self.step())
        return out

    # -- internals --------------------------------------------------------
    def _span(self, name, **args):
        if self.tracer is not None:
            return self.tracer.span(name, cat="serve", **args)
        import contextlib
        return contextlib.nullcontext()

    def _chain_keys(self, ids: List[int]) -> List[object]:
        """Prefix-index chain keys for every page-aligned run of `ids`
        (the last may be partial)."""
        ps, keys, parent = self.page_size, [], None
        for j in range(-(-len(ids) // ps)):
            parent = self.pool.chain_key(parent, ids[j * ps:(j + 1) * ps])
            keys.append(parent)
        return keys

    def _try_share(self, slot: int, st: _PrefillState) -> None:
        """At a page boundary, extend the slot's prefix through the pool's
        index instead of recomputing it: a donor page whose valid tokens
        lead-match the remaining ids is referenced in place (refcount++),
        and the cursor jumps past the shared run. A partial match (shorter
        donor tail, or a divergence inside the page) still shares the
        matched positions — visibility masks the rest — but ends the walk.
        Capped at len(ids)-1 so at least one position is always recomputed
        (its logits seed the first sampled token). Runs before every chunk
        dispatch, so a donor admitted in the SAME step is found as soon as
        its pages register."""
        ps = self.page_size
        while st.s % ps == 0:
            cap = len(st.ids) - 1 - st.s
            if cap <= 0:
                break
            j = st.s // ps
            parent = st.keys[j - 1] if j else None
            window = st.ids[st.s:st.s + min(ps, cap)]
            best_page, best_len = None, 0
            for page, toks in self.pool.children(parent):
                n = 0
                for a, b in zip(toks, window):
                    if a != b:
                        break
                    n += 1
                if n > best_len:
                    best_page, best_len = page, n
            if best_len == 0:
                break
            self.pool.ref(best_page)
            self._tbl[slot, j] = best_page
            st.s += best_len
            self.prefix_hit_tokens += best_len
            if self.rt is not None:
                self.rt.note(st.req, prefix_hit_tokens=best_len)
            if best_len < ps:
                break                      # partial match ends the walk

    def _admit(self, done: List[Request]) -> None:
        while self._free_slots or self.scheduler.pending:
            req = self.scheduler.peek()
            if req is None:
                break
            now = self._clock()
            overdue = req.deadline_t is not None and now >= req.deadline_t
            if not self._free_slots:
                # an overdue head may evict a looser-class victim
                if not (overdue and self._preempt_for(req)):
                    break
                continue
            ids = req.prompt + req.tokens
            # gate on the pages the FIRST chunk needs (conservative: prefix
            # sharing, resolved at chunk time, can only reduce it), so a
            # freshly admitted request never instantly deadlocks the pump
            need = -(-min(len(ids), self.prefill_chunk) // self.page_size)
            if not self._fits_free(need):
                if not (overdue and self._preempt_for(req)):
                    break
                continue
            self.scheduler.take()
            if req.admit_t is None:
                req.admit_t = now
                req.prompt_len = len(req.prompt)
                req.limit = min(req.prompt_len + req.max_new, self.buf_len)
                self.prompt_tokens += req.prompt_len
            if self.rt is not None:
                # covers the first admission AND every preempt-resume
                # re-admission (the span since `preempted` was queue time)
                self.rt.mark(req, "queued", now)
            if req.limit <= len(ids):      # max_new == 0
                req.finish_t = now
                self._complete(req, done)
                continue
            slot = self._free_slots.popleft()
            self.prefill_token_demand += len(ids)
            st = _PrefillState(req, ids, 0)
            st.keys = self._chain_keys(ids)
            self._prefilling[slot] = st

    def _candidates(self, exclude_slot=None):
        """Live + prefilling requests preemption may evict, worst first:
        loosest deadline class, then most generated tokens (the most
        over-budget work), then latest admission."""
        cands = []
        for slot, req in self._slot_req.items():
            if slot != exclude_slot:
                cands.append((slot, req))
        for slot, st in self._prefilling.items():
            if slot != exclude_slot:
                cands.append((slot, st.req))
        classes = self.scheduler.classes
        cands.sort(key=lambda sr: (-classes.get(sr[1].slo_class, 0.0),
                                   -len(sr[1].tokens),
                                   -(sr[1].admit_t or 0.0)))
        return cands

    def _preempt_for(self, req) -> bool:
        """Evict one victim from a STRICTLY looser deadline class than
        `req` (same-class work is never displaced — that would ping-pong).
        Returns True when something was freed."""
        classes = self.scheduler.classes
        bound = classes[req.slo_class or self.scheduler.default_class]
        for slot, victim in self._candidates():
            if classes.get(victim.slo_class, 0.0) > bound:
                self._preempt(slot)
                return True
        return False

    def _preempt(self, slot: int) -> None:
        """Evict a slot: pages unref'd (shared ones survive for their
        sharers), the request re-queued with prompt+generated as its new
        prefill prefix (COW re-admission); its pending sampled token is
        dropped — the resume prefill re-derives it (same prefix, same
        greedy argmax / same fold_in(seed, position) draw)."""
        if slot in self._slot_req:
            req = self._slot_req.pop(slot)
        else:
            req = self._prefilling.pop(slot).req
        freed = self._release_slot(slot)
        req.preemptions += 1
        self.preemptions += 1
        if self.rt is not None:
            self.rt.mark(req, "preempted", self._clock())
            self.rt.note(req, pages_freed=freed)
        if self.flight is not None:
            self.flight.record("preempt", rid=req.rid, slot=slot,
                               generated=len(req.tokens),
                               pages_freed=freed,
                               slo_class=req.slo_class)
        self.scheduler.requeue(req)

    def _release_slot(self, slot: int) -> int:
        """Returns the number of page references dropped (the request-
        trace pages_freed counter)."""
        scratch = self.pool.scratch_page
        freed = 0
        for j in range(self.max_pages):
            if self._tbl[slot, j] != scratch:
                self.pool.unref(int(self._tbl[slot, j]))
                self._tbl[slot, j] = scratch
                freed += 1
        self._pos[slot] = 0
        self._free_slots.append(slot)
        return freed

    def _fits_free(self, need: int) -> bool:
        """Can `need` pages for page-table columns [0, need) be leased
        right now? cp=1: one free list. cp>1: the columns split into
        per-rank spans of `mpp`, and every rank's share must fit its own
        slab — a pool half-free in aggregate still refuses when rank 0's
        slab is dry (ownership is positional, pages cannot migrate)."""
        if self.cp == 1:
            return need <= self.pool.free_pages
        for o in range(self.cp):
            cols = max(0, min(need, (o + 1) * self._mpp) - o * self._mpp)
            if cols > self.pool.free_pages_of(o):
                return False
        return True

    def _alloc_page(self, needy_slot: int, owner: int = 0) -> int:
        """A free page from cp rank `owner`'s slab (cp=1: the whole pool),
        evicting victims if the slab is dry (never the needy slot itself).
        Submit-time validation guarantees a sole live request fits, so
        exhaustion with no victim cannot happen. A PoolExhausted-forced
        preemption freezes the flight ring: the dump shows the
        pool/scheduler state that led to the eviction."""
        while True:
            try:
                return self.pool.alloc(owner)
            except PoolExhausted:
                cands = self._candidates(exclude_slot=needy_slot)
                if not cands:
                    raise RuntimeError(
                        "page pool exhausted with no preemption candidate "
                        "— a single request outgrew num_pages (submit-time "
                        "validation should have refused it)")
                victim_slot, victim = cands[0]
                self._preempt(victim_slot)
                if self.flight is not None:
                    self.flight.dump(
                        {"kind": "pool_exhausted_preempt",
                         "needy_slot": needy_slot,
                         "victim_rid": victim.rid,
                         "victim_slot": victim_slot,
                         "victim_generated": len(victim.tokens),
                         "num_pages": self.pool.num_pages},
                        tag="pool_exhausted")

    def _ensure_writable(self, slot: int, lo: int, hi: int):
        """Positions [lo, hi) of `slot` must land in PRIVATE pages before
        a write dispatch: unmapped entries allocate, shared entries
        copy-on-write (one bucketed copy dispatch). Returns
        (pages_allocated, cow_copies) so callers can attribute the page
        churn to the owning request's timeline."""
        ps, scratch = self.page_size, self.pool.scratch_page
        pairs = []
        allocated = 0
        for j in range(lo // ps, -(-hi // ps)):
            owner = j // self._mpp     # cp rank whose slab backs column j
            pid = int(self._tbl[slot, j])
            if pid == scratch:
                self._tbl[slot, j] = self._alloc_page(slot, owner)
                allocated += 1
            elif self.pool.refcount[pid] > 1:
                # same-column COW: src and dst share the owner, so the
                # device copy never crosses cp slabs
                new = self._alloc_page(slot, owner)
                pairs.append((pid, new))
                self.pool.unref(pid)
                self._tbl[slot, j] = new
        self.pool.copy_pages(pairs)
        return allocated, len(pairs)

    def _pump_prefill(self, done: List[Request]) -> None:
        """Advance prefills chunk by chunk. While ANY stream is live
        decoding, at most `prefill_chunk` positions are dispatched per
        engine step — the bound on how long a decode dispatch can be
        delayed by prefill work (`max_interleaved_prefill` tracks the
        realised max; tests assert it)."""
        interleaved = 0
        while self._prefilling:
            live_before = bool(self._slot_req)
            if live_before and interleaved >= self.prefill_chunk:
                break
            slot, st = next(iter(self._prefilling.items()))
            self._try_share(slot, st)      # COW prefix reuse, page-aligned
            budget = (self.prefill_chunk - interleaved if live_before
                      else self.prefill_chunk)
            n = min(len(st.ids) - st.s, budget)
            self._dispatch_chunk(slot, st, n, done)
            if live_before:
                interleaved += n
        self.max_interleaved_prefill = max(self.max_interleaved_prefill,
                                           interleaved)

    def _dispatch_chunk(self, slot: int, st: _PrefillState, n: int,
                        done: List[Request]) -> None:
        ps = self.page_size
        s, ids, req = st.s, st.ids, st.req
        leased, cowed = self._ensure_writable(slot, s, s + n)
        cw = _pow2_at_most(n, self.prefill_chunk)
        # the cp query ring splits the chunk into cp sub-blocks, so the
        # dispatch width rounds up to a cp multiple (pads are scratch-aimed)
        cw = self.cp * -(-cw // self.cp)
        buf, dstp, dsto = _chunk_maps(ids, s, n, cw, ps, self.eos_id,
                                      self.pool.scratch_page,
                                      self._tbl[slot])
        if cw not in self._chunk_fns:
            self._chunk_fns[cw] = self._build_chunk(cw)
        with self._span("prefill_chunk", slot=slot, pos0=s, n=n, cw=cw):
            ks, vs, tok = self._chunk_fns[cw](
                self._params_in, self.pool.ks, self.pool.vs, jnp.asarray(buf),
                jnp.asarray([s], np.int32), jnp.asarray([n], np.int32),
                jnp.asarray(self._tbl[slot:slot + 1]), jnp.asarray(dstp),
                jnp.asarray(dsto),
                jnp.asarray([req.seed], np.uint32))
            self.pool.adopt(ks, vs)
            tok = np.asarray(tok)
        self.prefill_positions += n
        # register freshly completed prompt pages in the prefix index:
        # full pages whose last position this chunk wrote, and the partial
        # tail once the whole prefix is in (shared donors dedupe inside
        # register_prefix)
        for j in range(s // ps, -(-(s + n) // ps)):
            end = min((j + 1) * ps, len(ids))
            if s + n >= end:
                parent = st.keys[j - 1] if j else None
                self.pool.register_prefix(parent, int(self._tbl[slot, j]),
                                          ids[j * ps:end])
        st.s += n
        if self.rt is not None:
            self.rt.mark(req, "prefill_chunk", self._clock(),
                         positions=n, cow=cowed)
            self.rt.note(req, pages_leased=leased, cow_copies=cowed)
        if st.s >= len(ids):
            self._finish_prefill(slot, st, int(tok[0]), done)

    def _finish_prefill(self, slot: int, st: _PrefillState, first: int,
                        done: List[Request]) -> None:
        req = st.req
        del self._prefilling[slot]
        now = self._clock()
        if req.first_token_t is None:
            req.first_token_t = now
        if self.prefill_only:
            self._stage_handoff(slot, st, int(first), now)
            return
        if first == self.eos_id:              # 0 (more) generated tokens
            req.finish_t = now
            freed = self._release_slot(slot)
            if self.rt is not None:
                self.rt.note(req, pages_freed=freed)
            self._complete(req, done)
            return
        self._slot_req[slot] = req
        self._tokens[slot] = first
        self._pos[slot] = len(st.ids)
        self._seeds[slot] = np.uint32(req.seed)

    # -- disaggregated prefill/decode handoff (ISSUE 19) ------------------
    def _stage_handoff(self, slot: int, st: _PrefillState, first: int,
                       now: float) -> None:
        """Park a finished prefill for stream-out instead of decoding:
        the page-table row detaches into the handoff ledger WITH its
        references — the pages (and their prefix-index registrations)
        stay live for export_pages and for sharing with later prefills —
        until finish_handoff drops them after the transfer. The slot
        frees immediately, so a prefill_only engine's slot count bounds
        concurrent prefills, not in-flight handoffs."""
        n_pages = -(-len(st.ids) // self.page_size)
        pages = [int(self._tbl[slot, j]) for j in range(n_pages)]
        self._tbl[slot, :] = self.pool.scratch_page
        self._pos[slot] = 0
        self._free_slots.append(slot)
        self.handoffs.append({"req": st.req, "pages": pages,
                              "first": first, "n_tokens": len(st.ids)})
        self.handoffs_staged += 1
        if self.rt is not None:
            self.rt.mark(st.req, "prefill_done", now, pages=n_pages)

    def export_handoff(self, h) -> tuple:
        """Host payload for one staged handoff: (k, v) from
        PagedKVPool.export_pages over the request's page list (global
        head layout — the importer reshards under its own tp width)."""
        k, v = self.pool.export_pages(h["pages"])
        self.pages_exported += len(h["pages"])
        return k, v

    def finish_handoff(self, h) -> None:
        """Drop the ledger's page references once the receiving pool
        holds its own copies (shared prefix pages survive for their
        other referents), and retire the local trace record — the decode
        side continues the trace from the exported context."""
        for p in h["pages"]:
            self.pool.unref(p)
        if self.rt is not None:
            self.rt.retire(h["req"])

    def admit_prefilled(self, req: Request, k, v, first: int) -> int:
        """Disaggregated decode intake: lease + import pages for an
        ALREADY-PREFILLED request (payload from export_pages on the
        prefill side — any tp/cp width) and install the slot state
        exactly as _finish_prefill would, so the decode loop continues
        token-identically to colocated serving (position math depends
        only on the prefix, and the prefix bytes just arrived). Returns
        the slot used, or -1 when the request completed immediately
        (first == eos, or max_new exhausted). Raises RuntimeError when
        no slot is free and PoolExhausted when the pool is — both are
        the caller's backpressure signals; nothing is partially
        admitted."""
        ids = req.prompt + req.tokens
        n_pages = -(-len(ids) // self.page_size)
        if n_pages > self.max_pages:
            raise ValueError(
                f"handoff {req.rid}: {len(ids)} prefilled tokens need "
                f"{n_pages} page-table columns but the row has "
                f"{self.max_pages} (buf_len {self.buf_len})")
        need = -(-min(len(ids) + req.max_new, self.buf_len)
                 // self.page_size)
        if need > self.pool.num_pages:
            raise ValueError(
                f"handoff {req.rid}: worst case {need} pages exceeds the "
                f"pool's {self.pool.num_pages} — raise --num_pages")
        if not self._free_slots:
            raise RuntimeError(
                f"no free slot for handoff {req.rid} "
                f"({self.num_slots} slots busy)")
        now = self._clock()
        if req.submit_t is None:
            req.submit_t = now
        req.admit_t = now
        req.prompt_len = len(req.prompt)
        req.limit = min(req.prompt_len + req.max_new, self.buf_len)
        self.prompt_tokens += req.prompt_len
        if self.rt is not None:
            self.rt.begin(req, ctx=_wire_ctx(req))
        pages = self.pool.import_pages(
            k, v, owners=[j // self._mpp for j in range(n_pages)])
        self.pages_imported += len(pages)
        slot = self._free_slots.popleft()
        for j, p in enumerate(pages):
            self._tbl[slot, j] = p
        # register the imported prompt pages so later LOCAL arrivals
        # share them exactly as a locally prefilled donor's
        keys = self._chain_keys(ids)
        ps = self.page_size
        for j in range(n_pages):
            self.pool.register_prefix(keys[j - 1] if j else None, pages[j],
                                      ids[j * ps:min((j + 1) * ps,
                                                     len(ids))])
        if self.rt is not None:
            self.rt.mark(req, "kv_import", self._clock(), pages=n_pages)
        if req.first_token_t is None:
            req.first_token_t = self._clock()
        if int(first) == self.eos_id or req.limit <= len(ids):
            req.finish_t = self._clock()
            freed = self._release_slot(slot)
            if self.rt is not None:
                self.rt.note(req, pages_freed=freed)
            self._complete(req, [])
            return -1
        self._slot_req[slot] = req
        self._tokens[slot] = int(first)
        self._pos[slot] = len(ids)
        self._seeds[slot] = np.uint32(req.seed)
        self.max_live = max(self.max_live, self.live_requests)
        return slot

    def _decode(self, done: List[Request]) -> None:
        # grow/privatise the write page of every live slot FIRST — this
        # may itself preempt victims (page exhaustion), so iterate a
        # snapshot and re-check liveness
        for slot in list(self._slot_req):
            if slot not in self._slot_req:
                continue
            pos = int(self._pos[slot])
            leased, cowed = self._ensure_writable(slot, pos, pos + 1)
            if self.rt is not None and (leased or cowed):
                req = self._slot_req.get(slot)
                if req is not None:
                    self.rt.note(req, pages_leased=leased, cow_copies=cowed)
        if not self._slot_req:
            return
        # the dispatch is dense over ALL slot rows, and a non-live row
        # (a slot mid-prefill, or freed this step) still flows through it
        # with cursor 0 and a stale pending token — so its spurious
        # position-0 K/V write must land on the scratch page, NOT the real
        # (possibly shared) page its table maps. Freed slots' tables are
        # already all-scratch; mid-prefill slots' are not, so mask them
        # here rather than hand the program a live page to scribble on.
        tbl = self._tbl
        if self._prefilling:
            tbl = self._tbl.copy()
            for slot in self._prefilling:
                tbl[slot, :] = self.pool.scratch_page
        with self._span("decode_step", live=len(self._slot_req)):
            ks, vs, tok = self._step_fn(
                self._params_in, self.pool.ks, self.pool.vs,
                jnp.asarray(self._tokens), jnp.asarray(self._pos),
                jnp.asarray(self._seeds), jnp.asarray(tbl))
            self.pool.adopt(ks, vs)
            if self._debug_host_sampler:
                tok = host_sample_tokens(
                    self.model, np.asarray(tok), self._seeds, self._pos + 1,
                    self._temperature, self._top_k, self._top_p)
            else:
                tok = np.asarray(tok)
        now = self._clock()
        self.decode_steps += 1
        live_tokens = sum(int(self._pos[s]) + 1 for s in self._slot_req)
        live_tokens += sum(st.s for st in self._prefilling.values())
        used = self.pool.pages_in_use
        self._occupancy_sum += self.live_requests / self.num_slots
        self._pages_used_sum += used
        if used:
            self._kv_util_sum += live_tokens / (used * self.page_size)
        if self.tracer is not None:
            self.tracer.counter("slots_live", len(self._slot_req))
            self.tracer.counter("pages_in_use", used)
        if self.flight is not None:
            self.flight.record("pool_stats", live=len(self._slot_req),
                               prefilling=len(self._prefilling),
                               pages_in_use=used,
                               free_pages=self.pool.free_pages,
                               queued=self.scheduler.pending)
            # device work for this step is already host-side (`tok`);
            # safe point to drive an armed anomaly-profiler window
            self.flight.tick(self.decode_steps)
        if self.duty_profiler is not None:
            self.duty_profiler.tick(self.decode_steps)
        if self.telemetry is not None:
            self._publish_telemetry(used, live_tokens)
        _publish_hbm_plane(self, pool_bytes=used * self._page_bytes_each)
        if self.controller is not None:
            self._control_tick()
        for slot, req in list(self._slot_req.items()):
            if self.rt is not None:
                self.rt.mark(req, "decode", now)
            req.tokens.append(int(self._tokens[slot]))
            self.generated_tokens += 1
            cand = int(tok[slot])
            self._pos[slot] += 1
            if cand == self.eos_id or req.prompt_len + len(req.tokens) >= req.limit:
                req.finish_t = now
                del self._slot_req[slot]
                freed = self._release_slot(slot)
                if self.rt is not None:
                    self.rt.note(req, pages_freed=freed)
                self._complete(req, done)
            else:
                self._tokens[slot] = cand

    @control_safe_point
    def _control_tick(self) -> None:
        """The control plane's registered safe point (ISSUE 16): device
        work for this decode step is already host-side (the same
        contract as flight.tick above), nothing is traced, and no
        capture window is mid-flight on this thread — so the SLO
        controller may observe AND (mode=act) actuate here. graftcheck's
        `controller-discipline` rule pins that `apply_decisions` is only
        ever called from a `@control_safe_point` function."""
        self.controller.tick(self.decode_steps)
        self.controller.apply_decisions()

    def _publish_telemetry(self, pages_used: int, live_tokens: int) -> None:
        """Per-decode-step exporter update (ISSUE 12): a handful of lock-
        guarded dict stores — the pinned hot-path budget is why nothing
        here formats strings or touches I/O."""
        tel = self.telemetry
        tel.gauge("serve/live", len(self._slot_req))
        tel.gauge("serve/prefilling", len(self._prefilling))
        tel.gauge("serve/queue_depth", self.scheduler.pending)
        tel.gauge("serve/pages_in_use", pages_used)
        tel.gauge("serve/free_pages", self.pool.free_pages)
        tel.gauge("serve/num_pages", self.pool.num_pages)
        if pages_used:
            tel.gauge("serve/kv_util",
                      live_tokens / (pages_used * self.page_size))
        tel.rate("serve/tokens_per_sec", self.generated_tokens)
        tel.counter("serve/decode_steps", self.decode_steps)
        tel.counter("serve/preemptions", self.preemptions)

    def _account_slo(self, req: Request) -> None:
        """Fold one completion into the live per-class attainment; an
        in-run collapse (< 50% attained over >= 4 completions) freezes
        the flight ring ONCE per class, while the pool/scheduler history
        that produced it is still in the ring — and, when an anomaly
        profiler is armed, cross-links a device capture of the very next
        steps."""
        cls = req.slo_class or self.scheduler.default_class
        deadline = self.scheduler.classes.get(cls)
        if deadline is None:
            return
        c = self._slo_counts.setdefault(cls, [0, 0])
        c[0] += 1
        if req.ttft_s is not None and req.ttft_s <= deadline:
            c[1] += 1
        attained = c[1] / c[0]
        if self.telemetry is not None:
            tel = self.telemetry
            tel.counter(f"slo/{cls}/completed", c[0])
            tel.counter(f"slo/{cls}/hit", c[1])
            tel.gauge(f"slo/{cls}/attained", attained)
        if (self.flight is not None and c[0] >= 4 and attained < 0.5
                and cls not in self.slo_collapsed):
            self.slo_collapsed.add(cls)
            self.flight.dump(
                {"kind": "slo_attainment_collapse", "slo_class": cls,
                 "completed": c[0], "attained": round(attained, 4),
                 "deadline_s": deadline},
                tag="slo_collapse")

    def _complete(self, req: Request, done: List[Request]) -> None:
        self.completed.append(req)
        done.append(req)
        if self.scheduler.classes:
            self._account_slo(req)
        if self.rt is not None:
            self.rt.retire(req)
        if self.writer is not None:
            ms = lambda s: None if s is None else round(s * 1e3, 3)
            self.writer.event(
                "serve_request", rid=req.rid, prompt_len=req.prompt_len,
                generated=len(req.tokens), tenant=req.tenant,
                slo_class=req.slo_class, preemptions=req.preemptions,
                trace_id=req.trace_id,
                queue_wait_ms=ms(req.queue_wait_s), ttft_ms=ms(req.ttft_s),
                tpot_ms=ms(req.tpot_s))

    # -- aggregate view ---------------------------------------------------
    def stats(self) -> dict:
        steps = max(self.decode_steps, 1)
        demand = max(self.prefill_token_demand, 1)
        return {
            "decode_steps": self.decode_steps,
            "generated_tokens": self.generated_tokens,
            "prompt_tokens": self.prompt_tokens,
            "completed": len(self.completed),
            "rejected": self.scheduler.rejected,
            "slot_occupancy_mean": round(
                self._occupancy_sum / steps if self.decode_steps else 0.0, 4),
            "prefill_positions": self.prefill_positions,
            # -- token-granular occupancy (the paged win, measured) ------
            "page_size": self.page_size,
            "kv_dtype": self.kv_dtype or "native",
            "paged_attn": self.paged_attn_impl,
            # -- cp page sharding (ISSUE 18) -----------------------------
            "cp": self.cp,
            "pages_per_rank": self.pool.pages_per_rank,
            "num_pages": self.pool.num_pages,
            "pages_in_use": self.pool.pages_in_use,
            "pages_in_use_mean": round(self._pages_used_sum / steps
                                       if self.decode_steps else 0.0, 2),
            # live tokens / allocated page bytes: 1.0 = no dead space
            "kv_util_mean": round(
                self._kv_util_sum / steps if self.decode_steps else 0.0, 4),
            "kv_fragmentation_mean": round(
                1.0 - self._kv_util_sum / steps
                if self.decode_steps else 0.0, 4),
            # -- COW prefix cache ----------------------------------------
            "prefix_hit_tokens": self.prefix_hit_tokens,
            "prefix_hit_rate": round(self.prefix_hit_tokens / demand, 4)
            if self.prefill_token_demand else 0.0,
            "cow_copies": self.pool.cow_copies,
            # -- scheduler/preemption ------------------------------------
            "preemptions": self.preemptions,
            "max_live": self.max_live,
            "max_interleaved_prefill_positions": self.max_interleaved_prefill,
            # -- disaggregated handoff (ISSUE 19) ------------------------
            "prefill_only": self.prefill_only,
            "handoffs_staged": self.handoffs_staged,
            "pages_exported": self.pages_exported,
            "pages_imported": self.pages_imported,
        }
