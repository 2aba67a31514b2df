"""Where the persistent XLA compile cache lives, and what this process has
spent building programs.

Every entry point calls `enable_compile_cache()` before its first jit. The
cache directory is part of the cache key, so it must never move between
runs (no tempfile, pid or timestamp in it):

* `JAX_COMPILATION_CACHE_DIR` set: JAX reads the variable itself; this
  module sets no directory in code.
* unset: one fixed, git-ignored directory at the root of the checkout.

JAX's default skips programs that compile in under a second, which is most
of the serving programs (one per prefill bucket and decode shape); the
threshold is dropped to zero so those are cached too.

JAX times every program where it is built (`jax/_src/dispatch.py`'s
`log_elapsed_time`: tracing to a jaxpr, lowering to a module, the backend's
compile, which on a persistent-cache hit is the retrieval) and announces
each through `jax.monitoring`. This module is the one listener:

* counters, always on: `compile_cache_stats()`;
* spans, while an `obs/trace.SpanTracer` writes a timeline: complete events
  `compile.trace`, `compile.lower`, `compile.backend` / `compile.load` on
  the thread that compiled (retroactive, so not profiler annotations);
* `subscribe()`: a callback a program, for a loop that wants to know which
  of its steps built one (`train()`'s `recompile` events).

The events nest: tracing `step` announces `step` on entry, then whole
entry / exit pairs for every jitted callee (`matmul`, `tanh`, ...), then
`step`'s inclusive time; and a lowering rule written as a JAX function
(`random_bits`: `add`, `bitwise_xor`, ... by the hundred) is traced inside
the lowering. Entries and exits of traces and lowerings are paired per
thread and only the outermost is booked: the rest is inside its time.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Callable

import jax

from ..obs.trace import current_tracer

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_REPO_ROOT, ".jax_cache")

_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"
_SAVED = "/jax/compilation_cache/compile_time_saved_sec"
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"

# functions `compile_cache_stats()["by_function"]` names; the rest are summed
# under OTHERS (the runners print the dictionary on their `setup` log line)
TABLE_ROWS = 8
OTHERS = "(others)"
_TIMES = ("trace_s", "lower_s", "backend_compile_s", "cache_load_s")
# outermost functions one thread may have traced or lowered and not yet
# compiled (`jax.eval_shape` of a jitted function never compiles it)
_PENDING_MAX = 32
_NOTHING_YET = {"trace_s": 0.0, "lower_s": 0.0}

# the checkpoint writer and the serving threads compile too
_lock = threading.Lock()
_totals = {"hits": 0, "misses": 0, "programs": 0, "saved_s": 0.0,
           **dict.fromkeys(_TIMES, 0.0)}
_by_function: dict = {}
_subscribers: list = []
_listening = False


class _PerThread(threading.local):
    def __init__(self):
        self.depth = 0  # traces and lowerings open on this thread
        self.backend = None  # the backend compile open on this thread
        # fun -> seconds traced and lowered here, not yet at the backend
        self.pending = {}


_thread = _PerThread()


def _fun(fun_name: str) -> str:
    """`step` when traced, `jit(step)` when lowered and compiled: one key."""
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name


def _book(fun: str, key: str, secs: float) -> None:
    with _lock:
        _totals[key] += secs
        row = _by_function.setdefault(
            fun, {**dict.fromkeys(_TIMES, 0.0), "count": 0})
        row[key] += secs
        if key in ("backend_compile_s", "cache_load_s"):
            _totals["programs"] += 1
            row["count"] += 1


def _pending(fun: str) -> dict:
    """What this thread has spent on `fun` before it reaches the backend."""
    waiting = _thread.pending
    if fun not in waiting and len(waiting) >= _PENDING_MAX:
        del waiting[next(iter(waiting))]
    return waiting.setdefault(fun, dict(_NOTHING_YET))


def _emit(name: str, start: float, end: float, **args) -> None:
    tracer = current_tracer()
    if tracer is not None:
        # JAX stamps wall-clock seconds; the tracer has a clock of its own
        shift = tracer.now() - time.time()
        tracer.complete_span(name, start + shift, end + shift, cat="compile",
                             **args)


def _on_event(event: str, **_) -> None:
    if event not in (_HIT, _MISS):
        return
    hit = event == _HIT
    with _lock:
        _totals["hits" if hit else "misses"] += 1
    # between a backend compile's entry and its exit on this thread: what
    # that compile turned out to be
    if hit and _thread.backend is not None:
        _thread.backend["hit"] = True


def _on_entry(event: str, _stamp: float, **_) -> None:
    if event in (_TRACE, _LOWER):
        _thread.depth += 1
    elif event == _BACKEND:
        _thread.backend = {"hit": False}


def _on_duration(event: str, secs: float, **_) -> None:
    if event == _SAVED:
        with _lock:
            _totals["saved_s"] += secs
        if _thread.backend is not None:
            _thread.backend["saved_s"] = secs


def _on_exit(event: str, start: float, end: float, fun_name: str = "",
             **_) -> None:
    fun, secs = _fun(fun_name), end - start
    if event in (_TRACE, _LOWER):
        _thread.depth = max(_thread.depth - 1, 0)
        if _thread.depth:
            return  # a callee's trace, or one a lowering rule made
        key, name = (("trace_s", "compile.trace") if event == _TRACE
                     else ("lower_s", "compile.lower"))
        _book(fun, key, secs)
        _pending(fun)[key] += secs
        _emit(name, start, end, fun=fun)
    elif event == _BACKEND:
        # `compile_or_get_cached` under this span: on a hit the time IS the
        # retrieval; a miss, or a program without a cache key, compiled
        backend, _thread.backend = _thread.backend or {"hit": False}, None
        hit = backend["hit"]
        key = "cache_load_s" if hit else "backend_compile_s"
        _book(fun, key, secs)
        saved = {"saved_s": backend.get("saved_s")} if hit else {}
        _emit("compile.load" if hit else "compile.backend", start, end,
              fun=fun, **saved)
        spent = _thread.pending.pop(fun, _NOTHING_YET)
        program = {"fun": fun, **spent, key: secs, "hit": hit}
        with _lock:
            callbacks = list(_subscribers)
        for callback in callbacks:
            callback(program)


def _listen() -> None:
    global _listening
    with _lock:
        if _listening:
            return
        _listening = True
    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_scalar_listener(_on_entry)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_time_span_listener(_on_exit)


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns the directory in use."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    _listen()
    return path


def subscribe(callback: Callable[[dict], None]) -> None:
    """Call `callback(program)` on the compiling thread whenever a function
    has reached the backend: `{"fun", "trace_s", "lower_s", "hit"}` and
    `cache_load_s` (a hit) or `backend_compile_s`. Pair with
    `unsubscribe` in a `finally`."""
    _listen()
    with _lock:
        _subscribers.append(callback)


def unsubscribe(callback: Callable[[dict], None]) -> None:
    with _lock:
        _subscribers.remove(callback)


def _seconds(row: dict) -> float:
    return sum(row[k] for k in _TIMES)


def _plain(row: dict) -> dict:
    """Seconds to the microsecond: the runners print them on a log line."""
    return {k: round(v, 6) if isinstance(v, float) else v
            for k, v in row.items()}


def compile_cache_stats() -> dict:
    """This process so far, as a fresh snapshot of plain values (two calls
    share no object; `json.dumps` takes it):

    * `dir`; `hits`, programs loaded from the directory; `misses`, programs
      compiled and written to it;
    * `trace_s`, Python tracing to jaxprs, outermost functions only;
      `lower_s`, jaxpr to MLIR module; `backend_compile_s`, XLA's and
      Mosaic's compile (misses and programs without a cache key);
      `cache_load_s`, executables read back from the directory;
    * `saved_s`, what JAX says the hits spared; `programs`, functions that
      reached the backend, compiled or loaded;
    * `by_function`: those four times and a count by function name, the
      `TABLE_ROWS` largest by seconds and the rest summed under `OTHERS`.
    """
    with _lock:
        totals = _plain(_totals)
        rows = [(fun, dict(row)) for fun, row in _by_function.items()]
    rows.sort(key=lambda item: -_seconds(item[1]))
    table = {fun: _plain(row) for fun, row in rows[:TABLE_ROWS]}
    if rows[TABLE_ROWS:]:
        rest = [row for _, row in rows[TABLE_ROWS:]]
        table[OTHERS] = _plain({k: sum(row[k] for row in rest)
                                for k in rest[0]})
    return {"dir": os.environ.get(ENV_VAR) or DEFAULT_DIR, **totals,
            "by_function": table}
