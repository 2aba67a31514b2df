"""Compilation measured where it happens (ISSUE 37): `runtime/compile_cache`
listens to JAX's own trace, lower, backend-compile and cache events and keeps
them as counters (`compile_cache_stats()`), as `compile.*` spans of a writing
`SpanTracer`, and as `recompile` events of `train()`.

The persistent cache is pointed at a temporary directory and put back: a
`.jax_cache/` left in the checkout makes tier-1 abort. The listeners of the
module stay registered (they are the process's, under their guard); what a
test registers itself it unregisters."""

import json
import threading

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache as jax_cache

from distributed_pytorch_from_scratch_tpu.obs.trace import SpanTracer
from distributed_pytorch_from_scratch_tpu.runtime import compile_cache
from distributed_pytorch_from_scratch_tpu.runtime.compile_cache import (
    compile_cache_stats)

TIMES = ("trace_s", "lower_s", "backend_compile_s", "cache_load_s")
# a snapshot's seconds are rounded to the microsecond
US = 2e-6


@pytest.fixture
def cache_dir(tmp_path, monkeypatch):
    """The persistent cache on, in a temporary directory; off again after."""
    path = str(tmp_path / "jax_cache")
    monkeypatch.setenv(compile_cache.ENV_VAR, path)
    before = {name: getattr(jax.config, name) for name in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    jax.config.update("jax_compilation_cache_dir", path)
    jax_cache.reset_cache()
    assert compile_cache.enable_compile_cache() == path
    yield path
    for name, value in before.items():
        jax.config.update(name, value)
    jax_cache.reset_cache()


def fresh(name="step"):
    """A jitted function with a jitted callee that no test has built: the
    constant makes each program's cache key its own."""
    fresh.made += 1
    scale = 1.0 + fresh.made

    @jax.jit
    def callee(x):
        return jnp.tanh(x) * scale

    def step(x):
        return (callee(x) @ x).sum()

    step.__name__ = step.__qualname__ = name
    return jax.jit(step)


fresh.made = 0


def moved(before, after, keys=TIMES + ("programs", "hits", "misses")):
    return {k: after[k] - before[k] for k in keys}


def row(fun):
    """`fun`'s line of the whole table: the snapshot names the largest few,
    and which those are depends on what ran before in this process."""
    return compile_cache._by_function.get(fun)


def test_a_callee_is_traced_inside_its_caller_not_beside_it(cache_dir):
    """`step`'s trace announces `callee`, `tanh`, `matmul`, `_reduce_sum`
    as whole entry / exit pairs inside its own: only `step` is booked."""
    spans = []

    def listener(event, start, end, fun_name="", **_):
        if event.endswith("jaxpr_trace_duration"):
            spans.append((fun_name, end - start))

    x = jnp.ones((8, 8))
    jax.monitoring.register_event_time_span_listener(listener)
    try:
        before = compile_cache_stats()
        fresh("nested_once").lower(x)
        after = compile_cache_stats()
    finally:
        jax.monitoring.unregister_event_time_span_listener(listener)
    names = [name for name, _ in spans]
    assert names[-1] == "nested_once" and "callee" in names
    outer = dict(spans)["nested_once"]
    # the callees' own times are inside `outer`: booking them too would
    # pass it
    assert after["trace_s"] - before["trace_s"] == pytest.approx(outer,
                                                                 abs=US)
    assert row("nested_once")["trace_s"] == pytest.approx(outer)
    assert row("callee") is None


def test_traces_made_while_lowering_are_inside_the_lowering(cache_dir):
    """`random_bits` lowers through a JAX function: `add`, `bitwise_xor`, ...
    are traced between the lowering's entry and its exit, and their time is
    the lowering's, not tracing beside it."""
    log = []

    def entered(event, _stamp, fun_name="", **_):
        log.append(("in", event.split("/")[-1], fun_name, 0.0))

    def left(event, start, end, fun_name="", **_):
        log.append(("out", event.split("/")[-1], fun_name, end - start))

    key = jax.random.key(0)
    jax.block_until_ready(key)

    def draw(k):
        return jax.random.normal(k, (3, 5, 7))

    jax.monitoring.register_scalar_listener(entered)
    jax.monitoring.register_event_time_span_listener(left)
    try:
        before = compile_cache_stats()
        jax.jit(draw).lower(key)
        after = compile_cache_stats()
    finally:
        jax.monitoring.unregister_scalar_listener(entered)
        jax.monitoring.unregister_event_time_span_listener(left)
    opened = log.index(("in", "jaxpr_to_mlir_module_duration", "jit(draw)",
                        0.0))
    inside = [e for e in log[opened:]
              if e[:2] == ("out", "jaxpr_trace_duration")]
    assert inside, log  # or the premise of this test is gone
    (traced,) = [e[3] for e in log[:opened]
                 if e[:3] == ("out", "jaxpr_trace_duration", "draw")]
    (lowered,) = [e[3] for e in log
                  if e[:2] == ("out", "jaxpr_to_mlir_module_duration")]
    assert after["trace_s"] - before["trace_s"] == pytest.approx(traced,
                                                                 abs=US)
    assert after["lower_s"] - before["lower_s"] == pytest.approx(lowered,
                                                                 abs=US)
    assert sum(e[3] for e in inside) < lowered


@pytest.mark.parametrize("ahead_of_time", [True, False])
def test_lower_and_compile_are_booked_under_one_name(cache_dir,
                                                     ahead_of_time):
    """`f` when traced, `jit(f)` when lowered and compiled: one row."""
    name = f"one_name_{int(ahead_of_time)}"
    step, x = fresh(name), jnp.ones((8, 8))
    jax.block_until_ready(x)  # its own eager programs are built by now
    before = compile_cache_stats()
    if ahead_of_time:
        step.lower(x).compile()
    else:
        jax.block_until_ready(step(x))
    delta = moved(before, compile_cache_stats())
    assert delta["programs"] == 1 and delta["misses"] == 1
    assert delta["hits"] == 0 and delta["cache_load_s"] == 0
    assert min(delta["trace_s"], delta["lower_s"],
               delta["backend_compile_s"]) > 0
    assert row(f"jit({name})") is None
    assert row(name)["count"] == 1
    assert row(name)["backend_compile_s"] == pytest.approx(
        delta["backend_compile_s"], abs=US)


def test_a_second_build_is_a_hit_and_a_load(cache_dir):
    """After `jax.clear_caches()` the same program comes back from the
    directory: a hit, `cache_load_s`, no `backend_compile_s`; JAX's own
    retrieval time is inside the load."""
    retrieved = []

    def listener(event, secs, **_):
        if event.endswith("cache_retrieval_time_sec"):
            retrieved.append(secs)

    step, x = fresh("built_twice"), jnp.ones((8, 8))
    step.lower(x).compile()
    jax.clear_caches()
    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        before = compile_cache_stats()
        step.lower(x).compile()
        after = compile_cache_stats()
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    delta = moved(before, after)
    assert delta["programs"] == 1
    assert delta["hits"] == 1 and delta["misses"] == 0
    assert delta["backend_compile_s"] == 0
    assert delta["cache_load_s"] + US >= retrieved[0] > 0
    # traced and lowered again: no cache removes those
    assert delta["trace_s"] > 0 and delta["lower_s"] > 0
    # at this size JAX reports the seconds saved as negative (the compile
    # took less than the read): it moved, whatever its sign
    assert after["saved_s"] != before["saved_s"]
    assert row("built_twice")["count"] == 2


def test_a_snapshot_is_fresh_plain_and_keeps_the_old_keys(cache_dir):
    fresh("snapshot").lower(jnp.ones((8, 8))).compile()
    first, second = compile_cache_stats(), compile_cache_stats()
    assert first == second
    assert first["by_function"] is not second["by_function"]
    for fun, line in first["by_function"].items():
        assert line is not second["by_function"][fun]
        assert set(line) == set(TIMES) | {"count"}
    # the runners take `dict(compile_cache_stats())` after warm-up and read
    # it after the window: a later compile must not reach into it
    kept = dict(first)
    frozen = json.dumps(kept, sort_keys=True)
    fresh("after_snapshot").lower(jnp.ones((8, 8))).compile()
    assert json.dumps(kept, sort_keys=True) == frozen
    assert json.loads(frozen)["dir"] == cache_dir
    for key in ("hits", "misses", "programs"):
        assert type(first[key]) is int
    for key in TIMES + ("saved_s",):
        assert type(first[key]) is float


def test_the_table_is_bounded(cache_dir):
    x = jnp.ones((8, 8))
    for i in range(compile_cache.TABLE_ROWS + 3):
        fresh(f"many_{i}").lower(x).compile()
    stats = compile_cache_stats()
    table = stats["by_function"]
    assert len(table) == compile_cache.TABLE_ROWS + 1
    assert sum(line["count"] for line in table.values()) == stats["programs"]
    for key in TIMES:
        assert sum(line[key] for line in table.values()) == pytest.approx(
            stats[key], abs=len(table) * US)
    named = [sum(line[k] for k in TIMES) for fun, line in table.items()
             if fun != compile_cache.OTHERS]
    assert named == sorted(named, reverse=True)


def test_hits_and_misses_count_as_before(cache_dir):
    """The three `entry.*` readers that were there read `hits`, `misses`."""
    step, x = fresh("counted"), jnp.ones((8, 8))
    before = compile_cache_stats()
    jax.block_until_ready(step(x))
    mid = compile_cache_stats()
    jax.clear_caches()
    jax.block_until_ready(step(x))
    after = compile_cache_stats()
    assert (mid["hits"] - before["hits"], mid["misses"] - before["misses"]) \
        == (0, 1)
    assert (after["hits"] - mid["hits"], after["misses"] - mid["misses"]) \
        == (1, 0)


def compile_events(log_dir):
    with open(log_dir / "trace.jsonl") as f:
        events = [json.loads(line) for line in f]
    return [e for e in events if e.get("cat") == "compile"]


def test_spans_land_on_the_compiling_threads_track(cache_dir, tmp_path):
    """`compile.trace`, `compile.lower`, `compile.backend`, then after
    `jax.clear_caches()` a `compile.load` with what it saved: complete
    events with `fun`, on the `tid` of the thread that compiled."""
    x = jnp.ones((8, 8))
    jax.block_until_ready(x)
    tracer = SpanTracer(str(tmp_path / "logs"))
    tids = {}

    def build(name):
        tids[name] = threading.get_ident()
        step = fresh(name)
        step.lower(x).compile()
        jax.clear_caches()
        step.lower(x).compile()

    try:
        build("on_main")
        worker = threading.Thread(target=build, args=("on_worker",))
        worker.start()
        worker.join(timeout=120)
        assert not worker.is_alive()
    finally:
        tracer.close()
    events = compile_events(tmp_path / "logs")
    assert tids["on_main"] != tids["on_worker"]
    for name, tid in tids.items():
        mine = [e for e in events if e["args"]["fun"] == name]
        assert [e["name"] for e in mine] == [
            "compile.trace", "compile.lower", "compile.backend",
            "compile.trace", "compile.lower", "compile.load"]
        assert {e["tid"] for e in mine} == {tid}
        assert all(e["ph"] == "X" and e["dur"] > 0 for e in mine)
        # retroactive, on the tracer's clock: in order, inside its run
        starts = [e["ts"] for e in mine]
        assert starts == sorted(starts) and starts[0] >= 0
        assert "saved_s" in mine[-1]["args"]
        assert "saved_s" not in mine[2]["args"]


def test_without_a_writing_tracer_nothing_is_emitted(cache_dir, tmp_path):
    tracer = SpanTracer(str(tmp_path / "logs"), enabled=False)
    try:
        fresh("unseen").lower(jnp.ones((8, 8))).compile()
    finally:
        tracer.close()
    assert not (tmp_path / "logs").exists()
    # and a tracer that has closed is no sink either
    fresh("after_close").lower(jnp.ones((8, 8))).compile()
    assert not (tmp_path / "logs").exists()


def test_a_subscriber_hears_each_program_until_it_leaves(cache_dir):
    heard = []
    x = jnp.ones((8, 8))
    jax.block_until_ready(x)
    compile_cache.subscribe(heard.append)
    try:
        step = fresh("heard")
        step.lower(x).compile()
        jax.clear_caches()
        step.lower(x).compile()
    finally:
        compile_cache.unsubscribe(heard.append)
    fresh("unheard").lower(x).compile()
    assert [p["fun"] for p in heard] == ["heard", "heard"]
    built, loaded = heard
    assert built["hit"] is False and loaded["hit"] is True
    assert set(built) == {"fun", "trace_s", "lower_s", "backend_compile_s",
                          "hit"}
    assert set(loaded) == {"fun", "trace_s", "lower_s", "cache_load_s",
                           "hit"}
    assert min(built["trace_s"], built["lower_s"],
               built["backend_compile_s"], loaded["cache_load_s"]) > 0


def test_many_threads_lose_no_update(cache_dir):
    """The checkpoint writer and the serving threads compile beside the
    loop: JAX's announcements from 32 threads at once, each thread's
    entries and exits its own, every program counted once."""
    import sys
    threads, each = 32, 150
    heard = []
    barrier = threading.Barrier(threads)

    def announce(i):
        barrier.wait(timeout=60)
        for j in range(each):
            for event, fun in ((compile_cache._TRACE, "stress"),
                               (compile_cache._TRACE, "callee"),
                               (compile_cache._BACKEND, "jit(stress)")):
                jax.monitoring.record_scalar(event, 0.0, fun_name=fun)
                if fun == "stress":
                    continue  # its callee's pair comes inside its own
                if fun == "jit(stress)" and (i + j) % 2:
                    jax.monitoring.record_event(compile_cache._HIT)
                jax.monitoring.record_event_time_span(
                    event, 0.0, 0.25, fun_name=fun)
                if fun == "callee":
                    jax.monitoring.record_event_time_span(
                        event, 0.0, 0.5, fun_name="stress")

    before = compile_cache_stats()
    compile_cache.subscribe(heard.append)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        workers = [threading.Thread(target=announce, args=(i,))
                   for i in range(threads)]
        for worker in workers:
            worker.start()
        for worker in workers:
            worker.join(timeout=120)
        assert not any(worker.is_alive() for worker in workers)
    finally:
        sys.setswitchinterval(interval)
        compile_cache.unsubscribe(heard.append)
    delta = moved(before, compile_cache_stats())
    total = threads * each
    assert delta["programs"] == total == len(heard)
    assert delta["hits"] == total // 2
    assert delta["trace_s"] == pytest.approx(0.5 * total)
    assert delta["cache_load_s"] == pytest.approx(0.25 * total / 2)
    assert delta["backend_compile_s"] == pytest.approx(0.25 * total / 2)
    assert row("stress")["count"] == total and row("callee") is None
    assert all(p["trace_s"] == 0.5 and p["hit"] == ("cache_load_s" in p)
               for p in heard)


@pytest.fixture(scope="module")
def tokens(tmp_path_factory):
    """24 short texts: six batches of four an epoch, two full windows of
    three steps."""
    from distributed_pytorch_from_scratch_tpu.data.tokenizer import (
        pre_tokenize, train_bpe)
    tmp = tmp_path_factory.mktemp("recompile_data")
    texts = ["the king rode out at dawn with his men",
             "a quiet morning on the river bank",
             "she sold sea shells by the sea shore",
             "to be or not to be that is the question"] * 6
    with open(tmp / "texts.json", "w") as f:
        json.dump({"train": texts, "validation": texts[:2]}, f)
    train_bpe(str(tmp / "texts.json"), str(tmp / "tok.json"), vocab_size=270)
    pre_tokenize(str(tmp / "texts.json"), str(tmp / "tokens.json"),
                 str(tmp / "tok.json"))
    return str(tmp / "tokens.json")


@pytest.mark.parametrize("max_steps,recompiled_at", [(5, [3]), (6, [])])
def test_train_names_the_step_that_recompiled(tokens, tmp_path, cache_dir,
                                              max_steps, recompiled_at):
    """Windows of three steps: a run of five ends on a window of two, the
    step function is built again at step 3 and the run says so in
    `metrics.jsonl`, on the timeline and in its record; a run of six ends on
    a full window and says nothing.

    On a cache directory of its own (`cache_dir`): `train()` turns the
    persistent cache on, and in the checkout's `.jax_cache/` an earlier run
    of this very test has left both `multi_step` programs, so the builds
    this test waits for came back as loads (`compile.load` spans, `hit`
    true) wherever the suite ran twice on one tree: it passed alone on a
    fresh copy and failed in the driver's whole run (PR 39)."""
    from distributed_pytorch_from_scratch_tpu import train as train_mod
    save_dir = tmp_path / "ck"
    record = train_mod.train(train_mod.get_train_args(
        ["--data_path", tokens, "--save_dir", str(save_dir),
         "--attn_dim", "32", "--ffn_dim", "64", "--num_heads", "4",
         "--num_layers", "2", "--maxlen", "32", "--batch_size", "4",
         "--max_steps", str(max_steps), "--steps_per_dispatch", "3",
         "--save_interval", "100", "--log_interval", "100",
         "--warmup_steps", "2"]))
    assert record["steps"] == max_steps
    assert record["recompiles"]["count"] == len(recompiled_at)
    assert [e["step"] for e in record["recompiles"]["events"]] \
        == recompiled_at
    json.dumps(record["compile_cache"])

    with open(save_dir / "logs" / "metrics.jsonl") as f:
        logged = [json.loads(line) for line in f]
    events = [e for e in logged if e.get("tag") == "recompile"]
    assert [e["step"] for e in events] == recompiled_at
    with open(save_dir / "logs" / "trace.jsonl") as f:
        timeline = [json.loads(line) for line in f]
    instants = [e for e in timeline if e["name"] == "recompile"]
    assert [e["args"]["step"] for e in instants] == recompiled_at
    assert all(e["ph"] == "i" for e in instants)
    steady = [e for e in timeline if e["name"] == "compile.backend"
              and e["args"]["fun"] == "multi_step"]
    # the steady program's own build, inside `train()`'s `compile` span,
    # and the tail's, inside its `step` span
    assert len(steady) == 1 + len(recompiled_at)
    for event in events:
        assert event["fun"] == "multi_step" and event["hit"] is False
        assert min(event["trace_s"], event["lower_s"],
                   event["backend_compile_s"]) > 0
        (step_span,) = [e for e in timeline if e["name"] == "step"
                        and e["args"].get("step") == event["step"]]
        inside = steady[-1]
        assert step_span["ts"] <= inside["ts"]
        assert inside["ts"] + inside["dur"] \
            <= step_span["ts"] + step_span["dur"] + 1e3
    # nobody is left listening for a loop that has ended
    assert compile_cache._subscribers == []
