"""benchmark/lib/trace.py against hand-worked values on a hand-built capture
(read through `jax.profiler.ProfileData`, as a real one is), and against an
independent sweep on two steps cut from a real chip trace of
gpt2-medium.train-b12-t1024 (fixtures/gpt2-medium.two-steps.json.gz: recorded
with `run.py --trace 1 --dump`, names already cut down by `parse_hlo`)."""

import gzip
import json
import os
import re

import pytest

from benchmark.lib import kernels, trace

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")

FUSION_1 = "%fusion.1 = f32[8]{0:T(8)} fusion(f32[8]{0} %p.1)"
FUSION_2 = "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %fusion.1)"
ALL_REDUCE = "%all-reduce.1 = f32[8]{0} all-reduce(f32[8]{0} %fusion.1)"
WHILE = ("%while.1 = (s32[]{:T(128)}, f32[8]{0}) while((s32[]{:T(128)}, "
         "f32[8]{0}) %tuple.1)")
FLASH = ("%closed_call.8 = (bf16[2,8,4]{2,1,0:T(8,128)(2,1)}, f32[2,8,1]"
         "{2,1,0}) custom-call(bf16[2,8,4]{2,1,0} %bitcast.1, bf16[2,8,4]"
         "{2,1,0} %bitcast.2, bf16[2,8,4]{2,1,0} %bitcast.3), "
         "custom_call_target=\\\"tpu_custom_call\\\", operand_layout_"
         "constraints={bf16[2,8,4]{2,1,0}}")
# the chip never runs it: the capture stamps it, with no duration, at the
# start of the op that follows
BITCAST = "%bitcast.9 = f32[8]{0} bitcast(f32[8]{0} %fusion.1)"
GATHER = ("%all-gather-start.1 = (f32[4]{0}, f32[8]{0}) all-gather-start("
          "f32[4]{0} %p.2)")

# (name, start ns, duration ns); the hand-worked values below follow these
LINES = {
    "/device:TPU:0": {
        "XLA Modules": [("jit_init(2)", 0, 500), ("jit_step(1)", 1000, 1000),
                        ("jit_step(1)", 2100, 900)],
        "XLA Ops": [("%iota.1 = s32[8]{0} iota()", 100, 100),
                    (WHILE, 1000, 800), (FUSION_1, 1000, 300),
                    (ALL_REDUCE, 1300, 200), (FLASH, 1500, 300),
                    (FUSION_2, 1900, 100),
                    (FUSION_1, 2100, 300), (ALL_REDUCE, 2400, 100),
                    (FLASH, 2500, 300), (BITCAST, 2500, 0),
                    (FUSION_2, 2900, 100)],
        "Async XLA Ops": [(GATHER, 1200, 250)],
    },
    "/host:CPU": {
        "python3": [("bench.wait", 1750, 200), ("bench.dispatch", 1990, 100),
                    ("shard_args", 1995, 10)],
    },
}


def text_proto() -> str:
    out = []
    for plane, lines in LINES.items():
        names = sorted({e[0] for evs in lines.values() for e in evs})
        ids = {n: i + 1 for i, n in enumerate(names)}
        out.append(f'planes {{ name: "{plane}"')
        for line, evs in lines.items():
            out.append(f'  lines {{ name: "{line}" timestamp_ns: 0')
            out += [f"    events {{ metadata_id: {ids[n]} offset_ps: "
                    f"{s * 1000} duration_ps: {d * 1000} }}"
                    for n, s, d in evs]
            out.append("  }")
        out += [f'  event_metadata {{ key: {i} value {{ id: {i} name: '
                f'"{n}" }} }}' for n, i in ids.items()]
        out.append("}")
    return "\n".join(out)


@pytest.fixture(scope="module")
def built():
    from jax.profiler import ProfileData
    return trace.planes_of(ProfileData.from_text_proto(text_proto()))


def test_parse_hlo():
    assert trace.parse_hlo(FUSION_1) == ("fusion.1", "fusion")
    assert trace.parse_hlo(WHILE) == ("while.1", "while")
    assert trace.parse_hlo(FLASH.replace("\\", "")) == (
        "closed_call.8", "custom-call tpu_custom_call operands=3")
    assert trace.parse_hlo(GATHER) == ("all-gather-start.1",
                                       "all-gather-start")
    assert trace.parse_hlo("bench.wait") == ("bench.wait", "")


def test_interval_arithmetic():
    u = trace.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)])
    assert u == [(0, 3), (5, 8)] and trace.length(u) == 6
    assert trace.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert trace.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert trace.subtract([(0, 4)], []) == [(0, 4)]


def test_hand_built_capture(built):
    assert trace.step_module(built) == "jit_step(1)"
    (dev,) = trace.device_traces(built)
    assert dev.index == 0 and dev.steps == 2
    assert dev.window == (1000, 3000)
    # the while is a container, the iota ran before the window, and the
    # bitcast of no duration does not make a container of the flash call
    assert [e.name for e in dev.ops] == [
        "fusion.1", "all-reduce.1", "closed_call.8", "fusion.2"] * 2
    assert dev.busy_ns() == 1700            # 800 + 100 + 700 + 100
    assert dev.gaps() == [(1800, 1900), (2000, 2100), (2800, 2900)]
    assert 1 - dev.busy_ns() / dev.window_ns == pytest.approx(0.15)
    # collectives: all-reduce 200 + 100, the async all-gather span
    # [1200, 1450) overlaps the first: union [1200, 1500) + [2400, 2500)
    assert dev.collective_ns() == 400
    # fusion.1 runs until 1300, so [1200, 1300) of it is hidden
    assert dev.exposed_collective_ns() == 300
    flash = dev.select(kernels.FLASH_FORWARD)
    assert len(flash) == 2 and dev.time_ns(flash) == 600
    assert dev.select(kernels.FLASH_BACKWARD) == []
    assert dev.select(kernels.CUSTOM_CALL) == flash


def test_breakdown_of_hand_built_capture(built):
    (dev,) = trace.device_traces(built)
    assert trace.top_ops(dev, 3) == [
        ("fusion.1", 600e-9),
        ("closed_call.8 [custom-call tpu_custom_call operands=3]", 600e-9),
        ("all-reduce.1", 300e-9)]
    spans = trace.host_spans(built, "bench.")
    assert [s.name for s in spans] == ["bench.wait", "bench.dispatch"]
    assert trace.top_gaps(dev, spans) == [
        ("bench.wait", 100e-9), ("bench.dispatch", 100e-9),
        ("unattributed", 100e-9)]


def test_plain_round_trip(built):
    assert trace.from_plain(json.loads(json.dumps(
        trace.to_plain(built)))) == built


def test_no_device_plane_gives_nothing():
    host_only = [p for p in trace.from_plain(trace.to_plain(
        [trace.Plane("/host:CPU", [trace.Line("python3", [
            trace.Event("bench.wait", 0, 5)])])]))]
    assert trace.device_traces(host_only) == []


# ---- two steps of a real chip trace ----

@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(FIXTURES, "gpt2-medium.two-steps.json.gz")
    with gzip.open(path, "rt") as f:
        return trace.from_plain(json.load(f))


def sweep_busy(ops, lo, hi):
    """Busy time by an independent method: walk the sorted ends."""
    busy, reach = 0, lo
    for e in sorted(ops, key=lambda e: e.start_ns):
        a, b = max(e.start_ns, reach), min(e.end_ns, hi)
        if b > a:
            busy += b - a
            reach = b
    return busy


def test_recorded_steps(recorded):
    (dev,) = trace.device_traces(recorded)
    assert dev.steps == 2 and dev.window == (0, 607125970)
    assert len(dev.ops) == 13703
    assert dev.busy_ns() == sweep_busy(dev.ops, *dev.window) == 607028830
    assert 100 * (1 - dev.busy_ns() / dev.window_ns) == pytest.approx(
        0.0160, abs=1e-4)
    # 24 layers x 2 steps: forward twice a layer (remat), backward once
    fwd, bwd = (dev.select(p) for p in (kernels.FLASH_FORWARD,
                                        kernels.FLASH_BACKWARD))
    assert (len(fwd), len(bwd)) == (96, 48)
    assert {re.sub(r"\.\d+$", "", e.name) for e in fwd} == {
        "closed_call", "rematted_computation"}
    assert dev.time_ns(fwd) == sum(e.dur_ns for e in fwd) == 63597914
    assert dev.time_ns(bwd) == 65591138
    # one chip: no collective
    assert dev.collective_ns() == 0 and dev.exposed_collective_ns() == 0
    # every leaf once: per-op totals add up to the busy time
    totals = trace.top_ops(dev, n=10**6)
    assert sum(s for _, s in totals) * 1e9 == pytest.approx(
        dev.busy_ns(), rel=1e-9)
    assert totals[0][0].startswith("checkpoint.10 [custom-call")
    gaps = trace.top_gaps(dev, trace.host_spans(recorded, "bench."), 3)
    assert gaps[0][1] == pytest.approx(20.328e-6)


def test_recorded_collectives():
    """One step of chip 0 of gpt2-large.train-dp2-tp2 (no async line kept):
    6 tensor-parallel all-reduces a layer x 36 layers, and 8 more for the
    data-parallel gradient reduction, the loss and the norm, none hidden."""
    path = os.path.join(FIXTURES, "gpt2-large.one-step-chip0.json.gz")
    with gzip.open(path, "rt") as f:
        (dev,) = trace.device_traces(trace.from_plain(json.load(f)))
    assert dev.steps == 1 and dev.window == (0, 335356024)
    coll = dev.collectives()
    assert len(coll) == 6 * 36 + 8
    assert {e.meta for e in coll} == {"all-reduce"}
    # the program's psums keep JAX's name, XLA's own are `all-reduce.N`
    assert {re.sub(r"\.\d+$", "", e.name) for e in coll} == {
        "psum_invariant", "all-reduce", "pmax"}
    by_hand = sum(e.dur_ns for e in dev.ops if e.meta == "all-reduce")
    assert dev.collective_ns() == by_hand == 106940198
    # ops on one TensorCore do not overlap: all of it is exposed
    assert dev.exposed_collective_ns() == by_hand
    assert dev.busy_ns() == sweep_busy(dev.ops, *dev.window) == 333300482
    # every layer's flash calls: forward, recomputed forward, backward
    assert [len(dev.select(p)) for p in (
        kernels.FLASH_FORWARD, kernels.FLASH_BACKWARD)] == [72, 36]


@pytest.mark.parametrize("cell,fixture,flash_ms,roofline", [
    # by hand, medium: 12 x 16 rows of 1024 x 64 a call; a forward call is
    # 4 x 192 x 1024 x 1025 / 2 x 64 FLOPs = 130.9 us at 197 TFLOP/s, a
    # backward 327.4 us; 96 + 48 calls make 28.28 ms of the 129.19 ms taken
    ("gpt2-medium.train-b12-t1024", "gpt2-medium.two-steps.json.gz",
     64.594526, 21.893),
    # large under dp2 x tp2: 8 x 10 rows a chip; 72 forward calls of 54.6 us
    # and 36 backward of 136.4 us make 8.84 ms
    ("gpt2-large.train-dp2-tp2", "gpt2-large.one-step-chip0.json.gz",
     40.116558, 22.039),
])
def test_flash_readers_take_their_shapes_from_the_cell(cell, fixture,
                                                       flash_ms, roofline):
    """The runner hands the readers the workload file, the family's sizes
    and the mesh, and nothing of any kernel's."""
    from types import SimpleNamespace
    from benchmark.lib import peaks
    from benchmark.lib.files import load_json, load_module

    workload = load_json("workloads", cell + ".json")
    config = load_json("configs", workload["config"] + ".json")
    with gzip.open(os.path.join(FIXTURES, fixture), "rt") as f:
        devices = trace.device_traces(trace.from_plain(json.load(f)))
    m = SimpleNamespace(
        devices=devices, workload=workload, mesh=workload["mesh"],
        sizes=load_module("families", config["family"]).sizes_of(config),
        peak=peaks.peak_for("TPU v5 lite"))
    took_ms = load_module("layer_metrics", "kernels.flash_ms").read(m)
    share = load_module("layer_metrics", "kernels.flash_roofline").read(m)
    assert took_ms == pytest.approx(flash_ms, rel=1e-6)
    assert share == pytest.approx(roofline, rel=1e-3)
