"""BENCHMARK.json is well formed and every name in it finds its file."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def one_line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_keys_and_sizes(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["paths"] == ["benchmark"]
    assert manifest["command"][:2] == ["python3", "benchmark/run.py"]
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(manifest["configs"]) <= 24
    assert 1 <= len(manifest["workloads"]) <= 24
    assert 1 <= len(manifest["end_to_end"]) <= 16
    assert 1 <= len(manifest["per_layer"]) <= 128


def test_names_and_units(manifest):
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    for group in (manifest["configs"], manifest["workloads"], metrics):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher"), m
        assert m["source"] in SOURCES, m
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert one_line(m["layer"])
    assert "setup_s" in [m["name"] for m in manifest["end_to_end"]]


def test_configs_have_their_files(manifest):
    files = set()
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert one_line(c["source"]) and one_line(c["why"])
        assert c["file"].startswith("benchmark/") and c["file"] not in files
        files.add(c["file"])
        body = load(*c["file"].split("/")[1:])
        assert body["source"] == c["source"]
        assert sorted(body["reduced"]) == sorted(c["reduced"])
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key)
            # never a width
            assert not re.search(r"(_dim|_rank|n_embd|n_inner|hidden|head)",
                                 key), key
        assert os.path.isfile(os.path.join(
            BENCH, "families", body["family"] + ".py"))


def test_workloads_have_their_files(manifest):
    configs = {c["name"] for c in manifest["configs"]}
    used, pairs = set(), set()
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and one_line(w["why"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
        body = load("workloads", w["name"] + ".json")
        assert body["config"] == w["config"] and body["chips"] == w["chips"]
        assert os.path.isfile(os.path.join(
            BENCH, "runners", body["runner"] + ".py"))
        assert os.path.isfile(os.path.join(
            BENCH, "data", body["data"]["kind"] + ".py"))
        assert "rehearse" in body
    assert used == configs
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 4)


def test_layer_metrics_move_what_their_cells_report(manifest):
    cells = [w["name"] for w in manifest["workloads"]]

    def cells_of(metric):
        assert set(metric.get("workloads", cells)) <= set(cells)
        return set(metric.get("workloads", cells))

    reported = {m["name"]: cells_of(m) for m in manifest["end_to_end"]}
    assert reported["setup_s"] == set(cells)
    for m in manifest["per_layer"]:
        assert m["moves"] in reported, m
        assert cells_of(m) <= reported[m["moves"]], m
        assert os.path.isfile(os.path.join(
            BENCH, "layer_metrics", m["name"] + ".py")), m["name"]
    for cell in cells:
        assert sum(cell in c for c in reported.values()) >= 2
        assert any(cell in cells_of(m) for m in manifest["per_layer"])
    # one spelling per layer
    layers = {m["layer"] for m in manifest["per_layer"]}
    assert len({l.lower() for l in layers}) == len(layers)


def test_every_reader_is_in_the_manifest(manifest):
    names = {m["name"] for m in manifest["per_layer"]}
    on_disk = {f[:-3] for f in os.listdir(os.path.join(BENCH, "layer_metrics"))
               if f.endswith(".py")}
    assert on_disk == names


def _cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("cell", _cells())
def test_an_expert_cell_carries_its_weights_seed_and_no_other_does(cell):
    """A cell whose family routes experts times a step that follows its
    router's choices, so its weights are the file's (`init_seed`); a cell
    with no router keeps its weights on the driver's seed."""
    body = load("workloads", cell + ".json")
    routes = "num_experts_per_tok" in load("configs", body["config"] + ".json")
    assert ("init_seed" in body) == routes
    if routes:
        assert type(body["init_seed"]) is int
        assert 0 <= body["init_seed"] < 2 ** 31
    # (a rehearsal may pin another: the hybrid cell's tiny bfloat16 shape
    # flips a pair in a hundred and passes `train`'s limits on some weights
    # only, as it did on some seeds)


@pytest.mark.parametrize("cell", _cells())
def test_a_cell_that_pins_its_stream_says_replay(cell, manifest):
    """The fallback of PERF.md section 4: a `data.seed` in the file makes
    every run the same job's same batches, and both `why`s say so."""
    body = load("workloads", cell + ".json")
    listed = next(w for w in manifest["workloads"] if w["name"] == cell)
    pinned = "seed" in body["data"]
    assert pinned == ("replay" in listed["why"].lower())
    if pinned:
        assert type(body["data"]["seed"]) is int and "init_seed" in body
        assert "replay" in body["why"].lower()


REPLAYS = {"lfm2-8b-a1b.train-ep4share-b2-t8192",                 # PR 49
           "joyai-llm-flash.train-ep16share-b4-t4096",            # PR 55
           "sdar-30b-a3b.train-ep8share-b2-t4096"}                # PR 55


def test_three_expert_cells_are_replays_and_three_draw_their_batches():
    """The replays' files hold an integer `data.seed` beside `init_seed`;
    the three other expert cells hold no `data.seed`, so `correct` is still
    read on fresh batches there (PERF.md section 2)."""
    bodies = {c: load("workloads", c + ".json") for c in _cells()}
    expert = {c: b for c, b in bodies.items() if "init_seed" in b}
    assert len(expert) == 6
    assert {c for c, b in expert.items() if "seed" in b["data"]} == REPLAYS
    for cell in REPLAYS:
        assert type(expert[cell]["data"]["seed"]) is int
        assert type(expert[cell]["init_seed"]) is int
