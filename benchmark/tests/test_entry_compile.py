"""The five `entry.*` readers of what the program's compile listener counts
by the end of set-up (`compile_cache_stats()`, handed whole as
`measured.cache_setup`), and one cell rehearsed with them."""

import json
from types import SimpleNamespace

import pytest

from benchmark.lib.files import load_module
from benchmark.tests.test_run import CELLS, MANIFEST, run

READS = {"entry.trace_s": "trace_s", "entry.lower_s": "lower_s",
         "entry.backend_compile_s": "backend_compile_s",
         "entry.cache_load_s": "cache_load_s", "entry.programs": "programs"}
# what the program hands the runners today, and what the parent's did
TODAY = {"dir": "/x", "hits": 40, "misses": 2, "programs": 57, "saved_s": 91.5,
         "trace_s": 6.25, "lower_s": 3.5, "backend_compile_s": 1.75,
         "cache_load_s": 4.125,
         "by_function": {"step": {"trace_s": 2.0, "lower_s": 1.5,
                                  "backend_compile_s": 0.0,
                                  "cache_load_s": 3.0, "count": 1}}}
PARENT = {"dir": "/x", "hits": 40, "misses": 2}


@pytest.mark.parametrize("name,key", sorted(READS.items()))
def test_a_reader_reads_its_counter_or_nothing(name, key):
    read = load_module("layer_metrics", name).read
    assert read(SimpleNamespace(cache_setup=TODAY)) == TODAY[key]
    assert read(SimpleNamespace(cache_setup=PARENT)) is None


def test_the_five_entries_are_listed_and_move_setup():
    # found by name: later PRs append their metrics after these
    entries = [m for m in MANIFEST["per_layer"] if m["name"] in READS]
    assert [m["name"] for m in entries] == [
        "entry.trace_s", "entry.lower_s", "entry.backend_compile_s",
        "entry.cache_load_s", "entry.programs"]
    for m in entries:
        assert m["layer"] == "entry points" and m["moves"] == "setup_s"
        assert m["better"] == "lower" and "workloads" not in m
        assert m["source"] == ("program_counter" if m["name"]
                               == "entry.programs" else "program_span")


def test_rehearsed_times_are_null_and_the_count_is_a_number():
    done = run(["--workload", CELLS[0][0], "--seed", "3000000019",
                "--seconds", "2", "--trace", "1", "--rehearse"])
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(text) for text in done.stdout.strip().splitlines()]
    metrics = lines[-1]["metrics"]
    for name in READS:
        if name != "entry.programs":
            assert metrics[name] == {"value": None, "unit": "s"}
    assert metrics["entry.programs"]["unit"] == "count"
    assert metrics["entry.programs"]["value"] >= 3
    # the old readers read what they read
    assert metrics["entry.compiles_in_window"]["value"] == 0
    assert isinstance(metrics["entry.cache_misses"]["value"], int)
    # the `setup` log line still parses, with the table on it
    (setup,) = [line for line in lines[:-1] if line.get("event") == "setup"]
    counted = setup["compile_cache"]
    assert counted["programs"] == metrics["entry.programs"]["value"]
    assert counted["programs"] == sum(
        row["count"] for row in counted["by_function"].values())
    assert setup["compile_cache_after_window"]["programs"] \
        == counted["programs"]
