"""graftcheck (analysis/ + scripts/graftcheck.py) — ISSUE 11.

Three layers of pinning:

* **fixture corpus** — every lint rule has a known-bad snippet that must
  trigger EXACTLY that rule and a known-good sibling that must stay
  clean (tests/graftcheck_fixtures/); plus the pragma escape hatch.
* **clean-repo gate** — the layer-1 sweep over this repo returns zero
  violations. Every future PR inherits the contract: new dead imports,
  donation misuse, host calls in traced code etc. fail HERE, not on a chip.
* **trace contracts** — the acceptance pins: the compiled train step's
  collective inventory matches `obs/attribution.expected_collectives`
  for zero ∈ {1,2,3} at dp2 x tp2 + SP; the int8-wire step provably
  carries no wide dp payload; ZeRO-3 contains no whole-tree dp gather
  (and refuses int8 loudly); the paged decode step's donation actually
  aliases and its lowering is stable across host states.
"""

import glob
import json
import os
import subprocess
import sys

import pytest

from distributed_pytorch_from_scratch_tpu.analysis import (
    GRAFTCHECK_SCHEMA_VERSION, RULES, build_report, format_report,
    lint_file, lint_paths, validate_report)
from distributed_pytorch_from_scratch_tpu.analysis.report import (
    write_report)
from distributed_pytorch_from_scratch_tpu.analysis.rules import EXCLUDE_DIRS

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
FIXTURES = os.path.join(HERE, "graftcheck_fixtures")

ALL_RULES = sorted(RULES)


# ------------------------------------------------------------ fixtures --

def _fixture(name):
    return os.path.join(FIXTURES, name + ".py")


@pytest.mark.parametrize("rule", ALL_RULES)
def test_bad_fixture_triggers_exactly_its_rule(rule):
    """Positive fixture: the known-bad snippet fires its rule (and ONLY
    its rule — cross-talk would make every pragma suppress too much)."""
    path = _fixture("bad_" + rule.replace("-", "_"))
    assert os.path.exists(path), f"no bad fixture for rule {rule}"
    vios = lint_file(path)
    hit = sorted({v.rule for v in vios})
    assert hit == [rule], (rule, [v.format() for v in vios])
    assert all(v.line > 0 for v in vios)


@pytest.mark.parametrize("rule", ALL_RULES)
def test_good_fixture_stays_clean(rule):
    """Negative fixture: the corrected idiom produces no violations at
    all (any rule firing here is a false positive)."""
    path = _fixture("good_" + rule.replace("-", "_"))
    assert os.path.exists(path), f"no good fixture for rule {rule}"
    vios = lint_file(path)
    assert vios == [], [v.format() for v in vios]


def test_rule_count_meets_acceptance_floor():
    """ISSUE 11 acceptance: >= 8 rules, each with both fixture polarities
    (the two tests above parametrize over exactly these)."""
    assert len(ALL_RULES) >= 8, ALL_RULES


def test_pragma_suppresses_on_line_and_file():
    bad = open(_fixture("bad_unused_import")).read()
    # line pragma on the flagged import
    patched = bad.replace(
        "import json",
        "import json  # graftcheck: disable=unused-import", 1)
    vios = lint_file(_fixture("bad_unused_import"), text=patched)
    assert all("json" not in v.message for v in vios)
    assert any(v.rule == "unused-import" for v in vios)  # other import
    # file pragma kills the whole rule
    patched = "# graftcheck: disable-file=unused-import\n" + bad
    vios = lint_file(_fixture("bad_unused_import"), text=patched)
    assert vios == []


def test_report_path_override_names_snippets():
    vios = lint_file(_fixture("bad_unreachable_code"),
                     report_path="<snippet>")
    assert vios and all(v.path == "<snippet>" for v in vios)


# -------------------------------------------------------- clean-repo gate --

def _committed():
    """What the sweep walks of the repo's root: its files and directories
    but those `.gitignore` names as directories (a builder's `.scratch/`,
    what a chip call brings back: a probe there is in no checkout the
    driver makes) and those no sweep enters (`EXCLUDE_DIRS`)."""
    with open(os.path.join(REPO, ".gitignore")) as f:
        ignored = {line.strip().rstrip("/") for line in f
                   if line.strip().endswith("/")}
    return [os.path.join(REPO, name) for name in sorted(os.listdir(REPO))
            if name not in ignored | EXCLUDE_DIRS
            and (name.endswith(".py")
                 or os.path.isdir(os.path.join(REPO, name)))]


@pytest.fixture(scope="module")
def repo_sweep():
    return lint_paths(_committed(), root=REPO)


def test_repo_sweep_is_clean(repo_sweep):
    """THE gate: the layer-1 sweep over this repo is violation-free.
    When this fails, either fix the finding or (for a justified
    exception) add an inline `# graftcheck: disable=<rule>` pragma —
    see docs/ANALYSIS.md."""
    vios, files = repo_sweep
    assert files > 100, f"sweep saw only {files} files — wrong root?"
    assert vios == [], "\n".join(v.format() for v in vios)


def test_sweep_excludes_the_fixture_corpus(repo_sweep):
    """The deliberately-bad fixtures must NOT be swept (they would turn
    the clean-repo gate permanently red) — but sweeping the corpus
    directly does find them."""
    vios, _ = repo_sweep
    assert not any("graftcheck_fixtures" in v.path for v in vios)
    vios, files = lint_paths(glob.glob(os.path.join(FIXTURES, "bad_*.py")),
                             root=REPO)
    assert files >= 8 and vios


# ---------------------------------------------------------------- report --

def test_report_schema_roundtrip(tmp_path):
    vios = lint_file(_fixture("bad_unused_import"))
    doc = build_report(vios, files_scanned=1,
                       contracts=[{"name": "x", "ok": True, "detail": ""}],
                       duration_s=0.1)
    assert doc["schema_version"] == GRAFTCHECK_SCHEMA_VERSION
    assert doc["ok"] is False
    assert doc["violation_counts"] == {"unused-import": len(vios)}
    assert validate_report(doc) == []
    p = tmp_path / "graftcheck.json"
    write_report(doc, str(p))
    loaded = json.loads(p.read_text())
    assert validate_report(loaded) == []
    text = format_report(loaded)
    assert "unused-import" in text and "graftcheck:" in text


def test_report_validator_fails_loudly_on_drift():
    doc = build_report([], 0, [])
    doc["schema_version"] = GRAFTCHECK_SCHEMA_VERSION + 1
    assert any("NEWER" in p for p in validate_report(doc))
    assert any("missing field" in p
               for p in validate_report({"tool": "graftcheck"}))


def test_clean_report_is_ok_and_failed_contract_is_not():
    assert build_report([], 5, [])["ok"] is True
    doc = build_report([], 5, [{"name": "c", "ok": False, "detail": "d"}])
    assert doc["ok"] is False
    assert "FAIL" in format_report(doc)


# ------------------------------------------------------------------- CLI --

def _run_cli(args):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "graftcheck.py")]
        + args, capture_output=True, text=True, cwd=REPO, timeout=120)


@pytest.mark.parametrize("rule", ALL_RULES)
def test_cli_exits_1_on_each_fixture_violation(rule):
    """ISSUE 11 acceptance, literally: the CLI exits 1 on EACH rule's
    fixture violation (jax-free --no-trace path, ~1 s per run)."""
    out = _run_cli(["--no-trace", _fixture("bad_" + rule.replace("-", "_"))])
    assert out.returncode == 1, (rule, out.stdout, out.stderr)
    assert rule in out.stdout


def test_cli_no_trace_exits_by_verdict(tmp_path):
    """Exit 1 on each fixture violation, 0 on a clean file — without ever
    importing jax (--no-trace must stay chip-image-independent)."""
    bad = _run_cli(["--no-trace", _fixture("bad_use_after_donate")])
    assert bad.returncode == 1, bad.stdout + bad.stderr
    assert "use-after-donate" in bad.stdout
    good = _run_cli(["--no-trace", _fixture("good_use_after_donate"),
                     "--json", str(tmp_path / "r.json")])
    assert good.returncode == 0, good.stdout + good.stderr
    doc = json.loads((tmp_path / "r.json").read_text())
    assert validate_report(doc) == [] and doc["ok"] is True
    # the skipped trace layer is recorded as "no contracts", not "clean"
    assert doc["contracts"] == []


def test_summarize_run_renders_graftcheck_section(tmp_path):
    """scripts/summarize_run.py renders a 'Static contracts' section when
    a graftcheck report is present in the run dir (the CI/tooling
    satellite), including the failing contract's detail."""
    import importlib.util
    from distributed_pytorch_from_scratch_tpu.analysis.rules import (
        Violation)
    doc = build_report(
        [Violation("unused-import", "x.py", 3, "'json' never used")], 3,
        [{"name": "donation-aliased", "ok": False,
          "detail": "2 leaves un-aliased", "program": "paged_decode"}])
    write_report(doc, str(tmp_path / "graftcheck.json"))
    spec = importlib.util.spec_from_file_location(
        "_gc_summarize", os.path.join(REPO, "scripts", "summarize_run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    text = mod.summarize(str(tmp_path))
    assert "Static contracts" in text
    assert "VIOLATIONS" in text and "unused-import" in text
    assert "FAIL donation-aliased" in text and "paged_decode" in text
    # and a future-versioned report warns instead of rendering garbage
    doc["schema_version"] += 10
    write_report(doc, str(tmp_path / "graftcheck.json"))
    assert "SCHEMA DRIFT" in mod.summarize(str(tmp_path))


def test_cli_list_rules():
    out = _run_cli(["--list-rules"])
    assert out.returncode == 0
    for rule in ALL_RULES:
        assert rule in out.stdout


def test_cli_rejects_unknown_rule_ids():
    """A typo'd --rules must exit 2, not filter every finding and report
    a false 'clean'."""
    out = _run_cli(["--no-trace", "--rules", "use_after_donate",
                    _fixture("bad_use_after_donate")])
    assert out.returncode == 2, (out.stdout, out.stderr)
    assert "unknown rule id" in out.stderr
    # the kebab-case id works and still fails the file
    out = _run_cli(["--no-trace", "--rules", "use-after-donate",
                    _fixture("bad_use_after_donate")])
    assert out.returncode == 1


# ------------------------------------------------- trace contracts (L2) --

@pytest.fixture(scope="module")
def contracts_mod():
    from distributed_pytorch_from_scratch_tpu.analysis import contracts
    return contracts


@pytest.fixture(scope="module")
def programs_mod():
    from distributed_pytorch_from_scratch_tpu.analysis import programs
    return programs


@pytest.mark.parametrize("stage,wire", [(0, "f32"), (1, "f32"),
                                        (2, "f32"), (2, "int8"),
                                        (3, "f32")])
def test_collective_inventory_matches_priced_schedule(
        contracts_mod, programs_mod, stage, wire):
    """ISSUE 11 acceptance + the satellite pin: the compiled train step's
    per-axis collective inventory at dp2 x tp2 + SP equals what
    `expected_collectives` derives from the priced schedule, for zero
    stages 0-3 (and the int8 stage-2 wire). Attribution drift — a new
    collective, a vanished one, a dtype change — fails here. Stage 0's
    donation leg is the regression pin for the out_shardings fix this
    checker found in training/train_step.py."""
    from distributed_pytorch_from_scratch_tpu.obs.attribution import (
        expected_collectives)
    prog = programs_mod.train_step_program(stage, wire)
    res = contracts_mod.check_collective_inventory(
        prog, expected_collectives(**prog.config))
    assert res["ok"], res["detail"]
    # and the donation contract rides along on every lowered step
    res = contracts_mod.check_donation_aliased(prog)
    assert res["ok"], res["detail"]


def test_stage2_inventory_actually_detects_drift(contracts_mod,
                                                 programs_mod):
    """The inventory check must FAIL when the schedule and the program
    disagree — pin it against a deliberately wrong expectation."""
    from distributed_pytorch_from_scratch_tpu.obs.attribution import (
        expected_collectives)
    prog = programs_mod.train_step_program(2, "f32")
    wrong = expected_collectives(**dict(prog.config, zero_stage=3))
    res = contracts_mod.check_collective_inventory(prog, wrong)
    assert not res["ok"]
    assert "all-gather" in res["detail"]  # stage 3 forbids the dp gather


def test_int8_wire_carries_no_wide_dp_payload(contracts_mod,
                                              programs_mod):
    """ISSUE 11 acceptance: the int8-wire train step provably contains no
    f32 dp-axis collective beyond the documented param all-gather — the
    'int8 silently falls back to f32' hazard, checked statically."""
    prog = programs_mod.train_step_program(2, "int8")
    res = contracts_mod.check_no_wide_dp_wire(
        prog, allowed_ops=("all-gather",))
    assert res["ok"], res["detail"]
    # the f32-wire sibling must FAIL the same check (the contract has
    # teeth: it distinguishes the wires, not just passes everything)
    prog32 = programs_mod.train_step_program(2, "f32")
    res32 = contracts_mod.check_no_wide_dp_wire(
        prog32, allowed_ops=("all-gather",))
    assert not res32["ok"]


def test_zero3_has_no_whole_tree_gather_and_refuses_int8(
        contracts_mod, programs_mod):
    prog = programs_mod.train_step_program(3, "f32")
    res = contracts_mod.check_zero3_no_whole_tree_gather(prog)
    assert res["ok"], res["detail"]
    msg = programs_mod.train_step_refuses(3, "int8")
    assert msg is not None and "stage 2" in msg


def test_paged_decode_donation_aliased_and_lowering_stable(
        contracts_mod, programs_mod):
    """ISSUE 11 acceptance: the paged decode step's donated KV pool
    halves alias in the executable (in-place page writes survive
    compile), and the lowering is byte-identical across host states
    (cursors, step index, table contents) — no per-step recompiles."""
    prog = programs_mod.paged_decode_program()
    res = contracts_mod.check_donation_aliased(prog)
    assert res["ok"], res["detail"]
    assert prog.donated_leaves == 2  # pool ks + vs
    res = contracts_mod.check_stable_lowering(
        "paged_decode", contracts_mod._decode_lowerings())
    assert res["ok"], res["detail"]


def test_pallas_decode_same_schedule_and_stable_lowering(
        contracts_mod, programs_mod):
    """ISSUE 14 layer-2 satellite: the PALLAS decode dispatch (the
    kernel lowered through the interpreter on the contract mesh) must
    (a) satisfy the SAME expected_collectives schedule as the gather
    impl — the kernel changes HBM traffic, never the wire, so any new
    collective is a contract failure, (b) keep the donated pool halves
    aliased, and (c) lower byte-identically from 3 host states — the
    scalar-prefetched page table must never bake values into the
    program."""
    from distributed_pytorch_from_scratch_tpu.obs.attribution import (
        expected_collectives)
    prog = programs_mod.paged_decode_program(paged_attn="pallas")
    res = contracts_mod.check_collective_inventory(
        prog, expected_collectives(**prog.config))
    assert res["ok"], res["detail"]
    res = contracts_mod.check_donation_aliased(prog)
    assert res["ok"], res["detail"]
    res = contracts_mod.check_stable_lowering(
        "paged_decode_pallas",
        contracts_mod._decode_lowerings(paged_attn="pallas"))
    assert res["ok"], res["detail"]
    # the gather and pallas programs carry the same (axis, op) inventory
    gather = programs_mod.paged_decode_program()
    inv = lambda p: {k: v["count"] for k, v in contracts_mod.inventory(
        contracts_mod.parse_collectives_by_axis(p.compiled_text,
                                                p.mesh)).items()}
    assert inv(prog) == inv(gather), (inv(prog), inv(gather))


def test_axis_classification_on_the_test_mesh(contracts_mod):
    """The HLO group classifier must map both replica_groups formats and
    permute pairs onto the right mesh axes (everything else rests on
    this)."""
    from distributed_pytorch_from_scratch_tpu.config import MeshConfig
    from distributed_pytorch_from_scratch_tpu.runtime.mesh import make_mesh
    mesh = make_mesh(MeshConfig(dp=2, tp=2))
    ag = contracts_mod._axis_groups(mesh)
    assert set(ag) == {"dp", "tp", "all"}
    # braced + iota formats, pairs, singletons
    assert contracts_mod._classify([(0, 1), (2, 3)], ag) == "tp"
    assert contracts_mod._classify([(0, 2), (1, 3)], ag) == "dp"
    assert contracts_mod._classify([(0, 1, 2, 3)], ag) == "all"
    assert contracts_mod._classify([(0,), (1,)], ag) == "local"
    assert contracts_mod._parse_iota_groups("[2,2]<=[4]") == [
        (0, 1), (2, 3)]
    assert contracts_mod._parse_iota_groups("[2,2]<=[2,2]T(1,0)") == [
        (0, 2), (1, 3)]
    assert contracts_mod._classify_pairs([(0, 2), (2, 0)], ag) == "dp"
    assert contracts_mod._classify_pairs([(0, 1), (1, 0)], ag) == "tp"
