"""The driver-facing bench contract: `bench.py` must print exactly ONE JSON
line on stdout with the metric/value/unit/vs_baseline keys, whatever flags
are set. Runs the real harness on the virtual CPU mesh at a tiny shape."""

import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_no_backend_is_a_failed_run():
    """A bench that finds no backend exits non-zero and prints no record
    (it used to print `{"error": "backend_unavailable"}` and exit 0, so
    the old driver would keep the line)."""
    p = subprocess.run([sys.executable, "bench.py", "--model", "tiny"],
                       capture_output=True, text=True, timeout=240,
                       cwd=REPO_ROOT,
                       env={**os.environ, "JAX_PLATFORMS": "tpu"})
    assert p.returncode != 0
    assert not p.stdout.strip(), p.stdout
    assert "backend" in p.stderr.lower()


def test_failed_build_is_a_failed_run_not_a_quieter_config():
    """The requested config or nothing: a step that fails to build exits
    non-zero with no record — there is no ladder down to remat=true /
    attn=xla under the same name."""
    script = (
        "import jax; jax.config.update('jax_platforms','cpu');"
        "import bench\n"
        "def broken(*a, **kw):\n"
        "    raise RuntimeError('Mosaic failed to compile the kernel')\n"
        "bench.build_train_step = broken\n"
        "bench.main(['--model','tiny','--batch','2','--seqlen','64',"
        "'--iters','1','--steps_per_dispatch','1','--tp','1'])\n")
    p = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=240, cwd=REPO_ROOT)
    assert p.returncode != 0
    assert not p.stdout.strip(), p.stdout
    assert "Mosaic failed to compile" in p.stderr
    assert "fallback" not in p.stderr


@pytest.mark.parametrize("extra", [
    ["--steps_per_dispatch", "1", "--tp", "1"],
    ["--steps_per_dispatch", "2", "--tp", "2"],
])
def test_bench_emits_one_json_line(extra):
    p = subprocess.run(
        [sys.executable, "-c", (
            "import os;"
            "os.environ['XLA_FLAGS']=os.environ.get('XLA_FLAGS','')"
            " + ' --xla_force_host_platform_device_count=8';"
            "import jax; jax.config.update('jax_platforms','cpu');"
            "import bench;"
            "bench.main(['--model','tiny','--batch','2','--seqlen','64',"
            "'--iters','1'] + %r)" % (extra,))],
        capture_output=True, text=True, timeout=500, cwd=REPO_ROOT)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [l for l in p.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, f"stdout must be ONE JSON line, got: {p.stdout!r}"
    rec = json.loads(lines[0])
    assert set(rec) == {"metric", "value", "unit", "vs_baseline",
                        "zero_stage", "param_bytes_per_device"}
    assert rec["unit"] == "tokens/sec/chip"
    assert rec["value"] > 0
    assert rec["zero_stage"] == 0          # no --zero flag staged here
    assert rec["param_bytes_per_device"] > 0


def test_breakdown_bench_emits_one_json_line():
    """--breakdown (staged as bench line 45mbreakdown) must produce its
    JSON artifact on CPU before it ever runs on the scarce chip: one line,
    the component keys summarize_run.py renders, derived components
    consistent with the measured ones."""
    p = subprocess.run(
        [sys.executable, "-c", (
            "import os;"
            "os.environ['XLA_FLAGS']=os.environ.get('XLA_FLAGS','')"
            " + ' --xla_force_host_platform_device_count=8';"
            "import jax; jax.config.update('jax_platforms','cpu');"
            "import bench;"
            "bench.main(['--model','tiny','--breakdown','--batch','2',"
            "'--seqlen','64','--iters','2','--tp','1',"
            "'--steps_per_dispatch','4'])")],
        capture_output=True, text=True, timeout=500, cwd=REPO_ROOT)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [l for l in p.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, f"stdout must be ONE JSON line, got: {p.stdout!r}"
    rec = json.loads(lines[0])
    assert set(rec) == {"metric", "value", "unit", "vs_baseline",
                        "components", "wire_dtype", "attribution",
                        "zero_stage", "param_bytes_per_device"}
    assert rec["unit"] == "ms/step"
    assert rec["wire_dtype"] == "f32"   # default: uncompressed DP wire
    comp = rec["components"]
    for key in ("h2d_ms", "fwd_ms", "fwdbwd_ms", "step_ms", "step_ms_spd4",
                "derived_bwd_ms", "derived_adam_ms", "derived_dispatch_ms"):
        assert key in comp, comp
    assert rec["value"] == comp["step_ms"] > 0
    # derived components must be consistent with the measured ones
    assert abs(comp["derived_bwd_ms"]
               - (comp["fwdbwd_ms"] - comp["fwd_ms"])) < 0.02
    assert abs(comp["derived_dispatch_ms"]
               - (comp["step_ms"] - comp["step_ms_spd4"])) < 0.02
    # the roofline attribution rides the same artifact: ranked suspects
    # with shares of the measured amortised step
    att = rec["attribution"]
    assert att["analytic_step_ms"] > 0
    ranks = [s["rank"] for s in att["suspects"]]
    assert ranks == sorted(ranks) and ranks[0] == 1
    est = [s["est_ms"] for s in att["suspects"]]
    assert est == sorted(est, reverse=True)
    # the measured dispatch gap must appear as a suspect (spd mode ran)
    assert any(s["name"] == "dispatch overhead" for s in att["suspects"])


def test_breakdown_analytic_emits_one_json_line():
    """--breakdown --analytic: the CPU-runnable roofline attribution at the
    FLAGSHIP 45m b32xt1000 shape (no device timing — milliseconds to run),
    the exact artifact VERDICT r5 #1 asked for."""
    p = subprocess.run(
        [sys.executable, "-c", (
            "import os;"
            "os.environ['XLA_FLAGS']=os.environ.get('XLA_FLAGS','')"
            " + ' --xla_force_host_platform_device_count=8';"
            "import jax; jax.config.update('jax_platforms','cpu');"
            "import bench;"
            "bench.main(['--model','45m','--breakdown','--analytic',"
            "'--remat','dots','--tp','1'])")],
        capture_output=True, text=True, timeout=500, cwd=REPO_ROOT)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [l for l in p.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, f"stdout must be ONE JSON line, got: {p.stdout!r}"
    rec = json.loads(lines[0])
    assert set(rec) == {"metric", "value", "unit", "vs_baseline",
                        "wire_dtype", "tp_overlap", "comm", "suspects",
                        "zero_stage"}
    assert rec["unit"] == "ms/step (analytic)"
    assert rec["value"] > 0
    names = [s["name"] for s in rec["suspects"]]
    assert any("tile/pad waste" in n for n in names), names
    # single-chip config: no collectives, so no comm to hide
    assert rec["comm"] == {"total_ms": 0, "hidden_ms": 0, "exposed_ms": 0}
    # the full human table lands on stderr for the session log
    assert "step-time attribution" in p.stderr
    assert "rank" in p.stderr


def test_breakdown_analytic_overlapped_config_reports_comm_hidden():
    """ISSUE 4 acceptance: the overlapped config (tp4 + SP + ring, bucketed
    bf16 DP reduce) must report a NONZERO 'comm hidden' line — the
    measurable claim the ring decomposition exists to make. Runs the same
    CPU-only analytic path the driver can execute; --tp 4 prices a 4-chip
    mesh without needing one (no mesh is built in analytic mode)."""
    p = subprocess.run(
        [sys.executable, "-c", (
            "import os;"
            "os.environ['XLA_FLAGS']=os.environ.get('XLA_FLAGS','')"
            " + ' --xla_force_host_platform_device_count=8';"
            "import jax; jax.config.update('jax_platforms','cpu');"
            "import bench;"
            "bench.main(['--model','45m','--breakdown','--analytic',"
            "'--remat','dots','--tp','4','--dp','2','--sequence_parallel',"
            "'--tp_overlap','ring','--dp_reduce_bucket_mb','25',"
            "'--dp_reduce_dtype','bf16'])")],
        capture_output=True, text=True, timeout=500, cwd=REPO_ROOT)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [l for l in p.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, f"stdout must be ONE JSON line, got: {p.stdout!r}"
    rec = json.loads(lines[0])
    assert rec["comm"]["hidden_ms"] > 0, rec["comm"]
    assert rec["comm"]["total_ms"] >= rec["comm"]["hidden_ms"]
    # the stderr table carries the human-readable line
    assert "comm hidden / exposed" in p.stderr
    # and the overlapped config's per-record notes mention the ring
    assert "tp_overlap=ring" in p.stderr
    # exposed comm appears as a ranked suspect alongside the tile/remat ones
    names = [s["name"] for s in rec["suspects"]]
    assert any("exposed collective comm" in n for n in names), names


def test_serving_speculate_bench_emits_one_json_line():
    """ISSUE 7 acceptance criterion: `--serving --speculate K` must run on
    CPU and emit ONE JSON line carrying the speculative A/B — `vs_paged`
    (speculative / plain paged at equal HBM) plus the dispatch-economics
    fields summarize_run.py renders. With two independently random-init
    models the greedy acceptance rate is ~0, so accepted-tokens/dispatch
    must still floor at 1.0 (every verify emits at least the corrected
    token) — the equal-HBM page split must show the drafter paid for."""
    p = subprocess.run(
        [sys.executable, "-c", (
            "import jax; jax.config.update('jax_platforms','cpu');"
            "import bench;"
            "bench.main(['--model','tiny','--serving','--tp','1',"
            "'--slots','2','--serve_requests','3','--prompt_len','12',"
            "'--gen_tokens','6','--page_size','8','--prefill_chunk','16',"
            "'--speculate','2'])")],
        capture_output=True, text=True, timeout=500, cwd=REPO_ROOT)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [l for l in p.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, f"stdout must be ONE JSON line, got: {p.stdout!r}"
    rec = json.loads(lines[0])
    for key in ("vs_paged", "speculate_k", "accepted_tokens_per_dispatch",
                "acceptance_rate", "acceptance_rate_by_position",
                "spec_rounds", "drafter_ms_total", "target_ms_total",
                "target_pages", "drafter_pages", "drafter_budget_share",
                "paged_vs_slot", "vs_baseline"):
        assert key in rec, (key, sorted(rec))
    assert rec["unit"] == "tokens/sec (serving)"
    assert rec["value"] > 0
    assert rec["speculate_k"] == 2
    assert rec["vs_paged"] > 0
    assert len(rec["acceptance_rate_by_position"]) == 2
    assert rec["accepted_tokens_per_dispatch"] >= 1.0, rec
    assert rec["target_pages"] > 0 and rec["drafter_pages"] > 0


def test_decode_bench_emits_one_json_line():
    """--decode measures KV-cache generation throughput; vs_baseline is the
    speedup over the reference-semantics full-recompute per-token loop
    (`/root/reference/test.py:141-161`), which must come out > 1."""
    p = subprocess.run(
        [sys.executable, "-c", (
            "import os;"
            "os.environ['XLA_FLAGS']=os.environ.get('XLA_FLAGS','')"
            " + ' --xla_force_host_platform_device_count=8';"
            "import jax; jax.config.update('jax_platforms','cpu');"
            "import bench;"
            "bench.main(['--model','tiny','--decode','--batch','2',"
            "'--prompt_len','8','--gen_tokens','12','--tp','1'])")],
        capture_output=True, text=True, timeout=500, cwd=REPO_ROOT)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [l for l in p.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, f"stdout must be ONE JSON line, got: {p.stdout!r}"
    rec = json.loads(lines[0])
    # ADVICE r4: the decode line discloses batch size and probe coverage so
    # the batching win and the pure KV win are separable
    assert set(rec) == {"metric", "value", "unit", "vs_baseline", "batch",
                        "probe_steps", "kv_rate_per_stream",
                        "ref_recompute_rate"}
    assert rec["unit"] == "tokens/sec"
    assert rec["value"] > 0
    assert rec["batch"] == 2
    assert rec["probe_steps"] == 12  # the FULL gen budget, not a short probe
    # vs_baseline is the PER-STREAM KV-vs-recompute speedup; on the CPU toy
    # it is modest (no dispatch round-trip to amortise) but must be real
    assert rec["vs_baseline"] > 1, rec
