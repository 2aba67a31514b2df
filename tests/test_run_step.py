"""Unit tests for scripts/run_step.py — the hardware-session step wrapper.

VERDICT r4 #4: "failed rc=0" must be impossible; a unit test over the
wrapper's failure paths is the acceptance gate. These run the wrapper as a
real subprocess (it is itself a process supervisor) but with trivial
commands, so they are fast and TPU-free.
"""

import json
import os
import subprocess
import sys


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WRAPPER = os.path.join(REPO, "scripts", "run_step.py")


def run_wrapper(tmp_path, name, cmd, timeout=None, expect_rc=0):
    manifest = tmp_path / "manifest.jsonl"
    argv = [sys.executable, WRAPPER, "--manifest", str(manifest),
            "--name", name]
    if timeout is not None:
        argv += ["--timeout", str(timeout)]
    argv += ["--"] + cmd
    # hermetic against an operator shell's exported session deadline
    env = {**os.environ}
    env.pop("SESSION_DEADLINE", None)
    proc = subprocess.run(argv, capture_output=True, text=True, env=env)
    assert proc.returncode == expect_rc, proc.stderr
    lines = manifest.read_text().strip().splitlines()
    assert len(lines) == 1
    return json.loads(lines[0]), proc


def test_success_records_rc0_and_passes_stdout_through(tmp_path):
    rec, proc = run_wrapper(
        tmp_path, "ok-step",
        [sys.executable, "-c", "print('ARTIFACT_LINE')"])
    assert rec["rc"] == 0 and rec["timed_out"] is False
    assert rec["name"] == "ok-step"
    assert "ARTIFACT_LINE" in proc.stdout  # stdout must reach redirections


def test_failure_records_real_rc_and_stderr_tail(tmp_path):
    rec, proc = run_wrapper(
        tmp_path, "bad-flag",
        [sys.executable, "-c",
         "import sys; print('boom: unrecognized arguments', file=sys.stderr);"
         "sys.exit(2)"],
        expect_rc=2)
    assert rec["rc"] == 2 and rec["timed_out"] is False
    assert "unrecognized arguments" in rec["stderr_tail"]
    # the round-4 bug class: the wrapper's own exit code IS the step's
    assert proc.returncode == 2


def test_timeout_kills_and_records_124(tmp_path):
    rec, _ = run_wrapper(
        tmp_path, "hang",
        [sys.executable, "-c", "import time; time.sleep(60)"],
        timeout=1.5, expect_rc=124)
    assert rec["rc"] == 124 and rec["timed_out"] is True
    assert rec["secs"] < 10


def test_timeout_sends_sigterm_first_for_graceful_shutdown(tmp_path):
    """A training step that hits the step timeout must get SIGTERM (so
    train.py's preemption handler can write its shutdown checkpoint) before
    any SIGKILL — the priority-pass training slice depends on this."""
    marker = tmp_path / "graceful_checkpoint"
    child = ("import signal, sys, time\n"
             f"def h(sig, frame):\n"
             f"    open({str(marker)!r}, 'w').write('saved')\n"
             f"    sys.exit(0)\n"
             "signal.signal(signal.SIGTERM, h)\n"
             "time.sleep(60)\n")
    # timeout must exceed python's startup or SIGTERM lands before the
    # handler is installed
    rec, _ = run_wrapper(tmp_path, "train-slice",
                         [sys.executable, "-c", child],
                         timeout=8, expect_rc=124)
    assert rec["timed_out"] is True
    assert marker.exists(), "SIGTERM handler never ran (got SIGKILL?)"


def test_timeout_kills_whole_process_group(tmp_path):
    """A step that spawns its own child (bench.py's PJRT threads analogue)
    must not leave orphans holding the single-tenant chip."""
    marker = tmp_path / "orphan_alive"
    # the marker path rides argv, not a nested string literal — a tmpdir
    # containing a quote character must not produce a SyntaxError child
    inner = "import sys, time; time.sleep(5); open(sys.argv[1], 'w').write('x')"
    child = (f"import subprocess, sys, time; "
             f"subprocess.Popen([sys.executable, '-c', {inner!r}, "
             f"{str(marker)!r}]); "
             f"time.sleep(60)")
    rec, _ = run_wrapper(tmp_path, "tree-hang",
                         [sys.executable, "-c", child],
                         timeout=1.5, expect_rc=124)
    assert rec["timed_out"] is True
    import time
    time.sleep(5)  # give a surviving orphan time to write the marker
    assert not marker.exists(), "grandchild survived the group kill"


def test_stderr_tail_is_bounded(tmp_path):
    rec, _ = run_wrapper(
        tmp_path, "chatty",
        [sys.executable, "-c",
         "import sys; sys.stderr.write('x' * 100000 + 'THE_END')"])
    assert len(rec["stderr_tail"]) <= 2000
    assert rec["stderr_tail"].endswith("THE_END")


def test_usage_error_is_rc97_not_a_step_result(tmp_path):
    proc = subprocess.run(
        [sys.executable, WRAPPER, "--manifest", str(tmp_path / "m"),
         "--name", "x"],  # no `--` / command
        capture_output=True, text=True)
    assert proc.returncode == 97
    assert not (tmp_path / "m").exists()


def test_tee_duplicates_stdout_to_file(tmp_path):
    tee = tmp_path / "step.log"
    manifest = tmp_path / "manifest.jsonl"
    proc = subprocess.run(
        [sys.executable, WRAPPER, "--manifest", str(manifest),
         "--name", "teed", "--tee", str(tee), "--",
         sys.executable, "-c", "print('step 100/5000 -> avg loss 3.14')"],
        capture_output=True, text=True)
    assert proc.returncode == 0
    assert "step 100/5000" in proc.stdout  # still reaches the console
    assert "step 100/5000" in tee.read_text()  # and the artifact log


def test_manifest_appends_multiple_steps(tmp_path):
    manifest = tmp_path / "manifest.jsonl"
    for i, rc in enumerate((0, 3)):
        subprocess.run(
            [sys.executable, WRAPPER, "--manifest", str(manifest),
             "--name", f"s{i}", "--",
             sys.executable, "-c", f"import sys; sys.exit({rc})"],
            capture_output=True)
    recs = [json.loads(l) for l in manifest.read_text().splitlines()]
    assert [r["rc"] for r in recs] == [0, 3]
    assert [r["name"] for r in recs] == ["s0", "s1"]
