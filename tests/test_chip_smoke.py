"""chip_smoke.py, proven on the CPU before chip time is spent on it.

The smoke is the first command on any machine with a TPU; a typo in one of
its command lines would cost a chip call to find. `--allow-cpu` runs every
phase's command at tiny shapes on the CPU backend — kernels under the
Pallas interpreter, asked for by name — so the whole script is exercised
here. Without the opt-in, a machine with no chip must be told so: non-zero
exit, no result line.
"""

import fcntl
import os
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _smoke(*flags):
    # one smoke at a time: every run rebuilds the same .chip_smoke/ work dir
    # (parallel test workers would pull it out from under each other)
    lock_path = os.path.join(tempfile.gettempdir(), "chip_smoke_test.lock")
    # a compile cache of its own: the preflight failed once (PR 55's tree)
    # on a CPU executable cached in the `.jax_cache/` six workers share
    with open(lock_path, "w") as lock, \
            tempfile.TemporaryDirectory() as cache:
        env = {**os.environ, "JAX_PLATFORMS": "cpu",
               "JAX_COMPILATION_CACHE_DIR": cache}
        fcntl.flock(lock, fcntl.LOCK_EX)
        return subprocess.run([sys.executable, "chip_smoke.py", *flags],
                              capture_output=True, text=True, timeout=900,
                              cwd=REPO, env=env)


def test_chip_smoke_cpu_preflight_runs_every_phase():
    p = _smoke("--allow-cpu")
    assert p.returncode == 0, (p.stdout[-3000:], p.stderr[-3000:])
    out = p.stdout
    for phase in ("kernels", "train-45m", "serve-45m-gather",
                  "train-gpt2-124m", "train-4chip"):
        assert f"[{phase}] ran in" in out, (phase, out[-3000:])
    # off-chip the kernel flag is refused, and the smoke insists on that
    assert "[serve-45m-pallas] refused in" in out
    assert "kernel checks PASS (interpreted)" in out
    assert "attn=xla" in out and "cpu [cpu]" in out
    assert "validates" in out                      # the checkpoint
    assert "'{{0,1},{2,3}}'" in out                # tp groups and ...
    assert "'{{0,2},{1,3}}'" in out                # ... dp groups
    # a CPU run proves the commands and nothing about a chip: no result
    assert '"ok"' not in out
    assert "says nothing about a chip" in out


def test_chip_smoke_without_a_chip_fails_and_prints_no_result():
    p = _smoke()
    assert p.returncode != 0
    assert "no TPU attached" in p.stderr
    assert '"ok"' not in p.stdout
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
