"""Preemption-safe training: SIGTERM mid-run -> final checkpoint + clean
exit + --resume continues.

The reference has no failure-detection/recovery story at all
(`mp.spawn(join=True)`, SURVEY §5.3): a signal kills the job and any
progress since the last periodic save is lost. Here the train loop polls a
signal flag each step (train.py `_ShutdownFlag`) — the TPU-idiomatic
equivalent, since preemptible TPU VM evictions arrive as SIGTERM.

Runs the real CLI in a subprocess (signals can't be exercised in-process:
pytest owns the main thread's handlers).
"""

import json
import os
import signal
import subprocess
import sys
import threading

import pytest

pytestmark = pytest.mark.slow

from distributed_pytorch_from_scratch_tpu.data.tokenizer import (pre_tokenize,
                                                                 train_bpe)
from distributed_pytorch_from_scratch_tpu.training.checkpoint import (
    latest_step)

TEXTS = ["the king rode out at dawn with his men",
         "a quiet morning on the river bank",
         "she sold sea shells by the sea shore",
         "to be or not to be that is the question"] * 4


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    d = tmp_path_factory.mktemp("preempt")
    text_json = d / "texts.json"
    with open(text_json, "w") as f:
        json.dump({"train": TEXTS, "validation": TEXTS[:2]}, f)
    tok = d / "tokenizer.json"
    train_bpe(str(text_json), str(tok), vocab_size=270)
    tokens = d / "tokens.json"
    pre_tokenize(str(text_json), str(tokens), str(tok))
    return tokens


def test_sigterm_checkpoints_and_resumes(corpus, tmp_path):
    save_dir = str(tmp_path / "ckpts")
    # PYTHONUNBUFFERED: the child block-buffers stdout into a pipe, so the
    # "step N" marker would otherwise never arrive before the signal.
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONUNBUFFERED": "1"}
    args = [sys.executable, "-m", "distributed_pytorch_from_scratch_tpu.train",
            "--data_path", str(corpus), "--save_dir", save_dir,
            "--attn_dim", "32", "--ffn_dim", "64", "--num_heads", "4",
            "--num_layers", "2", "--maxlen", "32",
            "--batch_size", "2", "--log_interval", "1",
            "--save_interval", "100000", "--warmup_steps", "2"]
    proc = subprocess.Popen(args + ["--max_steps", "100000"],
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True, bufsize=1,
                            env=env)
    lines = []
    seen_step = threading.Event()

    def pump():
        for line in proc.stdout:
            lines.append(line)
            if line.startswith("step "):
                seen_step.set()

    t = threading.Thread(target=pump, daemon=True)
    t.start()
    try:
        assert seen_step.wait(timeout=300), (
            "no training step within 300s:\n" + "".join(lines))
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=120) == 0, "".join(lines)
    finally:
        proc.kill()
    t.join(timeout=10)
    out = "".join(lines)
    assert "shutdown requested: checkpointed at step" in out, out

    stopped_at = latest_step(save_dir)
    assert stopped_at is not None and stopped_at >= 1

    # the saved state must actually resume
    resumed = subprocess.run(
        args + ["--max_steps", str(stopped_at + 2), "--resume"],
        capture_output=True, text=True, timeout=300, env=env)
    assert resumed.returncode == 0, resumed.stdout + resumed.stderr
    assert f"resumed from iter {stopped_at}" in resumed.stdout
    assert f"training finished at step {stopped_at + 2}" in resumed.stdout
