"""End-to-end smoke: the full CLI pipeline on a tiny corpus.

The cluster-free analogue of the reference's `recipe.sh` integration flow
(SURVEY §3.3): texts -> tokenizer -> token JSON -> `train.main` (TP=2, DP=2,
checkpoints, resume) -> `evaluate.main` (per-ckpt val loss + greedy decode),
all on the virtual CPU mesh.
"""

import json
import os
import re

import numpy as np
import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from distributed_pytorch_from_scratch_tpu import evaluate as eval_mod
from distributed_pytorch_from_scratch_tpu import train as train_mod
from distributed_pytorch_from_scratch_tpu.data.tokenizer import (
    pre_tokenize, train_bpe)
from distributed_pytorch_from_scratch_tpu.training.checkpoint import (
    latest_step, list_checkpoints)

TEXTS = [
    "the king rode out at dawn with his men",
    "a quiet morning on the river bank",
    "she sold sea shells by the sea shore",
    "to be or not to be that is the question",
    "all the world is a stage and we are players",
    "the lazy dog slept while the fox jumped",
    # cover the bytes (capitals, punctuation) of evaluate.DECODE_PROMPTS so
    # the tiny tokenizer can round-trip them (byte-level BPE only includes
    # bytes seen in training)
    "Nice to meet you, it's a Great day; Your majesty, I shall be glad",
    "What a glory to see; Shame for the weak, The brave man ne, Poor old man",
] * 6


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    d = tmp_path_factory.mktemp("e2e")
    text_json = d / "texts.json"
    with open(text_json, "w") as f:
        json.dump({"train": TEXTS, "validation": TEXTS[:6]}, f)
    tok = d / "tokenizer.json"
    train_bpe(str(text_json), str(tok), vocab_size=280)
    tokens = d / "tokens.json"
    pre_tokenize(str(text_json), str(tokens), str(tok))
    return {"dir": d, "tokens": tokens, "tok": tok}


MODEL_FLAGS = ["--attn_dim", "32", "--ffn_dim", "64", "--num_heads", "8",
               "--num_layers", "2", "--maxlen", "32"]


def test_train_eval_resume_e2e(corpus):
    save_dir = str(corpus["dir"] / "ckpts")
    base = ["--tp_size", "2", "--dp_size", "2",
            "--data_path", str(corpus["tokens"]),
            "--save_dir", save_dir,
            "--batch_size", "4", "--log_interval", "2",
            "--save_interval", "4", "--warmup_steps", "2",
            *MODEL_FLAGS]

    # train 8 steps, checkpoints at 4 and 8
    train_mod.main(base + ["--max_steps", "8"])
    assert latest_step(save_dir) == 8
    assert len(list_checkpoints(save_dir, rank=0)) == 2
    assert len(list_checkpoints(save_dir, rank=1)) == 2

    # resume to 12: must continue from 8, not restart
    train_mod.main(base + ["--max_steps", "12", "--resume"])
    assert latest_step(save_dir) == 12

    # evaluate all checkpoints + greedy decode
    result = eval_mod.evaluate(eval_mod.get_eval_args([
        "--tp_size", "2",
        "--ckpt_dir", save_dir,
        "--data_path", str(corpus["tokens"]),
        "--tokenizer_path", str(corpus["tok"]),
        "--max_decode_len", "16",
        "--no-bf16",
        "--batch_size", "2",
        *MODEL_FLAGS]))
    assert set(result["val_losses"]) == {4, 8, 12}
    assert all(np.isfinite(v) for v in result["val_losses"].values())
    assert len(result["decoded"]) == len(eval_mod.DECODE_PROMPTS)
    report = os.path.join(save_dir, "val", "val.txt")
    assert os.path.exists(report)
    text = open(report).read()
    assert "Validation loss" in text and "Decoded texts" in text

    # the same evaluation on the full 3-D mesh (dp2 x cp2 x tp2, VERDICT
    # weak #5): val losses must agree with the tp-only run — dp shards the
    # batch (ragged final batch padded with IGNORE_INDEX rows), cp runs ring
    # attention over sequence chunks
    # --no_kv_cache: the full-recompute decode must also run on the 3-D
    # mesh (its buffer is replicated over dp/cp, not sharded); zigzag
    # exercises the balanced ring layout through the eval CLI
    result3d = eval_mod.evaluate(eval_mod.get_eval_args([
        "--tp_size", "2", "--dp_size", "2", "--cp_size", "2",
        "--cp_layout", "zigzag",
        "--ckpt_dir", save_dir,
        "--data_path", str(corpus["tokens"]),
        "--tokenizer_path", str(corpus["tok"]),
        "--max_decode_len", "16",
        "--no-bf16",
        "--batch_size", "2",
        "--no_kv_cache",
        *MODEL_FLAGS]))
    for it, v in result["val_losses"].items():
        np.testing.assert_allclose(result3d["val_losses"][it], v,
                                   rtol=0, atol=1e-5)


def test_train_rejects_oversized_mesh(corpus):
    with pytest.raises(SystemExit, match="devices"):
        train_mod.train(train_mod.get_train_args([
            "--tp_size", "64", "--data_path", str(corpus["tokens"]),
            *MODEL_FLAGS, "--max_steps", "1"]))


def test_pp_train_then_eval_on_dp_tp_mesh(corpus):
    """VERDICT r3 #6: the pp-train -> eval flow, end to end. Train on a
    pp2 x tp2 mesh (4 layers / 2 stages, microbatched GPipe), checkpoint,
    then evaluate on a pp-LESS dp x tp mesh — the mesh-independent
    checkpoint reload is what makes the handoff work (the reference's
    train->test handoff is same-mesh only, `/root/reference/test.py:94-98`;
    here the eval mesh is a different shape entirely). doc_loss refuses pp
    meshes at the API level (`Transformer.doc_loss_shard`), so the eval CLI
    deliberately has no --pp_size flag."""
    save_dir = str(corpus["dir"] / "ckpts_pp")
    pp_model_flags = ["--attn_dim", "32", "--ffn_dim", "64",
                      "--num_heads", "8", "--num_layers", "4",
                      "--maxlen", "32"]
    train_mod.main(["--pp_size", "2", "--tp_size", "2",
                    "--pp_microbatches", "4",
                    "--data_path", str(corpus["tokens"]),
                    "--save_dir", save_dir,
                    "--batch_size", "4", "--log_interval", "2",
                    "--save_interval", "3", "--warmup_steps", "2",
                    "--max_steps", "6", *pp_model_flags])
    assert latest_step(save_dir) == 6

    # reload on tp2 (pp=1) and on dp2 x tp2: val losses must agree exactly
    results = {}
    for name, mesh_flags in [("tp2", ["--tp_size", "2"]),
                             ("dp2tp2", ["--tp_size", "2",
                                         "--dp_size", "2"])]:
        results[name] = eval_mod.evaluate(eval_mod.get_eval_args([
            *mesh_flags,
            "--ckpt_dir", save_dir,
            "--data_path", str(corpus["tokens"]),
            "--tokenizer_path", str(corpus["tok"]),
            "--max_decode_len", "12",
            "--no-bf16",
            "--batch_size", "2",
            *pp_model_flags]))
    for r in results.values():
        assert set(r["val_losses"]) == {3, 6}
        assert all(np.isfinite(v) for v in r["val_losses"].values())
        assert len(r["decoded"]) == len(eval_mod.DECODE_PROMPTS)
    for it, v in results["tp2"]["val_losses"].items():
        np.testing.assert_allclose(results["dp2tp2"]["val_losses"][it], v,
                                   rtol=0, atol=1e-5)


def test_pp_ring_cp_train_cli_smoke(corpus):
    """pp x ring-CP through the train CLI: the live-gated ring schedule
    (unconditional ppermutes, cond-gated dense segments — VERDICT r3 #3)
    compiles and trains finite losses end to end."""
    r = train_mod.train(train_mod.get_train_args([
        "--pp_size", "2", "--cp_size", "2", "--pp_microbatches", "2",
        "--data_path", str(corpus["tokens"]),
        "--save_dir", str(corpus["dir"] / "ckpts_ppcp"),
        "--batch_size", "4", "--log_interval", "2", "--warmup_steps", "2",
        "--max_steps", "2", "--save_interval", "2",
        "--attn_dim", "32", "--ffn_dim", "64", "--num_heads", "8",
        "--num_layers", "4", "--maxlen", "32"]))
    assert r["steps"] == 2 and np.isfinite(r["avg_loss"])


@pytest.mark.slow  # heaviest of its family; shorter siblings stay fast
def test_interleaved_train_resume_eval(corpus):
    """The interleaved schedule through the train CLI: checkpoints are
    saved CANONICAL (layers flattened back to the (L, ...) stack), resume
    reloads them through canonical_specs + from_canonical (params AND Adam
    moments), and the eval CLI — which knows nothing about schedules —
    reads the same artifacts. A direct canonical-round-trip assertion pins
    the save-side layout: the saved checkpoint loaded into a plain pp=1
    template must reproduce the interleaved model's own loss."""
    import jax

    from distributed_pytorch_from_scratch_tpu import MeshConfig, make_mesh
    from distributed_pytorch_from_scratch_tpu.config import ModelConfig
    from distributed_pytorch_from_scratch_tpu.models.transformer import (
        Transformer)
    from distributed_pytorch_from_scratch_tpu.training.checkpoint import (
        load_checkpoint)

    save_dir = str(corpus["dir"] / "ckpts_interleaved")
    flags = ["--attn_dim", "32", "--ffn_dim", "64", "--num_heads", "4",
             "--num_layers", "4", "--maxlen", "32"]
    base = ["--pp_size", "2", "--tp_size", "2",
            "--pp_schedule", "interleaved", "--pp_microbatches", "2",
            "--data_path", str(corpus["tokens"]),
            "--save_dir", save_dir,
            "--batch_size", "4", "--log_interval", "2",
            "--save_interval", "2", "--warmup_steps", "2", *flags]
    train_mod.main(base + ["--max_steps", "4"])
    assert latest_step(save_dir) == 4
    # resume exercises canonical_specs load + from_canonical on params/moments
    train_mod.main(base + ["--max_steps", "6", "--resume"])
    assert latest_step(save_dir) == 6

    # canonical round-trip: checkpoint -> pp=1 template -> loss must equal
    # the interleaved model's loss on the same (from_canonical'd) params
    import jax.numpy as jnp
    vocab = json.load(open(corpus["tokens"]))["vocab_size"]
    cfg = ModelConfig(attn_dim=32, ffn_dim=64, num_heads=4, num_layers=4,
                      vocab_size=vocab, maxlen=32)
    flat = Transformer(cfg)
    template = flat.init(jax.random.key(0))
    loaded, _, st = load_checkpoint(save_dir, 6, template, flat.specs())
    assert st == 6
    ids = jnp.zeros((4, 8), jnp.int32)
    tgt = jnp.ones((4, 8), jnp.int32)
    pos = jnp.tile(jnp.arange(8)[None, :], (4, 1))
    l_flat = flat.make_loss(make_mesh(MeshConfig()))(loaded, ids, tgt, pos)

    iv = Transformer(cfg, pp_size=2, tp_size=2, pp_schedule="interleaved",
                     pp_microbatches=2)
    mesh = make_mesh(MeshConfig(pp=2, tp=2))
    sp = jax.device_put(iv.from_canonical(loaded), iv.shardings(mesh))
    l_iv = iv.make_loss(mesh)(sp, ids, tgt, pos)
    np.testing.assert_allclose(float(l_iv), float(l_flat), rtol=1e-5)

    result = eval_mod.evaluate(eval_mod.get_eval_args([
        "--tp_size", "2",
        "--ckpt_dir", save_dir,
        "--data_path", str(corpus["tokens"]),
        "--tokenizer_path", str(corpus["tok"]),
        "--max_decode_len", "8",
        "--no-bf16",
        "--batch_size", "2",
        *flags]))
    assert set(result["val_losses"]) == {2, 4, 6}
    assert all(np.isfinite(v) for v in result["val_losses"].values())


def test_generate_cli(corpus):
    """The generation CLI: prompt in -> extended text out, batched prompts
    in one dispatch, greedy and sampled modes (the reference has no
    generation entry point at all — its decode lives inside test.py)."""
    from distributed_pytorch_from_scratch_tpu import generate as gen_mod

    save_dir = str(corpus["dir"] / "ckpts_gen")
    train_mod.main(["--tp_size", "2",
                    "--data_path", str(corpus["tokens"]),
                    "--save_dir", save_dir,
                    "--batch_size", "4", "--log_interval", "2",
                    "--save_interval", "4", "--warmup_steps", "2",
                    "--max_steps", "4", *MODEL_FLAGS])

    base = ["--ckpt_dir", save_dir,
            "--tokenizer_path", str(corpus["tok"]),
            "--tp_size", "2", "--max_new_tokens", "8", "--no-bf16",
            *MODEL_FLAGS]
    outs = gen_mod.main(base + ["--prompt", "the king",
                                "--prompt", "a quiet morning"])
    assert len(outs) == 2
    assert outs[0].startswith("the king")
    assert outs[1].startswith("a quiet morning")

    sampled = gen_mod.main(base + ["--prompt", "the king",
                                   "--temperature", "1.0",
                                   "--decode_top_p", "0.9",
                                   "--seed", "3"])
    again = gen_mod.main(base + ["--prompt", "the king",
                                 "--temperature", "1.0",
                                 "--decode_top_p", "0.9",
                                 "--seed", "3"])
    assert sampled == again  # same seed reproduces


@pytest.mark.slow
def test_adamw_cosine_train_then_cp_decode_eval(corpus):
    """Round-4 additions through the REAL CLIs: train with AdamW decoupled
    decay + the cosine schedule, then evaluate with --cp_size 2 — the val
    forward shards the sequence over 'cp' (ring attention) and decoding
    routes through the paged engine's cp-sharded page pool (ISSUE 18)."""
    import subprocess
    import sys
    save = str(corpus["dir"] / "wd_ck")
    env = {**__import__("os").environ, "JAX_PLATFORMS": "cpu"}
    env["XLA_FLAGS"] = env.get("XLA_FLAGS", "") + \
        " --xla_force_host_platform_device_count=8"
    tr = subprocess.run(
        [sys.executable, "-m", "distributed_pytorch_from_scratch_tpu.train",
         "--data_path", str(corpus["tokens"]), "--save_dir", save,
         "--attn_dim", "64", "--ffn_dim", "128", "--num_heads", "4",
         "--num_layers", "2", "--maxlen", "32",
         "--dp_size", "2", "--tp_size", "2", "--batch_size", "8",
         "--max_steps", "4", "--warmup_steps", "2", "--save_interval", "2",
         "--weight_decay", "0.1", "--lr_schedule", "cosine"],
        capture_output=True, text=True, timeout=600, env=env,
        cwd=REPO_ROOT)
    assert tr.returncode == 0, tr.stderr
    assert "training finished" in tr.stdout

    ev = subprocess.run(
        [sys.executable, "-m",
         "distributed_pytorch_from_scratch_tpu.evaluate",
         "--data_path", str(corpus["tokens"]), "--ckpt_dir", save,
         "--tokenizer_path", str(corpus["tok"]),
         "--attn_dim", "64", "--ffn_dim", "128", "--num_heads", "4",
         "--num_layers", "2", "--maxlen", "32",
         "--cp_size", "2", "--tp_size", "2", "--batch_size", "4",
         "--max_decode_len", "16"],
        capture_output=True, text=True, timeout=900, env=env,
        cwd=REPO_ROOT)
    assert ev.returncode == 0, ev.stderr
    assert len(re.findall(r"val loss [0-9.]+", ev.stdout)) >= 2, ev.stdout
    assert "->" in ev.stdout  # decodes printed (cp-sharded prefill path)
