"""Does the system still start on the chip? One command, a few minutes.

    python chip_smoke.py            # on a machine with a TPU
    python chip_smoke.py --allow-cpu   # preflight of THIS script, tiny shapes

Drives the main path once through the entry points a user runs, at the full
width of the models the repo supports, on seeded random data, and checks
what comes out by the repo's own means:

  kernels          scripts/tpu_checks.py: every Pallas kernel, compiled by
                   Mosaic, against its XLA oracle; is block_until_ready
                   honest on this backend
  train-45m        `train --model 45m --bf16 --batch_size 32` at t=1000 (the
                   reference shape): flash kernel fwd+bwd, loss falls, a
                   checkpoint that validate_checkpoint accepts
  serve-45m        `serving.serve --paged` from that checkpoint, with the
                   gather attend and with the Pallas paged kernel: every
                   request completes, greedy tokens identical
  train-gpt2-124m  `train --family gpt2 --model gpt2-124m --bf16
                   --batch_size 8` at vocab 50,257, t=1024
  train-4chip      with >= 4 devices: `train --model 45m --dp_size 2
                   --tp_size 2`, no layout flag, so the default path runs
                   (sequence parallelism over the ring collective matmuls):
                   all four devices hold shards, step-1 loss matches the
                   one-chip run. Otherwise says it did not run.

One process uses the chip at a time: this parent never touches a JAX
backend, each phase is a child process and they run one after another. The
first failing phase ends the run with a non-zero exit. With no accelerator
(and without --allow-cpu) the first phase says so, and the run exits
non-zero and prints no result. The last
line of stdout on success is one JSON object:
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
"""

import argparse
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
PKG = "distributed_pytorch_from_scratch_tpu"
WORK = os.path.join(ROOT, ".chip_smoke")  # git-ignored; rebuilt every run
PHASE_TIMEOUT_S = 600  # the whole smoke has 1200 s; no phase needs half


class SmokeFailure(Exception):
    pass


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def run_child(name: str, cmd: list, env: dict, refusal: str = None) -> str:
    """Run one phase's process to the end in its own process group; its
    output goes to WORK/<name>.log and comes back as text. The group is
    killed on the way out, whatever happened, so nothing it started
    outlives the phase. A non-zero exit is a SmokeFailure carrying the tail
    of the log — unless `refusal` is given: then the command MUST exit
    non-zero and say `refusal`."""
    log = os.path.join(WORK, f"{name}.log")
    t0 = time.time()
    with open(log, "w") as f:
        proc = subprocess.Popen([sys.executable] + cmd, cwd=ROOT, env=env,
                                stdout=f, stderr=subprocess.STDOUT,
                                start_new_session=True)
        try:
            rc = proc.wait(timeout=PHASE_TIMEOUT_S)
        except subprocess.TimeoutExpired:
            rc = None
        finally:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()
    with open(log) as f:
        text = f.read()
    why = (f"timed out after {PHASE_TIMEOUT_S}s" if rc is None
           else f"exited {rc}")
    tail = f"{' '.join(cmd)}\n  {why}; the end of {log}:\n{text[-3000:]}"
    if refusal is not None:
        require(rc not in (0, None) and refusal in text,
                f"{name}: wanted a refusal saying {refusal!r}, got\n{tail}")
    elif rc != 0:
        raise SmokeFailure(tail)
    print(f"[{name}] {'refused' if refusal else 'ran'} in "
          f"{time.time() - t0:.1f}s", flush=True)
    return text


def last_json(text: str) -> dict:
    """The summary record an entry point prints as its last JSON line."""
    for line in reversed(text.strip().splitlines()):
        if line.startswith("{"):
            return json.loads(line)
    raise SmokeFailure("no JSON summary record in the output:\n"
                       + text[-2000:])


def write_tokens(path: str, vocab: int, docs: int, doc_len: int) -> None:
    """A seeded token JSON in the `data.tokenizer.pre_tokenize` schema. Ids
    follow a Zipf law, so a few optimizer steps already pull the loss from
    ~ln(vocab) toward the unigram entropy: "the loss fell" is then a
    statement about the optimizer, not about noise."""
    import numpy as np

    rng = np.random.default_rng(0)
    p = 1.0 / np.arange(1, vocab - 3 + 1)
    p /= p.sum()

    def split(n):  # ids 0..2 are the BOS/EOS/UNK specials
        return (rng.choice(vocab - 3, size=(n, doc_len), p=p) + 3).tolist()

    with open(path, "w") as f:
        json.dump({"train": split(docs), "validation": split(2),
                   "vocab_size": vocab,
                   "special_ids": {"<BOS>": 0, "<EOS>": 1, "<UNK>": 2}}, f)


def describe(rec: dict) -> str:
    return (f"{rec['device_count']} x {rec['platform']} "
            f"[{rec['device_kind']}]")


def check_device(rec: dict, device: dict) -> None:
    got = {"platform": rec["platform"], "kind": rec["device_kind"],
           "count": rec["device_count"]}
    require(got == device, f"phase ran on {got}, the first phase on {device}")


def check_train(name: str, rec: dict, steps: int, on_chip: bool) -> None:
    first, last = rec["first_loss"], rec["last_loss"]
    require(rec["steps"] == steps, f"{name}: {rec['steps']} steps, "
            f"wanted {steps}")
    require(first is not None and math.isfinite(first)
            and math.isfinite(last), f"{name}: loss not finite "
            f"({first} -> {last})")
    require(last < first, f"{name}: loss did not fall ({first:.4f} -> "
            f"{last:.4f})")
    if on_chip:
        require(rec["attn_impl"] == "flash", f"{name}: attention resolved "
                f"to {rec['attn_impl']!r}, not the Pallas flash kernel")
        require(rec["peak_flops_per_chip"], f"{name}: no peak FLOP/s for "
                f"device_kind {rec['device_kind']!r}")
    peak = rec["peak_flops_per_chip"]
    print(f"[{name}] {describe(rec)}, mesh {rec['mesh']}, "
          f"attn={rec['attn_impl']}, compile {rec['compile_s']:.1f}s, "
          f"{rec['steps']} steps, loss {first:.4f} -> {last:.4f}, peak "
          + (f"{peak / 1e12:.0f} TFLOP/s/chip" if peak else "unknown (cpu)")
          + f", compile cache "
          f"{rec['compile_cache']['hits']} hit(s) / "
          f"{rec['compile_cache']['misses']} miss(es)", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--allow-cpu", action="store_true",
                    help="no chip needed: run every phase on the CPU backend "
                         "at tiny shapes, kernels under the Pallas "
                         "interpreter. Proves this script's commands, says "
                         "nothing about a chip")
    args = ap.parse_args(argv)
    cpu = args.allow_cpu
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    env = dict(os.environ, PYTHONUNBUFFERED="1")
    if cpu:
        # asked for, not fallen into; four host devices for train-4chip
        env["JAX_PLATFORMS"] = "cpu"
        if "xla_force_host_platform_device_count" not in env.get(
                "XLA_FLAGS", ""):
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                                + " --xla_force_host_platform_device_count=4")
    t_start = time.time()
    cache = {"hits": 0, "misses": 0}

    def tally(rec):
        cache["hits"] += rec["compile_cache"]["hits"]
        cache["misses"] += rec["compile_cache"]["misses"]
        cache["dir"] = rec["compile_cache"]["dir"]

    # ---- kernels (also: which device is this?)
    kc_path = os.path.join(WORK, "kernel_checks.json")
    run_child("kernels", ["scripts/tpu_checks.py", "--out", kc_path]
              + (["--allow_cpu"] if cpu else []), env)
    with open(kc_path) as f:
        kc = json.load(f)
    device = kc["device"]
    on_chip = device["platform"] != "cpu"
    require(on_chip or cpu, f"kernels phase ran on {device}")
    require(kc["all_ok"] and kc["interpreted"] != on_chip,
            f"kernel checks: {kc}")
    tally(kc)
    t = kc["timer"]
    print(f"[kernels] {device['count']} x {device['platform']} "
          f"[{device['kind']}]: {len(kc['checks'])} kernel checks PASS "
          f"({'interpreted' if kc['interpreted'] else 'compiled by Mosaic'}"
          f"); block_until_ready {t['block_until_ready_s'] * 1e3:.1f} ms vs "
          f"D2H sync {t['d2h_sync_s'] * 1e3:.1f} ms over the same steps "
          f"(ratio {t['ratio']:.2f})", flush=True)

    # ---- train-45m: the reference shape, then a checkpoint
    shape = (["--model", "tiny", "--maxlen", "64"] if cpu
             else ["--model", "45m"])
    t45, b45, steps = (64, 4, 4) if cpu else (1000, 32, 8)
    data45 = os.path.join(WORK, "tokens_v1024.json")
    write_tokens(data45, 1024, b45 * steps, t45 - 1)
    ck45 = os.path.join(WORK, "ckpt_45m")
    common = ["--log_interval", "1", "--warmup_steps", "2", "--lr", "1e-3"]
    out45 = run_child("train-45m", [
        "-m", f"{PKG}.train", *shape, "--bf16", "--batch_size", str(b45),
        "--data_path", data45, "--max_steps", str(steps),
        "--save_interval", str(steps), "--save_dir", ck45, *common], env)
    rec45 = last_json(out45)
    # which collate path fed it: the C++ library built from csrc/ just now,
    # or numpy where there is no compiler
    print("[train-45m] " + next(ln for ln in out45.splitlines()
                                if ln.startswith("data: ")), flush=True)
    check_device(rec45, device)
    check_train("train-45m", rec45, steps, on_chip)
    tally(rec45)
    from distributed_pytorch_from_scratch_tpu.training.checkpoint import (
        latest_step, validate_checkpoint)
    require(latest_step(ck45) == steps, f"no iter-{steps} checkpoint in "
            f"{ck45}")
    _, files = validate_checkpoint(ck45, steps)
    print(f"[train-45m] checkpoint {os.path.basename(files[0])} "
          f"({os.path.getsize(files[0]) / 2**20:.0f} MiB) validates",
          flush=True)

    # ---- serve-45m from that checkpoint: gather attend, then the kernel
    serve = ["-m", f"{PKG}.serving.serve", *shape, "--ckpt_dir", ck45,
             "--paged", "--arrival", "burst", "--num_requests", "6",
             "--max_new_tokens", "8", "--prompt_len_min", "4",
             "--prompt_len_max", "24"] + (["--page_size", "8"] if cpu else [])
    recs = {}
    for impl in ("gather", "pallas"):
        name = f"serve-45m-{impl}"
        cmd = serve + ["--paged_attn", impl,
                       "--log_dir", os.path.join(WORK, f"serve_{impl}")]
        if impl == "pallas" and not on_chip:
            # off-chip the flag must be REFUSED: no interpreter, no gather
            run_child(name, cmd, env, refusal="needs a TPU backend")
            continue
        rec = recs[impl] = last_json(run_child(name, cmd, env))
        check_device(rec, device)
        require(rec["completed"] == rec["requests"] == 6,
                f"{name}: {rec['completed']}/{rec['requests']} completed")
        require(rec["paged_attn"] == impl, f"{name}: record says "
                f"paged_attn={rec['paged_attn']!r}")
        tally(rec)
        print(f"[{name}] {describe(rec)}, paged_attn={rec['paged_attn']}, "
              f"{rec['completed']}/{rec['requests']} requests, "
              f"{rec['generated_tokens']} tokens, digest "
              f"{rec['tokens_digest'][:12]}", flush=True)
    if on_chip:
        require(recs["gather"]["tokens_digest"]
                == recs["pallas"]["tokens_digest"],
                "serve-45m: greedy tokens differ between the gather attend "
                "and the Pallas paged kernel")
        print("[serve-45m] greedy tokens identical: gather == pallas",
              flush=True)

    # ---- train-gpt2-124m: the published-width model
    gshape = (["--model", "tiny", "--maxlen", "64"] if cpu
              else ["--model", "gpt2-124m"])
    tg, bg, vg, gsteps = (64, 4, 512, 4) if cpu else (1024, 8, 50257, 4)
    datag = os.path.join(WORK, f"tokens_v{vg}.json")
    write_tokens(datag, vg, bg * gsteps, tg - 1)
    recg = last_json(run_child("train-gpt2-124m", [
        "-m", f"{PKG}.train", "--family", "gpt2", *gshape, "--bf16",
        "--batch_size", str(bg), "--data_path", datag, "--max_steps",
        str(gsteps), "--save_interval", "1000", "--save_dir",
        os.path.join(WORK, "ckpt_gpt2"), *common], env))
    check_device(recg, device)
    check_train("train-gpt2-124m", recg, gsteps, on_chip)
    tally(recg)

    # ---- train-4chip: dp2 x tp2, when four devices are there
    if device["count"] >= 4:
        rec4 = last_json(run_child("train-4chip", [
            "-m", f"{PKG}.train", *shape, "--bf16", "--batch_size", str(b45),
            "--dp_size", "2", "--tp_size", "2", "--data_path", data45,
            "--max_steps", "4", "--save_interval", "1000", "--save_dir",
            os.path.join(WORK, "ckpt_4chip"), *common], env))
        check_device(rec4, device)
        check_train("train-4chip", rec4, 4, on_chip)
        tally(rec4)
        held = rec4["param_bytes_by_device"]
        require(len(held) == 4 and all(v > 0 for v in held.values()),
                f"train-4chip: parameter shards on {held}")
        if rec4["hbm"] is not None:  # the CPU backend has no memory stats
            used = {d["device"]: d["bytes_in_use"] for d in rec4["hbm"]}
            require(sum(1 for v in used.values() if v > 0) >= 4,
                    f"train-4chip: memory in use per device {used}")
        # same seed, same data, same global batch: step 1 is the same loss
        # up to bf16 rounding of a differently split sum
        require(abs(rec4["first_loss"] - rec45["first_loss"]) < 0.03,
                f"train-4chip: step-1 loss {rec4['first_loss']:.4f} vs "
                f"one-chip {rec45['first_loss']:.4f}")
        groups = {g for c in rec4["collectives"].values()
                  for g in c["groups"]}
        require(len(groups) >= 2, f"train-4chip: collectives over "
                f"{sorted(groups)} — wanted both tp and dp groups")
        # no layout flag was passed: at tp 2 the program itself picks
        # sequence parallelism over the ring collective matmuls, so these
        # four steps are the bring-up of the ring programs
        require(rec4["sequence_parallel"] is True
                and rec4["tp_overlap"] == "ring"
                and "collective-permute" in rec4["collectives"],
                f"train-4chip: the default tp layout ran as sequence_parallel="
                f"{rec4['sequence_parallel']}, tp_overlap="
                f"{rec4['tp_overlap']!r}, collectives "
                f"{sorted(rec4['collectives'])} — wanted the ring matmuls")
        comm = ", ".join(f"{op} x{c['count']}"
                         for op, c in sorted(rec4["collectives"].items()))
        print(f"[train-4chip] params per device {held}; step-1 loss "
              f"{rec4['first_loss']:.4f} (one chip {rec45['first_loss']:.4f}"
              f"); default tp layout: sequence parallel, tp_overlap="
              f"{rec4['tp_overlap']}; comm: {comm}; replica groups "
              f"{sorted(groups)}",
              flush=True)
    else:
        print(f"[train-4chip] DID NOT RUN: {device['count']} device(s) "
              f"visible, dp2 x tp2 needs 4. Not counted as passed.",
              flush=True)

    for d in ("ckpt_45m", "ckpt_gpt2", "ckpt_4chip"):  # ~0.6 GiB each
        shutil.rmtree(os.path.join(WORK, d), ignore_errors=True)
    print(f"compile cache at {cache['dir']}: {cache['hits']} hit(s), "
          f"{cache['misses']} miss(es) over all phases; compile "
          f"{rec45['compile_s']:.1f}s (train-45m) + "
          f"{recg['compile_s']:.1f}s (train-gpt2-124m); "
          f"{time.time() - t_start:.0f}s in all; logs in {WORK}", flush=True)
    if not on_chip:
        print("chip_smoke --allow-cpu: the commands run. This says nothing "
              "about a chip; no result line is printed.", flush=True)
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        sys.exit(1)
