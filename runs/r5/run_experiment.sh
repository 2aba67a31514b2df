#!/bin/bash
# Round-5 hardware session. Priorities from VERDICT r4 "Next round":
#   1. kernel checks (seconds) — the GQA/positional kernels' only chance at
#      on-chip proof (r3: sys.path bug, r4: chip dropped before the fix)
#   2. the REAL experiment: 5000-step training run + val sweep + decodes
#      (four rounds, zero training steps on silicon) — resumable in small
#      windows via --resume + save_interval 250
#   3. remaining bench lines (remat=false first: it's bench.py's default
#      and has never been measured), decode, spd16, t=8k (FIXED flags —
#      r4 staged --maxlen/--batch_size which bench.py does not have),
#      moe8, remat=true, step-time breakdown
#   4. block sweep, packed-mode run
# Every python step runs under scripts/run_step.py: real rc + stderr tail
# land in $R/session_manifest.jsonl ("failed rc=0" is impossible now).
# Idempotent: artifacts gate each step; safe to relaunch.
# Preflight-validated by tests/test_staged_session.py (every staged command
# line is parsed by the real argparsers on CPU in CI).
set -u
set -o pipefail
cd /root/repo
R=runs/r5
M=$R/session_manifest.jsonl
mkdir -p "$R"
. "$R/session_lib.sh" || { echo "session_lib.sh missing" >&2; exit 96; }  # step() + bench_line()

step probe 120 python -c "import jax; d=jax.devices(); assert d[0].platform!='cpu', d; print('devices:', d)" \
  || exit 17

# ---- 1. kernel checks (VERDICT r4 #2) ----------------------------------
if ! grep -q '"all_ok": true' "$R/kernel_checks.json" 2>/dev/null; then
  step kernel_checks 900 python scripts/tpu_checks.py --out "$R/kernel_checks.json" \
      | tee -a "$R/session.log"
fi

# ---- 2. the real experiment (VERDICT r4 #1) ----------------------------
if [ ! -s "$R/tokenizer.json" ]; then
  cp tokenizer/tokenizer.json "$R/tokenizer.json"
fi
TOKENS=/tmp/corpus_tokens.json
if [ ! -s "$TOKENS" ]; then
  echo "regenerating corpus (tmp was cleared)" | tee -a "$R/session.log"
  step corpus 1200 python scripts/make_image_corpus.py /tmp/corpus_texts.json \
      --root /opt/venv/lib/python3.12/site-packages
  step tokenize 1200 python -m distributed_pytorch_from_scratch_tpu.data.tokenizer encode \
      -i /tmp/corpus_texts.json -o "$TOKENS" -t "$R/tokenizer.json"
fi

if ! grep -q "training finished" "$R/train.log" 2>/dev/null; then
  python scripts/run_step.py --manifest "$M" --name train45m --timeout 5400 --grace 90 \
    --tee "$R/train.log" -- \
    python -m distributed_pytorch_from_scratch_tpu.train \
      --data_path "$TOKENS" --save_dir "$R/ckpt" \
      --bf16 --batch_size 32 --maxlen 512 \
      --max_steps 5000 --warmup_steps 500 --lr 3e-4 \
      --steps_per_dispatch 8 --remat dots \
      --log_interval 100 --save_interval 250 --reserve_last_n_ckpts 20 \
      --resume 2>> "$R/session.log" | tail -50
fi

if grep -q "training finished" "$R/train.log" 2>/dev/null \
    && ! grep -q "val loss" "$R/eval.log" 2>/dev/null; then
  python scripts/run_step.py --manifest "$M" --name eval45m --timeout 2700 \
    --tee "$R/eval.log" -- \
    python -m distributed_pytorch_from_scratch_tpu.evaluate \
      --data_path "$TOKENS" --ckpt_dir "$R/ckpt" \
      --tokenizer_path "$R/tokenizer.json" \
      --maxlen 512 --batch_size 8 --max_decode_len 64 \
      2>> "$R/session.log" | tail -60
fi

# ---- 3. bench lines (value order; fixed t=8k flags) --------------------
bench_line 45mrematfalse   1200 --model 45m --remat false
bench_line 45mdecode       1200 --model 45m --decode
bench_line 45mspd16        1200 --model 45m --remat false --steps_per_dispatch 16
bench_line 45mbreakdown    1200 --model 45m --remat false --breakdown
bench_line 45mt8k          1800 --model 45m --remat dots --seqlen 8192 --batch 2
bench_line 45m-moe8        1800 --model 45m-moe8 --remat dots
bench_line 45mremattrue    1200 --model 45m --remat true
bench_line gpt2-124mdecode 1200 --model gpt2-124m --decode --batch 4
bench_line gpt2-124mrematfalse 1200 --model gpt2-124m --remat false
bench_line gpt2-355mrematdots  2400 --model gpt2-355m --family gpt2 --remat dots

# ---- 4. extras ---------------------------------------------------------
# jax.profiler trace of the 45M config (VERDICT r4 #3: where do the step
# milliseconds go — the trace complements bench --breakdown's numbers).
# 24 steps = 3 dispatches at spd8; ProfilerTrace covers steps 3..3+8.
# guard: the trace lands at logs/profile/plugins (single-process; ProfilerTrace
# appends 'profile', jax.profiler adds 'plugins') — match that exact depth
if ! ls -d "$R"/ckpt_profile/logs/profile/plugins >/dev/null 2>&1; then
  python scripts/run_step.py --manifest "$M" --name profile_trace \
    --timeout 1200 --grace 90 -- \
    python -m distributed_pytorch_from_scratch_tpu.train \
      --data_path "$TOKENS" --save_dir "$R/ckpt_profile" \
      --bf16 --batch_size 32 --maxlen 512 \
      --max_steps 24 --warmup_steps 8 --lr 3e-4 \
      --steps_per_dispatch 8 --remat dots --profile_steps 8 \
      --log_interval 8 --save_interval 100000 \
      2>> "$R/session.log" | tail -10
fi
if [ ! -s "$R/tune_blocks.log" ] || ! grep -q "BEST" "$R/tune_blocks.log"; then
  python scripts/run_step.py --manifest "$M" --name block_sweep \
      --timeout 2400 --tee "$R/tune_blocks.log" -- \
      python scripts/tune_flash_blocks.py --quick --iters 10 \
      2>> "$R/session.log" | grep -E "===|BEST" | tee -a "$R/session.log"
fi

if ! grep -q "training finished" "$R/train_packed.log" 2>/dev/null; then
  python scripts/run_step.py --manifest "$M" --name train45m_packed \
    --timeout 2700 --grace 90 --tee "$R/train_packed.log" -- \
    python -m distributed_pytorch_from_scratch_tpu.train \
      --data_path "$TOKENS" --save_dir "$R/ckpt_packed" \
      --data_mode packed \
      --bf16 --batch_size 32 --maxlen 512 \
      --max_steps 1000 --warmup_steps 100 --lr 3e-4 \
      --steps_per_dispatch 8 --remat dots \
      --log_interval 100 --save_interval 500 --reserve_last_n_ckpts 2 \
      --resume 2>> "$R/session.log" | tail -20
fi

# ---- 5. collect results (round-agnostic plumbing, VERDICT r4 #6) -------
python scripts/summarize_run.py "$R" \
  && python scripts/refresh_baseline.py "$R" | tee -a "$R/session.log"
echo "=== session pass done $(date -u +%FT%TZ) ===" | tee -a "$R/session.log"
