#!/bin/bash
# Trimmed round-5 pass for a short session (~10 min): kernel checks,
# as much of the resumable training run as fits in a short budget, and the
# two highest-value bench lines. Idempotent; shares artifacts/manifest with
# run_experiment.sh so a later full pass skips whatever this one landed.
set -u
set -o pipefail
cd /root/repo
R=runs/r5
M=$R/session_manifest.jsonl
mkdir -p "$R"
. "$R/session_lib.sh" || { echo "session_lib.sh missing" >&2; exit 96; }  # step() + bench_line()
echo "=== PRIORITY pass $(date -u +%FT%TZ) ===" | tee -a "$R/session.log"
step probe 120 python -c "import jax; d=jax.devices(); assert d[0].platform!='cpu', d" \
  || exit 17

if ! grep -q '"all_ok": true' "$R/kernel_checks.json" 2>/dev/null; then
  step kernel_checks 600 python scripts/tpu_checks.py --out "$R/kernel_checks.json" \
      | tee -a "$R/session.log"
fi

TOKENS=/tmp/corpus_tokens.json
if [ ! -s "$R/tokenizer.json" ]; then cp tokenizer/tokenizer.json "$R/tokenizer.json"; fi
if [ ! -s "$TOKENS" ]; then
  step corpus 1200 python scripts/make_image_corpus.py /tmp/corpus_texts.json \
      --root /opt/venv/lib/python3.12/site-packages
  step tokenize 1200 python -m distributed_pytorch_from_scratch_tpu.data.tokenizer encode \
      -i /tmp/corpus_texts.json -o "$TOKENS" -t "$R/tokenizer.json"
fi

# short training slice: --resume + save_interval 250 means even a 6-minute
# budget banks permanent progress toward the 5000-step artifact
if ! grep -q "training finished" "$R/train.log" 2>/dev/null; then
  python scripts/run_step.py --manifest "$M" --name train45m_slice \
    --timeout 360 --grace 90 --tee "$R/train.log" -- \
    python -m distributed_pytorch_from_scratch_tpu.train \
      --data_path "$TOKENS" --save_dir "$R/ckpt" \
      --bf16 --batch_size 32 --maxlen 512 \
      --max_steps 5000 --warmup_steps 500 --lr 3e-4 \
      --steps_per_dispatch 8 --remat dots \
      --log_interval 100 --save_interval 250 --reserve_last_n_ckpts 20 \
      --resume 2>> "$R/session.log" | tail -20
fi

bench_line 45mrematfalse 600 --model 45m --remat false
bench_line 45mdecode     600 --model 45m --decode
python scripts/summarize_run.py "$R" && python scripts/refresh_baseline.py "$R" || true
echo "=== priority pass done $(date -u +%FT%TZ) ===" | tee -a "$R/session.log"
