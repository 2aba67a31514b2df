#!/bin/bash
# Round-21 elastic reshard session (ISSUE 20): mesh-elastic checkpoints
# + any-layout->any-layout redistribution on real chips. CI pins
# bit-identity (tp4->tp2, tp2->dp2xtp2, zero3->zero0, moments riding the
# same plan), the peak-host-one-leaf law, and the graftcheck
# reshard-fragmentwise contract on the CPU mesh; this window lands the
# NUMBERS and the live restart paths:
#   1. static + trace preflight — layer 1 AND layer 2 (which now pins
#      the lowered live-mesh reshard against the planner's schedule).
#   2. the tp4 training artifact — a short slice that saves a STAMPED
#      checkpoint (layout in the shard metadata) at tp4.
#   3. the offline reshard — plan first (op counts, bytes, printed
#      without writing), then the real tp4 -> tp2 file->file pass; the
#      output is validate_checkpoint-clean at tp2.
#   4. serving the resharded artifact at tp2 — the dp2xtp4-training ->
#      tp2-serving handoff the subsystem exists for.
#   5. the ELASTIC resume — train --resume on a dp2xtp2 mesh pointed at
#      the tp4 checkpoint dir: mesh mismatch detected, leaves streamed
#      through the reshard plan, reshard_event in the metrics stream
#      (forensics joins it into the run lineage).
#   6. the fleet width restart — a live replica swapped to a different
#      tp width mid-traffic (device-to-device reshard, token-identical
#      by CI pin); replica_restart carries the plan summary.
#   7. the bench pair + gate — two identical bench --reshard lines
#      gated against each other (reshard_ms directional at 25%,
#      reshard_bytes_moved must not grow — the minimal-transfer claim).
# Idempotent; reuses the round-5 session helpers.
set -u
set -o pipefail
cd /root/repo
R=runs/r21
M=$R/session_manifest.jsonl
mkdir -p "$R"
. runs/r5/session_lib.sh || { echo "session_lib.sh missing" >&2; exit 96; }
echo "=== r21 elastic pass $(date -u +%FT%TZ) ===" | tee -a "$R/session.log"

step probe 120 python -c "import jax; d=jax.devices(); assert d[0].platform != 'cpu', d" \
  || exit 17

# 1. static sweep + the traced contracts (reshard-fragmentwise included)
step graftcheck 600 python scripts/graftcheck.py --json runs/r21/graftcheck.json

# 2. the tp4 training artifact (the corpus regenerates when /tmp was
# cleared — the r5 convention); saves a stamped ckpt at iter 60
TOKENS=/tmp/corpus_tokens.json
if [ ! -s "$TOKENS" ]; then
  echo "regenerating corpus (tmp was cleared)" | tee -a "$R/session.log"
  step corpus 1200 python scripts/make_image_corpus.py /tmp/corpus_texts.json \
      --root /opt/venv/lib/python3.12/site-packages
  step tokenize 1200 python -m distributed_pytorch_from_scratch_tpu.data.tokenizer encode \
      -i /tmp/corpus_texts.json -o "$TOKENS" -t tokenizer/tokenizer.json
fi
python scripts/run_step.py --manifest "$M" --name train_tp4 --timeout 1200 --grace 90 \
  --tee "$R/train_tp4.log" -- \
  python -m distributed_pytorch_from_scratch_tpu.train \
    --data_path "$TOKENS" --save_dir "$R/ckpt_tp4" --tp_size 4 \
    --sequence_parallel --bf16 --batch_size 32 --maxlen 512 \
    --max_steps 60 --warmup_steps 10 --lr 3e-4 \
    --log_interval 20 --save_interval 30 2>> "$R/session.log" | tail -20

# 3. the offline reshard: plan (printed, nothing written), then the
# real tp4 -> tp2 pass — validate_checkpoint-clean output, stamped with
# the target layout, peak host bytes bounded by the largest leaf
step reshard_plan 300 python scripts/reshard_ckpt.py --src runs/r21/ckpt_tp4 \
  --dst runs/r21/ckpt_tp2 --tp 2 --plan_only
step reshard_tp2 600 python scripts/reshard_ckpt.py --src runs/r21/ckpt_tp4 \
  --dst runs/r21/ckpt_tp2 --tp 2

# 4. serve the resharded artifact at tp2 (training layout -> serving
# layout, through files)
step serve_tp2 1200 python scripts/serve_fleet.py --replicas 1 --tp_size 2 \
  --model 45m --ckpt_dir runs/r21/ckpt_tp2 --slots 8 --page_size 64 \
  --num_requests 24 --arrival burst \
  --prompt_len_min 16 --prompt_len_max 64 --max_new_tokens 64 \
  --log_dir runs/r21/serve_logs_tp2

# 5. the elastic resume: the tp4 checkpoint restarted on a dp2xtp2 mesh
# — mismatch detected, leaves resharded on load, ZeRO ownership
# re-derived, reshard_event in the metrics stream
python scripts/run_step.py --manifest "$M" --name elastic_resume --timeout 1200 --grace 90 \
  --tee "$R/train_elastic.log" -- \
  python -m distributed_pytorch_from_scratch_tpu.train \
    --data_path "$TOKENS" --save_dir "$R/ckpt_tp4" --tp_size 2 --dp_size 2 \
    --sequence_parallel --bf16 --batch_size 32 --maxlen 512 \
    --max_steps 90 --warmup_steps 10 --lr 3e-4 \
    --log_interval 10 --save_interval 1000 \
    --resume 2>> "$R/session.log" | tail -20

# 6. the fleet width restart: two tp1 replicas under traffic, r1 swapped
# to tp2 between waves (device-to-device reshard; CI pins the swapped
# replica token-identical)
step fleet_restart 1500 python scripts/serve_fleet.py --replicas 2 --tp_size 1 \
  --model 45m --random_init --slots 8 --page_size 64 \
  --num_requests 48 --arrival poisson --rate 8 \
  --prompt_len_min 16 --prompt_len_max 64 --max_new_tokens 64 \
  --restart_tp 2 --restart_replica r1 \
  --log_dir runs/r21/serve_logs_restart

# 7. the bench pair + gate: two identical reshard lines, the second
# gated against the first (reshard_ms 25% band; reshard_bytes_moved
# must not grow — the minimal-transfer planner's claim)
bench_line reshard 900 --reshard --model 45m --tp 4 --reshard_tp 2
bench_line reshard2 900 --reshard --model 45m --tp 4 --reshard_tp 2
step gate 240 python scripts/check_bench_regression.py --fresh runs/r21/bench_reshard2.json --baseline runs/r21/bench_reshard.json --tol_latency_pct 25 --explain

python scripts/summarize_run.py "$R" || true
echo "=== r21 elastic done $(date -u +%FT%TZ) ===" | tee -a "$R/session.log"
