#!/bin/bash
# Round-17 control-plane session (ISSUE 16): the obs stack stops being
# read-only — drift-driven self-tuning + the online SLO controller, with
# every decision in an auditable ledger.
#   0. static preflight — graftcheck layer 1 (incl. the new
#      controller-discipline rule: actuation only inside
#      @control_safe_point functions).
#   1. advise-mode TRAIN window — the duty profiler's measured
#      reconciles feed the RetuneAdvisor; every proposal lands as a
#      versioned tuning_decision event with its evidence (per-phase
#      drift ms, HBM headroom, capture id) but NOTHING moves (the
#      advise rung of the --control ladder; dp bucket MiB is an
#      init-boundary knob anyway).
#   2. act-mode SERVING loadgen with a mid-run traffic shift — burst
#      arrivals against a tight interactive SLO force the SLOController
#      to adapt (admission clamp under the burst, recovery after);
#      every actuation is a controller_decision cross-linked to the
#      telemetry snapshot that triggered it (snapshot_seq), and the
#      duty profiler rides along so the RetuneAdvisor can move
#      prefill_chunk/pages_per_block at its between-window safe point.
#      The record (stdout JSON line) carries controller.windows —
#      pre/post first-actuation metrics.
#   3. off-mode CONTROL arm — the same loadgen with the controller off:
#      the record and event stream must look exactly like pre-v5 output
#      (the zero-cost-off contract tests/test_control.py pins on CPU,
#      demonstrated here on chip).
#   4. collector pass — obs_top --once renders the fleet view with the
#      new ctl column (mode, decisions, last knob) and the control
#      header over the act arm's metrics chains.
#   5. gate — check_bench_regression --controller on the act record:
#      the post-decision window must not be worse than the pre-decision
#      window (tok/s within tolerance, p95 latencies not up).
# Weights are random inits (control behaviour depends on load, not
# values); decision rules are pinned by CPU tests (tests/test_control.py).
# Idempotent; reuses the round-5 session helpers.
set -u
set -o pipefail
cd /root/repo
R=runs/r17
M=$R/session_manifest.jsonl
mkdir -p "$R"
. runs/r5/session_lib.sh || { echo "session_lib.sh missing" >&2; exit 96; }
echo "=== r17 control pass $(date -u +%FT%TZ) ===" | tee -a "$R/session.log"
step probe 120 python -c "import jax; d=jax.devices(); assert d[0].platform != 'cpu', d" \
  || exit 17

# 0. static preflight: layer-1 sweep (controller-discipline included)
step graftcheck 240 python scripts/graftcheck.py --no-trace --json runs/r17/graftcheck.json

# 1. advise-mode train window (the corpus regenerates when /tmp was
# cleared — the r5 convention)
TOKENS=/tmp/corpus_tokens.json
if [ ! -s "$TOKENS" ]; then
  echo "regenerating corpus (tmp was cleared)" | tee -a "$R/session.log"
  step corpus 1200 python scripts/make_image_corpus.py /tmp/corpus_texts.json \
      --root /opt/venv/lib/python3.12/site-packages
  step tokenize 1200 python -m distributed_pytorch_from_scratch_tpu.data.tokenizer encode \
      -i /tmp/corpus_texts.json -o "$TOKENS" -t tokenizer/tokenizer.json
fi
python scripts/run_step.py --manifest "$M" --name trainadvise --timeout 2400 --grace 90 \
  --tee "$R/train.log" -- \
  python -m distributed_pytorch_from_scratch_tpu.train \
    --data_path "$TOKENS" --save_dir "$R/ckpt" \
    --bf16 --batch_size 32 --maxlen 512 \
    --max_steps 300 --warmup_steps 50 --lr 3e-4 \
    --steps_per_dispatch 1 --remat dots --seq_bucket 128 \
    --log_interval 50 --save_interval 1000 \
    --profile_every 60 --profile_window 4 --profile_budget_mb 256 \
    --control advise \
    --metrics_port 9317 2>> "$R/session.log" | tail -30

# 2. act-mode serving loadgen, burst arrivals = the mid-run traffic
# shift; the stdout JSON record is the gate's food (controller.windows)
python scripts/run_step.py --manifest "$M" --name ctlserve --timeout 1500 -- \
  python -m distributed_pytorch_from_scratch_tpu.serving.serve \
    --random_init --paged --arrival burst \
    --control act --control_interval 24 --control_force \
    --profile_every 40 --profile_window 4 \
    --num_requests 96 --rate 24 --slots 8 --num_pages 48 --page_size 16 \
    --max_new_tokens 48 --prompt_len_min 8 --prompt_len_max 96 \
    --slo_classes interactive=0.05,standard=1.0 \
    --class_mix interactive=3,standard=1 \
    --metrics_port 9319 --rollup_interval 1 \
    --log_dir runs/r17/ctl_logs \
    > "$R/serve_control.json" 2>> "$R/session.log"
cat "$R/serve_control.json" | tee -a "$R/session.log"

# 3. off-mode arm: same loadgen, controller off — the pre-v5-identical
# record/event-stream the zero-cost-off contract demands
python scripts/run_step.py --manifest "$M" --name offserve --timeout 1200 -- \
  python -m distributed_pytorch_from_scratch_tpu.serving.serve \
    --random_init --paged --arrival burst \
    --num_requests 96 --rate 24 --slots 8 --num_pages 48 --page_size 16 \
    --max_new_tokens 48 --prompt_len_min 8 --prompt_len_max 96 \
    --slo_classes interactive=0.05,standard=1.0 \
    --class_mix interactive=3,standard=1 \
    --log_dir runs/r17/off_logs \
    > "$R/serve_off.json" 2>> "$R/session.log"

# 4. collector pass: the ctl column + control header over the act arm
step rollup 120 python scripts/obs_top.py runs/r17/ctl_logs --once --no_clear

# 5. the continuous gate: post- vs pre-decision windows of the act record
step ctlgate 120 python scripts/check_bench_regression.py --fresh runs/r17/serve_control.json --controller

python scripts/summarize_run.py "$R" || true
echo "=== r17 control done $(date -u +%FT%TZ) ===" | tee -a "$R/session.log"
