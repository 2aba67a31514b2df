#!/bin/bash
# Round-16 measured-attribution session (ISSUE 15): close the
# analytic-vs-measured loop on real chips.
#   0. static preflight — graftcheck layer 1 (incl. the new
#      profiler-discipline rule: start/stop only in training/metrics.py).
#   1. duty-cycled profiled TRAIN window — a short 45m run with
#      --profile_every/--profile_window/--profile_budget_mb: every
#      finished capture parses into a profile_attribution event carrying
#      the measured-vs-analytic reconcile against the roofline this
#      repo has priced since PR 3; HBM watermark gauges + events ride
#      the log interval.
#   2. measured breakdown — bench --breakdown --capture_profile wraps
#      the scanned step program in a real capture and reconciles it
#      against the attribution report IN the record
#      (measured_vs_analytic; the gate treats its ms directionally).
#   3. profiled serving bench arm — bench --serving --profile_every on
#      the paged arm: the record carries measured_vs_analytic against
#      the decode HBM roofline (the ISSUE-14 byte model, now checked).
#   4. anomaly arm — impossible interactive deadline forces an online
#      SLO collapse; the anomaly-armed capture now PARSES too (the
#      flight dump cross-links an attributed timeline, not just a dir).
#   5. collector pass — obs_top --once renders the fleet view with the
#      new HBM column over the serving runs' metrics chains.
#   6. gate — check_bench_regression vs the committed trajectory; the
#      measured per-phase / comm ms are directional (up = fail).
# Weights are random inits where possible (measured ms depend on shapes,
# not values); parser correctness is pinned by CPU tests
# (tests/test_measured_attribution.py). Idempotent; reuses the round-5
# session helpers.
set -u
set -o pipefail
cd /root/repo
R=runs/r16
M=$R/session_manifest.jsonl
mkdir -p "$R"
. runs/r5/session_lib.sh || { echo "session_lib.sh missing" >&2; exit 96; }
echo "=== r16 measured pass $(date -u +%FT%TZ) ===" | tee -a "$R/session.log"
step probe 120 python -c "import jax; d=jax.devices(); assert d[0].platform != 'cpu', d" \
  || exit 17

# 0. static preflight: layer-1 sweep (profiler-discipline included),
# report landed for summarize
step graftcheck 240 python scripts/graftcheck.py --no-trace --json runs/r16/graftcheck.json

# 1. duty-cycled profiled train window (the corpus regenerates when /tmp
# was cleared — the r5 convention)
TOKENS=/tmp/corpus_tokens.json
if [ ! -s "$TOKENS" ]; then
  echo "regenerating corpus (tmp was cleared)" | tee -a "$R/session.log"
  step corpus 1200 python scripts/make_image_corpus.py /tmp/corpus_texts.json \
      --root /opt/venv/lib/python3.12/site-packages
  step tokenize 1200 python -m distributed_pytorch_from_scratch_tpu.data.tokenizer encode \
      -i /tmp/corpus_texts.json -o "$TOKENS" -t tokenizer/tokenizer.json
fi
python scripts/run_step.py --manifest "$M" --name trainduty --timeout 2400 --grace 90 \
  --tee "$R/train.log" -- \
  python -m distributed_pytorch_from_scratch_tpu.train \
    --data_path "$TOKENS" --save_dir "$R/ckpt" \
    --bf16 --batch_size 32 --maxlen 512 \
    --max_steps 300 --warmup_steps 50 --lr 3e-4 \
    --steps_per_dispatch 1 --remat dots --seq_bucket 128 \
    --log_interval 50 --save_interval 1000 \
    --profile_every 60 --profile_window 4 --profile_budget_mb 256 \
    --metrics_port 9317 2>> "$R/session.log" | tail -30

# 2. measured breakdown: the roofline report reconciled against a real
# capture of the scanned step program, in the record
bench_line breakdownprof 1800 --breakdown --capture_profile --obs_dir runs/r16/breakdown_obs --steps_per_dispatch 8 --remat dots

# 3. profiled serving bench arm (paged, duty-profiled): the record
# carries measured_vs_analytic vs the decode byte roofline
bench_line servingprof 1500 --serving --profile_every 40 --profile_window 4 --obs_dir runs/r16/bench_obs --page_size 16 --serve_requests 24 --slots 8 --prompt_len 64 --gen_tokens 128

# 4. anomaly arm: impossible deadline -> online SLO collapse -> flight
# dump cross-linking a capture that now PARSES into the metrics chain
step anomaly 900 python -m distributed_pytorch_from_scratch_tpu.serving.serve --random_init --paged --trace_requests --flight_records --profile_on_anomaly 8 --metrics_port 9318 --rollup_interval 1 --num_requests 48 --rate 32 --slots 8 --num_pages 24 --page_size 16 --max_new_tokens 48 --prompt_len_min 8 --prompt_len_max 96 --slo_classes interactive=0.001,standard=1.0,batch=8.0 --class_mix interactive=3,standard=1 --log_dir runs/r16/anomaly_logs

# 5. collector pass: fleet view with the HBM column over the runs' chains
step rollup 120 python scripts/obs_top.py runs/r16/anomaly_logs runs/r16/bench_obs --once --no_clear

# 6. regression gate: the profiled serving line vs the committed
# trajectory (throughput within tolerance AND measured ms not up)
step gate 120 python scripts/check_bench_regression.py --fresh runs/r16/bench_servingprof.json

python scripts/summarize_run.py "$R" || true
echo "=== r16 measured done $(date -u +%FT%TZ) ===" | tee -a "$R/session.log"
