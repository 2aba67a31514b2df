#!/bin/bash
# Round-6 fast-path session (ISSUE 3 acceptance): the staged 45M >=45%-MFU
# line. Order: on-chip flash block sweep -> autotuner cache, the measured
# breakdown+attribution at the round-4 config (so the before/after is on
# the SAME chip session), then the fast-path line (tuned blocks + pad-aware
# seq bucketing + remat auto + spd16) and its spd8 control. Idempotent;
# reuses the round-5 session helpers (step/bench_line artifact guards,
# SESSION_DEADLINE chokepoint via scripts/run_step.py).
set -u
set -o pipefail
cd /root/repo
R=runs/r6
M=$R/session_manifest.jsonl
mkdir -p "$R"
. runs/r5/session_lib.sh || { echo "session_lib.sh missing" >&2; exit 96; }
echo "=== r6 fast-45m pass $(date -u +%FT%TZ) ===" | tee -a "$R/session.log"
step probe 120 python -c "import jax; d=jax.devices(); assert d[0].platform != 'cpu', d" \
  || exit 17

# 1. one-time flash block sweep -> the autotuner cache every later
#    flash_attention call on this backend reads (get_block_config)
if [ ! -s distributed_pytorch_from_scratch_tpu/ops/pallas/flash_blocks.json ]; then
  step block_sweep 1800 python scripts/tune_flash_blocks.py --quick --write_cache
fi

# 2. attribution evidence at the round-4 config: measured components +
#    ranked suspects + XLA cost/alias cross-check, same chip session
bench_line 45mbreakdownr6 1200 --model 45m --remat dots --breakdown --introspect

# 3. the fast path (tuned blocks + bucketed t=1000->1024 + remat auto +
#    spd16) and its spd8 control; then the unmodified r4 config as the
#    same-session baseline
bench_line 45mfast     1200 --model 45m --remat auto --seq_bucket 128 --steps_per_dispatch 16
bench_line 45mfastspd8 1200 --model 45m --remat auto --seq_bucket 128
bench_line 45mr4cfg    1200 --model 45m --remat dots

python scripts/summarize_run.py "$R" || true
echo "=== r6 fast-45m done $(date -u +%FT%TZ) ===" | tee -a "$R/session.log"
