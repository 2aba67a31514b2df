#!/bin/bash
# Round-6 fast-path session (ISSUE 3 acceptance): the staged 45M >=45%-MFU
# line. Order: on-chip flash block sweep (printed, nothing written), the
# measured breakdown+attribution at the round-4 config (so the before/after
# is on the SAME chip session), then the fast-path line (pad-aware seq
# bucketing + remat auto + spd16) and its spd8 control. Idempotent;
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

# 1. flash block sweep: a reading of this chip's blocks beside the lines
#    below. It writes nothing; the kernels take their blocks from the shapes
#    (flash_attention.flash_blocks), and a winner other than DEFAULT_BLOCK
#    is adopted by editing the kernel module with this reading beside it
step block_sweep 1800 python scripts/tune_flash_blocks.py --quick

# 2. attribution evidence at the round-4 config: measured components +
#    ranked suspects + XLA cost/alias cross-check, same chip session
bench_line 45mbreakdownr6 1200 --model 45m --remat dots --breakdown --introspect

# 3. the fast path (bucketed t=1000->1024 + remat auto + spd16) and its
#    spd8 control; then the unmodified r4 config as the same-session
#    baseline
bench_line 45mfast     1200 --model 45m --remat auto --seq_bucket 128 --steps_per_dispatch 16
bench_line 45mfastspd8 1200 --model 45m --remat auto --seq_bucket 128
bench_line 45mr4cfg    1200 --model 45m --remat dots

python scripts/summarize_run.py "$R" || true
echo "=== r6 fast-45m done $(date -u +%FT%TZ) ===" | tee -a "$R/session.log"
