#!/bin/bash
# Round-7 overlap session (ISSUE 4): comm-overlap A/B on the 45M config.
# Order: breakdown+attribution at the r6 fast config (same-session
# baseline, now with the comm hidden/exposed line), then the overlap
# on/off A/B — tp over all chips with SP, monolithic vs ring collective
# matmuls — and, ONLY when the session has >= 2 chips, the bucketed bf16
# DP reduce A/B on a dp=2 mesh (skipped with a logged note on a
# single-chip machine).
# Idempotent; reuses the round-5 session helpers (step/bench_line
# artifact guards, SESSION_DEADLINE chokepoint via scripts/run_step.py).
set -u
set -o pipefail
cd /root/repo
R=runs/r7
M=$R/session_manifest.jsonl
mkdir -p "$R"
. runs/r5/session_lib.sh || { echo "session_lib.sh missing" >&2; exit 96; }
echo "=== r7 overlap pass $(date -u +%FT%TZ) ===" | tee -a "$R/session.log"
step probe 120 python -c "import jax; d=jax.devices(); assert d[0].platform != 'cpu', d" \
  || exit 17

# 1. attribution evidence at the r6 fast config, now with the comm
#    hidden/exposed line + ring chunk-schedule cross-check in --introspect
bench_line 45mbreakdownr7 1200 --model 45m --remat auto --seq_bucket 128 --breakdown --introspect

# 2. the overlap A/B, single-chip-count controlled: SP monolithic vs SP
#    ring on the same mesh (tp = all chips, --tp 0), seq bucketed so the
#    ring chunks tile cleanly (t=1024 % tp == 0 for tp in {2,4,8})
bench_line 45mspoff  1200 --model 45m --remat auto --seq_bucket 128 --sequence_parallel --steps_per_dispatch 16
bench_line 45mspring 1200 --model 45m --remat auto --seq_bucket 128 --sequence_parallel --tp_overlap ring --steps_per_dispatch 16

# 3. ring + introspect: the HLO collective-permute bytes vs the ring's
#    chunk schedule, measured components + comm attribution on-chip
bench_line 45mringbreak 1200 --model 45m --remat auto --seq_bucket 128 --sequence_parallel --tp_overlap ring --breakdown --introspect

# 4. bucketed bf16 DP grad reduce A/B — needs a real dp axis, so only on
#    multi-chip sessions (skipped on one chip, logged so the manifest
#    says why)
if timeout 120 python -c "import jax, sys; sys.exit(0 if jax.device_count() >= 2 else 1)"; then
  bench_line 45mdpblob   1200 --model 45m --remat auto --seq_bucket 128 --dp 2 --tp 1 --steps_per_dispatch 16
  bench_line 45mdpbucket 1200 --model 45m --remat auto --seq_bucket 128 --dp 2 --tp 1 --dp_reduce_bucket_mb 25 --dp_reduce_dtype bf16 --steps_per_dispatch 16
else
  echo "r7: single-chip session — dp-bucket A/B skipped (needs >= 2 chips)" | tee -a "$R/session.log"
fi

python scripts/summarize_run.py "$R" || true
echo "=== r7 overlap done $(date -u +%FT%TZ) ===" | tee -a "$R/session.log"
