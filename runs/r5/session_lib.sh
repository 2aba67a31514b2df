# Shared helpers for the round-5 hardware session scripts. Sourced by
# run_experiment.sh and run_priority.sh (single definition — the two
# scripts' helpers can't drift). Tested in tests/test_session_shell.py
# against stub commands, so the shell plumbing (rc propagation, artifact
# guards, error-payload cleanup) is proven before any chip window.
#
# Requires: $R (runs dir), $M (manifest path) set by the sourcing script;
# `set -o pipefail` recommended (step's tee must not mask the rc).

# Deadline protection (the driver benches the single-tenant chip at round
# end) lives in scripts/run_step.py::past_deadline — the one chokepoint
# every step passes through. Past SESSION_DEADLINE (YYYYmmddHHMM UTC)
# run_step refuses to start the child (rc 18,
# recorded in the manifest) so the chip stays free; no per-call-site guard
# needed here.

step() { # step NAME TIMEOUT cmd...   -> real rc via scripts/run_step.py
  local name=$1 to=$2; shift 2
  echo "=== $name $(date -u +%FT%TZ) ===" | tee -a "$R/session.log"
  python scripts/run_step.py --manifest "$M" --name "$name" --timeout "$to" \
      -- "$@" 2>> "$R/session.log"
}

bench_line() { # bench_line TAG TIMEOUT args...  -> $R/bench_TAG.json
  local tag=$1 to=$2; shift 2
  # an error artifact must not satisfy the guard
  if grep -q '"error"' "$R/bench_${tag}.json" 2>/dev/null; then
    rm -f "$R/bench_${tag}.json"
  fi
  if [ ! -s "$R/bench_${tag}.json" ]; then
    echo "=== bench $tag $(date -u +%FT%TZ) ===" | tee -a "$R/session.log"
    python scripts/run_step.py --manifest "$M" --name "bench_${tag}" \
        --timeout "$to" -- python bench.py "$@" \
        > "$R/bench_${tag}.json" 2>> "$R/session.log"
    if [ $? -ne 0 ]; then
      rm -f "$R/bench_${tag}.json"
    else
      cat "$R/bench_${tag}.json" | tee -a "$R/session.log"
    fi
  fi
}
