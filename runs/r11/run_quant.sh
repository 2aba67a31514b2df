#!/bin/bash
# Round-11 quantization session (ISSUE 8): int8 on the wires and in the
# caches, priced against the bf16/f32 baselines it claims to beat.
#   1. wire sweep — the bucketed DP grad reduce at f32 / bf16 / int8 on
#      a dp2xtp4 mesh with SP (the PR 4 overlap config): same model,
#      same buckets, only the wire dtype moves, so the tok/s deltas ARE
#      the wire. Needs >= 8 chips; a dp2xtp1 fallback covers the wire
#      on smaller multi-chip windows, and single-chip sessions skip with
#      a logged note.
#   2. ring_q — the tp ring collective matmuls with int8 ppermute
#      payloads vs round 7's bf16 ring, tp = all chips (works from 2).
#   3. int8-KV serving arm — equal-page-byte-budget A/B: the int8 pool
#      is granted ~2x the pages at the SAME bytes (kv_capacity_ratio in
#      the record) and the bench reports paged-vs-slot + TTFT under the
#      long/short interleave; plus the int8 decode-weight variant to
#      price the weight-read floor.
#   4. breakdown lines — comm attribution pricing the int8 wire at
#      quarter bytes (wire_dtype lands in the record, so the r11 numbers
#      stay attributable).
# Weights are random inits; wire/cache dtype effects are value-free
# (latency depends on shapes) and the accuracy story is pinned by CPU
# tests, so no checkpoint transfer burns window. Idempotent; reuses the
# round-5 session helpers (step/bench_line artifact guards,
# SESSION_DEADLINE chokepoint via scripts/run_step.py).
set -u
set -o pipefail
cd /root/repo
R=runs/r11
M=$R/session_manifest.jsonl
mkdir -p "$R"
. runs/r5/session_lib.sh || { echo "session_lib.sh missing" >&2; exit 96; }
echo "=== r11 quant pass $(date -u +%FT%TZ) ===" | tee -a "$R/session.log"
step probe 120 python -c "import jax; d=jax.devices(); assert d[0].platform != 'cpu', d" \
  || exit 17

# 1. the wire sweep on the dp2xtp4 overlap config (>= 8 chips), else the
#    dp2 fallback (>= 2 chips), else skip with a note
if timeout 120 python -c "import jax, sys; sys.exit(0 if jax.device_count() >= 8 else 1)"; then
  bench_line 45mwiref32  1200 --model 45m --remat auto --seq_bucket 128 --dp 2 --tp 4 --sequence_parallel --dp_reduce_bucket_mb 25 --steps_per_dispatch 16
  bench_line 45mwirebf16 1200 --model 45m --remat auto --seq_bucket 128 --dp 2 --tp 4 --sequence_parallel --dp_reduce_bucket_mb 25 --dp_reduce_dtype bf16 --steps_per_dispatch 16
  bench_line 45mwireint8 1200 --model 45m --remat auto --seq_bucket 128 --dp 2 --tp 4 --sequence_parallel --dp_reduce_bucket_mb 25 --dp_reduce_dtype int8 --steps_per_dispatch 16
elif timeout 120 python -c "import jax, sys; sys.exit(0 if jax.device_count() >= 2 else 1)"; then
  bench_line 45mwiref32  1200 --model 45m --remat auto --seq_bucket 128 --dp 2 --tp 1 --dp_reduce_bucket_mb 25 --steps_per_dispatch 16
  bench_line 45mwirebf16 1200 --model 45m --remat auto --seq_bucket 128 --dp 2 --tp 1 --dp_reduce_bucket_mb 25 --dp_reduce_dtype bf16 --steps_per_dispatch 16
  bench_line 45mwireint8 1200 --model 45m --remat auto --seq_bucket 128 --dp 2 --tp 1 --dp_reduce_bucket_mb 25 --dp_reduce_dtype int8 --steps_per_dispatch 16
else
  echo "r11: single-chip session — DP wire sweep skipped (needs >= 2 chips)" | tee -a "$R/session.log"
fi

# 2. ring_q vs ring: the tp rings with int8 payloads, tp = all chips
bench_line 45mring   1200 --model 45m --remat auto --seq_bucket 128 --sequence_parallel --tp_overlap ring --steps_per_dispatch 16
bench_line 45mringq  1200 --model 45m --remat auto --seq_bucket 128 --sequence_parallel --tp_overlap ring_q --steps_per_dispatch 16

# 3. the serving arms: native vs int8 KV at the SAME page-byte budget,
#    then int8 KV + int8 decode weights (the latency-floor variant)
bench_line 45mkvnative 1200 --serving --model 45m --tp 1 --slots 8 --serve_requests 32 --prompt_len 128 --gen_tokens 128 --page_size 64 --prefill_chunk 128
bench_line 45mkvint8   1200 --serving --model 45m --tp 1 --slots 8 --serve_requests 32 --prompt_len 128 --gen_tokens 128 --page_size 64 --prefill_chunk 128 --kv_dtype int8
bench_line 45mkvwint8  1200 --serving --model 45m --tp 1 --slots 8 --serve_requests 32 --prompt_len 128 --gen_tokens 128 --page_size 64 --prefill_chunk 128 --kv_dtype int8 --decode_weight_dtype int8
step serve_int8 1200 python -m distributed_pytorch_from_scratch_tpu.serving.serve --random_init --model 45m --tp_size 1 --paged --kv_dtype int8 --decode_weight_dtype int8 --slots 16 --num_pages 96 --page_size 64 --prefill_chunk 128 --num_requests 48 --rate 8 --prompt_len_min 32 --prompt_len_max 256 --max_new_tokens 128 --log_dir runs/r11/serve_int8

# 4. attribution evidence: the int8 wire priced at quarter bytes in the
#    comm hidden/exposed line (record carries wire_dtype/tp_overlap)
bench_line 45mquantbreak 1200 --model 45m --remat auto --seq_bucket 128 --sequence_parallel --tp_overlap ring_q --breakdown --introspect

python scripts/summarize_run.py "$R" || true
echo "=== r11 quant done $(date -u +%FT%TZ) ===" | tee -a "$R/session.log"
