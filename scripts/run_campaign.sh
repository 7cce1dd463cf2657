#!/usr/bin/env bash
# One-command benchmark campaign: reproduces every BASELINE.json row on
# the current backend (intended for a real TPU chip). Results land in
# campaign_<timestamp>/ as raw CSV/JSON logs, one file per experiment
# (the scripts/summit/512node_jacobi3d.sh:15-37 ethos: a reproducible
# sweep, every number written down).
#
# CAMPAIGN_SMOKE=1 runs the same sweep structure on an 8-device virtual
# CPU mesh with tiny sizes — a plumbing check for CI, not a benchmark.
set -uo pipefail
cd "$(dirname "$0")/.."

SMOKE="${CAMPAIGN_SMOKE:-0}"
OUT="$(pwd)/campaign_$(date +%Y%m%d_%H%M%S)"
mkdir -p "$OUT"
echo "campaign output -> $OUT/ (smoke=$SMOKE)"

FAKE=()
if [ "$SMOKE" = "1" ]; then
    FAKE=(--fake-cpu 8)
    JN=16; JI=4; MN=16; MI=2; EX=8; EI=2
else
    JN=256; JI=50; MN=128; MI=10; EX=256; EI=30
fi

run() {  # run <logfile> <cmd...>; failures are recorded, not fatal
    local log="$OUT/$1" rc; shift
    echo "== $* (-> $log)"
    "$@" > "$log" 2> "$log.err"
    rc=$?
    if [ "$rc" -ne 0 ]; then
        echo "FAILED rc=$rc (see $log.err)" | tee -a "$log"
    fi
}

# 1. headline: jacobi3d 512^3 iters/s + exchange stats (BENCH schema;
#    needs the real backend — skipped in smoke mode)
if [ "$SMOKE" != "1" ]; then
    run bench.json python bench.py
fi

# 2. single-chip kernel A/B: wrap vs halo vs xla, both models
run kernels_default.csv python scripts/bench_kernels.py \
    --model both --kernels wrap,halo,xla "${FAKE[@]}"

# 3. block-shape sweeps at the benchmark sizes
for b in "8,128" "16,128" "8,256" "16,64"; do
    run "kernels_jacobi_b${b/,/x}.csv" python scripts/bench_kernels.py \
        --model jacobi --kernels wrap,halo --blocks "$b" \
        --iters "$([ "$SMOKE" = 1 ] && echo 4 || echo 100)" "${FAKE[@]}"
done
for b in "8,32" "8,64" "16,32"; do
    run "kernels_mhd_b${b/,/x}.csv" python scripts/bench_kernels.py \
        --model mhd --kernels wrap,halo --blocks "$b" \
        --iters "$([ "$SMOKE" = 1 ] && echo 2 || echo 10)" "${FAKE[@]}"
done
# fused RK substep-0+1 pair, wrap + halo paths (A/B vs the rows above)
run kernels_mhd_pair.csv env STENCIL_MHD_PAIR=1 \
    python scripts/bench_kernels.py --model mhd --kernels wrap,halo \
    --iters "$([ "$SMOKE" = 1 ] && echo 2 || echo 10)" "${FAKE[@]}"
# bfloat16 (half HBM traffic; MHD stores bf16 / computes f32) — same
# default iteration counts as kernels_default.csv for a like-for-like
# f32-vs-bf16 A/B
run kernels_bf16.csv python scripts/bench_kernels.py \
    --model both --kernels wrap,halo --dtype bf16 \
    "${FAKE[@]}"
# limiter evidence: stream ceiling + ladder + LIMITER verdict per model
PROF=()
if [ "$SMOKE" = "1" ]; then PROF=(--size 16 --iters 2); fi
run profile_jacobi.csv python scripts/profile_wrap.py \
    ${PROF[@]+"${PROF[@]}"} "${FAKE[@]}"
run profile_mhd.csv python scripts/profile_wrap.py \
    --model mhd ${PROF[@]+"${PROF[@]}"} "${FAKE[@]}"

# 4. exchange microbenchmarks (BASELINE.json configs 2/4 analogs)
( cd apps
  run bench_exchange.csv python bench_exchange.py \
      --x "$EX" --y "$EX" --z "$EX" --fr 2 --er 2 --cr 2 \
      --iters "$EI" "${FAKE[@]}"
  run bench_pack.csv python bench_pack.py "${FAKE[@]}"
  run pingpong.csv python pingpong.py "${FAKE[@]}"
  run bench_methods.csv python bench_methods.py \
      --x "$EX" --y "$EX" --z "$EX" --iters "$EI" "${FAKE[@]}"
  run bench_qap.csv python bench_qap.py --sizes 4 6 8
  # the fused fast paths' transfer standalone (same byte accounting as
  # the models' exchange_stats)
  run exchange_slabs.csv python exchange_weak.py \
      --x "$EX" --y "$EX" --z "$EX" --radius 3 --iters "$EI" \
      --interior-slabs "${FAKE[@]}"
)

# 5. apps at reference configs (weak scaling on whatever devices exist)
( cd apps
  run jacobi3d.csv python jacobi3d.py \
      --x "$JN" --y "$JN" --z "$JN" --iters "$JI" --batch 2 "${FAKE[@]}"
  run astaroth.csv python astaroth.py \
      --nx "$MN" --ny "$MN" --nz "$MN" --iters "$MI" "${FAKE[@]}"
  run measure_overlap.csv python measure_overlap.py \
      --x "$MN" --y "$MN" --z "$MN" --iters "$MI" "${FAKE[@]}"
)

echo "campaign complete: $OUT/"
# bench.json is absent in smoke mode; the summary glob must not turn a
# fully-green run into a nonzero exit
grep -H "" "$OUT"/*.csv "$OUT"/*.json 2>/dev/null | tail -40 || true
