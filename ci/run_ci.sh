#!/usr/bin/env bash
# The CI pipeline, runnable locally or from a trigger (the
# .travis.yml:1-20 analog): static lint gate, native build, unit tests
# on the 8-device virtual CPU mesh, app smoke runs, and the multi-chip
# certification sweep. No TPU required.
#
# Tiers (CI_TIER env): "smoke" (default) skips the @pytest.mark.slow
# interpret-mode parity tests and finishes in a few minutes — the
# pre-push / per-commit tier; "full" runs the entire suite (~15 min) —
# the nightly/merge tier.
#
# Lint stage ("lint" job marker): smoke runs stencil-lint + ruff only
# (seconds); full also runs mypy. ruff/mypy are optional dev deps
# (pyproject.toml [project.optional-dependencies].lint) — absent, they
# are skipped with a notice; stencil-lint is part of the tree and
# always gates.
#
# Triggers that invoke this script:
#   * .github/workflows/ci.yml  — push/PR (smoke) + nightly cron (full)
#   * scripts/install_hooks.sh  — local git pre-push hook (smoke)
#   * manual: CI_TIER=full bash ci/run_ci.sh
set -euo pipefail
cd "$(dirname "$0")/.."
TIER="${CI_TIER:-smoke}"

echo "== 1/13 lint (stencil-lint + ruff; tier=$TIER) =="
# stencil-lint: all thirteen static checkers — halo-radius footprint,
# DMA discipline, ppermute sanity, HLO collective-permute-only
# lowering, analytic-vs-HLO byte cross-check, the Pallas VMEM/tiling
# audit, the dataflow trio (donation aliasing, host-transfer hygiene,
# recompile-hazard fingerprints), the prescriptive block-shape tiling
# gate (every Pallas kernel at 256^3/512^3-per-device shapes against
# the PHYSICAL VMEM budget — trace-only, no TPU), the link
# observatory's traffic-matrix-vs-HLO exactness gate, the RDMA
# schedule certifier (happens-before under k-fold replay), and the
# precision certifier (dtype-flow proofs gating low-precision wire
# formats)
# (python -m stencil_tpu.analysis, see README "Static analysis").
# The hlo/costmodel byte checks capability-gate themselves on the
# image's JAX (StableHLO lowering support is probed; Pallas targets
# skip off-TPU with a note in the report) — no env detection needed
# here. Exits nonzero on findings; the JSON report is the CI artifact
# (archived to $CI_ARTIFACT_DIR when a trigger provides one).
# capture the exit code so the report is archived even (especially)
# when the lint stage fails — red CI with no artifact helps no one
lint_rc=0
python -m stencil_tpu.analysis --json stencil_lint_report.json \
  || lint_rc=$?
if [ -n "${CI_ARTIFACT_DIR:-}" ] && [ -f stencil_lint_report.json ]; then
  mkdir -p "$CI_ARTIFACT_DIR"
  cp stencil_lint_report.json "$CI_ARTIFACT_DIR/"
fi
if [ "$lint_rc" -ne 0 ]; then
  echo "stencil-lint failed (exit $lint_rc)"
  exit "$lint_rc"
fi
# the prescriptive tiling PLAN report (ranked legal block shapes /
# named binding constraints for every registered Pallas kernel at the
# production per-device shapes) — a CI artifact for real-TPU runs to
# pick their shapes from; the audit itself already gated above
python -m stencil_tpu.analysis --plan-tiling 'analysis.tiling.*' \
  --json stencil_tiling_plans.json > /dev/null
if [ -n "${CI_ARTIFACT_DIR:-}" ] && [ -f stencil_tiling_plans.json ]; then
  cp stencil_tiling_plans.json "$CI_ARTIFACT_DIR/"
fi
# the RDMA schedule certificates (analysis/schedule.py): the per-kernel
# happens-before verdicts megastep's fusion gate consumes. Archived
# next to the tiling plans; then the fused⇒certified invariant — every
# registry target megastep fuses (fused_by_megastep) MUST hold a
# replay_safe certificate this run, and at least one such target must
# exist (a deregistered fused target would otherwise pass vacuously).
python -m stencil_tpu.analysis -q --only 'analysis.schedule.*' \
  --json stencil_schedule_certificates.json > /dev/null
if [ -n "${CI_ARTIFACT_DIR:-}" ] && \
   [ -f stencil_schedule_certificates.json ]; then
  cp stencil_schedule_certificates.json "$CI_ARTIFACT_DIR/"
fi
python - stencil_schedule_certificates.json <<'EOF'
import json
import sys
d = json.load(open(sys.argv[1]))
fused = {k: v for k, v in d["metrics"].items()
         if k.startswith("schedule:") and v.get("fused_by_megastep")}
assert fused, "no fused-by-megastep schedule targets registered"
bad = [k for k, v in fused.items() if not v.get("replay_safe")]
assert not bad, \
    f"megastep fuses UNCERTIFIED RDMA schedules: {bad} — every fused " \
    f"kernel must hold a replay_safe certificate (analysis/schedule.py)"
print(f"schedule certificates OK: {len(fused)} fused target(s), all "
      f"replay_safe")
EOF
# the precision certificates (analysis/precision.py): the per-target
# dtype-flow verdicts the wire-format gate consumes. Archived next to
# the schedule certificates; then the realized⇒certified invariant —
# every declared-narrowing wire target in the registry MUST hold a
# safe certificate with zero silent converts this run (and at least
# one such target must exist, or dropping the bf16 registry entries
# would pass vacuously), and every target of checker 13 must certify
# safe — the same certificates make_exchange's realize()-time gate
# re-derives before it lets a narrow wire ship.
python -m stencil_tpu.analysis -q --only precision \
  --json precision_certificates.json > /dev/null
if [ -n "${CI_ARTIFACT_DIR:-}" ] && [ -f precision_certificates.json ]
then
  cp precision_certificates.json "$CI_ARTIFACT_DIR/"
fi
python - precision_certificates.json <<'EOF'
import json
import sys
d = json.load(open(sys.argv[1]))
certs = {k: v for k, v in d["metrics"].items()
         if k.startswith("precision:")}
assert len(certs) >= 13, f"precision coverage shrank: {sorted(certs)}"
unsafe = [k for k, v in certs.items() if not v.get("safe")]
assert not unsafe, \
    f"UNCERTIFIED precision targets: {unsafe} — every registered " \
    f"entry point must hold a safe PrecisionCertificate " \
    f"(analysis/precision.py)"
leaky = [k for k, v in certs.items() if v.get("silent_converts")]
assert not leaky, f"silent converts in shipped paths: {leaky}"
wired = {k: v for k, v in certs.items() if any(
    rec.get("declared") not in (None, "f32")
    for rec in v.get("wire_dtypes", {}).values())}
assert wired, "no declared-narrowing wire targets registered"
for k, v in wired.items():
    assert v["max_rel_error_bound"] > 0, (k, v)
# the irredundant wire layout must hold its own safe certificates —
# the layout reroutes every halo byte through the packed-box pack/
# unpack path, and dropping its registry entries would let a dtype
# regression in that path ship unproven
irr = [k for k, v in certs.items()
       if "layout=irredundant" in k and v.get("safe")]
assert irr, "no safe irredundant-layout precision certificate " \
    "registered (make_exchange[...,layout=irredundant])"
fp8 = [k for k, v in certs.items() if "wire=e4m3" in k and v.get("safe")]
assert fp8, "no safe fp8 wire certificate registered"
print(f"precision certificates OK: {len(certs)} target(s) all safe, "
      f"{len(wired)} narrow-wire declaration(s) certified, "
      f"{len(irr)} irredundant-layout, {len(fp8)} fp8")
EOF
# the pack-layout report (parallel/packing.py): slab-vs-irredundant
# modeled wire bytes for the canonical exchange configs — the numbers
# the registry's CostModel targets just pinned HLO-exactly above,
# archived standalone next to the precision certificates so TPU runs
# can read the expected savings without re-deriving the model
python - > pack_layout_report.json <<'EOF'
import json
from stencil_tpu.parallel.packing import pack_layout_report
rep = pack_layout_report()
assert rep and all(r["irredundant_bytes"] < r["slab_bytes"]
                   for r in rep.values()), rep
json.dump(rep, __import__("sys").stdout, indent=1)
EOF
if [ -n "${CI_ARTIFACT_DIR:-}" ] && [ -f pack_layout_report.json ]; then
  cp pack_layout_report.json "$CI_ARTIFACT_DIR/"
fi
# the link observatory artifact: the modeled per-link traffic matrix
# (whose per-method totals the linkmap checker just pinned HLO-exactly
# above) plus the placement-quality report — both the QAP hill-climb
# AND the placement make_placement(mode="auto") actually DEPLOYS (the
# new default: QAP on non-uniform fabrics, trivial on uniform ones)
# must not lose to trivial placement on any registered mesh (ROADMAP
# item 3's gate, exit nonzero on failure)
python -m stencil_tpu.observatory linkmap --placement-report \
  --json stencil_linkmap.json > /dev/null
if [ -n "${CI_ARTIFACT_DIR:-}" ] && [ -f stencil_linkmap.json ]; then
  cp stencil_linkmap.json "$CI_ARTIFACT_DIR/"
fi
# registry-count ratchet: audit coverage may only grow. A refactor
# that drops targets (deregisters an entry point, deletes a checker
# block) must bump ci/registry_floor.txt EXPLICITLY in review — it
# cannot shrink the gate silently.
python - stencil_lint_report.json ci/registry_floor.txt <<'EOF'
import json
import sys
n = json.load(open(sys.argv[1]))["counts"]["targets"]
floor = int(open(sys.argv[2]).read().split()[0])
assert n >= floor, \
    f"registry shrank: {n} targets < committed floor {floor} " \
    f"(ci/registry_floor.txt) — audit coverage silently dropped"
print(f"registry ratchet OK: {n} targets >= committed floor {floor}")
EOF
if python -c "import ruff" 2>/dev/null; then
  python -m ruff check stencil_tpu/
elif command -v ruff >/dev/null; then
  ruff check stencil_tpu/
else
  echo "-- ruff not installed; skipping (pip install .[lint] to enable)"
fi
if [ "$TIER" = "full" ]; then
  if python -c "import mypy" 2>/dev/null; then
    python -m mypy stencil_tpu/
  elif command -v mypy >/dev/null; then
    mypy stencil_tpu/
  else
    echo "-- mypy not installed; skipping (pip install .[lint] to enable)"
  fi
fi

echo "== 2/13 native build =="
bash ci/build.sh

echo "== 3/13 unit tests, tier=$TIER (8-device virtual CPU mesh) =="
# The full tier is dominated by interpret-mode Pallas parity tests
# (CPU-bound, independent): fan them out with pytest-xdist when the
# machine has cores to spare. Each worker process builds its own
# 8-virtual-device CPU mesh (conftest env), so workers don't interact.
NP=$(nproc 2>/dev/null || echo 1)
XDIST=()
if [ "$NP" -ge 4 ] && python -c "import xdist" 2>/dev/null; then
  XDIST=(-n "$((NP / 2))")
fi
if [ "$TIER" = "full" ]; then
  python -m pytest tests/ -q --maxfail=1 "${XDIST[@]+"${XDIST[@]}"}"
else
  python -m pytest tests/ -q --maxfail=1 -m "not slow"
fi

echo "== 4/13 app smoke runs =="
smoke() { echo "-- $*"; python "$@" > /dev/null; }
( cd apps
  smoke jacobi3d.py --x 8 --y 8 --z 8 --iters 2 --batch 1 --fake-cpu 8
  smoke jacobi3d.py --x 8 --y 8 --z 8 --iters 2 --batch 1 --fake-cpu 8 \
        --packed
  smoke jacobi3d.py --x 8 --y 8 --z 8 --iters 2 --batch 1 --fake-cpu 8 \
        --fake-slices 2 --dcn-axis z
  smoke astaroth.py --nx 8 --ny 8 --nz 8 --iters 1 --fake-cpu 8
  smoke astaroth.py --nx 8 --ny 8 --nz 8 --iters 1 --fake-cpu 4 \
        --kernel halo --overlap
  smoke bench_exchange.py --x 8 --y 8 --z 8 --iters 2 --fake-cpu 8
  smoke machine_info.py --fake-cpu 8
  smoke bench_qap.py --sizes 4 6
)

echo "== 5/13 bench smoke: temporal blocking + autotuned plan =="
# communication-avoiding temporal blocking must not regress steps/s of
# the REAL blocked hot path (Jacobi3D's fused run loop, redundant ring
# compute included) on the fake CPU mesh; the amortized byte model
# (cross-checked against HLO by stencil-lint's costmodel checker) is
# archived next to the measured numbers. --autotune additionally races
# the MEASURED plan against Method.Default on the same loop. The JSON
# pins the exchange-rounds-per-step 4x cut and both steps/s
# comparisons; it is written to a scratch path (the committed
# BENCH_pr4.json records the PR-time numbers and must not churn on
# every CI run) and archived to $CI_ARTIFACT_DIR when a trigger
# provides one.
BENCH_JSON="$(mktemp -t BENCH_pr4.XXXXXX.json)"
BENCH_METRICS="$(mktemp -t BENCH_metrics.XXXXXX.json)"
TUNE_CACHE="$(mktemp -t tune_cache.XXXXXX.json)"; rm -f "$TUNE_CACHE"
# scratch observatory ledger: the bench (here) and pic (stage 8) smoke
# runs append their versioned records to it; the observatory stage (9)
# validates it, gates it, and proves a synthetic regression fails
OBS_LEDGER="$(mktemp -t obs_ledger.XXXXXX.jsonl)"; rm -f "$OBS_LEDGER"
# the exchange-every sweep carries the per-axis asymmetric leg
# (z=4,y=1,x=1: deep temporal blocking on z only — the DCN-crossing
# axis on hierarchical fabrics — while x/y refresh every step); its
# record must land in the ledger with the config.depths stamp the
# observatory keys asymmetric trajectories by
( cd apps
  STENCIL_BENCH_LEDGER="$OBS_LEDGER" \
  python bench_exchange.py --x 8 --y 8 --z 8 --iters 20 --fake-cpu 8 \
        --exchange-every 1,4,z=4,y=1,x=1 --autotune \
        --tune-cache "$TUNE_CACHE" \
        --fuse-segments --check-every 8 \
        --wire-layout slab,irredundant \
        --json-out "$BENCH_JSON" --metrics-json "$BENCH_METRICS" )
BENCH_JSON="$BENCH_JSON" BENCH_METRICS="$BENCH_METRICS" \
OBS_LEDGER="$OBS_LEDGER" python - <<'EOF'
import json
import os
d = json.load(open(os.environ["BENCH_JSON"]))
# telemetry parity: the metrics snapshot records the SAME steps/s the
# BENCH json pins — one number, two artifacts, no drift
from stencil_tpu.telemetry import snapshot_value
snap = json.load(open(os.environ["BENCH_METRICS"]))
for cfg in d["configs"]:
    s = str(cfg["exchange_every"])
    got = snapshot_value(snap, "stencil_bench_steps_per_s",
                         exchange_every=s)
    assert got == cfg["steps_per_s"], (s, got, cfg["steps_per_s"])
rounds = d["rounds_per_step_ratio"]
speed = d["steps_per_s_ratio"]
assert abs(rounds["4"] - 0.25) < 1e-9, rounds
# steps/s of the blocked loop must not regress beyond run-to-run noise
assert speed["4"] > 0.8, speed
# the MEASURED tuned plan must not lose to the static default beyond
# noise (the committed BENCH_pr4.json pins the PR-time tuned >= default)
at = d["autotune"]
assert at["plan"]["provenance"] in ("tuned", "cached"), at["plan"]
assert at["tuned_over_default"] > 0.8, at
# megastep gate: ONE fused dispatch per check_every steps must beat the
# per-step dispatch loop >= 1.5x at the dispatch-bound smoke size
# (committed BENCH_pr8.json pins the PR-time numbers; this re-measures)
fz = d["fused"]
assert fz["fused_over_stepwise"] >= 1.5, fz
# the newly fused carry contracts' race legs must exist and land
# their measured records (their ledger trajectories were empty before
# the segment compiler; stage 9 gates the trajectories). On the
# fake-CPU mesh these two paths are NOT dispatch-bound — PIC's step
# is ~100s of tiny XLA ops and the temporal path's minimal legal
# shard (deep radius 6 on an 8-point axis) balloons the redundant
# deep-window compute, neither of which fusion can remove — so the
# >= 1.5 dispatch gate stays on the Jacobi leg where the dispatch
# signal is clean; the carry-contract legs gate presence + positive
# measurements here and their own regression trajectory in stage 9
# (the 1.5x expectation for them is a real-TPU figure, where device
# steps are ~us against ~100us host dispatches).
for leg in ("pic", "astaroth_temporal"):
    sub = d["fused"][leg]
    assert sub["fused_steps_per_s"] > 0, (leg, sub)
    assert sub["stepwise_steps_per_s"] > 0, (leg, sub)
    assert sub["steps"] >= d["fused"]["check_every"], (leg, sub)
ck = str(fz["check_every"])
for mode, key in (("fused", "fused_steps_per_s"),
                  ("stepwise", "stepwise_steps_per_s")):
    got = snapshot_value(snap, "stencil_bench_fused_steps_per_s",
                         mode=mode, check_every=ck)
    assert got == fz[key], (mode, got, fz[key])
# link observatory parity: the two per-link gauges record the SAME
# figures the JSON's link_classes block pins — and the classes must
# actually partition the traffic (shares sum to 1)
lc = d.get("link_classes")
assert lc, "bench payload carries no link_classes block"
assert abs(sum(v["share"] for v in lc.values()) - 1.0) < 1e-9, lc
for key, v in lc.items():
    axis, klass = key.split("/")
    got = snapshot_value(snap, "stencil_link_bytes_per_step",
                         axis=axis, link_class=klass)
    assert got == v["bytes_per_step"], (key, got, v)
    got = snapshot_value(snap, "stencil_link_utilization_ratio",
                         axis=axis, link_class=klass)
    assert got == v["utilization"], (key, got, v)
    assert 0 < v["utilization"] < 1, (key, v)
# wire-layout race: the irredundant leg must move strictly fewer
# modeled bytes than the slab baseline (the static analyzer pinned the
# exact figures against HLO in stage 1; here the measured race must
# exist and agree with the model's direction), and the ledger record
# this run appended must carry the layout provenance stamp
assert d["wire_layout"] == "slab", d["wire_layout"]
race = d["wire_layout_race"]["races"]["irredundant"]
assert 0 < race["bytes_ratio"] < 1, race
assert race["steps_per_s"] > 0, race
# asymmetric-depth leg: the z=4,y=1,x=1 config must exist in the
# sweep with its per-axis depths surfaced, and its ledger record must
# carry the config.depths stamp (stamped post-fingerprint so uniform
# trajectories never fork; the observatory groups asym runs by it)
asym = [c for c in d["configs"] if c["exchange_every"] == "1.1.4"]
assert asym and asym[0].get("depths") == [1, 1, 4], d["configs"]
assert asym[0]["steps_per_s"] > 0, asym
led = [json.loads(l) for l in open(os.environ["OBS_LEDGER"])
       if l.strip()]
mine = [r for r in led if r.get("bench") == "bench_exchange"]
assert mine and mine[-1]["config"].get("wire_layout") == "slab", \
    "ledger record missing config.wire_layout stamp"
led_asym = [r for r in mine
            if r["config"].get("exchange_every") == "1.1.4"]
assert led_asym and led_asym[-1]["config"].get("depths") == [1, 1, 4], \
    "asymmetric-depth ledger record missing config.depths stamp"
print(f"bench smoke OK: rounds/step x{1/rounds['4']:.0f} fewer, "
      f"steps/s ratio {speed['4']:.2f}, tuned/default "
      f"x{at['tuned_over_default']:.2f} "
      f"({at['plan']['config']['method']}"
      f"[s={at['plan']['config']['exchange_every']}]), "
      f"megastep fused/stepwise x{fz['fused_over_stepwise']:.2f} "
      f"[k={ck}]")
EOF
if [ -n "${CI_ARTIFACT_DIR:-}" ]; then
  mkdir -p "$CI_ARTIFACT_DIR"
  cp "$BENCH_JSON" "$CI_ARTIFACT_DIR/BENCH_pr4.json"
  cp "$BENCH_JSON" "$CI_ARTIFACT_DIR/BENCH_pr8.json"
  cp "$BENCH_METRICS" "$CI_ARTIFACT_DIR/bench_metrics.json"
  # the megastep ratio, archived standalone for trend dashboards
  python - "$BENCH_JSON" > "$CI_ARTIFACT_DIR/megastep_ratio.json" <<'EOF'
import json, sys
d = json.load(open(sys.argv[1]))
json.dump(d["fused"], sys.stdout, indent=1)
EOF
fi
rm -f "$BENCH_JSON" "$BENCH_METRICS" "$TUNE_CACHE"
# NOTE: "$OBS_LEDGER" survives into stages 8/9 (the observatory stage)

echo "== 6/13 exchange autotuner (fake timer: search/fit/plan/cache) =="
# the tuner's whole pipeline with deterministic fake measurements (no
# hardware dependence): first invocation tunes and writes the plan
# cache, the second MUST be a cache hit performing zero measurements.
# The plan JSON is the CI artifact.
TUNE_CACHE="$(mktemp -t tune_cache.XXXXXX.json)"; rm -f "$TUNE_CACHE"
PLAN1="$(mktemp -t tune_plan1.XXXXXX.json)"
PLAN2="$(mktemp -t tune_plan2.XXXXXX.json)"
python -m stencil_tpu.tune --x 64 --y 64 --z 64 --fields 2 --fake-cpu 8 \
  --fake-timer --cache "$TUNE_CACHE" --json "$PLAN1"
python -m stencil_tpu.tune --x 64 --y 64 --z 64 --fields 2 --fake-cpu 8 \
  --fake-timer --cache "$TUNE_CACHE" --json "$PLAN2"
PLAN1="$PLAN1" PLAN2="$PLAN2" python - <<'EOF'
import json
import os
p1 = json.load(open(os.environ["PLAN1"]))
p2 = json.load(open(os.environ["PLAN2"]))
assert p1["provenance"] == "tuned" and p1["measurements"] > 0, p1
assert p2["provenance"] == "cached" and p2["measurements"] == 0, p2
assert p1["config"] == p2["config"], (p1["config"], p2["config"])
print(f"autotune smoke OK: {p1['config']['method']}"
      f"[s={p1['config']['exchange_every']}] tuned with "
      f"{p1['measurements']} measurements; second run cache hit "
      f"with 0")
EOF
if [ -n "${CI_ARTIFACT_DIR:-}" ]; then
  mkdir -p "$CI_ARTIFACT_DIR"
  cp "$PLAN1" "$CI_ARTIFACT_DIR/tuned_plan.json"
fi
rm -f "$TUNE_CACHE" "$PLAN1" "$PLAN2"

echo "== 7/13 chaos smoke: resilient run loop under injected faults =="
# the Jacobi app under run_resilient (stencil_tpu/resilience) with a
# seeded fault plan: one NaN injection (must trip the health sentinel
# and roll back to the last good checkpoint) and one transient save
# IOError (must be retried with backoff, not kill the run). The run
# must COMPLETE all iterations with >= 1 rollback and >= 1 save retry
# recorded; the resilience event log JSON is the CI artifact.
# The fused dispatch runs under jax.transfer_guard("disallow") (the
# driver wires it; STENCIL_ALLOW_TRANSFERS=1 is the escape hatch) and
# under the recompile watchdog (STENCIL_ASSERT_SINGLE_COMPILE=1 set
# here): an implicit host transfer or a re-traced megastep inside the
# hot loop fails this stage loudly.
CHAOS_CKPT="$(mktemp -d -t chaos_ckpt.XXXXXX)"
CHAOS_EVENTS="$(mktemp -t chaos_events.XXXXXX.json)"
CHAOS_FLIGHT="$(mktemp -d -t chaos_flight.XXXXXX)"
( cd apps
  STENCIL_ASSERT_SINGLE_COMPILE=1 \
  STENCIL_FLIGHT_RECORDER_DIR="$CHAOS_FLIGHT" \
  python jacobi3d.py --x 8 --y 8 --z 8 --iters 12 --fake-cpu 8 \
        --resilient --fuse-segments --ckpt-dir "$CHAOS_CKPT" \
        --ckpt-every 4 --check-every 1 --chaos-nan 6 \
        --chaos-save-fail 4 --events-json "$CHAOS_EVENTS" )
CHAOS_EVENTS="$CHAOS_EVENTS" python - <<'EOF'
import json
import os
d = json.load(open(os.environ["CHAOS_EVENTS"]))
assert d["steps"] == 12, d
assert d["rollbacks"] >= 1, d
assert d["save_retries"] >= 1, d
assert not d["preempted"], d
# the run went through the FUSED megastep driver (a silent stepwise
# fallback now shows up as fused: false + a fused_decline event)
assert d["fused"] is True, d
kinds = [e["event"] for e in d["events"]]
assert "sentinel_tripped" in kinds and "restored" in kinds, kinds
print(f"chaos smoke OK: {d['steps']} steps completed with "
      f"{d['rollbacks']} rollback(s), {d['save_retries']} save "
      f"retr(ies), final config {d['final_config']}")
EOF
# the resilience report speaks the unified telemetry event schema
python -m stencil_tpu.telemetry validate-events "$CHAOS_EVENTS"
# flight recorder: the injected NaN trip must have produced a schema-
# valid black-box dump whose incident timeline contains the trip AND
# the rollback it resolved into (observatory/recorder.py)
CHAOS_DUMP="$(ls "$CHAOS_FLIGHT"/flight_*sentinel_trip*.json | head -1)"
python -m stencil_tpu.observatory validate "$CHAOS_DUMP"
CHAOS_DUMP="$CHAOS_DUMP" python - <<'EOF'
import os
from stencil_tpu.observatory import render_timeline
tl = render_timeline(os.environ["CHAOS_DUMP"])
assert "sentinel_tripped" in tl, tl
assert "restored" in tl, tl
print("chaos flight dump OK: timeline carries the trip + rollback "
      f"({len(tl.splitlines())} timeline rows)")
EOF
if [ -n "${CI_ARTIFACT_DIR:-}" ]; then
  mkdir -p "$CI_ARTIFACT_DIR"
  cp "$CHAOS_EVENTS" "$CI_ARTIFACT_DIR/chaos_events.json"
  cp "$CHAOS_DUMP" "$CI_ARTIFACT_DIR/chaos_flight_dump.json"
fi
rm -rf "$CHAOS_CKPT" "$CHAOS_EVENTS" "$CHAOS_FLIGHT"

echo "== 8/13 pic smoke: particle migration + ParticleLoss chaos =="
# the particle-in-cell workload (stencil_tpu/models/pic.py): a short
# run proves the dynamic migration path end-to-end (CSV line, zero
# overflow, charge conserved), then a chaos run injects a ParticleLoss
# fault (NaN'd particle records) that must trip the sentinel via the
# particle lanes, roll back to a checkpoint carrying the lanes as
# extras, and still complete every step. The event log is the CI
# artifact.
PIC_CKPT="$(mktemp -d -t pic_ckpt.XXXXXX)"
PIC_EVENTS="$(mktemp -t pic_events.XXXXXX.json)"
PIC_BENCH="$(mktemp -t pic_bench.XXXXXX.json)"
PIC_METRICS="$(mktemp -t pic_metrics.XXXXXX.json)"
( cd apps
  STENCIL_BENCH_LEDGER="$OBS_LEDGER" \
  python pic.py --x 8 --y 8 --z 8 --particles 64 --iters 4 --batch 2 \
        --fake-cpu 8 --deposition ngp --f64 \
        --json-out "$PIC_BENCH" --metrics-json "$PIC_METRICS" \
        > /dev/null
  # second fingerprint-identical measured run: gives the observatory
  # ledger a genuine same-(fingerprint, bench) TRAJECTORY (two
  # records, one group) so stage 9's gate actually compares something
  # — its --min-groups floor pins that this never silently regresses
  # to a vacuous 0-group pass
  STENCIL_BENCH_LEDGER="$OBS_LEDGER" \
  python pic.py --x 8 --y 8 --z 8 --particles 64 --iters 4 --batch 2 \
        --fake-cpu 8 --deposition ngp --f64 \
        --json-out "$PIC_BENCH.2" > /dev/null
  rm -f "$PIC_BENCH.2"
  # chaos leg runs FUSED by default (the megastep driver is the
  # production path now): ParticleLoss must trip at the exact step
  # from the in-graph trace rows and recover bitwise
  python pic.py --x 8 --y 8 --z 8 --particles 64 --iters 6 --fake-cpu 8 \
        --resilient --fuse-segments --ckpt-dir "$PIC_CKPT" \
        --ckpt-every 2 --check-every 1 --chaos-particle-loss 3 \
        --events-json "$PIC_EVENTS" > /dev/null )
PIC_EVENTS="$PIC_EVENTS" PIC_BENCH="$PIC_BENCH" \
PIC_METRICS="$PIC_METRICS" python - <<'EOF'
import json
import os
b = json.load(open(os.environ["PIC_BENCH"]))
assert b["overflow"] == 0, b
assert b["total_charge"] == b["config"]["particles"], b
assert b["particle_steps_per_s"] > 0, b
# telemetry parity: the metrics snapshot records the SAME figures the
# pic JSON pins — one number, two artifacts, no drift (the same gate
# stage 5 applies to stencil_bench_steps_per_s{exchange_every})
from stencil_tpu.telemetry import snapshot_value
snap = json.load(open(os.environ["PIC_METRICS"]))
dep = b["config"]["deposition"]
got = snapshot_value(snap, "stencil_bench_particle_steps_per_s",
                     deposition=dep)
assert got == b["particle_steps_per_s"], (got, b)
got = snapshot_value(snap, "stencil_bench_migration_bytes_per_shard",
                     deposition=dep)
assert got == b["migration_bytes_per_shard"], (got, b)
# the megastep race (pic.py --fuse-segments, default on): the fused
# dispatch mode must produce a positive measured ratio — its record
# lands the pic.megastep ledger trajectory stage 9 gates (the smoke
# box is not dispatch-bound for PIC's op-count-heavy step, so the
# race is a trajectory signal here, not a 1.5x gate; see stage 5)
fz = b.get("fused")
assert fz, "pic payload carries no fused race block"
assert fz["fused_steps_per_s"] > 0, fz
assert fz["stepwise_steps_per_s"] > 0, fz
d = json.load(open(os.environ["PIC_EVENTS"]))
assert d["steps"] == 6, d
assert d["rollbacks"] >= 1, d
# the chaos run went through the FUSED driver (megastep mode)
assert d["fused"] is True, d
kinds = [e["event"] for e in d["events"]]
assert "fault_particle_loss" in kinds, kinds
assert "sentinel_tripped" in kinds and "restored" in kinds, kinds
trip = [e for e in d["events"] if e["event"] == "sentinel_tripped"][0]
assert trip["step"] == 3, trip
print(f"pic smoke OK: {b['particle_steps_per_s']:.0f} particle "
      f"steps/s, charge conserved, fused chaos driver tripped "
      f"ParticleLoss at step 3 + {d['rollbacks']} rollback(s), "
      f"{d['steps']}/6 steps, megastep race "
      f"x{fz['fused_over_stepwise']:.2f}")
EOF
python -m stencil_tpu.telemetry validate-events "$PIC_EVENTS"
if [ -n "${CI_ARTIFACT_DIR:-}" ]; then
  mkdir -p "$CI_ARTIFACT_DIR"
  cp "$PIC_EVENTS" "$CI_ARTIFACT_DIR/pic_events.json"
  cp "$PIC_BENCH" "$CI_ARTIFACT_DIR/BENCH_pr10.json"
  cp "$PIC_METRICS" "$CI_ARTIFACT_DIR/pic_metrics.json"
  # the pic megastep ratio, archived standalone next to
  # megastep_ratio.json (stage 5) for trend dashboards
  python - "$PIC_BENCH" > "$CI_ARTIFACT_DIR/pic_megastep_ratio.json" <<'EOF'
import json, sys
d = json.load(open(sys.argv[1]))
json.dump(d["fused"], sys.stdout, indent=1)
EOF
fi
rm -rf "$PIC_CKPT" "$PIC_EVENTS" "$PIC_BENCH" "$PIC_METRICS"

echo "== 9/13 observatory: bench ledger validate/gate + backfill =="
# the bench trajectory ledger (stencil_tpu/observatory/ledger.py): the
# bench (stage 5) and pic (stage 8) smoke runs appended their records
# to the scratch ledger — validate it, prove the regression gate
# passes on the real run, prove an injected synthetic same-fingerprint
# steps/s regression exits NONZERO, and backfill-convert the committed
# legacy BENCH_*.json history (validated + diffed) the way the
# committed bench/ledger.jsonl was seeded.
python -m stencil_tpu.observatory validate "$OBS_LEDGER"
# --min-groups 1: the smoke runs above MUST have produced at least one
# comparable (fingerprint, bench) group — an empty/group-less ledger
# exits 0 with a "no measured trajectory" note in dev, but in CI that
# would be a vacuous pass (benches stopped appending), so the
# committed coverage floor turns it into a loud failure; the verdict
# JSON (groups_checked stamped) is archived with the stage artifacts
OBS_GATE_JSON="$(mktemp -t obs_gate.XXXXXX.json)"
# threshold 0.8: back-to-back 8^3 smoke runs on a shared CI box are
# noisy (compile/thread scheduling) — the gate exists to catch the
# order-of-magnitude class of regression, which the synthetic 10x
# check below proves it does at this threshold
# --min-groups 2: the pic smoke's double run now creates TWO
# comparable trajectory groups — the pic bench itself AND the
# pic.megastep fused/stepwise race (the carry-contract paths' ledger
# trajectories, empty before the segment compiler, are gated here)
python -m stencil_tpu.observatory gate "$OBS_LEDGER" --threshold 0.8 \
  --min-groups 2 --json "$OBS_GATE_JSON"
OBS_BAD="$(mktemp -t obs_bad.XXXXXX.jsonl)"
cp "$OBS_LEDGER" "$OBS_BAD"
OBS_LEDGER="$OBS_LEDGER" OBS_BAD="$OBS_BAD" python - <<'EOF'
import json
import os
# synthetic regression: clone the newest record with steps/s cut 10x —
# the same-(fingerprint, bench) gate must catch it
with open(os.environ["OBS_LEDGER"]) as f:
    rec = json.loads(f.read().splitlines()[-1])
rec["metrics"]["steps_per_s"] /= 10.0
rec["created"] += 1.0
with open(os.environ["OBS_BAD"], "a") as f:
    f.write(json.dumps(rec) + "\n")
EOF
if python -m stencil_tpu.observatory gate "$OBS_BAD" --threshold 0.8; then
  echo "observatory gate FAILED to catch the synthetic regression"
  exit 1
else
  echo "observatory gate correctly rejects the synthetic regression"
fi
OBS_LEGACY="$(mktemp -t obs_legacy.XXXXXX.jsonl)"; rm -f "$OBS_LEGACY"
python -m stencil_tpu.observatory backfill --out "$OBS_LEGACY" \
  BENCH_pr3.json BENCH_pr4.json BENCH_pr8.json BENCH_pr10.json
python -m stencil_tpu.observatory validate "$OBS_LEGACY"
# the live smoke records and their backfilled ancestors share one
# converter, so the bench_exchange trajectory diffs across them. A
# group-less ledger now exits 0 with a note, so grep for an actual
# metric row — a converter regression that forked the trajectory
# groups must fail HERE, not print a polite note and pass
OBS_DIFF_OUT="$(python -m stencil_tpu.observatory diff "$OBS_LEGACY" \
  --bench bench_exchange)"
echo "$OBS_DIFF_OUT"
if ! grep -q "steps_per_s" <<< "$OBS_DIFF_OUT"; then
  echo "observatory diff found no comparable bench_exchange" \
       "trajectory — the backfill converter forked the groups"
  exit 1
fi
# the committed seed ledger stays in sync with the backfill converter
python -m stencil_tpu.observatory validate bench/ledger.jsonl
if [ -n "${CI_ARTIFACT_DIR:-}" ]; then
  mkdir -p "$CI_ARTIFACT_DIR"
  cp "$OBS_LEDGER" "$CI_ARTIFACT_DIR/bench_ledger.jsonl"
  cp "$OBS_LEGACY" "$CI_ARTIFACT_DIR/bench_ledger_legacy.jsonl"
  cp "$OBS_GATE_JSON" "$CI_ARTIFACT_DIR/bench_ledger_gate.json"
fi
rm -f "$OBS_LEDGER" "$OBS_BAD" "$OBS_LEGACY" "$OBS_GATE_JSON"

echo "== 10/13 service smoke: concurrent multi-tenant ensemble campaigns =="
# the campaign service (stencil_tpu/serving) on the fake CPU mesh:
# three concurrent fake tenants share one problem fingerprint and ride
# ONE batched ensemble dispatch stream (tenant0 gets a chaos NaN that
# must roll back ONLY its campaign), then a fingerprint-identical
# second wave must hit the engine cache (zero recompiles) and a second
# PROCESS on the same tune cache must hit the plan cache (zero tuner
# measurements). The event log JSON is the CI artifact.
SERVE_ROOT="$(mktemp -d -t serve_root.XXXXXX)"
SERVE_CACHE="$(mktemp -t serve_cache.XXXXXX.json)"; rm -f "$SERVE_CACHE"
SERVE_EVENTS1="$(mktemp -t serve_events1.XXXXXX.json)"
SERVE_EVENTS2="$(mktemp -t serve_events2.XXXXXX.json)"
( cd apps
  python serve.py --tenants 3 --steps 6 --width 8 --fake-cpu 8 \
        --chaos-nan 3 --fake-timer --tune-cache "$SERVE_CACHE" \
        --root "$SERVE_ROOT/run1" --events-json "$SERVE_EVENTS1"
  python serve.py --tenants 1 --second-wave 0 --steps 4 --width 8 \
        --fake-cpu 8 --fake-timer --tune-cache "$SERVE_CACHE" \
        --root "$SERVE_ROOT/run2" --events-json "$SERVE_EVENTS2" )
SERVE_EVENTS1="$SERVE_EVENTS1" SERVE_EVENTS2="$SERVE_EVENTS2" \
python - <<'EOF'
import json
import os
d1 = json.load(open(os.environ["SERVE_EVENTS1"]))
d2 = json.load(open(os.environ["SERVE_EVENTS2"]))
s1, s2 = d1["stats"], d2["stats"]
# run 1: 3 concurrent tenants + 1 warm-path request, all complete; the
# chaos NaN rolled back only its campaign
assert s1["completed"] == 4 and s1["failed"] == 0, s1
assert s1["rollbacks"] >= 1, s1
batches = [e for e in d1["events"] if e["event"] == "batch_started"]
assert batches[0]["compiled"] and batches[0]["measurements"] > 0, batches
# the fingerprint-identical second wave: zero recompiles, zero
# measurements (engine cache + in-process plan reuse)
assert not batches[-1]["compiled"], batches
assert batches[-1]["measurements"] == 0, batches
trips = [e for e in d1["events"] if e["event"] == "sentinel_tripped"]
assert trips and all(e["tenant"] == "tenant0" for e in trips), trips
done = {e["tenant"] for e in d1["events"]
        if e["event"] == "campaign_completed"}
assert done == {"tenant0", "tenant1", "tenant2", "tenant3"}, done
# run 2 (fresh process, same tune cache): plan-cache hit, zero
# tuner measurements
assert s2["completed"] == 1 and s2["plan_cache_hits"] == 1, s2
assert s2["tuner_measurements"] == 0, s2
print(f"service smoke OK: {s1['completed']}+{s2['completed']} campaigns"
      f" completed, {s1['rollbacks']} member-isolated rollback(s), "
      f"warm path compiled=False/measurements=0, second process "
      f"plan-cache hit with 0 measurements")
EOF
if [ -n "${CI_ARTIFACT_DIR:-}" ]; then
  mkdir -p "$CI_ARTIFACT_DIR"
  cp "$SERVE_EVENTS1" "$CI_ARTIFACT_DIR/serve_events.json"
fi
rm -rf "$SERVE_ROOT" "$SERVE_CACHE" "$SERVE_EVENTS1" "$SERVE_EVENTS2"

echo "== 11/13 telemetry: metrics surface, span trace, unified events =="
# the observability acceptance gate (stencil_tpu/telemetry): a first
# service process (cold: tunes once) and a second process on the same
# plan cache (warm) each export their metrics snapshot, span trace,
# and unified event log. The warm-path invariants are asserted from
# the EXPORTED metrics — recompiles_total == 0 (the in-process warm
# wave re-used the cached engine) and, in the second process,
# tuner_measurements_total == 0 with plan_cache_hits_total == 1 — not
# from internal fields. The Perfetto trace and both event logs are
# schema-validated by the telemetry CLI and archived.
TM_ROOT="$(mktemp -d -t tm_root.XXXXXX)"
TM_CACHE="$(mktemp -t tm_cache.XXXXXX.json)"; rm -f "$TM_CACHE"
TM_EVENTS1="$(mktemp -t tm_events1.XXXXXX.json)"
TM_EVENTS2="$(mktemp -t tm_events2.XXXXXX.json)"
TM_METRICS1="$(mktemp -t tm_metrics1.XXXXXX.json)"
TM_METRICS2="$(mktemp -t tm_metrics2.XXXXXX.json)"
TM_TRACE="$(mktemp -t tm_trace.XXXXXX.json)"
( cd apps
  python serve.py --tenants 2 --steps 4 --width 8 --fake-cpu 8 \
        --fake-timer --tune-cache "$TM_CACHE" --root "$TM_ROOT/run1" \
        --events-json "$TM_EVENTS1" --metrics-json "$TM_METRICS1" \
        --trace-json "$TM_TRACE"
  python serve.py --tenants 1 --second-wave 0 --steps 4 --width 8 \
        --fake-cpu 8 --fake-timer --tune-cache "$TM_CACHE" \
        --root "$TM_ROOT/run2" --events-json "$TM_EVENTS2" \
        --metrics-json "$TM_METRICS2" )
# the trace loads (Perfetto format) and both event logs are schema-valid
python -m stencil_tpu.telemetry validate-trace "$TM_TRACE"
python -m stencil_tpu.telemetry validate-events "$TM_EVENTS1"
python -m stencil_tpu.telemetry validate-events "$TM_EVENTS2"
TM_METRICS1="$TM_METRICS1" TM_METRICS2="$TM_METRICS2" python - <<'EOF'
import json
import os
from stencil_tpu.telemetry import snapshot_value as v
m1 = json.load(open(os.environ["TM_METRICS1"]))
m2 = json.load(open(os.environ["TM_METRICS2"]))
# the "== 0" gates below must test series that EXIST in the export
# (counters are seeded to 0 at registration) — a renamed or deleted
# metric must fail here, not read back as an absent-series 0.0
for snap, which in ((m1, "cold"), (m2, "warm")):
    for n in ("stencil_service_recompiles_total",
              "stencil_service_tuner_measurements_total"):
        assert snap["metrics"][n]["samples"], f"{n} absent ({which})"
# run 1 (cold + in-process warm wave): one compile, zero REcompiles,
# the warm wave hit the engine cache; the tuner measured exactly once
assert v(m1, "stencil_service_compiles_total") == 1, m1
assert v(m1, "stencil_service_recompiles_total") == 0, m1
assert v(m1, "stencil_service_engine_cache_hits_total") >= 1, m1
assert v(m1, "stencil_service_tuner_measurements_total") > 0, m1
assert v(m1, "stencil_service_campaigns_total",
         tenant="tenant0", outcome="completed") == 1, m1
# run 2 (fresh process, same plan cache): the warm path verbatim —
# zero recompiles, zero tuner measurements, one plan-cache hit
assert v(m2, "stencil_service_recompiles_total") == 0, m2
assert v(m2, "stencil_service_tuner_measurements_total") == 0, m2
assert v(m2, "stencil_service_plan_cache_hits_total") == 1, m2
assert v(m2, "stencil_service_member_steps_total") >= 4, m2
print("telemetry smoke OK: warm path proven from exported metrics "
      "(recompiles=0, tuner_measurements=0, plan_cache_hits=1), "
      "trace + event logs schema-valid")
EOF
if [ -n "${CI_ARTIFACT_DIR:-}" ]; then
  mkdir -p "$CI_ARTIFACT_DIR"
  cp "$TM_METRICS1" "$CI_ARTIFACT_DIR/telemetry_metrics_cold.json"
  cp "$TM_METRICS2" "$CI_ARTIFACT_DIR/telemetry_metrics_warm.json"
  cp "$TM_TRACE" "$CI_ARTIFACT_DIR/telemetry_trace.json"
  cp "$TM_EVENTS1" "$CI_ARTIFACT_DIR/telemetry_events.json"
fi
rm -rf "$TM_ROOT" "$TM_CACHE" "$TM_EVENTS1" "$TM_EVENTS2" \
       "$TM_METRICS1" "$TM_METRICS2" "$TM_TRACE"

echo "== 12/13 fleet chaos smoke: replica kill + admission flood =="
# the zero-loss gate (ROADMAP item 4) proven from EXPORTED surfaces:
# a calm 3-replica / 4-tenant fleet establishes the reference digests,
# then a chaos fleet on the SAME plan cache kills the replica that
# rendezvous-owns tenant t0 mid-batch (member step 2, after that
# step's checkpoints landed) while a priority-0 admission flood
# hammers the front door. Gates: zero campaigns lost (every final
# field digest bitwise-equal to the calm run), recovered campaigns
# RESUMED from a checkpoint (not restarted), survivors'
# recompiles_total and tuner_measurements_total both 0 (shared plan
# cache + bounded engine cache), >= 1 request shed with a NAMED
# reason, the fleet event log schema-valid, and the dead replica's
# flight-recorder black box archived.
FLEET_ROOT="$(mktemp -d -t fleet_root.XXXXXX)"
FLEET_CACHE="$(mktemp -t fleet_cache.XXXXXX.json)"; rm -f "$FLEET_CACHE"
FLEET_CALM="$(mktemp -t fleet_calm.XXXXXX.json)"
FLEET_CHAOS="$(mktemp -t fleet_chaos.XXXXXX.json)"
FLEET_EVENTS="$(mktemp -t fleet_events.XXXXXX.json)"
FLEET_METRICS="$(mktemp -t fleet_metrics.XXXXXX.json)"
FLEET_FLIGHT="$(mktemp -d -t fleet_flight.XXXXXX)"
( cd apps
  python fleet.py --replicas 3 --tenants 4 --steps 6 --fake-cpu 8 \
        --fake-timer --tune-cache "$FLEET_CACHE" \
        --root "$FLEET_ROOT/calm" --results-json "$FLEET_CALM"
  python fleet.py --replicas 3 --tenants 4 --steps 6 --fake-cpu 8 \
        --fake-timer --tune-cache "$FLEET_CACHE" \
        --root "$FLEET_ROOT/chaos" --kill-owner-of t0 \
        --kill-at-step 2 --flood 6 --max-queue-depth 3 \
        --results-json "$FLEET_CHAOS" --events-json "$FLEET_EVENTS" \
        --metrics-json "$FLEET_METRICS" --flight-dir "$FLEET_FLIGHT" )
python -m stencil_tpu.telemetry validate-events "$FLEET_EVENTS"
[ -n "$(ls -A "$FLEET_FLIGHT")" ] \
  || { echo "FAIL: dead replica left no flight-recorder dump"; exit 1; }
FLEET_CALM="$FLEET_CALM" FLEET_CHAOS="$FLEET_CHAOS" \
FLEET_EVENTS="$FLEET_EVENTS" FLEET_METRICS="$FLEET_METRICS" \
python - <<'EOF'
import json
import os
from stencil_tpu.telemetry import snapshot_value as v
calm = json.load(open(os.environ["FLEET_CALM"]))
chaos = json.load(open(os.environ["FLEET_CHAOS"]))
ev = json.load(open(os.environ["FLEET_EVENTS"]))
met = json.load(open(os.environ["FLEET_METRICS"]))
# zero campaigns lost: every tenant finished, bitwise-equal to calm
assert set(chaos["campaigns"]) == set(calm["campaigns"]), chaos
for t, c in chaos["campaigns"].items():
    assert c["ok"], (t, c)
    assert c["digest"] == calm["campaigns"][t]["digest"], t
# the killed replica really died and its campaigns really RESUMED
killed = f"replica-{chaos['killed']}"
states = {n: r["state"] for n, r in chaos["replicas"].items()}
assert states[killed] == "dead", states
assert v(met, "stencil_fleet_replicas", state="dead") == 1, met
assert v(met, "stencil_fleet_replicas", state="active") == 2, met
assert v(met, "stencil_fleet_recovered_campaigns_total") >= 1, met
resumed = [c for c in chaos["campaigns"].values()
           if c.get("resumed_from") is not None]
assert resumed and all(c["resumed_from"] > 0 for c in resumed), chaos
# survivors: zero recompiles, zero tuner measurements — and the
# series EXIST in the export (seeded 0), not absent-series 0.0
for n, r in chaos["replicas"].items():
    if r["state"] != "active":
        continue
    assert r["recompiles"] == 0, (n, r)
    assert r["tuner_measurements"] == 0, (n, r)
    for m in ("stencil_service_recompiles_total",
              "stencil_service_tuner_measurements_total"):
        assert r["metrics"]["metrics"][m]["samples"], (n, m)
# the flood was shed LOUDLY: counter + named-reason events agree
shed = v(met, "stencil_fleet_shed_total",
         tenant="flood", reason="queue_depth")
assert shed >= 1, met
sheds = [e for e in ev["events"] if e["event"] == "request_shed"]
assert len(sheds) == int(shed), (shed, sheds)
assert all(e["reason"] in ("queue_depth", "admission_latency")
           for e in sheds), sheds
kinds = {e["event"] for e in ev["events"]}
assert {"fault_replica_crash", "replica_dead",
        "campaign_recovered"} <= kinds, kinds
n_rec = sum(1 for e in ev["events"]
            if e["event"] == "campaign_recovered")
print(f"fleet chaos smoke OK: {killed} killed mid-batch, "
      f"{n_rec} campaign(s) recovered bitwise-equal, survivors "
      f"recompiles=0 tuner_measurements=0, {int(shed)} request(s) "
      f"shed (queue_depth), events schema-valid")
EOF
if [ -n "${CI_ARTIFACT_DIR:-}" ]; then
  mkdir -p "$CI_ARTIFACT_DIR"
  cp "$FLEET_CALM" "$CI_ARTIFACT_DIR/fleet_calm.json"
  cp "$FLEET_CHAOS" "$CI_ARTIFACT_DIR/fleet_chaos.json"
  cp "$FLEET_EVENTS" "$CI_ARTIFACT_DIR/fleet_events.json"
  cp "$FLEET_METRICS" "$CI_ARTIFACT_DIR/fleet_metrics.json"
  mkdir -p "$CI_ARTIFACT_DIR/fleet_flight"
  cp "$FLEET_FLIGHT"/* "$CI_ARTIFACT_DIR/fleet_flight/" 2>/dev/null || true
fi
rm -rf "$FLEET_ROOT" "$FLEET_CACHE" "$FLEET_CALM" "$FLEET_CHAOS" \
       "$FLEET_EVENTS" "$FLEET_METRICS" "$FLEET_FLIGHT"

echo "== 13/13 multi-chip certification sweep =="
python __graft_entry__.py 8 | tail -1

echo "CI PASSED"
