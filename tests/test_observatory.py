"""Performance observatory: attribution, drift, ledger, flight recorder.

The ISSUE 11 acceptance contracts:

* the drift detector's state machine (fake-injected error ratios: no
  event inside tolerance, one ``perf_drift`` event + plan-cache
  invalidation after K consecutive misses, a re-tuned plan clears the
  gauge);
* the ledger schema, the append-only trajectory, the regression gate
  (a synthetic same-fingerprint steps/s drop = nonzero CLI exit, the
  honest ledger passes), and the legacy BENCH_*.json backfill;
* the flight recorder (a chaos NaN trip produces a schema-valid dump
  whose timeline contains the trip step and the rollback; the SIGTERM
  path dumps BEFORE the preemption checkpoint);
* the attribution honesty contract (the attributed program IS the
  uninstrumented one — the registry targets pin the HLO identity, and
  the host-callback timer fixture is the proven-flagged negative
  control).
"""

import glob
import json
import os
import pathlib

import numpy as np
import pytest

from stencil_tpu.models.jacobi import Jacobi3D
from stencil_tpu.observatory import (FlightRecorder, PerfAttributor,
                                     METRIC_MODEL_ERROR_RATIO,
                                     append_record, backfill_records,
                                     diff_records, gate_regressions,
                                     make_record, model_step_seconds_for,
                                     payload_records, read_ledger,
                                     render_timeline, validate_dump,
                                     validate_record)
from stencil_tpu.observatory.__main__ import main as observatory_cli
from stencil_tpu.resilience import (FaultPlan, NaNInjection, Preemption,
                                    ResiliencePolicy)
from stencil_tpu.telemetry import MetricsRegistry, metric_value
from stencil_tpu.tuning import (Candidate, Plan, invalidate_plan,
                                load_plan, store_plan)

REPO = pathlib.Path(__file__).parent.parent

N = 16
STEPS = 12


def make_jacobi(**kw):
    j = Jacobi3D(N, N, N, mesh_shape=(2, 2, 2), dtype=np.float32, **kw)
    j.init()
    return j


def fast_policy(**kw):
    kw.setdefault("check_every", 1)
    kw.setdefault("ckpt_every", 4)
    kw.setdefault("base_delay", 0.0)
    kw.setdefault("sleep", lambda s: None)
    return ResiliencePolicy(**kw)


def make_attributor(events, reg, on_drift=None, **kw):
    kw.setdefault("model_step_seconds", 1.0)
    kw.setdefault("model_bytes_per_step", 1000.0)
    kw.setdefault("tolerance", 0.25)
    kw.setdefault("window", 3)
    return PerfAttributor("test", "PpermuteSlab", 2,
                          emit=lambda k, **a: events.append((k, a)),
                          on_drift=on_drift, registry=reg,
                          fingerprint="f" * 32, **kw)


# ----------------------------------------------------------------------
# attribution + drift detector
# ----------------------------------------------------------------------
def test_in_tolerance_ratios_never_drift():
    events, reg = [], MetricsRegistry()
    att = make_attributor(events, reg)
    # calibration + jitter inside the 25% band
    for seconds in (4.0, 4.3, 3.8, 4.1, 4.4):
        assert att.observe(4, seconds) is None
    assert not events
    # gauges exported with the {entry,method,s} labels
    txt = reg.to_prometheus_text()
    got = metric_value(txt, METRIC_MODEL_ERROR_RATIO, entry="test",
                       method="PpermuteSlab", s="2")
    assert got == pytest.approx(4.4 / 4)
    achieved = metric_value(txt, "stencil_perf_achieved_bytes_per_s",
                            entry="test", method="PpermuteSlab", s="2")
    assert achieved == pytest.approx(1000.0 / 1.1)


def test_drift_fires_once_after_k_consecutive_misses():
    events, reg = [], MetricsRegistry()
    att = make_attributor(events, reg)
    att.observe(1, 1.0)           # calibrate: ratio 1.0
    att.observe(1, 2.0)           # miss 1
    att.observe(1, 2.0)           # miss 2
    assert not events
    verdict = att.observe(1, 2.0, step=30)  # miss 3 = K -> drift
    assert verdict is not None
    assert events and events[0][0] == "perf_drift"
    attrs = events[0][1]
    assert attrs["consecutive"] == 3 and attrs["step"] == 30
    assert attrs["fingerprint"] == "f" * 32
    # latched: further misses do not refire
    att.observe(1, 2.0)
    assert len(events) == 1


def test_recovery_inside_tolerance_rearms_the_detector():
    events, reg = [], MetricsRegistry()
    att = make_attributor(events, reg)
    att.observe(1, 1.0)
    for _ in range(3):
        att.observe(1, 2.0)
    assert len(events) == 1
    # back in tolerance: streak clears, latch re-arms
    for _ in range(4):
        att.observe(1, 1.05)
    for _ in range(3):
        att.observe(1, 2.2)
    assert len(events) == 2


def test_gradual_slowdown_still_drifts():
    """The boiling frog: the calibrated reference stays FIXED, so a
    4%-per-observation compounding slowdown must eventually register
    as drift (a moving/EWMA reference would chase it forever)."""
    events, reg = [], MetricsRegistry()
    att = make_attributor(events, reg)
    seconds = 1.0
    att.observe(1, seconds)
    for _ in range(60):
        seconds *= 1.04
        att.observe(1, seconds)
        if events:
            break
    assert events and events[0][0] == "perf_drift"


def test_zero_duration_observation_cannot_poison_calibration():
    """A degenerate zero-seconds observation (fake clocks) must not
    anchor the reference at 0 and divide by it later."""
    events, reg = [], MetricsRegistry()
    att = make_attributor(events, reg)
    att.observe(1, 0.0)          # cannot calibrate a relative band
    att.observe(1, 0.5)          # calibrates HERE instead of crashing
    att.observe(1, 0.6)
    assert att.last_ratio == pytest.approx(0.6)
    assert not events


def test_miss_streak_must_be_consecutive():
    events, reg = [], MetricsRegistry()
    att = make_attributor(events, reg)
    att.observe(1, 1.0)
    att.observe(1, 2.0)
    att.observe(1, 2.0)
    att.observe(1, 1.0)           # clean observation breaks the streak
    att.observe(1, 2.0)
    att.observe(1, 2.0)
    assert not events


def test_reset_clears_gauge_and_recalibrates():
    """The re-tuned-plan contract: reset() zeroes the exported ratio
    gauge and drops the calibrated reference."""
    events, reg = [], MetricsRegistry()
    att = make_attributor(events, reg)
    att.observe(1, 1.7)
    assert metric_value(reg.to_prometheus_text(),
                        METRIC_MODEL_ERROR_RATIO, entry="test",
                        method="PpermuteSlab", s="2") == 1.7
    att.reset(model_step_seconds=0.5, fingerprint="a" * 32)
    assert metric_value(reg.to_prometheus_text(),
                        METRIC_MODEL_ERROR_RATIO, entry="test",
                        method="PpermuteSlab", s="2") == 0.0
    assert att.last_ratio is None
    # the next observation calibrates against the NEW model price
    att.observe(1, 1.0)
    assert att.last_ratio == pytest.approx(2.0)


def test_drift_invalidates_plan_cache(tmp_path):
    """K consecutive misses + on_drift wired to the cache: the stale
    plan's record is dropped so the next tune re-measures."""
    cache = tmp_path / "plans.json"
    plan = Plan(config=Candidate("PpermuteSlab", 1),
                fingerprint="f" * 32, coefficients={}, costs={})
    store_plan(plan, cache)
    assert load_plan("f" * 32, cache) is not None

    events, reg = [], MetricsRegistry()
    att = make_attributor(
        events, reg,
        on_drift=lambda a: invalidate_plan(a["fingerprint"], cache))
    att.observe(1, 1.0)
    for _ in range(3):
        att.observe(1, 3.0)
    assert load_plan("f" * 32, cache) is None
    # a second invalidation is a clean miss, not an error
    assert invalidate_plan("f" * 32, cache) is False


def test_driver_wires_retune_on_drift(tmp_path):
    """The resilience driver's drift hook: with retune_on_drift the
    attributor's on_drift drops the domain plan's cache record and
    logs plan_invalidated through the report's event log."""
    from stencil_tpu.resilience.driver import _ResilientRun

    cache = tmp_path / "plans.json"
    j = make_jacobi()
    fp = "c" * 32
    plan = Plan(config=Candidate("PpermuteSlab", 1), fingerprint=fp,
                coefficients={"ici": {"alpha_s": 1e-5,
                                      "beta_bytes_per_s": 1e10}},
                costs={})
    store_plan(plan, cache)
    j.dd.plan = plan
    run = _ResilientRun(j.dd, j.step, 2,
                        fast_policy(retune_on_drift=True,
                                    plan_cache_path=str(cache)),
                        None, None, None, None, None, None, None)
    assert run.attributor is not None and run.attributor.enabled
    assert run.attributor.fingerprint == fp
    run.attributor._on_drift({"fingerprint": fp})
    assert load_plan(fp, cache) is None
    kinds = [e["event"] for e in run.report.events]
    assert "plan_invalidated" in kinds


def test_model_step_seconds_for_domains():
    j = make_jacobi()
    model = model_step_seconds_for(j.dd)
    assert model is not None and model > 0
    # a single-device mesh has nothing on the wire to attribute
    import jax
    j1 = Jacobi3D(8, 8, 8, mesh_shape=(1, 1, 1),
                  devices=jax.devices()[:1], dtype=np.float32)
    j1.init()
    assert model_step_seconds_for(j1.dd) is None


def test_disabled_attributor_is_a_passthrough():
    events, reg = [], MetricsRegistry()
    att = make_attributor(events, reg, model_step_seconds=None)
    assert not att.enabled
    with att.dispatch(4, block=lambda: (_ for _ in ()).throw(
            AssertionError("disabled attribution must not block"))):
        pass
    assert att.observe(4, 10.0) is None and not events


def test_attributed_program_is_the_uninstrumented_one():
    """The honesty contract the observatory.attribution.* registry
    targets pin: attribution never edits the dispatched program."""
    def fn(x):
        return x
    assert PerfAttributor.attributed(fn) is fn


def test_host_callback_timer_fixture_flagged(tmp_path):
    """Negative control: a timer that sneaks a host callback into the
    step must fail the transfer checker (nonzero CLI exit)."""
    from stencil_tpu.analysis import run_targets
    from stencil_tpu.analysis.registry import load_targets
    fixtures = pathlib.Path(__file__).parent / "fixtures" / "lint"
    report = run_targets(load_targets(fixtures / "bad_attribution.py"))
    assert len(report.errors) >= 2
    assert all(f.checker == "transfer" for f in report.findings)
    assert any("pure_callback" in f.message for f in report.errors)
    assert any("io_callback" in f.message for f in report.errors)


# ----------------------------------------------------------------------
# ledger
# ----------------------------------------------------------------------
def _record(sps=100.0, bench="b", fp="a" * 32, prov="measured",
            created=1.0):
    return make_record(bench, {"grid": [8, 8, 8]},
                       {"steps_per_s": sps}, provenance=prov,
                       fingerprint=fp, created=created)


def test_record_schema_validates():
    rec = _record()
    assert validate_record(rec) == []
    bad = dict(rec)
    bad["provenance"] = "guessed"
    assert any("provenance" in p for p in validate_record(bad))
    bad = dict(rec)
    bad["metrics"] = {"steps_per_s": -1.0}
    assert any("steps_per_s" in p for p in validate_record(bad))
    with pytest.raises(ValueError):
        make_record("b", {}, {"steps_per_s": float("nan")})


def test_append_read_roundtrip_and_torn_line(tmp_path):
    path = tmp_path / "ledger.jsonl"
    append_record(path, _record(100.0))
    append_record(path, _record(120.0, created=2.0))
    recs = read_ledger(path)
    assert [r["metrics"]["steps_per_s"] for r in recs] == [100.0, 120.0]
    with open(path, "a") as f:
        f.write("{torn\n")
    with pytest.raises(ValueError):
        read_ledger(path)


def test_gate_passes_improvement_and_catches_regression():
    honest = [_record(100.0), _record(110.0, created=2.0)]
    assert gate_regressions(honest, threshold=0.2) == []
    regressed = honest + [_record(50.0, created=3.0)]
    fails = gate_regressions(regressed, threshold=0.2)
    assert len(fails) == 1 and "regressed" in fails[0]
    # different fingerprint = different trajectory: never compared
    other = honest + [_record(50.0, fp="b" * 32, created=3.0)]
    assert gate_regressions(other, threshold=0.2) == []
    # legacy provenance does not gate by default, but can opt in
    legacy = [_record(100.0, prov="legacy"),
              _record(10.0, prov="legacy", created=2.0)]
    assert gate_regressions(legacy) == []
    assert len(gate_regressions(legacy,
                                provenances=("measured", "legacy"))) == 1


def test_diff_records_ratio_and_comparability():
    d = diff_records(_record(100.0), _record(150.0, created=2.0))
    assert d["comparable"]
    assert d["metrics"]["steps_per_s"]["ratio"] == pytest.approx(1.5)
    d = diff_records(_record(100.0), _record(150.0, fp="b" * 32))
    assert not d["comparable"]


def _harness_payload(n, rc, parsed):
    """A bench.py run wrapped the way the old harness stored it
    (``{"n", "cmd", "rc", "tail", "parsed"}``)."""
    return {"n": n, "cmd": "python bench.py", "rc": rc,
            "tail": json.dumps(parsed) if parsed else "Traceback ...",
            "parsed": parsed}


def test_backfill_committed_legacy_history(tmp_path):
    """The committed BENCH_pr*.json shapes and the harness-wrapped
    bench.py shape all convert; failed and suspect legacy runs are
    skipped, never invented."""
    from stencil_tpu.observatory.ledger import backfill_files
    metric = "jacobi3d_512c_iters_per_sec"
    extra = {"devices": 1, "mesh": [1, 1, 1], "platform": "tpu"}
    harness = [
        _harness_payload(1, 0, {"metric": metric, "value": 100.0,
                                "unit": "iters/s", "extra": extra}),
        _harness_payload(2, 1, None),
        _harness_payload(3, 0, {"metric": metric, "value": 0.0,
                                "unit": "iters/s", "suspect": True,
                                "extra": {}}),
        _harness_payload(4, 0, {"metric": metric, "value": None,
                                "unit": "iters/s", "suspect": True,
                                "extra": {}}),
    ]
    wrapped = []
    for p in harness:
        path = tmp_path / f"BENCH_harness{p['n']}.json"
        path.write_text(json.dumps(p))
        wrapped.append(path)
    files = [REPO / f for f in
             ("BENCH_pr3.json", "BENCH_pr4.json", "BENCH_pr8.json",
              "BENCH_pr10.json")] + wrapped
    records, skipped = backfill_files(files)
    assert len(records) == 9
    assert all(r["provenance"] == "legacy" for r in records)
    assert all(validate_record(r) == [] for r in records)
    benches = {r["bench"] for r in records}
    assert {"bench_exchange", "bench_exchange.megastep",
            "bench_exchange.autotune", "pic", metric} <= benches
    # run 2 failed, runs 3/4 are suspect: skipped with a reason each
    assert len(skipped) == 3
    # legacy history seeds trajectories but never trips the gate
    assert gate_regressions(records) == []


def test_payload_records_carry_contract_race_legs():
    """The segment compiler's race legs convert into their OWN
    trajectory groups: bench_exchange payloads with pic /
    astaroth_temporal fused legs and pic payloads with a fused block
    each land one extra megastep record (the one shared converter —
    live emission and backfill can never fork these groups)."""
    from stencil_tpu.observatory.ledger import payload_records

    leg = {"check_every": 8, "steps": 16,
           "stepwise_steps_per_s": 100.0, "fused_steps_per_s": 180.0,
           "fused_over_stepwise": 1.8}
    be = {"bench": "bench_exchange", "mesh": [1, 1, 1],
          "per_device_size": [8, 8, 8], "radius": [1, 1, 1],
          "fields": 1,
          "configs": [{"exchange_every": 1, "steps_per_s": 50.0}],
          "fused": {**leg, "pic": dict(leg),
                    "astaroth_temporal": {**leg,
                                          "exchange_every": 2}}}
    records, skipped = payload_records(be, "t", provenance="measured",
                                       created=1.0)
    assert not skipped
    by_bench = {r["bench"]: r for r in records}
    assert {"bench_exchange", "bench_exchange.megastep",
            "bench_exchange.megastep.pic",
            "bench_exchange.megastep.astaroth_temporal"} \
        <= set(by_bench)
    ast = by_bench["bench_exchange.megastep.astaroth_temporal"]
    assert ast["config"]["exchange_every"] == 2
    assert ast["metrics"]["steps_per_s"] == 180.0
    assert ast["metrics"]["fused_over_stepwise"] == 1.8

    pic = {"bench": "pic", "seconds_per_step": 0.01,
           "particle_steps_per_s": 1000.0,
           "migration_bytes_per_shard": 64, "overflow": 0,
           "config": {"grid": [8, 8, 8]}, "fused": dict(leg)}
    records, skipped = payload_records(pic, "t", provenance="measured",
                                       created=1.0)
    assert not skipped
    by_bench = {r["bench"]: r for r in records}
    assert set(by_bench) == {"pic", "pic.megastep"}
    assert by_bench["pic.megastep"]["metrics"]["steps_per_s"] == 180.0
    assert by_bench["pic.megastep"]["config"]["check_every"] == 8


def test_payload_records_stamp_depths_post_fingerprint():
    """Asymmetric-depth bench configs carry a structured ``depths``
    vector in the ledger record, stamped AFTER the fingerprint is
    taken: a payload with and without the vector lands in the same
    (fingerprint, bench) trajectory group (the ``exchange_every``
    label string already keys it)."""
    from stencil_tpu.observatory.ledger import payload_records

    base = {"bench": "bench_exchange", "mesh": [2, 2, 2],
            "per_device_size": [8, 8, 8], "radius": [1, 1, 1],
            "fields": 1}
    with_depths = {**base,
                   "configs": [{"exchange_every": "1.1.4",
                                "depths": [1, 1, 4],
                                "steps_per_s": 80.0}]}
    without = {**base,
               "configs": [{"exchange_every": "1.1.4",
                            "steps_per_s": 80.0}]}
    stamped, _ = payload_records(with_depths, "t",
                                 provenance="measured", created=1.0)
    plain, _ = payload_records(without, "t",
                               provenance="measured", created=1.0)
    assert stamped[0]["config"]["depths"] == [1, 1, 4]
    assert "depths" not in plain[0]["config"]
    assert stamped[0]["fingerprint"] == plain[0]["fingerprint"]
    assert stamped[0]["config"]["exchange_every"] == "1.1.4"


def test_gate_and_groups_accept_bench_globs_and_brackets():
    """The ledger CLIs' ``--bench`` filter is a glob with
    literal-bracket tolerance: ``bench_exchange*`` restricts the gate,
    and a bench id carrying ``[...]`` (the candidate-key spelling)
    matches both its exact string and a ``*[s=...]`` pattern that raw
    fnmatch would misread as a character class."""
    from stencil_tpu.observatory.ledger import gate_groups_checked

    regressed = [_record(100.0), _record(50.0, created=2.0),
                 _record(100.0, bench="pic", fp="b" * 32),
                 _record(90.0, bench="pic", fp="b" * 32, created=2.0)]
    assert len(gate_regressions(regressed, threshold=0.2)) == 1
    assert len(gate_regressions(regressed, threshold=0.2,
                                bench="b*")) == 1
    assert gate_regressions(regressed, threshold=0.2,
                            bench="pic") == []
    assert gate_groups_checked(regressed, bench="b*") == 1
    assert gate_groups_checked(regressed) == 2

    bracketed = [_record(100.0, bench="bench_exchange[s=1.1.4]"),
                 _record(40.0, bench="bench_exchange[s=1.1.4]",
                         created=2.0)]
    for pat in ("bench_exchange[s=1.1.4]", "*[s=1.1.4]",
                "bench_exchange*"):
        assert len(gate_regressions(bracketed, threshold=0.2,
                                    bench=pat)) == 1, pat
        assert gate_groups_checked(bracketed, bench=pat) == 1, pat
    assert gate_regressions(bracketed, threshold=0.2,
                            bench="*[s=2]") == []


def test_committed_seed_ledger_matches_backfill():
    """bench/ledger.jsonl: the first ten records are exactly the
    backfill of the committed legacy snapshots; everything after is a
    measured record (PR 15 landed the megastep carry-contract race
    trajectories — bench_exchange.megastep.pic / .astaroth_temporal /
    pic.megastep — as measured history), all schema-valid and the
    whole file gate-clean."""
    from stencil_tpu.observatory.ledger import (gate_regressions,
                                                validate_ledger)
    recs = read_ledger(REPO / "bench" / "ledger.jsonl")
    assert validate_ledger(recs) == []
    assert len(recs) >= 22
    assert all(r["provenance"] == "legacy" for r in recs[:10])
    assert all(r["provenance"] == "measured" for r in recs[10:])
    benches = {r["bench"] for r in recs[10:]}
    assert {"bench_exchange.megastep", "bench_exchange.megastep.pic",
            "bench_exchange.megastep.astaroth_temporal",
            "pic.megastep"} <= benches
    # the measured trajectories gate clean at the committed threshold
    assert gate_regressions(recs, threshold=0.8) == []


def test_live_and_backfilled_records_share_groups(tmp_path):
    """One converter serves live emission and backfill, so a live
    bench_exchange record lands in the same (fingerprint, bench)
    trajectory group as its legacy ancestor."""
    payload = json.load(open(REPO / "BENCH_pr3.json"))
    legacy, _ = backfill_records(payload, "BENCH_pr3.json", created=1.0)
    live, _ = payload_records(payload, "smoke", provenance="measured",
                              created=2.0)
    assert [r["fingerprint"] for r in legacy] == \
        [r["fingerprint"] for r in live]
    assert [r["bench"] for r in legacy] == [r["bench"] for r in live]


def test_cli_validate_backfill_diff_gate(tmp_path, capsys):
    ledger = tmp_path / "ledger.jsonl"
    # backfill the committed history through the CLI
    rc = observatory_cli(["backfill", "--out", str(ledger),
                          str(REPO / "BENCH_pr3.json"),
                          str(REPO / "BENCH_pr4.json")])
    assert rc == 0
    assert observatory_cli(["validate", str(ledger)]) == 0
    assert observatory_cli(["gate", str(ledger)]) == 0
    # pr3 and pr4 measured the same fingerprints: diffable trajectory
    assert observatory_cli(["diff", str(ledger),
                            "--bench", "bench_exchange"]) == 0
    out = capsys.readouterr().out
    assert "steps_per_s" in out
    # legacy-inclusive gate sees the pr3 -> pr4 slowdown (different
    # machines — exactly why legacy is excluded by default)
    assert observatory_cli(["gate", str(ledger),
                            "--include-legacy"]) == 1
    # synthetic same-fingerprint regression: nonzero exit
    recs = read_ledger(ledger)
    bad = dict(recs[-1])
    bad["metrics"] = dict(bad["metrics"],
                          steps_per_s=bad["metrics"]["steps_per_s"] / 10)
    bad["provenance"] = "measured"
    good = dict(recs[-1])
    good["provenance"] = "measured"
    for r in (good, bad):
        r = dict(r)
        append_record(ledger, r)
    assert observatory_cli(["gate", str(ledger)]) == 1
    # bad input paths exit 2
    assert observatory_cli(["validate",
                            str(tmp_path / "missing.jsonl")]) == 2


def test_empty_ledger_env_var_disables(monkeypatch, tmp_path):
    """STENCIL_BENCH_LEDGER='' must disable the ledger exactly like
    --ledger '' — never fall through to the committed checkout file."""
    import sys
    sys.path.insert(0, str(REPO / "apps"))
    try:
        import _common
    finally:
        sys.path.pop(0)

    class Args:
        ledger = None
    monkeypatch.setenv("STENCIL_BENCH_LEDGER", "")
    assert _common.resolve_ledger_path(Args()) is None
    monkeypatch.setenv("STENCIL_BENCH_LEDGER", str(tmp_path / "l.jsonl"))
    assert _common.resolve_ledger_path(Args()) == \
        str(tmp_path / "l.jsonl")
    monkeypatch.delenv("STENCIL_BENCH_LEDGER")
    assert _common.resolve_ledger_path(Args()).endswith(
        os.path.join("bench", "ledger.jsonl"))
    Args.ledger = ""
    assert _common.resolve_ledger_path(Args()) is None


def test_cli_validate_rejects_malformed_ledger(tmp_path):
    path = tmp_path / "ledger.jsonl"
    with open(path, "w") as f:
        f.write(json.dumps({"schema": 99, "bench": "x"}) + "\n")
    assert observatory_cli(["validate", str(path)]) == 1


# ----------------------------------------------------------------------
# flight recorder
# ----------------------------------------------------------------------
def test_recorder_dump_schema_and_bounds(tmp_path):
    from stencil_tpu.telemetry import EventLog, Tracer
    reg = MetricsRegistry()
    reg.counter("c_total", "help").inc(3)
    tracer = Tracer(run_id="runx")
    fr = FlightRecorder(run_id="runx", events_capacity=4,
                        registry=reg, tracer=tracer)
    elog = EventLog(run_id="runx", sinks=(fr,))
    with tracer.span("segment.dispatch", k=4):
        pass
    for i in range(6):
        elog.emit("tick", n=i)
    fr.record_probe({"step": 5, "tripped": True, "reason": "nan"})
    path = fr.dump(tmp_path, "sentinel_trip", trip_step=5)
    payload = json.load(open(path))
    assert validate_dump(payload) == []
    # bounded ring: only the newest 4 events, truncation visible
    assert len(payload["events"]) == 4
    assert payload["dropped_events"] == 2
    assert payload["spans"][0]["name"] == "segment.dispatch"
    assert payload["metrics"]["metrics"]["c_total"]
    tl = render_timeline(payload)
    assert "TRIPPED" in tl and "segment.dispatch" in tl
    # corrupted dumps are caught
    bad = dict(payload, kind="blackbox")
    assert validate_dump(bad)


def test_chaos_trip_produces_valid_dump_with_trip_and_rollback(tmp_path):
    """ISSUE acceptance: the chaos NaN trip's dump is schema-valid and
    its timeline contains the trip step and the rollback."""
    fdir = tmp_path / "flight"
    j = make_jacobi()
    plan = FaultPlan(nans=[NaNInjection(step=6)])
    rep = j.run_resilient(
        STEPS, policy=fast_policy(flight_recorder_dir=str(fdir)),
        ckpt_dir=str(tmp_path / "ckpt"), faults=plan)
    assert rep.steps == STEPS and rep.rollbacks >= 1
    dumps = sorted(glob.glob(str(fdir / "flight_*sentinel_trip*.json")))
    assert dumps
    assert validate_dump(dumps[0]) == []
    payload = json.load(open(dumps[0]))
    kinds = [e["event"] for e in payload["events"]]
    assert "sentinel_tripped" in kinds and "restored" in kinds
    trip = next(e for e in payload["events"]
                if e["event"] == "sentinel_tripped")
    assert trip["step"] == 6
    tl = render_timeline(dumps[0])
    assert "sentinel_tripped" in tl and "restored" in tl
    # probe history rode along
    assert any(p.get("tripped") for p in payload["probes"])


def test_sigterm_dumps_before_the_preemption_checkpoint(tmp_path):
    """ISSUE acceptance: the SIGTERM path dumps BEFORE the preemption
    checkpoint — the black box must not contain the final save."""
    fdir = tmp_path / "flight"
    j = make_jacobi()
    plan = FaultPlan(preemptions=[Preemption(step=6)])
    rep = j.run_resilient(
        STEPS, policy=fast_policy(check_every=2,
                                  flight_recorder_dir=str(fdir)),
        ckpt_dir=str(tmp_path / "ckpt"), faults=plan)
    assert rep.preempted
    dumps = sorted(glob.glob(str(fdir / "flight_*preempt*.json")))
    assert dumps
    payload = json.load(open(dumps[0]))
    assert validate_dump(payload) == []
    # dumped before the tagged save: no preempted checkpoint event yet
    assert not any(e["event"] == "checkpoint" and e.get("preempted")
                   for e in payload["events"])
    # ...but the preempted checkpoint DID happen afterwards
    assert any(e["event"] == "checkpoint" and e.get("preempted")
               for e in rep.events)


def test_unhandled_error_dumps_black_box(tmp_path):
    from stencil_tpu.resilience import ResilienceError
    fdir = tmp_path / "flight"
    j = make_jacobi()
    plan = FaultPlan(nans=[NaNInjection(step=3)])
    # watchdog mode (no ckpt_dir): the trip raises — and dumps
    with pytest.raises(ResilienceError):
        j.run_resilient(
            STEPS, policy=fast_policy(flight_recorder_dir=str(fdir)),
            faults=plan)
    dumps = glob.glob(str(fdir / "flight_*unhandled_error*.json"))
    assert dumps and validate_dump(dumps[0]) == []


def test_recorder_disarmed_without_directory(tmp_path):
    j = make_jacobi()
    rep = j.run_resilient(4, policy=fast_policy(),
                          ckpt_dir=str(tmp_path / "ckpt"))
    assert rep.steps == 4  # no recorder, no dumps, loop unchanged


# ----------------------------------------------------------------------
# driver integration: attribution is on by default and harmless
# ----------------------------------------------------------------------
def test_resilient_run_attributes_by_default(tmp_path):
    from stencil_tpu.telemetry import get_registry
    j = make_jacobi()
    rep = j.run_resilient(4, policy=fast_policy(),
                          ckpt_dir=str(tmp_path / "ckpt"))
    assert rep.steps == 4
    reg = get_registry()
    ratio = reg.get(METRIC_MODEL_ERROR_RATIO)
    assert ratio is not None
    assert ratio.value(entry="jacobi", method="PpermuteSlab",
                       s="1") > 0
