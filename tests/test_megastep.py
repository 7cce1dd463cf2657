"""Megastep: whole-campaign fused segments (parallel/megastep.py).

The ISSUE 8 acceptance contract: a ``check_every=k`` segment compiles
to ONE program that is numerically indistinguishable from the stepwise
loop (bitwise for Jacobi — periodic AND zero-Dirichlet, even AND
uneven partitions; accumulator-carrying ~1-ULP for Astaroth), carries
the per-step health probe in-graph so the driver can locate the exact
tripped step, donates its state end-to-end, and passes the same
registry gates as the stepwise path (exact collective counts, exact
bytes, negative control flagged).
"""

import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from stencil_tpu.models.jacobi import Jacobi3D
from stencil_tpu.parallel.megastep import (MAX_UNROLL, probe_rel_steps,
                                           segment_chunks)

N = 16
BAD_FIXTURE = Path(__file__).parent / "fixtures" / "lint" / \
    "bad_megastep.py"


def make_jacobi(**kw):
    kw.setdefault("mesh_shape", (2, 2, 2))
    kw.setdefault("dtype", np.float32)
    kw.setdefault("kernel", "xla")
    j = Jacobi3D(kw.pop("x", N), kw.pop("y", N), kw.pop("z", N), **kw)
    j.init()
    return j


# ----------------------------------------------------------------------
# segmentation helpers
# ----------------------------------------------------------------------
def test_segment_chunks_and_probe_points():
    assert segment_chunks(5) == [1] * 5
    assert segment_chunks(7, stride=3) == [3, 3, 1]
    assert probe_rel_steps([1] * 6, 2) == (2, 4, 6)
    # the final step is ALWAYS probed, cadence or not
    assert probe_rel_steps([1] * 5, 2) == (2, 4, 5)
    assert probe_rel_steps([3, 3, 1], 1) == (3, 6, 7)
    assert MAX_UNROLL >= 16


# ----------------------------------------------------------------------
# fused == stepwise, bitwise (jacobi)
# ----------------------------------------------------------------------
def _compare_jacobi(steps=8, seg=None, **kw):
    a = make_jacobi(**kw)
    b = make_jacobi(**kw)
    for _ in range(steps):
        a.step()
    done = 0
    while done < steps:
        k = min(seg or steps, steps - done)
        s = b.make_segment(k)
        assert s is not None and s.steps == k
        s.run(done)
        done += k
    np.testing.assert_array_equal(a.temperature(), b.temperature())


def test_jacobi_segment_bitwise_periodic():
    _compare_jacobi(steps=8, seg=4)


def test_jacobi_segment_bitwise_uneven_partitions():
    _compare_jacobi(steps=6, seg=3, x=17, y=17, z=17)


def test_jacobi_segment_bitwise_boundary_none():
    from stencil_tpu.topology import Boundary
    _compare_jacobi(steps=6, seg=3, boundary=Boundary.NONE)


def test_jacobi_segment_bitwise_uneven_none():
    from stencil_tpu.topology import Boundary
    _compare_jacobi(steps=5, seg=2, x=17, y=17, z=17,
                    boundary=Boundary.NONE)


def test_jacobi_temporal_segment_bitwise():
    """exchange_every=2: the fused segment advances whole temporal
    groups plus depth-1 tails, bitwise-equal to the blocked loop."""
    a = make_jacobi(exchange_every=2)
    assert a.kernel_path == "xla-temporal[s=2]"
    b = make_jacobi(exchange_every=2)
    a.run(7)
    s = b.make_segment(7)
    # 3 groups of 2 + 1 tail step, probed per chunk
    assert s.probe_steps == (2, 4, 6, 7)
    s.run(0)
    np.testing.assert_array_equal(a.temperature(), b.temperature())


def test_wrap_path_segment_bitwise():
    """The single-chip Pallas wrap path fuses: segment chunks mirror
    run(n)'s N-step in-kernel groups + single-step tail, bitwise."""
    import jax

    def mk():
        j = Jacobi3D(16, 16, 16, mesh_shape=(1, 1, 1),
                     devices=jax.devices()[:1], dtype=np.float32,
                     kernel="wrap")
        j.init()
        return j

    a, b = mk(), mk()
    a.run(5)
    seg = b.make_segment(5)
    assert seg and seg.steps == 5
    # N=2 in-kernel groups + a single-step tail, probed per chunk
    assert seg.probe_steps == (2, 4, 5)
    seg.run(0)
    np.testing.assert_array_equal(a.temperature(), b.temperature())


def test_halo_path_segment_bitwise():
    """The multi-device Pallas halo path fuses: each segment chunk is
    one temporally-blocked kernel launch (slab exchange inside),
    bitwise-equal to the fused run loop."""
    import jax

    def mk():
        j = Jacobi3D(16, 16, 16, mesh_shape=(1, 2, 2),
                     devices=jax.devices()[:4], dtype=np.float32,
                     kernel="halo")
        j.init()
        return j

    a, b = mk(), mk()
    assert a.kernel_path == "halo"
    a.run(5)
    seg = b.make_segment(5)
    assert seg and seg.probe_steps == (2, 4, 5)
    seg.run(0)
    np.testing.assert_array_equal(a.temperature(), b.temperature())


def _make_overlap_jacobi():
    import jax

    j = Jacobi3D(16, 16, 16, mesh_shape=(1, 2, 2),
                 devices=jax.devices()[:4], dtype=np.float32,
                 kernel="halo", overlap=True)
    j.init()
    assert j.kernel_path == "overlap"
    return j


def test_overlap_path_fuses_under_certificate():
    """The in-kernel RDMA overlap path FUSES: the schedule certifier
    (analysis/schedule.py) proves the kernel's semaphore schedule
    replay-safe — four face slabs, every slot drained per launch —
    and make_segment consumes the certificate into a real Segment.
    Traced only here; execution is covered by the capability-gated
    bitwise test below."""
    j = _make_overlap_jacobi()
    seg = j.make_segment(4)
    assert seg and seg.steps == 4
    cert = j._schedule_certificate
    assert cert is not None and cert.replay_safe is True
    assert cert.max_in_flight == 4 and not cert.reasons


def test_overlap_path_declines_on_unsafe_certificate(monkeypatch):
    """replay_safe=False gates fusion OFF: make_segment returns a
    falsy SegmentDecline quoting the certificate's reasons[] under
    the uncertified-rdma-schedule code — never a silent None. (The
    certificate memo keys on the certifier's identity, so the
    monkeypatched verdict is never shadowed by a cached one.)"""
    from stencil_tpu.analysis import schedule as schedule_checker
    from stencil_tpu.parallel.megastep import (
        DECLINE_UNCERTIFIED_SCHEDULE, SegmentDecline)

    def unsafe(fn, args, axis_names=(), replay=4):
        return schedule_checker.ScheduleCertificate(
            kernel="jacobi7_overlap", replay=replay, max_in_flight=9,
            replay_safe=False,
            reasons=["in-flight aliasing across sub-steps"])

    monkeypatch.setattr(schedule_checker, "certify_traceable", unsafe)
    j = _make_overlap_jacobi()
    d = j.make_segment(4)
    assert not d and isinstance(d, SegmentDecline)
    assert d.model == "jacobi" and d.path == "overlap"
    assert d.code == DECLINE_UNCERTIFIED_SCHEDULE
    assert "uncertified RDMA schedule" in d.reason
    assert "in-flight aliasing across sub-steps" in d.reason


def test_overlap_segment_bitwise():
    """Certificate-gated fused RDMA segment == stepwise, bitwise: the
    k launches fused into one program carry exactly the per-launch
    semaphore drain the certificate proved."""
    a, b = _make_overlap_jacobi(), _make_overlap_jacobi()
    a.run(4)
    seg = b.make_segment(4)
    assert seg and seg.steps == 4
    seg.run(0)
    np.testing.assert_array_equal(a.temperature(), b.temperature())


def test_decline_reason_vocabulary():
    """The decline_reason vocabulary is pinned: fused:false events and
    the flight-recorder timeline are greppable by CAUSE, and decline()
    refuses codes outside the set."""
    from stencil_tpu.parallel import megastep as ms

    assert ms.DECLINE_REASONS == frozenset({
        "no-fused-builder", "uncertified-rdma-schedule",
        "interior-resident-state", "policy-disabled",
        "no-segment-factory", "rebuild-no-segment-factory",
    })
    d = ms.decline("jacobi", "xla", "free-form prose")
    assert d.code == ms.DECLINE_NO_BUILDER  # the default
    d = ms.decline("jacobi", "overlap", "gate said no",
                   code=ms.DECLINE_UNCERTIFIED_SCHEDULE)
    assert not d and d.code == "uncertified-rdma-schedule"
    with pytest.raises(ValueError, match="unknown decline code"):
        ms.decline("jacobi", "xla", "typo", code="not-a-real-code")


def test_astaroth_fast_path_declines_loudly():
    """The interior-resident MHD fast paths decline with the
    extract/loop/insert reason (their state lives outside dd.curr)."""
    import jax

    from stencil_tpu.models.astaroth import Astaroth
    from stencil_tpu.parallel.megastep import SegmentDecline

    a = Astaroth(16, 16, 16, mesh_shape=(1, 1, 1),
                 devices=jax.devices()[:1], dtype=np.float32,
                 kernel="wrap")
    d = a.make_segment(2)
    assert not d and isinstance(d, SegmentDecline)
    assert d.model == "astaroth" and d.path == "wrap"
    assert "extract/loop/insert" in d.reason


# ----------------------------------------------------------------------
# the in-graph probe trace
# ----------------------------------------------------------------------
def test_segment_trace_rows_and_metrics():
    from stencil_tpu.telemetry.probe import StepMetrics

    j = make_jacobi()
    m = StepMetrics(j.dd)
    seg = j.make_segment(6, probe_every=2, metrics=m)
    tr = seg.run(10)
    assert tr.steps == (2, 4, 6)
    assert tr.abs_steps == [12, 14, 16]
    host = np.asarray(tr.array)
    # columns: temp, substeps, wire_bytes; rows replicated f32
    assert host.shape == (3, 2, 3)
    np.testing.assert_array_equal(host[:, 0, 1], [12.0, 14.0, 16.0])
    np.testing.assert_allclose(
        host[:, 0, 2],
        [m.cumulative_bytes(s) for s in (12, 14, 16)], rtol=1e-6)
    # health columns are real: nonfinite 0, max-abs 1 (hot sphere)
    assert host[0, 0, 0] == 0.0
    assert host[0, 1, 0] == pytest.approx(1.0)


def test_sentinel_locates_exact_tripped_step_in_trace():
    """A NaN planted mid-segment: the trace row of ITS step trips, with
    earlier rows clean — the driver learns the exact step without
    replaying the segment."""
    from stencil_tpu.resilience.health import HealthSentinel

    j = make_jacobi()
    s = HealthSentinel(j.dd)
    clean = j.dd.curr["temp"]
    rows = []
    for i in range(4):
        p = clean if i < 2 else clean.at[3, 3, 3].set(float("nan"))
        rows.append(jnp.stack([
            jnp.stack([jnp.sum(~jnp.isfinite(p)).astype(jnp.float32)]),
            jnp.stack([jnp.max(jnp.abs(jnp.nan_to_num(p)))]),
        ]))
    s.observe_segment(jnp.stack(rows), steps=[5, 6, 7, 8])
    results = s.poll(block=True)
    assert [r.step for r in results] == [5, 6, 7, 8]
    assert [r.tripped for r in results] == [False, False, True, True]
    assert s.tripped.step == 7


def test_driver_fused_equals_stepwise(tmp_path):
    """run_resilient fused (default) vs fuse_segments=False: identical
    final state, identical checkpoint trail."""
    from stencil_tpu.resilience import ResiliencePolicy

    def pol(fused):
        return ResiliencePolicy(check_every=3, ckpt_every=4,
                                base_delay=0.0, sleep=lambda s: None,
                                fuse_segments=fused)

    a = make_jacobi()
    ra = a.run_resilient(10, policy=pol(True),
                         ckpt_dir=str(tmp_path / "fused"))
    b = make_jacobi()
    rb = b.run_resilient(10, policy=pol(False),
                         ckpt_dir=str(tmp_path / "stepwise"))
    assert ra.steps == rb.steps == 10
    np.testing.assert_array_equal(a.temperature(), b.temperature())
    from stencil_tpu.utils.checkpoint import all_steps
    assert sorted(all_steps(str(tmp_path / "fused"))) == \
        sorted(all_steps(str(tmp_path / "stepwise")))


def test_driver_fused_rollback_bitwise(tmp_path):
    """A NaN inside a fused segment: rollback restores and the final
    state is bitwise-equal to the fault-free run — with the trip
    located at the exact injected step in the event log."""
    from stencil_tpu.resilience import (FaultPlan, NaNInjection,
                                        ResiliencePolicy)

    clean = make_jacobi()
    clean.run(12)

    j = make_jacobi()
    plan = FaultPlan(nans=[NaNInjection(step=7)])
    rep = j.run_resilient(
        12, policy=ResiliencePolicy(check_every=4, ckpt_every=4,
                                    base_delay=0.0,
                                    sleep=lambda s: None),
        ckpt_dir=str(tmp_path), faults=plan)
    assert rep.steps == 12 and rep.rollbacks == 1
    trips = [e for e in rep.events if e["event"] == "sentinel_tripped"]
    assert trips and trips[0]["step"] == 7
    np.testing.assert_array_equal(j.temperature(), clean.temperature())


# ----------------------------------------------------------------------
# DistributedDomain.make_segment (the generic entry)
# ----------------------------------------------------------------------
def test_domain_make_segment_generic():
    from stencil_tpu.distributed import DistributedDomain
    from stencil_tpu.geometry import Radius
    from stencil_tpu.parallel.exchange import exchange_shard
    from stencil_tpu.parallel.mesh import mesh_dim

    dd = DistributedDomain(16, 16, 16)
    dd.set_mesh_shape((2, 2, 2))
    dd.set_radius(1)
    dd.add_data("a", np.float32)
    dd.add_data("b", np.float32)
    dd.realize()
    counts = mesh_dim(dd.mesh)
    radius = Radius.constant(1)

    def shard_step(fields):
        out = {}
        for q, p in fields.items():
            p = exchange_shard(p, radius, counts)
            out[q] = p * 0.5
        return out

    dd.curr["a"] = dd.curr["a"] + 1.0
    dd.curr["b"] = dd.curr["b"] + 2.0
    seg = dd.make_segment(shard_step, check_every=3)
    tr = seg.run(0)
    assert tr.steps == (1, 2, 3)
    host = np.asarray(tr.array)
    assert host.shape == (3, 2, 2)  # rows x (nonfinite,max) x {a,b}
    np.testing.assert_allclose(host[:, 1, 0], [0.5, 0.25, 0.125])
    np.testing.assert_allclose(host[:, 1, 1], [1.0, 0.5, 0.25])
    np.testing.assert_allclose(np.asarray(dd.curr["a"]),
                               np.full_like(host[0, 0, 0], 0.125),
                               rtol=0)


# ----------------------------------------------------------------------
# astaroth: accumulator carry
# ----------------------------------------------------------------------
def test_astaroth_segment_accumulator_carry():
    """Fused RK3 segments vs stepwise: <= 1 ULP on the fields AND the
    carried w accumulators (float64 on CPU pins the comparison)."""
    from stencil_tpu.models.astaroth import Astaroth, MhdParams

    prm = MhdParams()
    a = Astaroth(8, 8, 8, params=prm, mesh_shape=(2, 2, 2),
                 dtype=np.float64)
    a.init()
    b = Astaroth(8, 8, 8, params=prm, mesh_shape=(2, 2, 2),
                 dtype=np.float64)
    b.init()
    for _ in range(2):
        a.step()
    seg = b.make_segment(2)
    tr = seg.run(0)
    assert tr.steps == (1, 2)
    assert np.asarray(tr.array).shape == (2, 2, 8)
    for q in ("lnrho", "uux", "ax", "ss"):
        np.testing.assert_allclose(b.field(q), a.field(q),
                                   rtol=1e-12, atol=1e-15)
        np.testing.assert_allclose(np.asarray(b._w[q]),
                                   np.asarray(a._w[q]),
                                   rtol=1e-12, atol=1e-15)


def _astaroth_temporal_pair(s, size, iters, check_every):
    """(stepwise_fields, fused_engine) for the temporal path at depth
    ``s``: the reference runs the blocked loop, the other runs ONE
    fused segment — the same lcm(3, s)-period group sequence."""
    import jax

    from stencil_tpu.models.astaroth import Astaroth
    from stencil_tpu.parallel.methods import Method

    devs = jax.devices()[:2]

    def mk():
        a = Astaroth(*size, mesh_shape=(1, 1, 2), devices=devs,
                     dtype=np.float64, kernel="xla",
                     methods=Method.PpermuteSlab, exchange_every=s)
        a.init()
        return a

    a, b = mk(), mk()
    assert a.kernel_path == f"xla-temporal[s={s}]"
    a.run(iters)
    seg = b.make_segment(check_every)
    assert seg and seg.steps == check_every
    done = 0
    while done < iters:
        k = min(check_every, iters - done)
        s2 = b.make_segment(k) if k != check_every else seg
        s2.run(done)
        done += k
    return a, b


def test_astaroth_temporal_segment_s2_group_straddle():
    """s=2 fused segments vs the blocked loop, <= 1 ULP (f64): the
    lcm(3,2)=6-substep period straddles iteration boundaries, so two
    of three groups start at alpha != 0 and ship the w carry in the
    deep exchange — the group-straddle case, INSIDE one fused
    program."""
    from stencil_tpu.models.astaroth import FIELDS

    a, b = _astaroth_temporal_pair(2, (8, 8, 16), iters=6,
                                   check_every=4)
    for q in FIELDS:
        np.testing.assert_allclose(b.field(q), a.field(q), rtol=1e-12,
                                   atol=1e-16, err_msg=q)
        np.testing.assert_allclose(np.asarray(b._w[q]),
                                   np.asarray(a._w[q]),
                                   rtol=1e-12, atol=1e-16, err_msg=q)


@pytest.mark.slow
def test_astaroth_temporal_segment_s3():
    """s=3 (period == 3: every group starts at alpha_0 == 0, w never
    rides the wire): fused segments match the blocked loop <= 1 ULP,
    with an uneven check_every exercising the tail-iteration chunks."""
    from stencil_tpu.models.astaroth import FIELDS

    # every per-shard axis (unsharded ones included — the local
    # periodic wrap ships s*r rows too) must be >= the deepened
    # radius 9, hence 9x9 cross-sections
    a, b = _astaroth_temporal_pair(3, (9, 9, 20), iters=3,
                                   check_every=2)
    for q in FIELDS:
        np.testing.assert_allclose(b.field(q), a.field(q), rtol=1e-12,
                                   atol=1e-16, err_msg=q)


# ----------------------------------------------------------------------
# decline visibility: fused: false is a reported fact, not a silence
# ----------------------------------------------------------------------
def test_driver_reports_fused_decline(tmp_path):
    """A declining path under the fused-by-default driver: the report
    says fused: false with the decline reason, the event log carries
    fused_decline, and the stencil_run_fused_dispatch_total{fused}
    counter accumulates the stepwise dispatches. (Certificate-gated
    overlap declines are pinned by
    test_overlap_path_declines_on_unsafe_certificate; here a declining
    factory drives the DRIVER's visibility contract without needing
    interpreted remote DMA to execute steps.)"""
    from stencil_tpu.parallel.megastep import decline
    from stencil_tpu.resilience import ResiliencePolicy
    from stencil_tpu.resilience.driver import run_resilient
    from stencil_tpu.telemetry import get_registry

    c = get_registry().counter("stencil_run_fused_dispatch_total", "")
    before_f = c.value(fused="false")
    before_t = c.value(fused="true")
    j = make_jacobi()
    rep = run_resilient(
        j.dd, j.step, 4,
        policy=ResiliencePolicy(check_every=2, base_delay=0.0,
                                sleep=lambda s: None),
        make_segment=lambda k, pe, m: decline(
            "jacobi", "overlap",
            "uncertified RDMA schedule: replay_safe=false (test stub)",
            code="uncertified-rdma-schedule"))
    assert rep.steps == 4
    assert rep.fused is False
    assert "RDMA" in rep.fused_decline_reason
    assert rep.fused_decline_code == "uncertified-rdma-schedule"
    declines = [e for e in rep.events if e["event"] == "fused_decline"]
    assert declines and declines[0]["model"] == "jacobi"
    assert declines[0]["path"] == "overlap"
    assert c.value(fused="false") - before_f == 4
    assert c.value(fused="true") == before_t
    # the record round-trips the verdict (chaos-smoke CI artifact)
    assert rep.to_record()["fused"] is False


def test_driver_reports_fused_true():
    from stencil_tpu.resilience import ResiliencePolicy
    from stencil_tpu.telemetry import get_registry

    c = get_registry().counter("stencil_run_fused_dispatch_total", "")
    before_t = c.value(fused="true")
    j = make_jacobi()
    rep = j.run_resilient(
        4, policy=ResiliencePolicy(check_every=2, base_delay=0.0,
                                   sleep=lambda s: None))
    assert rep.fused is True and rep.fused_decline_reason == ""
    assert not [e for e in rep.events
                if e["event"] == "fused_decline"]
    assert c.value(fused="true") - before_t >= 2


# ----------------------------------------------------------------------
# ensemble: batched segments
# ----------------------------------------------------------------------
def test_ensemble_segment_matches_stepwise_run():
    from stencil_tpu.serving.ensemble import EnsembleJacobi

    a = EnsembleJacobi(4, 16, 16, 16, mesh_shape=(2, 2, 2))
    a.init()
    a.set_member_params(2, {"hot_temp": 1.25})
    b = EnsembleJacobi(4, 16, 16, 16, mesh_shape=(2, 2, 2))
    b.init()
    b.set_member_params(2, {"hot_temp": 1.25})
    a.run(5)
    tr = b.run_segment(5)
    assert tr.steps == (1, 2, 3, 4, 5)
    host = np.asarray(tr.array)
    assert host.shape == (5, 4, 2, 1)  # rows x members x stats x temp
    assert not host[:, :, 0, :].any()  # all members finite throughout
    for k in range(4):
        np.testing.assert_array_equal(a.member_interior("temp", k),
                                      b.member_interior("temp", k))


def test_ensemble_segment_trace_isolates_tripped_member():
    from stencil_tpu.serving.ensemble import (EnsembleJacobi,
                                              EnsembleSentinel)

    eng = EnsembleJacobi(4, 16, 16, 16, mesh_shape=(2, 2, 2))
    eng.init()
    host = eng.member_interior("temp", 1)
    host[0, 0, 0] = np.nan
    eng.set_member_interior("temp", 1, host)
    sentinel = EnsembleSentinel(eng)
    tr = eng.run_segment(3)
    sentinel.observe_segment(tr.array, [r for r in tr.steps])
    healths = sentinel.poll(block=True)
    assert [h.step for h in healths] == [1, 2, 3]
    for h in healths:
        assert h.tripped_members == [1]


# ----------------------------------------------------------------------
# registry gates
# ----------------------------------------------------------------------
def test_megastep_registry_targets_prove_exact_counts():
    """The shipped megastep targets pass: k x per-step ppermutes + one
    all-reduce per probe row, bytes exactly k x the per-step model."""
    from stencil_tpu.analysis import run_targets
    from stencil_tpu.analysis.hlo import lowering_supported
    from stencil_tpu.analysis.registry import default_targets

    if not lowering_supported():
        pytest.skip("StableHLO lowering unavailable")
    targets = [t for t in default_targets() if "megastep" in t.name]
    assert {t.name for t in targets} == {
        "parallel.megastep.segment[k=4,hlo]",
        "parallel.megastep.segment[k=4,cost]",
        # the dataflow audits of the same fused program (PR 9)
        "parallel.megastep.segment[k=4,donation]",
        "parallel.megastep.segment[k=4,transfer]",
        "parallel.megastep.segment[k=4,recompile]",
        # the fused RDMA segment's schedule certificate (PR 16);
        # pinned by test_lint's schedule tests, excluded from the
        # collective-count audit below (it is traced, not lowered)
        "analysis.schedule.parallel.megastep.segment[overlap,k=4]",
        # the fused segment's dtype-flow certificate (PR 17); pinned
        # by test_lint's precision tests, likewise traced not lowered
        "analysis.precision.parallel.megastep.segment"}
    targets = [t for t in targets
               if t.checker not in ("schedule", "precision")]
    report = run_targets(targets)
    assert not report.findings, report.findings
    hlo = report.metrics["hlo:parallel.megastep.segment[k=4,hlo]"]
    assert hlo["collectives"]["collective_permute"]["count"] == 24
    assert hlo["collectives"]["all_reduce"]["count"] == 2
    cost = report.metrics[
        "costmodel:parallel.megastep.segment[k=4,cost]"]
    # exact-byte cross-check: observed == expected == k x per-step
    assert cost["observed_bytes_per_shard"] == \
        cost["expected_bytes_per_shard"]


def test_carry_contract_registry_targets_prove_exact_counts():
    """The segment compiler's per-model carry contracts, pinned: a
    fused PIC segment lowers to exactly k x 18 collective-permutes +
    one probe all-reduce per trace row with HLO-exact bytes AND the
    full (2, 9) probe column set; the astaroth temporal segment pays
    exactly its lcm(3, s)-period grouped deep exchanges (w riding only
    where a group starts at alpha != 0) — k x the amortized
    deep-exchange model, byte-exact."""
    from stencil_tpu.analysis import run_targets
    from stencil_tpu.analysis.hlo import lowering_supported
    from stencil_tpu.analysis.registry import default_targets

    if not lowering_supported():
        pytest.skip("StableHLO lowering unavailable")
    targets = [t for t in default_targets()
               if "models.pic.segment" in t.name
               or "models.astaroth.segment" in t.name]
    assert {t.name for t in targets} == {
        "models.pic.segment[k=4,hlo]",
        "models.pic.segment[k=4,cost]",
        "models.pic.segment[k=4,probe]",
        "models.pic.segment[k=4,donation]",
        "models.astaroth.segment[temporal,s=2,k=4,hlo]",
        "models.astaroth.segment[temporal,s=2,k=4,cost]",
        # the segments' dtype-flow certificates (PR 17) — pinned by
        # test_lint's precision tests, not re-certified here
        "analysis.precision.models.pic.segment",
        "analysis.precision.models.astaroth.segment"}
    targets = [t for t in targets if t.checker != "precision"]
    report = run_targets(targets)
    assert not report.findings, [str(f) for f in report.findings]
    pic = report.metrics["hlo:models.pic.segment[k=4,hlo]"]
    assert pic["collectives"]["collective_permute"]["count"] == 72
    assert pic["collectives"]["all_reduce"]["count"] == 2
    # the probe bill: 2 rows x (2, 9) f32 — overflow column included
    assert pic["collectives"]["all_reduce"]["bytes_per_shard"] == 144
    cost = report.metrics["costmodel:models.pic.segment[k=4,cost]"]
    assert cost["observed_bytes_per_shard"] == \
        cost["expected_bytes_per_shard"]
    ast = report.metrics[
        "hlo:models.astaroth.segment[temporal,s=2,k=4,hlo]"]
    # 2 period chunks x (8 + 16 + 16 quantities) x 2 ppermutes on the
    # one active axis — the w-carrying groups double their quantities
    assert ast["collectives"]["collective_permute"]["count"] == 160
    acost = report.metrics[
        "costmodel:models.astaroth.segment[temporal,s=2,k=4,cost]"]
    assert acost["observed_bytes_per_shard"] == \
        acost["expected_bytes_per_shard"]


def test_reprobed_megastep_fixture_flagged():
    """The negative control — a fused segment re-reducing the probe on
    every sub-step — is flagged with a nonzero CLI exit."""
    from stencil_tpu.analysis.hlo import lowering_supported

    if not lowering_supported():
        pytest.skip("StableHLO lowering unavailable")
    proc = subprocess.run(
        [sys.executable, "-m", "stencil_tpu.analysis",
         str(BAD_FIXTURE)],
        capture_output=True, text=True,
        cwd=str(Path(__file__).parent.parent), timeout=600)
    assert proc.returncode != 0, proc.stdout + proc.stderr
    assert "all_reduce" in proc.stdout
    assert "requires exactly 2" in proc.stdout
