"""stencil-lint: each checker proven positive AND negative.

Positive: the shipped registry is clean (the same property CI's lint
stage gates on). Negative: every fixture under tests/fixtures/lint/
is flagged by its checker — the pass is not vacuously green. Plus CLI
exit codes and the JSON artifact schema. Everything here is pure
tracing: no kernel executes, so this runs identically with or without
a TPU/interpreter.
"""

import json
import pathlib

import pytest

from stencil_tpu.analysis import Finding, Report, run_targets
from stencil_tpu.analysis.footprint import required_radius
from stencil_tpu.analysis.registry import default_targets, load_targets

FIXTURES = pathlib.Path(__file__).parent / "fixtures" / "lint"


# ---------------------------------------------------------------------------
# positive: shipped code is clean


@pytest.fixture(scope="module")
def full_report():
    """One run of all ten checkers over the shipped registry, shared
    by every test that asserts on it (the donation block compiles all
    its entry points — paying that once per module, not per test)."""
    return run_targets(default_targets())


def test_shipped_registry_is_clean(full_report):
    """The acceptance property: every registered op, DMA kernel and
    exchange path upholds its contract — zero errors, zero warnings
    (a warning would mean a shipped path went statically unverifiable
    without anyone deciding that)."""
    report = full_report
    assert report.findings == [], [str(f) for f in report.findings]
    # the committed coverage floor — read from the SAME file CI stage 1
    # ratchets against, so the two gates cannot drift
    floor_file = pathlib.Path(__file__).parent.parent / "ci" / \
        "registry_floor.txt"
    floor = int(floor_file.read_text().split()[0])
    assert floor >= 105  # the PR 9 acceptance criterion itself
    assert len(report.targets_checked) >= floor
    assert report.ok
    # all thirteen checkers actually ran (and were timed)
    assert set(report.checker_seconds) == {
        "footprint", "dma", "collectives", "hlo", "costmodel", "vmem",
        "donation", "transfer", "recompile", "tiling", "linkmap",
        "schedule", "precision"}


def test_checker_filter():
    report = run_targets(default_targets(), checkers=["collectives"])
    assert report.ok
    assert all(t.startswith(("parallel.exchange", "parallel.temporal",
                             "parallel.migrate", "serving.ensemble"))
               for t in report.targets_checked)
    with pytest.raises(ValueError):
        run_targets([], checkers=["nope"])


def test_costmodel_cross_check_not_vacuous():
    """The analytic-vs-HLO byte cross-check must actually compare
    nonzero numbers on every ppermute exchange method (a lowering
    regression detector that observes zero bytes detects nothing).
    Skips only where this JAX cannot produce StableHLO at all."""
    from stencil_tpu.analysis.hlo import lowering_supported

    if not lowering_supported():
        pytest.skip("no StableHLO lowering in this JAX/backend")
    report = run_targets(default_targets(), checkers=["costmodel"])
    assert report.ok
    compared = [m for m in report.metrics.values()
                if "observed_bytes_per_shard" in m]
    assert len(compared) >= 6
    for m in compared:
        assert m["observed_bytes_per_shard"] > 0
        assert (m["observed_bytes_per_shard"]
                == m["expected_bytes_per_shard"])


def test_hlo_registry_collective_permute_only():
    """The acceptance criterion: every registered ppermute exchange
    method lowers to collective-permute ONLY (the all-gather control
    path is pinned to all_gather; the Pallas method is capability-
    gated off-TPU, recorded as a skip, never silently green)."""
    from stencil_tpu.analysis.hlo import lowering_supported

    if not lowering_supported():
        pytest.skip("no StableHLO lowering in this JAX/backend")
    report = run_targets(default_targets(), checkers=["hlo"])
    assert report.ok
    kinds_by_target = {}
    for key, m in report.metrics.items():
        if "collectives" in m:
            kinds_by_target[key] = set(m["collectives"])
    for key, kinds in kinds_by_target.items():
        if "allgather" in key.lower():
            assert kinds == {"all_gather"}, (key, kinds)
        elif ("resilience.health" in key
              or "serving.ensemble.probe" in key
              or "models.pic.probe" in key
              or "telemetry." in key
              or "parallel.megastep" in key
              or ".segment[" in key
              or "observatory.attribution" in key):
            # (the observatory's attributed segment IS the megastep
            # program — identical HLO is the whole point — so it
            # carries the same one-reduce-per-probe-row contract)
            # the health sentinels' contract is different by design:
            # exactly ONE small all-reduce (pinned via exact_counts on
            # their HloSpecs; the ensemble probe batches per-member
            # stats through the same single reduce, the telemetry
            # step-metrics columns ride that same reduce — never a
            # second one — and the fused megastep carries one such
            # reduce per declared probe row)
            assert kinds <= {"collective_permute", "all_reduce"}, \
                (key, kinds)
        else:
            assert kinds <= {"collective_permute"}, (key, kinds)
    assert any("collective_permute" in k
               for k in kinds_by_target.values())


# ---------------------------------------------------------------------------
# negative controls: one per checker, with the finding shape pinned


def test_footprint_fixture_flagged():
    report = run_targets(load_targets(FIXTURES / "bad_footprint.py"))
    assert not report.ok
    msgs = {f.target: f.message for f in report.errors}
    # the understated 5-point z stencil: both z faces under-declared
    assert any("(0, 0, 1)" in m and "declared radius 1 < required 2" in m
               for t, m in msgs.items()
               if t == "fixture.wide5_z_radius_understated"), msgs
    # diagonal access with zero edge radius: flagged in (1,1,0) ONLY
    edge = [f for f in report.errors
            if f.target == "fixture.cross_with_zero_edge_radius"]
    assert len(edge) == 1 and "(1, 1, 0)" in edge[0].message, edge
    # asymmetric: the -x side specifically
    assert any("(-1, 0, 0)" in f.message for f in report.errors
               if f.target == "fixture.asymmetric_minus_x_understated")
    # alias propagation: the access slices `padded * 0.5`, not padded
    assert any("(0, 1, 0)" in f.message and "required 2" in f.message
               for f in report.errors
               if f.target == "fixture.laundered_through_elementwise")


def test_temporal_fixture_flagged():
    """A blocked kernel whose sub-step window forgot to shrink reads
    depth 3 against a deepened depth-2 halo contract — the footprint
    checker must catch the fused program's total reach."""
    report = run_targets(load_targets(FIXTURES / "bad_temporal.py"))
    assert not report.ok
    errs = [f for f in report.errors
            if f.target == "fixture.temporal_substep_reads_past_deep_halo"]
    assert any("(0, 0, 1)" in f.message
               and "declared radius 2 < required 3" in f.message
               for f in errs), [str(f) for f in errs]
    assert any("(0, 0, -1)" in f.message for f in errs)


def test_dma_fixture_flagged():
    report = run_targets(load_targets(FIXTURES / "bad_dma.py"))
    assert not report.ok
    by_target = {}
    for f in report.errors:
        by_target.setdefault(f.target.split(":")[0], []).append(f.message)
    assert any("never awaited" in m
               for m in by_target["fixture.remote_dma_missing_wait"])
    assert any("before any neighbor barrier" in m
               for m in by_target["fixture.remote_dma_missing_barrier"])
    assert any("re-armed while" in m
               for m in by_target["fixture.semaphore_reused_in_flight"])
    assert any("barrier wait value 2 != 1" in m
               for m in by_target["fixture.barrier_signal_wait_mismatch"])


def test_schedule_fixture_flagged():
    """The two replay-soundness negative controls, each named by its
    violated condition: in-flight aliasing across sub-steps vs the
    cross-shard wait-cycle deadlock."""
    report = run_targets(load_targets(FIXTURES / "bad_schedule.py"))
    assert not report.ok
    by_target = {}
    for f in report.errors:
        by_target.setdefault(f.target.split(":")[0], []).append(f.message)
    assert any("in-flight aliasing across sub-steps" in m
               for m in by_target["fixture.schedule_slot_reuse_under_replay"])
    assert any("deadlock cycle" in m
               for m in by_target["fixture.schedule_wait_cycle_deadlock"])
    # the certificates say WHY in the metrics artifact too
    slot = report.metrics[
        "schedule:fixture.schedule_slot_reuse_under_replay"]
    assert slot["replay_safe"] is False
    assert any(not k["replay_safe"] for k in slot["kernels"].values())


def test_schedule_registry_certifies_fused_kernels(full_report):
    """The proof megastep consumes: every schedule target the segment
    compiler fuses through (``fused_by_megastep``) holds a
    ``replay_safe`` certificate with the pinned in-flight peak — and
    at least one production RDMA kernel earns it."""
    fused = {name: m for name, m in full_report.metrics.items()
             if name.startswith("schedule:") and m.get("fused_by_megastep")}
    assert any("jacobi7_overlap_pallas" in name for name in fused), \
        list(full_report.metrics)
    for name, m in fused.items():
        assert m["replay_safe"] is True, (name, m)
    overlap = full_report.metrics[
        "schedule:analysis.schedule.ops.pallas_overlap."
        "jacobi7_overlap_pallas[k=4]"]
    assert overlap["max_in_flight"] == 4
    assert overlap["replay"] == 4


def test_precision_fixture_flagged():
    """The three dtype-flow negative controls, each named by its
    violated condition: the bf16 psum sold as f32 (condition (a)),
    the silent in-step narrowing, and the double-quantized wire hop
    (condition (c))."""
    report = run_targets(load_targets(FIXTURES / "bad_precision.py"))
    assert not report.ok
    by_target = {}
    for f in report.errors:
        by_target.setdefault(f.target.split(":")[0], []).append(f.message)
    assert any("(a) accumulation below the compute floor" in m
               for m in by_target["fixture.precision_bf16_psum_sold_as_f32"])
    assert any("silent convert" in m
               for m in by_target["fixture.precision_silent_step_narrowing"])
    assert any("(c) double quantization" in m
               for m in by_target[
                   "fixture.precision_double_quantized_wire_hop"])
    # the certificates say WHY in the metrics artifact too
    psum = report.metrics["precision:fixture.precision_bf16_psum_sold_as_f32"]
    assert psum["safe"] is False
    assert psum["narrowest_accum"] == "bfloat16"
    silent = report.metrics[
        "precision:fixture.precision_silent_step_narrowing"]
    assert silent["silent_converts"] == [
        {"from": "float32", "to": "bfloat16", "count": 1}]


def test_precision_registry_certifies_shipped_paths(full_report):
    """The proof the wire-format gate consumes: EVERY registered entry
    point holds a ``safe`` certificate with zero silent converts, the
    declared-bf16 exchange targets carry exactly the bf16 wire dtype on
    every narrowing axis with the analytic 2^-8 bound, and the f32
    paths certify bitwise-identity wire (bound 0.0)."""
    certs = {name: m for name, m in full_report.metrics.items()
             if name.startswith("precision:")}
    assert len(certs) >= 13, list(certs)
    for name, m in certs.items():
        assert m["safe"] is True, (name, m)
        assert m["silent_converts"] == [], (name, m)
    bf16 = full_report.metrics[
        "precision:analysis.precision.parallel.exchange."
        "make_exchange[PpermuteSlab,wire=bf16]"]
    assert bf16["max_rel_error_bound"] == 2.0 ** -8
    for ax, rec in bf16["wire_dtypes"].items():
        if rec["declared"] == "bf16":
            assert rec["dtypes"] == ["bfloat16"], (ax, rec)
    f32 = full_report.metrics[
        "precision:analysis.precision.parallel.exchange."
        "make_exchange[PpermuteSlab]"]
    assert f32["max_rel_error_bound"] == 0.0
    # accumulation floor held everywhere it was observed
    for name, m in certs.items():
        if m["narrowest_accum"] is not None:
            assert m["narrowest_accum"] in ("float32", "float64"), \
                (name, m)


def test_collectives_fixture_flagged():
    report = run_targets(load_targets(FIXTURES / "bad_collective.py"))
    assert not report.ok
    msgs = {f.target: f.message for f in report.errors}
    assert "duplicated destination" in \
        msgs["fixture.ppermute_duplicate_destination"]
    assert "outside [0, 2)" in msgs["fixture.ppermute_index_out_of_range"]
    assert "not a full bijection" in \
        msgs["fixture.ppermute_partial_ring"]


def test_hlo_fixture_flagged():
    from stencil_tpu.analysis.hlo import lowering_supported

    if not lowering_supported():
        pytest.skip("no StableHLO lowering in this JAX/backend")
    report = run_targets(load_targets(FIXTURES / "bad_hlo.py"))
    assert not report.ok
    msgs = {f.target: f.message for f in report.errors}
    # the accidental all-gather from "fixing" mismatched out_specs
    assert "stablehlo.all_gather" in \
        msgs["fixture.allgather_via_mismatched_out_specs"]
    # a psum left in the hot step lowers to all-reduce
    assert "stablehlo.all_reduce" in msgs["fixture.psum_in_step"]
    # the costmodel catches a radius-2 exchange sold as radius-1
    m = msgs["fixture.exchange_moves_more_than_model"]
    assert "2304 B/shard" in m and "1152 B/shard" in m and "+100.0%" in m


def test_plan_fixture_flagged():
    """A tampered/buggy tuned plan that silently enables the AllGather
    strategy must trip the registry's ppermute-only HLO gate — the
    negative control proving tuned-plan coverage is not vacuous."""
    from stencil_tpu.analysis.hlo import lowering_supported

    if not lowering_supported():
        pytest.skip("no StableHLO lowering in this JAX/backend")
    report = run_targets(load_targets(FIXTURES / "bad_plan.py"))
    assert not report.ok
    msgs = {f.target: f.message for f in report.errors}
    assert "stablehlo.all_gather" in \
        msgs["fixture.plan_silently_enables_allgather"]


def test_tuner_emittable_configs_are_registered():
    """Every (method, depth) configuration the autotuner's candidate
    space can emit on a capability-complete backend has a tuning.plan
    HLO target in the shipped registry (the Auto manifest entry's
    substance)."""
    from stencil_tpu.tuning.plan import DEFAULT_DEPTHS, PLAN_METHODS

    names = _registry_names()
    for method in PLAN_METHODS:
        depths = DEFAULT_DEPTHS if method in (
            "PpermuteSlab", "PpermutePacked") else (1,)
        for s in depths:
            assert f"tuning.plan[{method},s={s},hlo]" in names, \
                f"emittable plan config {method} s={s} unregistered"


def test_donation_fixture_flagged():
    """Both donation-death modes are caught: a jit that lost its
    donate_argnums, and a donated buffer XLA silently copies because
    the output dtype narrowed."""
    from stencil_tpu.analysis.hlo import lowering_supported

    if not lowering_supported():
        pytest.skip("no StableHLO lowering in this JAX/backend")
    report = run_targets(load_targets(FIXTURES / "bad_donation.py"))
    assert not report.ok
    msgs = {f.target: f.message for f in report.errors}
    assert "missing from the compiled input_output_alias" in \
        msgs["fixture.donation_never_declared"]
    assert "missing from the compiled input_output_alias" in \
        msgs["fixture.donated_but_copied"]
    # donated-bytes metrics computed even for flagged targets
    m = report.metrics["donation:fixture.donation_never_declared"]
    assert m["donated_bytes"] == 8 * 8 * 8 * 4
    assert m["donated_leaves"] == 1 and m["aliased_params"] == []


def test_transfer_fixture_flagged():
    report = run_targets(load_targets(FIXTURES / "bad_transfer.py"))
    assert not report.ok
    msgs = {f.target: f.message for f in report.errors}
    assert "debug_print" in msgs["fixture.debug_print_in_step"]
    assert "pure_callback" in msgs["fixture.pure_callback_in_step"]
    m = report.metrics["transfer:fixture.debug_print_in_step"]
    assert m["host_escapes"] == {"debug_print": 1}


def test_recompile_fixture_flagged():
    """All three fingerprint-drift modes are caught: curr/next dtype
    drift, weak-type promotion of the carried state, and a Python
    scalar passed where the warm path feeds a committed array."""
    report = run_targets(load_targets(FIXTURES / "bad_recompile.py"))
    assert not report.ok
    msgs = {f.target: f.message for f in report.errors}
    assert "dtype drift float32 -> bfloat16" in \
        msgs["fixture.carry_dtype_drift"]
    assert "weak-type promotion" in msgs["fixture.weak_type_promotion"]
    assert "Python scalar" in msgs["fixture.python_scalar_arg"]
    # the abstract-fingerprint manifest is still recorded
    m = report.metrics["recompile:fixture.carry_dtype_drift"]
    assert len(m["fingerprint"]) == 64 and m["carry_leaves"] == 1


def test_dataflow_entry_points_all_pass(full_report):
    """The acceptance criterion: every registered production entry
    point — the model step loops, every runnable make_exchange method,
    the fused megastep segments, and the ensemble step/segment/lane
    programs — is donation-clean, transfer-clean, and single-compile
    (its abstract fingerprint is dispatch-stable). Asserted on the
    shared nine-checker report (one registry run per module)."""
    from stencil_tpu.analysis.hlo import lowering_supported

    if not lowering_supported():
        pytest.skip("no StableHLO lowering in this JAX/backend")
    report = full_report
    dataflow = [f for f in report.findings
                if f.checker in ("donation", "transfer", "recompile")]
    assert dataflow == [], [str(f) for f in dataflow]
    names = set(report.targets_checked)
    # every runnable exchange method's orchestrator donates
    for method in ("PpermuteSlab", "PpermutePacked", "AllGather"):
        assert (f"parallel.exchange.make_exchange[{method},donation]"
                in names), names
    # the megastep + ensemble entry points carry all three audits
    for suffix in ("donation", "transfer", "recompile"):
        assert f"parallel.megastep.segment[k=4,{suffix}]" in names
        assert f"serving.ensemble.step[N=4,{suffix}]" in names
        assert f"serving.ensemble.segment[N=4,k=2,{suffix}]" in names
        assert f"models.jacobi.step_n[xla,{suffix}]" in names
        assert f"models.astaroth.iter_n[{suffix}]" in names
    # donated-bytes metrics are live for the model steps
    m = report.metrics["donation:models.jacobi.step_n[xla,donation]"]
    assert m["donated_bytes"] > 0
    assert m["aliased_params"] and 0 in m["aliased_params"]


def test_tiling_fixture_flagged():
    """The SNIPPETS.md 512^3 failure as a negative control: the Jacobi
    halo kernel pinned to the old default (16, 128) block shape is
    flagged at the PHYSICAL budget (its raised vmem_limit_bytes hid it
    from the plain vmem checker) and the finding carries the planner's
    concrete prescription — the (8, 128) shape the registry's legal
    512^3 target proves clean."""
    report = run_targets(load_targets(FIXTURES / "bad_tiling.py"))
    assert not report.ok
    (f,) = report.errors
    assert f.checker == "tiling"
    assert f.target.startswith(
        "fixture.jacobi_halo_old_default_shape_at_512")
    assert "20971520 B" in f.message and "exceeds" in f.message
    assert "suggestion: block shape (8, 128)" in f.message


def test_tiling_registry_production_sizes(full_report):
    """The acceptance criterion: every registered Pallas kernel is
    gated at 256^3- AND 512^3-per-device shapes, the Jacobi production
    family (plane/wrap/wrapn/halo/halon) proves LEGAL planner-derived
    shapes at 512^3, and the pinned-infeasible kernels are verdicts,
    not silences (refused or flagged-as-expected, never unaudited)."""
    report = full_report
    tiling = [n for n in report.targets_checked
              if n.startswith("analysis.tiling.")]
    assert len(tiling) >= 28
    for side in (256, 512):
        assert sum(1 for n in tiling if n.endswith(f"[{side}]")) >= 14
    for kernel in ("ops.pallas_stencil.jacobi7_pallas",
                   "ops.pallas_stencil.jacobi7_wrap_pallas",
                   "ops.pallas_stencil.jacobi7_wrapn_pallas[n=2]",
                   "ops.pallas_halo.jacobi7_halo_pallas",
                   "ops.pallas_halo.jacobi7_halon_pallas[n=2]"):
        m = report.metrics[f"tiling:analysis.tiling.{kernel}[512]"]
        assert m["verdict"] == "legal", (kernel, m)
    # the pinned-infeasible kernels record WHY (binding constraint or
    # expected findings), proving the audit has teeth at these sizes
    for kernel in ("ops.pallas_halo.mhd_substep_halo_pallas",
                   "ops.pallas_mhd.mhd_substep_wrap_pallas"):
        m = report.metrics[f"tiling:analysis.tiling.{kernel}[512]"]
        assert m["verdict"] in ("refused-at-build", "refused-at-trace",
                                "flagged-as-expected"), (kernel, m)


def test_linkmap_fixture_flagged():
    """The 6-neighbor-only traffic matrix (corner messages dropped)
    must under-sum against the HLO-extracted bytes and be flagged
    with the zero-corner-share hint."""
    from stencil_tpu.analysis.hlo import lowering_supported

    if not lowering_supported():
        pytest.skip("no StableHLO lowering in this JAX/backend")
    report = run_targets(load_targets(FIXTURES / "bad_linkmap.py"))
    assert not report.ok
    (f,) = report.errors
    assert f.checker == "linkmap"
    assert f.target == "fixture.linkmap_drops_corner_messages"
    assert "B unattributed" in f.message
    assert "6-neighbor-only" in f.message


def test_placement_fixture_flagged():
    """A linkmap target that SHIPS a QAP-refined placement costing
    more than the identity order on its own declared fabric
    (tests/fixtures/lint/bad_placement.py: an x/z transpose that drags
    the fat x faces across the DCN seam) must be flagged by the
    placement-payload re-pricing inside the linkmap checker."""
    from stencil_tpu.analysis.hlo import lowering_supported

    if not lowering_supported():
        pytest.skip("no StableHLO lowering in this JAX/backend")
    report = run_targets(load_targets(FIXTURES / "bad_placement.py"))
    assert not report.ok
    errs = [f for f in report.errors if "placement" in f.message]
    assert errs, [str(f) for f in report.errors]
    (f,) = errs
    assert f.checker == "linkmap"
    assert f.target.startswith("fixture.placement_ships_qap_loser")
    assert "never lose to the identity assignment" in f.message


def test_segment_carry_fixture_flagged():
    """A PIC fused segment whose carry contract DROPS the overflow
    probe column (tests/fixtures/lint/bad_segment_carry.py): every
    trace row's all-reduce shrinks from the contract's (2, 9) to
    (2, 8) f32, so the byte pin must flag the missing column."""
    from stencil_tpu.analysis.hlo import lowering_supported

    if not lowering_supported():
        pytest.skip("no StableHLO lowering in this JAX/backend")
    report = run_targets(load_targets(FIXTURES / "bad_segment_carry.py"))
    assert not report.ok
    (f,) = report.errors
    assert f.checker == "costmodel"
    assert "128 B/shard" in f.message
    assert "144 B/shard" in f.message


def test_linkmap_registry_pins_exact_hlo_bytes(full_report):
    """The acceptance criterion: every observatory.linkmap.* target's
    modeled traffic matrix sums EXACTLY to the HLO-extracted wire
    bytes — slab/packed x s, the all-gather control, migration, and
    the PIC step (accumulate adjoint included)."""
    from stencil_tpu.analysis.hlo import lowering_supported

    if not lowering_supported():
        pytest.skip("no StableHLO lowering in this JAX/backend")
    report = full_report
    keys = [k for k in report.metrics if k.startswith("linkmap:")]
    assert len(keys) >= 9
    for key in keys:
        m = report.metrics[key]
        assert m["matrix_bytes_per_shard"] > 0, key
        assert (m["observed_bytes_per_shard"]
                == m["matrix_bytes_per_shard"]), (key, m)
    for name in ("observatory.linkmap.exchange[r1]",
                 "observatory.linkmap.plan[PpermuteSlab,s=2]",
                 "observatory.linkmap.plan[PpermutePacked,s=4]",
                 "observatory.linkmap.allgather",
                 "observatory.linkmap.migrate",
                 "observatory.linkmap.pic_step"):
        assert f"linkmap:{name}" in report.metrics, name


def test_vmem_fixture_flagged():
    report = run_targets(load_targets(FIXTURES / "bad_vmem.py"))
    assert not report.ok
    by_target = {}
    for f in report.errors:
        by_target.setdefault(f.target.split(":")[0], []).append(f.message)
    assert any("exceeds the 16777216 B budget" in m
               for m in by_target["fixture.block_over_vmem_budget"])
    assert any("lane (last) dim 96 is neither a multiple of 128" in m
               for m in by_target["fixture.misaligned_trailing_tile"])
    assert any("block 8 does not divide the array extent 20" in m
               for m in by_target["fixture.ragged_grid_tiling"])
    # footprint metrics computed even for flagged kernels
    key = "vmem:fixture.block_over_vmem_budget"
    kernels = report.metrics[key]["kernels"]
    (m,) = kernels.values()
    assert m["vmem_estimate_bytes"] == 2 * 2 * 128 * 128 * 128 * 4
    assert m["pipeline_buffers"] == 2


# ---------------------------------------------------------------------------
# unit: the 26-direction requirement formula


def test_required_radius_formula():
    # an access reaching (+3 x, +3 y): edge (1,1,0) needs 3, faces too,
    # and any direction involving z needs nothing
    access = {(0, -1): 0, (0, 1): 3, (1, -1): 0, (1, 1): 3,
              (2, -1): 0, (2, 1): 0}
    req = required_radius([access])
    assert req[(1, 0, 0)] == 3
    assert req[(0, 1, 0)] == 3
    assert req[(1, 1, 0)] == 3
    assert req[(1, 1, 1)] == 0
    assert req[(0, 0, 1)] == 0
    assert req[(-1, 0, 0)] == 0


# ---------------------------------------------------------------------------
# CLI + JSON artifact


def test_cli_exit_codes_and_json(tmp_path):
    from stencil_tpu.analysis.__main__ import main

    out = tmp_path / "report.json"
    # fixtures -> nonzero, and the artifact records the errors
    rc = main(["-q", "--json", str(out),
               str(FIXTURES / "bad_collective.py")])
    assert rc == 1
    data = json.loads(out.read_text())
    assert data["schema_version"] == 2
    assert data["tool"] == "stencil-lint"
    assert data["tool_version"]
    assert data["counts"]["errors"] >= 3
    assert data["counts"]["errors_by_checker"] == {
        "collectives": data["counts"]["errors"]}
    # schema v2: per-checker wall time
    assert set(data["checker_seconds"]) == {"collectives"}
    assert data["checker_seconds"]["collectives"] >= 0
    assert {f["severity"] for f in data["findings"]} == {"error"}
    assert all(set(f) == {"checker", "target", "message", "severity"}
               for f in data["findings"])


def test_cli_list_and_only(capsys, tmp_path):
    from stencil_tpu.analysis import CHECKERS
    from stencil_tpu.analysis.__main__ import main

    assert main(["--list"]) == 0
    out = capsys.readouterr().out
    for name in CHECKERS:
        assert name in out
    # --list also prints the registry target counts per group
    assert "registry targets by group" in out
    for group in ("ops", "parallel", "tuning", "serving", "telemetry",
                  "resilience", "models"):
        assert group in out
    assert "donation=" in out and "recompile=" in out

    # --only restricts the run AND the artifact to one checker
    report = tmp_path / "r.json"
    rc = main(["-q", "--only", "vmem", "--json", str(report),
               str(FIXTURES / "bad_vmem.py")])
    assert rc == 1
    data = json.loads(report.read_text())
    assert set(data["checker_seconds"]) == {"vmem"}
    assert {f["checker"] for f in data["findings"]} == {"vmem"}
    # vmem metrics land keyed by checker:target
    assert any(k.startswith("vmem:fixture.") for k in data["metrics"])


def test_cli_only_accepts_target_globs(tmp_path):
    """--only values that are not checker names filter TARGET names by
    glob: '--only fixture.ppermute_*' runs only the matching targets,
    and composes with a checker-name filter."""
    from stencil_tpu.analysis.__main__ import main

    report = tmp_path / "r.json"
    rc = main(["-q", "--only", "fixture.ppermute_*", "--json",
               str(report), str(FIXTURES / "bad_collective.py")])
    assert rc == 1
    data = json.loads(report.read_text())
    assert data["counts"]["targets"] == 3
    assert all(t.startswith("fixture.ppermute_")
               for t in data["targets_checked"])

    # composed to NOTHING: the glob matches only collectives targets,
    # the checker filter says vmem — a vacuously green run is refused
    # the same way an unmatched glob is
    rc = main(["-q", "--only", "fixture.ppermute_*", "--only", "vmem",
               str(FIXTURES / "bad_collective.py")])
    assert rc == 2
    # composed to SOMETHING: same glob with the matching checker
    rc = main(["-q", "--only", "fixture.ppermute_*", "--only",
               "collectives", "--json", str(report),
               str(FIXTURES / "bad_collective.py")])
    assert rc == 1
    assert json.loads(report.read_text())["counts"]["targets"] == 3

    # literal brackets in target names: fnmatch treats [..] as a
    # character class, so '--only' escapes them — the bracketed
    # schedule fixtureless registry names match as spelled. The
    # fixture's targets carry no brackets, so exercise the escape
    # against the shipped registry spelling instead
    report2 = tmp_path / "r2.json"
    rc = main(["-q", "--only", "analysis.schedule.*[k=4]",
               "--json", str(report2)])
    assert rc == 0
    data2 = json.loads(report2.read_text())
    assert data2["counts"]["targets"] >= 4
    assert all("k=4]" in t for t in data2["targets_checked"])

    # a glob matching nothing is a usage error — even when OTHER
    # patterns matched (a typo'd glob must not silently drop its
    # coverage from a green run)
    rc = main(["-q", "--only", "no.such.target.*",
               str(FIXTURES / "bad_collective.py")])
    assert rc == 2
    rc = main(["-q", "--only", "fixture.ppermute_*",
               "--only", "no.such.target.*",
               str(FIXTURES / "bad_collective.py")])
    assert rc == 2


@pytest.mark.parametrize("fixture", ["bad_footprint.py", "bad_dma.py",
                                     "bad_collective.py", "bad_hlo.py",
                                     "bad_vmem.py", "bad_temporal.py",
                                     "bad_plan.py", "bad_probe.py",
                                     "bad_probe_metrics.py",
                                     "bad_megastep.py",
                                     "bad_donation.py",
                                     "bad_transfer.py",
                                     "bad_recompile.py",
                                     "bad_migration.py",
                                     "bad_attribution.py",
                                     "bad_tiling.py",
                                     "bad_linkmap.py",
                                     "bad_placement.py",
                                     "bad_segment_carry.py",
                                     "bad_schedule.py",
                                     "bad_precision.py",
                                     "bad_packing.py",
                                     "bad_bucketing.py"])
def test_cli_nonzero_on_every_fixture(fixture):
    """The acceptance criterion verbatim: the CLI exits nonzero on
    EVERY negative-control fixture."""
    from stencil_tpu.analysis.__main__ import main

    if fixture in ("bad_hlo.py", "bad_plan.py", "bad_probe.py",
                   "bad_probe_metrics.py", "bad_megastep.py",
                   "bad_donation.py", "bad_migration.py",
                   "bad_linkmap.py", "bad_placement.py",
                   "bad_segment_carry.py", "bad_packing.py"):
        from stencil_tpu.analysis.hlo import lowering_supported

        if not lowering_supported():
            pytest.skip("no StableHLO lowering in this JAX/backend")
    assert main(["-q", str(FIXTURES / fixture)]) == 1


def test_cli_usage_error_on_missing_fixture(tmp_path):
    from stencil_tpu.analysis.__main__ import main

    assert main(["-q", str(tmp_path / "nope.py")]) == 2


def test_report_json_roundtrip():
    r = Report()
    r.targets_checked.append("t")
    r.findings.append(Finding("dma", "t", "boom"))
    d = json.loads(r.to_json())
    assert d["counts"] == {"targets": 1, "errors": 1, "warnings": 0,
                           "errors_by_checker": {"dma": 1}}
    assert not r.ok


def test_vmem_handles_squeezed_block_dims():
    """The standard Pallas squeezed-dim pattern (``None`` in a
    BlockSpec) must audit cleanly — a None dim occupies one array
    slice per grid step, it must not crash the checker (regression:
    the Mapped sentinel is not int()-able)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from stencil_tpu.analysis import VmemSpec, VmemTarget, check_vmem

    def kern(x, o):
        o[...] = x[...]

    def fn(x):
        return pl.pallas_call(
            kern,
            grid=(4,),
            in_specs=[pl.BlockSpec((None, 8, 128), lambda i: (i, 0, 0))],
            out_specs=pl.BlockSpec((None, 8, 128), lambda i: (i, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((4, 8, 128), jnp.float32),
            interpret=False,
        )(x)

    target = VmemTarget(
        "unit.squeezed", lambda: VmemSpec(
            fn=fn, args=(jax.ShapeDtypeStruct((4, 8, 128),
                                              jnp.float32),)))
    findings, metrics = check_vmem(target)
    assert findings == [], [str(f) for f in findings]
    (m,) = metrics["kernels"].values()
    # squeezed z dim counts as 1 slice: 8*128 f32 x 2 blocks x 2 buffers
    assert m["vmem_block_bytes"] == 2 * 8 * 128 * 4
    assert m["pipeline_buffers"] == 2


# ---------------------------------------------------------------------------
# registry-drift guard: new public ops / exchange methods cannot
# silently escape the lint gate


def _registry_names():
    return [t.name for t in default_targets()]


def test_every_exchange_method_is_registered():
    """Every ``Method`` strategy flag maps (via the parallel package's
    coverage manifest) to a registered analysis target."""
    from stencil_tpu.parallel import exchange_method_targets

    names = _registry_names()
    manifest = exchange_method_targets()
    assert set(manifest) == {"PpermuteSlab", "PpermutePacked",
                             "PallasDMA", "AllGather", "Auto"}
    for method, prefix in manifest.items():
        assert any(n.startswith(prefix) for n in names), \
            f"exchange method {method} ({prefix}) has no analysis target"


def test_every_public_op_is_registered():
    """Every entry of the ops package's coverage manifest points at a
    live registry target, and the manifest itself covers every public
    kernel entry point defined in ops/ (every module-level *_pallas
    function plus the XLA core ops) — code cannot be added to ops/
    without either registering it or failing here."""
    import importlib
    import inspect
    import pkgutil

    import stencil_tpu.ops as ops_pkg
    from stencil_tpu.ops import PUBLIC_OPS

    names = _registry_names()
    for op, prefix in PUBLIC_OPS.items():
        assert any(n.startswith(prefix) for n in names), \
            f"public op {op} maps to unregistered target prefix {prefix}"

    core_ops = {"jacobi7", "laplacian27", "der1", "der2", "der_cross"}
    expected = set()
    for info in pkgutil.iter_modules(ops_pkg.__path__):
        mod = importlib.import_module(f"stencil_tpu.ops.{info.name}")
        for fname, obj in vars(mod).items():
            if fname.startswith("_") or not inspect.isfunction(obj):
                continue
            if inspect.getmodule(obj) is not mod:
                continue  # re-exports
            if fname.endswith("_pallas") or fname in core_ops:
                expected.add(f"ops.{info.name}.{fname}")
    missing = expected - set(PUBLIC_OPS)
    assert not missing, \
        f"public ops missing from the lint-coverage manifest: {sorted(missing)}"


# ---------------------------------------------------------------------------
# the analytic byte model (geometry/partition) the costmodel checker
# cross-checks against


def test_sweep_wire_bytes_matches_exchange_counter():
    """partition.sweep_wire_bytes (derived from the partition) must
    equal n_shards x parallel.exchange.exchanged_bytes_per_sweep
    (derived from one shard's padded shape) — two independent routes
    to the same model, uneven remainders included."""
    from stencil_tpu.geometry import Dim3, Radius
    from stencil_tpu.parallel.exchange import exchanged_bytes_per_sweep
    from stencil_tpu.partition import RankPartition, sweep_wire_bytes

    radius = Radius.constant(0)
    radius.set_dir((1, 0, 0), 2)
    radius.set_dir((-1, 0, 0), 1)
    radius.set_dir((0, 1, 0), 1)
    radius.set_dir((0, 0, 1), 3)
    radius.set_dir((0, 0, -1), 3)
    # 21 is not divisible by 2: x and y get +-1 remainder subdomains
    part = RankPartition.from_dim((21, 21, 16), (2, 2, 2))
    model = sweep_wire_bytes(part, radius, 4)

    dim = part.dim()
    cap = part.subdomain_size(Dim3(0, 0, 0))  # the capacity shard
    padded = cap + radius.pad_lo() + radius.pad_hi()
    per_shard = exchanged_bytes_per_sweep(
        (padded.z, padded.y, padded.x), radius, dim, 4)
    for ax in ("x", "y", "z"):
        assert model[ax] == per_shard[ax] * dim.flatten(), ax
    assert model["total"] == sum(per_shard.values()) * dim.flatten()
    # uneven capacity: ceil(21/2) = 11, and the filler rows DO ride
    # the wire (static-shape slabs), so the model must price them
    assert cap.x == 11 and cap.y == 11


# ---------------------------------------------------------------------------
# the runtime twins of the dataflow checkers: the trace-count guard
# (recompile) and the hot-loop transfer guard (transfer)


def test_assert_single_compile_guard():
    import jax
    import jax.numpy as jnp

    from stencil_tpu.analysis.recompile import (RecompileGuardError,
                                                assert_single_compile)

    fn = jax.jit(lambda x: x + 1.0)
    # one fingerprint, many dispatches: fine
    with assert_single_compile(fn, "unit"):
        fn(jnp.zeros((4,), jnp.float32))
        fn(jnp.ones((4,), jnp.float32))
    # a second fingerprint inside the block: the recompile loop
    with pytest.raises(RecompileGuardError, match="re-traced"):
        with assert_single_compile(fn, "unit"):
            fn(jnp.zeros((8,), jnp.float32))
            fn(jnp.zeros((16,), jnp.float32))


def test_single_compile_guard_cross_dispatch():
    import jax
    import jax.numpy as jnp

    from stencil_tpu.analysis.recompile import (RecompileGuardError,
                                                SingleCompileGuard)

    fn = jax.jit(lambda x: x * 2.0)
    guard = SingleCompileGuard()
    fn(jnp.zeros((4,), jnp.float32))
    guard.observe(fn, "unit")
    fn(jnp.ones((4,), jnp.float32))
    guard.observe(fn, "unit")  # same fingerprint: cache flat, fine
    fn(jnp.zeros((8,), jnp.float32))  # fingerprint drift
    with pytest.raises(RecompileGuardError, match="recompiling"):
        guard.observe(fn, "unit")


def test_hot_loop_transfer_guard_blocks_implicit(monkeypatch):
    import contextlib

    import jax.numpy as jnp
    import numpy as np

    from stencil_tpu.analysis.transfer import (ALLOW_TRANSFERS_ENV,
                                               hot_loop_transfer_guard)

    monkeypatch.delenv(ALLOW_TRANSFERS_ENV, raising=False)
    with pytest.raises(Exception, match="[Dd]isallow"):
        with hot_loop_transfer_guard():
            _ = jnp.asarray(np.ones((4,), np.float32)) + 1.0
    # the escape hatch turns the guard into a no-op
    monkeypatch.setenv(ALLOW_TRANSFERS_ENV, "1")
    guard = hot_loop_transfer_guard()
    assert isinstance(guard, contextlib.nullcontext)
    with guard:
        _ = jnp.asarray(np.ones((4,), np.float32)) + 1.0


def test_fused_driver_single_compile_under_guard(monkeypatch, tmp_path):
    """The driver wiring: a fused resilient run under
    STENCIL_ASSERT_SINGLE_COMPILE=1 (and the always-on transfer guard)
    completes — the megastep programs never re-trace mid-campaign."""
    import numpy as np

    from stencil_tpu.analysis.recompile import ASSERT_SINGLE_COMPILE_ENV
    from stencil_tpu.models.jacobi import Jacobi3D
    from stencil_tpu.resilience import ResiliencePolicy

    monkeypatch.setenv(ASSERT_SINGLE_COMPILE_ENV, "1")
    j = Jacobi3D(16, 16, 16, mesh_shape=(2, 2, 2), dtype=np.float32,
                 kernel="xla")
    j.init()
    policy = ResiliencePolicy(check_every=2, ckpt_every=4,
                              fuse_segments=True)
    report = j.run_resilient(8, policy=policy,
                             ckpt_dir=str(tmp_path / "ckpt"))
    assert report.steps == 8 and report.rollbacks == 0


def test_halo_byte_model_counts_face_edge_corner():
    from stencil_tpu.geometry import Radius
    from stencil_tpu.partition import RankPartition, halo_byte_model

    part = RankPartition.from_dim((8, 8, 8), (2, 2, 2))
    model = halo_byte_model(part, Radius.constant(1), 4)
    # 8 subdomains of 4^3: per subdomain 6 faces x 16 cells,
    # 12 edges x 4 cells, 8 corners x 1 cell, 4 B elements
    assert model["face"] == 8 * 6 * 16 * 4
    assert model["edge"] == 8 * 12 * 4 * 4
    assert model["corner"] == 8 * 8 * 1 * 4
    assert model["total"] == sum(
        model[k] for k in ("face", "edge", "corner"))
    # zero edge/corner radius -> only faces priced (the reference's
    # "edge radius gates diagonal exchanges" rule)
    fo = halo_byte_model(part, Radius.face_edge_corner(1, 0, 0), 4)
    assert fo["edge"] == fo["corner"] == 0 and fo["face"] == model["face"]
    # a 1-subdomain axis is an in-core wrap: no wire bytes for any
    # direction that uses it
    flat = RankPartition.from_dim((8, 8, 8), (1, 2, 2))
    m2 = halo_byte_model(flat, Radius.constant(1), 4)
    assert m2["corner"] == 0  # corners all need the x axis
    # 4 subdomains of (8,4,4): 4 y/z faces x 8*4 cells each
    assert m2["face"] == 4 * 4 * 32 * 4
