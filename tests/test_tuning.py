"""Tests for the exchange autotuner (stencil_tpu/tuning).

Everything runs off-TPU: the injectable FakeTimer evaluates the same
analytic alpha-beta model the calibrated cost model uses, so the full
measure -> fit -> plan -> cache pipeline is deterministic on the
8-device virtual CPU mesh — search, pruning, fit recovery, cache
round-trip/invalidation, and plan application through realize().
"""

import json

import numpy as np
import pytest

from stencil_tpu.analysis.costmodel import (LinkCoefficients,
                                            configured_step_seconds)
from stencil_tpu.distributed import DistributedDomain
from stencil_tpu.geometry import Dim3, Radius
from stencil_tpu.parallel.methods import Method, pick_method
from stencil_tpu.tuning import (Candidate, FakeTimer, Plan,
                                TuneGeometry, calibrate_link,
                                candidate_space, fingerprint,
                                fingerprint_inputs, fit_alpha_beta,
                                load_plan, run_autotune, store_plan)
from stencil_tpu.tuning.cache import load_cache
from stencil_tpu.tuning.plan import SCHEMA_VERSION, candidate_feasible


def _domain(radius=1, dtype=np.float32, mesh=(2, 2, 2), nfields=2,
            grid=(16, 16, 16)):
    dd = DistributedDomain(*grid)
    dd.set_mesh_shape(mesh)
    dd.set_radius(radius)
    for i in range(nfields):
        dd.add_data(f"q{i}", dtype)
    return dd


def _geom(radius=1, shard=(8, 8, 8), counts=(2, 2, 2),
          elem_sizes=(4, 4), **kw) -> TuneGeometry:
    r = Radius.constant(radius) if isinstance(radius, int) else radius
    return TuneGeometry(shard_interior_zyx=shard,
                        min_interior_zyx=kw.pop("min_interior", shard),
                        radius=r, counts=Dim3(*counts),
                        elem_sizes=tuple(elem_sizes), **kw)


# ---------------------------------------------------------------------------
# fit


def test_fit_recovers_alpha_beta_exactly():
    truth = LinkCoefficients(alpha_s=37e-6, beta_bytes_per_s=2.5e10)
    fit = fit_alpha_beta([(b, truth.seconds(1, b))
                          for b in (1 << 12, 1 << 17, 1 << 21)])
    assert fit.alpha_s == pytest.approx(truth.alpha_s, rel=1e-9)
    assert fit.beta_bytes_per_s == pytest.approx(truth.beta_bytes_per_s,
                                                 rel=1e-9)


def test_calibrate_link_from_fake_timer():
    timer = FakeTimer(LinkCoefficients(50e-6, 1e10))
    fit = calibrate_link(timer.pingpong)
    assert fit.alpha_s == pytest.approx(50e-6, rel=1e-9)
    assert fit.beta_bytes_per_s == pytest.approx(1e10, rel=1e-9)


def test_fit_degenerate_single_sample():
    fit = fit_alpha_beta([(4096, 1e-4)])
    assert fit.alpha_s == pytest.approx(1e-4)
    assert fit.beta_bytes_per_s > 1e20  # bandwidth term inert


# ---------------------------------------------------------------------------
# candidate space / feasibility


def test_candidate_space_depths_and_methods():
    cands = candidate_space(_geom(), runnable=lambda m: True)
    keys = {c.key() for c in cands}
    # ppermute methods sweep every depth that fits an 8^3 r=1 shard
    for m in ("PpermuteSlab", "PpermutePacked"):
        for s in (1, 2, 4, 8):
            assert f"{m}[s={s}]" in keys
    # non-ppermute strategies are depth-1 only
    assert "AllGather[s=1]" in keys
    assert "PallasDMA[s=1]" in keys
    assert not any(k.startswith("AllGather[s=2")
                   or k.startswith("PallasDMA[s=2") for k in keys)
    # the overlap dimension (opt-in): ppermute methods only
    ovl = candidate_space(_geom(), overlap_options=(False, True),
                          runnable=lambda m: True)
    assert Candidate("PpermuteSlab", 4, True) in ovl
    assert not any(c.overlap for c in ovl
                   if c.method in ("AllGather", "PallasDMA"))


def test_candidate_space_respects_geometry_and_capability():
    # radius 2 on an 8^3 shard: depth 8 needs 16 rows -> infeasible
    cands = candidate_space(_geom(radius=2), runnable=lambda m: True)
    depths = {c.exchange_every for c in cands
              if c.method == "PpermuteSlab"}
    assert depths == {1, 2, 4}
    # capability probe filters whole strategies
    cands = candidate_space(
        _geom(), runnable=lambda m: m != Method.PallasDMA)
    assert not any(c.method == "PallasDMA" for c in cands)


def test_candidate_feasibility_uneven_and_nonperiodic():
    geom = _geom(uneven=True)
    assert not candidate_feasible(Candidate("AllGather", 1), geom)
    assert not candidate_feasible(Candidate("PallasDMA", 1), geom)
    assert candidate_feasible(Candidate("PpermutePacked", 2), geom)
    geom = _geom(nonperiodic=True)
    assert not candidate_feasible(Candidate("AllGather", 1), geom)
    assert candidate_feasible(Candidate("PpermuteSlab", 1), geom)
    # the SMALLEST shard bounds the depth (realize()'s rule)
    geom = _geom(min_interior=(7, 7, 7))
    assert not candidate_feasible(Candidate("PpermuteSlab", 8), geom)
    assert candidate_feasible(Candidate("PpermuteSlab", 4), geom)


def test_packed_model_groups_by_dtype_not_size():
    """The packed engine concatenates per DTYPE (f32 and i32 pack
    separately despite equal itemsize — parallel/exchange.py groups by
    .dtype); the cost model must count launches the same way."""
    from stencil_tpu.analysis.costmodel import exchange_round_model

    geom = _geom()  # two 4-byte quantities
    msgs_one_dtype, _ = exchange_round_model(
        "PpermutePacked", geom.shard_interior_zyx, geom.radius,
        geom.counts, geom.elem_sizes, 1, dtype_groups=1)
    msgs_two_dtypes, _ = exchange_round_model(
        "PpermutePacked", geom.shard_interior_zyx, geom.radius,
        geom.counts, geom.elem_sizes, 1, dtype_groups=2)
    assert msgs_two_dtypes == 2 * msgs_one_dtype
    # the domain adapter carries real dtype names: f32 + i32 (same
    # itemsize) must rank packed at TWO launch groups, not one
    from stencil_tpu.tuning import geometry_from_domain

    dd = DistributedDomain(16, 16, 16)
    dd.set_mesh_shape((2, 2, 2))
    dd.set_radius(1)
    dd.add_data("a", np.float32)
    dd.add_data("b", np.int32)
    g = geometry_from_domain(dd, Dim3(2, 2, 2))
    assert g.dtype_groups == 2
    assert g.elem_sizes == (4, 4)


# ---------------------------------------------------------------------------
# plan cache


def test_plan_cache_round_trip(tmp_path):
    cache = tmp_path / "plans.json"
    plan = Plan(config=Candidate("PpermutePacked", 4),
                fingerprint="abc123", coefficients={
                    "ici": {"alpha_s": 1e-5, "beta_bytes_per_s": 1e10}},
                costs={"PpermutePacked[s=4]": {"predicted_s": 1e-4,
                                               "measured_s": 9e-5}},
                provenance="tuned", measurements=7, created=123.0,
                library_version="0.1.0")
    store_plan(plan, cache)
    back = load_plan("abc123", cache)
    assert back is not None
    assert back.config == plan.config
    assert back.coefficients == plan.coefficients
    assert back.costs == plan.costs
    assert back.measurements == 7
    assert back.library_version == "0.1.0"
    # unknown fingerprint is a miss, not an error
    assert load_plan("zzz", cache) is None


def test_plan_cache_rejects_corrupt_file(tmp_path):
    cache = tmp_path / "plans.json"
    cache.write_text("{ not json !!!")
    assert load_plan("abc", cache) is None
    # a rewrite recovers the file
    plan = Plan(config=Candidate("PpermuteSlab", 1), fingerprint="f1",
                coefficients={}, costs={})
    store_plan(plan, cache)
    assert load_plan("f1", cache) is not None


def test_plan_cache_rejects_old_schema(tmp_path):
    cache = tmp_path / "plans.json"
    plan = Plan(config=Candidate("PpermuteSlab", 1), fingerprint="f1",
                coefficients={}, costs={})
    store_plan(plan, cache)
    data = json.loads(cache.read_text())
    assert data["schema"] == SCHEMA_VERSION
    data["schema"] = SCHEMA_VERSION + 999
    cache.write_text(json.dumps(data))
    assert load_cache(cache) == {}
    assert load_plan("f1", cache) is None


def test_plan_cache_rejects_unparsable_record(tmp_path):
    cache = tmp_path / "plans.json"
    cache.write_text(json.dumps(
        {"schema": SCHEMA_VERSION, "plans": {"f1": {"bogus": 1}}}))
    assert load_plan("f1", cache) is None


def test_cache_env_override(tmp_path, monkeypatch):
    target = tmp_path / "fleet" / "plans.json"
    monkeypatch.setenv("STENCIL_TUNE_CACHE", str(target))
    plan = Plan(config=Candidate("PpermuteSlab", 1), fingerprint="f1",
                coefficients={}, costs={})
    store_plan(plan)  # no explicit path: env decides
    assert target.exists()
    assert load_plan("f1") is not None


# ---------------------------------------------------------------------------
# fingerprint semantics


def test_fingerprint_invalidation_radius_dtype_mesh():
    base = dict(platform="cpu", device_count=8, mesh_shape=[2, 2, 2],
                grid=[16, 16, 16], radius=Radius.constant(1),
                quantities={"q0": "float32"}, boundary="PERIODIC")
    fp = fingerprint(fingerprint_inputs(**base))
    assert fp == fingerprint(fingerprint_inputs(**base))  # stable
    changed = dict(base, radius=Radius.constant(2))
    assert fingerprint(fingerprint_inputs(**changed)) != fp
    changed = dict(base, quantities={"q0": "float64"})
    assert fingerprint(fingerprint_inputs(**changed)) != fp
    changed = dict(base, mesh_shape=[4, 2, 1])
    assert fingerprint(fingerprint_inputs(**changed)) != fp
    changed = dict(base)
    assert fingerprint(fingerprint_inputs(
        library_version="99.0", **changed)) != fp


def test_fingerprint_invalidation_wire_format():
    """A cached plan tuned for the f32 wire must NOT be served to a
    bf16-wire campaign (its measured seconds priced twice the wire
    bytes) — the wire format is part of the fingerprint key."""
    base = dict(platform="cpu", device_count=8, mesh_shape=[2, 2, 2],
                grid=[16, 16, 16], radius=Radius.constant(1),
                quantities={"q0": "float32"}, boundary="PERIODIC")
    fp = fingerprint(fingerprint_inputs(**base))
    # the default IS f32 — spelling it out must not re-key the cache
    assert fingerprint(fingerprint_inputs(wire_format="f32",
                                          **base)) == fp
    assert fingerprint(fingerprint_inputs(wire_format="bf16",
                                          **base)) != fp


def test_candidate_wire_format_space_and_feasibility():
    """Opting wire formats into the sweep doubles the ppermute
    candidates only (narrow wire is a slab/packed capability), the
    bf16 variants rank strictly cheaper than their f32 twins under the
    calibrated model (half the wire bytes), and the key round-trips."""
    geom = TuneGeometry(shard_interior_zyx=(8, 8, 8),
                        min_interior_zyx=(8, 8, 8),
                        radius=Radius.constant(1), counts=Dim3(2, 2, 2),
                        elem_sizes=(4,))
    base = candidate_space(geom, depths=(1,))
    wired = candidate_space(geom, depths=(1,),
                            wire_formats=("f32", "bf16"))
    ppermute = [c for c in base
                if c.method in ("PpermuteSlab", "PpermutePacked")]
    assert len(wired) == len(base) + len(ppermute)
    assert all(c.method in ("PpermuteSlab", "PpermutePacked")
               for c in wired if c.wire_format == "bf16")
    coeffs = LinkCoefficients(alpha_s=0.0, beta_bytes_per_s=1e10)
    for c in wired:
        if c.wire_format != "bf16":
            continue
        twin = next(t for t in wired
                    if t.method == c.method and t.wire_format == "f32"
                    and t.exchange_every == c.exchange_every
                    and t.overlap == c.overlap)

        def price(cand):
            return configured_step_seconds(
                cand.method, geom.shard_interior_zyx, geom.radius,
                geom.counts, geom.elem_sizes, cand.exchange_every,
                coeffs, wire_format=cand.wire_format)

        assert price(c) < price(twin)
        assert "wire=bf16" in c.key()
        assert Candidate.from_key(c.key()) == c


# ---------------------------------------------------------------------------
# per-axis depths + placement: keys, candidate space, cache compat


def test_asym_candidate_key_roundtrip_and_feasibility():
    """Asymmetric depths serialize as a dot-separated (x, y, z) depth
    (``PpermuteSlab[s=1.1.4]``), round-trip through from_key, and obey
    the realize()-equivalent feasibility rules per axis."""
    c = Candidate("PpermuteSlab", 4, depths=(1, 1, 4))
    assert c.key() == "PpermuteSlab[s=1.1.4]"
    assert Candidate.from_key(c.key()) == c
    # a uniform depths tuple collapses to the symmetric spelling
    assert Candidate("PpermuteSlab", 2, depths=(2, 2, 2)).key() == \
        "PpermuteSlab[s=2]"
    geom = _geom()
    assert candidate_feasible(Candidate("PpermuteSlab", 4,
                                        depths=(1, 1, 4)), geom)
    # the deep axis is bounded by the SMALLEST shard (min_interior is
    # zyx: 7 rows on z reject depth 8 there, depth 4 fits)
    short = _geom(min_interior=(7, 8, 8))
    assert not candidate_feasible(Candidate("PpermuteSlab", 8,
                                            depths=(1, 1, 8)), short)
    assert candidate_feasible(Candidate("PpermuteSlab", 4,
                                        depths=(1, 1, 4)), short)
    # asym declines: non-ppermute engines, overlap, non-slab layout,
    # and cadences that do not divide the group length
    assert not candidate_feasible(Candidate("AllGather", 4,
                                            depths=(1, 1, 4)), geom)
    assert not candidate_feasible(Candidate("PpermuteSlab", 4, True,
                                            depths=(1, 1, 4)), geom)
    assert not candidate_feasible(
        Candidate("PpermuteSlab", 4, wire_layout="irredundant",
                  depths=(1, 1, 4)), geom)
    assert not candidate_feasible(Candidate("PpermuteSlab", 4,
                                            depths=(1, 3, 4)), geom)


def test_candidate_space_asymmetric_depth_specs():
    """Depth entries may be per-axis dicts/tuples: they become
    asymmetric candidates on the ppermute engines only, and uniform
    spellings collapse into the symmetric set (no duplicate keys)."""
    cands = candidate_space(_geom(), depths=(1, 4, {"z": 4}, (4, 4, 4)),
                            runnable=lambda m: True)
    keys = [c.key() for c in cands]
    assert len(keys) == len(set(keys))
    assert "PpermuteSlab[s=1.1.4]" in keys
    assert "PpermutePacked[s=1.1.4]" in keys
    assert "PpermuteSlab[s=4]" in keys
    assert not any(k.startswith(("AllGather[s=1.1.4",
                                 "PallasDMA[s=1.1.4")) for k in keys)


def test_plan_cache_loads_pre_deployment_records(tmp_path):
    """Cache records written before the per-axis depth / placement
    axes existed carry neither ``config.depths`` nor ``placement`` —
    they must load cleanly as symmetric-depth auto-placement plans
    (the same old-record contract as ``Plan.tiling``), and a new
    asymmetric/qap plan round-trips its keys."""
    cache = tmp_path / "plans.json"
    store_plan(Plan(config=Candidate("PpermutePacked", 4),
                    fingerprint="old1", coefficients={}, costs={}),
               cache)
    data = json.loads(cache.read_text())
    rec = data["plans"]["old1"]
    del rec["config"]["depths"]
    del rec["placement"]
    cache.write_text(json.dumps(data))
    back = load_plan("old1", cache)
    assert back is not None
    assert back.config.depths is None
    assert back.config.depths_xyz() == (4, 4, 4)
    assert back.placement == "auto"
    store_plan(Plan(config=Candidate("PpermuteSlab", 4,
                                     depths=(1, 1, 4)),
                    fingerprint="new1", coefficients={}, costs={},
                    placement="qap"), cache)
    b2 = load_plan("new1", cache)
    assert b2.config.depths == (1, 1, 4)
    assert b2.config.key() == "PpermuteSlab[s=1.1.4]"
    assert b2.placement == "qap"


def test_fingerprint_depths_and_placement_only_when_nondefault():
    """Symmetric depths and auto placement are the identity: spelling
    them out must not re-key plans cached before these axes existed;
    non-uniform depths and forced placement modes must."""
    base = dict(platform="cpu", device_count=8, mesh_shape=[2, 2, 2],
                grid=[16, 16, 16], radius=Radius.constant(1),
                quantities={"q0": "float32"}, boundary="PERIODIC")
    fp = fingerprint(fingerprint_inputs(**base))
    assert fingerprint(fingerprint_inputs(
        exchange_depths=(4, 4, 4), placement="auto", **base)) == fp
    assert fingerprint(fingerprint_inputs(
        exchange_depths=(1, 1, 4), **base)) != fp
    assert fingerprint(fingerprint_inputs(placement="qap", **base)) != fp
    assert fingerprint(fingerprint_inputs(placement="trivial",
                                          **base)) != fp


# ---------------------------------------------------------------------------
# the end-to-end search (fake timer; deterministic)


def test_autotune_selects_model_cheapest_plan(tmp_path):
    """The acceptance criterion: with the fake timer (which evaluates
    the same analytic model), autotune() selects exactly the plan the
    CALIBRATED cost model ranks cheapest, prunes the sweep before
    timing, and a second run is a pure cache hit."""
    cache = tmp_path / "plans.json"
    dd = _domain()  # 16^3 over 2x2x2: 8^3 shards, r=1, two f32 fields
    plan = dd.autotune(timer=FakeTimer(), cache_path=cache)

    assert plan.provenance == "tuned"
    # pruning: 10 feasible candidates (PallasDMA runs through the
    # distributed interpreter here), only 4 measured (+3 pingpongs)
    n_cands = len(plan.costs)
    n_measured = sum(1 for rec in plan.costs.values()
                     if "measured_s" in rec)
    assert n_cands == 10 and n_measured == 4
    assert plan.measurements == n_measured + 3

    # the calibrated model's argmin IS the winner (fake measurements
    # realize the model exactly)
    coeffs = LinkCoefficients(**plan.coefficients["ici"])
    geom = _geom()
    best = min(
        (Candidate.from_key(k) for k in plan.costs),
        key=lambda c: configured_step_seconds(
            c.method, geom.shard_interior_zyx, geom.radius, geom.counts,
            geom.elem_sizes, c.exchange_every, coeffs))
    assert plan.config == best
    # ...and concretely: two fields + tiny latency-bound shards ->
    # per-direction packing at the deepest feasible blocking
    assert plan.config == Candidate("PpermutePacked", 8)

    # the plan applied: realize() runs the tuned configuration
    dd.realize()
    assert dd.methods == Method.PpermutePacked
    assert dd.exchange_every == 8
    assert dd.plan_provenance == "tuned"
    dd.exchange()  # the tuned program actually runs

    # second run, same fingerprint: cache hit, ZERO measurements
    dd2 = _domain()
    plan2 = dd2.autotune(timer=FakeTimer(), cache_path=cache)
    assert plan2.provenance == "cached"
    assert plan2.measurements == 0
    assert plan2.config == plan.config
    assert dd2.plan_provenance == "cached"


def test_autotune_retunes_on_fingerprint_mismatch(tmp_path):
    cache = tmp_path / "plans.json"
    _domain().autotune(timer=FakeTimer(), cache_path=cache)
    # radius change -> new fingerprint -> forced re-tune
    dd = _domain(radius=2)
    plan = dd.autotune(timer=FakeTimer(), cache_path=cache)
    assert plan.provenance == "tuned" and plan.measurements > 0
    # dtype change likewise
    dd = _domain(dtype=np.float64)
    plan = dd.autotune(timer=FakeTimer(), cache_path=cache)
    assert plan.provenance == "tuned" and plan.measurements > 0
    # mesh change likewise
    dd = _domain(mesh=(4, 2, 1))
    plan = dd.autotune(timer=FakeTimer(), cache_path=cache)
    assert plan.provenance == "tuned" and plan.measurements > 0
    # all four plans coexist in one cache file
    assert len(load_cache(cache)) == 4


def test_autotune_force_remeasures(tmp_path):
    cache = tmp_path / "plans.json"
    _domain().autotune(timer=FakeTimer(), cache_path=cache)
    plan = _domain().autotune(timer=FakeTimer(), cache_path=cache,
                              force=True)
    assert plan.provenance == "tuned" and plan.measurements > 0


def test_measurements_decide_among_survivors(tmp_path):
    """The tuner trusts measurements over the model within the pruned
    set: a fake timer that (only) slows PpermutePacked 10x flips the
    winner to the next-best measured survivor."""
    cache = tmp_path / "plans.json"
    dd = _domain()
    plan = dd.autotune(timer=FakeTimer(scale={"PpermutePacked": 10.0}),
                       cache_path=cache)
    assert plan.config == Candidate("PpermuteSlab", 8)


def test_autotune_fits_dcn_link_class(tmp_path):
    """A timer exposing a (slower) DCN link gets a second per-link
    alpha-beta fit; ranking uses the bottleneck combine (sequential
    axis sweeps must cross the slow fabric), recorded in the plan."""
    cache = tmp_path / "plans.json"
    ici = LinkCoefficients(50e-6, 1e10)
    dcn = LinkCoefficients(500e-6, 1e9)
    dd = _domain()
    plan = dd.autotune(timer=FakeTimer(ici, dcn_coeffs=dcn),
                       cache_path=cache)
    assert set(plan.coefficients) == {"ici", "dcn"}
    assert plan.coefficients["dcn"]["alpha_s"] == \
        pytest.approx(500e-6, rel=1e-9)
    assert plan.coefficients["ici"]["alpha_s"] == \
        pytest.approx(50e-6, rel=1e-9)
    # 3 ici + 3 dcn pingpongs + 4 exchange timings
    assert plan.measurements == 10
    # predicted costs were priced at the bottleneck (dcn) coefficients
    geom = _geom()
    bottleneck = LinkCoefficients(500e-6, 1e9)
    c = plan.config
    assert plan.costs[c.key()]["predicted_s"] == pytest.approx(
        configured_step_seconds(c.method, geom.shard_interior_zyx,
                                geom.radius, geom.counts,
                                geom.elem_sizes, c.exchange_every,
                                bottleneck), rel=1e-9)


def test_method_auto_resolves_at_realize(tmp_path, monkeypatch):
    """Method.Auto is the standing autotune request: realize() runs
    the tuner (here with the fake timer substituted for the real
    MeshTimer) and deploys the winner."""
    import stencil_tpu.tuning as tuning

    monkeypatch.setenv("STENCIL_TUNE_CACHE",
                       str(tmp_path / "plans.json"))
    monkeypatch.setattr(tuning, "MeshTimer",
                        lambda *a, **kw: FakeTimer())
    dd = _domain()
    dd.set_methods(Method.Auto)
    dd.realize()
    assert Method.Auto not in dd.methods
    assert dd.methods == Method.PpermutePacked
    assert dd.exchange_every == 8
    assert dd.plan_provenance == "tuned"
    dd.exchange()


def test_plan_file_records_provenance(tmp_path):
    dd = _domain()
    dd.autotune(timer=FakeTimer(), cache_path=tmp_path / "plans.json")
    dd.set_output_prefix(str(tmp_path) + "/")
    dd.realize()
    text = (tmp_path / "plan.txt").read_text()
    assert "plan provenance: tuned" in text
    assert "plan config: PpermutePacked[s=8]" in text
    # an untuned domain records the static-default provenance
    dd = _domain()
    dd.set_output_prefix(str(tmp_path) + "/untuned_")
    dd.realize()
    text = (tmp_path / "untuned_plan.txt").read_text()
    assert "plan provenance: default" in text


def test_run_autotune_rejects_impossible_geometry(tmp_path):
    geom = _geom(radius=16)  # radius exceeds the 8^3 shard everywhere
    inputs = fingerprint_inputs(
        platform="cpu", device_count=8, mesh_shape=[2, 2, 2],
        grid=[16, 16, 16], radius=Radius.constant(16),
        quantities={"q0": "float32"}, boundary="PERIODIC")
    with pytest.raises(ValueError, match="no feasible"):
        run_autotune(geom, inputs, FakeTimer(),
                     cache_path=tmp_path / "plans.json")


# ---------------------------------------------------------------------------
# capability-aware pick_method (both branches, capability injected)


def test_pick_method_keeps_runnable_request():
    assert pick_method(Method.PallasDMA,
                       runnable=lambda m: True) == Method.PallasDMA
    assert pick_method(Method.Default) == Method.PpermuteSlab


def test_pick_method_falls_back_when_unrunnable(capsys):
    from stencil_tpu.parallel import methods as methods_mod

    methods_mod._warned.clear()
    no_dma = lambda m: m != Method.PallasDMA  # noqa: E731
    # next requested strategy wins...
    got = pick_method(Method.PallasDMA | Method.PpermutePacked,
                      runnable=no_dma)
    assert got == Method.PpermutePacked
    # ...or Default when nothing requested is runnable
    methods_mod._warned.clear()
    assert pick_method(Method.PallasDMA,
                       runnable=no_dma) == Method.PpermuteSlab
    err = capsys.readouterr().err
    assert "PallasDMA" in err and "falling back" in err


def test_pick_method_warns_once_per_fact(capsys):
    from stencil_tpu.parallel import methods as methods_mod

    methods_mod._warned.clear()
    no_dma = lambda m: m != Method.PallasDMA  # noqa: E731
    for _ in range(3):
        pick_method(Method.PallasDMA, runnable=no_dma)
    err = capsys.readouterr().err
    assert err.count("falling back") == 1


def test_pick_method_rejects_bare_auto():
    with pytest.raises(ValueError, match="Auto"):
        pick_method(Method.Auto)
    with pytest.raises(ValueError):
        pick_method(Method.NONE)


def test_cache_concurrent_writers_drop_no_records(tmp_path):
    """Two service workers storing plans for DIFFERENT fingerprints
    concurrently must both land: store_plan is a read-merge-write
    under the cache's writer lock, not a blind whole-file overwrite."""
    import threading

    from stencil_tpu.tuning.cache import load_cache, store_plan
    from stencil_tpu.tuning.plan import Candidate, Plan

    path = tmp_path / "plans.json"
    n = 16

    def mkplan(i):
        return Plan(config=Candidate("PpermuteSlab", 1, False),
                    fingerprint=f"{i:02d}" * 16, coefficients={},
                    costs={}, provenance="tuned", measurements=1)

    start = threading.Barrier(n)
    errors = []

    def worker(i):
        try:
            start.wait()
            store_plan(mkplan(i), path)
        except BaseException as e:  # noqa: BLE001 - surface in main
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    plans = load_cache(path)
    assert sorted(plans) == sorted(f"{i:02d}" * 16 for i in range(n))


def test_cache_lock_released_after_store(tmp_path):
    """The writer lock is released even when the publish raises — a
    poisoned lock would deadlock every later tune."""
    from stencil_tpu.tuning import cache as cache_mod
    from stencil_tpu.tuning.plan import Candidate, Plan

    plan = Plan(config=Candidate("PpermuteSlab", 1, False),
                fingerprint="a" * 32, coefficients={}, costs={},
                provenance="tuned", measurements=1)
    path = tmp_path / "nested" / "plans.json"
    cache_mod.store_plan(plan, path)
    # immediately storable again (no held flock / thread mutex)
    cache_mod.store_plan(plan, path)
    assert cache_mod.load_plan("a" * 32, path) is not None
