"""The prescriptive VMEM tiling planner (analysis/tiling.py).

Three properties anchor the module:

* plan -> audit round trip: every planner-EMITTED block shape, traced
  through the real kernel it was planned for, passes ``check_vmem``
  at the PHYSICAL budget with zero findings — across kernel families,
  sizes (8^3 smoke, 17^3 uneven, 256^3/512^3 production) and dtypes
  (f32, bf16). Where the planner refuses, the refusal IS the contract
  (TilingInfeasibleError naming the binding constraint), never a
  silently shrunken shape.
* prescription correctness: the SNIPPETS.md 512^3 failure shape
  (16, 128) is flagged and the planner's (8, 128) replacement is
  clean — and block shapes never change numerics (bitwise equality
  across shapes at a small size).
* the tuner integration: planner-legal shapes rank by the modeled
  HBM price and ride ``Plan.tiling`` records through the cache.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from stencil_tpu.analysis.tiling import (TILE_SELECT_BUDGET_BYTES,
                                         TilingInfeasibleError,
                                         plan_blocks, reset_warnings,
                                         snap_blocks)
from stencil_tpu.analysis.vmem import VMEM_BUDGET_BYTES


def _f(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


# ---------------------------------------------------------------------------
# planner unit properties


def _unit_elems(bz, by):
    return (2 * bz * by, bz * by, 0)


def test_plan_blocks_candidates_are_aligned_divisible():
    plan = plan_blocks("unit", 64, 64, 128, 4, _unit_elems)
    assert plan.options
    for o in plan.options:
        assert 64 % o.block_z == 0 and 64 % o.block_y == 0
        assert o.block_y % 8 == 0            # f32 sublane tile
        assert o.footprint_bytes <= plan.budget_bytes
    # cheapest traffic first; ties prefer fatter block_y then block_z
    amps = [o.amplification for o in plan.options]
    assert amps == sorted(amps)
    assert plan.best.block_z == 64 and plan.best.block_y == 64


def test_plan_blocks_caps_and_sublanes():
    plan = plan_blocks("unit", 64, 64, 128, 4, _unit_elems,
                       cap_z=16, cap_y=32, sublane_z=4)
    assert plan.best.block_z == 16 and plan.best.block_y == 32
    for o in plan.options:
        assert o.block_z <= 16 and o.block_y <= 32
        assert o.block_z % 4 == 0
    # bf16 doubles the sublane tile; a cap below the floor means "the
    # smallest legal shape", not infeasible (the old fitters' clamp-up)
    plan16 = plan_blocks("unit", 64, 64, 128, 2, _unit_elems, cap_y=8)
    assert plan16.best.block_y == 16


def test_plan_blocks_budget_binds_and_names_constraint():
    # 3 full-array streams of (bz, by, 128) f32: force the budget down
    # until only small blocks survive, then to nothing
    elems = lambda bz, by: (2 * bz * by, bz * by, 0)  # noqa: E731
    tight = plan_blocks("unit", 256, 256, 512, 4, elems,
                        budget=4 * 2**20)
    assert tight.options and tight.over_budget > 0
    for o in tight.options:
        assert o.footprint_bytes <= 4 * 2**20
    nothing = plan_blocks("unit", 256, 256, 512, 4, elems, budget=1024)
    assert not nothing.options
    assert "VMEM footprint is the binding constraint" in nothing.infeasible
    with pytest.raises(TilingInfeasibleError, match="binding constraint"):
        nothing.blocks()


def test_plan_blocks_alignment_infeasible_named():
    # Y=17 with an 8-row sublane requirement: no aligned block_y at all
    plan = plan_blocks("unit", 16, 17, 128, 4, _unit_elems, sublane_y=8)
    assert not plan.options
    assert "sublane tile 8" in plan.infeasible
    with pytest.raises(TilingInfeasibleError):
        plan.blocks()


def test_snap_blocks_warns_once_per_replacement(capsys):
    reset_warnings()
    bz, by = snap_blocks("unit_kernel", 16, 16, 16, 128, sublane_y=8)
    assert (bz, by) == (16, 16)
    err = capsys.readouterr().err
    assert "unit_kernel" in err and "(16, 128)" in err \
        and "(16, 16)" in err
    # the same replacement again: silent (once per kernel+shape+request)
    snap_blocks("unit_kernel", 16, 16, 16, 128, sublane_y=8)
    assert "unit_kernel" not in capsys.readouterr().err
    # a legal explicit request passes through silently
    reset_warnings()
    assert snap_blocks("unit_kernel", 16, 16, 8, 8) == (8, 8)
    assert "unit_kernel" not in capsys.readouterr().err
    with pytest.raises(TilingInfeasibleError):
        snap_blocks("unit_kernel", 17, 16, 16, 16, sublane_z=8, min_z=8)


# ---------------------------------------------------------------------------
# plan -> audit round trip: the planner's shapes pass the PHYSICAL-
# budget VMEM audit through the real kernels, or the planner refuses
# with the constraint named — across families x sizes x dtypes


def _wrap_fn(side, dtype, steps):
    from stencil_tpu.ops.pallas_stencil import (jacobi7_wrap_pallas,
                                                jacobi7_wrapn_pallas)

    hot = (side // 4, side // 2, side // 2)
    cold = (3 * side // 4, side // 2, side // 2)

    def fn(q):
        if steps == 1:
            return jacobi7_wrap_pallas(q, hot, cold, max(side // 8, 1),
                                       interpret=False)
        return jacobi7_wrapn_pallas(q, hot, cold, max(side // 8, 1),
                                    steps=steps, interpret=False)

    return fn, (_f((side, side, side), dtype),)


def _halo_fn(side, dtype):
    from stencil_tpu.ops.pallas_stencil import sublane_tile
    from stencil_tpu.ops.pallas_halo import jacobi7_halo_pallas

    esub = sublane_tile(dtype)
    if side % esub:
        esub = 1
    slabs = {"zlo": _f((1, side, side), dtype),
             "zhi": _f((1, side, side), dtype),
             "ylo": _f((side, esub, side), dtype),
             "yhi": _f((side, esub, side), dtype)}
    org = jax.ShapeDtypeStruct((3,), jnp.int32)

    def fn(interior, zlo, zhi, ylo, yhi, o):
        return jacobi7_halo_pallas(
            interior, {"zlo": zlo, "zhi": zhi, "ylo": ylo, "yhi": yhi},
            o, (2, 4, 4), (5, 4, 4), 1, interpret=False)

    return fn, (_f((side, side, side), dtype), slabs["zlo"],
                slabs["zhi"], slabs["ylo"], slabs["yhi"], org)


def _mhd_wrap_fn(side, dtype):
    from stencil_tpu.models.astaroth import FIELDS, MhdParams
    from stencil_tpu.ops.pallas_mhd import mhd_substep_wrap_pallas

    prm = MhdParams()

    def fn(*fs):
        f, w = mhd_substep_wrap_pallas(dict(zip(FIELDS, fs)), None, 0,
                                       prm, prm.dt, interpret=False)
        return tuple(f[q] for q in FIELDS)

    return fn, tuple(_f((side, side, side), dtype) for _ in FIELDS)


def _mhd_haloz_fn(side, dtype):
    """The MHD halo kernel's y-local window (z slabs only) in its
    worst-case substep: w read and written."""
    from stencil_tpu.models.astaroth import FIELDS, MhdParams
    from stencil_tpu.ops.pallas_halo import (mhd_halo_blocks,
                                             mhd_substep_halo_pallas)
    from stencil_tpu.ops.pallas_mhd import mhd_tile

    prm = MhdParams()
    bz, _by = mhd_halo_blocks(side, side, 8, 32, mhd_tile(dtype),
                              X=side, itemsize=jnp.dtype(dtype).itemsize,
                              y_local=True)
    field = _f((side, side, side), dtype)
    slab = _f((bz, side, side), dtype)

    def fn(fields, w, slabs):
        return mhd_substep_halo_pallas(fields, w, slabs, 1, prm, prm.dt,
                                       interpret=False)

    return fn, ({q: field for q in FIELDS}, {q: field for q in FIELDS},
                {q: {"zlo": slab, "zhi": slab} for q in FIELDS})


_FAMILIES = {
    "wrap": lambda side, dtype: _wrap_fn(side, dtype, 1),
    "wrapn2": lambda side, dtype: _wrap_fn(side, dtype, 2),
    "halo": _halo_fn,
    "mhd_wrap": _mhd_wrap_fn,
    "mhd_haloz": _mhd_haloz_fn,
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("side", [8, 17, 256, 512])
@pytest.mark.parametrize("family", sorted(_FAMILIES))
def test_planner_shapes_round_trip_through_vmem_audit(family, side,
                                                      dtype):
    """Every planner-emitted default shape passes check_vmem at the
    PHYSICAL budget (declared vmem_limit raises ignored); where the
    planner refuses, the refusal names its binding constraint — never
    a silent shrink, never an audit failure."""
    if family in ("wrapn2", "mhd_wrap", "mhd_haloz") and side == 17:
        pytest.skip("kernel requires sublane-divisible Y (model gates)")
    if family in ("mhd_wrap", "mhd_haloz") and side == 8:
        side = 16  # 8^3 leaves no room for the radius-3 window ring
    from stencil_tpu.ops.pallas_stencil import sublane_tile

    # arrays whose Y is not a multiple of the dtype's sublane tile run
    # in the kernels' documented degraded-alignment mode (single-row
    # edge slabs): Mosaic pads those fetches, and the audit reports
    # exactly that — the ONLY findings allowed there
    degraded = side % sublane_tile(dtype) != 0
    try:
        fn, args = _FAMILIES[family](side, dtype)
        # trace once; audit against the physical budget ourselves
        from stencil_tpu.analysis.jaxprs import iter_eqns, trace
        from stencil_tpu.analysis.vmem import audit_pallas_call

        name = f"roundtrip.{family}[{side}]"

        closed = trace(fn, *args)
        findings = []
        n_kernels = 0
        for eqn in iter_eqns(closed.jaxpr):
            if eqn.primitive.name != "pallas_call":
                continue
            n_kernels += 1
            f, _m = audit_pallas_call(eqn, VMEM_BUDGET_BYTES, "k",
                                      name,
                                      honor_kernel_limit=False)
            findings.extend(f)
        assert n_kernels >= 1
        if degraded:
            assert all("sublane dim 1 is neither" in str(f)
                       for f in findings), [str(f) for f in findings]
        else:
            assert findings == [], [str(f) for f in findings]
    except TilingInfeasibleError as e:
        assert "no legal block shape" in str(e)
    except ValueError as e:
        # the N-step kernels refuse non-sublane-divisible Y outright
        assert degraded and "== 0" in str(e), e


def test_snippets_512_failure_flagged_and_prescription_clean():
    """The motivating failure end-to-end: the old default (16, 128)
    halo blocking at 512^3 exceeds the physical budget (check_vmem
    honoring the kernel's raised limit MISSES it — which is exactly
    why the tiling checker exists), the planner prescribes (8, 128),
    and the prescribed shape audits clean."""
    from stencil_tpu.analysis.jaxprs import iter_eqns, trace
    from stencil_tpu.analysis.vmem import audit_pallas_call
    from stencil_tpu.ops.pallas_halo import (_jacobi_block_bytes,
                                             fit_jacobi_halo_blocks)

    assert _jacobi_block_bytes(16, 128, 512, 8, 4) > VMEM_BUDGET_BYTES
    assert fit_jacobi_halo_blocks(512, 512, 512, 8, 4, 16, 128) \
        == (8, 128)

    def audit(block_z, block_y):
        from stencil_tpu.ops.pallas_halo import jacobi7_halo_pallas

        S = 512
        slabs = {"zlo": _f((1, S, S)), "zhi": _f((1, S, S)),
                 "ylo": _f((S, 8, S)), "yhi": _f((S, 8, S))}
        org = jax.ShapeDtypeStruct((3,), jnp.int32)

        def fn(interior, zlo, zhi, ylo, yhi, o):
            return jacobi7_halo_pallas(
                interior,
                {"zlo": zlo, "zhi": zhi, "ylo": ylo, "yhi": yhi},
                o, (2, 4, 4), (5, 4, 4), 1, block_z=block_z,
                block_y=block_y, interpret=False)

        closed = trace(fn, _f((S, S, S)), slabs["zlo"], slabs["zhi"],
                       slabs["ylo"], slabs["yhi"], org)
        out = []
        for eqn in iter_eqns(closed.jaxpr):
            if eqn.primitive.name == "pallas_call":
                f, _ = audit_pallas_call(eqn, VMEM_BUDGET_BYTES, "k",
                                         "t", honor_kernel_limit=False)
                out.extend(f)
        return out

    reset_warnings()
    assert audit(16, 128), "the SNIPPETS shape must be flagged"
    assert audit(None, None) == [], "the prescribed shape must be clean"


def test_mhd_halo_y_local_window_fits_at_256():
    """At a 256^3 float32 shard the y-sharded MHD halo window (y slabs
    and yz corner segments for every field) stages more than the
    selection budget; the y-local window (y wrapped in-kernel, z slabs
    only) tiles at (8, 8) in (8 + 4R)(8 + 16) elements a field a lane
    column: 11,010,048 B. At 512^3 neither fits."""
    from stencil_tpu.ops.pallas_halo import (_mhd_halo_elems,
                                             mhd_halo_blocks)

    S = 256
    assert mhd_halo_blocks(S, S, 8, 32, 8, X=S, itemsize=4,
                           y_local=True) == (8, 8)
    ein, eout, held = _mhd_halo_elems(8, y_local=True)(8, 8)
    footprint = 4 * S * (2 * (ein + eout) + held)
    assert footprint == 11_010_048 <= TILE_SELECT_BUDGET_BYTES
    with pytest.raises(TilingInfeasibleError, match="binding constraint"):
        mhd_halo_blocks(S, S, 8, 32, 8, X=S, itemsize=4)
    for y_local in (False, True):
        with pytest.raises(TilingInfeasibleError):
            mhd_halo_blocks(512, 512, 8, 32, 8, X=512, itemsize=4,
                            y_local=y_local)


def test_astaroth_halo_blocks_on_the_benchmark_mesh():
    """``Astaroth._fast_path_blocks`` at the four-chip weak cell's
    256x256x1024 grid on a (1, 1, 4) mesh: the halo path finds the
    y-local window's blocks, so ``kernel="auto"`` takes it; the RDMA
    overlap kernels keep the y-sharded window and stay infeasible.
    Built without realizing the domain (no 256^3 fields)."""
    from types import SimpleNamespace

    from stencil_tpu.geometry import Dim3
    from stencil_tpu.models.astaroth import Astaroth
    from stencil_tpu.parallel.mesh import make_mesh

    m = object.__new__(Astaroth)
    m.dd = SimpleNamespace(local_size=Dim3(256, 256, 256),
                           mesh=make_mesh((1, 1, 4), jax.devices()[:4]))
    m._dtype = np.dtype(np.float32)
    assert m._halo_y_local("halo")
    assert m._fast_path_blocks("halo") == (8, 8)
    with pytest.raises(TilingInfeasibleError):
        m._fast_path_blocks("halo-overlap")


def test_block_shape_never_changes_numerics():
    """Bitwise equality across block shapes at a small size: the
    planner choosing a different legal shape can never change results
    (same per-point op order by kernel construction)."""
    from stencil_tpu.ops.pallas_stencil import jacobi7_wrap_pallas

    n = 16
    rng = np.random.default_rng(11)
    t = jnp.asarray(rng.random((n, n, n)).astype(np.float32))
    hot, cold, r = (4, 8, 8), (12, 8, 8), 2
    default = np.asarray(jacobi7_wrap_pallas(t, hot, cold, r,
                                             interpret=True))
    for bz, by in ((4, 8), (16, 16), (2, 8)):
        got = np.asarray(jacobi7_wrap_pallas(t, hot, cold, r,
                                             block_z=bz, block_y=by,
                                             interpret=True))
        np.testing.assert_array_equal(got, default, err_msg=(bz, by))


# ---------------------------------------------------------------------------
# tuner integration: planner-legal shapes rank and ride Plan records


def _geom(side=512, itemsize=4):
    from stencil_tpu.geometry import Dim3, Radius
    from stencil_tpu.tuning import TuneGeometry

    return TuneGeometry(
        shard_interior_zyx=(side, side, side),
        min_interior_zyx=(side, side, side),
        radius=Radius.constant(1), counts=Dim3(1, 2, 2),
        elem_sizes=(itemsize,), dtype_strs=("float32",))


def test_tiling_candidate_space_is_planner_legal():
    from stencil_tpu.tuning import (rank_tiling_candidates,
                                    tiling_candidate_space)

    cands = tiling_candidate_space(_geom())
    assert cands and all(c.footprint_bytes <= TILE_SELECT_BUDGET_BYTES
                         for c in cands)
    ranked = rank_tiling_candidates(_geom(), cands)
    costs = [s for s, _c in ranked]
    assert costs == sorted(costs)
    # the winner is the judge-measured 512^3 fast point
    assert (ranked[0][1].block_z, ranked[0][1].block_y) == (8, 128)


def test_plan_record_carries_tiling_and_roundtrips(tmp_path):
    from stencil_tpu.tuning import (FakeTimer, fingerprint_inputs,
                                    load_plan, run_autotune,
                                    tiling_record)
    from stencil_tpu.geometry import Radius

    geom = _geom(side=64)
    inputs = fingerprint_inputs(
        platform="cpu", device_count=4, mesh_shape=[1, 2, 2],
        grid=[64, 128, 128], radius=Radius.constant(1),
        quantities={"q": "float32"}, boundary="PERIODIC")
    cache = tmp_path / "plans.json"
    plan = run_autotune(geom, inputs, FakeTimer(), cache_path=cache)
    assert plan.tiling == tiling_record(geom)
    rec = plan.tiling["jacobi7_halo_pallas"]
    assert rec["block"] and rec["footprint_bytes"] > 0
    # the cached record round-trips the tiling payload bit-for-bit
    cached = load_plan(plan.fingerprint, cache)
    assert cached is not None and cached.tiling == plan.tiling


def test_infeasible_geometry_records_constraint():
    from stencil_tpu.tuning import tiling_candidate_space, tiling_record

    # Y=17: no sublane-aligned block_y exists for the halo kernel
    geom = _geom()
    geom = type(geom)(shard_interior_zyx=(16, 17, 16),
                     min_interior_zyx=(16, 17, 16),
                     radius=geom.radius, counts=geom.counts,
                     elem_sizes=(4,), dtype_strs=("float32",))
    assert tiling_candidate_space(geom)  # esub falls back to 1: legal
    rec = tiling_record(geom)
    assert "jacobi7_halo_pallas" in rec and rec["jacobi7_halo_pallas"]


# ---------------------------------------------------------------------------
# CLI --plan-tiling


def test_cli_plan_tiling(tmp_path, capsys):
    import json

    from stencil_tpu.analysis.__main__ import main

    out = tmp_path / "plans.json"
    rc = main(["--plan-tiling", "*jacobi7_halo_pallas?512?",
               "--json", str(out)])
    assert rc == 0
    text = capsys.readouterr().out
    assert "best (8, 128)" in text
    data = json.loads(out.read_text())
    assert data["mode"] == "plan-tiling"
    (name,) = [k for k in data["plans"]
               if k.endswith("jacobi7_halo_pallas[512]")]
    entry = data["plans"][name]
    assert entry["expect"] == "legal" and entry["findings"] == []
    (kern,) = entry["kernels"].values()
    best = kern["plan"]["options"][0]
    assert (best["block_z"], best["block_y"]) == (8, 128)
    # an unmatched glob is a usage error, same contract as --only
    assert main(["--plan-tiling", "no.such.kernel.*"]) == 2
