"""The shipped-code target registry for stencil-lint.

Every stencil op, Pallas kernel, and collective exchange path the
framework ships is registered here with its declared contract; the
checkers in this package prove the contracts against the traced IR
(footprint/dma/collectives/vmem) or the lowered StableHLO
(hlo/costmodel). Negative-control fixtures under
``tests/fixtures/lint/`` define the same target types with
deliberately broken kernels (loaded via :func:`load_targets`) — each
checker must flag them, proving the pass is not vacuously green.

Coverage is drift-guarded: ``stencil_tpu.ops.PUBLIC_OPS`` and
``stencil_tpu.parallel.EXCHANGE_METHOD_TARGETS`` list every public op
and exchange method with the target name (prefix) here that covers it,
and ``tests/test_lint.py`` cross-checks both manifests against
:func:`default_targets` — new code cannot silently escape the gate.
"""

from __future__ import annotations

import functools
import importlib.util
from pathlib import Path
from typing import List, Union

from .collectives import CollectiveSpec, CollectiveTarget
from .costmodel import CostModelSpec, CostModelTarget
from .dma import PallasKernelSpec, PallasKernelTarget
from .donation import DonationSpec, DonationTarget
from .footprint import StencilOpSpec, StencilOpTarget
from .hlo import HloSpec, HloTarget
from .precision import PrecisionSpec, PrecisionTarget
from .recompile import RecompileSpec, RecompileTarget
from .schedule import ScheduleSpec, ScheduleTarget
from .transfer import TransferSpec, TransferTarget
from .vmem import VmemSpec, VmemTarget
from ..observatory.linkmap import LinkmapSpec, LinkmapTarget

Target = Union[StencilOpTarget, PallasKernelTarget, CollectiveTarget,
               HloTarget, CostModelTarget, VmemTarget, DonationTarget,
               TransferTarget, RecompileTarget, LinkmapTarget,
               ScheduleTarget, PrecisionTarget]


def _f32(shape):
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _mesh(shape):
    import jax

    from ..parallel.mesh import make_mesh

    n = shape[0] * shape[1] * shape[2]
    return make_mesh(shape, jax.devices()[:n])


# ---------------------------------------------------------------------------
# footprint targets: registered stencil ops vs. their declared Radius


def _jacobi7_spec() -> StencilOpSpec:
    from ..geometry import Dim3, Radius
    from ..ops.stencil_kernels import jacobi7

    radius = Radius.constant(1)
    interior = Dim3(8, 8, 8)
    shape = tuple(interior[2 - i] + radius.pad_lo()[2 - i]
                  + radius.pad_hi()[2 - i] for i in range(3))
    return StencilOpSpec(fn=lambda p: jacobi7(p, radius, interior),
                         args=(_f32(shape),), radius=radius,
                         interior=interior)


def _laplacian27_spec() -> StencilOpSpec:
    from ..geometry import Dim3, Radius
    from ..ops.stencil_kernels import laplacian27

    radius = Radius.constant(1)
    interior = Dim3(8, 8, 8)
    return StencilOpSpec(fn=lambda p: laplacian27(p, radius, interior),
                         args=(_f32((10, 10, 10)),), radius=radius,
                         interior=interior)


def _fd6_spec(kind: str, axes) -> StencilOpSpec:
    from ..geometry import Dim3, Radius
    from ..ops import fd6

    radius = Radius.constant(fd6.RADIUS)
    interior = Dim3(8, 8, 8)
    pad_lo = radius.pad_lo()
    shape = (14, 14, 14)  # 8 + 2 * RADIUS per dim

    if kind == "der1":
        fn = lambda p: fd6.der1(p, axes, 1.0, pad_lo, interior)  # noqa: E731
    elif kind == "der2":
        fn = lambda p: fd6.der2(p, axes, 1.0, pad_lo, interior)  # noqa: E731
    else:
        a, b = axes
        fn = lambda p: fd6.der_cross(p, a, b, 1.0, 1.0, pad_lo,  # noqa: E731
                                     interior)
    return StencilOpSpec(fn=fn, args=(_f32(shape),), radius=radius,
                         interior=interior)


def _mhd_rates_spec() -> StencilOpSpec:
    from ..geometry import Dim3, Radius
    from ..models.astaroth import FIELDS, MhdParams, mhd_rates
    from ..ops.fd6 import RADIUS, FieldData

    import jax.numpy as jnp

    radius = Radius.constant(RADIUS)
    interior = Dim3(8, 8, 8)
    pad_lo = radius.pad_lo()
    prm = MhdParams()
    inv_ds = (1.0 / prm.dsx, 1.0 / prm.dsy, 1.0 / prm.dsz)

    def fn(*padded):
        data = {q: FieldData(p, inv_ds, pad_lo, interior)
                for q, p in zip(FIELDS, padded)}
        rates = mhd_rates(data, prm, jnp.float32)
        return tuple(rates[q] for q in FIELDS)

    nf = len(FIELDS)
    return StencilOpSpec(fn=fn, args=tuple(_f32((14, 14, 14))
                                           for _ in range(nf)),
                         radius=radius, interior=interior,
                         padded_argnums=tuple(range(nf)))


# ---------------------------------------------------------------------------
# DMA-discipline targets: every Pallas kernel issuing (remote) DMA


def _rdma_exchange_spec(side: int = 8) -> PallasKernelSpec:
    import jax
    from jax.sharding import PartitionSpec as P

    from ..geometry import Radius
    from ..parallel.mesh import mesh_dim
    from ..parallel.pallas_exchange import exchange_shard_pallas

    mesh = _mesh((2, 2, 2))
    counts = mesh_dim(mesh)
    radius = Radius.constant(1)

    def shard(p):
        return exchange_shard_pallas(p, radius, counts, interpret=False)

    sm = jax.shard_map(shard, mesh=mesh, in_specs=P("z", "y", "x"),
                       out_specs=P("z", "y", "x"), check_vma=False)
    g = 2 * side
    return PallasKernelSpec(fn=sm, args=(_f32((g, g, g)),),
                            axis_names=("x", "y", "z"),
                            expect_remote_dma=True)


def _jacobi_overlap_spec(side: int = 8) -> PallasKernelSpec:
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..geometry import Dim3
    from ..ops.pallas_overlap import jacobi7_overlap_pallas

    mesh = _mesh((1, 2, 2))
    counts = Dim3(1, 2, 2)
    bz = 4 if side <= 8 else 8

    def shard(q):
        iz = jax.lax.axis_index("z")
        iy = jax.lax.axis_index("y")
        org = jnp.stack([iz * side, iy * side,
                         jnp.int32(0)]).astype(jnp.int32)
        return jacobi7_overlap_pallas(
            q, org, (side // 4, side // 2, side // 2),
            (5 * side // 8, side // 2, side // 2), 1, counts,
            block_z=bz, interpret=False)

    sm = jax.shard_map(shard, mesh=mesh, in_specs=P("z", "y", "x"),
                       out_specs=P("z", "y", "x"), check_vma=False)
    return PallasKernelSpec(fn=sm, args=(_f32((2 * side, 2 * side, side)),),
                            axis_names=("x", "y", "z"),
                            expect_remote_dma=True)


def _mhd_overlap_spec(pair: bool, side: int = 8) -> PallasKernelSpec:
    import jax
    from jax.sharding import PartitionSpec as P

    from ..geometry import Dim3
    from ..models.astaroth import FIELDS, MhdParams
    from ..ops.pallas_mhd_overlap import mhd_substep_overlap

    mesh = _mesh((1, 2, 2))
    counts = Dim3(1, 2, 2)
    prm = MhdParams()

    def shard(fields, w):
        f, wk = mhd_substep_overlap(fields, None if pair else w, 0, prm,
                                    prm.dt, counts, pair=pair,
                                    interpret=False)
        return f, (wk if wk is not None else f)

    spec = P("z", "y", "x")
    fspec = {q: spec for q in FIELDS}
    sm = jax.shard_map(shard, mesh=mesh, in_specs=(fspec, fspec),
                       out_specs=(fspec, fspec), check_vma=False)
    fields = {q: _f32((2 * side, 2 * side, side)) for q in FIELDS}
    w = {q: _f32((2 * side, 2 * side, side)) for q in FIELDS}
    return PallasKernelSpec(fn=sm, args=(fields, w),
                            axis_names=("x", "y", "z"),
                            expect_remote_dma=True)


def _jacobi_halo_kernel_spec(side: int = 8) -> PallasKernelSpec:
    """The fused halo kernel: no DMA at all — the checker proves its
    discipline vacuously and (more importantly) that it never gained a
    stray semaphore/DMA without review."""
    import jax.numpy as jnp

    from ..ops.pallas_halo import jacobi7_halo_pallas

    Z = Y = X = side
    slabs = {"zlo": _f32((1, Y, X)), "zhi": _f32((1, Y, X)),
             "ylo": _f32((Z, 8, X)), "yhi": _f32((Z, 8, X))}

    def fn(interior, zlo, zhi, ylo, yhi, org):
        return jacobi7_halo_pallas(
            interior, {"zlo": zlo, "zhi": zhi, "ylo": ylo, "yhi": yhi},
            org, (2, 4, 4), (5, 4, 4), 1, interpret=False)

    import jax
    org = jax.ShapeDtypeStruct((3,), jnp.int32)
    return PallasKernelSpec(
        fn=fn, args=(_f32((Z, Y, X)), slabs["zlo"], slabs["zhi"],
                     slabs["ylo"], slabs["yhi"], org),
        axis_names=(), expect_remote_dma=False)


# ---------------------------------------------------------------------------
# schedule-certification targets: checker 12 — the same remote-DMA
# kernels, their semaphore schedules certified sound under k-fold
# replay (the proof megastep's certificate-gated fusion consumes)

_SCHED_K = 4


def _schedule_from_kernel(build, expect_max_in_flight=None,
                          fused_by_megastep: bool = False
                          ) -> ScheduleSpec:
    """Lift a dma-checker kernel spec into a schedule spec: the same
    traceable fn, certified under ``_SCHED_K``-fold replay."""
    ps = build()
    return ScheduleSpec(
        fn=ps.fn, args=ps.args, axis_names=ps.axis_names,
        replay=_SCHED_K, expect_remote_dma=ps.expect_remote_dma,
        expect_max_in_flight=expect_max_in_flight,
        fused_by_megastep=fused_by_megastep)


def _overlap_schedule_spec() -> ScheduleSpec:
    from ..ops.pallas_overlap import SCHEDULE_EXPECT

    return _schedule_from_kernel(
        _jacobi_overlap_spec,
        expect_max_in_flight=SCHEDULE_EXPECT["max_in_flight"],
        fused_by_megastep=True)


def _mhd_overlap_schedule_spec(pair: bool) -> ScheduleSpec:
    from ..ops.pallas_mhd_overlap import SCHEDULE_EXPECT

    return _schedule_from_kernel(
        lambda: _mhd_overlap_spec(pair=pair),
        expect_max_in_flight=SCHEDULE_EXPECT["max_in_flight"])


def _halo_schedule_spec() -> ScheduleSpec:
    from ..ops.pallas_halo import SCHEDULE_EXPECT

    return _schedule_from_kernel(
        _jacobi_halo_kernel_spec,
        expect_max_in_flight=SCHEDULE_EXPECT["max_in_flight"])


def _overlap_segment_schedule_spec(side: int = 8) -> ScheduleSpec:
    """The fused overlap SEGMENT pinned as a registry target:
    ``_SCHED_K`` sequential ``jacobi7_overlap_pallas`` launches inside
    ONE traced program — the exact multi-launch shape megastep's
    chunk-of-1 unroll dispatches once the per-launch certificate
    licenses fusion (models/jacobi.py:_build_overlap_step). Every
    constituent launch must certify replay-safe; CI stage 1 asserts
    it."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..geometry import Dim3
    from ..ops.pallas_overlap import SCHEDULE_EXPECT, \
        jacobi7_overlap_pallas

    mesh = _mesh((1, 2, 2))
    counts = Dim3(1, 2, 2)
    bz = 4 if side <= 8 else 8

    def shard(q):
        iz = jax.lax.axis_index("z")
        iy = jax.lax.axis_index("y")
        org = jnp.stack([iz * side, iy * side,
                         jnp.int32(0)]).astype(jnp.int32)
        for _ in range(_SCHED_K):
            q = jacobi7_overlap_pallas(
                q, org, (side // 4, side // 2, side // 2),
                (5 * side // 8, side // 2, side // 2), 1, counts,
                block_z=bz, interpret=False)
        return q

    sm = jax.shard_map(shard, mesh=mesh, in_specs=P("z", "y", "x"),
                       out_specs=P("z", "y", "x"), check_vma=False)
    return ScheduleSpec(
        fn=sm, args=(_f32((2 * side, 2 * side, side)),),
        axis_names=("x", "y", "z"), replay=_SCHED_K,
        expect_remote_dma=True,
        expect_max_in_flight=SCHEDULE_EXPECT["max_in_flight"],
        fused_by_megastep=True)


def _schedule_targets() -> List[Target]:
    k = _SCHED_K
    return [
        ScheduleTarget(
            f"analysis.schedule.parallel.pallas_exchange."
            f"exchange_shard_pallas[k={k}]",
            lambda: _schedule_from_kernel(_rdma_exchange_spec)),
        ScheduleTarget(
            f"analysis.schedule.ops.pallas_overlap."
            f"jacobi7_overlap_pallas[k={k}]",
            _overlap_schedule_spec),
        ScheduleTarget(
            f"analysis.schedule.ops.pallas_mhd_overlap."
            f"mhd_substep_overlap[k={k}]",
            lambda: _mhd_overlap_schedule_spec(pair=False)),
        ScheduleTarget(
            f"analysis.schedule.ops.pallas_mhd_overlap."
            f"mhd_substep_overlap[pair,k={k}]",
            lambda: _mhd_overlap_schedule_spec(pair=True)),
        ScheduleTarget(
            f"analysis.schedule.ops.pallas_halo."
            f"jacobi7_halo_pallas[k={k}]",
            _halo_schedule_spec),
        ScheduleTarget(
            f"analysis.schedule.parallel.megastep."
            f"segment[overlap,k={k}]",
            _overlap_segment_schedule_spec),
    ]


# ---------------------------------------------------------------------------
# collective targets: ppermute bijections + axis-name hygiene


# the exchange_shard targets' geometry, shared by the collective spec
# builder AND the cost-model expectation so the two cannot drift: a
# (28,28,28) padded global over the 2x2x2 mesh -> (14,14,14) shards
_EXCHANGE_GLOBAL = (28, 28, 28)
_EXCHANGE_MESH = (2, 2, 2)


def _exchange_shard_shape():
    return tuple(g // m for g, m in zip(_EXCHANGE_GLOBAL,
                                        _EXCHANGE_MESH))


def _exchange_radius(radius_kind: str):
    from ..geometry import Radius

    if radius_kind == "r1":
        return Radius.constant(1)
    if radius_kind == "r3":
        return Radius.constant(3)
    if radius_kind == "asym":  # asymmetric, zero on some sides
        radius = Radius.constant(0)
        radius.set_dir((1, 0, 0), 2)
        radius.set_dir((-1, 0, 0), 1)
        radius.set_dir((0, 1, 0), 1)
        return radius
    raise ValueError(f"unknown exchange radius kind {radius_kind!r}")


def _exchange_spec(radius_kind: str) -> CollectiveSpec:
    import jax
    from jax.sharding import PartitionSpec as P

    from ..parallel.exchange import exchange_shard
    from ..parallel.mesh import mesh_dim

    mesh = _mesh(_EXCHANGE_MESH)
    counts = mesh_dim(mesh)
    radius = _exchange_radius(radius_kind)

    def shard(p):
        return exchange_shard(p, radius, counts)

    sm = jax.shard_map(shard, mesh=mesh, in_specs=P("z", "y", "x"),
                       out_specs=P("z", "y", "x"), check_vma=False)
    return CollectiveSpec(fn=sm, args=(_f32(_EXCHANGE_GLOBAL),),
                          axis_sizes=dict(mesh.shape),
                          expect_ppermute=True)


def _exchange_packed_uneven_spec() -> CollectiveSpec:
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..geometry import Dim3, Radius
    from ..parallel.exchange import exchange_shard_packed
    from ..parallel.mesh import mesh_dim

    mesh = _mesh((2, 2, 2))
    counts = mesh_dim(mesh)
    radius = Radius.constant(1)
    rem = Dim3(1, 1, 1)

    def shard(fields):
        return exchange_shard_packed(fields, radius, counts, rem=rem)

    spec = {"a": P("z", "y", "x"), "b": P("z", "y", "x")}
    sm = jax.shard_map(shard, mesh=mesh, in_specs=(spec,),
                       out_specs=spec, check_vma=False)
    fields = {"a": _f32((20, 20, 20)),
              "b": jax.ShapeDtypeStruct((20, 20, 20), jnp.bfloat16)}
    return CollectiveSpec(fn=sm, args=(fields,),
                          axis_sizes=dict(mesh.shape),
                          expect_ppermute=True)


def _exchange_allgather_spec() -> CollectiveSpec:
    import jax
    from jax.sharding import PartitionSpec as P

    from ..geometry import Radius
    from ..parallel.exchange import exchange_shard_allgather
    from ..parallel.mesh import mesh_dim

    mesh = _mesh((2, 2, 2))
    counts = mesh_dim(mesh)
    radius = Radius.constant(1)

    def shard(p):
        return exchange_shard_allgather(p, radius, counts)

    sm = jax.shard_map(shard, mesh=mesh, in_specs=P("z", "y", "x"),
                       out_specs=P("z", "y", "x"), check_vma=False)
    return CollectiveSpec(fn=sm, args=(_f32((16, 16, 16)),),
                          axis_sizes=dict(mesh.shape))


def _interior_slabs_spec(yzext: bool) -> CollectiveSpec:
    import jax
    from jax.sharding import PartitionSpec as P

    from ..geometry import Dim3
    from ..parallel.exchange import exchange_interior_slabs

    mesh = _mesh((1, 2, 2))
    counts = Dim3(1, 2, 2)

    def shard(p):
        s = exchange_interior_slabs(p, counts, rz=8, ry=8, radius_rows=3,
                                    y_z_extended=yzext)
        return (s["zlo"], s["zhi"], s["ylo"], s["yhi"])

    spec = P("z", "y", "x")
    sm = jax.shard_map(shard, mesh=mesh, in_specs=spec,
                       out_specs=(spec,) * 4, check_vma=False)
    return CollectiveSpec(fn=sm, args=(_f32((16, 16, 8)),),
                          axis_sizes=dict(mesh.shape),
                          expect_ppermute=True)


def _temporal_group_spec(s: int = 2) -> CollectiveSpec:
    """The temporal-blocking fused group (parallel/temporal.py): one
    depth-s exchange + s jacobi sub-steps on shrinking windows. Audited
    like any exchange method — ppermute bijections, collective-permute-
    only lowering, and the deep-slab byte model must match the HLO."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ..geometry import Radius
    from ..ops.stencil_kernels import jacobi7
    from ..parallel.mesh import mesh_dim
    from ..parallel.methods import Method
    from ..parallel.temporal import temporal_shard_steps

    mesh = _mesh(_EXCHANGE_MESH)
    counts = mesh_dim(mesh)
    radius = Radius.constant(1)

    def upd(blocks, dims, off, k):
        return {"q": jacobi7(blocks["q"], radius, dims)}

    def shard(p):
        return temporal_shard_steps({"q": p}, radius, counts,
                                    Method.PpermuteSlab, upd, s)["q"]

    sm = jax.shard_map(shard, mesh=mesh, in_specs=P("z", "y", "x"),
                       out_specs=P("z", "y", "x"), check_vma=False)
    side = (8 + 2 * s)  # 8^3 interiors + deep pads, per shard
    g = tuple(side * m for m in _EXCHANGE_MESH)
    return CollectiveSpec(fn=sm, args=(_f32(g),),
                          axis_sizes=dict(mesh.shape),
                          expect_ppermute=True)


def _temporal_group_cost(s: int = 2) -> CostModelSpec:
    from ..geometry import Dim3, Radius
    from .costmodel import deep_exchange_bytes_per_shard

    cs = _temporal_group_spec(s)
    expected = deep_exchange_bytes_per_shard(
        (8, 8, 8), Radius.constant(1), Dim3(*_EXCHANGE_MESH), 4, s)
    return CostModelSpec(fn=cs.fn, args=cs.args,
                         expected_bytes_per_shard=expected)


#: the per-axis depth vector the asymmetric-group targets pin:
#: deep z (the DCN-friendly axis), shallow x/y — Dim3(1, 1, 2)
_ASYM_DEPTHS = ((1, 1, 2),)


def _temporal_group_asym_spec(depths=None) -> CollectiveSpec:
    """The PER-AXIS (asymmetric) temporal group: one exchange shipping
    each axis at its own depth, then ``max(depths)`` sub-steps with
    mid-group refreshes of the shallow axes (``refresh_axes``). Audited
    like the uniform group — ppermute bijections, collective-permute-
    only lowering, and the asymmetric byte model matching the HLO."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ..geometry import Radius, normalize_depths
    from ..ops.stencil_kernels import jacobi7
    from ..parallel.mesh import mesh_dim
    from ..parallel.methods import Method
    from ..parallel.temporal import temporal_shard_steps

    d = normalize_depths(depths if depths is not None
                         else _ASYM_DEPTHS[0])
    mesh = _mesh(_EXCHANGE_MESH)
    counts = mesh_dim(mesh)
    radius = Radius.constant(1)

    def upd(blocks, dims, off, k):
        return {"q": jacobi7(blocks["q"], radius, dims)}

    def shard(p):
        return temporal_shard_steps({"q": p}, radius, counts,
                                    Method.PpermuteSlab, upd, d)["q"]

    sm = jax.shard_map(shard, mesh=mesh, in_specs=P("z", "y", "x"),
                       out_specs=P("z", "y", "x"), check_vma=False)
    # 8^3 interiors + per-axis deep pads, (z, y, x) layout
    sides = (8 + 2 * d.z, 8 + 2 * d.y, 8 + 2 * d.x)
    g = tuple(side * m for side, m in zip(sides, _EXCHANGE_MESH))
    return CollectiveSpec(fn=sm, args=(_f32(g),),
                          axis_sizes=dict(mesh.shape),
                          expect_ppermute=True)


def _temporal_group_asym_cost(depths=None) -> CostModelSpec:
    from ..geometry import Dim3, Radius
    from .costmodel import asymmetric_group_bytes_per_shard

    d = depths if depths is not None else _ASYM_DEPTHS[0]
    cs = _temporal_group_asym_spec(d)
    expected = asymmetric_group_bytes_per_shard(
        (8, 8, 8), Radius.constant(1), Dim3(*_EXCHANGE_MESH), 4, d)
    return CostModelSpec(fn=cs.fn, args=cs.args,
                         expected_bytes_per_shard=expected)


def _deep_tail_exchange_spec() -> CollectiveSpec:
    """The partial-depth exchange on a deep-carry allocation (the tail
    steps of a blocked loop): wire depth r on s*r pads."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ..geometry import Radius
    from ..parallel.exchange import exchange_shard
    from ..parallel.mesh import mesh_dim

    mesh = _mesh(_EXCHANGE_MESH)
    counts = mesh_dim(mesh)
    radius = Radius.constant(1)

    def shard(p):
        return exchange_shard(p, radius, counts,
                              alloc_radius=radius.deepened(2))

    sm = jax.shard_map(shard, mesh=mesh, in_specs=P("z", "y", "x"),
                       out_specs=P("z", "y", "x"), check_vma=False)
    g = tuple(12 * m for m in _EXCHANGE_MESH)  # 8^3 interiors, pads 2
    return CollectiveSpec(fn=sm, args=(_f32(g),),
                          axis_sizes=dict(mesh.shape),
                          expect_ppermute=True)


def _deep_tail_exchange_cost() -> CostModelSpec:
    from ..geometry import Dim3, Radius

    cs = _deep_tail_exchange_spec()
    # base-radius rows ride on the DEEP allocation's cross-sections
    expected = _sweep_bytes((12, 12, 12), Radius.constant(1),
                            Dim3(*_EXCHANGE_MESH), 4)
    return CostModelSpec(fn=cs.fn, args=cs.args,
                         expected_bytes_per_shard=expected)


def _make_exchange_jit_spec() -> CollectiveSpec:
    from ..geometry import Radius
    from ..parallel.exchange import make_exchange
    from ..parallel.methods import Method

    mesh = _mesh((2, 2, 2))
    radius = Radius.constant(1)
    ex = make_exchange(mesh, radius, Method.PpermutePacked)
    return CollectiveSpec(fn=ex, args=({"q": _f32((20, 20, 20))},),
                          axis_sizes=dict(mesh.shape),
                          expect_ppermute=True)


# ---------------------------------------------------------------------------
# HLO / cost-model targets: every exchange METHOD, audited at the
# StableHLO level (collective-permute-only lowering) and cross-checked
# against the analytic halo byte model. The builders reuse the
# collective specs above and attach the geometry-derived expectation
# from parallel.exchange's byte counters — the same source of truth
# the runtime observability (utils/profiling.exchange_stats_report)
# prints.


def _sweep_bytes(shard_padded_zyx, radius, counts, elem_size) -> int:
    from ..parallel.exchange import exchanged_bytes_per_sweep

    return sum(exchanged_bytes_per_sweep(shard_padded_zyx, radius,
                                         counts, elem_size).values())


def _exchange_cost(radius_kind: str) -> CostModelSpec:
    from ..geometry import Dim3

    cs = _exchange_spec(radius_kind)
    expected = _sweep_bytes(_exchange_shard_shape(),
                            _exchange_radius(radius_kind),
                            Dim3(*_EXCHANGE_MESH), 4)
    return CostModelSpec(fn=cs.fn, args=cs.args,
                         expected_bytes_per_shard=expected)


def _packed_uneven_cost() -> CostModelSpec:
    from ..geometry import Dim3, Radius

    cs = _exchange_packed_uneven_spec()
    r = Radius.constant(1)
    counts = Dim3(2, 2, 2)
    # capacity shard (10,10,10) per field; bf16 packs in its own group
    expected = (_sweep_bytes((10, 10, 10), r, counts, 4)
                + _sweep_bytes((10, 10, 10), r, counts, 2))
    return CostModelSpec(fn=cs.fn, args=cs.args,
                         expected_bytes_per_shard=expected)


def _allgather_cost() -> CostModelSpec:
    from ..geometry import Dim3, Radius

    cs = _exchange_allgather_spec()
    expected = _sweep_bytes((8, 8, 8), Radius.constant(1),
                            Dim3(2, 2, 2), 4)
    return CostModelSpec(fn=cs.fn, args=cs.args,
                         expected_bytes_per_shard=expected)


def _interior_slabs_cost(yzext: bool) -> CostModelSpec:
    from ..geometry import Dim3
    from ..parallel.exchange import interior_slab_bytes

    cs = _interior_slabs_spec(yzext)
    expected = interior_slab_bytes((8, 8, 8), Dim3(1, 2, 2), 3, 4,
                                   y_z_extended=yzext)
    return CostModelSpec(fn=cs.fn, args=cs.args,
                         expected_bytes_per_shard=expected)


def _make_exchange_jit_cost() -> CostModelSpec:
    from ..geometry import Dim3, Radius

    cs = _make_exchange_jit_spec()
    expected = _sweep_bytes((10, 10, 10), Radius.constant(1),
                            Dim3(2, 2, 2), 4)
    return CostModelSpec(fn=cs.fn, args=cs.args,
                         expected_bytes_per_shard=expected)


def _hlo_from_collective(build, allow=("collective_permute",)) -> HloSpec:
    cs = build()
    return HloSpec(fn=cs.fn, args=cs.args, allow=tuple(allow))


# ---------------------------------------------------------------------------
# irredundant wire-layout targets: the packed layout (parallel/
# packing.py) keeps the slab engine's collective bill — 2 ppermutes
# per active radius direction — but each sweep ships only the rows no
# earlier sweep already delivered, so every halo cell crosses the wire
# exactly once. Each registered slab config gets an irredundant twin
# under the same three gates (ppermute bijection, collective-permute-
# only lowering, analytic-vs-HLO byte equality), with the byte
# expectation additionally pinned STRICTLY below the slab bill for
# every config carrying a diagonal (edge/corner) ride-along.
# tests/fixtures/lint/bad_packing.py (a fat slab program sold under
# the irredundant byte model) is the negative control.


def _irr_bytes(shard_padded_zyx, radius, counts, elem_size,
               wire_format=None, alloc_radius=None) -> int:
    from .costmodel import sweep_wire_bytes

    return sum(sweep_wire_bytes(shard_padded_zyx, radius, counts,
                                elem_size, wire_format=wire_format,
                                layout="irredundant",
                                alloc_radius=alloc_radius).values())


def _exchange_irr_spec(radius_kind: str) -> CollectiveSpec:
    import jax
    from jax.sharding import PartitionSpec as P

    from ..parallel.exchange import exchange_shard
    from ..parallel.mesh import mesh_dim

    mesh = _mesh(_EXCHANGE_MESH)
    counts = mesh_dim(mesh)
    radius = _exchange_radius(radius_kind)

    def shard(p):
        return exchange_shard(p, radius, counts,
                              wire_layout="irredundant")

    sm = jax.shard_map(shard, mesh=mesh, in_specs=P("z", "y", "x"),
                       out_specs=P("z", "y", "x"), check_vma=False)
    return CollectiveSpec(fn=sm, args=(_f32(_EXCHANGE_GLOBAL),),
                          axis_sizes=dict(mesh.shape),
                          expect_ppermute=True)


def _exchange_irr_hlo(radius_kind: str) -> HloSpec:
    cs = _exchange_irr_spec(radius_kind)
    # the layout shrinks messages, never their count: same ppermute
    # bill as the slab engine (one per nonzero radius direction)
    n = {"r1": 6, "r3": 6, "asym": 3}[radius_kind]
    return HloSpec(fn=cs.fn, args=cs.args,
                   allow=("collective_permute",),
                   exact_counts={"collective_permute": n})


def _exchange_irr_cost(radius_kind: str) -> CostModelSpec:
    from ..geometry import Dim3

    cs = _exchange_irr_spec(radius_kind)
    counts = Dim3(*_EXCHANGE_MESH)
    radius = _exchange_radius(radius_kind)
    expected = _irr_bytes(_exchange_shard_shape(), radius, counts, 4)
    # the layout's contract, pinned: strictly below the slab bill
    # (every registered config has a diagonal carry to shed)
    assert expected < _sweep_bytes(_exchange_shard_shape(), radius,
                                   counts, 4)
    return CostModelSpec(fn=cs.fn, args=cs.args,
                         expected_bytes_per_shard=expected)


def _exchange_packed_irr_uneven_spec() -> CollectiveSpec:
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..geometry import Dim3, Radius
    from ..parallel.exchange import exchange_shard_packed
    from ..parallel.mesh import mesh_dim

    mesh = _mesh((2, 2, 2))
    counts = mesh_dim(mesh)
    radius = Radius.constant(1)
    rem = Dim3(1, 1, 1)

    def shard(fields):
        return exchange_shard_packed(fields, radius, counts, rem=rem,
                                     wire_layout="irredundant")

    spec = {"a": P("z", "y", "x"), "b": P("z", "y", "x")}
    sm = jax.shard_map(shard, mesh=mesh, in_specs=(spec,),
                       out_specs=spec, check_vma=False)
    fields = {"a": _f32((20, 20, 20)),
              "b": jax.ShapeDtypeStruct((20, 20, 20), jnp.bfloat16)}
    return CollectiveSpec(fn=sm, args=(fields,),
                          axis_sizes=dict(mesh.shape),
                          expect_ppermute=True)


def _packed_irr_uneven_cost() -> CostModelSpec:
    from ..geometry import Dim3, Radius

    cs = _exchange_packed_irr_uneven_spec()
    r = Radius.constant(1)
    counts = Dim3(2, 2, 2)
    # capacity shard (10,10,10); static irredundant boxes — a short
    # shard's overhang rows are dead slack or halo rows a later sweep
    # rewrites, so uneven remainders change nothing on the wire
    expected = (_irr_bytes((10, 10, 10), r, counts, 4)
                + _irr_bytes((10, 10, 10), r, counts, 2))
    assert expected < (_sweep_bytes((10, 10, 10), r, counts, 4)
                       + _sweep_bytes((10, 10, 10), r, counts, 2))
    return CostModelSpec(fn=cs.fn, args=cs.args,
                         expected_bytes_per_shard=expected)


def _temporal_irr_spec(s: int = 2) -> CollectiveSpec:
    """The temporal-blocking fused group on irredundant wire boxes —
    where the layout's win is largest: the deep slab's diagonal carry
    grows with s^2 while the irredundant boxes grow only with s."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ..geometry import Radius
    from ..ops.stencil_kernels import jacobi7
    from ..parallel.mesh import mesh_dim
    from ..parallel.methods import Method
    from ..parallel.temporal import temporal_shard_steps

    mesh = _mesh(_EXCHANGE_MESH)
    counts = mesh_dim(mesh)
    radius = Radius.constant(1)

    def upd(blocks, dims, off, k):
        return {"q": jacobi7(blocks["q"], radius, dims)}

    def shard(p):
        return temporal_shard_steps({"q": p}, radius, counts,
                                    Method.PpermuteSlab, upd, s,
                                    wire_layout="irredundant")["q"]

    sm = jax.shard_map(shard, mesh=mesh, in_specs=P("z", "y", "x"),
                       out_specs=P("z", "y", "x"), check_vma=False)
    side = (8 + 2 * s)
    g = tuple(side * m for m in _EXCHANGE_MESH)
    return CollectiveSpec(fn=sm, args=(_f32(g),),
                          axis_sizes=dict(mesh.shape),
                          expect_ppermute=True)


def _temporal_irr_cost(s: int = 2) -> CostModelSpec:
    from ..geometry import Dim3, Radius
    from .costmodel import deep_exchange_bytes_per_shard

    cs = _temporal_irr_spec(s)
    expected = deep_exchange_bytes_per_shard(
        (8, 8, 8), Radius.constant(1), Dim3(*_EXCHANGE_MESH), 4, s,
        wire_layout="irredundant")
    assert expected < deep_exchange_bytes_per_shard(
        (8, 8, 8), Radius.constant(1), Dim3(*_EXCHANGE_MESH), 4, s)
    return CostModelSpec(fn=cs.fn, args=cs.args,
                         expected_bytes_per_shard=expected)


def _deep_tail_irr_spec() -> CollectiveSpec:
    """The partial-depth tail exchange, irredundant: wire-radius boxes
    on the DEEP allocation — extension spans sized by the wire radius,
    so the tail sheds the deep slab's fat cross-sections entirely."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ..geometry import Radius
    from ..parallel.exchange import exchange_shard
    from ..parallel.mesh import mesh_dim

    mesh = _mesh(_EXCHANGE_MESH)
    counts = mesh_dim(mesh)
    radius = Radius.constant(1)

    def shard(p):
        return exchange_shard(p, radius, counts,
                              alloc_radius=radius.deepened(2),
                              wire_layout="irredundant")

    sm = jax.shard_map(shard, mesh=mesh, in_specs=P("z", "y", "x"),
                       out_specs=P("z", "y", "x"), check_vma=False)
    g = tuple(12 * m for m in _EXCHANGE_MESH)
    return CollectiveSpec(fn=sm, args=(_f32(g),),
                          axis_sizes=dict(mesh.shape),
                          expect_ppermute=True)


def _deep_tail_irr_cost() -> CostModelSpec:
    from ..geometry import Dim3, Radius

    cs = _deep_tail_irr_spec()
    r = Radius.constant(1)
    expected = _irr_bytes((12, 12, 12), r, Dim3(*_EXCHANGE_MESH), 4,
                          alloc_radius=r.deepened(2))
    assert expected < _sweep_bytes((12, 12, 12), r,
                                   Dim3(*_EXCHANGE_MESH), 4)
    return CostModelSpec(fn=cs.fn, args=cs.args,
                         expected_bytes_per_shard=expected)


def _rdma_hlo_spec() -> HloSpec:
    """The PallasDMA exchange method: off-TPU the checker records a
    capability-gate skip (pallas_call cannot lower there); on a TPU
    backend it proves the kernel adds no XLA-level collectives around
    its explicit RDMA."""
    cs = _rdma_exchange_spec()
    return HloSpec(fn=cs.fn, args=cs.args, allow=(),
                   expect_collective=False)


# ---------------------------------------------------------------------------
# tuning-plan targets: every exchange configuration the autotuner
# (stencil_tpu/tuning) can EMIT — Method x exchange_every over the
# plan's depth set — built exactly the way plan application deploys
# them (make_exchange on the deepened radius). Whatever plan the tuner
# picks, its data path is already under the HLO ppermute-only gate and
# the analytic byte cross-check; a tuned win can never smuggle in an
# unaudited lowering. (PallasDMA plans exist only where the RDMA
# engine is runnable; its path is audited by the
# parallel.pallas_exchange targets above, aliased below so the
# coverage manifest's Auto entry maps to live targets.)

_PLAN_INTERIOR = 8


def _plan_depths():
    from ..tuning.plan import DEFAULT_DEPTHS

    return DEFAULT_DEPTHS


def _plan_exchange_spec(method_name: str, s: int,
                        layout: str = "slab") -> CollectiveSpec:
    from ..geometry import Radius
    from ..parallel.exchange import make_exchange
    from ..parallel.methods import Method

    mesh = _mesh(_EXCHANGE_MESH)
    deep = Radius.constant(1).deepened(s)
    ex = make_exchange(mesh, deep, Method[method_name],
                       wire_layout=layout)
    side = _PLAN_INTERIOR + 2 * s
    g = tuple(side * m for m in _EXCHANGE_MESH)
    return CollectiveSpec(fn=ex, args=({"q": _f32(g)},),
                          axis_sizes=dict(mesh.shape),
                          expect_ppermute=(method_name != "AllGather"))


def _plan_exchange_hlo(method_name: str, s: int,
                       layout: str = "slab") -> HloSpec:
    allow = (("all_gather",) if method_name == "AllGather"
             else ("collective_permute",))
    return _hlo_from_collective(
        lambda: _plan_exchange_spec(method_name, s, layout),
        allow=allow)


def _plan_exchange_cost(method_name: str, s: int,
                        layout: str = "slab") -> CostModelSpec:
    from ..geometry import Dim3, Radius
    from .costmodel import sweep_wire_bytes

    cs = _plan_exchange_spec(method_name, s, layout)
    side = _PLAN_INTERIOR + 2 * s
    expected = sum(sweep_wire_bytes(
        (side, side, side), Radius.constant(1).deepened(s),
        Dim3(*_EXCHANGE_MESH), 4, layout=layout).values())
    return CostModelSpec(fn=cs.fn, args=cs.args,
                         expected_bytes_per_shard=expected)


def _plan_targets() -> List[Target]:
    targets: List[Target] = []
    emittable = ([("PpermuteSlab", s) for s in _plan_depths()]
                 + [("PpermutePacked", s) for s in _plan_depths()]
                 + [("AllGather", 1)])
    for method, s in emittable:
        targets.append(HloTarget(
            f"tuning.plan[{method},s={s},hlo]",
            lambda m=method, d=s: _plan_exchange_hlo(m, d)))
        targets.append(CostModelTarget(
            f"tuning.plan[{method},s={s},cost]",
            lambda m=method, d=s: _plan_exchange_cost(m, d)))
    # the tuner's wire-layout axis (candidate keys
    # ``...,layout=irredundant``): one audited irredundant plan per
    # ppermute method, at a representative depth each
    for method, s in (("PpermuteSlab", 2), ("PpermutePacked", 4)):
        targets.append(HloTarget(
            f"tuning.plan[{method},s={s},layout=irredundant,hlo]",
            lambda m=method, d=s: _plan_exchange_hlo(
                m, d, "irredundant")))
        targets.append(CostModelTarget(
            f"tuning.plan[{method},s={s},layout=irredundant,cost]",
            lambda m=method, d=s: _plan_exchange_cost(
                m, d, "irredundant")))
    # the RDMA plan path (emittable on TPU only) — same audited spec
    # as parallel.pallas_exchange.exchange_shard_pallas[hlo]
    targets.append(HloTarget("tuning.plan[PallasDMA,s=1,hlo]",
                             _rdma_hlo_spec))
    return targets


# ---------------------------------------------------------------------------
# ensemble-serving targets: the batched member axis must be a free
# ride on the wire — the vmapped exchange lowers to the SAME
# collective-permutes as one member, each carrying the batch, so wire
# bytes are EXACTLY n_members x the single-member analytic model, and
# the batched production step smuggles in no extra collectives.

_ENSEMBLE_N = 4


def _ensemble_exchange_spec() -> CollectiveSpec:
    import jax
    from jax.sharding import PartitionSpec as P

    from ..parallel.exchange import exchange_shard
    from ..parallel.mesh import mesh_dim

    mesh = _mesh(_EXCHANGE_MESH)
    counts = mesh_dim(mesh)
    radius = _exchange_radius("r1")

    def shard(batched):
        return jax.vmap(
            lambda p: exchange_shard(p, radius, counts))(batched)

    spec = P(None, "z", "y", "x")
    sm = jax.shard_map(shard, mesh=mesh, in_specs=spec, out_specs=spec,
                       check_vma=False)
    return CollectiveSpec(fn=sm,
                          args=(_f32((_ENSEMBLE_N,) + _EXCHANGE_GLOBAL),),
                          axis_sizes=dict(mesh.shape),
                          expect_ppermute=True)


def _ensemble_exchange_cost() -> CostModelSpec:
    from ..geometry import Dim3

    cs = _ensemble_exchange_spec()
    # bytes scale EXACTLY xN over the single-member sweep model — the
    # serving contract: batching multiplies payload, never rounds
    expected = _ENSEMBLE_N * _sweep_bytes(_exchange_shard_shape(),
                                          _exchange_radius("r1"),
                                          Dim3(*_EXCHANGE_MESH), 4)
    return CostModelSpec(fn=cs.fn, args=cs.args,
                         expected_bytes_per_shard=expected)


def _ensemble_step_spec() -> HloSpec:
    """The production batched Jacobi step (serving/ensemble.py): the
    same 6 collective-permutes as the single-member step — pinned
    exactly, so a vmap batching regression that unrolled the member
    axis into per-member collectives fails the gate."""
    from ..serving.ensemble import EnsembleJacobi

    eng = EnsembleJacobi(_ENSEMBLE_N, 24, 24, 24,
                         mesh_shape=_EXCHANGE_MESH)
    hot, cold = eng._param_args()
    import jax.numpy as jnp
    args = (eng.state["temp"], hot, cold, jnp.asarray(1, jnp.int32))
    return HloSpec(fn=eng._step_n, args=args,
                   allow=("collective_permute",),
                   exact_counts={"collective_permute": 6})


def _ensemble_probe_spec() -> HloSpec:
    """The per-member health probe: (N, 2, nq) stats via still exactly
    ONE small all-reduce (the vmapped pmax batches, it does not
    multiply)."""
    from ..serving.ensemble import make_ensemble_probe

    mesh = _mesh((2, 2, 2))
    fn = make_ensemble_probe(mesh, ["a", "b"])
    fields = {"a": _f32((_ENSEMBLE_N, 16, 16, 16)),
              "b": _f32((_ENSEMBLE_N, 16, 16, 16))}
    return HloSpec(fn=fn, args=(fields,), allow=("all_reduce",),
                   exact_counts={"all_reduce": 1})


# ---------------------------------------------------------------------------
# resilience targets: the health sentinel's in-graph probe. The probe
# rides the production step loop, so its communication contract is the
# whole point: exactly ONE small all-reduce (the stacked-stats pmax)
# and nothing else — a sentinel that smuggled extra collectives into
# every check_every-th step would tax the fabric it is guarding.


def _health_probe_spec() -> HloSpec:
    import jax
    from jax.sharding import PartitionSpec as P

    from ..resilience.health import probe_shard

    mesh = _mesh((2, 2, 2))
    spec = P("z", "y", "x")

    def shard(a, b):
        return probe_shard({"a": a, "b": b})

    sm = jax.shard_map(shard, mesh=mesh, in_specs=(spec, spec),
                       out_specs=P(), check_vma=False)
    return HloSpec(fn=sm, args=(_f32((16, 16, 16)), _f32((16, 16, 16))),
                   allow=("all_reduce",),
                   exact_counts={"all_reduce": 1})


def _health_step_probe_spec() -> HloSpec:
    """The probe fused INTO the production jacobi step: the step's own
    collective-permutes plus exactly one all-reduce — the lowering the
    resilient run loop actually dispatches on probe steps."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ..geometry import Dim3, Radius
    from ..models.jacobi import jacobi_shard_step
    from ..parallel.exchange import shard_origin
    from ..parallel.mesh import mesh_dim
    from ..parallel.methods import Method
    from ..resilience.health import probe_shard

    mesh = _mesh(_EXCHANGE_MESH)
    counts = mesh_dim(mesh)
    radius = Radius.constant(1)
    local = Dim3(12, 12, 12)
    gsize = Dim3(24, 24, 24)

    def shard(p):
        origin = shard_origin(local, Dim3(0, 0, 0))
        stepped = jacobi_shard_step(p, radius, counts, local, gsize,
                                    origin, Method.PpermuteSlab)
        return stepped, probe_shard({"temp": stepped})

    spec = P("z", "y", "x")
    sm = jax.shard_map(shard, mesh=mesh, in_specs=spec,
                       out_specs=(spec, P()), check_vma=False)
    # 6 ppermutes = the slab exchange's own 2-per-axis contract; a
    # probe-fusion regression that re-triggers the exchange would
    # double them and must fail the gate, not just the all_reduce pin
    return HloSpec(fn=sm, args=(_f32(_EXCHANGE_GLOBAL),),
                   allow=("collective_permute", "all_reduce"),
                   exact_counts={"all_reduce": 1,
                                 "collective_permute": 6})


# ---------------------------------------------------------------------------
# telemetry targets: the in-graph step-metrics instrumentation
# (stencil_tpu/telemetry/probe.py). Its license to ride the production
# loop is the acceptance contract verbatim: metric columns piggyback
# on the health probe's ONE existing all-reduce, so the instrumented
# production step lowers to the SAME collectives as the bare step —
# 6 collective-permutes + exactly 1 all-reduce, with the exchange's
# byte cross-check still exact (telemetry adds zero wire bytes).


def _telemetry_probe_spec() -> HloSpec:
    """The metrics-carrying probe alone: still exactly ONE small
    all-reduce — the extra columns ride the stacked-stats pmax."""
    import jax
    import jax.numpy as jnp

    from ..resilience.health import make_probe
    from ..telemetry.probe import STEP_METRIC_NAMES

    mesh = _mesh((2, 2, 2))
    fn = make_probe(mesh, ["a", "b"], extra_names=STEP_METRIC_NAMES)
    fields = {"a": _f32((16, 16, 16)), "b": _f32((16, 16, 16))}
    vec = jax.ShapeDtypeStruct((len(STEP_METRIC_NAMES),), jnp.float32)
    return HloSpec(fn=fn, args=(fields, vec), allow=("all_reduce",),
                   exact_counts={"all_reduce": 1})


def _telemetry_step_probe_fn():
    """The INSTRUMENTED production jacobi step: step + metrics-carrying
    probe fused, exactly as the resilient run loop dispatches it on
    probe steps when telemetry is on. Shared by the hlo gate and the
    byte cross-check so the two audit one program."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ..geometry import Dim3
    from ..models.jacobi import jacobi_shard_step
    from ..parallel.exchange import shard_origin
    from ..parallel.mesh import mesh_dim
    from ..parallel.methods import Method
    from ..resilience.health import probe_shard
    from ..telemetry.probe import STEP_METRIC_NAMES

    mesh = _mesh(_EXCHANGE_MESH)
    counts = mesh_dim(mesh)
    radius = _exchange_radius("r1")
    local = Dim3(12, 12, 12)
    gsize = Dim3(24, 24, 24)

    def shard(p, vec):
        origin = shard_origin(local, Dim3(0, 0, 0))
        stepped = jacobi_shard_step(p, radius, counts, local, gsize,
                                    origin, Method.PpermuteSlab)
        extra = {m: vec[i] for i, m in enumerate(STEP_METRIC_NAMES)}
        return stepped, probe_shard({"temp": stepped}, extra=extra)

    spec = P("z", "y", "x")
    sm = jax.shard_map(shard, mesh=mesh, in_specs=(spec, P()),
                       out_specs=(spec, P()), check_vma=False)
    import jax.numpy as jnp
    vec = jax.ShapeDtypeStruct((len(STEP_METRIC_NAMES),), jnp.float32)
    return sm, (_f32(_EXCHANGE_GLOBAL), vec)


def _telemetry_step_probe_spec() -> HloSpec:
    fn, args = _telemetry_step_probe_fn()
    # identical pins to resilience.health.step+probe[hlo]: telemetry
    # must not change the production step's collective bill at all
    return HloSpec(fn=fn, args=args,
                   allow=("collective_permute", "all_reduce"),
                   exact_counts={"all_reduce": 1,
                                 "collective_permute": 6})


def _telemetry_step_probe_cost() -> CostModelSpec:
    """Zero extra wire bytes: the instrumented step's exchange still
    moves exactly the analytic halo bytes (the all-reduce is outside
    ``count_kinds`` by the package's byte convention — its count is
    pinned by the hlo target above)."""
    from ..geometry import Dim3

    fn, args = _telemetry_step_probe_fn()
    expected = _sweep_bytes(_exchange_shard_shape(),
                            _exchange_radius("r1"),
                            Dim3(*_EXCHANGE_MESH), 4)
    return CostModelSpec(fn=fn, args=args,
                         expected_bytes_per_shard=expected,
                         count_kinds=("collective_permute",))


# ---------------------------------------------------------------------------
# megastep targets: the whole-campaign fused segment
# (parallel/megastep.py). A check_every=k segment must compile to ONE
# program whose collective bill is exactly k x the per-step
# collective_permute count plus ONE small all-reduce per probe row and
# NOTHING else, with the exchange bytes exactly k x the per-step
# analytic model — the fusion can neither smuggle in hidden
# communication nor re-reduce the probe per sub-step
# (tests/fixtures/lint/bad_megastep.py is that negative control).

_MEGASTEP_K = 4
_MEGASTEP_PROBE_EVERY = 2


def _megastep_segment_fn(probe_every: int = _MEGASTEP_PROBE_EVERY):
    """The production fused segment over the jacobi shard step: k
    steps + the metric-carrying probe every ``probe_every`` sub-steps,
    built with the same ``fused_segment_shard`` machinery the model
    and driver deploy. Shared by the hlo gate and the byte cross-check
    so both audit one program."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..geometry import Dim3
    from ..models.jacobi import jacobi_shard_step
    from ..parallel.exchange import shard_origin
    from ..parallel.megastep import (fused_segment_shard, health_probe,
                                     segment_chunks)
    from ..parallel.mesh import mesh_dim
    from ..parallel.methods import Method
    from ..telemetry.probe import STEP_METRIC_NAMES

    mesh = _mesh(_EXCHANGE_MESH)
    counts = mesh_dim(mesh)
    radius = _exchange_radius("r1")
    local = Dim3(12, 12, 12)
    gsize = Dim3(24, 24, 24)

    def shard(p, vec):
        origin = shard_origin(local, Dim3(0, 0, 0))

        def advance(q, c, i):
            return jacobi_shard_step(q, radius, counts, local, gsize,
                                     origin, Method.PpermuteSlab)

        probe = health_probe(lambda q: {"temp": q}, base_vec=vec,
                             metric_names=STEP_METRIC_NAMES,
                             bytes_per_step=1.0)
        return fused_segment_shard(p, advance, probe,
                                   segment_chunks(_MEGASTEP_K),
                                   probe_every)

    spec = P("z", "y", "x")
    sm = jax.shard_map(shard, mesh=mesh, in_specs=(spec, P()),
                       out_specs=(spec, P()), check_vma=False)
    vec = jax.ShapeDtypeStruct((2,), jnp.float32)
    return sm, (_f32(_EXCHANGE_GLOBAL), vec)


def _megastep_segment_hlo() -> HloSpec:
    fn, args = _megastep_segment_fn()
    n_probes = -(-_MEGASTEP_K // _MEGASTEP_PROBE_EVERY)
    # k x the per-step slab sweep's 6 collective-permutes + exactly one
    # all-reduce per probe row — the whole fused bill, nothing hidden
    return HloSpec(fn=fn, args=args,
                   allow=("collective_permute", "all_reduce"),
                   exact_counts={"collective_permute": 6 * _MEGASTEP_K,
                                 "all_reduce": n_probes})


def _megastep_segment_cost() -> CostModelSpec:
    """Exact-byte cross-check: the fused segment's exchanges move
    exactly k x the per-step analytic halo bytes (probe all-reduces
    are outside ``count_kinds``; their count is pinned above)."""
    from ..geometry import Dim3

    fn, args = _megastep_segment_fn()
    expected = _MEGASTEP_K * _sweep_bytes(_exchange_shard_shape(),
                                          _exchange_radius("r1"),
                                          Dim3(*_EXCHANGE_MESH), 4)
    return CostModelSpec(fn=fn, args=args,
                         expected_bytes_per_shard=expected,
                         count_kinds=("collective_permute",))


# ---------------------------------------------------------------------------
# performance-observatory attribution targets: model-vs-measured
# attribution (observatory/attribution.py) is a HOST wall clock around
# the dispatch — the dispatched program must be byte-identical to the
# uninstrumented one. These targets lower exactly what
# PerfAttributor.attributed() hands the dispatcher and pin it to the
# SAME exact collective counts, the SAME analytic byte bill, and the
# SAME dispatch-stable compile fingerprint as the bare megastep/PIC
# entries above — attribution adds zero collectives, zero wire bytes,
# zero retraces. tests/fixtures/lint/bad_attribution.py (a timer that
# sneaks a host callback into the step) is the negative control.


def _attributed(spec):
    """The bare spec with its fn routed through
    ``PerfAttributor.attributed`` — everything else (exact counts,
    byte expectations, allowed vocabulary) stays the BARE target's by
    construction, so the two registrations cannot drift apart: any
    future attribution scheme that edits the program fails the bare
    target's own pins under the attribution name."""
    import dataclasses

    from ..observatory.attribution import PerfAttributor

    return dataclasses.replace(spec,
                               fn=PerfAttributor.attributed(spec.fn))


def _attribution_segment_hlo() -> HloSpec:
    return _attributed(_megastep_segment_hlo())


def _attribution_segment_cost() -> CostModelSpec:
    return _attributed(_megastep_segment_cost())


def _attributed_segment_entry():
    from ..observatory.attribution import PerfAttributor

    fn, args = _megastep_segment_entry()
    return PerfAttributor.attributed(fn), args


def _attributed_pic_entry():
    from ..observatory.attribution import PerfAttributor

    fn, args = _pic_step_entry()
    return PerfAttributor.attributed(fn), args


def _attribution_pic_hlo() -> HloSpec:
    return _attributed(_pic_step_hlo())


# ---------------------------------------------------------------------------
# link-observatory targets: the modeled per-(src, dst) traffic matrix
# (observatory/linkmap.py) must sum EXACTLY to the HLO-extracted wire
# bytes for every registered exchange method — slab/packed at every
# plan depth, the all-gather control, particle migration, and the full
# fused PIC step (whose bill includes the halo-accumulate adjoint).
# Each builder pairs a collective spec already under the hlo/cost
# gates with the linkmap twin of its byte expectation, so the matrix
# the placement QAP consumes and the wire bill the HLO proves are one
# object. tests/fixtures/lint/bad_linkmap.py (a matrix that drops the
# corner bytes riding the fat axis slabs — the classic 6-neighbor-only
# bug) is the negative control.


def _linkmap_exchange_spec(radius_kind: str) -> LinkmapSpec:
    from ..geometry import Dim3
    from ..observatory.linkmap import sweep_traffic

    cs = _exchange_spec(radius_kind)
    traffic = sweep_traffic(_exchange_shard_shape(),
                            _exchange_radius(radius_kind),
                            Dim3(*_EXCHANGE_MESH), (4,))
    return LinkmapSpec(fn=cs.fn, args=cs.args, traffic=traffic)


def _linkmap_exchange_irr_spec(radius_kind: str) -> LinkmapSpec:
    from ..geometry import Dim3
    from ..observatory.linkmap import sweep_traffic

    cs = _exchange_irr_spec(radius_kind)
    traffic = sweep_traffic(_exchange_shard_shape(),
                            _exchange_radius(radius_kind),
                            Dim3(*_EXCHANGE_MESH), (4,),
                            layout="irredundant")
    return LinkmapSpec(fn=cs.fn, args=cs.args, traffic=traffic)


def _linkmap_packed_uneven_spec() -> LinkmapSpec:
    from ..geometry import Dim3, Radius
    from ..observatory.linkmap import sweep_traffic

    cs = _exchange_packed_uneven_spec()
    # capacity shard (10,10,10); f32 + bf16 pack in separate groups —
    # launches differ, payload does not (same convention as the cost
    # target)
    traffic = sweep_traffic((10, 10, 10), Radius.constant(1),
                            Dim3(2, 2, 2), (4, 2))
    return LinkmapSpec(fn=cs.fn, args=cs.args, traffic=traffic)


def _linkmap_plan_spec(method_name: str, s: int) -> LinkmapSpec:
    from ..geometry import Dim3, Radius
    from ..observatory.linkmap import method_traffic

    cs = _plan_exchange_spec(method_name, s)
    traffic = method_traffic(
        method_name, (_PLAN_INTERIOR,) * 3, Radius.constant(1),
        Dim3(*_EXCHANGE_MESH), (4,), steps=s)
    return LinkmapSpec(fn=cs.fn, args=cs.args, traffic=traffic)


def _linkmap_temporal_asym_spec(depths=None) -> LinkmapSpec:
    """The asymmetric temporal group's traffic matrix: the group
    matrix carries axis ``a``'s deep slab ``max(s) / s_a`` times (the
    mid-group refreshes), and its per-shard row sum must equal the
    group program's HLO wire bytes exactly."""
    from ..geometry import Dim3, Radius, normalize_depths
    from ..observatory.linkmap import method_traffic

    d = depths if depths is not None else _ASYM_DEPTHS[0]
    cs = _temporal_group_asym_spec(d)
    traffic = method_traffic("PpermuteSlab", (8, 8, 8),
                             Radius.constant(1), Dim3(*_EXCHANGE_MESH),
                             (4,), steps=normalize_depths(d))
    return LinkmapSpec(fn=cs.fn, args=cs.args, traffic=traffic)


@functools.lru_cache(maxsize=None)
def _hier_dcn_domain():
    """The hierarchical partition planner's actual deployment on a
    DCN-blocked fake mesh: 2 fake slices of 4 devices each, mesh shape
    and slice axis chosen by ``_plan_dcn_partition`` (per-link priced),
    placement by the ``auto`` default."""
    import jax
    import numpy as np

    from ..distributed import DistributedDomain

    devs = jax.devices()[:8]
    dd = DistributedDomain(32, 16, 16, devices=devs)
    dd.set_radius(1)
    dd.add_data("q", np.float32)
    dd.set_dcn_axis(groups=[devs[:4], devs[4:]])
    dd.realize()
    return dd


def _linkmap_hier_dcn_spec() -> LinkmapSpec:
    """The hierarchical partition's per-link byte split, HLO-exact on
    the DCN-blocked mesh — plus the deployed placement payload: the
    assignment realize() shipped must cost no more than trivial device
    order under the NodeAware objective on the two-tier fabric."""
    from ..observatory.linkmap import sweep_traffic
    from ..parallel.mesh import mesh_dim

    dd = _hier_dcn_domain()
    local = dd.local_size
    lo, hi = dd.alloc_radius.pad_lo(), dd.alloc_radius.pad_hi()
    counts = mesh_dim(dd.mesh)
    traffic = sweep_traffic((local.z + lo.z + hi.z,
                             local.y + lo.y + hi.y,
                             local.x + lo.x + hi.x), dd.radius,
                            counts, (4,), alloc_radius=dd.alloc_radius)
    placement = {
        "counts": tuple(counts),
        "grid": tuple(dd.size),
        "assignment": list(dd.placement.assignment),
        "radius": dd.radius,
        "dcn_axis": dd.dcn_axis,
        "n_slices": dd.n_slices,
    }
    return LinkmapSpec(fn=dd._exchange_fn, args=(dict(dd.curr),),
                       traffic=traffic, placement=placement)


def _linkmap_allgather_spec() -> LinkmapSpec:
    from ..geometry import Dim3, Radius
    from ..observatory.linkmap import allgather_traffic

    cs = _exchange_allgather_spec()
    traffic = allgather_traffic((8, 8, 8), Radius.constant(1),
                                Dim3(2, 2, 2), (4,))
    return LinkmapSpec(fn=cs.fn, args=cs.args, traffic=traffic)


def _linkmap_migrate_spec() -> LinkmapSpec:
    from ..geometry import Dim3
    from ..observatory.linkmap import migration_traffic

    cs = _migrate_spec()
    traffic = migration_traffic(Dim3(*_MIGRATE_MESH),
                                len(_MIGRATE_FIELDS), _MIGRATE_BUDGET,
                                4)
    return LinkmapSpec(fn=cs.fn, args=cs.args, traffic=traffic,
                       count_kinds=("collective_permute",))


def _linkmap_pic_spec() -> LinkmapSpec:
    from ..geometry import Dim3, Radius
    from ..models.pic import PARTICLE_FIELDS, RADIUS
    from ..observatory.linkmap import pic_traffic

    eng = _pic_engine()
    fn, args = _pic_step_entry()
    local = eng.dd.local_size
    traffic = pic_traffic((local.z, local.y, local.x),
                          Radius.constant(RADIUS),
                          Dim3(*_EXCHANGE_MESH), 4,
                          len(PARTICLE_FIELDS), _PIC_BUDGET)
    return LinkmapSpec(fn=fn, args=args, traffic=traffic,
                       count_kinds=("collective_permute",))


# ---------------------------------------------------------------------------
# particle-migration / PIC targets: the DYNAMIC communication pattern.
# The fixed-capacity migration ring must lower to collective-permute
# only with its static budget x record-rows wire bill matching the
# analytic model EXACTLY (payload occupancy is runtime-dynamic; wire
# bytes are not — that is the whole design), and the full fused PIC
# step (deposit + reverse accumulate + exchange + gather + push +
# migrate) must bill exactly 2 ppermutes per active axis per engine
# and nothing else. tests/fixtures/lint/bad_migration.py (a migration
# that all-gathers every shard's outbox) is the negative control.

_MIGRATE_MESH = (2, 2, 2)
_MIGRATE_FIELDS = ("q", "x", "y")
_MIGRATE_CAPACITY = 16
_MIGRATE_BUDGET = 4

_PIC_N = 64
_PIC_CAPACITY = 32
_PIC_BUDGET = 8


def _migrate_spec() -> CollectiveSpec:
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import mesh_dim
    from ..parallel.migrate import migrate_shard

    mesh = _mesh(_MIGRATE_MESH)
    counts = mesh_dim(mesh)
    cap = _MIGRATE_CAPACITY

    def shard(fields, valid, ox, oy, oz):
        f, v, ovf = migrate_shard(fields, valid, (ox, oy, oz), counts,
                                  _MIGRATE_BUDGET)
        return f, v, ovf.reshape(1)

    spec = P(("z", "y", "x"))
    fspec = {q: spec for q in _MIGRATE_FIELDS}
    sm = jax.shard_map(shard, mesh=mesh,
                       in_specs=(fspec, spec, spec, spec, spec),
                       out_specs=(fspec, spec, spec), check_vma=False)
    n = 8 * cap
    fields = {q: _f32((n,)) for q in _MIGRATE_FIELDS}
    valid = jax.ShapeDtypeStruct((n,), jnp.bool_)
    off = jax.ShapeDtypeStruct((n,), jnp.int32)
    return CollectiveSpec(fn=sm, args=(fields, valid, off, off, off),
                          axis_sizes=dict(mesh.shape),
                          expect_ppermute=True)


def _migrate_hlo() -> HloSpec:
    cs = _migrate_spec()
    # 2 directions x 3 active axes, one packed record buffer each —
    # the dynamic exchange's whole collective bill
    return HloSpec(fn=cs.fn, args=cs.args, allow=("collective_permute",),
                   exact_counts={"collective_permute": 6})


def _migrate_cost() -> CostModelSpec:
    from ..geometry import Dim3
    from .costmodel import migration_wire_bytes_per_shard

    cs = _migrate_spec()
    expected = migration_wire_bytes_per_shard(
        len(_MIGRATE_FIELDS), _MIGRATE_BUDGET, Dim3(*_MIGRATE_MESH), 4)
    return CostModelSpec(fn=cs.fn, args=cs.args,
                         expected_bytes_per_shard=expected,
                         count_kinds=("collective_permute",))


@functools.lru_cache(maxsize=None)
def _pic_engine():
    import numpy as np

    from ..models.pic import Pic

    return Pic(16, 16, 16, _PIC_N, mesh_shape=_EXCHANGE_MESH,
               dtype=np.float32, capacity=_PIC_CAPACITY,
               budget=_PIC_BUDGET)


@functools.lru_cache(maxsize=None)
def _pic_step_entry():
    eng = _pic_engine()
    return eng._step, (dict(eng.state),)


def _pic_step_bytes() -> int:
    """The fused PIC step's exact wire bill: reverse accumulate +
    forward exchange (each one radius-2 sweep on the padded shard) +
    the migration ring."""
    from ..geometry import Dim3, Radius
    from ..models.pic import PARTICLE_FIELDS, RADIUS
    from .costmodel import migration_wire_bytes_per_shard

    eng = _pic_engine()
    local = eng.dd.local_size
    pad = 2 * RADIUS
    padded = (local.z + pad, local.y + pad, local.x + pad)
    sweep = _sweep_bytes(padded, Radius.constant(RADIUS),
                         Dim3(*_EXCHANGE_MESH), 4)
    return 2 * sweep + migration_wire_bytes_per_shard(
        len(PARTICLE_FIELDS), _PIC_BUDGET, Dim3(*_EXCHANGE_MESH), 4)


def _pic_step_hlo() -> HloSpec:
    fn, args = _pic_step_entry()
    # 6 ppermutes each for accumulate, exchange, and migration — the
    # dynamic pattern pays the same ring discipline as the static one
    return HloSpec(fn=fn, args=args, allow=("collective_permute",),
                   exact_counts={"collective_permute": 18})


def _pic_step_cost() -> CostModelSpec:
    fn, args = _pic_step_entry()
    return CostModelSpec(fn=fn, args=args,
                         expected_bytes_per_shard=_pic_step_bytes(),
                         count_kinds=("collective_permute",))


def _pic_probe_hlo() -> HloSpec:
    """The PIC sentinel probe: rho + every particle SoA lane + the
    IN-GRAPH migration-overflow column, still exactly ONE small
    all-reduce — the overflow counter rides the existing reduction."""
    eng = _pic_engine()
    return HloSpec(fn=eng._probe_fn, args=(dict(eng.state),),
                   allow=("all_reduce",),
                   exact_counts={"all_reduce": 1})


# ---------------------------------------------------------------------------
# PIC megastep targets: the segment compiler's carry-contract proof.
# A check_every=k fused PIC segment must lower to exactly k x the
# step's 18 collective-permutes plus ONE probe all-reduce per declared
# trace row and NOTHING else, with the exchange+migration bytes
# exactly k x the per-step analytic model AND the probe rows carrying
# the full contract column set (rho + 7 particle lanes + the overflow
# column — tests/fixtures/lint/bad_segment_carry.py, a contract that
# drops the overflow column, is the negative control).

_PIC_SEG_ROWS = -(-_MEGASTEP_K // _MEGASTEP_PROBE_EVERY)
#: probe-vector columns of the shipped PIC carry contract: rho + the
#: 7 particle SoA lanes + the migration-overflow extra column
_PIC_SEG_COLS = 9


@functools.lru_cache(maxsize=None)
def _pic_segment_entry():
    from ..parallel.megastep import metric_base_vec

    eng = _pic_engine()
    seg = eng.make_segment(_MEGASTEP_K,
                           probe_every=_MEGASTEP_PROBE_EVERY)
    return seg.fn, (dict(eng.state),
                    metric_base_vec(None, 0, mesh=eng.dd.mesh))


def _pic_segment_hlo() -> HloSpec:
    fn, args = _pic_segment_entry()
    return HloSpec(fn=fn, args=args,
                   allow=("collective_permute", "all_reduce"),
                   exact_counts={
                       "collective_permute": 18 * _MEGASTEP_K,
                       "all_reduce": _PIC_SEG_ROWS})


def _pic_segment_cost() -> CostModelSpec:
    fn, args = _pic_segment_entry()
    return CostModelSpec(fn=fn, args=args,
                         expected_bytes_per_shard=(
                             _MEGASTEP_K * _pic_step_bytes()),
                         count_kinds=("collective_permute",))


def _pic_segment_probe_cost() -> CostModelSpec:
    """The probe side of the carry contract, byte-exact: every trace
    row's single all-reduce moves the full (2, 9) f32 column set —
    rho + 7 particle lanes + the overflow column. A contract that
    drops a column (the bad_segment_carry fixture) shrinks the
    all-reduce operand and fails this pin."""
    fn, args = _pic_segment_entry()
    return CostModelSpec(fn=fn, args=args,
                         expected_bytes_per_shard=(
                             _PIC_SEG_ROWS * 2 * _PIC_SEG_COLS * 4),
                         count_kinds=("all_reduce",))


# ---------------------------------------------------------------------------
# Astaroth temporal megastep targets: the fused segment over
# lcm(3, s)-period temporal groups must pay exactly the grouped deep
# exchanges (w riding only where a group starts at alpha != 0) — the
# segment's wire bill is k x the amortized deep-exchange model,
# HLO-exact, with one probe all-reduce per declared trace row.

_AST_SEG_S = 2
_AST_SEG_K = 4


@functools.lru_cache(maxsize=None)
def _astaroth_temporal_engine():
    import jax
    import numpy as np

    from ..models.astaroth import Astaroth
    from ..parallel.methods import Method

    a = Astaroth(8, 8, 16, mesh_shape=(1, 1, 2),
                 devices=jax.devices()[:2], dtype=np.float32,
                 kernel="xla", methods=Method.PpermuteSlab,
                 exchange_every=_AST_SEG_S)
    a._ensure_w()
    return a


@functools.lru_cache(maxsize=None)
def _astaroth_segment_entry():
    from ..parallel.megastep import metric_base_vec

    a = _astaroth_temporal_engine()
    seg = a.make_segment(_AST_SEG_K,
                         probe_every=_MEGASTEP_PROBE_EVERY)
    return seg.fn, ((dict(a.dd.curr), dict(a._w)),
                    metric_base_vec(None, 0, mesh=a.dd.mesh))


def _astaroth_segment_counts():
    """(ppermutes, probe rows, expected bytes/shard) of the registered
    temporal segment: per lcm(3, s)-period chunk the groups start at
    RK substeps (g*s) % 3 — a group starting at alpha != 0 ships the
    8 w accumulators in the SAME deep exchange (2x quantities, same
    launches per quantity)."""
    import math

    from ..models.astaroth import FIELDS, RK3_ALPHA
    from ..parallel.mesh import mesh_dim
    from .costmodel import deep_exchange_bytes_per_shard

    a = _astaroth_temporal_engine()
    s = _AST_SEG_S
    period = math.lcm(3, s)
    counts = mesh_dim(a.dd.mesh)
    local = a.dd.local_size
    # one f32 quantity's depth-s deep exchange, per shard
    deep1 = deep_exchange_bytes_per_shard(
        (local.z, local.y, local.x), a.dd.radius, counts, 4, s)
    # ppermutes per quantity per deep exchange: 2 per active mesh axis
    active = sum(1 for ax in range(3) if counts[ax] > 1)
    starts = [(g * s) % 3 for g in range(period // s)]
    qs = [len(FIELDS) * (2 if RK3_ALPHA[st] != 0.0 else 1)
          for st in starts]
    n_chunks = _AST_SEG_K // (period // 3)
    cp = n_chunks * sum(qs) * 2 * active
    from ..parallel.megastep import probe_rel_steps
    rows = len(probe_rel_steps([period // 3] * n_chunks,
                               _MEGASTEP_PROBE_EVERY))
    return cp, rows, n_chunks * sum(qs) * deep1


def _astaroth_segment_hlo() -> HloSpec:
    fn, args = _astaroth_segment_entry()
    cp, rows, _ = _astaroth_segment_counts()
    return HloSpec(fn=fn, args=args,
                   allow=("collective_permute", "all_reduce"),
                   exact_counts={"collective_permute": cp,
                                 "all_reduce": rows})


def _astaroth_segment_cost() -> CostModelSpec:
    fn, args = _astaroth_segment_entry()
    _, _, expected = _astaroth_segment_counts()
    return CostModelSpec(fn=fn, args=args,
                         expected_bytes_per_shard=expected,
                         count_kinds=("collective_permute",))


def _central_diff_spec(axis: int) -> StencilOpSpec:
    from ..geometry import Dim3, Radius
    from ..ops.stencil_kernels import central_diff

    radius = Radius.constant(1)
    interior = Dim3(8, 8, 8)
    return StencilOpSpec(
        fn=lambda p: central_diff(p, axis, radius, interior),
        args=(_f32((10, 10, 10)),), radius=radius, interior=interior)


# ---------------------------------------------------------------------------
# dataflow targets: donation / transfer / recompile for every compiled
# entry point the drivers dispatch — the model step loops, the
# temporal path, make_exchange, the fused megastep segments, and the
# ensemble step/segment/lane programs. Each entry builder returns
# (jitted_fn, args) exactly the way the production caller invokes it,
# so the donation checker audits the SHIPPED jit (its declared
# donate_argnums), the transfer checker walks the same traced program,
# and the recompile checker fingerprints the same abstract signature.
# Builders are memoized: the three checkers audit ONE engine instead
# of realizing the same domain per target (nothing here dispatches —
# lower/trace/eval_shape only — so sharing the jitted fn is safe).


@functools.lru_cache(maxsize=None)
def _jacobi_step_entry(exchange_every: int = 1):
    import jax.numpy as jnp
    import numpy as np

    from ..models.jacobi import Jacobi3D

    j = Jacobi3D(16, 16, 16, mesh_shape=_EXCHANGE_MESH,
                 dtype=np.float32, kernel="xla",
                 exchange_every=exchange_every)
    return j._step_n, (j.dd.curr["temp"], jnp.asarray(2, jnp.int32))


@functools.lru_cache(maxsize=None)
def _astaroth_iter_entry():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..models.astaroth import Astaroth
    from ..parallel.methods import Method

    a = Astaroth(8, 8, 8, mesh_shape=(1, 1, 2),
                 devices=jax.devices()[:2], dtype=np.float32,
                 kernel="xla", methods=Method.PpermuteSlab)
    a._ensure_w()
    return a._iter_n, (a.dd.curr, a._w, jnp.asarray(1, jnp.int32))


@functools.lru_cache(maxsize=None)
def _make_exchange_entry(method_name: str):
    from ..geometry import Radius
    from ..parallel.exchange import make_exchange
    from ..parallel.methods import Method

    mesh = _mesh(_EXCHANGE_MESH)
    ex = make_exchange(mesh, Radius.constant(1), Method[method_name])
    return ex, ({"q": _f32((20, 20, 20))},)


@functools.lru_cache(maxsize=None)
def _make_exchange_wire_entry(method_name: str, fmt: str = "bf16"):
    """The certified low-precision wire path: building this entry IS
    the gate — make_exchange refuses (PrecisionGateError) unless the
    precision checker certifies the narrowing program safe."""
    from ..geometry import Radius
    from ..parallel.exchange import make_exchange
    from ..parallel.methods import Method

    mesh = _mesh(_EXCHANGE_MESH)
    fs = {"q": _f32((20, 20, 20))}
    ex = make_exchange(mesh, Radius.constant(1), Method[method_name],
                       wire_format=fmt, fields_spec=fs)
    return ex, (dict(fs),)


def _wire_exchange_hlo(method_name: str) -> HloSpec:
    fn, args = _make_exchange_wire_entry(method_name)
    return HloSpec(fn=fn, args=args, allow=("collective_permute",))


def _wire_exchange_cost(method_name: str) -> CostModelSpec:
    from ..geometry import Dim3, Radius
    from ..parallel.exchange import exchanged_bytes_per_sweep

    fn, args = _make_exchange_wire_entry(method_name)
    expected = sum(exchanged_bytes_per_sweep(
        (10, 10, 10), Radius.constant(1), Dim3(*_EXCHANGE_MESH), 4,
        wire_format="bf16").values())
    # the whole point of the format, pinned: bf16 wire bytes are
    # EXACTLY half the f32 bill (the HLO cross-check then proves the
    # lowered program pays this figure)
    full = _sweep_bytes((10, 10, 10), Radius.constant(1),
                        Dim3(*_EXCHANGE_MESH), 4)
    assert expected * 2 == full
    return CostModelSpec(fn=fn, args=args,
                         expected_bytes_per_shard=expected)


@functools.lru_cache(maxsize=None)
def _make_exchange_layout_entry(method_name: str):
    """The jitted orchestrator under the irredundant wire layout —
    the exact engine ``DistributedDomain.realize`` deploys when
    ``wire_layout="irredundant"`` is set or a tuned plan carries it."""
    from ..geometry import Radius
    from ..parallel.exchange import make_exchange
    from ..parallel.methods import Method

    mesh = _mesh(_EXCHANGE_MESH)
    ex = make_exchange(mesh, Radius.constant(1), Method[method_name],
                       wire_layout="irredundant")
    return ex, ({"q": _f32((20, 20, 20))},)


@functools.lru_cache(maxsize=None)
def _make_exchange_fp8_entry(method_name: str = "PpermuteSlab",
                             layout: str = "slab"):
    """The certified fp8 (e4m3) wire path, optionally composed with
    the irredundant layout: building this entry IS the gate — exactly
    as for bf16, make_exchange refuses unless the precision checker
    certifies the narrowing safe."""
    from ..geometry import Radius
    from ..parallel.exchange import make_exchange
    from ..parallel.methods import Method

    mesh = _mesh(_EXCHANGE_MESH)
    fs = {"q": _f32((20, 20, 20))}
    ex = make_exchange(mesh, Radius.constant(1), Method[method_name],
                       wire_format="e4m3", fields_spec=fs,
                       wire_layout=layout)
    return ex, (dict(fs),)


def _layout_exchange_hlo(method_name: str) -> HloSpec:
    fn, args = _make_exchange_layout_entry(method_name)
    return HloSpec(fn=fn, args=args, allow=("collective_permute",))


def _layout_exchange_cost(method_name: str) -> CostModelSpec:
    from ..geometry import Dim3, Radius

    fn, args = _make_exchange_layout_entry(method_name)
    counts = Dim3(*_EXCHANGE_MESH)
    expected = _irr_bytes((10, 10, 10), Radius.constant(1), counts, 4)
    assert expected < _sweep_bytes((10, 10, 10), Radius.constant(1),
                                   counts, 4)
    return CostModelSpec(fn=fn, args=args,
                         expected_bytes_per_shard=expected)


def _fp8_exchange_hlo(method_name: str = "PpermuteSlab",
                      layout: str = "slab") -> HloSpec:
    fn, args = _make_exchange_fp8_entry(method_name, layout)
    return HloSpec(fn=fn, args=args, allow=("collective_permute",))


def _fp8_exchange_cost(method_name: str = "PpermuteSlab",
                       layout: str = "slab") -> CostModelSpec:
    from ..geometry import Dim3, Radius
    from .costmodel import sweep_wire_bytes

    fn, args = _make_exchange_fp8_entry(method_name, layout)
    counts = Dim3(*_EXCHANGE_MESH)
    r = Radius.constant(1)
    expected = sum(sweep_wire_bytes(
        (10, 10, 10), r, counts, 4, wire_format="e4m3",
        layout=layout).values())
    # the fp8 headline, pinned: wire bytes exactly ONE QUARTER of the
    # f32 bill under the same layout (the HLO cross-check then proves
    # the lowered program pays this figure)
    full = sum(sweep_wire_bytes((10, 10, 10), r, counts, 4,
                                layout=layout).values())
    assert expected * 4 == full
    return CostModelSpec(fn=fn, args=args,
                         expected_bytes_per_shard=expected)


@functools.lru_cache(maxsize=None)
def _megastep_segment_entry():
    import numpy as np

    from ..models.jacobi import Jacobi3D
    from ..parallel.megastep import metric_base_vec

    j = Jacobi3D(16, 16, 16, mesh_shape=_EXCHANGE_MESH,
                 dtype=np.float32, kernel="xla")
    seg = j.make_segment(_MEGASTEP_K, probe_every=_MEGASTEP_PROBE_EVERY)
    return seg.fn, (j.dd.curr["temp"],
                    metric_base_vec(None, 0, mesh=j.dd.mesh))


@functools.lru_cache(maxsize=None)
def _domain_segment_entry():
    import numpy as np

    from ..distributed import DistributedDomain
    from ..geometry import Radius
    from ..parallel.exchange import exchange_shard
    from ..parallel.megastep import metric_base_vec
    from ..parallel.mesh import mesh_dim

    dd = DistributedDomain(16, 16, 16)
    dd.set_mesh_shape(_EXCHANGE_MESH)
    dd.set_radius(1)
    dd.add_data("a", np.float32)
    dd.add_data("b", np.float32)
    dd.realize()
    counts = mesh_dim(dd.mesh)
    radius = Radius.constant(1)

    def shard_step(fields):
        return {q: exchange_shard(p, radius, counts)
                for q, p in fields.items()}

    seg = dd.make_segment(shard_step, check_every=2)
    return seg.fn, (dict(dd.curr),
                    metric_base_vec(None, 0, mesh=dd.mesh))


@functools.lru_cache(maxsize=None)
def _ensemble_engine():
    from ..serving.ensemble import EnsembleJacobi

    return EnsembleJacobi(_ENSEMBLE_N, 24, 24, 24,
                          mesh_shape=_EXCHANGE_MESH)


@functools.lru_cache(maxsize=None)
def _ensemble_step_entry():
    import jax.numpy as jnp

    eng = _ensemble_engine()
    hot, cold = eng._param_args()
    return eng._step_n, (eng.state["temp"], hot, cold,
                         jnp.asarray(1, jnp.int32))


@functools.lru_cache(maxsize=None)
def _ensemble_segment_entry():
    eng = _ensemble_engine()
    fn = eng._segments.get((2, 1))
    if fn is None:
        fn = eng._segment_fn(2, 1)
    hot, cold = eng._param_args()
    return fn, (eng.state["temp"], hot, cold)


def _ensemble_set_lane_entry():
    import jax.numpy as jnp

    eng = _ensemble_engine()
    lane = {q: eng.state[q][0] for q in eng.state}
    return eng._set_lane, (dict(eng.state), lane, jnp.int32(0))


@functools.lru_cache(maxsize=None)
def _fleet_bucket_requests():
    """A padded admission (user grid strictly inside the bucket) and
    the native bucket-shape request — the pair the fleet bucketing
    targets compare."""
    from ..serving.queue import CampaignRequest
    from ..serving.slo import GridBucketer

    bucketer = GridBucketer(((24, 24, 24),))
    padded, was_padded = bucketer.apply(CampaignRequest(
        tenant="lint", campaign="pad", grid=(18, 21, 13),
        mesh_shape=_EXCHANGE_MESH))
    native = CampaignRequest(tenant="lint", campaign="native",
                             grid=(24, 24, 24),
                             mesh_shape=_EXCHANGE_MESH)
    return padded, native, was_padded


@functools.lru_cache(maxsize=None)
def _fleet_bucket_entry():
    """The fleet admission path's compiled step: bucketing replaces
    the user grid with its bucket BEFORE fingerprinting, so a padded
    request must share the native bucket request's fingerprint (ONE
    engine-cache slot — the bounded-cache contract). Raises when
    bucketing leaks the pre-pad grid into the admission key; returns
    the bucket-shaped ensemble step entry the padded request reuses."""
    from ..serving.queue import request_fingerprint

    padded, native, was_padded = _fleet_bucket_requests()
    if not was_padded or tuple(padded.grid) != (24, 24, 24):
        raise AssertionError(
            f"grid bucketing failed: (18, 21, 13) admitted at "
            f"{tuple(padded.grid)}, want the (24, 24, 24) bucket")
    fp_pad = request_fingerprint(padded)
    fp_nat = request_fingerprint(native)
    if fp_pad != fp_nat:
        raise AssertionError(
            f"padded admission does not share the native bucket "
            f"fingerprint ({fp_pad} != {fp_nat}) — the pre-pad grid "
            f"leaked into the admission key, so the per-replica "
            f"engine cache is unbounded again")
    return _ensemble_step_entry()


def _fleet_bucket_step_spec() -> HloSpec:
    """Bucketed-admission HLO identity: the step an engine built from
    the PADDED request lowers to StableHLO text byte-identical to the
    native bucket-shape step (bucketing must not leak the pre-pad
    grid into the compiled program), with the same pinned collective
    contract as ``serving.ensemble.step``."""
    from .hlo import lowering_supported

    padded, _, _ = _fleet_bucket_requests()
    fn, args = _fleet_bucket_entry()
    if lowering_supported():
        import jax
        import jax.numpy as jnp

        from ..serving.ensemble import EnsembleJacobi
        eng_pad = EnsembleJacobi(_ENSEMBLE_N, *padded.grid,
                                 mesh_shape=_EXCHANGE_MESH)
        hot, cold = eng_pad._param_args()
        pad_args = (eng_pad.state["temp"], hot, cold,
                    jnp.asarray(1, jnp.int32))
        pad_text = jax.jit(eng_pad._step_n).lower(*pad_args).as_text()
        nat_text = jax.jit(fn).lower(*args).as_text()
        if pad_text != nat_text:
            raise AssertionError(
                "padded-bucket step does not lower to HLO identical "
                "to the native bucket-shape step — bucketed admission "
                "compiled a different program than the bucket it "
                "claims to reuse")
    return HloSpec(fn=fn, args=args, allow=("collective_permute",),
                   exact_counts={"collective_permute": 6})


def _donation_spec(entry, donate=(0,)):
    fn, args = entry()
    return DonationSpec(fn=fn, args=args, donate_argnums=tuple(donate))


def _transfer_spec(entry):
    fn, args = entry()
    return TransferSpec(fn=fn, args=args)


def _health_step_probe_transfer() -> TransferSpec:
    hs = _health_step_probe_spec()
    return TransferSpec(fn=hs.fn, args=hs.args)


def _recompile_spec(entry, carry=((0, None),)):
    fn, args = entry()
    return RecompileSpec(fn=fn, args=args, carry=tuple(carry))


def _dataflow_targets() -> List[Target]:
    """The donation/transfer/recompile registry block (one audit per
    production entry point per applicable checker)."""
    targets: List[Target] = []
    # donation: every declared donate_argnums buffer must alias
    donation = [
        ("models.jacobi.step_n[xla,donation]",
         _jacobi_step_entry, (0,)),
        ("models.jacobi.step_n[xla-temporal[s=2],donation]",
         lambda: _jacobi_step_entry(2), (0,)),
        ("models.astaroth.iter_n[donation]",
         _astaroth_iter_entry, (0, 1)),
        ("parallel.exchange.make_exchange[PpermuteSlab,donation]",
         lambda: _make_exchange_entry("PpermuteSlab"), (0,)),
        ("parallel.exchange.make_exchange[PpermutePacked,donation]",
         lambda: _make_exchange_entry("PpermutePacked"), (0,)),
        ("parallel.exchange.make_exchange[AllGather,donation]",
         lambda: _make_exchange_entry("AllGather"), (0,)),
        (f"parallel.megastep.segment[k={_MEGASTEP_K},donation]",
         _megastep_segment_entry, (0,)),
        ("distributed.make_segment[donation]",
         _domain_segment_entry, (0,)),
        (f"serving.ensemble.step[N={_ENSEMBLE_N},donation]",
         _ensemble_step_entry, (0,)),
        (f"serving.ensemble.segment[N={_ENSEMBLE_N},k=2,donation]",
         _ensemble_segment_entry, (0,)),
        (f"serving.ensemble.set_lane[N={_ENSEMBLE_N},donation]",
         _ensemble_set_lane_entry, (0,)),
        ("models.pic.step[donation]", _pic_step_entry, (0,)),
        (f"models.pic.segment[k={_MEGASTEP_K},donation]",
         _pic_segment_entry, (0,)),
    ]
    for name, entry, donate in donation:
        targets.append(DonationTarget(
            name, lambda e=entry, d=donate: _donation_spec(e, d)))
    # transfer: no host escape inside the compiled hot path
    transfer = [
        ("models.jacobi.step_n[xla,transfer]", _jacobi_step_entry),
        ("models.astaroth.iter_n[transfer]", _astaroth_iter_entry),
        ("parallel.exchange.make_exchange[PpermutePacked,transfer]",
         lambda: _make_exchange_entry("PpermutePacked")),
        (f"parallel.megastep.segment[k={_MEGASTEP_K},transfer]",
         _megastep_segment_entry),
        (f"serving.ensemble.step[N={_ENSEMBLE_N},transfer]",
         _ensemble_step_entry),
        (f"serving.ensemble.segment[N={_ENSEMBLE_N},k=2,transfer]",
         _ensemble_segment_entry),
        ("serving.fleet.admission[transfer]", _fleet_bucket_entry),
        ("models.pic.step[transfer]", _pic_step_entry),
    ]
    for name, entry in transfer:
        targets.append(TransferTarget(
            name, lambda e=entry: _transfer_spec(e)))
    targets.append(TransferTarget("resilience.health.step+probe[transfer]",
                                  _health_step_probe_transfer))
    # recompile: dispatch-stable abstract fingerprints; carry pairs
    # the donated state with the output subtree that feeds back
    recompile = [
        ("models.jacobi.step_n[xla,recompile]",
         _jacobi_step_entry, ((0, None),)),
        ("models.astaroth.iter_n[recompile]",
         _astaroth_iter_entry, ((0, (0,)), (1, (1,)))),
        ("parallel.exchange.make_exchange[PpermutePacked,recompile]",
         lambda: _make_exchange_entry("PpermutePacked"), ((0, None),)),
        (f"parallel.megastep.segment[k={_MEGASTEP_K},recompile]",
         _megastep_segment_entry, ((0, (0,)),)),
        (f"serving.ensemble.step[N={_ENSEMBLE_N},recompile]",
         _ensemble_step_entry, ((0, None),)),
        (f"serving.ensemble.segment[N={_ENSEMBLE_N},k=2,recompile]",
         _ensemble_segment_entry, ((0, (0,)),)),
        ("serving.fleet.admission[recompile]",
         _fleet_bucket_entry, ((0, None),)),
        ("models.pic.step[recompile]", _pic_step_entry, ((0, None),)),
    ]
    for name, entry, carry in recompile:
        targets.append(RecompileTarget(
            name, lambda e=entry, c=carry: _recompile_spec(e, c)))
    return targets


# ---------------------------------------------------------------------------
# VMEM targets: every shipped Pallas kernel's static memory/tiling
# audit. The overlap/RDMA builders are shared with the dma targets;
# the single-chip wrap/halo fast-path kernels (previously outside the
# registry) enter here.


def _vmem_from_kernel(build) -> VmemSpec:
    ks = build()
    return VmemSpec(fn=ks.fn, args=ks.args)


def _jacobi7_plane_vmem_spec(side: int = 8) -> VmemSpec:
    from ..geometry import Dim3, Radius
    from ..ops.pallas_stencil import jacobi7_pallas

    radius = Radius.constant(1)
    interior = Dim3(side, side, side)
    g = side + 2

    def fn(p):
        return jacobi7_pallas(p, radius, interior, interpret=False)

    return VmemSpec(fn=fn, args=(_f32((g, g, g)),))


def _jacobi_wrap_vmem_spec(steps: int, side: int = 16) -> VmemSpec:
    """The wrap kernels as the model runs them on a side^3 grid: a side
    that is no multiple of the sublane tile is stepped in the stored
    extent ``wrap_layout`` pads it to, wrapped at ``side``."""
    import jax.numpy as jnp

    from ..ops.pallas_stencil import (jacobi7_wrap_pallas,
                                      jacobi7_wrapn_pallas, wrap_layout)

    hot = (side // 4, side // 2, side // 2)
    cold = (3 * side // 4, side // 2, side // 2)
    r = side // 8
    stored_y, _n = wrap_layout(side, side, side, jnp.float32, steps)

    def fn(q):
        if steps == 1:
            return jacobi7_wrap_pallas(q, hot, cold, r, interpret=False,
                                       y_extent=side)
        return jacobi7_wrapn_pallas(q, hot, cold, r, steps=steps,
                                    interpret=False, y_extent=side)

    return VmemSpec(fn=fn, args=(_f32((side, stored_y, side)),))


def _mhd_wrap_vmem_spec(pair: bool, side: int = 16) -> VmemSpec:
    from ..models.astaroth import FIELDS, MhdParams
    from ..ops.pallas_mhd import (mhd_substep01_wrap_pallas,
                                  mhd_substep_wrap_pallas)

    prm = MhdParams()

    def fn(*fs):
        fields = dict(zip(FIELDS, fs))
        if pair:
            f, w = mhd_substep01_wrap_pallas(fields, prm, prm.dt,
                                             interpret=False)
        else:
            f, w = mhd_substep_wrap_pallas(fields, None, 0, prm, prm.dt,
                                           interpret=False)
        return tuple(f[q] for q in FIELDS) + tuple(w[q] for q in FIELDS)

    return VmemSpec(fn=fn, args=tuple(_f32((side, side, side))
                                      for _ in FIELDS))


def _jacobi_halon_vmem_spec() -> VmemSpec:
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..geometry import Dim3
    from ..ops.pallas_halo import jacobi7_halon_pallas
    from ..parallel.exchange import exchange_interior_slabs, shard_origin

    mesh = _mesh((1, 2, 2))
    counts = Dim3(1, 2, 2)
    local = Dim3(16, 8, 8)
    bz, steps = 4, 2

    def shard(p):
        ox, oy, oz = shard_origin(local, Dim3(0, 0, 0))
        org = jnp.stack([oz, oy, ox]).astype(jnp.int32)
        slabs = exchange_interior_slabs(p, counts, rz=bz, ry=8,
                                        radius_rows=steps,
                                        y_z_extended=True)
        return jacobi7_halon_pallas(p, slabs, org, (16, 16, 16),
                                    (5, 8, 8), (11, 8, 8), 1,
                                    steps=steps, block_z=bz, block_y=8,
                                    interpret=False)

    spec = P("z", "y", "x")
    sm = jax.shard_map(shard, mesh=mesh, in_specs=spec, out_specs=spec,
                       check_vma=False)
    return VmemSpec(fn=sm, args=(_f32((16, 16, 16)),))


def _mhd_halo_vmem_spec(pair: bool) -> VmemSpec:
    import jax
    from jax.sharding import PartitionSpec as P

    from ..geometry import Dim3
    from ..models.astaroth import FIELDS, MhdParams
    from ..ops.pallas_halo import (mhd_halo_blocks,
                                   mhd_substep01_halo_pallas,
                                   mhd_substep_halo_pallas)
    from ..parallel.exchange import exchange_interior_slabs

    mesh = _mesh((1, 2, 2))
    counts = Dim3(1, 2, 2)
    prm = MhdParams()
    Z = Y = X = 8
    bz, _by = mhd_halo_blocks(Z, Y)
    rr = 6 if pair else 3

    def shard(fields):
        slabs = {q: exchange_interior_slabs(fields[q], counts, rz=bz,
                                            ry=8, radius_rows=rr,
                                            y_z_extended=True)
                 for q in FIELDS}
        if pair:
            f, w = mhd_substep01_halo_pallas(fields, slabs, prm, prm.dt,
                                             interpret=False)
        else:
            f, w = mhd_substep_halo_pallas(fields, None, slabs, 0, prm,
                                           prm.dt, interpret=False)
        return f, w

    spec = P("z", "y", "x")
    fspec = {q: spec for q in FIELDS}
    sm = jax.shard_map(shard, mesh=mesh, in_specs=(fspec,),
                       out_specs=(fspec, fspec), check_vma=False)
    fields = {q: _f32((2 * Z, 2 * Y, X)) for q in FIELDS}
    return VmemSpec(fn=sm, args=(fields,))


# ---------------------------------------------------------------------------
# prescriptive-tiling targets (checker 10): every shipped Pallas
# compute/exchange kernel audited at 256^3- and 512^3-PER-DEVICE
# shapes against the PHYSICAL VMEM budget — trace-only, so tier-1 on
# CPU proves the production-size story the 8^3 bench trajectory never
# could. Expectations are part of the registered contract:
#
# * "legal"      — the kernel's planner-derived default block shape
#                  passes the full VMEM audit at this size (the
#                  SNIPPETS.md 512^3 Mosaic failure, closed: the old
#                  (16, 128) Jacobi halo default is the bad_tiling
#                  fixture, proven flagged);
# * "infeasible" — the planner must REFUSE this size (build raises
#                  TilingInfeasibleError) or the audit must flag it:
#                  the full-lane (X-wide) MHD halo corner segments and
#                  the 7-plane laplace window genuinely cannot stage
#                  under 16 MiB at these shapes — re-tiling the lane
#                  dim is the named ROADMAP follow-up, and until then
#                  the gate proves the model paths decline loudly
#                  instead of dying in Mosaic's allocator.

from .tiling import TilingSpec, TilingTarget  # noqa: E402


def _tiling_from_vmem(build) -> TilingSpec:
    ks = build()
    return TilingSpec(fn=ks.fn, args=ks.args)


def _jacobi_halon_tiling_spec(side: int) -> TilingSpec:
    """The N=2 halo pair kernel called directly at a production
    per-device shape; slab shapes derive from the SAME planner fit the
    model deploys (fit_pair_halo_blocks raises when infeasible — the
    refused-at-build verdict)."""
    import jax
    import jax.numpy as jnp

    from ..ops.pallas_halo import (fit_pair_halo_blocks,
                                   jacobi7_halon_pallas)

    S = side
    bz, by = fit_pair_halo_blocks(S, S, S, 4, 2)
    slabs = {"zlo": _f32((bz, S, S)), "zhi": _f32((bz, S, S)),
             "ylo": _f32((S + 2 * bz, 8, S)),
             "yhi": _f32((S + 2 * bz, 8, S))}
    org = jax.ShapeDtypeStruct((3,), jnp.int32)

    def fn(interior, zlo, zhi, ylo, yhi, o):
        return jacobi7_halon_pallas(
            interior, {"zlo": zlo, "zhi": zhi, "ylo": ylo, "yhi": yhi},
            o, (S, S, S), (S // 4, S // 2, S // 2),
            (3 * S // 4, S // 2, S // 2), S // 8, steps=2,
            block_z=bz, block_y=by, interpret=False)

    return TilingSpec(fn=fn, args=(_f32((S, S, S)), slabs["zlo"],
                                   slabs["zhi"], slabs["ylo"],
                                   slabs["yhi"], org))


def _mhd_halo_tiling_spec(pair: bool, side: int,
                          y_local: bool = False) -> TilingSpec:
    """The MHD halo kernels at a production per-device shape, direct
    call. ``mhd_halo_blocks`` (the same fit the model and the slab
    exchange share) raises for the y-sharded window at these sizes —
    the full-lane corner segments bind — so that registered
    expectation is the refusal; the y-local window (z slabs only, the
    (1, 1, N) mesh) tiles at 256^3 and is refused at 512^3."""
    from ..models.astaroth import FIELDS, MhdParams
    from ..ops.pallas_halo import (mhd_halo_blocks,
                                   mhd_substep01_halo_pallas,
                                   mhd_substep_halo_pallas)

    S = side
    bz, _by = mhd_halo_blocks(S, S, 8, 32, 8, X=S, itemsize=4,
                              y_local=y_local)
    prm = MhdParams()
    fields = {q: _f32((S, S, S)) for q in FIELDS}
    slabs = {q: {"zlo": _f32((bz, S, S)), "zhi": _f32((bz, S, S))}
             for q in FIELDS}
    if not y_local:
        for q in FIELDS:
            slabs[q].update(ylo=_f32((S + 2 * bz, 8, S)),
                            yhi=_f32((S + 2 * bz, 8, S)))

    def fn(fields, slabs):
        if pair:
            return mhd_substep01_halo_pallas(fields, slabs, prm, prm.dt,
                                             interpret=False)
        return mhd_substep_halo_pallas(fields, None, slabs, 0, prm,
                                       prm.dt, interpret=False)

    return TilingSpec(fn=fn, args=(fields, slabs))


def _tiling_targets() -> List[Target]:
    targets: List[Target] = []

    def vmem_backed(prefix: str, build_for_side, sides=_TILING_SIDES):
        for side in sides:
            targets.append(TilingTarget(
                f"analysis.tiling.{prefix}[{side}]",
                lambda b=build_for_side, s=side:
                    _tiling_from_vmem(lambda: b(s)),
                expect=_TILING_EXPECT[prefix][side]))

    vmem_backed("ops.pallas_stencil.jacobi7_pallas",
                _jacobi7_plane_vmem_spec)
    vmem_backed("ops.pallas_stencil.jacobi7_wrap_pallas",
                lambda s: _jacobi_wrap_vmem_spec(1, s),
                _TILING_SIDES + _UNALIGNED_SIDES)
    vmem_backed("ops.pallas_stencil.jacobi7_wrapn_pallas[n=2]",
                lambda s: _jacobi_wrap_vmem_spec(2, s),
                _TILING_SIDES + _UNALIGNED_SIDES)
    vmem_backed("ops.pallas_stencil.jacobi7_wrapn_pallas[n=4]",
                lambda s: _jacobi_wrap_vmem_spec(4, s),
                _TILING_SIDES + _UNALIGNED_SIDES)
    vmem_backed("ops.pallas_halo.jacobi7_halo_pallas",
                _jacobi_halo_kernel_spec)
    vmem_backed("ops.pallas_mhd.mhd_substep_wrap_pallas",
                lambda s: _mhd_wrap_vmem_spec(False, s))
    vmem_backed("ops.pallas_mhd.mhd_substep01_wrap_pallas",
                lambda s: _mhd_wrap_vmem_spec(True, s))
    vmem_backed("ops.pallas_overlap.jacobi7_overlap_pallas",
                lambda s: _jacobi_overlap_spec(s))
    vmem_backed("ops.pallas_mhd_overlap.mhd_substep_overlap",
                lambda s: _mhd_overlap_spec(False, s))
    vmem_backed("parallel.pallas_exchange.exchange_shard_pallas",
                lambda s: _rdma_exchange_spec(s))
    for side in _TILING_SIDES:
        targets.append(TilingTarget(
            f"analysis.tiling.ops.pallas_halo."
            f"jacobi7_halon_pallas[n=2][{side}]",
            lambda s=side: _jacobi_halon_tiling_spec(s),
            expect=_TILING_EXPECT[
                "ops.pallas_halo.jacobi7_halon_pallas[n=2]"][side]))
        for pair in (False, True):
            for y_local in (False, True):
                key = ("ops.pallas_halo.mhd_substep"
                       + ("01" if pair else "") + "_halo_pallas"
                       + ("[y-local]" if y_local else ""))
                targets.append(TilingTarget(
                    f"analysis.tiling.{key}[{side}]",
                    lambda p=pair, s=side, y=y_local:
                        _mhd_halo_tiling_spec(p, s, y),
                    expect=_TILING_EXPECT[key][side]))
    return targets


_TILING_SIDES = (256, 512)
#: the reference's weak-scaling share, 750^3 a rank: no multiple of the
#: (8, 128) tile, so the Jacobi wrap kernels step it padded in y
_UNALIGNED_SIDES = (750,)

#: the registered per-size verdicts (see the block comment above);
#: probed on this image and pinned — a kernel whose story changes must
#: change this table in review
_TILING_EXPECT = {
    "ops.pallas_stencil.jacobi7_pallas": {256: "legal", 512: "legal"},
    "ops.pallas_stencil.jacobi7_wrap_pallas": {256: "legal",
                                               512: "legal",
                                               750: "legal"},
    "ops.pallas_stencil.jacobi7_wrapn_pallas[n=2]": {256: "legal",
                                                     512: "legal",
                                                     750: "legal"},
    "ops.pallas_stencil.jacobi7_wrapn_pallas[n=4]": {256: "legal",
                                                     512: "legal",
                                                     750: "legal"},
    "ops.pallas_halo.jacobi7_halo_pallas": {256: "legal", 512: "legal"},
    "ops.pallas_halo.jacobi7_halon_pallas[n=2]": {256: "legal",
                                                  512: "legal"},
    "ops.pallas_mhd.mhd_substep_wrap_pallas": {256: "legal",
                                               512: "infeasible"},
    "ops.pallas_mhd.mhd_substep01_wrap_pallas": {256: "legal",
                                                 512: "infeasible"},
    "ops.pallas_halo.mhd_substep_halo_pallas": {256: "infeasible",
                                                512: "infeasible"},
    "ops.pallas_halo.mhd_substep01_halo_pallas": {256: "infeasible",
                                                  512: "infeasible"},
    # the y-local window (y wrapped in-kernel, z slabs only): what the
    # halo path runs on a (1, 1, N) mesh
    "ops.pallas_halo.mhd_substep_halo_pallas[y-local]": {
        256: "legal", 512: "infeasible"},
    "ops.pallas_halo.mhd_substep01_halo_pallas[y-local]": {
        256: "legal", 512: "infeasible"},
    # the RDMA overlap kernel stages its slab exchange buffers as
    # block-independent VMEM scratch: ~42 MB at 512^3/device — no
    # block shape can fix that; lane re-tiling is the named follow-up
    "ops.pallas_overlap.jacobi7_overlap_pallas": {256: "legal",
                                                  512: "infeasible"},
    "ops.pallas_mhd_overlap.mhd_substep_overlap": {256: "infeasible",
                                                   512: "infeasible"},
    "parallel.pallas_exchange.exchange_shard_pallas": {256: "legal",
                                                       512: "legal"},
}


# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# precision targets: dtype-flow certification of every exchange/step/
# segment entry point (checker 13), plus the certified bf16-wire
# customer's HLO/byte cross-checks


def _precision_spec(entry, wire=None, counts=None):
    from ..geometry import Dim3

    fn, args = entry()
    return PrecisionSpec(fn=fn, args=args,
                         wire=dict(wire) if wire else None,
                         counts=Dim3(*(counts or _EXCHANGE_MESH)))


def _wire_format_targets() -> List[Target]:
    """The narrow-wire / packed-layout lowering contracts:
    collective-permute-only, with HLO-observed wire bytes exactly half
    (bf16) or exactly a quarter (fp8 e4m3) of the f32 bill, and the
    irredundant layout's bytes strictly below slab — separately and
    composed."""
    out: List[Target] = []
    for m in ("PpermuteSlab", "PpermutePacked"):
        out.append(HloTarget(
            f"parallel.exchange.make_exchange[{m},wire=bf16,hlo]",
            lambda m=m: _wire_exchange_hlo(m)))
        out.append(CostModelTarget(
            f"parallel.exchange.make_exchange[{m},wire=bf16,bytes]",
            lambda m=m: _wire_exchange_cost(m)))
        out.append(HloTarget(
            f"parallel.exchange.make_exchange"
            f"[{m},layout=irredundant,hlo]",
            lambda m=m: _layout_exchange_hlo(m)))
        out.append(CostModelTarget(
            f"parallel.exchange.make_exchange"
            f"[{m},layout=irredundant,bytes]",
            lambda m=m: _layout_exchange_cost(m)))
    out += [
        HloTarget(
            "parallel.exchange.make_exchange"
            "[PpermuteSlab,wire=e4m3,hlo]",
            lambda: _fp8_exchange_hlo("PpermuteSlab")),
        CostModelTarget(
            "parallel.exchange.make_exchange"
            "[PpermuteSlab,wire=e4m3,bytes]",
            lambda: _fp8_exchange_cost("PpermuteSlab")),
        HloTarget(
            "parallel.exchange.make_exchange"
            "[PpermuteSlab,wire=e4m3,layout=irredundant,hlo]",
            lambda: _fp8_exchange_hlo("PpermuteSlab", "irredundant")),
        CostModelTarget(
            "parallel.exchange.make_exchange"
            "[PpermuteSlab,wire=e4m3,layout=irredundant,bytes]",
            lambda: _fp8_exchange_cost("PpermuteSlab", "irredundant")),
    ]
    return out


def _precision_targets() -> List[Target]:
    w32 = {"x": "f32", "y": "f32", "z": "f32"}
    wbf = {"x": "bf16", "y": "bf16", "z": "bf16"}
    wf8 = {"x": "e4m3", "y": "e4m3", "z": "e4m3"}
    targets: List[Target] = []
    for m in ("PpermuteSlab", "PpermutePacked"):
        targets.append(PrecisionTarget(
            f"analysis.precision.parallel.exchange.make_exchange[{m}]",
            lambda m=m: _precision_spec(
                lambda: _make_exchange_entry(m), wire=w32)))
        targets.append(PrecisionTarget(
            f"analysis.precision.parallel.exchange."
            f"make_exchange[{m},wire=bf16]",
            lambda m=m: _precision_spec(
                lambda: _make_exchange_wire_entry(m), wire=wbf)))
        # the irredundant layout's pack/unpack must not perturb the
        # dtype flow: full-precision certificate on the packed boxes
        targets.append(PrecisionTarget(
            f"analysis.precision.parallel.exchange."
            f"make_exchange[{m},layout=irredundant]",
            lambda m=m: _precision_spec(
                lambda: _make_exchange_layout_entry(m), wire=w32)))
    # the fp8 wire certificates — slab and composed with the
    # irredundant layout (the certified-safe customer the quarter-
    # bytes HLO targets ride on)
    targets.append(PrecisionTarget(
        "analysis.precision.parallel.exchange."
        "make_exchange[PpermuteSlab,wire=e4m3]",
        lambda: _precision_spec(
            lambda: _make_exchange_fp8_entry("PpermuteSlab"),
            wire=wf8)))
    targets.append(PrecisionTarget(
        "analysis.precision.parallel.exchange."
        "make_exchange[PpermuteSlab,wire=e4m3,layout=irredundant]",
        lambda: _precision_spec(
            lambda: _make_exchange_fp8_entry(
                "PpermuteSlab", "irredundant"), wire=wf8)))
    targets += [
        PrecisionTarget("analysis.precision.models.jacobi.step_n",
                        lambda: _precision_spec(_jacobi_step_entry)),
        PrecisionTarget("analysis.precision.models.astaroth.iter_n",
                        lambda: _precision_spec(_astaroth_iter_entry,
                                                counts=(1, 1, 2))),
        PrecisionTarget("analysis.precision.models.astaroth.segment",
                        lambda: _precision_spec(
                            _astaroth_segment_entry, counts=(1, 1, 2))),
        PrecisionTarget("analysis.precision.parallel.megastep.segment",
                        lambda: _precision_spec(_megastep_segment_entry)),
        PrecisionTarget("analysis.precision.distributed.segment",
                        lambda: _precision_spec(_domain_segment_entry)),
        PrecisionTarget("analysis.precision.models.pic.step",
                        lambda: _precision_spec(_pic_step_entry)),
        PrecisionTarget("analysis.precision.models.pic.segment",
                        lambda: _precision_spec(_pic_segment_entry)),
        PrecisionTarget("analysis.precision.serving.ensemble.step_n",
                        lambda: _precision_spec(_ensemble_step_entry)),
        PrecisionTarget("analysis.precision.serving.ensemble.segment",
                        lambda: _precision_spec(_ensemble_segment_entry)),
    ]
    return targets


def default_targets() -> List[Target]:
    """Every shipped contract stencil-lint proves on each run."""
    targets: List[Target] = [
        StencilOpTarget("ops.stencil_kernels.jacobi7", _jacobi7_spec),
        StencilOpTarget("ops.stencil_kernels.laplacian27",
                        _laplacian27_spec),
        StencilOpTarget("models.astaroth.mhd_rates", _mhd_rates_spec),
    ]
    for axis, ax_name in enumerate("xyz"):
        targets.append(StencilOpTarget(
            f"ops.fd6.der1[{ax_name}]",
            lambda a=axis: _fd6_spec("der1", a)))
        targets.append(StencilOpTarget(
            f"ops.fd6.der2[{ax_name}]",
            lambda a=axis: _fd6_spec("der2", a)))
    for a, b in ((0, 1), (0, 2), (1, 2)):
        targets.append(StencilOpTarget(
            f"ops.fd6.der_cross[{'xyz'[a]}{'xyz'[b]}]",
            lambda p=(a, b): _fd6_spec("cross", p)))
    targets += [
        PallasKernelTarget("parallel.pallas_exchange.exchange_shard_pallas",
                           _rdma_exchange_spec),
        PallasKernelTarget("ops.pallas_overlap.jacobi7_overlap_pallas",
                           _jacobi_overlap_spec),
        PallasKernelTarget("ops.pallas_mhd_overlap.mhd_substep_overlap",
                           lambda: _mhd_overlap_spec(pair=False)),
        PallasKernelTarget("ops.pallas_mhd_overlap.mhd_substep_overlap[pair]",
                           lambda: _mhd_overlap_spec(pair=True)),
        PallasKernelTarget("ops.pallas_halo.jacobi7_halo_pallas",
                           _jacobi_halo_kernel_spec),
        CollectiveTarget("parallel.exchange.exchange_shard[r1]",
                         lambda: _exchange_spec("r1")),
        CollectiveTarget("parallel.exchange.exchange_shard[r3]",
                         lambda: _exchange_spec("r3")),
        CollectiveTarget("parallel.exchange.exchange_shard[asym]",
                         lambda: _exchange_spec("asym")),
        CollectiveTarget("parallel.exchange.exchange_shard_packed[uneven]",
                         _exchange_packed_uneven_spec),
        CollectiveTarget("parallel.exchange.exchange_shard_allgather",
                         _exchange_allgather_spec),
        CollectiveTarget("parallel.exchange.exchange_interior_slabs[yzext]",
                         lambda: _interior_slabs_spec(True)),
        CollectiveTarget("parallel.exchange.exchange_interior_slabs",
                         lambda: _interior_slabs_spec(False)),
        CollectiveTarget("parallel.exchange.make_exchange[jit,packed]",
                         _make_exchange_jit_spec),
        # temporal blocking: the fused s-step group and the partial-
        # depth tail exchange on a deep-carry allocation
        CollectiveTarget("parallel.temporal.temporal_shard_steps[s=2]",
                         lambda: _temporal_group_spec(2)),
        CollectiveTarget("parallel.temporal.temporal_shard_steps[s=4]",
                         lambda: _temporal_group_spec(4)),
        CollectiveTarget("parallel.temporal.temporal_shard_steps[s=1.1.2]",
                         _temporal_group_asym_spec),
        CollectiveTarget("parallel.exchange.exchange_shard[deep-tail]",
                         _deep_tail_exchange_spec),
    ]
    # HLO-lowering audit: one target per exchange METHOD (+ the jitted
    # orchestrator), collective-permute-only unless the method is the
    # deliberate all-gather control
    targets += [
        HloTarget("parallel.exchange.exchange_shard[r1,hlo]",
                  lambda: _hlo_from_collective(
                      lambda: _exchange_spec("r1"))),
        HloTarget("parallel.exchange.exchange_shard[asym,hlo]",
                  lambda: _hlo_from_collective(
                      lambda: _exchange_spec("asym"))),
        HloTarget("parallel.exchange.exchange_shard_packed[uneven,hlo]",
                  lambda: _hlo_from_collective(
                      _exchange_packed_uneven_spec)),
        HloTarget("parallel.exchange.exchange_shard_allgather[hlo]",
                  lambda: _hlo_from_collective(
                      _exchange_allgather_spec, allow=("all_gather",))),
        HloTarget("parallel.exchange.exchange_interior_slabs[yzext,hlo]",
                  lambda: _hlo_from_collective(
                      lambda: _interior_slabs_spec(True))),
        HloTarget("parallel.exchange.make_exchange[jit,packed,hlo]",
                  lambda: _hlo_from_collective(_make_exchange_jit_spec)),
        HloTarget("parallel.pallas_exchange.exchange_shard_pallas[hlo]",
                  _rdma_hlo_spec),
        HloTarget("parallel.temporal.temporal_shard_steps[s=2,hlo]",
                  lambda: _hlo_from_collective(
                      lambda: _temporal_group_spec(2))),
        HloTarget("parallel.temporal.temporal_shard_steps[s=1.1.2,hlo]",
                  lambda: _hlo_from_collective(
                      _temporal_group_asym_spec)),
        HloTarget("parallel.exchange.exchange_shard[deep-tail,hlo]",
                  lambda: _hlo_from_collective(_deep_tail_exchange_spec)),
    ]
    # analytic-vs-HLO byte cross-check for the same methods
    targets += [
        CostModelTarget("parallel.exchange.exchange_shard[r1,cost]",
                        lambda: _exchange_cost("r1")),
        CostModelTarget("parallel.exchange.exchange_shard[r3,cost]",
                        lambda: _exchange_cost("r3")),
        CostModelTarget("parallel.exchange.exchange_shard[asym,cost]",
                        lambda: _exchange_cost("asym")),
        CostModelTarget(
            "parallel.exchange.exchange_shard_packed[uneven,cost]",
            _packed_uneven_cost),
        CostModelTarget("parallel.exchange.exchange_shard_allgather[cost]",
                        _allgather_cost),
        CostModelTarget(
            "parallel.exchange.exchange_interior_slabs[yzext,cost]",
            lambda: _interior_slabs_cost(True)),
        CostModelTarget("parallel.exchange.exchange_interior_slabs[cost]",
                        lambda: _interior_slabs_cost(False)),
        CostModelTarget("parallel.exchange.make_exchange[jit,packed,cost]",
                        _make_exchange_jit_cost),
        # the amortized temporal-blocking byte model: one deep exchange
        # per fused group, priced on the deepened allocation — the HLO
        # must move exactly these bytes, at both registered depths
        CostModelTarget("parallel.temporal.temporal_shard_steps[s=2,cost]",
                        lambda: _temporal_group_cost(2)),
        CostModelTarget("parallel.temporal.temporal_shard_steps[s=4,cost]",
                        lambda: _temporal_group_cost(4)),
        CostModelTarget(
            "parallel.temporal.temporal_shard_steps[s=1.1.2,cost]",
            _temporal_group_asym_cost),
        CostModelTarget("parallel.exchange.exchange_shard[deep-tail,cost]",
                        _deep_tail_exchange_cost),
    ]
    # irredundant wire-layout twins of the registered exchange
    # configs: same ppermute ring, packed boxes — collective bijection,
    # ppermute-only lowering (count pinned UNCHANGED vs slab), and
    # HLO-exact bytes strictly below the slab bill (see the block
    # comment at the builders)
    targets += [
        CollectiveTarget("parallel.exchange.exchange_shard[r1,irr]",
                         lambda: _exchange_irr_spec("r1")),
        CollectiveTarget("parallel.exchange.exchange_shard[r3,irr]",
                         lambda: _exchange_irr_spec("r3")),
        CollectiveTarget("parallel.exchange.exchange_shard[asym,irr]",
                         lambda: _exchange_irr_spec("asym")),
        CollectiveTarget(
            "parallel.exchange.exchange_shard_packed[uneven,irr]",
            _exchange_packed_irr_uneven_spec),
        CollectiveTarget(
            "parallel.temporal.temporal_shard_steps[s=2,irr]",
            lambda: _temporal_irr_spec(2)),
        CollectiveTarget(
            "parallel.exchange.exchange_shard[deep-tail,irr]",
            _deep_tail_irr_spec),
        HloTarget("parallel.exchange.exchange_shard[r1,irr,hlo]",
                  lambda: _exchange_irr_hlo("r1")),
        HloTarget("parallel.exchange.exchange_shard[asym,irr,hlo]",
                  lambda: _exchange_irr_hlo("asym")),
        HloTarget(
            "parallel.exchange.exchange_shard_packed[uneven,irr,hlo]",
            lambda: _hlo_from_collective(
                _exchange_packed_irr_uneven_spec)),
        HloTarget("parallel.temporal.temporal_shard_steps[s=2,irr,hlo]",
                  lambda: _hlo_from_collective(
                      lambda: _temporal_irr_spec(2))),
        HloTarget("parallel.exchange.exchange_shard[deep-tail,irr,hlo]",
                  lambda: _hlo_from_collective(_deep_tail_irr_spec)),
        CostModelTarget("parallel.exchange.exchange_shard[r1,irr,cost]",
                        lambda: _exchange_irr_cost("r1")),
        CostModelTarget("parallel.exchange.exchange_shard[r3,irr,cost]",
                        lambda: _exchange_irr_cost("r3")),
        CostModelTarget(
            "parallel.exchange.exchange_shard[asym,irr,cost]",
            lambda: _exchange_irr_cost("asym")),
        CostModelTarget(
            "parallel.exchange.exchange_shard_packed[uneven,irr,cost]",
            _packed_irr_uneven_cost),
        CostModelTarget(
            "parallel.temporal.temporal_shard_steps[s=2,irr,cost]",
            lambda: _temporal_irr_cost(2)),
        CostModelTarget(
            "parallel.exchange.exchange_shard[deep-tail,irr,cost]",
            _deep_tail_irr_cost),
    ]
    # every exchange configuration the autotuner can emit (Method.Auto)
    targets += _plan_targets()
    # ensemble serving: the batched member axis rides existing
    # collectives (same op count, bytes exactly xN)
    targets += [
        CollectiveTarget("serving.ensemble.exchange[N=4]",
                         _ensemble_exchange_spec),
        HloTarget("serving.ensemble.exchange[N=4,hlo]",
                  lambda: _hlo_from_collective(_ensemble_exchange_spec)),
        CostModelTarget("serving.ensemble.exchange[N=4,cost]",
                        _ensemble_exchange_cost),
        HloTarget("serving.ensemble.step[N=4,hlo]",
                  _ensemble_step_spec),
        HloTarget("serving.ensemble.probe[N=4,hlo]",
                  _ensemble_probe_spec),
        HloTarget("serving.fleet.bucket_step[hlo]",
                  _fleet_bucket_step_spec),
    ]
    # the health sentinel's probe: exactly one small all-reduce, alone
    # and fused into the production step (see resilience/health.py)
    targets += [
        HloTarget("resilience.health.probe[hlo]", _health_probe_spec),
        HloTarget("resilience.health.step+probe[hlo]",
                  _health_step_probe_spec),
    ]
    # the telemetry step-metrics instrumentation: metric columns ride
    # the probe's one all-reduce — the instrumented production step
    # keeps the bare step's exact collective counts and exact exchange
    # bytes (see stencil_tpu/telemetry/probe.py)
    targets += [
        HloTarget("telemetry.probe+metrics[hlo]",
                  _telemetry_probe_spec),
        HloTarget("telemetry.step+probe+metrics[hlo]",
                  _telemetry_step_probe_spec),
        CostModelTarget("telemetry.step+probe+metrics[cost]",
                        _telemetry_step_probe_cost),
    ]
    # the megastep: a check_every=k fused segment is ONE program with
    # exactly k x the per-step collective_permutes + one all-reduce per
    # probe row, bytes exactly k x the per-step model
    targets += [
        HloTarget(f"parallel.megastep.segment[k={_MEGASTEP_K},hlo]",
                  _megastep_segment_hlo),
        CostModelTarget(
            f"parallel.megastep.segment[k={_MEGASTEP_K},cost]",
            _megastep_segment_cost),
    ]
    # performance observatory: the ATTRIBUTED entry points (what
    # PerfAttributor.attributed hands the dispatcher) lower to the
    # IDENTICAL program as the bare ones — same exact collective
    # counts, same analytic byte bill, no host escapes, unchanged
    # compile fingerprints under the recompile checker. Attribution
    # is host-side by contract; these targets make the contract a gate
    targets += [
        HloTarget("observatory.attribution.segment[hlo]",
                  _attribution_segment_hlo),
        CostModelTarget("observatory.attribution.segment[cost]",
                        _attribution_segment_cost),
        TransferTarget("observatory.attribution.segment[transfer]",
                       lambda: _transfer_spec(_attributed_segment_entry)),
        RecompileTarget("observatory.attribution.segment[recompile]",
                        lambda: _recompile_spec(_attributed_segment_entry,
                                                ((0, (0,)),))),
        HloTarget("observatory.attribution.pic_step[hlo]",
                  _attribution_pic_hlo),
        TransferTarget("observatory.attribution.pic_step[transfer]",
                       lambda: _transfer_spec(_attributed_pic_entry)),
    ]
    # link observatory: the modeled per-link traffic matrix sums
    # EXACTLY to the HLO-extracted wire bytes for every registered
    # method — slab/packed x s, the all-gather control, migration, and
    # the PIC step's accumulate adjoint (see the block comment above)
    targets += [
        LinkmapTarget("observatory.linkmap.exchange[r1]",
                      lambda: _linkmap_exchange_spec("r1")),
        LinkmapTarget("observatory.linkmap.exchange[r3]",
                      lambda: _linkmap_exchange_spec("r3")),
        LinkmapTarget("observatory.linkmap.exchange[asym]",
                      lambda: _linkmap_exchange_spec("asym")),
        LinkmapTarget("observatory.linkmap.exchange[r1,irr]",
                      lambda: _linkmap_exchange_irr_spec("r1")),
        LinkmapTarget("observatory.linkmap.exchange[r3,irr]",
                      lambda: _linkmap_exchange_irr_spec("r3")),
        LinkmapTarget("observatory.linkmap.packed[uneven]",
                      _linkmap_packed_uneven_spec),
        LinkmapTarget("observatory.linkmap.plan[PpermuteSlab,s=2]",
                      lambda: _linkmap_plan_spec("PpermuteSlab", 2)),
        LinkmapTarget("observatory.linkmap.plan[PpermutePacked,s=4]",
                      lambda: _linkmap_plan_spec("PpermutePacked", 4)),
        LinkmapTarget("observatory.linkmap.plan[PpermuteSlab,s=1.1.2]",
                      _linkmap_temporal_asym_spec),
        LinkmapTarget("observatory.linkmap.hierarchical[dcn]",
                      _linkmap_hier_dcn_spec),
        LinkmapTarget("observatory.linkmap.allgather",
                      _linkmap_allgather_spec),
        LinkmapTarget("observatory.linkmap.migrate",
                      _linkmap_migrate_spec),
        LinkmapTarget("observatory.linkmap.pic_step",
                      _linkmap_pic_spec),
    ]
    # the particle-migration ring and the fused PIC step: the dynamic
    # communication pattern under the same gates as the static sweep —
    # ppermute-only lowering with the static budget x record-rows wire
    # bill matching the model exactly, and the overflow column riding
    # the probe's one all-reduce
    targets += [
        CollectiveTarget("parallel.migrate.migrate_shard",
                         _migrate_spec),
        HloTarget("parallel.migrate.migrate_shard[hlo]", _migrate_hlo),
        CostModelTarget("parallel.migrate.migrate_shard[cost]",
                        _migrate_cost),
        HloTarget("models.pic.step[hlo]", _pic_step_hlo),
        CostModelTarget("models.pic.step[cost]", _pic_step_cost),
        HloTarget("models.pic.probe[hlo]", _pic_probe_hlo),
    ]
    # the segment compiler's per-model carry contracts: a fused PIC
    # segment bills exactly k x 18 collective-permutes + one probe
    # all-reduce per trace row with HLO-exact bytes AND the full
    # contract probe columns (overflow included, byte-pinned); the
    # astaroth temporal segment pays exactly its lcm(3,s)-period
    # grouped deep exchanges — k x the amortized deep-exchange model —
    # with w riding only where a group starts at alpha != 0
    targets += [
        HloTarget(f"models.pic.segment[k={_MEGASTEP_K},hlo]",
                  _pic_segment_hlo),
        CostModelTarget(f"models.pic.segment[k={_MEGASTEP_K},cost]",
                        _pic_segment_cost),
        CostModelTarget(f"models.pic.segment[k={_MEGASTEP_K},probe]",
                        _pic_segment_probe_cost),
        HloTarget(f"models.astaroth.segment[temporal,s={_AST_SEG_S},"
                  f"k={_AST_SEG_K},hlo]", _astaroth_segment_hlo),
        CostModelTarget(
            f"models.astaroth.segment[temporal,s={_AST_SEG_S},"
            f"k={_AST_SEG_K},cost]", _astaroth_segment_cost),
    ]
    for axis, ax_name in enumerate("xyz"):
        targets.append(StencilOpTarget(
            f"ops.stencil_kernels.central_diff[{ax_name}]",
            lambda a=axis: _central_diff_spec(a)))
    # the dataflow block: donation / transfer / recompile audits for
    # every compiled entry point the drivers dispatch
    targets += _dataflow_targets()
    # static VMEM/tiling audit: every shipped Pallas kernel
    targets += [
        VmemTarget("parallel.pallas_exchange.exchange_shard_pallas[vmem]",
                   lambda: _vmem_from_kernel(_rdma_exchange_spec)),
        VmemTarget("ops.pallas_overlap.jacobi7_overlap_pallas[vmem]",
                   lambda: _vmem_from_kernel(_jacobi_overlap_spec)),
        VmemTarget("ops.pallas_mhd_overlap.mhd_substep_overlap[vmem]",
                   lambda: _vmem_from_kernel(
                       lambda: _mhd_overlap_spec(pair=False))),
        VmemTarget("ops.pallas_halo.jacobi7_halo_pallas[vmem]",
                   lambda: _vmem_from_kernel(_jacobi_halo_kernel_spec)),
        VmemTarget("ops.pallas_stencil.jacobi7_pallas",
                   _jacobi7_plane_vmem_spec),
        VmemTarget("ops.pallas_stencil.jacobi7_wrap_pallas",
                   lambda: _jacobi_wrap_vmem_spec(1)),
        VmemTarget("ops.pallas_stencil.jacobi7_wrapn_pallas[n=2]",
                   lambda: _jacobi_wrap_vmem_spec(2)),
        VmemTarget("ops.pallas_stencil.jacobi7_wrapn_pallas[n=4]",
                   lambda: _jacobi_wrap_vmem_spec(4)),
        VmemTarget("ops.pallas_mhd.mhd_substep_wrap_pallas",
                   lambda: _mhd_wrap_vmem_spec(pair=False)),
        VmemTarget("ops.pallas_mhd.mhd_substep01_wrap_pallas",
                   lambda: _mhd_wrap_vmem_spec(pair=True)),
        VmemTarget("ops.pallas_halo.jacobi7_halon_pallas[n=2]",
                   _jacobi_halon_vmem_spec),
        VmemTarget("ops.pallas_halo.mhd_substep_halo_pallas",
                   lambda: _mhd_halo_vmem_spec(pair=False)),
        VmemTarget("ops.pallas_halo.mhd_substep01_halo_pallas",
                   lambda: _mhd_halo_vmem_spec(pair=True)),
    ]
    # prescriptive tiling: every shipped Pallas kernel gated at
    # 256^3/512^3-per-device shapes (checker 10)
    targets += _tiling_targets()
    # replay-soundness certification of every remote-DMA kernel's
    # semaphore schedule (checker 12)
    targets += _schedule_targets()
    # the certified bf16 wire customer: HLO/byte proofs that wire
    # bytes exactly halve
    targets += _wire_format_targets()
    # dtype-flow certification of every exchange/step/segment entry
    # point (checker 13)
    targets += _precision_targets()
    return targets


def load_targets(path: Union[str, Path]) -> List[Target]:
    """Load a fixture module (a .py file defining ``TARGETS``) and
    return its targets — the negative-control entry point."""
    path = Path(path)
    spec = importlib.util.spec_from_file_location(
        f"stencil_lint_fixture_{path.stem}", path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load fixture module {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    targets = getattr(mod, "TARGETS", None)
    if not targets:
        raise ValueError(f"fixture {path} defines no TARGETS")
    return list(targets)
