"""Jacobi-3D heat solver: the flagship demo application.

TPU-native re-implementation of the reference's jacobi3d app
(reference: bin/jacobi3d.cu): a 7-point Jacobi relaxation over a
periodic global grid with a hot sphere (T=1) at x=1/3 and a cold sphere
(T=0) at x=2/3, each of radius gx/10, re-imposed every iteration
(bin/jacobi3d.cu:40-85); everything else initialized to the mean
temperature 0.5 (bin/jacobi3d.cu:18-27).

Design: unlike the reference's interior-launch / exchange / exterior-
launch choreography (bin/jacobi3d.cu:296-377), the whole iteration —
halo exchange + stencil + sources — is ONE ``shard_map``-ped XLA
program; XLA schedules the ppermutes against the compute (async
collectives are its overlap mechanism), and buffer donation makes the
double-buffer swap an in-place update.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..distributed import DistributedDomain
from ..geometry import Dim3, Dim3Like, Radius
from ..local_domain import zyx_shape
from ..ops.stencil_kernels import global_coords, jacobi7, write_interior
from ..parallel.exchange import dispatch_exchange
from ..parallel.mesh import mesh_dim
from ..parallel.methods import Method, pick_method

HOT_TEMP = 1.0   # reference: bin/jacobi3d.cu:12
COLD_TEMP = 0.0  # reference: bin/jacobi3d.cu:11


def sphere_geometry(gsize: Dim3):
    """Hot/cold Dirichlet sphere centers and radius for a global grid
    (reference: bin/jacobi3d.cu:255-260): hot at x/3, cold at 2x/3,
    both mid-(y,z), radius x/10. Returns (hot_xyz, cold_xyz, r)."""
    hot = Dim3(gsize.x // 3, gsize.y // 2, gsize.z // 2)
    cold = Dim3(gsize.x * 2 // 3, gsize.y // 2, gsize.z // 2)
    return hot, cold, gsize.x // 10


def jacobi_shard_step(p, radius: Radius, counts: Dim3, local: Dim3,
                      gsize: Dim3, origin_xyz, method: Method,
                      kernel: str = "xla", rem: Dim3 = Dim3(0, 0, 0),
                      nonperiodic: bool = False, wire_format=None,
                      wire_layout=None):
    """One fused Jacobi step on one shard: exchange + 7-point update +
    Dirichlet sphere sources. ``origin_xyz`` is the shard's global
    origin (traced axis_index-derived inside shard_map, or static
    (0,0,0) single-chip). Shared by Jacobi3D and the driver entry.
    ``kernel``: "xla" (fused slicing) or "pallas" (z-plane-pipelined
    VMEM kernel, ops/pallas_stencil.py). ``wire_format`` narrows the
    halo WIRE only (send-boundary convert, widen on arrival —
    parallel/exchange.py); the update math runs at storage dtype.
    ``wire_layout`` picks the wire message geometry ("slab" or
    "irredundant" — parallel/packing.py); interiors are bitwise
    identical either way."""
    hot_c, cold_c, sph_r = sphere_geometry(gsize)

    p = dispatch_exchange({"temp": p}, radius, counts, method,
                          rem=rem, nonperiodic=nonperiodic,
                          wire_format=wire_format,
                          wire_layout=wire_layout)["temp"]
    if kernel == "pallas":
        from ..ops.pallas_stencil import jacobi7_pallas
        new = jacobi7_pallas(p, radius, local)
    else:
        new = jacobi7(p, radius, local)
    new = _apply_sources(new, origin_xyz, local, hot_c, cold_c, sph_r)
    return write_interior(p, new, radius)


def _apply_sources(new, origin_xyz, local: Dim3, hot_c: Dim3, cold_c: Dim3,
                   sph_r: int):
    """Re-impose the Dirichlet hot/cold spheres
    (reference: bin/jacobi3d.cu:40-63)."""
    gz, gy, gx = global_coords(origin_xyz, local)

    def dist2(c: Dim3):
        return (gx - c.x) ** 2 + (gy - c.y) ** 2 + (gz - c.z) ** 2

    new = jnp.where(dist2(hot_c) <= sph_r * sph_r,
                    jnp.asarray(HOT_TEMP, new.dtype), new)
    new = jnp.where(dist2(cold_c) <= sph_r * sph_r,
                    jnp.asarray(COLD_TEMP, new.dtype), new)
    return new


def _apply_sources_windowed(new, origin_xyz, dims: Dim3, gsize: Dim3,
                            hot_c: Dim3, cold_c: Dim3, sph_r: int,
                            nonperiodic: bool):
    """Per-sub-step sources for a temporal-blocking window that may
    reach into the halo ring: ring cells must get exactly what their
    OWNER shard computes, so periodic coords wrap mod the global size
    before the sphere test; with the zero-Dirichlet exterior
    (Boundary.NONE) out-of-domain cells are forced to zero instead."""
    gz, gy, gx = global_coords(origin_xyz, dims)
    if nonperiodic:
        inside = ((gx >= 0) & (gx < gsize.x) & (gy >= 0) & (gy < gsize.y)
                  & (gz >= 0) & (gz < gsize.z))
    else:
        gx = gx % gsize.x
        gy = gy % gsize.y
        gz = gz % gsize.z

    def dist2(c: Dim3):
        return (gx - c.x) ** 2 + (gy - c.y) ** 2 + (gz - c.z) ** 2

    new = jnp.where(dist2(hot_c) <= sph_r * sph_r,
                    jnp.asarray(HOT_TEMP, new.dtype), new)
    new = jnp.where(dist2(cold_c) <= sph_r * sph_r,
                    jnp.asarray(COLD_TEMP, new.dtype), new)
    if nonperiodic:
        new = jnp.where(inside, new, jnp.zeros_like(new))
    return new


def jacobi_shard_step_overlap(p, radius: Radius, counts: Dim3, local: Dim3,
                              gsize: Dim3, origin_xyz, method: Method,
                              kernel: str = "xla",
                              nonperiodic: bool = False):
    """Overlapped variant of ``jacobi_shard_step``: the deep-interior
    update is computed from pre-exchange owned data so XLA can schedule
    it against the in-flight halo transfers; thin exterior shells are
    computed after (the reference's interior-launch / exchange /
    exterior-launch choreography, bin/jacobi3d.cu:296-377, as one
    program)."""
    from ..parallel.overlap import overlapped_update

    hot_c, cold_c, sph_r = sphere_geometry(gsize)

    def upd(blocks, dims, off):
        blk = blocks["temp"]
        if kernel == "pallas":
            from ..ops.pallas_stencil import jacobi7_pallas
            return {"temp": jacobi7_pallas(blk, radius, dims)}
        return {"temp": jacobi7(blk, radius, dims)}

    p_ex, new = overlapped_update({"temp": p}, radius, counts, method, upd,
                                  nonperiodic=nonperiodic)
    out = _apply_sources(new["temp"], origin_xyz, local, hot_c, cold_c, sph_r)
    return write_interior(p_ex["temp"], out, radius)


def _dcn_request_kwargs(dd) -> dict:
    """The DCN-tier request the domain was configured with, as model
    constructor kwargs — a degradation rebuild must not silently strip
    the slice tiering (``None`` axis means auto-derive, which the
    constructors spell ``"auto"``)."""
    if not dd._dcn_requested:
        return {}
    req = dd._dcn_axis_req
    return {"dcn_axis": "auto" if req is None else req,
            "dcn_groups": dd._dcn_groups}


def _wrap_steps(tile: int, requested: int = 0) -> int:
    """Temporal-blocking depth for the Pallas fast paths: an explicit
    ``exchange_every`` request wins; else STENCIL_WRAP_STEPS (default
    2). Clamped to [1, sublane tile] — shared by the wrap and halo step
    builders (one tunable, two kernel families)."""
    import os

    if requested:
        return min(max(int(requested), 1), tile)
    try:
        n = int(os.environ.get("STENCIL_WRAP_STEPS", "2") or 2)
    except ValueError:
        from ..utils.logging import LOG_WARN
        LOG_WARN("STENCIL_WRAP_STEPS is not an integer; using 2")
        n = 2
    return min(max(n, 1), tile)


#: memoized overlap-kernel schedule certificates, keyed by the traced
#: geometry AND the certifier's identity (so a monkeypatched certifier
#: in tests is never shadowed by a cached verdict)
_OVERLAP_CERT_MEMO: dict = {}


def _overlap_schedule_certificate(dd, dtype, hot, cold, sph_r,
                                  counts: Dim3):
    """Ask the schedule certifier (analysis/schedule.py) whether the
    in-kernel RDMA overlap kernel's semaphore schedule is sound under
    k-fold replay on ``dd``'s mesh: trace the same per-shard program
    ``_build_overlap_step`` runs (a synthetic even global of
    base-shard interiors — the schedule's shape does not depend on the
    ±1 remainder rows) and certify every Pallas kernel inside.  Any
    trace failure comes back as an unsafe certificate, so callers
    decline instead of crashing."""
    from ..analysis import schedule as schedule_checker
    from ..ops.pallas_overlap import jacobi7_overlap_pallas
    from ..parallel.exchange import shard_origin

    local = dd.local_size
    rem = dd.rem
    key = ((counts.z, counts.y, counts.x),
           (local.z, local.y, local.x), (rem.z, rem.y, rem.x),
           str(jnp.dtype(dtype)),
           id(schedule_checker.certify_traceable))
    hit = _OVERLAP_CERT_MEMO.get(key)
    if hit is not None:
        return hit

    def shard(q):
        ox, oy, oz = shard_origin(local, rem)
        org = jnp.stack([oz, oy, ox]).astype(jnp.int32)
        return jacobi7_overlap_pallas(q, org, hot, cold, sph_r, counts,
                                      interpret=False)

    spec = P("z", "y", "x")
    sm = jax.shard_map(shard, mesh=dd.mesh, in_specs=spec,
                       out_specs=spec, check_vma=False)
    gshape = (local.z * counts.z, local.y * counts.y,
              local.x * counts.x)
    cert = schedule_checker.certify_traceable(
        sm, (jax.ShapeDtypeStruct(gshape, dtype),))
    _OVERLAP_CERT_MEMO[key] = cert
    return cert


def _dcn_xfree_shape(size: Dim3, devices, dcn_axis, dcn_groups, kernel,
                     align: int = 1):
    """Slice-compatible x-unsharded mesh shape when a DCN tier is
    requested together with a halo-family fast path (explicit
    kernel='halo', or 'auto' on TPU) — NodePartition's derived split
    may shard x, which the slab kernels cannot use. Returns None —
    letting realize()'s NodePartition ladder stand — for non-halo
    kernels, an x-axis DCN tier, indivisible device counts, or a
    candidate shape the GRID cannot host (every axis must divide
    evenly with local z/y multiples of ``align``; the same
    guarantee-or-decline contract as ``partition_dims_even_xfree``)."""
    from ..ops.pallas_stencil import on_tpu

    if not (kernel == "halo" or (kernel == "auto" and on_tpu())):
        return None
    axis = dcn_axis
    if isinstance(axis, str):
        axis = {"x": 0, "y": 1, "z": 2, "auto": None}[axis]
    if axis == 0:
        return None          # x-axis DCN tier cannot be x-free
    from ..parallel.mesh import default_mesh_shape_dcn
    from ..parallel.multihost import slice_groups

    groups = dcn_groups or slice_groups(devices)
    if len(groups) <= 1 or len(devices) % len(groups):
        return None
    shape = default_mesh_shape_dcn(len(devices), len(groups),
                                   axis=2 if axis is None else axis,
                                   xfree=True)
    for a in range(3):
        if size[a] % shape[a]:
            return None
    if (size.z // shape.z) % align or (size.y // shape.y) % align:
        return None
    return shape


class Jacobi3D:
    """Distributed Jacobi-3D solver over a TPU mesh."""

    def __init__(self, x: int, y: int, z: int,
                 mesh_shape: Optional[Dim3Like] = None,
                 dtype=jnp.float32,
                 devices: Optional[Sequence] = None,
                 methods: Method = Method.Default,
                 placement=None, output_prefix: str = "",
                 kernel: str = "auto", overlap: bool = False,
                 dcn_axis=None, dcn_groups=None,
                 exchange_every: Optional[int] = None,
                 boundary=None, wire_format=None,
                 wire_layout=None) -> None:
        self.dd = DistributedDomain(x, y, z, devices=devices)
        self.dd.set_radius(1)
        self.dd.set_methods(methods)
        # temporal blocking: None = unset (fast paths keep their
        # STENCIL_WRAP_STEPS default); an explicit s pins the depth —
        # deep-carry allocations + one deep exchange per s steps on the
        # XLA path (parallel/temporal.py), the in-kernel step count on
        # the Pallas wrap/halo paths (s == 1 forces per-step exchange).
        # Per-axis specs ({"z": 4}, (1, 1, 4)) deepen only the named
        # axes — the XLA temporal engine only; the Pallas fast paths
        # decline them loudly below
        if exchange_every is None:
            self._exchange_every = 0
        elif isinstance(exchange_every, int):
            self._exchange_every = max(int(exchange_every), 1)
        else:
            from ..geometry import normalize_depths
            self._exchange_every = max(normalize_depths(exchange_every))
        if self._exchange_every > 1:
            self.dd.set_exchange_every(exchange_every)
        if boundary is not None:
            self.dd.set_boundary(boundary)
        if wire_format is not None:
            # halo wire narrowing (send-boundary bf16, widen on
            # arrival); realize() below runs the precision gate
            self.dd.set_wire_format(wire_format)
        if wire_layout is not None:
            # wire message geometry (slab / irredundant packed boxes)
            self.dd.set_wire_layout(wire_layout)
        if dcn_axis is not None or dcn_groups is not None:
            self.dd.set_dcn_axis(dcn_axis, dcn_groups)
        if placement is not None:
            self.dd.set_placement(placement)
        if output_prefix:
            self.dd.set_output_prefix(output_prefix)
        if mesh_shape is not None:
            self.dd.set_mesh_shape(mesh_shape)
        elif dcn_axis is not None or dcn_groups is not None:
            # DCN tier with no explicit shape: normally let realize()
            # derive the grid from NodePartition's two-level split —
            # but the halo fast paths need the lane (x) axis unsharded,
            # which that split does not know, so derive the x-free
            # slice-compatible shape here (the apps' dcn_mesh_shape
            # rule, in the model so library users get it too)
            shape = _dcn_xfree_shape(Dim3(x, y, z), self.dd._devices,
                                     dcn_axis, dcn_groups, kernel)
            if shape is not None:
                self.dd.set_mesh_shape(shape)
        else:
            from ..ops.pallas_stencil import on_tpu
            if (len(self.dd._devices) > 1 and not overlap
                    and (kernel == "halo"
                         or (kernel == "auto" and on_tpu()))):
                # prefer an x-unsharded decomposition so the fused halo
                # kernel path is available (ops/pallas_halo.py: cutting
                # the lane axis is the worst TPU choice anyway); other
                # paths keep the cube-like partition_dims_even choice
                from ..partition import partition_dims_even_xfree
                shape = partition_dims_even_xfree(
                    Dim3(x, y, z), len(self.dd._devices))
                if shape is not None:
                    self.dd.set_mesh_shape(shape)
        self.dd.add_data("temp", dtype)
        self.dd.realize()
        self._dtype = dtype
        if kernel not in ("auto", "wrap", "halo", "xla", "pallas"):
            raise ValueError(
                f"kernel must be auto|wrap|halo|xla|pallas, got {kernel!r}")
        self._kernel = kernel
        self._overlap = overlap
        self._build_step()

    # -- initial conditions (reference: bin/jacobi3d.cu:18-27) ---------
    def init(self) -> None:
        mean = np.asarray((HOT_TEMP + COLD_TEMP) / 2, dtype=self._dtype)
        vals = np.full(zyx_shape(self.dd.size), mean, dtype=self._dtype)
        self.dd.set_interior("temp", vals)

    # -- megastep: whole campaign segments as one program --------------
    def _set_segment_builder(self, shard_advance, stride: int = 1
                             ) -> None:
        """Register the fused-segment factory for the built compute
        path: ``shard_advance(p, steps)`` advances one shard's padded
        field ``steps`` steps (``steps`` is the path's stride — a
        whole temporal group or a Pallas kernel's in-kernel multi-step
        count — or a depth-1 tail step). The carry contract is one
        padded field under ``P('z','y','x')``; :meth:`make_segment`
        compiles/caches the megastep programs through the generic
        segment compiler (``parallel/megastep.py``)."""
        from jax.sharding import PartitionSpec as P

        from ..parallel import megastep as ms

        dd = self.dd
        self.step_stride = stride

        def adopt(out):
            self.dd.curr["temp"] = out

        self._segment_decline = None
        self._segment_builder = ms.SegmentCompiler(
            dd.mesh,
            ms.CarryContract(specs=P("z", "y", "x"),
                             probe_view=lambda p: {"temp": p},
                             stride=stride),
            lambda p, c, i: shard_advance(p, c),
            lambda: self.dd.curr["temp"], adopt)

    def _set_segment_decline(self, reason: str,
                             code: Optional[str] = None) -> None:
        """The built path cannot fuse: record why (prose + a
        ``megastep.DECLINE_*`` vocabulary code), so
        :meth:`make_segment` returns a loud, reason-carrying
        :class:`~stencil_tpu.parallel.megastep.SegmentDecline` instead
        of a silent None."""
        self._segment_builder = None
        self._segment_decline = reason
        self._segment_decline_code = code

    def make_segment(self, check_every: int, probe_every: int = 1,
                     metrics=None):
        """ONE compiled program advancing ``check_every`` iterations
        with the health probe fused in-graph every ``probe_every``
        steps (``parallel/megastep.py``): the resilient driver, the
        apps, and the bench dispatch one of these per health boundary
        instead of one jitted step per iteration. Field state is
        donated end-to-end. Every built compute path fuses — the XLA
        and temporal paths unroll their shard bodies, the wrap/halo
        Pallas paths chunk into their in-kernel multi-step launches,
        and the in-kernel RDMA overlap path fuses its kernel launches
        when the schedule certifier (``analysis/schedule.py``) proves
        the semaphore schedule ``replay_safe``. A path that cannot
        fuse returns a falsy ``SegmentDecline`` carrying the reason
        (for the overlap path: the certificate's own reasons) and a
        ``DECLINE_*`` vocabulary code; the driver reports it and falls
        back to the stepwise dispatch loop."""
        builder = getattr(self, "_segment_builder", None)
        if builder is None:
            from ..parallel import megastep as ms
            reason = (getattr(self, "_segment_decline", None)
                      or "no fused-segment builder for this path")
            code = (getattr(self, "_segment_decline_code", None)
                    or ms.DECLINE_NO_BUILDER)
            return ms.decline("jacobi", self.kernel_path, reason,
                              code=code)
        return builder(int(check_every), max(int(probe_every), 1),
                       metrics)

    # -- the fused step ------------------------------------------------
    def _build_step(self) -> None:
        self._segment_builder = None
        #: steps one launch of the built path advances: the in-kernel
        #: temporal depth on the Pallas wrap/halo paths (2 = the pair
        #: kernel), the group depth on the XLA temporal path
        self.step_stride = 1
        self._segment_decline = None
        dd = self.dd
        radius = dd.radius
        counts = mesh_dim(dd.mesh)
        local = dd.local_size
        gsize = dd.size
        method = pick_method(self.dd.methods)
        kernel = self._kernel
        rem = dd.rem
        if self._overlap and rem != Dim3(0, 0, 0):
            raise NotImplementedError("overlap mode requires an evenly "
                                      "divisible grid")
        from ..topology import Boundary
        nonper = dd.boundary == Boundary.NONE
        s_every = dd.exchange_every
        depths = dd.exchange_depths
        asym = not (depths.x == depths.y == depths.z)
        if asym and self._overlap:
            raise NotImplementedError(
                "asymmetric temporal depths (per-axis exchange_every) "
                "are not supported with overlap=True — the overlap "
                "composition assumes one symmetric deep exchange per "
                "group (parallel/temporal.py declines it too)")
        if asym and kernel in ("wrap", "halo", "pallas"):
            raise NotImplementedError(
                f"asymmetric temporal depths "
                f"(exchange_every={tuple(depths)}) are not supported "
                f"with kernel={kernel!r} — the Pallas in-kernel "
                f"multi-step paths have one step count, not one per "
                f"axis; use kernel='xla' or 'auto'")
        from ..parallel.exchange import normalize_wire_format
        from ..parallel.packing import normalize_wire_layout
        wire = dd.wire_format
        wire_narrows = any(v != "f32"
                           for v in normalize_wire_format(wire).values())
        layout = getattr(dd, "wire_layout", "slab")
        irr_layout = normalize_wire_layout(layout) == "irredundant"
        # single-chip fast path: periodic wrap fused INTO the stencil
        # kernel (no halo storage, no exchange program) — the TPU-native
        # answer to the reference's same-GPU PeerAccessSender shortcut.
        # All Pallas fast paths assume the periodic wrap rule, so the
        # zero-Dirichlet exterior (Boundary.NONE) runs the XLA paths.
        radius_ok = all(radius.face(a, s) == 1
                        for a in range(3) for s in (-1, 1))
        wrap_ok = (counts == Dim3(1, 1, 1) and rem == Dim3(0, 0, 0)
                   and not self._overlap and radius_ok and not nonper
                   and not asym)
        # the multi-device fast path: interior-resident shards + slab
        # exchange + fused halo kernel (ops/pallas_halo.py); uneven
        # (+-1) z/y shards supported via the kernel's interior-length
        # overlay (x is never sharded here, so rem.x is always 0)
        halo_ok = (counts.x == 1 and not self._overlap and radius_ok
                   and not nonper and not wire_narrows
                   and not irr_layout and not asym)
        # the overlapped fast path: in-kernel RDMA slab exchange hidden
        # behind the interior compute (ops/pallas_overlap.py) — the
        # reference's interior/exchange/exterior choreography as one
        # kernel (bin/jacobi3d.cu:296-377). With exchange_every > 1 the
        # temporal paths amortize the exchange instead (the deep
        # exchange already hides behind sub-step-0 interior compute).
        overlap_ok = (self._overlap and counts.x == 1
                      and rem == Dim3(0, 0, 0) and radius_ok
                      and local.z >= 4 and local.y >= 2
                      and not nonper and s_every == 1
                      and not wire_narrows and not irr_layout)
        from ..ops.pallas_stencil import on_tpu
        from ..utils.logging import LOG_INFO
        # explicit kernel='halo' with overlap opts into the RDMA overlap
        # kernel anywhere (tests run it interpreted); 'auto' only
        # selects Pallas paths on real TPU hardware
        if overlap_ok and (kernel == "halo"
                           or (kernel == "auto" and on_tpu())):
            self.kernel_path = "overlap"
            self._build_overlap_step()
            LOG_INFO("jacobi kernel path: overlap (in-kernel RDMA)")
            return
        if kernel == "auto":
            if on_tpu():
                kernel = ("wrap" if wrap_ok
                          else "halo" if halo_ok else "xla")
            else:
                kernel = "xla"
            why = ""
            if kernel == "xla" and on_tpu():
                blockers = []
                if counts.x != 1:
                    blockers.append("x-axis sharded")
                if self._overlap:
                    blockers.append("overlap requested")
                if not radius_ok:
                    blockers.append("radius != 1")
                why = f" (fast paths unavailable: {', '.join(blockers)})"
            LOG_INFO(f"jacobi kernel path: {kernel}{why}")
        if kernel == "wrap":
            if not wrap_ok:
                raise ValueError("kernel='wrap' needs a (1,1,1) mesh, "
                                 "radius 1, even grid, overlap off")
            self.kernel_path = "wrap"
            self._build_wrap_step()
            return
        if kernel == "halo":
            if not halo_ok:
                raise ValueError("kernel='halo' needs an x-unsharded "
                                 "mesh, radius 1, periodic boundaries, "
                                 "overlap off (or overlap with local "
                                 "z>=4)")
            self.kernel_path = "halo"
            self._build_halo_step()
            return
        if s_every > 1:
            if kernel == "pallas":
                raise ValueError("exchange_every > 1 is not supported "
                                 "with kernel='pallas' (use xla, wrap "
                                 "or halo)")
            if wire_narrows:
                raise NotImplementedError(
                    "a narrowing wire_format is not supported with "
                    "exchange_every > 1 (the temporal deep exchange "
                    "has no wire-narrowing variant yet)")
            tag = (f"s={depths.x}.{depths.y}.{depths.z}" if asym
                   else f"s={s_every}")
            self.kernel_path = (f"xla-temporal[{tag}]"
                                + ("-overlap" if self._overlap else ""))
            self._build_temporal_step()
            from ..utils.logging import LOG_INFO
            LOG_INFO(f"jacobi kernel path: {self.kernel_path}")
            return
        self.kernel_path = f"{kernel}-overlap" if self._overlap else kernel
        step_fn = (jacobi_shard_step_overlap if self._overlap
                   else jacobi_shard_step)

        if wire_narrows and self._overlap:
            raise NotImplementedError(
                "a narrowing wire_format is not supported with "
                "overlap=True (overlapped_update has no wire-narrowing "
                "variant yet)")

        def shard_step(p):
            from ..parallel.exchange import shard_origin
            origin = shard_origin(local, rem)
            if self._overlap:
                return step_fn(p, radius, counts, local, gsize,
                               origin, method, kernel, nonper)
            return step_fn(p, radius, counts, local, gsize,
                           origin, method, kernel, rem, nonper,
                           wire_format=wire, wire_layout=layout)

        spec = P("z", "y", "x")
        sm = jax.shard_map(shard_step, mesh=dd.mesh, in_specs=spec,
                           out_specs=spec, check_vma=False)
        self._step = jax.jit(sm, donate_argnums=0)

        def shard_steps(p, n):
            return lax.fori_loop(0, n, lambda _, q: shard_step(q), p)

        sm_n = jax.shard_map(shard_steps, mesh=dd.mesh, in_specs=(spec, P()),
                             out_specs=spec, check_vma=False)
        self._step_n = jax.jit(sm_n, donate_argnums=0)
        self._set_segment_builder(lambda p, c: shard_step(p))

    def _build_temporal_step(self) -> None:
        """Communication-avoiding XLA steps: iterations run in groups of
        ``s = exchange_every`` through ``parallel/temporal.py`` — ONE
        depth-``s`` exchange, then ``s`` fused 7-point sub-steps on the
        shrinking window (ring cells recomputed redundantly, numerically
        identical to step-by-step) — with a depth-1 tail for the
        remainder. With ``overlap=True`` the deep exchange hides behind
        sub-step 0's interior compute (even shards)."""
        from ..parallel.exchange import shard_origin
        from ..parallel.temporal import temporal_shard_steps, validate_temporal
        from ..topology import Boundary

        dd = self.dd
        radius = dd.radius
        counts = mesh_dim(dd.mesh)
        local = dd.local_size
        gsize = dd.size
        method = pick_method(dd.methods)
        rem = dd.rem
        s = dd.exchange_every
        depths = dd.exchange_depths  # per-axis; == (s, s, s) when uniform
        nonper = dd.boundary == Boundary.NONE
        overlap = self._overlap
        layout = getattr(dd, "wire_layout", "slab")
        hot_c, cold_c, sph_r = sphere_geometry(gsize)
        validate_temporal(radius, local, depths, rem)

        def make_update(origin):
            ox, oy, oz = origin

            def update_fn(blocks, dims, off, k):
                new = jacobi7(blocks["temp"], radius, dims)
                org = (ox + off[0], oy + off[1], oz + off[2])
                new = _apply_sources_windowed(new, org, dims, gsize, hot_c,
                                              cold_c, sph_r, nonper)
                return {"temp": new.astype(blocks["temp"].dtype)}

            return update_fn

        def shard_steps(p, n):
            upd = make_update(shard_origin(local, rem))

            def group(q, depth, ovl):
                return temporal_shard_steps(
                    {"temp": q}, radius, counts, method, upd, depth,
                    alloc_steps=depths, rem=rem, overlap=ovl,
                    nonperiodic=nonper, wire_layout=layout)["temp"]

            p = lax.fori_loop(0, n // s,
                              lambda _, q: group(q, depths, overlap), p)
            return lax.fori_loop(0, n % s,
                                 lambda _, q: group(q, 1, False), p)

        spec = P("z", "y", "x")
        sm = jax.shard_map(shard_steps, mesh=dd.mesh, in_specs=(spec, P()),
                           out_specs=spec, check_vma=False)
        self._step_n = jax.jit(sm, donate_argnums=0)
        self._step = jax.jit(
            lambda p: sm(p, jnp.asarray(1, jnp.int32)), donate_argnums=0)

        def shard_advance(p, c):
            # one temporal group of c steps (c == s, run at the
            # configured per-axis depths) or a depth-1 tail step — the
            # same bodies the fused run loop iterates
            upd = make_update(shard_origin(local, rem))
            return temporal_shard_steps(
                {"temp": p}, radius, counts, method, upd,
                depths if c == s else c,
                alloc_steps=depths, rem=rem,
                overlap=(overlap and c == s),
                nonperiodic=nonper, wire_layout=layout)["temp"]

        self._set_segment_builder(shard_advance, stride=s)

    def _build_wrap_step(self) -> None:
        """Single-chip fused steps on the interior view: iterations run
        in groups of N through the temporally-blocked multi-step kernel
        (ops/pallas_stencil.jacobi7_wrapn_pallas — ~1/N the HBM traffic
        per iteration; N=2 default, STENCIL_WRAP_STEPS to tune) with a
        single-step tail; grids the blocked kernel can't tile fall back
        to single steps."""
        import os

        from ..ops.pallas_stencil import (jacobi7_wrapn_pallas,
                                          jacobi7_wrap_pallas,
                                          sublane_tile)
        from ..utils.config import wrap2_disabled

        dd = self.dd
        lo = dd.alloc_radius.pad_lo()
        local = dd.local_size
        gsize = dd.size
        hot, cold, sph_r = sphere_geometry(gsize)
        tile = sublane_tile(self._dtype)
        N = _wrap_steps(tile, self._exchange_every)
        pair_ok = (local.y % tile == 0 and N > 1
                   and not wrap2_disabled())

        def steps(p, n):
            inner = lax.slice(p, (lo.z, lo.y, lo.x),
                              (lo.z + local.z, lo.y + local.y,
                               lo.x + local.x))
            if pair_ok:
                inner = lax.fori_loop(
                    0, n // N,
                    lambda _, q: jacobi7_wrapn_pallas(q, hot, cold,
                                                      sph_r, steps=N),
                    inner)
                inner = lax.fori_loop(
                    0, n % N,
                    lambda _, q: jacobi7_wrap_pallas(q, hot, cold, sph_r),
                    inner)
            else:
                inner = lax.fori_loop(
                    0, n,
                    lambda _, q: jacobi7_wrap_pallas(q, hot, cold, sph_r),
                    inner)
            # halos go stale; nothing reads them before the next
            # exchange, and temperature() reads the interior only
            return lax.dynamic_update_slice(p, inner, (lo.z, lo.y, lo.x))

        self._step_n = jax.jit(steps, donate_argnums=0)
        self._step = jax.jit(
            lambda p: steps(p, jnp.asarray(1, jnp.int32)), donate_argnums=0)

        def shard_advance(p, c):
            # one segment chunk: c == N runs the temporally-blocked
            # multi-step kernel as ONE pallas launch; c == 1 tail steps
            # run the single-step kernel. Interior is sliced out and
            # written back per chunk (the probe reads the padded state)
            inner = lax.slice(p, (lo.z, lo.y, lo.x),
                              (lo.z + local.z, lo.y + local.y,
                               lo.x + local.x))
            if pair_ok and c == N:
                inner = jacobi7_wrapn_pallas(inner, hot, cold, sph_r,
                                             steps=N)
            else:
                for _ in range(c):
                    inner = jacobi7_wrap_pallas(inner, hot, cold, sph_r)
            return lax.dynamic_update_slice(p, inner, (lo.z, lo.y, lo.x))

        self._set_segment_builder(shard_advance,
                                  stride=N if pair_ok else 1)

    def _build_interior_resident_steps(self, make_body,
                                       segment_decline: Optional[str]
                                       = None,
                                       segment_stride: int = 1,
                                       segment_decline_code:
                                       Optional[str] = None) -> None:
        """Shared scaffolding for the interior-resident multi-device
        builders: slice the unpadded interior out of the padded shard,
        fori_loop the per-iteration body from ``make_body(org)``, write
        the interior back (halos go stale; nothing reads them before
        the next exchange, and temperature() reads the interior only),
        all inside one shard_map/jit with buffer donation.

        ``make_body(org)`` returns either a single-iteration body, or a
        ``(body, group_body, group_n)`` tuple — then ``n`` iterations
        run as ``n // group_n`` temporally-blocked groups plus a
        single-step tail."""
        from ..parallel.exchange import shard_origin

        dd = self.dd
        lo = dd.alloc_radius.pad_lo()
        local = dd.local_size
        rem = dd.rem

        def shard_steps(p, n):
            ox, oy, oz = shard_origin(local, rem)
            org = jnp.stack([oz, oy, ox]).astype(jnp.int32)
            inner = lax.slice(p, (lo.z, lo.y, lo.x),
                              (lo.z + local.z, lo.y + local.y,
                               lo.x + local.x))
            made = make_body(org)
            if isinstance(made, tuple):
                body, group_body, gn = made
                inner = lax.fori_loop(0, n // gn,
                                      lambda _, q: group_body(q), inner)
                inner = lax.fori_loop(0, n % gn,
                                      lambda _, q: body(q), inner)
            else:
                body = made
                inner = lax.fori_loop(0, n, lambda _, q: body(q), inner)
            return lax.dynamic_update_slice(p, inner, (lo.z, lo.y, lo.x))

        spec = P("z", "y", "x")
        sm = jax.shard_map(shard_steps, mesh=dd.mesh, in_specs=(spec, P()),
                           out_specs=spec, check_vma=False)
        self._step_n = jax.jit(sm, donate_argnums=0)
        self._step = jax.jit(
            lambda p: sm(p, jnp.asarray(1, jnp.int32)), donate_argnums=0)

        if segment_decline is not None:
            self._set_segment_decline(segment_decline,
                                      code=segment_decline_code)
            return

        def shard_advance(p, c):
            # one segment chunk, per shard: c == group_n is ONE
            # temporally-blocked kernel launch (its slab exchange
            # inside), c == 1 a single-step tail — the same bodies the
            # fused run loop iterates, with the interior written back
            # per chunk so the in-graph probe reads current state
            ox, oy, oz = shard_origin(local, rem)
            org = jnp.stack([oz, oy, ox]).astype(jnp.int32)
            inner = lax.slice(p, (lo.z, lo.y, lo.x),
                              (lo.z + local.z, lo.y + local.y,
                               lo.x + local.x))
            made = make_body(org)
            if isinstance(made, tuple):
                body, group_body, gn = made
                if c == gn:
                    inner = group_body(inner)
                else:
                    for _ in range(c):
                        inner = body(inner)
            else:
                for _ in range(c):
                    inner = made(inner)
            return lax.dynamic_update_slice(p, inner, (lo.z, lo.y, lo.x))

        self._set_segment_builder(shard_advance, stride=segment_stride)

    def _build_halo_step(self) -> None:
        """Multi-device fused steps: interior-resident shards, thin slab
        ppermutes, one fused Pallas kernel per step — so an N-chip mesh
        keeps single-chip per-chip throughput (the analog of the
        reference's fused solve kernel running at every scale,
        astaroth/astaroth.cu:552-646; see ops/pallas_halo.py).

        Even grids run iterations in groups of N through the
        temporally-blocked kernel (``jacobi7_halon_pallas``, N=2
        default / STENCIL_WRAP_STEPS): one radius-N exchange feeds N
        fused steps, dividing per-iteration HBM traffic AND exchange
        count by ~N (the slab-layout counterpart of the wrap-path
        kernel), with a single-step tail. Uneven (+-1) grids and grids
        the blocked kernel can't tile keep the single-step kernel."""
        import os

        from ..ops.pallas_halo import (fit_pair_halo_blocks,
                                       jacobi7_halon_pallas,
                                       jacobi7_halo_pallas)
        from ..ops.pallas_stencil import sublane_tile
        from ..parallel.exchange import (exchange_interior_slabs,
                                         shard_interior_len)
        from ..utils.config import wrap2_disabled

        dd = self.dd
        local = dd.local_size
        counts = mesh_dim(dd.mesh)
        rem = dd.rem
        gsize = (dd.size.z, dd.size.y, dd.size.x)
        hot, cold, sph_r = sphere_geometry(dd.size)
        tile = sublane_tile(self._dtype)
        esub = tile if local.y % tile == 0 else 1
        N = _wrap_steps(tile, self._exchange_every)
        pair_ok = (rem == Dim3(0, 0, 0) and N > 1 and esub == tile
                   and not wrap2_disabled())
        if pair_ok:
            from ..analysis.tiling import TilingInfeasibleError

            try:
                pbz, pby = fit_pair_halo_blocks(
                    local.z, local.y, local.x,
                    jnp.dtype(self._dtype).itemsize, N)
            except TilingInfeasibleError as e:
                # the planner found no legal blocking for the N-step
                # kernel at this shard: fall back to the single-step
                # kernel LOUDLY (the old fitter clamped silently and
                # let Mosaic fail at compile time). The planner
                # enforces bz >= steps, so a partial clamp cannot
                # happen — it is all-or-nothing by construction.
                from ..utils.logging import LOG_WARN
                LOG_WARN(f"halo temporal blocking declined: {e}")
                pair_ok = False
        if pair_ok:
            from ..utils.logging import LOG_INFO
            LOG_INFO(f"jacobi halo path: {N}-step temporal blocking, "
                     f"blocks ({pbz}, {pby})")
        # exchange accounting for exchange_stats(): the N-step groups
        # do one radius-N extended exchange per N iterations (the tail
        # uses the single-row config; stats report the group-amortized
        # steady state)
        self._slab_exchange_cfg = (
            dict(rz=pbz, ry=tile, radius_rows=N, y_z_extended=True,
                 per_iter_div=N) if pair_ok
            else dict(rz=1, ry=esub, radius_rows=1, y_z_extended=False,
                      per_iter_div=1))

        def make_body(org):
            lens = jnp.stack([
                jnp.asarray(shard_interior_len(2, local.z, rem)),
                jnp.asarray(shard_interior_len(1, local.y, rem)),
            ]).astype(jnp.int32)

            def body(q):
                slabs = exchange_interior_slabs(q, counts, rz=1, ry=esub,
                                                rem=rem)
                return jacobi7_halo_pallas(q, slabs, org, hot, cold,
                                           sph_r, interior_len_zy=lens)

            if not pair_ok:
                return body

            def pair_body(q):
                slabs = exchange_interior_slabs(
                    q, counts, rz=pbz, ry=tile, radius_rows=N,
                    y_z_extended=True)
                return jacobi7_halon_pallas(q, slabs, org, gsize, hot,
                                            cold, sph_r, steps=N,
                                            block_z=pbz, block_y=pby)

            return body, pair_body, N

        self._build_interior_resident_steps(
            make_body, segment_stride=N if pair_ok else 1)

    def _build_overlap_step(self) -> None:
        """Overlapped multi-device fused steps: ONE Pallas kernel per
        iteration issues the slab RDMA, computes the interior while the
        transfers fly, and fixes the faces once they land (the
        reference's polled-transport overlap, src/stencil.cu:1081-1118,
        as a single kernel; see ops/pallas_overlap.py)."""
        from ..ops.pallas_overlap import jacobi7_overlap_pallas
        from ..parallel import megastep as ms

        counts = mesh_dim(self.dd.mesh)
        hot, cold, sph_r = sphere_geometry(self.dd.size)

        def make_body(org):
            def body(q):
                return jacobi7_overlap_pallas(q, org, hot, cold, sph_r,
                                              counts)
            return body

        # the in-kernel RDMA moves the same single-row face slabs as a
        # radius-1 slab exchange (ops/pallas_overlap.py phase 2)
        self._slab_exchange_cfg = dict(rz=1, ry=1, radius_rows=1,
                                       y_z_extended=False, per_iter_div=1)
        # the formerly name-matched fused-segment decline is now
        # certificate-gated: the schedule certifier
        # (analysis/schedule.py) replays the kernel's semaphore
        # schedule k times and proves every launch hands the next a
        # quiescent semaphore file (drained send/recv slots, balanced
        # barrier, no unwaited-inbound reads). A replay_safe
        # certificate licenses chunk-of-1 fusion — k kernel launches
        # inside ONE compiled segment; anything else declines citing
        # the certificate's own reasons
        cert = _overlap_schedule_certificate(
            self.dd, self._dtype, hot, cold, sph_r, counts)
        self._schedule_certificate = cert
        gate = ms.certificate_gate(cert)
        if gate is None:
            self._build_interior_resident_steps(make_body)
        else:
            self._build_interior_resident_steps(
                make_body, segment_decline=gate,
                segment_decline_code=ms.DECLINE_UNCERTIFIED_SCHEDULE)

    def exchange_stats(self) -> dict:
        """Per-iteration exchange accounting for the BUILT compute
        path. The fused fast paths (wrap/halo/overlap) bypass
        ``dd.exchange()`` entirely, so the orchestrator's counters say
        nothing about exactly the paths that get benchmarked (the
        reference keeps per-iteration exchange stats on its one path,
        src/stencil.cu:1005-1008,1174-1181); this reports the wire
        bytes the built path moves per iteration (whole mesh, the
        ``exchange_bytes_total`` convention bench_exchange prints) and
        the exchange rounds per iteration (temporal blocking amortizes
        rounds below 1)."""
        from ..parallel.exchange import interior_slab_bytes

        counts = mesh_dim(self.dd.mesh)
        local = self.dd.local_size
        path = self.kernel_path
        if path == "wrap":
            return {"path": path, "bytes_per_iteration": 0,
                    "rounds_per_iteration": 0.0}
        cfg = getattr(self, "_slab_exchange_cfg", None)
        if cfg is not None and path in ("halo", "overlap"):
            per_shard = interior_slab_bytes(
                (local.z, local.y, local.x), counts, cfg["radius_rows"],
                jnp.dtype(self._dtype).itemsize, cfg["y_z_extended"])
            n = counts.flatten()
            return {"path": path,
                    "bytes_per_iteration":
                        per_shard * n / cfg["per_iter_div"],
                    "rounds_per_iteration": 1.0 / cfg["per_iter_div"]}
        d = self.dd.exchange_depths
        s = self.dd.exchange_every
        if d.x == d.y == d.z:
            rounds = 1.0 / s
        else:
            # asymmetric group: the deep exchange at sub-step 0 plus a
            # mid-group refresh at every k where some axis's cadence
            # divides k (parallel.temporal.refresh_axes)
            rounds = (1 + sum(1 for k in range(1, s)
                              if any(k % d[a] == 0
                                     for a in range(3)))) / s
        return {"path": path,
                "bytes_per_iteration":
                    float(self.dd.exchange_bytes_amortized_per_step()),
                "rounds_per_iteration": rounds}

    def measure_exchange_seconds(self, reps: int = 10) -> float:
        """Estimated exchange seconds per ITERATION of the built path,
        measured standalone per round config (the fused loops perform
        the exchange inside one XLA program where it cannot be timed
        separately) and scaled by the path's rounds-per-iteration —
        the same per-iteration convention as
        ``Astaroth.measure_exchange_seconds``. Returns 0.0 on the wrap
        path (no exchange exists)."""
        path = self.kernel_path
        if path == "wrap":
            return 0.0
        cfg = getattr(self, "_slab_exchange_cfg", None)
        if cfg is not None and path in ("halo", "overlap"):
            from ..parallel.exchange import measure_slab_exchange_seconds
            round_s = measure_slab_exchange_seconds(
                self.dd.mesh, self.dd.local_size, self._dtype,
                rz=cfg["rz"], ry=cfg["ry"],
                radius_rows=cfg["radius_rows"],
                y_z_extended=cfg["y_z_extended"], reps=reps)
            return round_s / cfg["per_iter_div"]
        import time

        from ..utils.timers import device_sync
        self.dd.exchange()
        device_sync(self.dd.curr["temp"])
        t0 = time.perf_counter()
        for _ in range(reps):
            self.dd.exchange()
        device_sync(self.dd.curr["temp"])
        # one (possibly deep) exchange feeds exchange_every iterations
        return (time.perf_counter() - t0) / reps / self.dd.exchange_every

    def step(self) -> None:
        """One iteration: exchange + 7-point update + sources."""
        self.dd.curr["temp"] = self._step(self.dd.curr["temp"])

    def run(self, iters: int) -> None:
        """``iters`` iterations in one XLA program (fori_loop — no
        per-iteration dispatch)."""
        self.dd.curr["temp"] = self._step_n(self.dd.curr["temp"],
                                            jnp.asarray(iters, jnp.int32))

    def block(self) -> None:
        from ..utils.timers import device_sync
        device_sync(self.dd.curr["temp"])

    def temperature(self) -> np.ndarray:
        """Global interior (z,y,x) on host."""
        return self.dd.interior_to_host("temp")

    # -- resilient run loop (stencil_tpu/resilience) -------------------
    def run_resilient(self, n_steps: int, policy=None,
                      ckpt_dir: Optional[str] = None, faults=None):
        """``n_steps`` iterations under the checkpoint-rollback
        recovery driver (:func:`stencil_tpu.resilience.run_resilient`):
        health sentinels every ``policy.check_every`` steps, integrity-
        checked checkpoints every ``policy.ckpt_every``, rollback +
        bounded retry on divergence, configuration degradation on
        repeat failure (the solver is rebuilt in place at the softer
        config), and clean SIGTERM preemption/resume via ``ckpt_dir``.
        Returns the :class:`~stencil_tpu.resilience.ResilienceReport`."""
        from ..resilience.driver import run_resilient

        def rebuild(cfg):
            new = Jacobi3D(
                self.dd.size.x, self.dd.size.y, self.dd.size.z,
                mesh_shape=tuple(self.dd.placement.dim()),
                dtype=self._dtype, devices=self.dd._devices,
                methods=cfg.method, kernel=self._kernel,
                overlap=self._overlap,
                exchange_every=cfg.exchange_every,
                boundary=self.dd.boundary,
                placement=self.dd.strategy,
                output_prefix=self.dd._output_prefix,
                **_dcn_request_kwargs(self.dd))
            # adopt the rebuilt engine in place so the caller's handle
            # (and the driver's fields_fn closure) stay valid; the
            # fused-segment factory is rebuilt with it (third element)
            # so the degraded configuration's megastep serves from here
            self.__dict__.update(new.__dict__)
            return self.dd, self.step, self.make_segment

        return run_resilient(self.dd, self.step, n_steps, policy=policy,
                             ckpt_dir=ckpt_dir, faults=faults,
                             rebuild=rebuild,
                             fields_fn=lambda: self.dd.curr,
                             # always passed: a path with no builder
                             # returns a reason-carrying decline the
                             # driver reports (never a silent stepwise
                             # fallback)
                             make_segment=self.make_segment,
                             perf_entry="jacobi")


def ripple_field(shape_zyx: Tuple[int, int, int], dtype) -> np.ndarray:
    """The deterministic field ``((13z + 7y + 3x) mod 17) / 17`` that
    oracle checks seed: a uniform field cannot expose an exchange that
    reads the wrong neighbor."""
    gz, gy, gx = shape_zyx
    iz = np.arange(gz)[:, None, None]
    iy = np.arange(gy)[None, :, None]
    ix = np.arange(gx)[None, None, :]
    return (((iz * 13 + iy * 7 + ix * 3) % 17) / 17.0).astype(dtype)


def dense_reference_step(temp: np.ndarray, hot_c: Tuple[int, int, int],
                         cold_c: Tuple[int, int, int], sph_r: int
                         ) -> np.ndarray:
    """Single-device dense oracle of one jacobi step on a (z,y,x) global
    array with periodic wrap — the correctness reference for the
    distributed solver (BASELINE.json config 1)."""
    out = np.zeros_like(temp)
    for axis, dim in ((0, 0), (1, 1), (2, 2)):
        out += np.roll(temp, 1, axis=axis) + np.roll(temp, -1, axis=axis)
    out /= 6.0
    gz, gy, gx = np.meshgrid(np.arange(temp.shape[0]),
                             np.arange(temp.shape[1]),
                             np.arange(temp.shape[2]), indexing="ij",
                             sparse=True)
    hx, hy, hz = hot_c
    cx, cy, cz = cold_c
    d2h = (gx - hx) ** 2 + (gy - hy) ** 2 + (gz - hz) ** 2
    d2c = (gx - cx) ** 2 + (gy - cy) ** 2 + (gz - cz) ** 2
    out = np.where(d2h <= sph_r * sph_r, HOT_TEMP, out)
    out = np.where(d2c <= sph_r * sph_r, COLD_TEMP, out)
    return out.astype(temp.dtype)
