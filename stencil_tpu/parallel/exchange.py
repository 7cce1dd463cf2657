"""The halo-exchange engine: per-axis neighbor shifts over the mesh.

This is the TPU-native replacement for the reference's entire transport
stack (reference: include/stencil/tx_cuda.cuh, tx_colocated.cuh,
tx_ipc.hpp, packer.cuh and the exchange orchestration in
src/stencil.cu:1002-1186). Where the reference plans 26 point-to-point
messages per subdomain and routes each over the fastest of 4 transports
(same-GPU kernel / cudaMemcpyPeer / IPC / MPI), the TPU design performs
**three sequential axis sweeps** of ``lax.ppermute`` shifts inside one
``shard_map``-ped XLA program:

* sweep x: exchange +-x face slabs spanning the full (y, z) allocation;
* sweep y: slabs span full (x, z) — x halos are now valid, so xy edge
  data propagates automatically;
* sweep z: slabs span full (x, y) — fills all z faces, xz/yz edges and
  corners.

26 directions collapse into at most 6 shifts, and edge/corner data
rides along for free (SURVEY.md section 7 step 3). Per-direction radii
are honored: the slab widths on each side of axis ``a`` are the *face*
radii (allocation geometry, reference local_domain.cuh raw_size), which
is exactly what the reference's messages carry (halo_extent uses face
radii — local_domain.cuh:212-222); zero-radius sides skip the shift.

Everything here operates on one shard's padded (z,y,x)-ordered block
and must run inside ``shard_map`` (or on a 1-device axis, where the
periodic neighbor is the shard itself and the shift degenerates to a
local slab copy — the analog of the reference's same-GPU
PeerAccessSender, tx_cuda.cuh:41-113).
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax
import numpy as np
from jax import lax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..geometry import Dim3, Radius
from ..telemetry.spans import EXCHANGE, layer_scope
from .methods import Method, pick_method

# grid axis index -> array dimension of a (z,y,x)-ordered field
AXIS_TO_DIM = {0: 2, 1: 1, 2: 0}
AXIS_NAME = {0: "x", 1: "y", 2: "z"}

#: halo WIRE formats: what a slab is converted to at the send boundary
#: (TEMPI's canonical-datatype pack layer, arXiv:2012.14363). "f32" is
#: the identity path — full storage precision on the wire; "bf16"
#: narrows float32 slabs to bfloat16 for the ppermute and widens on
#: arrival, so halo math runs unchanged at storage precision while
#: wire bytes exactly halve; "e4m3"/"e5m2" narrow to the fp8 dtypes
#: (quarter bytes — certificate-gated like bf16, with the coarser
#: ``max_rel_error_bound`` from their 3-/2-bit mantissas). Narrower
#: storage dtypes are never re-narrowed, and non-float lanes always
#: ride at full width.
WIRE_FORMATS = ("f32", "bf16", "e4m3", "e5m2")

#: wire format -> numpy dtype NAME of the on-wire element type for a
#: float32 lane — the single naming source the precision certifier
#: (analysis/precision.py) and the cost model both consume.
WIRE_DTYPE_NAMES = {"f32": "float32", "bf16": "bfloat16",
                    "e4m3": "float8_e4m3fn", "e5m2": "float8_e5m2"}

#: on-wire byte width of a 4-byte float32 element per wire format
_WIRE_F32_BYTES = {"f32": 4, "bf16": 2, "e4m3": 1, "e5m2": 1}


def normalize_wire_format(wire_format) -> Dict[str, str]:
    """Canonical per-axis wire-format map ``{"x"|"y"|"z": fmt}``.

    Accepts ``None`` (full precision), a single format string applied
    to every mesh axis, or a per-axis dict (missing axes default to
    "f32") — the per-link declaration surface: bf16 on the DCN axis,
    f32 on ICI."""
    if wire_format is None:
        return {"x": "f32", "y": "f32", "z": "f32"}
    if isinstance(wire_format, str):
        if wire_format not in WIRE_FORMATS:
            raise ValueError(f"unknown wire format {wire_format!r}; "
                             f"expected one of {WIRE_FORMATS}")
        return {"x": wire_format, "y": wire_format, "z": wire_format}
    out = {"x": "f32", "y": "f32", "z": "f32"}
    for k, v in dict(wire_format).items():
        if k not in out:
            raise ValueError(f"unknown mesh axis {k!r} in wire_format")
        if v not in WIRE_FORMATS:
            raise ValueError(f"unknown wire format {v!r} for axis "
                             f"{k!r}; expected one of {WIRE_FORMATS}")
        out[k] = v
    return out


def wire_dtype(dtype, fmt: str):
    """The on-wire dtype of a slab stored as ``dtype`` under wire
    format ``fmt`` — only float32 narrows (to the format's dtype,
    ``WIRE_DTYPE_NAMES``); everything else ships at storage width."""
    if fmt != "f32" and np.dtype(dtype) == np.dtype(np.float32):
        return {"bf16": jnp.bfloat16, "e4m3": jnp.float8_e4m3fn,
                "e5m2": jnp.float8_e5m2}[fmt]
    return dtype


def wire_elem_size(elem_size: int, fmt: str) -> int:
    """Byte width of one element on the wire (the cost-model twin of
    :func:`wire_dtype`): a 4-byte element ships as 2 under "bf16" and
    as 1 under the fp8 formats."""
    if int(elem_size) == 4:
        return _WIRE_F32_BYTES[fmt]
    return int(elem_size)


def _to_wire(slab, fmt: str):
    wd = wire_dtype(slab.dtype, fmt)
    return slab if wd == slab.dtype else slab.astype(wd)


def _from_wire(slab, dtype):
    return slab if slab.dtype == dtype else slab.astype(dtype)


def _axis_size(axis_name: str) -> int:
    """Size of a mesh axis from inside shard_map."""
    return lax.axis_size(axis_name)


def _shift_from_plus(block, axis_name: str, n: int):
    """Bring data from the +axis neighbor (periodic): device i receives
    from device i+1."""
    if n == 1:
        return block
    return lax.ppermute(block, axis_name, [((i + 1) % n, i) for i in range(n)])


def _shift_from_minus(block, axis_name: str, n: int):
    """Bring data from the -axis neighbor (periodic): device i receives
    from device i-1."""
    if n == 1:
        return block
    return lax.ppermute(block, axis_name, [(i, (i + 1) % n) for i in range(n)])


def shard_interior_len(axis: int, capacity: int, rem: Dim3):
    """This shard's actual interior extent along ``axis``: the +-1
    remainder rule (reference: partition.hpp:55-69) as a traced value —
    the first ``rem`` shards hold ``capacity`` points, the rest one
    fewer. Static ``capacity`` when the axis divides evenly."""
    r = rem[axis]
    if r == 0:
        return capacity
    i = lax.axis_index(AXIS_NAME[axis])
    return jnp.int32(capacity) - (i >= jnp.int32(r)).astype(jnp.int32)


def shard_origin(local: Dim3, rem: Dim3) -> Tuple:
    """Traced (ox, oy, oz) global origin of this shard's interior
    (reference: partition.hpp:71-86 _remainder_origin), valid inside
    shard_map. ``local`` is the per-shard capacity (ceil sizes)."""
    out = []
    for a in range(3):
        i = lax.axis_index(AXIS_NAME[a])
        o = i * jnp.int32(local[a])
        if rem[a] != 0:
            o = o - jnp.maximum(i - jnp.int32(rem[a]), jnp.int32(0))
        out.append(o)
    return tuple(out)


def _edge_masked(recv, side: int, axis_name: str, n_dev: int):
    """Non-periodic boundary rule: the mesh-edge shard's halo on the
    open side holds ZEROS (the zero-Dirichlet exterior), not the
    wrapped-around neighbor's data the periodic ppermute ring delivered.
    ``side`` is +1 for the hi-side halo (zeroed on the last shard) and
    -1 for the lo-side halo (zeroed on shard 0). A 1-device axis has no
    interior boundary at all: the whole halo is exterior, so it zeroes
    unconditionally."""
    if n_dev == 1:
        return jnp.zeros_like(recv)
    i = lax.axis_index(axis_name)
    edge = (i == n_dev - 1) if side == 1 else (i == 0)
    return jnp.where(edge, jnp.zeros_like(recv), recv)


def _box_starts(spans, Ls):
    """Per-array-dim (z,y,x) start indices of a DirectionPlan box —
    static ints except the two ``plus_L`` placements, which add the
    traced interior length of their grid axis. When any start is
    traced (uneven shards), ALL are cast to int32: dynamic_slice
    demands one index dtype, and an x64-enabled session would promote
    the static Python ints to int64 otherwise."""
    starts = []
    for d in range(3):
        s = spans[2 - d]  # grid axis of array dim d
        starts.append(s.base + Ls[2 - d] if s.plus_L else s.base)
    if not all(isinstance(st, (int, np.integer)) for st in starts):
        starts = [jnp.asarray(st, jnp.int32) for st in starts]
    return tuple(starts)


def _box_take(arr, spans, Ls):
    sizes = tuple(spans[2 - d].size for d in range(3))
    return lax.dynamic_slice(arr, _box_starts(spans, Ls), sizes)


def _box_put(arr, box, spans, Ls):
    return lax.dynamic_update_slice(arr, box, _box_starts(spans, Ls))


def _shard_interiors(arr, alloc_r: Radius) -> Tuple[int, int, int]:
    """Per-grid-axis interior capacity of one padded shard block."""
    return tuple(arr.shape[AXIS_TO_DIM[a]]
                 - alloc_r.face(a, -1) - alloc_r.face(a, 1)
                 for a in range(3))


@layer_scope(EXCHANGE)
def exchange_shard(arr: jnp.ndarray, radius: Radius,
                   mesh_counts: Dim3,
                   axis_order: Tuple[int, ...] = (0, 1, 2),
                   rem: Dim3 = Dim3(0, 0, 0),
                   alloc_radius: "Radius | None" = None,
                   nonperiodic: bool = False,
                   wire_format=None,
                   wire_layout=None) -> jnp.ndarray:
    """Fill all halo regions of one padded shard via sequential axis
    sweeps. Must be traced inside ``shard_map`` over mesh axes
    ('x','y','z') when the corresponding mesh_counts entry is > 1.

    ``arr``: padded (z,y,x) block; interior *capacity* along grid axis a
    is ``arr.shape[AXIS_TO_DIM[a]] - p_lo - p_hi`` where the allocation
    pads come from ``alloc_radius`` (default: ``radius``).
    ``mesh_counts``: subdomain count along each grid axis.
    ``rem``: per-axis remainder counts for uneven (+-1) subdomains
    (reference: partition.hpp:55-69). Shards allocate to the capacity;
    a short shard's halo is placed immediately after its actual
    interior (dynamic position), keeping interior+halo contiguous so
    stencil reads stay static slices. The slack row at the top of a
    short shard's allocation is dead space.

    ``alloc_radius``: when the allocation is padded deeper than this
    exchange's wire depth (temporal blocking: the buffer carries
    ``s x r`` pads but a tail step only refreshes the innermost ``r``
    ring), pass the allocation's Radius here; the slabs then ship
    ``radius`` rows placed immediately around the interior. Wire depth
    must not exceed the allocation pads on any face.
    ``nonperiodic``: zero-fill halos across the open global boundary
    (``topology.Boundary.NONE`` — zero-Dirichlet exterior).
    ``wire_format``: per-axis halo wire format (see
    :func:`normalize_wire_format`) — a narrowing axis converts the send
    slab at the wire boundary, one ppermute later widens it back to the
    storage dtype on arrival; halo math is unchanged. Single-device
    axes are local copies and always stay at full precision.
    ``wire_layout``: "slab" (default — full-allocation cross-sections)
    or "irredundant" (each wire-halo cell ships exactly once; see
    :mod:`.packing`). Same collective bill, smaller payload; the live
    window (interior + wire-radius shell) is bitwise identical.
    """
    alloc_r = alloc_radius if alloc_radius is not None else radius
    wf = normalize_wire_format(wire_format)
    from .packing import normalize_wire_layout, plan_sweep
    if normalize_wire_layout(wire_layout) == "irredundant":
        interiors = _shard_interiors(arr, alloc_r)
        plans = plan_sweep(radius, alloc_r, interiors, tuple(axis_order))
        Ls = [shard_interior_len(a, interiors[a], rem) for a in range(3)]
        for a in axis_order:
            if radius.wire_rows(a) == 0:
                continue
            assert (alloc_r.face(a, -1) >= radius.face(a, -1)
                    and alloc_r.face(a, 1) >= radius.face(a, 1)), \
                (f"axis {a}: wire depth exceeds allocation pads")
            name = AXIS_NAME[a]
            n_dev = mesh_counts[a]
            narrow = n_dev > 1 and wf[name] != "f32"
            for side, shift in ((1, _shift_from_plus),
                                (-1, _shift_from_minus)):
                plan = plans.get((a, side))
                if plan is None:
                    continue
                src = _box_take(arr, plan.src, Ls)
                if narrow:
                    src = _to_wire(src, wf[name])
                recv = _from_wire(shift(src, name, n_dev), arr.dtype)
                if nonperiodic:
                    recv = _edge_masked(recv, side, name, n_dev)
                arr = _box_put(arr, recv, plan.dst, Ls)
        return arr
    for a in axis_order:
        r_lo = radius.face(a, -1)
        r_hi = radius.face(a, 1)
        if r_lo == 0 and r_hi == 0:
            continue
        p_lo = alloc_r.face(a, -1)
        p_hi = alloc_r.face(a, 1)
        assert p_lo >= r_lo and p_hi >= r_hi, \
            (f"axis {a}: wire depth ({r_lo},{r_hi}) exceeds allocation "
             f"pads ({p_lo},{p_hi})")
        dim = AXIS_TO_DIM[a]
        name = AXIS_NAME[a]
        n_dev = mesh_counts[a]
        alloc = arr.shape[dim]
        interior = alloc - p_lo - p_hi
        # actual interior length of this shard (traced when uneven)
        L = shard_interior_len(a, interior, rem)

        # fill the hi-side halo [p_lo+L, p_lo+L+r_hi): data lives at the
        # +a neighbor's interior lo edge [p_lo, p_lo + r_hi)
        narrow = n_dev > 1 and wf[name] != "f32"
        if r_hi > 0:
            src = lax.slice_in_dim(arr, p_lo, p_lo + r_hi, axis=dim)
            if narrow:
                src = _to_wire(src, wf[name])
            recv = _from_wire(_shift_from_plus(src, name, n_dev),
                              arr.dtype)
            if nonperiodic:
                recv = _edge_masked(recv, 1, name, n_dev)
            arr = lax.dynamic_update_slice_in_dim(arr, recv, p_lo + L,
                                                  axis=dim)
        # fill the lo-side halo [p_lo-r_lo, p_lo): data lives at the -a
        # neighbor's interior hi edge [p_lo + L - r_lo, p_lo + L)
        if r_lo > 0:
            src = lax.dynamic_slice_in_dim(arr, p_lo + L - r_lo, r_lo,
                                           axis=dim)
            if narrow:
                src = _to_wire(src, wf[name])
            recv = _from_wire(_shift_from_minus(src, name, n_dev),
                              arr.dtype)
            if nonperiodic:
                recv = _edge_masked(recv, -1, name, n_dev)
            arr = lax.dynamic_update_slice_in_dim(arr, recv, p_lo - r_lo,
                                                  axis=dim)
    return arr


@layer_scope(EXCHANGE)
def accumulate_shard(arr: jnp.ndarray, radius: Radius,
                     mesh_counts: Dim3,
                     axis_order: Tuple[int, ...] = (2, 1, 0),
                     rem: Dim3 = Dim3(0, 0, 0),
                     nonperiodic: bool = False) -> jnp.ndarray:
    """The ADJOINT of :func:`exchange_shard`: fold halo-pad
    contributions back into the interiors that own them (scatter-add
    deposition — a PIC particle near a shard edge deposits charge into
    this shard's pad cells, which belong to the neighbor's interior).

    Per axis, each pad slab is shipped to the neighboring shard whose
    interior it overlays and ADDED into that interior's edge rows, then
    zeroed locally. Axis order is the REVERSE of the exchange sweep
    (z, y, x by default): a slab spans the full allocation in the other
    dims, so edge/corner contributions ride into the other axes' pads
    and are folded by the subsequent sweeps — the transpose of the
    sequential-sweep corner rule. After all sweeps the pads are zero
    and every interior cell holds the full periodic sum.

    ``rem``: uneven (+-1) subdomains — a short shard's hi pad sits at
    its ACTUAL interior end (dynamic position), same placement rule as
    :func:`exchange_shard`. ``nonperiodic``: contributions crossing the
    open global boundary are discarded (the zero-Dirichlet exterior
    absorbs them) instead of wrapping. Must be traced inside
    ``shard_map``; lowers to the same collective-permute-only bill as
    the forward exchange (2 ppermutes per active axis), with identical
    wire bytes — ``exchanged_bytes_per_sweep`` prices both."""
    for a in axis_order:
        r_lo = radius.face(a, -1)
        r_hi = radius.face(a, 1)
        if r_lo == 0 and r_hi == 0:
            continue
        dim = AXIS_TO_DIM[a]
        name = AXIS_NAME[a]
        n_dev = mesh_counts[a]
        alloc = arr.shape[dim]
        interior = alloc - r_lo - r_hi
        L = shard_interior_len(a, interior, rem)

        # hi pad [p_lo+L, p_lo+L+r_hi) overlays the +a neighbor's
        # interior lo rows [p_lo, p_lo+r_hi): ship it +1 and add
        if r_hi > 0:
            src = lax.dynamic_slice_in_dim(arr, r_lo + L, r_hi, axis=dim)
            recv = _shift_from_minus(src, name, n_dev)
            if nonperiodic:
                # shard 0 received the wrapped last shard's pad: the
                # open boundary absorbs it
                recv = _edge_masked(recv, -1, name, n_dev)
            cur = lax.slice_in_dim(arr, r_lo, r_lo + r_hi, axis=dim)
            arr = lax.dynamic_update_slice_in_dim(arr, cur + recv, r_lo,
                                                  axis=dim)
            arr = lax.dynamic_update_slice_in_dim(
                arr, jnp.zeros_like(src), r_lo + L, axis=dim)
        # lo pad [p_lo-r_lo, p_lo) overlays the -a neighbor's interior
        # hi rows [p_lo+L-r_lo, p_lo+L): ship it -1 and add
        if r_lo > 0:
            src = lax.slice_in_dim(arr, 0, r_lo, axis=dim)
            recv = _shift_from_plus(src, name, n_dev)
            if nonperiodic:
                recv = _edge_masked(recv, 1, name, n_dev)
            cur = lax.dynamic_slice_in_dim(arr, r_lo + L - r_lo, r_lo,
                                           axis=dim)
            arr = lax.dynamic_update_slice_in_dim(arr, cur + recv,
                                                  r_lo + L - r_lo,
                                                  axis=dim)
            arr = lax.dynamic_update_slice_in_dim(
                arr, jnp.zeros_like(src), 0, axis=dim)
    return arr


@layer_scope(EXCHANGE)
def exchange_interior_slabs(p: jnp.ndarray, mesh_counts: Dim3,
                            rz: int, ry: int, radius_rows: int = 0,
                            y_z_extended: bool = False,
                            rem: Dim3 = Dim3(0, 0, 0),
                            z_only: bool = False
                            ) -> Dict[str, jnp.ndarray]:
    """Exchange halo SLABS of one interior-resident (unpadded) shard —
    the data plane of the fused halo kernels (ops/pallas_halo.py).

    Unlike ``exchange_shard`` (which fills halo regions of a padded
    allocation in place), this returns the four slab arrays the halo
    kernels consume, leaving the shard untouched:

    * ``zlo`` (rz, Y, X): the z-minus neighbor's TOP rows, right-
      aligned (the row adjacent to this shard is ``zlo[-1]``);
    * ``zhi`` (rz, Y, X): the z-plus neighbor's BOTTOM rows, left-
      aligned (adjacent row is ``zhi[0]``);
    * ``ylo`` / ``yhi``: the y-minus (y-plus) neighbor's LAST (FIRST)
      rows, right-/left-aligned in an ry-row buffer — shape
      (Z, ry, X), or (Z + 2*rz, ry, X) when ``y_z_extended`` (the y
      sources then span the z halo too, so yz edge/corner data
      propagates — the sequential-sweep corner rule, reference
      src/stencil.cu:331-464 collapsed per SURVEY.md §7).

    ``rz``/``ry`` are the buffer row counts the kernels' block specs
    want (block-aligned); ``radius_rows`` (default ``min(rz, ry)``)
    is how many rows actually cross the wire — only the stencil radius
    is needed, the rest of each buffer is zero filler. On a 1-device
    mesh axis the shift degenerates to the shard's own wrapped edge
    (periodic). x must not be mesh-sharded (the halo kernels wrap x
    in-kernel). Must be traced inside ``shard_map``.

    ``rem``: uneven (+-1) subdomain counts. Shards are capacity-sized
    with a dead tail row/column on short shards, so the hi-edge sends
    come from the shard's ACTUAL last interior rows (dynamic slice at
    ``shard_interior_len - r``, the partition.hpp:55-69 rule); lo-edge
    sends start at 0 regardless. Not supported with ``y_z_extended``.

    ``z_only``: return ``zlo``/``zhi`` alone, for a caller that wraps
    y in-core (the MHD halo kernels' y-local window); needs a y mesh
    axis of one device, where the y slabs would cross no wire.
    """
    Z = p.shape[0]
    Y = p.shape[1]
    X = p.shape[2]
    nz = mesh_counts.z
    ny = mesh_counts.y
    r = radius_rows or min(rz, ry)
    assert r <= rz and r <= ry, (r, rz, ry)
    uneven = rem != Dim3(0, 0, 0)
    assert not (uneven and y_z_extended), \
        "uneven shards unsupported with z-extended y slabs"
    assert not z_only or ny == 1, "z-only slabs need an unsharded y axis"
    dt = p.dtype

    def zfill(n, yext):
        return jnp.zeros((n, yext, X), dt)

    def yfill(zext, n):
        return jnp.zeros((zext, n, X), dt)

    # r-row wire transfers (reference sends exactly the halo bytes,
    # src/packer.cu:78-82; buffers are padded to block-aligned rows).
    # Hi-edge sends slice at the actual interior end (traced when
    # uneven; shard_interior_len collapses to the static Z/Y otherwise).
    Lz = shard_interior_len(2, Z, rem)
    Ly = shard_interior_len(1, Y, rem)
    if uneven and rem[2] != 0:
        ztop = lax.dynamic_slice_in_dim(p, Lz - r, r, axis=0)
    else:
        ztop = lax.slice_in_dim(p, Z - r, Z, axis=0)
    zlo_r = _shift_from_minus(ztop, "z", nz)
    zhi_r = _shift_from_plus(lax.slice_in_dim(p, 0, r, axis=0), "z", nz)

    def zembed(recv, align_hi: bool):
        if rz == r:
            return recv
        pad = zfill(rz - r, Y)
        return jnp.concatenate([pad, recv] if align_hi else [recv, pad],
                               axis=0)

    if z_only:
        return {"zlo": zembed(zlo_r, True), "zhi": zembed(zhi_r, False)}
    if y_z_extended:
        # this shard's y-edge columns spanning z in [-r, Z+r): own
        # interior plus the just-received z slabs (corner ride-along)
        def ysrc_hi():
            return jnp.concatenate(
                [zlo_r[:, Y - r:Y], p[:, Y - r:Y], zhi_r[:, Y - r:Y]],
                axis=0)

        def ysrc_lo():
            return jnp.concatenate(
                [zlo_r[:, 0:r], p[:, 0:r], zhi_r[:, 0:r]], axis=0)
        zext = Z + 2 * rz
        zoff = rz - r
    else:
        def ysrc_hi():
            if uneven and rem[1] != 0:
                return lax.dynamic_slice_in_dim(p, Ly - r, r, axis=1)
            return p[:, Y - r:Y]

        def ysrc_lo():
            return p[:, 0:r]
        zext = Z
        zoff = 0
    ylo_r = _shift_from_minus(ysrc_hi(), "y", ny)
    yhi_r = _shift_from_plus(ysrc_lo(), "y", ny)
    zlo = zembed(zlo_r, True)
    zhi = zembed(zhi_r, False)

    def yembed(recv, align_hi: bool):
        out = recv
        if ry != r:
            pad = yfill(out.shape[0], ry - r)
            out = (jnp.concatenate([pad, out], axis=1) if align_hi
                   else jnp.concatenate([out, pad], axis=1))
        if zoff:
            zpad = jnp.zeros((zoff, ry, X), dt)
            out = jnp.concatenate([zpad, out, zpad], axis=0)
        return out

    return {"zlo": zlo, "zhi": zhi,
            "ylo": yembed(ylo_r, True), "yhi": yembed(yhi_r, False)}


@layer_scope(EXCHANGE)
def exchange_shard_packed(arrs: Dict[str, jnp.ndarray], radius: Radius,
                          mesh_counts: Dim3,
                          axis_order: Tuple[int, ...] = (0, 1, 2),
                          rem: Dim3 = Dim3(0, 0, 0),
                          alloc_radius: "Radius | None" = None,
                          nonperiodic: bool = False,
                          wire_format=None,
                          wire_layout=None
                          ) -> Dict[str, jnp.ndarray]:
    """Multi-quantity exchange with per-direction packing: all
    quantities' slabs for one axis-direction are flattened and
    concatenated into a single buffer, moved with ONE ppermute, then
    unpacked — the analog of DevicePacker/DeviceUnpacker packing all
    quantities per message (reference: src/packer.cu:10-44, 69-82).

    All quantities are bitcast to a common byte layout via flattening in
    float32/raw dtype groups; quantities of differing dtypes are packed
    in separate groups (alignment rule analog, src/packer.cu:76-82).

    ``rem``: uneven (+-1) subdomain counts (reference:
    partition.hpp:55-69) — same placement rule as ``exchange_shard``:
    a short shard's hi-edge send comes from its ACTUAL last interior
    rows (dynamic slice at the traced interior length) and its hi-side
    halo lands immediately after the actual interior; packed buffer
    shapes stay static (capacity-sized slabs), so one program serves
    every shard.

    ``alloc_radius``/``nonperiodic``/``wire_format``/``wire_layout``:
    same contract as :func:`exchange_shard` (deep-carry allocations for
    temporal blocking; zero-Dirichlet exterior for ``Boundary.NONE``;
    per-axis halo wire narrowing — here the whole packed
    per-dtype-group buffer narrows once before its single ppermute and
    widens once on arrival; "irredundant" packs each quantity's
    minimal box instead of its fat slab, see :mod:`.packing`).
    """
    from .packing import normalize_wire_layout, plan_direction

    alloc_r = alloc_radius if alloc_radius is not None else radius
    wf = normalize_wire_format(wire_format)
    irredundant = normalize_wire_layout(wire_layout) == "irredundant"
    names = sorted(arrs.keys())  # sorted so both endpoints agree on
    # layout (reference sorts messages by size, src/packer.cu:69,182-183)
    out = {k: v for k, v in arrs.items()}
    for a in axis_order:
        r_lo = radius.face(a, -1)
        r_hi = radius.face(a, 1)
        if r_lo == 0 and r_hi == 0:
            continue
        p_lo = alloc_r.face(a, -1)
        p_hi = alloc_r.face(a, 1)
        assert p_lo >= r_lo and p_hi >= r_hi, \
            (f"axis {a}: wire depth ({r_lo},{r_hi}) exceeds allocation "
             f"pads ({p_lo},{p_hi})")
        dim = AXIS_TO_DIM[a]
        name = AXIS_NAME[a]
        n_dev = mesh_counts[a]
        uneven_axis = rem[a] != 0

        for side, r_fill in ((1, r_hi), (-1, r_lo)):
            if r_fill == 0:
                continue
            # group quantities by dtype so concatenation is well-typed
            groups: Dict[np.dtype, List[str]] = {}
            for q in names:
                groups.setdefault(out[q].dtype, []).append(q)
            for dt, qs in groups.items():
                slabs = []
                shapes = []
                unpacks = []  # irredundant: (DirectionPlan, Ls) per q
                for q in qs:
                    arr = out[q]
                    if irredundant:
                        interiors = _shard_interiors(arr, alloc_r)
                        plan = plan_direction(a, side, radius, alloc_r,
                                              tuple(axis_order), interiors)
                        Ls = [shard_interior_len(b, interiors[b], rem)
                              for b in range(3)]
                        src = _box_take(arr, plan.src, Ls)
                        unpacks.append((plan, Ls))
                        shapes.append(src.shape)
                        slabs.append(src.reshape(-1))
                        continue
                    alloc = arr.shape[dim]
                    interior = alloc - p_lo - p_hi
                    L = shard_interior_len(a, interior, rem)
                    if side == 1:
                        src = lax.slice_in_dim(arr, p_lo, p_lo + r_hi, axis=dim)
                    elif uneven_axis:
                        # hi edge of a short shard sits at its actual
                        # interior end [p_lo + L - r_lo, p_lo + L)
                        src = lax.dynamic_slice_in_dim(arr, p_lo + L - r_lo,
                                                       r_lo, axis=dim)
                    else:
                        src = lax.slice_in_dim(arr, p_lo + interior - r_lo,
                                               p_lo + interior, axis=dim)
                    shapes.append(src.shape)
                    slabs.append(src.reshape(-1))
                packed = jnp.concatenate(slabs) if len(slabs) > 1 else slabs[0]
                if n_dev > 1 and wf[name] != "f32":
                    packed = _to_wire(packed, wf[name])
                moved = (_shift_from_plus(packed, name, n_dev) if side == 1
                         else _shift_from_minus(packed, name, n_dev))
                moved = _from_wire(moved, dt)
                if nonperiodic:
                    moved = _edge_masked(moved, side, name, n_dev)
                # unpack
                off = 0
                for i, (q, shp) in enumerate(zip(qs, shapes)):
                    cnt = int(np.prod(shp))
                    recv = lax.dynamic_slice_in_dim(moved, off, cnt, axis=0
                                                    ).reshape(shp)
                    off += cnt
                    arr = out[q]
                    if irredundant:
                        plan, Ls = unpacks[i]
                        out[q] = _box_put(arr, recv, plan.dst, Ls)
                        continue
                    alloc = arr.shape[dim]
                    interior = alloc - p_lo - p_hi
                    if side == 1:
                        L = shard_interior_len(a, interior, rem)
                        start = p_lo + L
                    else:
                        start = p_lo - r_lo
                    out[q] = lax.dynamic_update_slice_in_dim(arr, recv, start,
                                                             axis=dim)
    return out


@layer_scope(EXCHANGE)
def exchange_shard_allgather(arr: jnp.ndarray, radius: Radius,
                             mesh_counts: Dim3,
                             axis_order: Tuple[int, ...] = (0, 1, 2)
                             ) -> jnp.ndarray:
    """Control strategy: per axis, all_gather the boundary slabs and
    slice out the two needed neighbors. Strictly more bytes on the wire
    than ppermute — exists for method A/B sweeps like the reference's
    bench_alltoallv (bin/bench_alltoallv.cu)."""
    for a in axis_order:
        r_lo = radius.face(a, -1)
        r_hi = radius.face(a, 1)
        if r_lo == 0 and r_hi == 0:
            continue
        dim = AXIS_TO_DIM[a]
        name = AXIS_NAME[a]
        n_dev = mesh_counts[a]
        alloc = arr.shape[dim]
        interior = alloc - r_lo - r_hi
        if n_dev == 1:
            arr = exchange_shard(arr, _single_axis_radius(radius, a), mesh_counts,
                                 axis_order=(a,))
            continue
        idx = lax.axis_index(name)
        if r_hi > 0:
            src = lax.slice_in_dim(arr, r_lo, r_lo + r_hi, axis=dim)
            gath = lax.all_gather(src, name, axis=0)  # (n_dev, ...slab)
            recv = gath[(idx + 1) % n_dev]
            arr = lax.dynamic_update_slice_in_dim(arr, recv, r_lo + interior,
                                                  axis=dim)
        if r_lo > 0:
            src = lax.slice_in_dim(arr, interior, r_lo + interior, axis=dim)
            gath = lax.all_gather(src, name, axis=0)
            recv = gath[(idx - 1) % n_dev]
            arr = lax.dynamic_update_slice_in_dim(arr, recv, 0, axis=dim)
    return arr


def _single_axis_radius(radius: Radius, axis: int) -> Radius:
    r = Radius.constant(0)
    for side in (-1, 1):
        d = [0, 0, 0]
        d[axis] = side
        r.set_dir(tuple(d), radius.face(axis, side))
    return r


@layer_scope(EXCHANGE)
def dispatch_exchange(fields: Dict[str, jnp.ndarray], radius: Radius,
                      mesh_counts: Dim3, method: Method,
                      axis_order: Tuple[int, ...] = (0, 1, 2),
                      rem: Dim3 = Dim3(0, 0, 0),
                      alloc_radius: "Radius | None" = None,
                      nonperiodic: bool = False,
                      wire_format=None,
                      wire_layout=None) -> Dict[str, jnp.ndarray]:
    """Route a multi-quantity shard exchange to the selected strategy —
    the single dispatch point shared by the orchestrator and the fused
    model steps (the Method-routing analog of src/stencil.cu:371-458).

    ``alloc_radius``/``nonperiodic``/``wire_format``/``wire_layout``
    (ppermute methods only): deep-carry allocations for temporal
    blocking, the zero-Dirichlet exterior of ``Boundary.NONE``,
    per-axis halo wire narrowing, and the irredundant wire layout —
    see :func:`exchange_shard`."""
    from .packing import normalize_wire_layout

    uneven = rem != Dim3(0, 0, 0)
    wf = normalize_wire_format(wire_format)
    narrows = any(v != "f32" for v in wf.values())
    layout = normalize_wire_layout(wire_layout)
    if uneven and method not in (Method.PpermuteSlab,
                                 Method.PpermutePacked):
        raise NotImplementedError(
            f"uneven (+-1 remainder) subdomains are only supported by "
            f"the PpermuteSlab and PpermutePacked methods, not {method}")
    if ((alloc_radius is not None or nonperiodic or narrows
         or layout != "slab")
            and method not in (Method.PpermuteSlab, Method.PpermutePacked)):
        raise NotImplementedError(
            f"deep-carry allocations, non-periodic boundaries, narrow "
            f"wire formats, and non-slab wire layouts are only "
            f"supported by the PpermuteSlab and PpermutePacked "
            f"methods, not {method}")
    if method == Method.PallasDMA:
        from .pallas_exchange import exchange_shard_pallas
        return {k: exchange_shard_pallas(v, radius, mesh_counts, axis_order)
                for k, v in fields.items()}
    if method == Method.PpermutePacked:
        return exchange_shard_packed(fields, radius, mesh_counts,
                                     axis_order, rem, alloc_radius,
                                     nonperiodic, wf, layout)
    if method == Method.AllGather:
        return {k: exchange_shard_allgather(v, radius, mesh_counts, axis_order)
                for k, v in fields.items()}
    return {k: exchange_shard(v, radius, mesh_counts, axis_order, rem,
                              alloc_radius, nonperiodic, wf, layout)
            for k, v in fields.items()}


def make_exchange(mesh: Mesh, radius: Radius,
                  methods: Method = Method.Default,
                  axis_order: Tuple[int, ...] = (0, 1, 2),
                  rem: Dim3 = Dim3(0, 0, 0),
                  nonperiodic: bool = False,
                  wire_format=None, fields_spec=None,
                  wire_layout=None):
    """Build a jitted multi-quantity halo exchange over ``mesh``.

    Returns ``exchange(fields: dict[str, Array]) -> dict[str, Array]``
    where each field is a *global* padded (z,y,x) array sharded
    ``P('z','y','x')``. The orchestrator analog of
    DistributedDomain::exchange() (reference: src/stencil.cu:1002-1186)
    — except the whole dance (pack, send, poll, unpack, sync) is one
    XLA program.

    The input fields are DONATED: the exchange updates halos in place
    (XLA aliases each output to its input buffer), so the per-call HBM
    copy of every field disappears. Callers must drop their references
    to the passed arrays (``DistributedDomain.exchange`` rebinds
    ``curr`` from the result).

    ``wire_format`` declares the per-axis halo wire dtype ("f32" |
    "bf16", uniform string or per-axis dict — see
    :func:`normalize_wire_format`). A NARROWING wire format is
    certificate-gated: ``fields_spec`` (a ``{name: ShapeDtypeStruct}``
    dict of the global padded fields) is then required, the precision
    checker (checker 13, ``analysis/precision.py``) proves the built
    program's dtype flow sound — declared converts only, reductions at
    >= f32, exactly the declared wire dtype per link class, no double
    quantization — and an unsafe certificate raises
    ``PrecisionGateError`` instead of realizing. The returned callable
    carries ``wire_format``, ``wire_layout``, ``precision_declaration``,
    and ``precision_certificate`` attributes.

    ``wire_layout`` selects the message shape ("slab" | "irredundant",
    see :mod:`.packing`) — orthogonal to ``wire_format`` and composed
    with it (pack -> narrow -> ship -> widen -> unpack).
    """
    from .packing import normalize_wire_layout

    method = pick_method(methods)
    counts = Dim3(mesh.shape["x"], mesh.shape["y"], mesh.shape["z"])
    spec = P("z", "y", "x")
    wf = normalize_wire_format(wire_format)
    narrows = any(v != "f32" for v in wf.values())
    layout = normalize_wire_layout(wire_layout)

    def shard_fn(fields: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
        return dispatch_exchange(fields, radius, counts, method, axis_order,
                                 rem, nonperiodic=nonperiodic,
                                 wire_format=wf, wire_layout=layout)

    sm = jax.shard_map(shard_fn, mesh=mesh,
                       in_specs=spec, out_specs=spec, check_vma=False)
    ex = jax.jit(sm, donate_argnums=0)
    cert = None
    if narrows:
        # the certificate gate: an uncertified narrow wire format
        # refuses to realize, loudly (the schedule-certifier precedent,
        # parallel/megastep.certificate_gate)
        from ..analysis import precision as _precision

        if fields_spec is None:
            raise ValueError(
                "make_exchange: a narrowing wire_format is certificate-"
                "gated — pass fields_spec={name: jax.ShapeDtypeStruct("
                "global_padded_shape, dtype)} so the precision checker "
                "can prove the program before it realizes")
        cert = _precision.certify_wire_format(
            ex, ({q: jax.ShapeDtypeStruct(v.shape, v.dtype)
                  for q, v in dict(fields_spec).items()},),
            counts=counts, wire_formats=wf)
        if not cert.safe:
            raise _precision.PrecisionGateError(
                "make_exchange: wire format "
                f"{ {k: v for k, v in wf.items()} } is NOT certified "
                f"safe — refusing to realize: "
                + "; ".join(cert.reasons))
    ex.wire_format = dict(wf)
    ex.wire_layout = layout
    ex.precision_declaration = {"wire": {ax: fmt for ax, fmt in wf.items()},
                                "compute": "float32"}
    ex.precision_certificate = cert
    return ex


def interior_slab_bytes(shard_zyx: Sequence[int], mesh_counts: Dim3,
                        radius_rows: int, elem_size: int,
                        y_z_extended: bool = False) -> int:
    """Wire bytes ONE shard puts on the ICI per
    ``exchange_interior_slabs`` call — the fast-path counterpart of
    ``exchanged_bytes_per_sweep`` (reference byte-counter ethos:
    src/stencil.cu:516-637). Counts the r-row transfers actually
    ppermuted (buffer filler rows are local zeros, not traffic);
    axes with one device are in-core wraps and cost nothing."""
    Z, Y, X = shard_zyx
    r = radius_rows
    total = 0
    if mesh_counts.z > 1:
        total += 2 * r * Y * X * elem_size
    if mesh_counts.y > 1:
        zspan = Z + 2 * r if y_z_extended else Z
        total += 2 * r * zspan * X * elem_size
    return total


def measure_slab_exchange_seconds(mesh: Mesh, local: Dim3, dtype,
                                  rz: int, ry: int, radius_rows: int,
                                  y_z_extended: bool, nfields: int = 1,
                                  reps: int = 10,
                                  z_only: bool = False) -> float:
    """Time ONE standalone ``exchange_interior_slabs`` round for
    ``nfields`` interior-resident fields over ``mesh`` — the honest
    exchange-cost estimate for the fused fast paths, which perform
    exactly this transfer inside their jitted loops where it cannot be
    timed separately (the per-iteration exchange-stats analog of
    src/stencil.cu:1005-1008,1174-1181). Returns seconds per exchange
    round (all fields). Compiles a throwaway program on zeros; the
    persistent compile cache keeps repeat calls cheap."""
    import time as _time

    from ..utils.timers import device_sync

    counts = Dim3(mesh.shape["x"], mesh.shape["y"], mesh.shape["z"])
    dim = Dim3(counts.x * local.x, counts.y * local.y,
               counts.z * local.z)
    sharding = jax.sharding.NamedSharding(mesh, P("z", "y", "x"))
    # allocate the zeros SHARDED (out_shardings), never staged on one
    # device — the global array at weak-scaled sizes would OOM the
    # default device if materialized there first
    make = jax.jit(lambda: jnp.zeros((dim.z, dim.y, dim.x), dtype),
                   out_shardings=sharding)
    fields = [make() for _ in range(nfields)]
    nslabs = 2 if z_only else 4

    def shard_fn(*fs):
        outs = []
        for f in fs:
            s = exchange_interior_slabs(f, counts, rz=rz, ry=ry,
                                        radius_rows=radius_rows,
                                        y_z_extended=y_z_extended,
                                        z_only=z_only)
            # ALL slabs are outputs: returning only zlo would let
            # XLA dead-code-eliminate the y-axis ppermutes (zlo depends
            # on the z shift alone) and the timing would silently drop
            # the y-face traffic
            outs.extend(s.values())
        return tuple(outs)

    spec = P("z", "y", "x")
    fn = jax.jit(jax.shard_map(shard_fn, mesh=mesh,
                               in_specs=(spec,) * nfields,
                               out_specs=(spec,) * (nslabs * nfields),
                               check_vma=False))
    out = fn(*fields)
    device_sync(out[0])
    t0 = _time.perf_counter()
    for _ in range(reps):
        out = fn(*fields)
    device_sync(out[0])
    return (_time.perf_counter() - t0) / reps


def exchanged_bytes_per_sweep(shard_padded_shape_zyx: Sequence[int],
                              radius: Radius, mesh_counts: Dim3,
                              elem_size: int,
                              axis_order: Tuple[int, ...] = (0, 1, 2),
                              wire_format=None) -> Dict[str, int]:
    """Per-axis bytes one shard puts on the wire per exchange — the
    byte-counter observability analog (reference: stencil.hpp:86-93,
    src/stencil.cu:516-637). Counts only shifts that cross devices
    (n_dev > 1); same-device wraps are local copies. A narrowing
    ``wire_format`` axis prices its elements at the on-wire width
    (4-byte lanes exactly halve under "bf16", quarter under fp8).
    Prices the SLAB layout; the irredundant twin is
    :func:`..parallel.packing.irredundant_bytes_per_sweep`."""
    out = {"x": 0, "y": 0, "z": 0}
    shape = list(shard_padded_shape_zyx)
    wf = normalize_wire_format(wire_format)
    for a in axis_order:
        dim = AXIS_TO_DIM[a]
        if mesh_counts[a] <= 1:
            continue
        other = 1
        for d in range(3):
            if d != dim:
                other *= shape[d]
        es = wire_elem_size(elem_size, wf[AXIS_NAME[a]])
        out[AXIS_NAME[a]] = radius.wire_rows(a) * other * es
    return out
