"""Pallas TPU kernels for the hot stencil compute paths.

These are the hand-scheduled analogs of the reference's application
CUDA kernels (reference: bin/jacobi3d.cu:40-85 stencil_kernel;
astaroth/user_kernels.h:383-453 solve), built the TPU way: the padded
shard stays in HBM and the kernel streams z-planes through VMEM — the
grid walks the interior z extent and each step sees a (2r+1)-plane
window, so HBM traffic is one read + one write per point while the VPU
does the adds on (y, x) planes (8x128 lanes).

The XLA slicing versions in ``stencil_kernels.py`` / ``fd6.py`` remain
the default on CPU and the correctness oracle; these kernels are the
optimization path selected with ``kernel="pallas"`` on models, and run
under the Pallas TPU interpreter off-TPU so tests exercise them
everywhere.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..geometry import Dim3, Radius


def on_tpu() -> bool:
    """Single source of truth for "is this process on a TPU backend"
    (shared by kernel selection and exchange interpret-mode choices).
    A backend that fails to initialise raises: it is never read as
    "not a TPU", which would quietly interpret every kernel."""
    return jax.default_backend() == "tpu"


def default_interpret() -> bool:
    """Interpret Pallas kernels when not running on a TPU backend."""
    return not on_tpu()


def sublane_tile_bytes(itemsize: int) -> int:
    """Minimum sublane (second-minor) tile rows for an ``itemsize``-byte
    dtype on TPU: 8 for 4-byte types, 16 for 2-byte (bf16), 32 for
    1-byte — edge-slab block shapes must be multiples of this to stay
    tile-aligned. The single source of the tile rule."""
    return max(8, 32 // max(itemsize, 1))


def sublane_tile(dtype) -> int:
    """``sublane_tile_bytes`` by dtype."""
    return sublane_tile_bytes(jnp.dtype(dtype).itemsize)


def _plane_specs(n_planes: int, z_lo: int, yp: int, xp: int):
    """One BlockSpec per z-offset: the same padded input is passed
    ``n_planes`` times with shifted index maps, giving the kernel an
    overlapping (n_planes, yp, xp) window per grid step (BlockSpec tiles
    cannot overlap, so the window is expressed as multiple views)."""
    specs = []
    for off in range(n_planes):
        specs.append(pl.BlockSpec(
            (1, yp, xp),
            functools.partial(lambda k, o: (k + z_lo + o - (n_planes // 2), 0, 0),
                              o=off)))
    return specs


def jacobi7_pallas(padded: jnp.ndarray, radius: Radius, interior: Dim3,
                   interpret: Optional[bool] = None) -> jnp.ndarray:
    """7-point Jacobi average over a halo-padded (z,y,x) shard
    (reference: bin/jacobi3d.cu:65-80), z-plane-pipelined through VMEM.

    Returns the interior-shaped (Z, Y, X) update; the caller writes it
    back with ``write_interior``.
    """
    if interpret is None:
        interpret = default_interpret()
    lo = radius.pad_lo()
    Z, Y, X = interior.z, interior.y, interior.x
    Zp, Yp, Xp = padded.shape
    ly, lx = lo.y, lo.x

    def kern(pm, pc, pp, out):
        c = pc[0]
        acc = pm[0, ly:ly + Y, lx:lx + X] + pp[0, ly:ly + Y, lx:lx + X]
        acc += c[ly - 1:ly - 1 + Y, lx:lx + X]
        acc += c[ly + 1:ly + 1 + Y, lx:lx + X]
        acc += c[ly:ly + Y, lx - 1:lx - 1 + X]
        acc += c[ly:ly + Y, lx + 1:lx + 1 + X]
        out[0] = acc * (1.0 / 6.0)

    return pl.pallas_call(
        kern,
        grid=(Z,),
        in_specs=_plane_specs(3, lo.z, Yp, Xp),
        out_specs=pl.BlockSpec((1, Y, X), lambda k: (k, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((Z, Y, X), padded.dtype),
        interpret=interpret,
    )(padded, padded, padded)


#: default block-shape ceilings for the wrap kernels — the planner
#: picks the cheapest-traffic legal shape at or below these
_WRAP_CAPS = (8, 128)
_WRAPN_CAPS = (16, 128)


def _wrap_elems(esub: int, n_steps: int = 0):
    """Per-lane-column element model of the wrap kernels for the block
    planner (analysis/tiling.py): streamed inputs (main + 2 z segments
    of ``max(n_steps, 1)`` rows + 2 esub-col y slabs + 4*n_steps corner
    singles on the N-step kernel), the output block, and — for the
    N-step kernel — the held assembled window plus its first shrinking
    intermediate. Must count at least what the GridMapping will show
    (the plan -> audit round-trip contract)."""
    n = max(int(n_steps), 0)
    rows = max(n, 1)

    def elems(bz: int, by: int):
        ein = bz * by + 2 * rows * by + 2 * bz * esub + 4 * n * esub
        held = 0
        if n:
            held = ((bz + 2 * n) * (by + 2 * n)
                    + (bz + 2 * n - 2) * (by + 2 * n - 2))
        return ein, bz * by, held

    return elems


def jacobi7_wrap_pallas(interior: jnp.ndarray,
                        hot_c: Tuple[int, int, int],
                        cold_c: Tuple[int, int, int], sph_r: int,
                        block_z: Optional[int] = None,
                        block_y: Optional[int] = None,
                        interpret: Optional[bool] = None) -> jnp.ndarray:
    """Fully-fused periodic Jacobi step for a single-shard axis layout:
    7-point update + Dirichlet sphere sources on an UNPADDED (Z, Y, X)
    array, with the periodic wrap done inside the kernel — z/y wrap via
    wrapped edge-slab index maps, x wrap via in-VMEM circular shift
    (``pltpu.roll``). No halo storage, no exchange program: ~1.3 HBM
    passes per step instead of the padded path's slab copies
    (the single-chip fast path; reference semantics bin/jacobi3d.cu:40-85).

    ``hot_c``/``cold_c`` are (cx, cy, cz) sphere centers. Blocks tile
    (z, y); edge reads come from four thin wrapped slabs, so the read
    amplification is ``1 + 2/block_z + 2/block_y`` (esub-scaled for the
    slab fetches) and VMEM use is ``~2 * 2 * block_z * block_y * X``
    elements. Default (None) blocks come from the VMEM block-shape
    planner (``analysis/tiling.py``: cheapest legal traffic at or
    below ``_WRAP_CAPS``, raising when nothing legal exists); explicit
    blocks are snapped to alignment with a one-shot warning when
    replaced (budget deliberately unchecked — sweeps measure what they
    asked for).
    """
    from ..analysis.tiling import plan_blocks, snap_blocks

    if interpret is None:
        interpret = default_interpret()
    Z, Y, X = interior.shape
    dt_i = jnp.dtype(interior.dtype)
    # y edge slabs are esub rows: the dtype's min sublane tile (8 f32 /
    # 16 bf16) when Y allows, else single rows (small/interpret grids)
    esub = sublane_tile(interior.dtype)
    if Y % esub:
        esub = 1
    if block_z is None and block_y is None:
        bz, by = plan_blocks(
            "jacobi7_wrap_pallas", Z, Y, X, dt_i.itemsize,
            _wrap_elems(esub), sublane_y=esub,
            cap_z=_WRAP_CAPS[0], cap_y=_WRAP_CAPS[1]).blocks()
    else:
        bz, by = snap_blocks(
            "jacobi7_wrap_pallas", Z, Y,
            block_z if block_z is not None else _WRAP_CAPS[0],
            block_y if block_y is not None else _WRAP_CAPS[1],
            sublane_y=esub)
    dt = jnp.dtype(interior.dtype)
    hx, hy, hz = hot_c
    cx, cy, cz = cold_c
    r2 = sph_r * sph_r

    def kern(zprev, main, znext, yprev, ynext, out):
        kz = pl.program_id(0)
        ky = pl.program_id(1)
        c = main[...]                            # (bz, by, X)
        # the wrapped neighbor row is the last row of the preceding
        # edge slab / first row of the following one
        ext = jnp.concatenate([yprev[:, esub - 1:esub], c, ynext[:, 0:1]],
                              axis=1)
        ym = ext[:, :by]                         # row j-1 (wrapped)
        yp = ext[:, 2:]
        xm = pltpu.roll(c, 1, 2)
        xp = pltpu.roll(c, X - 1, 2)
        lat = ym + yp + xm + xp
        gy = (ky * by
              + jax.lax.broadcasted_iota(jnp.int32, (by, X), 0))
        gx = jax.lax.broadcasted_iota(jnp.int32, (by, X), 1)
        d2yx_h = (gx - hx) ** 2 + (gy - hy) ** 2
        d2yx_c = (gx - cx) ** 2 + (gy - cy) ** 2
        for r in range(bz):
            zm = zprev[0] if r == 0 else c[r - 1]
            zp = znext[0] if r == bz - 1 else c[r + 1]
            new = (lat[r] + zm + zp) * dt.type(1.0 / 6.0)
            gz = kz * bz + r
            new = jnp.where(d2yx_h + (gz - jnp.int32(hz)) ** 2 <= r2,
                            dt.type(1.0), new)
            new = jnp.where(d2yx_c + (gz - jnp.int32(cz)) ** 2 <= r2,
                            dt.type(0.0), new)
            out[r] = new

    return pl.pallas_call(
        kern,
        grid=(Z // bz, Y // by),
        in_specs=[
            # plane before this z block, periodic
            pl.BlockSpec((1, by, X),
                         lambda kz, ky: ((kz * bz - 1) % Z, ky, 0)),
            pl.BlockSpec((bz, by, X), lambda kz, ky: (kz, ky, 0)),
            # plane after this z block, periodic
            pl.BlockSpec((1, by, X),
                         lambda kz, ky: ((kz * bz + bz) % Z, ky, 0)),
            # esub-row y slabs just outside this block, periodic
            pl.BlockSpec((bz, esub, X),
                         lambda kz, ky: (kz,
                                         (ky * (by // esub) - 1)
                                         % (Y // esub), 0)),
            pl.BlockSpec((bz, esub, X),
                         lambda kz, ky: (kz,
                                         (ky * (by // esub) + by // esub)
                                         % (Y // esub), 0)),
        ],
        out_specs=pl.BlockSpec((bz, by, X), lambda kz, ky: (kz, ky, 0)),
        out_shape=jax.ShapeDtypeStruct((Z, Y, X), interior.dtype),
        # allow larger-than-default blockings in tuning sweeps (Mosaic's
        # default scoped-VMEM ceiling is 16 MiB)
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
    )(interior, interior, interior, interior, interior)


def jacobi7_wrapn_pallas(interior: jnp.ndarray,
                         hot_c: Tuple[int, int, int],
                         cold_c: Tuple[int, int, int], sph_r: int,
                         steps: int = 2,
                         block_z: Optional[int] = None,
                         block_y: Optional[int] = None,
                         interpret: Optional[bool] = None) -> jnp.ndarray:
    """``steps`` fused periodic Jacobi iterations (+ sphere sources
    after each) in ONE HBM pass — temporal blocking. The single-step
    kernel is bandwidth-bound at ~2.4 HBM passes per iteration;
    evaluating step k+1 from step k's values while they are still in
    VMEM (recomputing an edge ring of step-k values at block borders)
    costs the same traffic per *pass* but advances ``steps``
    iterations, dividing per-iteration traffic by ~``steps`` at the
    price of ring recompute that grows with ``steps``. Bit-identical
    to ``steps`` ``jacobi7_wrap_pallas`` calls (same op order per
    point; the ring is recomputed, not approximated). Reference
    semantics: bin/jacobi3d.cu:40-85 applied ``steps`` times.

    Each (bz, by, X) output block reads a wrapped (bz+2N, by+2N, X)
    window assembled from a main block, 2N single-row z segments, 2
    esub-col y slabs, and 4N corner singles (x wraps in-core via
    ``pltpu.roll``; z is the majormost dim, so single-row fetches are
    exact-radius). Needs Z % bz == 0, Y and by multiples of the
    dtype's sublane tile (8 f32 / 16 bf16), and steps <= that tile.
    """
    from ..analysis.tiling import plan_blocks, snap_blocks

    if interpret is None:
        interpret = default_interpret()
    N = int(steps)
    Z, Y, X = interior.shape
    esub = sublane_tile(interior.dtype)
    if N < 1 or N > esub:
        raise ValueError(f"wrapN kernel needs 1 <= steps <= {esub}, "
                         f"got steps={N}")
    if Y % esub:
        raise ValueError(f"wrap{N} kernel needs Y % {esub} == 0, "
                         f"got Y={Y}")
    isz = jnp.dtype(interior.dtype).itemsize
    if block_z is None and block_y is None:
        bz, by = plan_blocks(
            f"jacobi7_wrapn_pallas[n={N}]", Z, Y, X, isz,
            _wrap_elems(esub, N), sublane_y=esub,
            cap_z=_WRAPN_CAPS[0], cap_y=_WRAPN_CAPS[1]).blocks()
    else:
        bz, by = snap_blocks(
            f"jacobi7_wrapn_pallas[n={N}]", Z, Y,
            block_z if block_z is not None else _WRAPN_CAPS[0],
            block_y if block_y is not None else _WRAPN_CAPS[1],
            sublane_y=esub)
    # N-row slab fetches when block alignment permits (fewer, fatter
    # DMAs — the N=2 default then matches the original pair kernel's
    # descriptor structure exactly); single-row fetches otherwise
    slabbed = (bz % N == 0) and (Z % N == 0)
    dt = jnp.dtype(interior.dtype)
    hx, hy, hz = hot_c
    cx, cy, cz = cold_c
    r2 = sph_r * sph_r
    byb = by // esub       # y index maps use esub-col granularity
    nyb8 = Y // esub

    def sources(vals, z0, y0, nz, ny):
        """Re-impose Dirichlet spheres on a (nz, ny, X) region whose
        global origin is (z0, y0, 0). Coords wrap modulo the global
        size: ring cells outside an edge block are PERIODIC neighbors,
        so their sphere test must use the wrapped position."""
        gy = (y0 + jax.lax.broadcasted_iota(jnp.int32, (ny, X), 0)) % Y
        gx = jax.lax.broadcasted_iota(jnp.int32, (ny, X), 1)
        gz = (z0 + jax.lax.broadcasted_iota(jnp.int32, (nz, 1, 1), 0)) % Z
        d2h = (gx - hx) ** 2 + (gy - hy) ** 2 + (gz - hz) ** 2
        d2c = (gx - cx) ** 2 + (gy - cy) ** 2 + (gz - cz) ** 2
        vals = jnp.where(d2h <= r2, dt.type(1.0), vals)
        vals = jnp.where(d2c <= r2, dt.type(0.0), vals)
        return vals

    def jstep(w):
        """One 7-point step on the interior of a (nz, ny, X) window:
        returns (nz-2, ny-2, X); x is periodic in-core."""
        zsum = w[:-2, 1:-1] + w[2:, 1:-1]
        ysum = w[1:-1, :-2] + w[1:-1, 2:]
        xm = pltpu.roll(w, 1, 2)
        xp = pltpu.roll(w, X - 1, 2)
        xsum = (xm + xp)[1:-1, 1:-1]
        return (zsum + ysum + xsum) * dt.type(1.0 / 6.0)

    # ref order: main | z- segments | z+ segments | ym | yp | corners
    # (slabbed: one N-row segment per side, 4 N-row corners; unaligned:
    # N single rows per side, 4N single-row corners)
    nzseg = 1 if slabbed else N

    def kern(*refs):
        main = refs[0]
        zms = refs[1:1 + nzseg]
        zps = refs[1 + nzseg:1 + 2 * nzseg]
        ym, yp = refs[1 + 2 * nzseg:3 + 2 * nzseg]
        corners = refs[3 + 2 * nzseg:-1]
        out = refs[-1]
        kz = pl.program_id(0)
        ky = pl.program_id(1)
        z0 = kz * bz
        y0 = ky * by
        eN = esub - N

        def row(zref, cm, cp):
            return jnp.concatenate([cm[:, eN:], zref[...], cp[:, :N]],
                                   axis=1)

        rows = [row(zms[i], corners[2 * i], corners[2 * i + 1])
                for i in range(nzseg)]
        rows.append(jnp.concatenate([ym[:, eN:], main[...], yp[:, :N]],
                                    axis=1))
        rows.extend(row(zps[i], corners[2 * nzseg + 2 * i],
                        corners[2 * nzseg + 2 * i + 1])
                    for i in range(nzseg))
        w = jnp.concatenate(rows, axis=0)     # (bz+2N, by+2N, X)
        for k in range(N):
            w = jstep(w)                      # ring shrinks by 1 each
            ring = N - 1 - k
            w = sources(w, z0 - ring, y0 - ring, bz + 2 * ring,
                        by + 2 * ring)
        out[...] = w

    ym_map = lambda ky: (ky * byb - 1) % nyb8
    yp_map = lambda ky: (ky * byb + byb) % nyb8
    if slabbed:
        # N-row z segments in N-row block units (bz % N == 0 makes the
        # maps integral; matches the original wrap2 structure at N=2)
        bzN = bz // N
        nzN = Z // N
        zmaps = {-1: (lambda kz: (kz * bzN - 1) % nzN),
                 +1: (lambda kz: (kz * bzN + bzN) % nzN)}
        zsegs = [(N, -1), (N, +1)]
    else:
        zoffs = [-(N - i) for i in range(N)] + [bz + i for i in range(N)]
        zmaps = {o: (lambda kz, o=o: (kz * bz + o) % Z) for o in zoffs}
        zsegs = [(1, o) for o in zoffs]

    in_specs = [pl.BlockSpec((bz, by, X), lambda kz, ky: (kz, ky, 0))]
    in_specs += [pl.BlockSpec((rows_, by, X),
                              lambda kz, ky, f=zmaps[key]: (f(kz), ky, 0))
                 for rows_, key in zsegs]
    in_specs += [
        # esub-col y slabs just outside the block, periodic
        pl.BlockSpec((bz, esub, X),
                     lambda kz, ky: (kz, ym_map(ky), 0)),
        pl.BlockSpec((bz, esub, X),
                     lambda kz, ky: (kz, yp_map(ky), 0)),
    ]
    for rows_, key in zsegs:
        for ymap in (ym_map, yp_map):
            in_specs.append(pl.BlockSpec(
                (rows_, esub, X),
                lambda kz, ky, f=zmaps[key], g=ymap: (f(kz), g(ky), 0)))
    return pl.pallas_call(
        kern,
        grid=(Z // bz, Y // by),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bz, by, X), lambda kz, ky: (kz, ky, 0)),
        out_shape=jax.ShapeDtypeStruct((Z, Y, X), interior.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret,
    )(*([interior] * len(in_specs)))


def jacobi7_wrap2_pallas(interior: jnp.ndarray,
                         hot_c: Tuple[int, int, int],
                         cold_c: Tuple[int, int, int], sph_r: int,
                         block_z: Optional[int] = None,
                         block_y: Optional[int] = None,
                         interpret: Optional[bool] = None) -> jnp.ndarray:
    """Two fused iterations per HBM pass — ``jacobi7_wrapn_pallas``
    with steps=2. Kept as a stable named entry for kernel-level tests
    and external callers; the model builder and the tuning harness
    patch ``jacobi7_wrapn_pallas`` directly."""
    return jacobi7_wrapn_pallas(interior, hot_c, cold_c, sph_r, steps=2,
                                block_z=block_z, block_y=block_y,
                                interpret=interpret)


# 6th-order central second-derivative coefficients (see ops/fd6.py)
_D2_C = -49.0 / 18.0
_D2 = (3.0 / 2.0, -3.0 / 20.0, 1.0 / 90.0)


def laplace6_pallas(padded: jnp.ndarray, radius: Radius, interior: Dim3,
                    inv_ds: Tuple[float, float, float] = (1.0, 1.0, 1.0),
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """Fused 6th-order Laplacian (the Astaroth-family hot derivative,
    reference: astaroth/user_kernels.h:49-62 second_derivative summed
    over axes) on a radius-3-padded shard, z-plane-pipelined: 7 planes
    resident in VMEM per grid step."""
    if interpret is None:
        interpret = default_interpret()
    lo = radius.pad_lo()
    Z, Y, X = interior.z, interior.y, interior.x
    Zp, Yp, Xp = padded.shape
    ly, lx = lo.y, lo.x
    dt = jnp.dtype(padded.dtype)
    ix2 = dt.type(inv_ds[0] * inv_ds[0])
    iy2 = dt.type(inv_ds[1] * inv_ds[1])
    iz2 = dt.type(inv_ds[2] * inv_ds[2])

    def kern(m3, m2, m1, c0, p1, p2, p3, out):
        c = c0[0]
        ctr = c[ly:ly + Y, lx:lx + X]
        accx = dt.type(_D2_C) * ctr
        accy = accx
        accz = dt.type(_D2_C) * ctr
        planes = {-3: m3, -2: m2, -1: m1, 1: p1, 2: p2, 3: p3}
        for i, w in enumerate(_D2, start=1):
            wc = dt.type(w)
            accx = accx + wc * (c[ly:ly + Y, lx + i:lx + i + X]
                                + c[ly:ly + Y, lx - i:lx - i + X])
            accy = accy + wc * (c[ly + i:ly + i + Y, lx:lx + X]
                                + c[ly - i:ly - i + Y, lx:lx + X])
            accz = accz + wc * (planes[i][0, ly:ly + Y, lx:lx + X]
                                + planes[-i][0, ly:ly + Y, lx:lx + X])
        out[0] = accx * ix2 + accy * iy2 + accz * iz2

    return pl.pallas_call(
        kern,
        grid=(Z,),
        in_specs=_plane_specs(7, lo.z, Yp, Xp),
        out_specs=pl.BlockSpec((1, Y, X), lambda k: (k, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((Z, Y, X), padded.dtype),
        interpret=interpret,
    )(*([padded] * 7))
