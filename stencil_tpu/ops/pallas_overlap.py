"""True comm/compute overlap: one Pallas kernel that exchanges halos
over the ICI with explicit RDMA *while* computing the stencil interior.

This is the TPU re-creation of the reference's whole overlap
architecture — interior kernels launch, transports are polled, exterior
kernels launch once halos land (reference: bin/jacobi3d.cu:296-377,
src/stencil.cu:1081-1118) — as ONE kernel per step:

1. neighbor barrier (destination buffers quiescent),
2. ``make_async_remote_copy`` of the 4 face slabs starts (z/y mesh
   neighbors; x is never mesh-sharded),
3. a hand-rolled double-buffered z-block pipeline computes every output
   block from owned data while the DMAs are in flight — the face cells
   it produces are placeholders,
4. ``wait()`` on the slab-transfer semaphores,
5. thin face passes recompute the two y rows and two z planes from the
   landed slabs, overwriting the placeholders.

The 7-point star needs no corner data, so the exchange is pure face
slabs. Single-count axes fall back to local wrap copies into the same
buffers, so the compute phases are identical at any mesh shape — and
the whole kernel runs under the Pallas TPU interpreter off-TPU
(interpreted inter-device DMA), which is how the multi-chip tests
exercise it on the CPU mesh.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..geometry import Dim3
from ..telemetry.spans import KERNEL, layer_scope
from .pallas_stencil import on_tpu

# collective_id namespace distinct from parallel/pallas_exchange.py
_OVERLAP_COLLECTIVE_ID = 21

#: schedule-certifier hint (analysis/schedule.py): the kernel arms at
#: most the four face-slab remote copies (z-lo/z-hi/y-lo/y-hi) before
#: the interior compute and drains all four before the face passes —
#: the registry pins the peak so a schedule refactor that raises the
#: in-flight pressure (or stops draining) fails the checker
SCHEDULE_EXPECT = {"max_in_flight": 4}


def _interpret_mode():
    return False if on_tpu() else pltpu.InterpretParams()


@layer_scope(KERNEL)
def jacobi7_overlap_pallas(interior: jnp.ndarray,
                           origin_zyx: jnp.ndarray,
                           hot_c: Tuple[int, int, int],
                           cold_c: Tuple[int, int, int], sph_r: int,
                           counts: Dim3,
                           block_z: int = 8,
                           interpret: Optional[object] = None
                           ) -> jnp.ndarray:
    """One overlapped Jacobi step on an interior-resident (Z, Y, X)
    shard. Call inside ``shard_map`` over mesh axes ('x','y','z') with
    x unsharded (``counts.x == 1``); ``origin_zyx`` is the shard's
    global interior origin (traced int32 (3,)).

    Semantics match the halo-kernel path (exchange_interior_slabs +
    jacobi7_halo_pallas) — but the slab exchange here is RDMA issued
    from inside the kernel, hidden behind the interior compute.
    """
    if interpret is None:
        interpret = _interpret_mode()
    Z, Y, X = interior.shape
    assert counts.x == 1, "x (lane) axis must not be mesh-sharded"
    if Z < 4 or Y < 2:
        raise ValueError(f"overlap kernel needs Z >= 4, Y >= 2, "
                         f"got {(Z, Y)}")
    bz = block_z
    while bz > 1 and Z % bz:
        bz //= 2
    while bz + 2 > Z:
        bz //= 2
    if bz < 1 or Z % bz:
        raise ValueError(f"no valid z block for Z={Z}")
    dt = jnp.dtype(interior.dtype)
    hx, hy, hz = hot_c
    cx, cy, cz = cold_c
    r2 = sph_r * sph_r
    nzb = Z // bz
    win = bz + 2                      # z window rows per block
    my_count = counts.y
    mz_count = counts.z

    def sources(vals, org, z0, y0):
        """Dirichlet spheres on a (nz, ny, X) region at shard-local
        (z0, y0); ``org`` is the shard's global (z, y, x) origin."""
        nz, ny = vals.shape[0], vals.shape[1]
        gy = (org[1] + y0
              + lax.broadcasted_iota(jnp.int32, (ny, X), 0))
        gx = org[2] + lax.broadcasted_iota(jnp.int32, (ny, X), 1)
        gz = (org[0] + z0
              + lax.broadcasted_iota(jnp.int32, (nz, 1, 1), 0))
        d2h = (gx - hx) ** 2 + (gy - hy) ** 2 + (gz - hz) ** 2
        d2c = (gx - cx) ** 2 + (gy - cy) ** 2 + (gz - cz) ** 2
        vals = jnp.where(d2h <= r2, dt.type(1.0), vals)
        vals = jnp.where(d2c <= r2, dt.type(0.0), vals)
        return vals

    def outer(org, in_hbm, out_hbm, zlo, zhi, ylo, yhi,
              wbuf, obuf, fbuf, frow, fout,
              slab_send, slab_recv, load_sem, store_sem, face_sem):
        # ---- 1. rendezvous: every mesh neighbor we will write into
        # must have entered this kernel (its slab buffers quiescent)
        n_remote_axes = (1 if mz_count > 1 else 0) + \
                        (1 if my_count > 1 else 0)
        if n_remote_axes:
            bsem = pltpu.get_barrier_semaphore()
            if mz_count > 1:
                me = lax.axis_index("z")
                up = lax.rem(me + 1, jnp.int32(mz_count))
                dn = lax.rem(me + jnp.int32(mz_count) - 1,
                             jnp.int32(mz_count))
                pltpu.semaphore_signal(bsem, inc=1, device_id={"z": up})
                pltpu.semaphore_signal(bsem, inc=1, device_id={"z": dn})
            if my_count > 1:
                me = lax.axis_index("y")
                up = lax.rem(me + 1, jnp.int32(my_count))
                dn = lax.rem(me + jnp.int32(my_count) - 1,
                             jnp.int32(my_count))
                pltpu.semaphore_signal(bsem, inc=1, device_id={"y": up})
                pltpu.semaphore_signal(bsem, inc=1, device_id={"y": dn})
            pltpu.semaphore_wait(bsem, 2 * n_remote_axes)

        # ---- 2. start the face-slab exchange. Slab contracts: zlo =
        # z-minus neighbor's top plane; zhi = z-plus neighbor's bottom
        # plane; ylo = y-minus neighbor's last row; yhi = y-plus
        # neighbor's first row (periodic wrap when that axis count is 1).
        copies = []
        if mz_count > 1:
            me = lax.axis_index("z")
            up = lax.rem(me + 1, jnp.int32(mz_count))
            dn = lax.rem(me + jnp.int32(mz_count) - 1,
                         jnp.int32(mz_count))
            copies.append(pltpu.make_async_remote_copy(
                src_ref=in_hbm.at[Z - 1:Z], dst_ref=zlo,
                send_sem=slab_send.at[0], recv_sem=slab_recv.at[0],
                device_id={"z": up}))
            copies.append(pltpu.make_async_remote_copy(
                src_ref=in_hbm.at[0:1], dst_ref=zhi,
                send_sem=slab_send.at[1], recv_sem=slab_recv.at[1],
                device_id={"z": dn}))
        else:
            copies.append(pltpu.make_async_copy(
                in_hbm.at[Z - 1:Z], zlo, slab_recv.at[0]))
            copies.append(pltpu.make_async_copy(
                in_hbm.at[0:1], zhi, slab_recv.at[1]))
        if my_count > 1:
            me = lax.axis_index("y")
            up = lax.rem(me + 1, jnp.int32(my_count))
            dn = lax.rem(me + jnp.int32(my_count) - 1,
                         jnp.int32(my_count))
            copies.append(pltpu.make_async_remote_copy(
                src_ref=in_hbm.at[:, Y - 1:Y], dst_ref=ylo,
                send_sem=slab_send.at[2], recv_sem=slab_recv.at[2],
                device_id={"y": up}))
            copies.append(pltpu.make_async_remote_copy(
                src_ref=in_hbm.at[:, 0:1], dst_ref=yhi,
                send_sem=slab_send.at[3], recv_sem=slab_recv.at[3],
                device_id={"y": dn}))
        else:
            copies.append(pltpu.make_async_copy(
                in_hbm.at[:, Y - 1:Y], ylo, slab_recv.at[2]))
            copies.append(pltpu.make_async_copy(
                in_hbm.at[:, 0:1], yhi, slab_recv.at[3]))
        for c in copies:
            c.start()

        # ---- 3. interior compute while the slabs fly: double-buffered
        # z-block pipeline over owned data. Each block k reads a
        # (bz+2)-row window clamped into [0, Z); rows 0 / Z-1 and
        # columns 0 / Y-1 of the output get placeholder values that
        # phase 5 overwrites.
        def win_start(k):
            s = k * bz - 1
            return jnp.clip(s, 0, Z - win)

        def load(k, slot):
            return pltpu.make_async_copy(
                in_hbm.at[pl.ds(win_start(k), win)],
                wbuf.at[slot], load_sem.at[slot])

        def store(k, slot):
            return pltpu.make_async_copy(
                obuf.at[slot], out_hbm.at[pl.ds(k * bz, bz)],
                store_sem.at[slot])

        def compute(k, slot):
            off = k * bz - win_start(k)        # my rows at [off, off+bz)
            c = wbuf[slot, pl.ds(off, bz)]
            # single boundary planes, clamped at the shard edge — the
            # clamp only affects rows 0 / Z-1 (placeholders; phase 5b
            # overwrites them). Interior rows' zm/zp come from c itself.
            zm0 = wbuf[slot, pl.ds(jnp.maximum(off - 1, 0), 1)]
            zp0 = wbuf[slot, pl.ds(jnp.minimum(off + bz, win - 1), 1)]
            zm = jnp.concatenate([zm0, c[:-1]], axis=0)
            zp = jnp.concatenate([c[1:], zp0], axis=0)
            # y neighbors in-shard; rows 0 / Y-1 clamped (placeholder)
            ym = jnp.concatenate([c[:, 0:1], c[:, :-1]], axis=1)
            yp = jnp.concatenate([c[:, 1:], c[:, -1:]], axis=1)
            xm = pltpu.roll(c, 1, 2)
            xp = pltpu.roll(c, X - 1, 2)
            new = (zm + zp + ym + yp + xm + xp) * dt.type(1.0 / 6.0)
            obuf[slot, pl.ds(0, bz)] = sources(new, org, k * bz, 0)

        load(0, 0).start()

        def body(k, _):
            slot = lax.rem(k, 2)
            nslot = lax.rem(k + 1, 2)

            @pl.when(k + 1 < nzb)
            def _():
                # the next load reuses the other slot; its previous
                # store (k-1) must have drained first
                @pl.when(k >= 1)
                def _():
                    store(k - 1, nslot).wait()
                load(k + 1, nslot).start()

            load(k, slot).wait()
            compute(k, slot)
            store(k, slot).start()
            return 0

        lax.fori_loop(0, nzb, body, 0)
        # drain the last two stores
        @pl.when(nzb >= 2)
        def _():
            store(nzb - 2, lax.rem(nzb - 2, 2)).wait()
        store(nzb - 1, lax.rem(nzb - 1, 2)).wait()

        # ---- 4. halos land
        for c in copies:
            c.wait()

        def sync_copy(src, dst, sem):
            pltpu.make_async_copy(src, dst, sem).start()
            pltpu.make_async_copy(src, dst, sem).wait()

        # ---- 5a. y rows: out[:, 0] and out[:, Y-1] from the y slabs.
        # fbuf stages in[:, edge 2 cols]; frow the slab (ANY -> VMEM);
        # fout the result. Rows z=0 / Z-1 stay placeholders (5b
        # overwrites them).
        for row, slab in ((0, ylo), (Y - 1, yhi)):
            src_lo = 0 if row == 0 else Y - 2
            sync_copy(in_hbm.at[:, pl.ds(src_lo, 2)], fbuf,
                      face_sem.at[0])
            sync_copy(slab, frow, face_sem.at[1])
            A = fbuf[...]                      # (Z, 2, X)
            me_col = 0 if row == 0 else 1      # my row within fbuf
            in_col = 1 if row == 0 else 0      # in-shard y neighbor
            c = A[:, me_col:me_col + 1]        # (Z, 1, X)
            nbr_in = A[:, in_col:in_col + 1]
            zm = jnp.concatenate([c[0:1], c[:-1]], axis=0)
            zp = jnp.concatenate([c[1:], c[-1:]], axis=0)
            xm = pltpu.roll(c, 1, 2)
            xp = pltpu.roll(c, X - 1, 2)
            new = (zm + zp + nbr_in + frow[...] + xm + xp) \
                * dt.type(1.0 / 6.0)
            fout[...] = sources(new, org, 0, row)
            sync_copy(fout, out_hbm.at[:, pl.ds(row, 1)],
                      face_sem.at[1])

        # ---- 5b. z planes: out[0] and out[Z-1] (including y-edge
        # cells from the slabs), overwriting 5a's corner placeholders.
        # wbuf slot 0 is free now; stage [plane; z-inner; zslab] rows
        # in it and the slab y rows in frow.
        for plane, zslab in ((0, zlo), (Z - 1, zhi)):
            zin_row = 1 if plane == 0 else Z - 2
            sync_copy(in_hbm.at[pl.ds(plane, 1)],
                      wbuf.at[0, pl.ds(0, 1)], face_sem.at[2])
            sync_copy(in_hbm.at[pl.ds(zin_row, 1)],
                      wbuf.at[0, pl.ds(1, 1)], face_sem.at[2])
            sync_copy(zslab, wbuf.at[0, pl.ds(2, 1)], face_sem.at[2])
            # the slab rows at this plane: frow[0] <- ylo[plane],
            # frow[1] <- yhi[plane] (frow is (Z,1,X); Z >= 4 > 2)
            sync_copy(ylo.at[pl.ds(plane, 1)],
                      frow.at[pl.ds(0, 1)], face_sem.at[3])
            sync_copy(yhi.at[pl.ds(plane, 1)],
                      frow.at[pl.ds(1, 1)], face_sem.at[3])
            c = wbuf[0, 0]                     # (Y, X)
            zin = wbuf[0, 1]
            zsl = wbuf[0, 2]
            ym = jnp.concatenate([frow[0], c[:-1]], axis=0)
            yp = jnp.concatenate([c[1:], frow[1]], axis=0)
            xm = pltpu.roll(c, 1, 1)
            xp = pltpu.roll(c, X - 1, 1)
            new = (ym + yp + zin + zsl + xm + xp) * dt.type(1.0 / 6.0)
            fplane = obuf.at[0, pl.ds(0, 1)]
            obuf[0, pl.ds(0, 1)] = sources(new[None], org, plane, 0)
            sync_copy(fplane, out_hbm.at[pl.ds(plane, 1)],
                      face_sem.at[3])

    out_shapes = [
        jax.ShapeDtypeStruct((Z, Y, X), dt),      # the new field
        jax.ShapeDtypeStruct((1, Y, X), dt),      # zlo slab buffer
        jax.ShapeDtypeStruct((1, Y, X), dt),      # zhi
        jax.ShapeDtypeStruct((Z, 1, X), dt),      # ylo
        jax.ShapeDtypeStruct((Z, 1, X), dt),      # yhi
    ]
    outs = pl.pallas_call(
        outer,
        name="jacobi7_overlap",
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 5,
        out_shape=out_shapes,
        scratch_shapes=[
            pltpu.VMEM((2, win, Y, X), dt),       # wbuf (in windows)
            pltpu.VMEM((2, bz, Y, X), dt),        # obuf (out blocks)
            pltpu.VMEM((Z, 2, X), dt),            # fbuf (y face cols)
            pltpu.VMEM((Z, 1, X), dt),            # frow (y slab, VMEM)
            pltpu.VMEM((Z, 1, X), dt),            # fout (y face out)
            pltpu.SemaphoreType.DMA((4,)),        # slab send
            pltpu.SemaphoreType.DMA((4,)),        # slab recv
            pltpu.SemaphoreType.DMA((2,)),        # window loads
            pltpu.SemaphoreType.DMA((2,)),        # block stores
            pltpu.SemaphoreType.DMA((4,)),        # face traffic
        ],
        compiler_params=pltpu.CompilerParams(
            collective_id=_OVERLAP_COLLECTIVE_ID, has_side_effects=True),
        interpret=interpret,
    )(jnp.asarray(origin_zyx, jnp.int32), interior)
    return outs[0]
