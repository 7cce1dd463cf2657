"""In-kernel RDMA comm/compute overlap for the MHD substeps.

The reference earns its overlap machinery in the astaroth app: every RK
substep runs interior-launch / exchange / exterior-launch over 26
per-region streams (reference: astaroth/astaroth.cu:552-646,476-486;
polled transports src/stencil.cu:1081-1118). This module is the TPU
re-creation for the multi-device slab layout, following the proven
Jacobi pattern (ops/pallas_overlap.py) at MHD scale:

* ``mhd_substep_overlap_pallas`` — ONE grid kernel per substep that
  (a) barriers with its mesh neighbors, (b) issues the radius-R slab
  RDMA for all 8 fields (z faces + the z-extended y faces, corner
  ride-along pieces fired as soon as the z slabs land), and (c) streams
  (bz, by, X) blocks through the SAME fused ``mhd_rates`` compute as
  the halo megakernel while the DMAs fly — reading CLAMPED in-shard
  windows, so blocks at shard edges hold placeholder values; the landed
  slab buffers are kernel outputs in the standard
  ``exchange_interior_slabs`` layout contract.
* ``mhd_substep_fixup_pallas`` — thin strip kernels (grids remapped
  onto only the z-edge / y-edge block rows, outputs aliased onto the
  overlap kernel's results) that recompute the edge blocks from the
  landed slabs via the halo kernel's own window plan — the exterior
  launch of the reference choreography.
* ``mhd_substep_overlap`` — the per-substep driver composing the two.

Even grids, x unsharded (the slab-layout contract); numerics match
``mhd_substep_halo_pallas`` exactly (same window values, same update).
The whole choreography runs under the Pallas TPU interpreter off-TPU
(interpreted inter-device DMA), which is how the multi-chip tests and
the race detector exercise it on the CPU mesh.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..geometry import Dim3
from ..telemetry.spans import KERNEL, layer_scope
from .pallas_halo import R, _mhd_window_plan, mhd_halo_blocks
from .pallas_mhd import compute_dtype, mhd_tile
from .pallas_stencil import default_interpret, on_tpu

# collective_id namespace distinct from pallas_overlap (21) and
# pallas_exchange
_MHD_OVERLAP_COLLECTIVE_ID = 23

#: schedule-certifier hint (analysis/schedule.py): peak outstanding
#: remote copies across the phased z/y slab + corner exchange on the
#: registry's (1,2,2) certification mesh — all eight fields' z-lo/z-hi
#: + y-lo/y-hi slabs plus both yz corner legs fly together before the
#: phase-B waits (8 fields x 6 copies). Pinned so a phase reordering
#: that piles more copies in flight (or stops draining a phase) fails
#: the schedule checker instead of re-certifying
SCHEDULE_EXPECT = {"max_in_flight": 48}


def _interpret_mode():
    return False if on_tpu() else pltpu.InterpretParams()


@layer_scope(KERNEL)
def mhd_substep_overlap_pallas(fields: Dict[str, jnp.ndarray],
                               w: Optional[Dict[str, jnp.ndarray]],
                               s: int, prm, dt_phys: float,
                               counts: Dim3,
                               block_z: int = 8, block_y: int = 32,
                               pair: bool = False,
                               write_w: bool = True,
                               interpret: Optional[object] = None):
    """One overlapped RK3 MHD substep on interior-resident (Z, Y, X)
    shards: slab RDMA issued from inside the kernel, the fused
    ``mhd_rates`` interior compute running behind the in-flight DMAs.
    Call inside ``shard_map`` over mesh axes ('x','y','z') with x
    unsharded. Returns ``(new_fields, new_w, slabs)`` where edge
    blocks of the f/w outputs are PLACEHOLDERS (clamped windows) and
    ``slabs[q]`` holds the landed halo data in the
    ``exchange_interior_slabs(rz=bz, ry=mhd_tile(dtype), radius_rows=R,
    y_z_extended=True)`` layout — feed both to
    ``mhd_substep_fixup_pallas``. Reference choreography:
    astaroth/astaroth.cu:552-646 (interior launch + transports in
    flight), compressed into one kernel.

    ``pair=True`` fuses RK substeps 0+1 into the pass (the
    STENCIL_MHD_PAIR temporal blocking, ``pallas_mhd.mhd_pair_update``):
    ``s`` and the incoming ``w`` are ignored (alpha_0 == 0), the
    windows and the RDMA carry radius 2R, and the slabs come back with
    2R valid rows.

    Dead-w elision as in ``mhd_substep_wrap_pallas``: ``w=None`` drops
    the w read sweep (valid only at alpha_s == 0, i.e. substep 0 —
    pair mode always elides it); ``write_w=False`` drops the w write
    sweep (substep 2, whose w no one reads) and returns ``new_w``
    as None. write_w elision is bit-exact; w=None is ~1-ulp (compiler
    fusion changes without the 0*w term).
    """
    from ..models.astaroth import FIELDS, RK3_ALPHA, RK3_BETA, mhd_rates
    from .fd6 import FieldData
    from .pallas_mhd import mhd_pair_update

    if interpret is None:
        interpret = _interpret_mode()
    assert counts.x == 1, "x (lane) axis must not be mesh-sharded"
    hr = 2 * R if pair else R      # halo rows windows and DMAs carry
    Z, Y, X = fields[FIELDS[0]].shape
    dtype = fields[FIELDS[0]].dtype
    esub = mhd_tile(dtype)         # slab row tile: 8 f32/f64, 16 bf16
    comp = compute_dtype(dtype)    # bf16 stores, f32 computes
    bz, by = mhd_halo_blocks(Z, Y, block_z, block_y, esub, X=X,
                             itemsize=jnp.dtype(dtype).itemsize)
    assert hr <= min(bz, esub), (hr, bz, esub)
    dta = jnp.dtype(comp)
    inv_ds = (1.0 / prm.dsx, 1.0 / prm.dsy, 1.0 / prm.dsz)
    alpha = float(RK3_ALPHA[s])
    beta = float(RK3_BETA[s])
    dt_ = float(dt_phys)
    pad_lo = Dim3(0, R, R)
    interior = Dim3(X, by, bz)
    nzg = Z // bz
    nyg = Y // by
    mz = counts.z
    my = counts.y
    nf = len(FIELDS)
    zext = Z + 2 * bz

    # the halo kernel's own window plan in slabless mode: clamped
    # in-shard segments only, one source of truth for the geometry
    field_specs, inputs_for_field, select_window = _mhd_window_plan(
        Z, Y, X, bz, by, rr=hr, slabless=True, esub=esub)
    nseg = len(field_specs)
    main_spec = pl.BlockSpec((bz, by, X), lambda kz, ky: (kz, ky, 0))

    # pair mode (and w=None at alpha_s == 0) never reads the incoming
    # w: feeding it anyway would stream a full HBM read sweep of all 8
    # w fields per pass — exactly the sweep the elision exists to save
    # — so the w inputs vanish from the operand list entirely
    if w is None and not pair:
        assert alpha == 0.0, "w=None is only valid when alpha_s == 0"
    nw = 0 if (pair or w is None) else nf
    nwo = nf if write_w else 0

    def kern(*refs):
        field_refs = refs[:nseg * nf]
        w_refs = refs[nseg * nf:nseg * nf + nw]
        any_refs = refs[nseg * nf + nw:nseg * nf + nw + nf]
        outs = refs[nseg * nf + nw + nf:-2]
        out_f = outs[:nf]
        out_w = outs[nf:nf + nwo]
        zlo_o = outs[nf + nwo:2 * nf + nwo]
        zhi_o = outs[2 * nf + nwo:3 * nf + nwo]
        ylo_o = outs[3 * nf + nwo:4 * nf + nwo]
        yhi_o = outs[4 * nf + nwo:5 * nf + nwo]
        send = refs[-2]
        recv = refs[-1]
        kz = pl.program_id(0)
        ky = pl.program_id(1)
        first = jnp.logical_and(kz == 0, ky == 0)
        last = jnp.logical_and(kz == nzg - 1, ky == nyg - 1)

        def nbr(axis, n, up):
            me = lax.axis_index(axis)
            d = (lax.rem(me + 1, jnp.int32(n)) if up
                 else lax.rem(me + jnp.int32(n) - 1, jnp.int32(n)))
            return {axis: d}

        def z_copies(i):
            """slots 0 (zlo to z-up) / 1 (zhi to z-down); local wrap
            copies on a 1-count axis (sem: recv only)."""
            f_any = any_refs[i]
            if mz > 1:
                return [
                    pltpu.make_async_remote_copy(
                        src_ref=f_any.at[Z - hr:Z],
                        dst_ref=zlo_o[i].at[bz - hr:bz],
                        send_sem=send.at[i, 0], recv_sem=recv.at[i, 0],
                        device_id=nbr("z", mz, True)),
                    pltpu.make_async_remote_copy(
                        src_ref=f_any.at[0:hr],
                        dst_ref=zhi_o[i].at[0:hr],
                        send_sem=send.at[i, 1], recv_sem=recv.at[i, 1],
                        device_id=nbr("z", mz, False)),
                ]
            return [
                pltpu.make_async_copy(f_any.at[Z - hr:Z],
                                      zlo_o[i].at[bz - hr:bz],
                                      recv.at[i, 0]),
                pltpu.make_async_copy(f_any.at[0:hr], zhi_o[i].at[0:hr],
                                      recv.at[i, 1]),
            ]

        def y_interior_copies(i):
            """slots 2/3: the Z interior rows of the z-extended y
            faces (no z-slab dependency — fired at entry)."""
            f_any = any_refs[i]
            if my > 1:
                return [
                    pltpu.make_async_remote_copy(
                        src_ref=f_any.at[:, Y - hr:Y],
                        dst_ref=ylo_o[i].at[bz:bz + Z, esub - hr:esub],
                        send_sem=send.at[i, 2], recv_sem=recv.at[i, 2],
                        device_id=nbr("y", my, True)),
                    pltpu.make_async_remote_copy(
                        src_ref=f_any.at[:, 0:hr],
                        dst_ref=yhi_o[i].at[bz:bz + Z, 0:hr],
                        send_sem=send.at[i, 3], recv_sem=recv.at[i, 3],
                        device_id=nbr("y", my, False)),
                ]
            return [
                pltpu.make_async_copy(f_any.at[:, Y - hr:Y],
                                      ylo_o[i].at[bz:bz + Z,
                                                  esub - hr:esub],
                                      recv.at[i, 2]),
                pltpu.make_async_copy(f_any.at[:, 0:hr],
                                      yhi_o[i].at[bz:bz + Z, 0:hr],
                                      recv.at[i, 3]),
            ]

        def y_corner_copies(i):
            """slots 4-7: the R-row yz corner pieces of the y faces,
            sourced from MY landed z slabs (hence fired only after the
            slot-0/1 recv waits) — the corner ride-along of the
            sequential-sweep rule, as explicit messages."""
            pieces = [
                (zlo_o[i].at[bz - hr:bz, Y - hr:Y],
                 ylo_o[i].at[bz - hr:bz, esub - hr:esub], True, 4),
                (zhi_o[i].at[0:hr, Y - hr:Y],
                 ylo_o[i].at[bz + Z:bz + Z + hr, esub - hr:esub],
                 True, 5),
                (zlo_o[i].at[bz - hr:bz, 0:hr],
                 yhi_o[i].at[bz - hr:bz, 0:hr], False, 6),
                (zhi_o[i].at[0:hr, 0:hr],
                 yhi_o[i].at[bz + Z:bz + Z + hr, 0:hr], False, 7),
            ]
            out = []
            for src, dst, up, slot in pieces:
                if my > 1:
                    out.append(pltpu.make_async_remote_copy(
                        src_ref=src, dst_ref=dst,
                        send_sem=send.at[i, slot],
                        recv_sem=recv.at[i, slot],
                        device_id=nbr("y", my, up)))
                else:
                    out.append(pltpu.make_async_copy(src, dst,
                                                     recv.at[i, slot]))
            return out

        # ---- phase A (first grid step): rendezvous, then fire the z
        # slabs and the y interior rows for all fields
        @pl.when(first)
        def _():
            n_remote_axes = (1 if mz > 1 else 0) + (1 if my > 1 else 0)
            if n_remote_axes:
                bsem = pltpu.get_barrier_semaphore()
                if mz > 1:
                    pltpu.semaphore_signal(bsem, inc=1,
                                           device_id=nbr("z", mz, True))
                    pltpu.semaphore_signal(bsem, inc=1,
                                           device_id=nbr("z", mz, False))
                if my > 1:
                    pltpu.semaphore_signal(bsem, inc=1,
                                           device_id=nbr("y", my, True))
                    pltpu.semaphore_signal(bsem, inc=1,
                                           device_id=nbr("y", my, False))
                pltpu.semaphore_wait(bsem, 2 * n_remote_axes)
            for i in range(nf):
                for c in z_copies(i) + y_interior_copies(i):
                    c.start()

        # ---- interior compute for this block, behind the DMAs
        wins = {q: select_window(field_refs[nseg * i:nseg * (i + 1)])
                for i, q in enumerate(FIELDS)}
        if pair:
            f2, w2 = mhd_pair_update(wins, prm, dtype, dt_phys, bz, by)
            for i, q in enumerate(FIELDS):
                if nwo:
                    out_w[i][...] = w2[q]
                out_f[i][...] = f2[q]
        else:
            data = {q: FieldData(wins[q].astype(comp), inv_ds,
                                 pad_lo, interior, x_wrap=True)
                    for q in FIELDS}
            rates = mhd_rates(data, prm, comp)
            for i, q in enumerate(FIELDS):
                wq = dta.type(dt_) * rates[q]
                if nw:
                    wq = (dta.type(alpha) * w_refs[i][...].astype(comp)
                          + wq)
                if nwo:
                    out_w[i][...] = wq.astype(dtype)
                out_f[i][...] = (data[q].value
                                 + dta.type(beta) * wq).astype(dtype)

        # ---- phase B (still the first grid step, after one block of
        # compute): z slabs have landed — fire the corner pieces
        @pl.when(first)
        def _():
            for i in range(nf):
                for c in z_copies(i):
                    c.wait()
                for c in y_corner_copies(i):
                    c.start()

        # ---- phase C (last grid step): drain everything else
        @pl.when(last)
        def _():
            for i in range(nf):
                for c in y_interior_copies(i) + y_corner_copies(i):
                    c.wait()

    in_specs = []
    inputs = []
    for q in FIELDS:
        in_specs.extend(field_specs)
        inputs.extend(inputs_for_field(fields[q]))
    if nw:
        for q in FIELDS:
            in_specs.append(main_spec)
            inputs.append(w[q])
    for q in FIELDS:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        inputs.append(fields[q])

    out_shape = ([jax.ShapeDtypeStruct((Z, Y, X), dtype)] * (nf + nwo)
                 + [jax.ShapeDtypeStruct((bz, Y, X), dtype)] * (2 * nf)
                 + [jax.ShapeDtypeStruct((zext, esub, X), dtype)]
                 * (2 * nf))
    out_specs = ([main_spec] * (nf + nwo)
                 + [pl.BlockSpec(memory_space=pl.ANY)] * (4 * nf))

    outs = pl.pallas_call(
        kern,
        name="mhd_substep_overlap",
        grid=(nzg, nyg),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.SemaphoreType.DMA((nf, 8)),
                        pltpu.SemaphoreType.DMA((nf, 8))],
        compiler_params=pltpu.CompilerParams(
            collective_id=_MHD_OVERLAP_COLLECTIVE_ID,
            has_side_effects=True,
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret,
    )(*inputs)
    new_f = {q: outs[i] for i, q in enumerate(FIELDS)}
    new_w = ({q: outs[nf + i] for i, q in enumerate(FIELDS)}
             if write_w else None)
    base = nf + nwo
    slabs = {}
    for i, q in enumerate(FIELDS):
        slabs[q] = {"zlo": outs[base + i], "zhi": outs[base + nf + i],
                    "ylo": outs[base + 2 * nf + i],
                    "yhi": outs[base + 3 * nf + i]}
    return new_f, new_w, slabs


@layer_scope(KERNEL)
def mhd_substep_fixup_pallas(fields: Dict[str, jnp.ndarray],
                             w: Optional[Dict[str, jnp.ndarray]],
                             f_partial: Dict[str, jnp.ndarray],
                             w_partial: Optional[Dict[str, jnp.ndarray]],
                             slabs: Dict[str, Dict[str, jnp.ndarray]],
                             s: int, prm, dt_phys: float, strip: str,
                             block_z: int = 8, block_y: int = 32,
                             pair: bool = False,
                             interpret: Optional[object] = None
                             ) -> Tuple[Dict[str, jnp.ndarray],
                                        Optional[Dict[str, jnp.ndarray]]]:
    """Exterior pass of the overlapped substep: recompute the shard-edge
    blocks from the landed slabs, writing into ``f_partial``/
    ``w_partial`` via output aliasing (unvisited blocks keep the
    overlap kernel's interior results). ``strip`` selects the z-edge
    block rows ("z": kz in {0, nzg-1}, all ky) or the y-edge columns
    excluding those rows ("y": ky in {0, nyg-1}, kz in [1, nzg-1));
    together they cover exactly the blocks whose clamped windows were
    placeholders. Window values come from the halo kernel's own
    ``_mhd_window_plan`` (same slab selection → numerics identical to
    ``mhd_substep_halo_pallas``). ``fields``/``w`` are the PRE-substep
    state. ``pair=True`` recomputes the fused substep-0+1 update on
    radius-2R windows (slabs must carry 2R rows). Dead-w elision
    mirrors the overlap kernel: ``w=None`` drops the w read (valid
    only at alpha_s == 0); ``w_partial=None`` drops the w outputs and
    aliases (the substep-2 case — the returned new_w is then None).
    Reference: the exterior kernel launches of
    astaroth/astaroth.cu:552-646."""
    from ..models.astaroth import FIELDS, RK3_ALPHA, RK3_BETA, mhd_rates
    from .fd6 import FieldData
    from .pallas_mhd import mhd_pair_update

    if interpret is None:
        interpret = default_interpret()
    hr = 2 * R if pair else R
    Z, Y, X = fields[FIELDS[0]].shape
    esub = mhd_tile(fields[FIELDS[0]].dtype)
    comp = compute_dtype(fields[FIELDS[0]].dtype)
    bz, by = mhd_halo_blocks(Z, Y, block_z, block_y, esub, X=X,
                             itemsize=jnp.dtype(
                                 fields[FIELDS[0]].dtype).itemsize)
    nzg = Z // bz
    nyg = Y // by
    if strip == "z":
        grid = (min(nzg, 2), nyg)

        def remap(i, j):
            return jnp.where(i == 0, 0, nzg - 1), j
    else:
        assert nzg > 2, "y strip only exists between the z strips"
        grid = (nzg - 2, min(nyg, 2))

        def remap(i, j):
            return i + 1, jnp.where(j == 0, 0, nyg - 1)

    dtype = fields[FIELDS[0]].dtype
    dta = jnp.dtype(comp)
    inv_ds = (1.0 / prm.dsx, 1.0 / prm.dsy, 1.0 / prm.dsz)
    alpha = float(RK3_ALPHA[s])
    beta = float(RK3_BETA[s])
    dt_ = float(dt_phys)
    pad_lo = Dim3(0, R, R)
    interior = Dim3(X, by, bz)
    nf = len(FIELDS)

    plan_specs, inputs_for_field, select_window = _mhd_window_plan(
        Z, Y, X, bz, by, rr=hr, esub=esub)
    nseg = len(plan_specs)

    def rm(spec):
        return pl.BlockSpec(
            spec.block_shape,
            functools.partial(lambda i, j, m: m(*remap(i, j)),
                              m=spec.index_map))

    field_specs = [rm(sp) for sp in plan_specs]
    main_spec = rm(pl.BlockSpec((bz, by, X), lambda kz, ky: (kz, ky, 0)))

    # pair (and w=None at alpha_s == 0) never reads w
    if w is None and not pair:
        assert alpha == 0.0, "w=None is only valid when alpha_s == 0"
    nw = 0 if (pair or w is None) else nf
    write_w = w_partial is not None
    nwo = nf if write_w else 0

    def kern(*refs):
        field_refs = refs[:nseg * nf]
        w_refs = refs[nseg * nf:nseg * nf + nw]
        # aliased f_partial/w_partial inputs follow; never read in-kern
        out_f = refs[nseg * nf + nw + nf + nwo:
                     nseg * nf + nw + nwo + 2 * nf]
        out_w = refs[nseg * nf + nw + nwo + 2 * nf:]
        kz, ky = remap(pl.program_id(0), pl.program_id(1))
        wins = {q: select_window(field_refs[nseg * i:nseg * (i + 1)],
                                 kz=kz, ky=ky)
                for i, q in enumerate(FIELDS)}
        if pair:
            f2, w2 = mhd_pair_update(wins, prm, dtype, dt_phys, bz, by)
            for i, q in enumerate(FIELDS):
                if nwo:
                    out_w[i][...] = w2[q]
                out_f[i][...] = f2[q]
            return
        data = {q: FieldData(wins[q].astype(comp), inv_ds, pad_lo,
                             interior, x_wrap=True) for q in FIELDS}
        rates = mhd_rates(data, prm, comp)
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
    alias_base = len(inputs)
    for q in FIELDS:
        in_specs.append(main_spec)
        inputs.append(f_partial[q])
    if write_w:
        for q in FIELDS:
            in_specs.append(main_spec)
            inputs.append(w_partial[q])

    out_shape = [jax.ShapeDtypeStruct((Z, Y, X), dtype)
                 for _ in range(nf + nwo)]
    out_specs = [main_spec] * (nf + nwo)
    aliases = {alias_base + i: i for i in range(nf + nwo)}

    outs = pl.pallas_call(
        kern,
        name="mhd_substep_fixup",
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret,
    )(*inputs)
    new_f = {q: outs[i] for i, q in enumerate(FIELDS)}
    new_w = ({q: outs[nf + i] for i, q in enumerate(FIELDS)}
             if write_w else None)
    return new_f, new_w


def mhd_substep_overlap(fields: Dict[str, jnp.ndarray],
                        w: Optional[Dict[str, jnp.ndarray]],
                        s: int, prm, dt_phys: float, counts: Dim3,
                        block_z: int = 8, block_y: int = 32,
                        pair: bool = False,
                        write_w: bool = True,
                        interpret: Optional[object] = None
                        ) -> Tuple[Dict[str, jnp.ndarray],
                                   Optional[Dict[str, jnp.ndarray]]]:
    """One full overlapped substep: RDMA-overlap interior kernel, then
    the z- and y-strip exterior fix-ups. Drop-in equivalent of an
    exchange + ``mhd_substep_halo_pallas`` call (same numerics), with
    the exchange hidden behind the interior compute. ``pair=True`` is
    the fused substep-0+1 equivalent (one radius-2R overlapped
    exchange + one pass for two substeps). Dead-w elision as in
    ``mhd_substep_wrap_pallas``: ``w=None`` skips the w read sweep
    (alpha_s == 0 only), ``write_w=False`` skips the w write sweep
    and returns (new_fields, None)."""
    from ..models.astaroth import FIELDS

    Z, Y, X = fields[FIELDS[0]].shape
    bz, _by = mhd_halo_blocks(Z, Y, block_z, block_y,
                              mhd_tile(fields[FIELDS[0]].dtype), X=X,
                              itemsize=jnp.dtype(
                                  fields[FIELDS[0]].dtype).itemsize)
    nzg = Z // bz
    # the caller's interpret mode passes through VERBATIM: an
    # InterpretParams (e.g. detect_races=True from the sanitizer tests)
    # must reach the aliased fix-up kernels too
    f1, w1, slabs = mhd_substep_overlap_pallas(
        fields, w, s, prm, dt_phys, counts, block_z=block_z,
        block_y=block_y, pair=pair, write_w=write_w,
        interpret=interpret)
    f1, w1 = mhd_substep_fixup_pallas(
        fields, w, f1, w1, slabs, s, prm, dt_phys, "z",
        block_z=block_z, block_y=block_y, pair=pair,
        interpret=interpret)
    if nzg > 2:
        f1, w1 = mhd_substep_fixup_pallas(
            fields, w, f1, w1, slabs, s, prm, dt_phys, "y",
            block_z=block_z, block_y=block_y, pair=pair,
            interpret=interpret)
    return f1, w1
