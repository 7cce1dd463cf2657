"""Race detection over the manual-DMA data planes — the memcheck /
racecheck analog of the reference's CUDA-sanitizer CI step (reference:
ci/build.sh runs tests under cuda-memcheck; SURVEY.md section 5.2).

The Pallas TPU interpreter's vector-clock race detector
(``InterpretParams(detect_races=True)``) checks every DMA, semaphore,
and buffer access the RDMA exchange and the in-kernel overlap kernel
make; a detected race prints ``RACE DETECTED`` — these tests fail on
any such report while also pinning the numerics.
"""

import contextlib
import io

import jax
import pytest
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.experimental.pallas import tpu as pltpu

from stencil_tpu.geometry import Dim3, Radius
from stencil_tpu.parallel.mesh import make_mesh, mesh_dim

def _capture_races(fn):
    """Run ``fn`` with stdout captured; return (result, race_report)."""
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        out = fn()
    text = buf.getvalue()
    return out, ("RACE DETECTED" in text, text)


def test_detector_fires_on_deliberate_race():
    """Negative control: an unsynchronized remote write racing a local
    write MUST be reported — proves the detector wiring is not
    vacuously quiet for the race-free tests below."""
    from jax import lax
    from jax.experimental import pallas as pl

    mesh = make_mesh((1, 1, 2), jax.devices()[:2])

    def kern(in_ref, out_ref, vbuf, send, recv):
        me = lax.axis_index("z")
        other = lax.rem(me + 1, jnp.int32(2))
        # remote-write into the neighbor's out[0:1] while the neighbor
        # writes the same rows locally — no barrier, no ordering
        rc = pltpu.make_async_remote_copy(
            src_ref=in_ref.at[0:1], dst_ref=out_ref.at[0:1],
            send_sem=send.at[0], recv_sem=recv.at[0],
            device_id={"z": other})
        rc.start()
        vbuf[...] = jnp.zeros_like(vbuf)
        pltpu.make_async_copy(vbuf, out_ref.at[0:1], send.at[1]).start()
        pltpu.make_async_copy(vbuf, out_ref.at[0:1], send.at[1]).wait()
        rc.wait()

    def shard(p):
        return pl.pallas_call(
            kern,
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            out_shape=jax.ShapeDtypeStruct(p.shape, p.dtype),
            scratch_shapes=[pltpu.VMEM((1,) + p.shape[1:], p.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SemaphoreType.DMA((2,))],
            compiler_params=pltpu.CompilerParams(
                collective_id=7, has_side_effects=True),
            interpret=pltpu.InterpretParams(detect_races=True),
        )(p)

    sm = jax.jit(jax.shard_map(shard, mesh=mesh,
                               in_specs=P("z", "y", "x"),
                               out_specs=P("z", "y", "x"),
                               check_vma=False))
    a = jnp.asarray(np.random.default_rng(0)
                    .random((8, 8, 128)).astype(np.float32))
    arr = jax.device_put(a, NamedSharding(mesh, P("z", "y", "x")))
    _, (raced, _) = _capture_races(lambda: np.asarray(sm(arr)))
    assert raced, "race detector failed to flag a deliberate race"


def test_rdma_exchange_race_free():
    """The explicit inter-chip RDMA exchange (barrier + remote DMA
    choreography) under the race detector on a 2x2x2 mesh."""
    from stencil_tpu.parallel.pallas_exchange import exchange_shard_pallas

    mesh = make_mesh((2, 2, 2), jax.devices()[:8])
    counts = mesh_dim(mesh)
    radius = Radius.constant(1)
    params = pltpu.InterpretParams(detect_races=True)

    def shard(p):
        return exchange_shard_pallas(p, radius, counts,
                                     interpret=params)

    sm = jax.jit(jax.shard_map(shard, mesh=mesh, in_specs=P("z", "y", "x"),
                               out_specs=P("z", "y", "x"),
                               check_vma=False))
    rng = np.random.default_rng(3)
    a = rng.random((8, 8, 8)).astype(np.float32)
    arr = jax.device_put(jnp.asarray(a),
                         NamedSharding(mesh, P("z", "y", "x")))

    def run():
        out = np.asarray(sm(arr))
        return out

    out, (raced, text) = _capture_races(run)
    assert not raced, text[:2000]
    # interiors untouched by the exchange
    np.testing.assert_array_equal(out[1:3, 1:3, 1:3], a[1:3, 1:3, 1:3])


@pytest.mark.parametrize("dtype", ["f32", "bf16"])
def test_mhd_overlap_kernel_race_free(dtype):
    """The MHD in-kernel RDMA overlap substep (barrier + two-phase slab
    DMA concurrent with the fused mhd_rates block pipeline + aliased
    strip fix-ups) under the race detector on a (1,2,2) mesh — in f32
    (8-row slab tiles) and bf16 (16-row tiles, different DMA offsets)."""
    from stencil_tpu.models.astaroth import FIELDS, MhdParams
    from stencil_tpu.ops.pallas_mhd_overlap import mhd_substep_overlap

    mesh = make_mesh((1, 2, 2), jax.devices()[:4])
    counts = Dim3(1, 2, 2)
    prm = MhdParams()
    params = pltpu.InterpretParams(detect_races=True)
    dt = np.float32 if dtype == "f32" else jnp.bfloat16
    # one block/shard: local (8,8,8) f32, (16,16,8) bf16 (tile-16 z/y)
    gz, gy, gx = (16, 16, 8) if dtype == "f32" else (32, 32, 8)

    def shard(fields, w):
        f, wk = mhd_substep_overlap(fields, w, 0, prm, prm.dt, counts,
                                    interpret=params)
        return f, wk

    spec = P("z", "y", "x")
    fspec = {q: spec for q in FIELDS}
    sm = jax.jit(jax.shard_map(shard, mesh=mesh, in_specs=(fspec, fspec),
                               out_specs=(fspec, fspec), check_vma=False))
    rng = np.random.default_rng(11)
    sh = NamedSharding(mesh, spec)
    fields = {q: jax.device_put(
        jnp.asarray(rng.random((gz, gy, gx)).astype(np.float32) * 0.1,
                    dtype=dt), sh) for q in FIELDS}
    w = {q: jax.device_put(jnp.zeros((gz, gy, gx), dt), sh)
         for q in FIELDS}

    out, (raced, text) = _capture_races(
        lambda: jax.tree.map(np.asarray, sm(fields, w)))
    assert not raced, text[:2000]
    f_out, _ = out
    for q in FIELDS:
        assert np.all(np.isfinite(np.asarray(f_out[q], np.float32))), q


@pytest.mark.parametrize("dtype", ["f32", "bf16"])
def test_mhd_overlap_pair_kernel_race_free(dtype):
    """The PACKED (fused substep-0+1, pair=True) MHD overlap kernel
    under the race detector: radius-2R slab RDMA concurrent with the
    fused pair update + aliased strip fix-ups. The 2R transfers use
    different slab offsets than the radius-R substep path, so this is
    a distinct DMA choreography from test_mhd_overlap_kernel_race_free."""
    from stencil_tpu.models.astaroth import FIELDS, MhdParams
    from stencil_tpu.ops.pallas_mhd_overlap import mhd_substep_overlap

    mesh = make_mesh((1, 2, 2), jax.devices()[:4])
    counts = Dim3(1, 2, 2)
    prm = MhdParams()
    params = pltpu.InterpretParams(detect_races=True)
    dt = np.float32 if dtype == "f32" else jnp.bfloat16
    # pair mode needs 2R=6 <= min(bz, esub): 8-row f32 tiles, 16 bf16
    gz, gy, gx = (16, 16, 8) if dtype == "f32" else (32, 32, 8)

    def shard(fields):
        f, wk = mhd_substep_overlap(fields, None, 0, prm, prm.dt, counts,
                                    pair=True, interpret=params)
        return f, wk

    spec = P("z", "y", "x")
    fspec = {q: spec for q in FIELDS}
    sm = jax.jit(jax.shard_map(shard, mesh=mesh, in_specs=(fspec,),
                               out_specs=(fspec, fspec), check_vma=False))
    rng = np.random.default_rng(17)
    sh = NamedSharding(mesh, spec)
    fields = {q: jax.device_put(
        jnp.asarray(rng.random((gz, gy, gx)).astype(np.float32) * 0.1,
                    dtype=dt), sh) for q in FIELDS}

    out, (raced, text) = _capture_races(
        lambda: jax.tree.map(np.asarray, sm(fields)))
    assert not raced, text[:2000]
    f_out, _ = out
    for q in FIELDS:
        assert np.all(np.isfinite(np.asarray(f_out[q], np.float32))), q


def test_pair_overlap_negative_control_missing_barrier():
    """Negative control for the packed-overlap choreography: the same
    shape of bug the pair kernel's rendezvous prevents — a remote slab
    write issued WITHOUT the neighbor barrier, racing the neighbor's
    local initialization of that slab buffer. MUST be reported."""
    from jax import lax
    from jax.experimental import pallas as pl

    mesh = make_mesh((1, 1, 2), jax.devices()[:2])
    R2 = 6  # pair-mode halo rows (2R)

    def kern(in_ref, out_ref, slab, send, recv):
        me = lax.axis_index("z")
        other = lax.rem(me + 1, jnp.int32(2))
        # the neighbor is still zero-filling its slab buffer when the
        # remote write lands: no rendezvous, unsynchronized
        slab[...] = jnp.zeros_like(slab)
        rc = pltpu.make_async_remote_copy(
            src_ref=in_ref.at[0:R2], dst_ref=slab.at[0:R2],
            send_sem=send.at[0], recv_sem=recv.at[0],
            device_id={"z": other})
        rc.start()
        rc.wait()
        pltpu.sync_copy(in_ref, out_ref)

    def shard(p):
        return pl.pallas_call(
            kern,
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            out_shape=jax.ShapeDtypeStruct(p.shape, p.dtype),
            scratch_shapes=[pltpu.VMEM((R2 + 2,) + p.shape[1:], p.dtype),
                            pltpu.SemaphoreType.DMA((1,)),
                            pltpu.SemaphoreType.DMA((1,))],
            compiler_params=pltpu.CompilerParams(
                collective_id=9, has_side_effects=True),
            interpret=pltpu.InterpretParams(detect_races=True),
        )(p)

    sm = jax.jit(jax.shard_map(shard, mesh=mesh,
                               in_specs=P("z", "y", "x"),
                               out_specs=P("z", "y", "x"),
                               check_vma=False))
    a = jnp.asarray(np.random.default_rng(5)
                    .random((16, 8, 128)).astype(np.float32))
    arr = jax.device_put(a, NamedSharding(mesh, P("z", "y", "x")))
    _, (raced, _) = _capture_races(lambda: np.asarray(sm(arr)))
    assert raced, "race detector failed to flag an unbarriered slab write"


def _uneven_rdma_exchange(off_by_one: bool):
    """One z-axis uneven (+-1 remainder) RDMA halo fill on a 2-shard
    ring: capacity-sized allocations, shard 1 one row short (rem=1).
    Each shard locally fills its ACTUAL interior [r, r+L) while remote
    writes land in the halos — correct dynamic placement puts the hi
    halo at [r+L, r+L+r) (disjoint); ``off_by_one=True`` plants the
    remainder-rule bug (destination at r+L-1, overlapping the last
    interior row the neighbor is writing) which MUST race."""
    from jax import lax
    from jax.experimental import pallas as pl

    mesh = make_mesh((1, 1, 2), jax.devices()[:2])
    r = 1
    cap = 8                    # interior capacity; shard 1 holds cap-1
    rem = 1                    # first `rem` shards are full-length
    alloc = cap + 2 * r

    def kern(in_ref, out_ref, buf, send, recv):
        me = lax.axis_index("z")
        n = jnp.int32(2)
        up = lax.rem(me + 1, n)
        dn = lax.rem(me + n - 1, n)
        # rendezvous: destination halos quiescent before remote writes
        bsem = pltpu.get_barrier_semaphore()
        pltpu.semaphore_signal(bsem, inc=1, device_id={"z": up})
        pltpu.semaphore_signal(bsem, inc=1, device_id={"z": dn})
        pltpu.semaphore_wait(bsem, 2)

        def actual_len(i):
            return jnp.int32(cap) - (i >= jnp.int32(rem)).astype(jnp.int32)

        L_me = actual_len(me)
        L_up = actual_len(up)
        # my top interior rows -> up neighbor's LO halo [0, r) (static)
        top = pltpu.make_async_remote_copy(
            src_ref=in_ref.at[pl.ds(r + L_me - r, r)],
            dst_ref=out_ref.at[pl.ds(0, r)],
            send_sem=send.at[0], recv_sem=recv.at[0],
            device_id={"z": up})
        # my bottom interior rows -> up neighbor's HI halo at its
        # actual interior end r+L (the partition.hpp:55-69 rule);
        # the negative control lands one row low, inside the
        # neighbor's interior
        dst_off = r + L_up - (1 if off_by_one else 0)
        bot = pltpu.make_async_remote_copy(
            src_ref=in_ref.at[pl.ds(r, r)],
            dst_ref=out_ref.at[pl.ds(dst_off, r)],
            send_sem=send.at[1], recv_sem=recv.at[1],
            device_id={"z": up})
        top.start()
        bot.start()
        # concurrent local fill of my ACTUAL interior rows [r, r+L)
        # (the halo regions are remote-write-only: disjoint when the
        # placement is correct); ANY refs move only by DMA, so the fill
        # is staged through VMEM
        pltpu.sync_copy(in_ref, buf)
        i = jnp.arange(alloc)[:, None, None]
        interior = jnp.logical_and(i >= r, i < r + L_me)
        buf[...] = jnp.where(interior, buf[...], jnp.zeros_like(buf))
        pltpu.sync_copy(buf.at[pl.ds(r, 1)], out_ref.at[pl.ds(r, 1)])
        idx = jnp.minimum(r + L_me - 1, jnp.int32(alloc - 1))
        pltpu.sync_copy(buf.at[pl.ds(idx, 1)], out_ref.at[pl.ds(idx, 1)])
        top.wait()
        bot.wait()

    def shard(p):
        return pl.pallas_call(
            kern,
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            out_shape=jax.ShapeDtypeStruct(p.shape, p.dtype),
            scratch_shapes=[pltpu.VMEM(p.shape, p.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SemaphoreType.DMA((2,))],
            compiler_params=pltpu.CompilerParams(
                collective_id=8, has_side_effects=True),
            interpret=pltpu.InterpretParams(detect_races=True),
        )(p)

    sm = jax.jit(jax.shard_map(shard, mesh=mesh,
                               in_specs=P("z", "y", "x"),
                               out_specs=P("z", "y", "x"),
                               check_vma=False))
    a = jnp.asarray(np.random.default_rng(21)
                    .random((2 * alloc, 8, 128)).astype(np.float32))
    arr = jax.device_put(a, NamedSharding(mesh, P("z", "y", "x")))
    _, (raced, text) = _capture_races(lambda: np.asarray(sm(arr)))
    return raced, text


def test_uneven_rdma_exchange_race_free():
    """Uneven (+-1 remainder) RDMA halo placement: dynamic hi-halo
    destinations at each shard's ACTUAL interior end must not overlap
    the neighbor's concurrent interior writes."""
    raced, text = _uneven_rdma_exchange(off_by_one=False)
    assert not raced, text[:2000]


def test_uneven_rdma_exchange_negative_control():
    """Negative control: the classic remainder-rule off-by-one (halo
    landed at r+L-1, inside the short neighbor's interior) MUST be
    reported as a race."""
    raced, _ = _uneven_rdma_exchange(off_by_one=True)
    assert raced, ("race detector failed to flag an off-by-one uneven "
                   "halo placement")


def test_overlap_kernel_race_free():
    """The in-kernel RDMA overlap step (remote slab DMA concurrent with
    the interior compute pipeline) under the race detector."""
    from functools import partial

    from stencil_tpu.models.jacobi import dense_reference_step
    from stencil_tpu.ops.pallas_overlap import jacobi7_overlap_pallas

    mesh = make_mesh((1, 2, 2), jax.devices()[:4])
    counts = Dim3(1, 2, 2)
    N = 16
    params = pltpu.InterpretParams(detect_races=True)
    hot = (N // 3, N // 2, N // 2)
    cold = (2 * N // 3, N // 2, N // 2)

    def shard(q):
        iz = jax.lax.axis_index("z")
        iy = jax.lax.axis_index("y")
        org = jnp.stack([iz * (N // 2), iy * (N // 2),
                         jnp.int32(0)]).astype(jnp.int32)
        return jacobi7_overlap_pallas(q, org, hot, cold, N // 10,
                                      counts, block_z=4,
                                      interpret=params)

    sm = jax.jit(jax.shard_map(shard, mesh=mesh, in_specs=P("z", "y", "x"),
                               out_specs=P("z", "y", "x"),
                               check_vma=False))
    rng = np.random.default_rng(9)
    a = rng.random((N, N, N)).astype(np.float32)
    arr = jax.device_put(jnp.asarray(a),
                         NamedSharding(mesh, P("z", "y", "x")))

    out, (raced, text) = _capture_races(lambda: np.asarray(sm(arr)))
    assert not raced, text[:2000]
    want = dense_reference_step(a, hot, cold, N // 10)
    np.testing.assert_allclose(out, want, rtol=2e-6, atol=2e-6)
