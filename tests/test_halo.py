"""Parity tests for the halo-aware fused Pallas kernels
(ops/pallas_halo.py + parallel/exchange.exchange_interior_slabs):
the multi-device analog of the single-chip wrap kernels, checked
against the dense single-device oracles on the 8-device CPU mesh
(the reference's method-sweep oracle pattern,
test/test_cuda_mpi_exchange.cu:193-234)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from stencil_tpu.geometry import Dim3
from stencil_tpu.models.jacobi import dense_reference_step
from stencil_tpu.ops.pallas_halo import jacobi7_halo_pallas
from stencil_tpu.parallel.exchange import (exchange_interior_slabs,
                                           shard_origin)
from stencil_tpu.parallel.mesh import make_mesh, mesh_dim


def _run_halo_jacobi(global_zyx: np.ndarray, mesh_shape, iters: int = 2):
    """Drive the interior-resident halo step under shard_map."""
    gz, gy, gx = global_zyx.shape
    gsize = Dim3(gx, gy, gz)
    mesh = make_mesh(mesh_shape, jax.devices()[:Dim3.of(mesh_shape).flatten()])
    counts = mesh_dim(mesh)
    assert counts.x == 1, "halo kernels require x unsharded"
    local = Dim3(gx // counts.x, gy // counts.y, gz // counts.z)
    hot = (gsize.x // 3, gsize.y // 2, gsize.z // 2)
    cold = (gsize.x * 2 // 3, gsize.y // 2, gsize.z // 2)
    sph_r = gsize.x // 10
    esub = 8 if local.y % 8 == 0 else 1

    def shard_steps(p, n):
        ox, oy, oz = shard_origin(local, Dim3(0, 0, 0))
        org = jnp.stack([oz, oy, ox]).astype(jnp.int32)

        def body(_, q):
            slabs = exchange_interior_slabs(q, counts, rz=1, ry=esub)
            return jacobi7_halo_pallas(q, slabs, org, hot, cold, sph_r)

        return lax.fori_loop(0, n, body, p)

    spec = P("z", "y", "x")
    sm = jax.shard_map(shard_steps, mesh=mesh, in_specs=(spec, P()),
                       out_specs=spec, check_vma=False)
    fn = jax.jit(sm, donate_argnums=0)
    arr = jax.device_put(jnp.asarray(global_zyx),
                         NamedSharding(mesh, spec))
    return np.asarray(fn(arr, jnp.asarray(iters, jnp.int32)))


@pytest.mark.parametrize("mesh_shape", [(1, 1, 1), (1, 2, 4), (1, 4, 2),
                                        (1, 1, 8), (1, 8, 1)])
def test_jacobi_halo_matches_dense(mesh_shape):
    """(x, y, z) mesh shapes with x unsharded; 2 steps vs dense oracle."""
    gz, gy, gx = 16, 16, 30
    rng = np.random.default_rng(7)
    init = rng.uniform(0.0, 1.0, size=(gz, gy, gx)).astype(np.float32)
    hot = (gx // 3, gy // 2, gz // 2)
    cold = (gx * 2 // 3, gy // 2, gz // 2)
    sph_r = gx // 10
    want = init
    for _ in range(2):
        want = dense_reference_step(want, hot, cold, sph_r)
    got = _run_halo_jacobi(init, mesh_shape, iters=2)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("gzyx,mesh_shape,blocks", [
    ((9, 16, 16), (1, 1, 2), (1, 8)),    # bz=1: row Lz-1 in block nzb-2
    ((9, 17, 16), (1, 2, 2), (1, 8)),    # + uneven y
    ((10, 15, 16), (1, 2, 2), (2, 8)),   # uneven y only, small blocks
])
def test_jacobi_halo_uneven_small_blocks(gzyx, mesh_shape, blocks):
    """Uneven (+-1) shards with explicit small blockings: the zhi slab
    must be fetched with the true y-block wherever row Lz-1 falls
    (regression: the revisit-cache pin to y-block 0 leaked into the
    short shard's last interior row when bz == 1 and nyb > 1)."""
    from stencil_tpu.parallel.exchange import shard_interior_len

    gz, gy, gx = gzyx
    mesh = make_mesh(mesh_shape,
                     jax.devices()[:Dim3.of(mesh_shape).flatten()])
    counts = mesh_dim(mesh)
    from stencil_tpu.numerics import div_ceil
    local = Dim3(gx, div_ceil(gy, counts.y), div_ceil(gz, counts.z))
    rem = Dim3(0, gy % counts.y, gz % counts.z)
    hot = (gx // 3, gy // 2, gz // 2)
    cold = (gx * 2 // 3, gy // 2, gz // 2)
    sph_r = gx // 10
    esub = 8 if local.y % 8 == 0 else 1
    bz, by = blocks

    def shard_step(p):
        ox, oy, oz = shard_origin(local, rem)
        org = jnp.stack([oz, oy, ox]).astype(jnp.int32)
        lens = jnp.stack([
            jnp.asarray(shard_interior_len(2, local.z, rem)),
            jnp.asarray(shard_interior_len(1, local.y, rem)),
        ]).astype(jnp.int32)
        slabs = exchange_interior_slabs(p, counts, rz=1, ry=esub,
                                        rem=rem)
        return jacobi7_halo_pallas(p, slabs, org, hot, cold, sph_r,
                                   block_z=bz, block_y=by,
                                   interior_len_zy=lens)

    spec = P("z", "y", "x")
    sm = jax.jit(jax.shard_map(shard_step, mesh=mesh, in_specs=spec,
                               out_specs=spec, check_vma=False))
    rng = np.random.default_rng(13)
    # capacity-padded global: valid data in the per-shard interiors
    capz = local.z * counts.z
    capy = local.y * counts.y
    init = rng.uniform(0.0, 1.0, (gz, gy, gx)).astype(np.float64)
    want = dense_reference_step(init, hot, cold, sph_r)
    # scatter into capacity layout
    cap = np.zeros((capz, capy, gx))
    for iz in range(counts.z):
        for iy in range(counts.y):
            Lz = local.z - (1 if rem.z and iz >= rem.z else 0)
            Ly = local.y - (1 if rem.y and iy >= rem.y else 0)
            oz = iz * local.z - max(iz - rem.z, 0) if rem.z else iz * local.z
            oy = iy * local.y - max(iy - rem.y, 0) if rem.y else iy * local.y
            cap[iz * local.z:iz * local.z + Lz,
                iy * local.y:iy * local.y + Ly] = \
                init[oz:oz + Lz, oy:oy + Ly]
    got_cap = np.asarray(sm(jax.device_put(
        jnp.asarray(cap), NamedSharding(mesh, spec))))
    # gather back from capacity layout
    got = np.zeros_like(want)
    for iz in range(counts.z):
        for iy in range(counts.y):
            Lz = local.z - (1 if rem.z and iz >= rem.z else 0)
            Ly = local.y - (1 if rem.y and iy >= rem.y else 0)
            oz = iz * local.z - max(iz - rem.z, 0) if rem.z else iz * local.z
            oy = iy * local.y - max(iy - rem.y, 0) if rem.y else iy * local.y
            got[oz:oz + Lz, oy:oy + Ly] = \
                got_cap[iz * local.z:iz * local.z + Lz,
                        iy * local.y:iy * local.y + Ly]
    np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-12)


@pytest.mark.parametrize("mesh_shape,blocks,steps", [
    ((1, 1, 1), (4, 8), 2),   # nzg=4, nyg=2 on one shard (wrapped slabs)
    ((1, 2, 2), (4, 8), 2),   # sharded + interior blocks both axes
    ((1, 2, 2), (2, 8), 2),   # bz=2 == steps: thinnest legal z block
    ((1, 2, 2), (4, 8), 3),   # depth 3: radius-3 exchange, deeper rings
    ((1, 1, 2), (8, 8), 4),   # depth 4 on a z-split mesh
])
def test_jacobi_halo_pair_multiblock(mesh_shape, blocks, steps):
    """The N-step halo kernel with MULTI-BLOCK grids (nzg > 1 and/or
    nyg > 1): exercises the in-shard ring singles, clamped corner maps,
    and revisit-cache slab pinning that the model-level tests (whose
    small shards collapse to one block) never select."""
    from stencil_tpu.ops.pallas_halo import jacobi7_halon_pallas

    gz, gy, gx = 16, 16, 30
    rng = np.random.default_rng(11)
    init = rng.uniform(0.0, 1.0, size=(gz, gy, gx)).astype(np.float32)
    hot = (gx // 3, gy // 2, gz // 2)
    cold = (gx * 2 // 3, gy // 2, gz // 2)
    sph_r = gx // 10
    bz, by = blocks

    mesh = make_mesh(mesh_shape,
                     jax.devices()[:Dim3.of(mesh_shape).flatten()])
    counts = mesh_dim(mesh)
    local = Dim3(gx, gy // counts.y, gz // counts.z)

    def shard_pair(p):
        ox, oy, oz = shard_origin(local, Dim3(0, 0, 0))
        org = jnp.stack([oz, oy, ox]).astype(jnp.int32)
        slabs = exchange_interior_slabs(p, counts, rz=bz, ry=8,
                                        radius_rows=steps,
                                        y_z_extended=True)
        return jacobi7_halon_pallas(p, slabs, org, (gz, gy, gx), hot,
                                    cold, sph_r, steps=steps,
                                    block_z=bz, block_y=by)

    spec = P("z", "y", "x")
    sm = jax.shard_map(shard_pair, mesh=mesh, in_specs=spec,
                       out_specs=spec, check_vma=False)
    arr = jax.device_put(jnp.asarray(init), NamedSharding(mesh, spec))
    got = np.asarray(jax.jit(sm)(arr))

    want = init
    for _ in range(steps):
        want = dense_reference_step(want, hot, cold, sph_r)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("mesh_shape", [(1, 2, 4), (1, 1, 1)])
def test_jacobi3d_model_halo_kernel(mesh_shape):
    """Jacobi3D(kernel='halo') end-to-end through the orchestrator."""
    from stencil_tpu.models.jacobi import Jacobi3D

    gx, gy, gz = 30, 16, 16
    ndev = mesh_shape[0] * mesh_shape[1] * mesh_shape[2]
    j = Jacobi3D(gx, gy, gz, mesh_shape=mesh_shape, kernel="halo",
                 devices=jax.devices()[:ndev])
    j.init()
    j.run(3)

    hot = (gx // 3, gy // 2, gz // 2)
    cold = (gx * 2 // 3, gy // 2, gz // 2)
    want = np.full((gz, gy, gx), 0.5, dtype=np.float32)
    for _ in range(3):
        want = dense_reference_step(want, hot, cold, gx // 10)
    np.testing.assert_allclose(j.temperature(), want, rtol=1e-5, atol=1e-6)


class TestAstarothHalo:
    """MHD halo megakernel (mhd_substep_halo_pallas) parity and the
    interior-resident state protocol."""

    @pytest.mark.parametrize("mesh_shape,pair", [
        ((1, 2, 4), False),
        pytest.param((1, 1, 1), False, marks=pytest.mark.slow),
        # fused substep-0+1 pair (exchange_every=2): the (1,2,4) case
        # has nzg=nyg=1 (every block slab-fed on all four sides at the
        # rr=2R window), the (1,1,1) case (nzg=4) exercises in-shard
        # rr=6 rows
        ((1, 2, 4), True),
        pytest.param((1, 1, 1), True, marks=pytest.mark.slow),
        # the benchmark's mesh: y local, so the y-local window wraps y
        # in-kernel and reads z slabs only (mhd_substep_haloz)
        ((1, 1, 4), False),
        ((1, 1, 4), True)])
    def test_halo_matches_xla(self, mesh_shape, pair):
        from stencil_tpu.models.astaroth import FIELDS, Astaroth

        size = (16, 16, 32)   # (nx, ny, nz): local z/y stay multiples of 8
        ndev = mesh_shape[0] * mesh_shape[1] * mesh_shape[2]
        a = Astaroth(*size, mesh_shape=(1, 1, 1), dtype=np.float64,
                     devices=jax.devices()[:1], kernel="xla")
        b = Astaroth(*size, mesh_shape=mesh_shape, dtype=np.float64,
                     devices=jax.devices()[:ndev], kernel="halo",
                     exchange_every=2 if pair else None)
        assert b._slab_exchange_cfg["pair"] == pair
        assert b._slab_exchange_cfg["z_only"] == (mesh_shape[1] == 1)
        for m in (a, b):
            m.init()
            m.step()
            m.step()
        for q in FIELDS:
            np.testing.assert_allclose(b.field(q), a.field(q),
                                       rtol=1e-11, atol=1e-13, err_msg=q)

    def test_reinit_resets_state(self):
        """Regression (round-1 advisor): re-init() after stepping must
        not be silently discarded by the interior-resident cache."""
        from stencil_tpu.models.astaroth import Astaroth

        m = Astaroth(16, 16, 16, mesh_shape=(1, 2, 2), dtype=np.float64,
                     devices=jax.devices()[:4], kernel="halo")
        m.init()
        m.step()
        after_one = m.field("uux").copy()
        m.init()   # must flush + reset the interior cache
        m.step()
        np.testing.assert_array_equal(m.field("uux"), after_one)

    def test_set_interior_after_step_is_honored(self):
        """dd.set_interior between steps must reach the fast path."""
        from stencil_tpu.models.astaroth import Astaroth

        m = Astaroth(16, 16, 16, mesh_shape=(1, 2, 2), dtype=np.float64,
                     devices=jax.devices()[:4], kernel="halo")
        m.init()
        m.step()
        new_ss = np.zeros((16, 16, 16), dtype=np.float64)
        m.dd.set_interior("ss", new_ss)
        got = m.field("ss")
        np.testing.assert_array_equal(got, new_ss)


def test_jacobi_halo_uneven_y_blocks():
    """Shard sizes that are not multiples of 8 exercise the esub=1 slab
    fallback and block shrinking."""
    gz, gy, gx = 12, 12, 20
    rng = np.random.default_rng(3)
    init = rng.uniform(0.0, 1.0, size=(gz, gy, gx)).astype(np.float32)
    hot = (gx // 3, gy // 2, gz // 2)
    cold = (gx * 2 // 3, gy // 2, gz // 2)
    want = dense_reference_step(init, hot, cold, gx // 10)
    got = _run_halo_jacobi(init, (1, 2, 2), iters=1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
