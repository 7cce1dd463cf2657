"""The multi-device halo path runs through the same run loop as the wrap
path: two chained kernel calls per loop iteration (so XLA needs no copy
of the loop carry), the odd call and the single-step tail after it. The
result must be bitwise the one-call-at-a-time schedule: ``n // 2``
two-step pair-kernel calls, each fed by a radius-2 slab exchange, then
``n % 2`` single-step calls fed by 1-row slabs; or, where the pair
kernel is off, ``n`` single-step calls. 32 x 32 x 64 float32 on four
CPU devices, mesh (1, 1, 4), kernels interpreted."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from stencil_tpu.geometry import Dim3
from stencil_tpu.models.jacobi import Jacobi3D, sphere_geometry
from stencil_tpu.ops.pallas_halo import (fit_pair_halo_blocks,
                                         jacobi7_halo_pallas,
                                         jacobi7_halon_pallas)
from stencil_tpu.parallel.exchange import (exchange_interior_slabs,
                                           shard_origin)
from stencil_tpu.parallel.mesh import make_mesh

X, Y, Z = 32, 32, 64
CHIPS = 4
COUNTS = Dim3(1, 1, CHIPS)
LOCAL = Dim3(X, Y, Z // CHIPS)
TILE = 8
SPEC = P("z", "y", "x")


@functools.lru_cache(maxsize=None)
def calls():
    """The two per-call programs, each one ``shard_map``-ped kernel call
    on the interior-resident global field: the two-step pair call and
    the single-step call."""
    mesh = make_mesh(COUNTS, jax.devices()[:CHIPS])
    hot, cold, sph_r = sphere_geometry(Dim3(X, Y, Z))
    bz, by = fit_pair_halo_blocks(LOCAL.z, LOCAL.y, LOCAL.x, 4, 2)

    def origin():
        ox, oy, oz = shard_origin(LOCAL, Dim3(0, 0, 0))
        return jnp.stack([oz, oy, ox]).astype(jnp.int32)

    def single(q):
        slabs = exchange_interior_slabs(q, COUNTS, rz=1, ry=TILE)
        lens = jnp.asarray([LOCAL.z, LOCAL.y], jnp.int32)
        return jacobi7_halo_pallas(q, slabs, origin(), hot, cold, sph_r,
                                   interior_len_zy=lens)

    def pair(q):
        slabs = exchange_interior_slabs(q, COUNTS, rz=bz, ry=TILE,
                                        radius_rows=2, y_z_extended=True)
        return jacobi7_halon_pallas(q, slabs, origin(), (Z, Y, X), hot,
                                    cold, sph_r, steps=2, block_z=bz,
                                    block_y=by)

    def program(fn):
        return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=SPEC,
                                     out_specs=SPEC, check_vma=False))

    return mesh, program(pair), program(single)


def reference(state, n, N):
    """One kernel call at a time: ``n // 2`` pair calls when ``N`` is 2
    (none when it is 1), then the single steps."""
    mesh, pair, single = calls()
    q = jax.device_put(state, NamedSharding(mesh, SPEC))
    pairs = n // 2 if N == 2 else 0
    for _ in range(pairs):
        q = pair(q)
    for _ in range(n - 2 * pairs):
        q = single(q)
    return np.asarray(q)


@pytest.mark.parametrize("N,n", [(2, 1), (2, 2), (2, 3), (2, 4), (2, 5),
                                 (2, 6), (2, 9), (2, 32), (1, 5)])
def test_halo_run_is_the_one_call_per_iteration_schedule(N, n):
    j = Jacobi3D(X, Y, Z, dtype=np.float32, devices=jax.devices()[:CHIPS],
                 kernel="halo", exchange_every=N)
    assert j.kernel_path == "halo" and j.step_stride == N
    assert list(j.dd.placement.dim()) == list(COUNTS)
    rng = np.random.default_rng(2000 + 10 * N + n)
    state = rng.random((Z, Y, X), dtype=np.float32)
    j.dd.set_interior("temp", state)
    j.run(n)
    np.testing.assert_array_equal(j.temperature(), reference(state, n, N))
