"""Halo-exchange correctness: the ripple oracle.

Reproduces the single most important reference test pattern
(test/test_exchange.cu:12-33,126-191): initialize every point of the
global grid with an analytic coordinate function, run one exchange, copy
the full padded region (including halos) of every shard to host, then
verify every halo point equals the oracle at the periodically-wrapped
global coordinate.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from stencil_tpu.geometry import Dim3, Radius
from stencil_tpu.local_domain import raw_size, zyx_shape
from stencil_tpu.parallel.exchange import (make_exchange,
                                           exchanged_bytes_per_sweep)
from stencil_tpu.parallel.mesh import make_mesh, mesh_dim
from stencil_tpu.parallel.methods import Method

RIPPLE = [1.0, 0.25, 0.5, 0.75]


def ripple(x, y, z):
    """f(p) = x + r[x%4] + y + r[y%4] + z + r[z%4]
    (reference: test/test_exchange.cu:12-33)."""
    return (x + RIPPLE[x % 4]) + (y + RIPPLE[y % 4]) + (z + RIPPLE[z % 4])


def make_padded_global(gsize: Dim3, mesh, radius: Radius) -> jnp.ndarray:
    """Build the global padded (z,y,x) array: each shard's interior holds
    the oracle values; halos start at a sentinel."""
    md = mesh_dim(mesh)
    local = gsize // md
    pr = raw_size(local, radius)
    full = np.full(zyx_shape(pr * md), -1000.0, dtype=np.float64)
    lo = radius.pad_lo()
    for bz in range(md.z):
        for by in range(md.y):
            for bx in range(md.x):
                block = np.zeros(zyx_shape(local))
                for lz in range(local.z):
                    for ly in range(local.y):
                        for lx in range(local.x):
                            gx = bx * local.x + lx
                            gy = by * local.y + ly
                            gz = bz * local.z + lz
                            block[lz, ly, lx] = ripple(gx, gy, gz)
                z0 = bz * pr.z + lo.z
                y0 = by * pr.y + lo.y
                x0 = bx * pr.x + lo.x
                full[z0:z0 + local.z, y0:y0 + local.y, x0:x0 + local.x] = block
    arr = jnp.asarray(full)
    return jax.device_put(arr, NamedSharding(mesh, P("z", "y", "x")))


def check_halos(host: np.ndarray, gsize: Dim3, mesh, radius: Radius,
                check_diagonals: bool = True):
    """Verify every halo point of every shard equals ripple(wrap(p))."""
    md = mesh_dim(mesh)
    local = gsize // md
    pr = raw_size(local, radius)
    lo = radius.pad_lo()
    bad = 0
    for bz in range(md.z):
        for by in range(md.y):
            for bx in range(md.x):
                z0, y0, x0 = bz * pr.z, by * pr.y, bx * pr.x
                blk = host[z0:z0 + pr.z, y0:y0 + pr.y, x0:x0 + pr.x]
                for lz in range(pr.z):
                    for ly in range(pr.y):
                        for lx in range(pr.x):
                            # global coordinate of this padded cell
                            gx = bx * local.x + lx - lo.x
                            gy = by * local.y + ly - lo.y
                            gz = bz * local.z + lz - lo.z
                            want = ripple(gx % gsize.x, gy % gsize.y,
                                          gz % gsize.z)
                            got = blk[lz, ly, lx]
                            if abs(got - want) > 1e-12:
                                bad += 1
                                assert bad < 5, (
                                    f"halo mismatch at block ({bx},{by},{bz}) "
                                    f"local ({lx},{ly},{lz}) global "
                                    f"({gx},{gy},{gz}): got {got}, want {want}")
    assert bad == 0


@pytest.fixture(scope="module")
def mesh222():
    return make_mesh((2, 2, 2))


class TestExchangeOracle:
    @pytest.mark.parametrize("method", [Method.PpermuteSlab,
                                        Method.PpermutePacked,
                                        Method.AllGather,
                                        Method.PallasDMA])
    def test_radius1_2x2x2(self, mesh222, method):
        gsize = Dim3(8, 8, 8)
        radius = Radius.constant(1)
        arr = make_padded_global(gsize, mesh222, radius)
        ex = make_exchange(mesh222, radius, method)
        out = ex({"q": arr})["q"]
        check_halos(np.asarray(out), gsize, mesh222, radius)

    def test_radius2_2x2x2(self, mesh222):
        gsize = Dim3(8, 8, 8)
        radius = Radius.constant(2)
        arr = make_padded_global(gsize, mesh222, radius)
        ex = make_exchange(mesh222, radius, Method.Default)
        out = ex({"q": arr})["q"]
        check_halos(np.asarray(out), gsize, mesh222, radius)

    def test_asymmetric_radius(self, mesh222):
        # uncentered kernel: +x 2, -x 1, +y 1, -y 0, z 0
        gsize = Dim3(8, 8, 8)
        radius = Radius.constant(0)
        radius.set_dir((1, 0, 0), 2)
        radius.set_dir((-1, 0, 0), 1)
        radius.set_dir((0, 1, 0), 1)
        arr = make_padded_global(gsize, mesh222, radius)
        ex = make_exchange(mesh222, radius, Method.Default)
        out = ex({"q": arr})["q"]
        # only face halos on padded sides exist; check full padded region
        check_halos(np.asarray(out), gsize, mesh222, radius)

    def test_pallas_dma_radius2(self, mesh222):
        gsize = Dim3(8, 8, 8)
        radius = Radius.constant(2)
        arr = make_padded_global(gsize, mesh222, radius)
        ex = make_exchange(mesh222, radius, Method.PallasDMA)
        out = ex({"q": arr})["q"]
        check_halos(np.asarray(out), gsize, mesh222, radius)

    def test_pallas_dma_asymmetric_1d(self):
        # uncentered kernel over a deep 1D ring: +x 2, -x 1
        mesh = make_mesh((8, 1, 1))
        gsize = Dim3(16, 4, 4)
        radius = Radius.constant(0)
        radius.set_dir((1, 0, 0), 2)
        radius.set_dir((-1, 0, 0), 1)
        arr = make_padded_global(gsize, mesh, radius)
        ex = make_exchange(mesh, radius, Method.PallasDMA)
        out = ex({"q": arr})["q"]
        check_halos(np.asarray(out), gsize, mesh, radius)

    def test_anisotropic_mesh_1d(self):
        mesh = make_mesh((8, 1, 1))
        gsize = Dim3(16, 4, 4)
        radius = Radius.constant(1)
        arr = make_padded_global(gsize, mesh, radius)
        ex = make_exchange(mesh, radius, Method.Default)
        out = ex({"q": arr})["q"]
        check_halos(np.asarray(out), gsize, mesh, radius)

    def test_multi_quantity(self, mesh222):
        gsize = Dim3(8, 8, 8)
        radius = Radius.constant(1)
        a = make_padded_global(gsize, mesh222, radius)
        b = (make_padded_global(gsize, mesh222, radius) * 2.0)
        ex = make_exchange(mesh222, radius, Method.PpermutePacked)
        out = ex({"a": a, "b": b})
        check_halos(np.asarray(out["a"]), gsize, mesh222, radius)
        md = mesh_dim(mesh222)
        local = gsize // md
        pr = raw_size(local, radius)
        host_b = np.asarray(out["b"])
        # b = 2*a everywhere in interiors, so halos must be 2*oracle
        lo = radius.pad_lo()
        assert host_b[0, lo.y, lo.x] == pytest.approx(
            2 * ripple(0, 0, (0 - lo.z) % gsize.z))


class TestSingleDeviceWrap:
    """mesh_counts == 1 on every axis: the periodic neighbor is the
    shard itself (the reference's same-GPU PeerAccessSender analog)."""

    def test_local_wrap(self):
        gsize = Dim3(6, 6, 6)
        radius = Radius.constant(2)
        mesh = make_mesh((1, 1, 1), devices=jax.devices()[:1])
        arr = make_padded_global(gsize, mesh, radius)
        ex = make_exchange(mesh, radius, Method.Default)
        out = ex({"q": arr})["q"]
        check_halos(np.asarray(out), gsize, mesh, radius)


class TestByteCounters:
    def test_counts(self):
        radius = Radius.constant(2)
        shape = (12, 12, 12)  # padded shard
        counts = Dim3(2, 2, 1)
        b = exchanged_bytes_per_sweep(shape, radius, counts, elem_size=4)
        assert b["x"] == 4 * (2 + 2) * 12 * 12
        assert b["y"] == 4 * (2 + 2) * 12 * 12
        assert b["z"] == 0  # single shard along z: local wrap
