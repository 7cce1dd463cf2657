"""Interior/exterior overlap decomposition correctness.

The overlapped step must produce the same state as the fused step
(the reference validates its overlap choreography the same way: the
jacobi/astaroth results don't depend on the interior/exterior split,
bin/jacobi3d.cu:296-377)."""

import numpy as np
import pytest

from stencil_tpu.geometry import Dim3, Radius
from stencil_tpu.parallel.overlap import split_regions


class TestSplitRegions:
    def test_covers_interior(self):
        local = Dim3(8, 6, 5)
        r = Radius.constant(2)
        inner, ext = split_regions(r, local)
        seen = np.zeros((local.z, local.y, local.x), dtype=int)
        for off, dims in inner + ext:
            seen[off.z:off.z + dims.z, off.y:off.y + dims.y,
                 off.x:off.x + dims.x] += 1
        assert (seen >= 1).all(), "every interior point computed"
        # inner region covered exactly once
        assert seen[2:-2, 2:-2, 2:-2].max() == 1

    def test_inner_reads_stay_owned(self):
        local = Dim3(8, 8, 8)
        r = Radius.constant(3)
        inner, _ = split_regions(r, local)
        (off, dims), = inner
        for a, (o, d) in enumerate(((off.x, dims.x), (off.y, dims.y),
                                    (off.z, dims.z))):
            assert o - r.face(a, -1) >= 0
            assert o + d + r.face(a, 1) <= local[a]

    def test_thin_shard_no_inner(self):
        local = Dim3(4, 4, 4)
        r = Radius.constant(2)
        inner, ext = split_regions(r, local)
        assert inner == []
        assert len(ext) == 1  # whole interior as one region

    def test_asymmetric_radius_slabs(self):
        local = Dim3(8, 8, 8)
        r = Radius.constant(0)
        r.set_dir((1, 0, 0), 2)
        r.set_dir((-1, 0, 0), 1)
        inner, ext = split_regions(r, local)
        (off, dims), = inner
        assert (off.x, dims.x) == (1, 5)  # [1, 8-2)
        assert (off.y, dims.y) == (0, 8)
        assert len(ext) == 2  # only +-x slabs


def test_jacobi_overlap_matches_fused():
    from stencil_tpu.models.jacobi import Jacobi3D

    n = 16
    a = Jacobi3D(n, n, n, mesh_shape=(2, 2, 2), dtype=np.float32)
    b = Jacobi3D(n, n, n, mesh_shape=(2, 2, 2), dtype=np.float32,
                 overlap=True)
    a.init()
    b.init()
    for _ in range(4):
        a.step()
        b.step()
    np.testing.assert_allclose(b.temperature(), a.temperature(), atol=1e-6)


def test_jacobi_overlap_kernel_in_kernel_rdma():
    """overlap=True on an x-unsharded even mesh routes to the in-kernel
    RDMA overlap kernel (ops/pallas_overlap.py) — interior computed
    while slabs fly, faces fixed after. Must match the dense oracle
    over several steps, odd and even counts (ripple analog of
    reference src/stencil.cu:1081-1118 overlap choreography)."""
    import jax

    from stencil_tpu.models.jacobi import Jacobi3D, dense_reference_step

    n = 32
    for mesh_shape in [(1, 2, 4), (1, 4, 2)]:
        # kernel="halo" + overlap opts into the RDMA overlap kernel
        # even off-TPU (auto only takes it on hardware)
        j = Jacobi3D(n, n, n, mesh_shape=mesh_shape, dtype=np.float32,
                     overlap=True, kernel="halo")
        # confirm the overlap kernel path was selected (not the XLA
        # interior/exterior split)
        assert j.kernel_path == "overlap", j.kernel_path
        j.init()
        temp = j.temperature()
        hot = (n // 3, n // 2, n // 2)
        cold = (2 * n // 3, n // 2, n // 2)
        for _ in range(3):
            temp = dense_reference_step(temp, hot, cold, n // 10)
            j.step()
        np.testing.assert_allclose(j.temperature(), temp, atol=2e-6,
                                   err_msg=str(mesh_shape))
        j.run(2)
        for _ in range(2):
            temp = dense_reference_step(temp, hot, cold, n // 10)
        np.testing.assert_allclose(j.temperature(), temp, atol=2e-6)


@pytest.mark.slow
@pytest.mark.parametrize("mesh_shape,size,thinz,pair", [
    # (1,2,2) on (16,16,48): local (16,8,24) -> nzg=3, exercising BOTH
    # fix-up strips (z edges + the middle y strip); (1,1,2) on
    # (16,16,32): local z=16 -> nzg=2, z strips cover everything and
    # the y axis is a local wrap; the thinz=0 case runs the slabless
    # interior plan AND the fix-up plan in tiled-z mode
    ((1, 2, 2), (16, 16, 48), "1", "0"),
    ((1, 1, 2), (16, 16, 32), "1", "0"),
    ((1, 1, 2), (16, 16, 32), "0", "0"),
    # tiled-z through BOTH fix-up strips (nzg=3 -> the y strip's
    # tiled z-segment remap is exercised too)
    ((1, 2, 2), (16, 16, 48), "0", "0"),
    # fused substep-0+1 pair composed with the overlap path: one
    # radius-2R overlapped exchange per pair, both fix-up strips —
    # under both window plans (tiled-z slices rr=6 differently)
    ((1, 2, 2), (16, 16, 48), "1", "1"),
    ((1, 1, 2), (16, 16, 32), "0", "1")])
def test_astaroth_rdma_overlap_matches_xla(mesh_shape, size, thinz,
                                           pair, monkeypatch):
    """The in-kernel RDMA overlap path (ops/pallas_mhd_overlap.py):
    slab RDMA behind the fused interior compute + strip fix-ups must
    match the XLA oracle exactly like the sequential halo path does
    (reference choreography: astaroth/astaroth.cu:552-646)."""
    import jax

    from stencil_tpu.models.astaroth import FIELDS, Astaroth

    monkeypatch.setenv("STENCIL_MHD_THINZ", thinz)
    monkeypatch.setenv("STENCIL_MHD_PAIR", pair)

    ndev = mesh_shape[0] * mesh_shape[1] * mesh_shape[2]
    a = Astaroth(*size, mesh_shape=(1, 1, 1), dtype=np.float64,
                 devices=jax.devices()[:1], kernel="xla")
    b = Astaroth(*size, mesh_shape=mesh_shape, dtype=np.float64,
                 devices=jax.devices()[:ndev], kernel="halo",
                 overlap=True)
    assert b.kernel_path == "halo-overlap", b.kernel_path
    # the pair cases must actually engage pair mode (guard against the
    # gate silently falling back to the already-covered non-pair path)
    assert b._slab_exchange_cfg["pair"] == (pair == "1")
    for m in (a, b):
        m.init()
        m.step()
        m.step()
    for q in FIELDS:
        np.testing.assert_allclose(b.field(q), a.field(q), rtol=1e-11,
                                   atol=1e-13, err_msg=q)


@pytest.mark.slow
def test_astaroth_overlap_matches_fused():
    from stencil_tpu.models.astaroth import Astaroth, MhdParams

    prm = MhdParams()
    a = Astaroth(16, 16, 16, params=prm, mesh_shape=(2, 2, 2),
                 dtype=np.float64)
    b = Astaroth(16, 16, 16, params=prm, mesh_shape=(2, 2, 2),
                 dtype=np.float64, overlap=True)
    a.init()
    b.init()
    a.step()
    b.step()
    for q in ("lnrho", "uux", "ss", "ax"):
        np.testing.assert_allclose(b.field(q), a.field(q),
                                   rtol=1e-10, atol=1e-12)
