"""Pallas kernel parity tests (interpreter-backed off-TPU).

Mirrors the reference's kernel unit tests (test/test_cuda_pack.cu,
test_derivative.cu): each Pallas kernel is checked against the XLA
slicing implementation it accelerates, and the pallas-kernel Jacobi
model is checked against the dense single-device oracle.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stencil_tpu.geometry import Dim3, Radius
from stencil_tpu.local_domain import raw_size, zyx_shape
from stencil_tpu.ops.fd6 import FieldData
from stencil_tpu.ops.pallas_stencil import jacobi7_pallas
from stencil_tpu.ops.stencil_kernels import jacobi7


@pytest.mark.parametrize("interior", [Dim3(8, 8, 8), Dim3(12, 10, 6)])
def test_jacobi7_pallas_matches_xla(interior):
    rng = np.random.default_rng(7)
    r = Radius.constant(1)
    p = jnp.asarray(rng.standard_normal(zyx_shape(raw_size(interior, r))),
                    dtype=jnp.float32)
    want = jacobi7(p, r, interior)
    got = jacobi7_pallas(p, r, interior, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


def test_jacobi7_pallas_asymmetric_radius():
    # pad offsets differ per side; kernel must honor pad_lo
    rng = np.random.default_rng(8)
    r = Radius.constant(1)
    r.set_dir((1, 0, 0), 2)   # x hi face radius 2
    r.set_dir((0, 0, -1), 3)  # z lo face radius 3
    interior = Dim3(6, 7, 8)
    p = jnp.asarray(rng.standard_normal(zyx_shape(raw_size(interior, r))),
                    dtype=jnp.float32)
    want = jacobi7(p, r, interior)
    got = jacobi7_pallas(p, r, interior, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


@pytest.mark.parametrize("bz,by", [(4, 8), (8, 128), (16, 16)])
def test_jacobi7_wrap_pallas_matches_oracle(bz, by):
    """The fused periodic single-chip kernel (wrap inside the kernel,
    no halo storage) against the dense reference step."""
    from stencil_tpu.models.jacobi import dense_reference_step
    from stencil_tpu.ops.pallas_stencil import jacobi7_wrap_pallas

    n = 16
    rng = np.random.default_rng(3)
    t = rng.random((n, n, n)).astype(np.float32)
    hot = (n // 3, n // 2, n // 2)
    cold = (2 * n // 3, n // 2, n // 2)
    want = dense_reference_step(t, hot, cold, n // 10)
    got = np.asarray(jacobi7_wrap_pallas(jnp.asarray(t), hot, cold, n // 10,
                                         block_z=bz, block_y=by,
                                         interpret=True))
    np.testing.assert_allclose(got, want, atol=1e-6)


@pytest.mark.parametrize("bz,by", [(4, 8), (16, 128), (8, 16)])
def test_jacobi7_wrap2_pallas_matches_two_steps(bz, by):
    """The temporally-blocked pair kernel (two fused iterations per
    HBM pass) against two dense reference steps — including sphere
    sources re-imposed between the fused steps and periodic-wrap
    coordinates for the step-1 edge ring."""
    from stencil_tpu.models.jacobi import dense_reference_step
    from stencil_tpu.ops.pallas_stencil import jacobi7_wrap2_pallas

    n = 16
    rng = np.random.default_rng(5)
    t = rng.random((n, n, n)).astype(np.float32)
    hot = (n // 3, n // 2, n // 2)
    cold = (2 * n // 3, n // 2, n // 2)
    want = dense_reference_step(
        dense_reference_step(t, hot, cold, n // 10), hot, cold, n // 10)
    got = np.asarray(jacobi7_wrap2_pallas(jnp.asarray(t), hot, cold,
                                          n // 10, block_z=bz, block_y=by,
                                          interpret=True))
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_jacobi_model_wrap_pair_and_tail_matches_oracle():
    """run(3) through the wrap path = one fused pair + one single-step
    tail; must match three sequential dense steps."""
    import jax

    from stencil_tpu.models.jacobi import Jacobi3D, dense_reference_step

    n = 16
    j = Jacobi3D(n, n, n, mesh_shape=(1, 1, 1), dtype=np.float32,
                 kernel="wrap", devices=jax.devices()[:1])
    j.init()
    temp = j.temperature()
    hot = (n // 3, n // 2, n // 2)
    cold = (2 * n // 3, n // 2, n // 2)
    for _ in range(3):
        temp = dense_reference_step(temp, hot, cold, n // 10)
    j.run(3)
    np.testing.assert_allclose(j.temperature(), temp, atol=2e-6)


def test_jacobi_model_wrap_kernel_matches_oracle():
    import jax

    from stencil_tpu.models.jacobi import Jacobi3D, dense_reference_step

    n = 16
    j = Jacobi3D(n, n, n, mesh_shape=(1, 1, 1), dtype=np.float32,
                 kernel="wrap", devices=jax.devices()[:1])
    j.init()
    temp = j.temperature()
    hot = (n // 3, n // 2, n // 2)
    cold = (2 * n // 3, n // 2, n // 2)
    for _ in range(2):
        temp = dense_reference_step(temp, hot, cold, n // 10)
        j.step()
    np.testing.assert_allclose(j.temperature(), temp, atol=1e-6)


def test_jacobi_model_full_pallas_path_matches_oracle():
    """Pallas compute kernel + Pallas RDMA exchange — the all-manual
    path (the reference's Colo*Kernel method analog)."""
    from stencil_tpu.models.jacobi import Jacobi3D, dense_reference_step
    from stencil_tpu.parallel.methods import Method

    n = 16
    j = Jacobi3D(n, n, n, mesh_shape=(2, 2, 2), dtype=np.float32,
                 kernel="pallas", methods=Method.PallasDMA)
    j.init()
    temp = j.temperature()
    hot = (n // 3, n // 2, n // 2)
    cold = (2 * n // 3, n // 2, n // 2)
    for _ in range(3):
        temp = dense_reference_step(temp, hot, cold, n // 10)
        j.step()
    np.testing.assert_allclose(j.temperature(), temp, atol=1e-5)


def test_jacobi_model_pallas_kernel_matches_oracle():
    from stencil_tpu.models.jacobi import Jacobi3D, dense_reference_step

    n = 16
    j = Jacobi3D(n, n, n, mesh_shape=(2, 2, 2), dtype=np.float32,
                 kernel="pallas")
    j.init()
    temp = j.temperature()
    hot = (n // 3, n // 2, n // 2)
    cold = (2 * n // 3, n // 2, n // 2)
    for _ in range(3):
        temp = dense_reference_step(temp, hot, cold, n // 10)
        j.step()
    np.testing.assert_allclose(j.temperature(), temp, atol=1e-5)


@pytest.mark.parametrize("kernel,mesh_shape", [
    ("wrap", (1, 1, 1)),     # pair kernel, 16-row bf16 edge slabs
    ("halo", (1, 2, 2)),     # slab-layout pair kernel, bf16 tiles
])
def test_jacobi_model_bf16(kernel, mesh_shape):
    """bfloat16 fields through the fused fast paths (the TPU-native
    analog of the reference's float/double templating,
    bin/jacobi3d.cu:40-85): the dtype's 16-row sublane tile changes
    every edge-slab block shape, so run the full model vs a float64
    dense oracle at bf16 tolerance."""
    import jax.numpy as jnp

    from stencil_tpu.models.jacobi import Jacobi3D, dense_reference_step

    n = 32
    ndev = mesh_shape[0] * mesh_shape[1] * mesh_shape[2]
    j = Jacobi3D(n, n, n, mesh_shape=mesh_shape, dtype=jnp.bfloat16,
                 kernel=kernel, devices=jax.devices()[:ndev])
    assert j.kernel_path == kernel
    j.init()
    j.run(2)
    hot = (n // 3, n // 2, n // 2)
    cold = (2 * n // 3, n // 2, n // 2)
    want = np.full((n, n, n), 0.5, dtype=np.float64)
    for _ in range(2):
        want = dense_reference_step(want, hot, cold, n // 10)
    got = np.asarray(j.temperature(), dtype=np.float64)
    # two bf16 steps: ~8 bits of mantissa -> absolute error ~1e-2
    np.testing.assert_allclose(got, want, atol=2e-2)


@pytest.mark.parametrize("steps,bz,by", [(1, 4, 8), (3, 4, 8),
                                         (3, 16, 128), (4, 2, 8),
                                         (4, 8, 8),   # slabbed N-row segs
                                         (5, 4, 16)])
def test_jacobi7_wrapn_pallas_matches_n_steps(steps, bz, by):
    """The generalized temporal-blocking kernel at depth N against N
    dense reference steps — the ring recompute, per-step sources, and
    wrapped single-row z fetches must hold at every depth (wrap2 is
    the N=2 special case, tested above)."""
    from stencil_tpu.models.jacobi import dense_reference_step
    from stencil_tpu.ops.pallas_stencil import jacobi7_wrapn_pallas

    n = 16
    rng = np.random.default_rng(6)
    t = rng.random((n, n, n)).astype(np.float32)
    hot = (n // 3, n // 2, n // 2)
    cold = (2 * n // 3, n // 2, n // 2)
    want = t
    for _ in range(steps):
        want = dense_reference_step(want, hot, cold, n // 10)
    got = np.asarray(jacobi7_wrapn_pallas(jnp.asarray(t), hot, cold,
                                          n // 10, steps=steps,
                                          block_z=bz, block_y=by,
                                          interpret=True))
    np.testing.assert_allclose(got, want, atol=3e-6)


def test_jacobi_model_wrap_exchange_every():
    """exchange_every=3 drives the wrap path in triples (+ tail)."""
    from stencil_tpu.models.jacobi import Jacobi3D, dense_reference_step

    n = 16
    j = Jacobi3D(n, n, n, mesh_shape=(1, 1, 1), dtype=np.float32,
                 kernel="wrap", devices=jax.devices()[:1],
                 exchange_every=3)
    j.init()
    j.run(4)   # one triple + one tail step
    hot = (n // 3, n // 2, n // 2)
    cold = (2 * n // 3, n // 2, n // 2)
    want = np.full((n, n, n), 0.5, dtype=np.float32)
    for _ in range(4):
        want = dense_reference_step(want, hot, cold, n // 10)
    np.testing.assert_allclose(j.temperature(), want, rtol=1e-5,
                               atol=1e-6)
