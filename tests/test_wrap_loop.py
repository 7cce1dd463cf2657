"""The single-chip wrap path's run loop chains two kernel calls per loop
iteration (so XLA needs no copy of the loop carry) and runs the odd
call and the single-step tail after it. The result must be bitwise the
one-call-per-iteration schedule: ``n // N`` N-step pair-kernel calls,
then ``n % N`` single-step calls, in that order. 16^3 float32 on one
CPU device, kernels interpreted."""

import jax
import numpy as np
import pytest

from stencil_tpu.geometry import Dim3
from stencil_tpu.models.jacobi import Jacobi3D, sphere_geometry
from stencil_tpu.ops.pallas_stencil import (jacobi7_wrap_pallas,
                                            jacobi7_wrapn_pallas)

SIZE = 16


def reference(state, n, N):
    """One kernel call at a time: ``n // N`` N-step calls (none when
    ``N`` is 1), then the single steps."""
    hot, cold, sph_r = sphere_geometry(Dim3(SIZE, SIZE, SIZE))
    single = jax.jit(lambda q: jacobi7_wrap_pallas(q, tuple(hot),
                                                   tuple(cold), sph_r))
    pair = jax.jit(lambda q: jacobi7_wrapn_pallas(q, tuple(hot),
                                                  tuple(cold), sph_r,
                                                  steps=N))
    q = jax.numpy.asarray(state)
    pairs = n // N if N > 1 else 0
    for _ in range(pairs):
        q = pair(q)
    for _ in range(n - pairs * N):
        q = single(q)
    return np.asarray(q)


@pytest.mark.parametrize("N,n", [(2, 1), (2, 2), (2, 3), (2, 4), (2, 5),
                                 (2, 6), (2, 9), (3, 7), (1, 5)])
def test_run_is_the_one_call_per_iteration_schedule(monkeypatch, N, n):
    monkeypatch.setenv("STENCIL_WRAP_STEPS", str(N))
    j = Jacobi3D(SIZE, SIZE, SIZE, mesh_shape=(1, 1, 1), dtype=np.float32,
                 kernel="wrap", devices=jax.devices()[:1])
    assert j.kernel_path == "wrap" and j.step_stride == N
    rng = np.random.default_rng(1000 + 10 * N + n)
    state = rng.random((SIZE,) * 3, dtype=np.float32)
    j.dd.set_interior("temp", state)
    j.run(n)
    np.testing.assert_array_equal(j.temperature(), reference(state, n, N))
