"""The one-chip wrap path on grids that are no multiple of the (8, 128)
tile (the reference's 750^3-per-GPU weak run is one): x is the full lane
extent at any size, and a y extent that is no multiple of the sublane
tile is stepped in a stored extent padded up to a multiple of the
kernels' block height, wrapped at the logical extent. Interpreted
kernels on one CPU device, against the dense reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stencil_tpu.geometry import Dim3, Radius
from stencil_tpu.models.jacobi import (Jacobi3D, dense_reference_step,
                                       sphere_geometry)
from stencil_tpu.ops.pallas_stencil import (jacobi7_wrap_pallas,
                                            jacobi7_wrapn_pallas,
                                            wrap_layout)
from stencil_tpu.ops.stencil_kernels import read_interior, write_interior


def _model(x, y, z, **kw):
    return Jacobi3D(x, y, z, mesh_shape=(1, 1, 1), dtype=np.float32,
                    kernel="wrap", devices=jax.devices()[:1], **kw)


def _dense(state, n, size: Dim3):
    hot, cold, r = sphere_geometry(size)
    for _ in range(n):
        state = dense_reference_step(state, tuple(hot), tuple(cold), r)
    return state


@pytest.mark.parametrize("xyz", [(150, 30, 25), (136, 14, 9)],
                         ids=lambda v: "x".join(map(str, v)))
@pytest.mark.parametrize("n", [1, 2, 3, 32])
def test_unaligned_wrap_matches_dense_reference(xyz, n):
    """Y % 8 != 0, X % 128 != 0, odd Z: the wrap path with the pair
    kernel matches the dense periodic reference; it is bitwise the same
    kernels called one at a time on the padded stored array (``n // 2``
    pair calls, then the single-step tail), and agrees with the
    single-step path to the last bits (the pair kernel sums a point's
    six neighbours in another order than the single-step kernel, on
    aligned grids too)."""
    x, y, z = xyz
    j = _model(x, y, z)
    assert j.kernel_path == "wrap" and j.step_stride == 2
    stored_y = j._wrap_layout[0]
    assert stored_y % 8 == 0 and stored_y > y
    assert j._run_attrs(n)["stored_zyx"] == f"{z}x{stored_y}x{x}"
    rng = np.random.default_rng(100 * n + y)
    state = rng.random((z, y, x), dtype=np.float32)
    j.dd.set_interior("temp", state)
    j.run(n)
    got = j.temperature()
    np.testing.assert_allclose(got, _dense(state, n, Dim3(x, y, z)),
                               atol=1e-6)

    hot, cold, r = sphere_geometry(Dim3(x, y, z))
    kw = dict(hot_c=tuple(hot), cold_c=tuple(cold), sph_r=r, y_extent=y)
    pair = jax.jit(lambda q: jacobi7_wrapn_pallas(q, steps=2, **kw))
    single = jax.jit(lambda q: jacobi7_wrap_pallas(q, **kw))
    q = jnp.pad(jnp.asarray(state), ((0, 0), (0, stored_y - y), (0, 0)))
    for _ in range(n // 2):
        q = pair(q)
    for _ in range(n % 2):
        q = single(q)
    np.testing.assert_array_equal(got, np.asarray(q)[:, :y])

    singles = _model(x, y, z, exchange_every=1)
    assert singles.step_stride == 1
    singles.dd.set_interior("temp", state)
    singles.run(n)
    np.testing.assert_allclose(got, singles.temperature(), rtol=0,
                               atol=4e-7)


@pytest.mark.parametrize("steps,bz,by,stored_y", [
    (1, 3, 8, 48),    # last logical block partial, two padding blocks
    (1, 9, 16, 32),
    (2, 3, 8, 48),
    (2, 9, 16, 32),
    (2, 1, 24, 48),
    (3, 3, 8, 40),
    (4, 3, 8, 32),
])
def test_kernels_wrap_at_the_logical_y_extent(steps, bz, by, stored_y):
    """Explicit blocks over a stored extent holding Y = 30 logical rows,
    padding rows NaN: the kernels never read them as a neighbour, and
    the logical rows are ``steps`` dense periodic steps."""
    z, y, x = 9, 30, 136
    hot, cold, r = (x // 3, y // 2, z // 2), (2 * x // 3, y // 2, z // 2), 4
    t = np.random.default_rng(steps * 1000 + by).random(
        (z, y, x)).astype(np.float32)
    stored = np.full((z, stored_y, x), np.nan, np.float32)
    stored[:, :y] = t
    kw = dict(block_z=bz, block_y=by, interpret=True, y_extent=y)
    if steps == 1:
        out = jacobi7_wrap_pallas(jnp.asarray(stored), hot, cold, r, **kw)
    else:
        out = jacobi7_wrapn_pallas(jnp.asarray(stored), hot, cold, r,
                                   steps=steps, **kw)
    got = np.asarray(out)[:, :y]
    want = t
    for _ in range(steps):
        want = dense_reference_step(want, hot, cold, r)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=1e-6)


def test_wrap_layout_pads_only_unaligned_y():
    """Aligned grids keep their extent; the 750^3 share is padded in y
    to a multiple of the pair kernel's block height (a few percent),
    and the pair kernel is declined where the rows before row 0 would
    straddle two sublane slabs."""
    assert wrap_layout(512, 512, 512, jnp.float32, 2) == (512, 2)
    stored_y, n = wrap_layout(750, 750, 750, jnp.float32, 2)
    assert n == 2 and stored_y % 8 == 0 and 750 < stored_y <= 768
    assert wrap_layout(9, 17, 136, jnp.float32, 2)[1] == 1
    j = _model(136, 17, 9)
    assert j.kernel_path == "wrap" and j.step_stride == 1
    state = np.random.default_rng(3).random((9, 17, 136), dtype=np.float32)
    j.dd.set_interior("temp", state)
    j.run(3)
    np.testing.assert_allclose(j.temperature(),
                               _dense(state, 3, Dim3(136, 17, 9)),
                               atol=1e-6)


def test_stored_layout_round_trip():
    """read_interior(stored=) puts the interior at the stored array's
    low corner in one op; write_interior(interior=) takes it back."""
    r = Radius.constant(1)
    interior = Dim3(5, 6, 3)
    p = jnp.asarray(np.random.default_rng(0).random((5, 8, 7)),
                    jnp.float32)
    s = read_interior(p, r, interior, Dim3(5, 16, 3))
    assert s.shape == (3, 16, 5)
    np.testing.assert_array_equal(s[:, :6], p[1:4, 1:7, 1:6])
    back = write_interior(jnp.zeros_like(p), s * 2, r, interior)
    np.testing.assert_array_equal(back[1:4, 1:7, 1:6], p[1:4, 1:7, 1:6] * 2)


def test_untileable_grid_runs_xla_and_says_why(monkeypatch, capsys):
    """A grid no wrap block shape fits (the minimal block's lane rows
    overflow VMEM) takes the XLA path under ``auto`` on the chip, with
    the planner's reason in the log; ``kernel='wrap'`` refuses it."""
    import stencil_tpu.ops.pallas_stencil as ps

    monkeypatch.setattr(ps, "on_tpu", lambda: True)
    x, y, z = 200_000, 8, 2
    j = Jacobi3D(x, y, z, mesh_shape=(1, 1, 1), dtype=np.float32,
                 kernel="auto", devices=jax.devices()[:1])
    assert j.kernel_path == "xla"
    assert "no wrap block shape fits" in capsys.readouterr().err
    with pytest.raises(ValueError, match="cannot tile"):
        Jacobi3D(x, y, z, mesh_shape=(1, 1, 1), dtype=np.float32,
                 kernel="wrap", devices=jax.devices()[:1])
