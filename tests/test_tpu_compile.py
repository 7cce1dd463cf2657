"""The main path's Pallas kernels compile for a TPU v5e that is described,
not attached: the chip's own compiler refuses what interpret mode
accepts (unaligned slices, more VMEM than a kernel may use), at no chip
time. Real widths: Jacobi 512^3 and MHD 256^3 f32, as chip_smoke.py
runs them.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and every xdist worker
imports this file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep it out
        prior = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", prior)
            cc.reset_cache()


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    # as deployed: 32-bit (conftest turns x64 on for the CPU suite, and
    # the kernels' index arithmetic does not lower for Mosaic in 64-bit)
    with jax.enable_x64(False):
        compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_jacobi_wrap_pair_512(one_chip):
    """The kernel Jacobi3D(512, 512, 512, kernel='auto') runs on one
    chip: the wrap pair (two steps per HBM pass)."""
    from stencil_tpu.geometry import Dim3
    from stencil_tpu.models.jacobi import sphere_geometry
    from stencil_tpu.ops.pallas_stencil import jacobi7_wrap2_pallas

    n = 512
    hot, cold, sph_r = sphere_geometry(Dim3(n, n, n))
    _compile(lambda x: jacobi7_wrap2_pallas(x, tuple(hot), tuple(cold),
                                            sph_r, interpret=False),
             _spec((n, n, n), one_chip))


@pytest.mark.parametrize("substep", [0, 1])
def test_mhd_wrap_substep_256(one_chip, substep):
    """The MHD wrap megakernel's substeps as Astaroth(256^3) runs them
    on one chip: substep 0 without the w read (alpha_0 == 0), substep
    1 with it."""
    from stencil_tpu.models.astaroth import FIELDS, MhdParams
    from stencil_tpu.ops.pallas_mhd import mhd_substep_wrap_pallas

    n = 256
    prm = MhdParams()
    fields = {q: _spec((n, n, n), one_chip) for q in FIELDS}
    w = None if substep == 0 else dict(fields)

    def sub(f, wk):
        return mhd_substep_wrap_pallas(f, wk, substep, prm, prm.dt,
                                       interpret=False)

    _compile(sub, fields, w)


def test_jacobi_halo_pair_4chip_shard(one_chip):
    """The kernel each chip runs for Jacobi3D(512^3) over four chips:
    auto picks the x-free mesh (1, 1, 4), so one shard is 512 x 512 x
    128 (x, y, z), and the halo pair kernel with the planner's blocks."""
    from stencil_tpu.geometry import Dim3
    from stencil_tpu.models.jacobi import sphere_geometry
    from stencil_tpu.ops.pallas_halo import (fit_pair_halo_blocks,
                                             jacobi7_halon_pallas)
    from stencil_tpu.partition import partition_dims_even_xfree

    n, chips, steps, tile = 512, 4, 2, 8
    gsize = Dim3(n, n, n)
    mesh = partition_dims_even_xfree(gsize, chips)
    assert tuple(mesh) == (1, 1, 4)
    Z, Y, X = n // mesh.z, n // mesh.y, n // mesh.x
    bz, by = fit_pair_halo_blocks(Z, Y, X, 4, steps)
    hot, cold, sph_r = sphere_geometry(gsize)
    slabs = {"zlo": _spec((bz, Y, X), one_chip),
             "zhi": _spec((bz, Y, X), one_chip),
             "ylo": _spec((Z + 2 * bz, tile, X), one_chip),
             "yhi": _spec((Z + 2 * bz, tile, X), one_chip)}

    def shard(q, sl, origin):
        return jacobi7_halon_pallas(q, sl, origin, (n, n, n), tuple(hot),
                                    tuple(cold), sph_r, steps=steps,
                                    block_z=bz, block_y=by,
                                    interpret=False)

    _compile(shard, _spec((Z, Y, X), one_chip), slabs,
             _spec((3,), one_chip, jnp.int32))

