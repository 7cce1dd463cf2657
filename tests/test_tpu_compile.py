"""The main path's Pallas kernels compile for a TPU v5e that is described,
not attached: the chip's own compiler refuses what interpret mode
accepts (unaligned slices, more VMEM than a kernel may use), at no chip
time. Real widths: Jacobi 512^3 and MHD 256^3 f32, as chip_smoke.py
runs them. Each compiled kernel keeps its stable name (its function's
name without ``_pallas``) under the ``stencil.kernel`` scope, which is
how a profile tells the kernels apart.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and every xdist worker
imports this file.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    """The described v5e 2x2, for the whole module."""
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
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
        finally:
            jax.config.update("jax_enable_compilation_cache", prior)
            cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, kernel, donate=()):
    """Compile ``fn`` for the described chip; every custom call in it
    must be one of ``kernel`` (a name or a tuple of names)."""
    kernels = (kernel,) if isinstance(kernel, str) else kernel
    # as deployed: 32-bit (conftest turns x64 on for the CPU suite, and
    # the kernels' index arithmetic does not lower for Mosaic in 64-bit)
    with jax.enable_x64(False):
        compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    calls = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls
    for line in calls:
        name = re.match(r"\s*(?:ROOT )?%([\w-]+)(\.\d+)? = ", line).group(1)
        op_name = re.search(r'op_name="([^"]*)"', line).group(1)
        assert name in kernels and name not in ("body", "kern"), line[:120]
        assert f"/stencil.kernel/{name}/pallas_call" in op_name, op_name
    return compiled


def _computations(hlo_text):
    """Compiled HLO text split into ``{computation name: its lines}``."""
    comps, name = {}, None
    for line in hlo_text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif name is not None:
            comps[name].append(line)
    return comps


def _while_bodies(compiled):
    """``(computations, names of the while loops' bodies)``."""
    comps = _computations(compiled.as_text())
    bodies = {m.group(1) for lines in comps.values() for line in lines
              for m in [re.search(r" while\(.*\bbody=%([\w.\-]+)", line)]
              if m}
    return comps, bodies


def _calls(lines, kernel):
    """Custom calls of Pallas kernel ``kernel`` among ``lines``."""
    call = re.compile(rf"%{kernel}(\.\d+)? = .* custom-call\(")
    return sum(bool(call.search(line)) for line in lines)


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
             _spec((n, n, n), one_chip), kernel="jacobi7_wrapn")


def test_jacobi_wrap_run_loop_512_has_no_carry_copy(one_chip):
    """The program Jacobi3D(512^3).run(n) compiles on one chip: interior
    sliced out of the padded field, the wrap run loop, interior written
    back, ``n`` traced. Its loop body chains two pair-kernel calls, so
    XLA needs no full-grid copy of the loop carry there (with one call
    per body it copied 512^3 floats before every call)."""
    from stencil_tpu.geometry import Dim3, Radius
    from stencil_tpu.models.jacobi import run_loop, sphere_geometry
    from stencil_tpu.ops.pallas_stencil import (jacobi7_wrap_pallas,
                                                jacobi7_wrapn_pallas)
    from stencil_tpu.ops.stencil_kernels import read_interior, write_interior

    n = 512
    hot, cold, sph_r = sphere_geometry(Dim3(n, n, n))
    alloc_r = Radius.constant(1)

    def single(q):
        return jacobi7_wrap_pallas(q, tuple(hot), tuple(cold), sph_r,
                                   interpret=False)

    def pair(q):
        return jacobi7_wrapn_pallas(q, tuple(hot), tuple(cold), sph_r,
                                    steps=2, interpret=False)

    def steps(p, k):
        inner = read_interior(p, alloc_r, Dim3(n, n, n))
        inner = run_loop(inner, k, pair, single, 2)
        return write_interior(p, inner, alloc_r)

    compiled = _compile(steps, _spec((n + 2,) * 3, one_chip),
                        _spec((), one_chip, jnp.int32),
                        kernel=("jacobi7_wrapn", "jacobi7_wrap"), donate=0)
    comps, bodies = _while_bodies(compiled)

    def pair_calls(name):
        return _calls(comps[name], "jacobi7_wrapn")

    assert sorted(pair_calls(b) for b in bodies if pair_calls(b)) == [2]
    grid = re.compile(r"= f32\[512,512,512\]\S* copy\(")
    for name, lines in comps.items():
        if pair_calls(name) >= 2:
            assert not any(grid.search(line) for line in lines), name


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

    _compile(sub, fields, w, kernel="mhd_substep_wrap")


def test_mhd_wrap_run_loop_256_program(one_chip):
    """The loop program Astaroth(256^3).run(n) compiles on one chip (the
    wrap path, no pair kernel): the shared interior-resident RK3 loop,
    ``n`` traced, fields and w donated. Its while body holds the three
    substep calls, and no copy of a 256^3 field appears anywhere."""
    from stencil_tpu.models.astaroth import (FIELDS, MhdParams,
                                             resident_loop)
    from stencil_tpu.ops.pallas_mhd import mhd_substep_wrap_pallas

    n = 256
    prm = MhdParams()

    def substep(f, w, s, write_w=True):
        return mhd_substep_wrap_pallas(f, w, s, prm, prm.dt,
                                       write_w=write_w, interpret=False)

    fields = {q: _spec((n, n, n), one_chip) for q in FIELDS}
    compiled = _compile(resident_loop(substep), fields, dict(fields),
                        _spec((), one_chip, jnp.int32),
                        kernel="mhd_substep_wrap", donate=(0, 1))
    comps, bodies = _while_bodies(compiled)
    assert sorted(_calls(comps[b], "mhd_substep_wrap") for b in bodies) \
        == [3]
    grid = re.compile(r"= f32\[256,256,256\]\S* copy\(")
    for name, lines in comps.items():
        assert not any(grid.search(line) for line in lines), name


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
             _spec((3,), one_chip, jnp.int32), kernel="jacobi7_halon")


def test_jacobi_halo_run_loop_4chip_has_no_carry_copy(topo):
    """The program Jacobi3D(512^3).run(n) compiles on four chips as the
    halo path builds it: mesh (1, 1, 4), each 128 x 512 x 512 interior
    sliced out of its padded shard, radius-2 slab exchange and the halo
    pair kernel through the shared run loop, interior written back,
    ``n`` traced, the field donated. The loop body chains two pair
    calls, so XLA needs no copy of the shard to carry the loop (with one
    call per body it copied the shard before every call)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from stencil_tpu.geometry import Dim3, Radius
    from stencil_tpu.models.jacobi import run_loop, sphere_geometry
    from stencil_tpu.ops.pallas_halo import (fit_pair_halo_blocks,
                                             jacobi7_halo_pallas,
                                             jacobi7_halon_pallas)
    from stencil_tpu.ops.stencil_kernels import read_interior, write_interior
    from stencil_tpu.parallel.exchange import (exchange_interior_slabs,
                                               shard_origin)
    from stencil_tpu.parallel.mesh import make_mesh
    from stencil_tpu.partition import partition_dims_even_xfree

    n, chips, steps, tile = 512, 4, 2, 8
    gsize = Dim3(n, n, n)
    counts = partition_dims_even_xfree(gsize, chips)
    assert tuple(counts) == (1, 1, 4)
    local = Dim3(n // counts.x, n // counts.y, n // counts.z)
    bz, by = fit_pair_halo_blocks(local.z, local.y, local.x, 4, steps)
    hot, cold, sph_r = sphere_geometry(gsize)
    alloc_r = Radius.constant(1)
    mesh = make_mesh(counts, topo.devices[:chips])

    def shard_steps(p, k):
        ox, oy, oz = shard_origin(local, Dim3(0, 0, 0))
        org = jnp.stack([oz, oy, ox]).astype(jnp.int32)

        def single(q):
            slabs = exchange_interior_slabs(q, counts, rz=1, ry=tile)
            return jacobi7_halo_pallas(q, slabs, org, tuple(hot),
                                       tuple(cold), sph_r, interpret=False)

        def pair(q):
            slabs = exchange_interior_slabs(q, counts, rz=bz, ry=tile,
                                            radius_rows=steps,
                                            y_z_extended=True)
            return jacobi7_halon_pallas(q, slabs, org, (n, n, n),
                                        tuple(hot), tuple(cold), sph_r,
                                        steps=steps, block_z=bz,
                                        block_y=by, interpret=False)

        inner = read_interior(p, alloc_r, local)
        inner = run_loop(inner, k, pair, single, steps)
        return write_interior(p, inner, alloc_r)

    spec = P("z", "y", "x")
    program = jax.shard_map(shard_steps, mesh=mesh, in_specs=(spec, P()),
                            out_specs=spec, check_vma=False)
    field = (counts.z * (local.z + 2), local.y + 2, local.x + 2)
    compiled = _compile(program,
                        _spec(field, NamedSharding(mesh, spec)),
                        _spec((), NamedSharding(mesh, P()), jnp.int32),
                        kernel=("jacobi7_halon", "jacobi7_halo"), donate=0)
    comps, bodies = _while_bodies(compiled)

    def pair_calls(name):
        return _calls(comps[name], "jacobi7_halon")

    assert sorted(pair_calls(b) for b in bodies if pair_calls(b)) == [2]
    shard = re.compile(r"= f32\[128,512,512\]\S* copy\(")
    for name in bodies:
        assert not any(shard.search(line) for line in comps[name]), name


def test_mhd_halo_run_loop_4chip_program(topo):
    """The loop program Astaroth(256 x 256 x 1024).run(n) compiles on
    four chips as the halo path builds it: mesh (1, 1, 4), y local, so
    each substep exchanges radius-3 z slabs only and runs the y-local
    window (``mhd_substep_haloz``, blocks (8, 8)) on each 256^3 shard.
    The while body holds the three substep calls and no copy of a
    shard."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from stencil_tpu.geometry import Dim3
    from stencil_tpu.models.astaroth import (FIELDS, MhdParams,
                                             resident_loop)
    from stencil_tpu.ops.pallas_halo import (R, mhd_halo_blocks,
                                             mhd_substep_halo_pallas)
    from stencil_tpu.parallel.exchange import exchange_interior_slabs
    from stencil_tpu.parallel.mesh import make_mesh
    from stencil_tpu.partition import partition_dims_even_xfree

    n, chips, tile = 256, 4, 8
    counts = partition_dims_even_xfree(Dim3(n, n, n * chips), chips)
    assert tuple(counts) == (1, 1, 4)
    bz, by = mhd_halo_blocks(n, n, 8, 32, tile, X=n, itemsize=4,
                             y_local=True)
    assert (bz, by) == (8, 8)
    prm = MhdParams()
    mesh = make_mesh(counts, topo.devices[:chips])

    def substep(f, w, s, write_w=True):
        slabs = {q: exchange_interior_slabs(f[q], counts, rz=bz, ry=tile,
                                            radius_rows=R, z_only=True)
                 for q in FIELDS}
        return mhd_substep_halo_pallas(f, w, slabs, s, prm, prm.dt,
                                       write_w=write_w, interpret=False)

    spec = {q: P("z", "y", "x") for q in FIELDS}
    program = jax.shard_map(resident_loop(substep), mesh=mesh,
                            in_specs=(spec, spec, P()),
                            out_specs=(spec, spec), check_vma=False)
    sharding = NamedSharding(mesh, P("z", "y", "x"))
    fields = {q: _spec((n * chips, n, n), sharding) for q in FIELDS}
    compiled = _compile(program, fields, dict(fields),
                        _spec((), NamedSharding(mesh, P()), jnp.int32),
                        kernel="mhd_substep_haloz", donate=(0, 1))
    comps, bodies = _while_bodies(compiled)
    assert sorted(_calls(comps[b], "mhd_substep_haloz") for b in bodies) \
        == [3]
    shard = re.compile(r"= f32\[256,256,256\]\S* copy\(")
    for name in bodies:
        assert not any(shard.search(line) for line in comps[name]), name



def test_jacobi_wrap_single_step(one_chip):
    """The single-step wrap kernel (the tail of an odd run(n))."""
    from stencil_tpu.geometry import Dim3
    from stencil_tpu.models.jacobi import sphere_geometry
    from stencil_tpu.ops.pallas_stencil import jacobi7_wrap_pallas

    n = 128
    hot, cold, sph_r = sphere_geometry(Dim3(n, n, n))
    _compile(lambda x: jacobi7_wrap_pallas(x, tuple(hot), tuple(cold),
                                           sph_r, interpret=False),
             _spec((n, n, n), one_chip), kernel="jacobi7_wrap")


def test_mhd_wrap_pair_substep(one_chip):
    """The fused substep-0+1 MHD wrap kernel (exchange_every=2)."""
    from stencil_tpu.models.astaroth import FIELDS, MhdParams
    from stencil_tpu.ops.pallas_mhd import mhd_substep01_wrap_pallas

    n = 64
    prm = MhdParams()
    fields = {q: _spec((n, n, n), one_chip) for q in FIELDS}
    _compile(lambda f: mhd_substep01_wrap_pallas(f, prm, prm.dt,
                                                 interpret=False),
             fields, kernel="mhd_substep01_wrap")
