"""HBM bytes of the Jacobi halo path's kernel on each shard of an
x-unsharded mesh (``ops/pallas_halo.jacobi7_halon_pallas``;
``step_stride`` steps per call, 2 for the pair kernel): one call reads
the shard's (Z, Y, X) interior once and writes it once, and reads of
its slab buffers what the stencil needs: the ``steps`` rows next to the
shard of each (bz, Y, X) z slab (the other rows are filler the kernel
never fetches) and the rows of each (Z + 2 bz, 8, X) y slab that its
windows span, Z + 2 ``steps`` (Z for the single-step kernel, whose y
slabs are (Z, 8, X)). Block windows re-read interior rows, so real
traffic is higher: this is a floor."""

#: rows of a y slab: the float32 sublane tile
SUBLANE = 8


def bytes_per_call(z: int, y: int, x: int, steps: int,
                   itemsize: int = 4) -> int:
    """One call on one (z, y, x) shard."""
    interior = 2 * z * y * x
    zslabs = 2 * steps * y * x
    yslabs = 2 * (z + 2 * steps if steps > 1 else z) * SUBLANE * x
    return (interior + zslabs + yslabs) * itemsize


def bytes_per_step(cell, info) -> float:
    """Whole grid, all chips."""
    mx, my, mz = info["mesh_xyz"]
    z, y, x = cell.global_zyx()
    steps = info["step_stride"]
    return (mx * my * mz * bytes_per_call(z // mz, y // my, x // mx, steps)
            / steps)
