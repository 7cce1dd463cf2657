"""Writes the seeded initial state straight into a program's padded,
sharded fields on the device, in one jitted call per field. The
program's own ``set_interior`` builds the padded grid on the host and
copies it over, which would make set-up mostly host work. Halo cells
are zero, as ``set_interior`` leaves them: a program that skips its
first exchange then reads zeros, not the right neighbours."""

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark.harness import seeded


def write_fields(dd, reference, seed: int, global_zyx) -> None:
    """``dd.curr[name]`` = the reference's initial field ``name`` for
    ``seed``, laid out as ``dd`` lays out its padded shards."""
    mesh = tuple(dd.placement.dim())[::-1]
    local = tuple(dd.local_size)[::-1]
    lo = tuple(dd.alloc_radius.pad_lo())[::-1]
    sharding = NamedSharding(dd.mesh, P("z", "y", "x"))
    for name in list(dd.curr):
        raw = tuple(s // m for s, m in zip(dd.curr[name].shape, mesh))
        dtype = dd.curr[name].dtype
        dd.curr[name].delete()
        def make(key, name=name, raw=raw, dtype=dtype):
            coords, inside = seeded.padded_coords(global_zyx, mesh, local,
                                                  lo, raw)
            value = reference.initial_field(name, key, coords, global_zyx)
            return jnp.where(inside, value, 0.0).astype(dtype)

        key = seeded.stream_key(seed, reference.FIELDS.index(name))
        with jax.profiler.TraceAnnotation("bench.init"):
            dd.curr[name] = jax.jit(make, out_shardings=sharding)(key)
