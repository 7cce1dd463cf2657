"""Compile rehearsal, no chip: the benchmark's reference programs at
the cells' real sizes, compiled for a described TPU v5e chip, with the
device memory each needs (the 4-chip cell's reference runs on one
chip). The program's own kernels at these sizes are compiled by
``tests/test_tpu_compile.py``.

    JAX_PLATFORMS=cpu python benchmark/tools/rehearse_compile.py
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    import json

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark.reference import astaroth, jacobi3d

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    cfg = json.load(open(os.path.join(
        os.path.dirname(__file__), "..", "configs",
        "astaroth-256-per-chip.json")))
    params = tuple(sorted(cfg["params"].items()))

    def report(label, compiled):
        m = compiled.memory_analysis()
        need = (m.argument_size_in_bytes + m.output_size_in_bytes
                + m.temp_size_in_bytes - m.alias_size_in_bytes)
        print(f"{label}: compiled for {topo.devices[0].device_kind}; "
              f"args {m.argument_size_in_bytes} out "
              f"{m.output_size_in_bytes} temp {m.temp_size_in_bytes} "
              f"alias {m.alias_size_in_bytes} -> {need} B", flush=True)

    for dtype in (jnp.float32, jnp.bfloat16):
        t = jax.ShapeDtypeStruct((512, 512, 512), jnp.float32, sharding=one)
        report(f"jacobi3d 512^3 reference, 64 steps, {jnp.dtype(dtype).name}",
               jacobi3d._advance.lower(t, 64, jnp.dtype(dtype)).compile())
        for z in (256, 1024):
            f = {q: jax.ShapeDtypeStruct((z, 256, 256), dtype, sharding=one)
                 for q in astaroth.FIELDS}
            ab = jax.ShapeDtypeStruct((2,), jnp.float32, sharding=one)
            report(f"astaroth {z}x256x256 reference substep, "
                   f"{jnp.dtype(dtype).name}",
                   astaroth._substep.lower(f, dict(f), ab, params,
                                           jnp.dtype(dtype)).compile())
    return 0


if __name__ == "__main__":
    sys.exit(main())
