"""The Astaroth MHD integrator, driven through its public entry point
``stencil_tpu.models.astaroth.Astaroth`` with ``kernel="auto"`` and
the default methods. One step is one RK3 iteration (3 substeps)."""

import jax.numpy as jnp
import numpy as np

from benchmark.harness import domain
from benchmark.reference import astaroth as reference


class Solver:
    def __init__(self, cell, devices, seed: int):
        from stencil_tpu.models.astaroth import Astaroth, MhdParams

        if cell.config["dtype"] != "float32":
            raise ValueError("the astaroth family runs float32 fields")
        z, y, x = self.global_zyx = cell.global_zyx()
        self.k = int(cell.traffic["steps_per_chunk"])
        self.model = Astaroth(x, y, z,
                              params=MhdParams(**cell.config["params"]),
                              dtype=np.float32, devices=devices,
                              kernel="auto")
        domain.write_fields(self.model.dd, reference, seed,
                            self.global_zyx)

    def info(self) -> dict:
        return {"kernel_path": self.model.kernel_path,
                "mesh_xyz": list(self.model.dd.placement.dim())}

    def run_chunk(self) -> None:
        self.model.run(self.k)

    def block(self) -> None:
        self.model.block()

    def snapshot(self) -> dict:
        return {q: self.model.field(q) for q in reference.FIELDS}

    def finite(self) -> bool:
        self.model.sync_domain()
        return all(bool(jnp.isfinite(a).all())
                   for a in self.model.dd.curr.values())

    def close(self) -> None:
        """Frees the fields now; the RK accumulators go with the model
        at the caller's ``gc.collect()``."""
        self.model.sync_domain()
        for a in self.model.dd.curr.values():
            a.delete()
        self.model = None
