"""The Jacobi heat solver, driven through its public entry point
``stencil_tpu.models.jacobi.Jacobi3D`` with ``kernel="auto"`` and the
default methods."""

import jax.numpy as jnp
import numpy as np

from benchmark.harness import domain
from benchmark.reference import jacobi3d as reference


class Solver:
    def __init__(self, cell, devices, seed: int):
        from stencil_tpu.models.jacobi import Jacobi3D

        if cell.config["dtype"] != "float32":
            raise ValueError("the jacobi3d family runs float32 fields")
        z, y, x = self.global_zyx = cell.global_zyx()
        self.k = int(cell.traffic["steps_per_chunk"])
        self.model = Jacobi3D(x, y, z, dtype=np.float32, devices=devices,
                              kernel="auto")
        domain.write_fields(self.model.dd, reference, seed,
                            self.global_zyx)

    def info(self) -> dict:
        return {"kernel_path": self.model.kernel_path,
                "step_stride": self.model.step_stride,
                "mesh_xyz": list(self.model.dd.placement.dim())}

    def run_chunk(self) -> None:
        self.model.run(self.k)

    def block(self) -> None:
        self.model.block()

    def snapshot(self) -> dict:
        return {"temp": self.model.temperature()}

    def finite(self) -> bool:
        return bool(jnp.isfinite(self.model.dd.curr["temp"]).all())

    def close(self) -> None:
        for a in self.model.dd.curr.values():
            a.delete()
        self.model = None
