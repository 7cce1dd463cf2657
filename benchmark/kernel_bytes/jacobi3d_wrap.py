"""HBM bytes of the Jacobi wrap path's kernel
(``ops/pallas_stencil.jacobi7_wrapn_pallas``; ``step_stride`` steps
per call, 2 for the pair kernel): one call reads the (Z, Y, X)
interior once and writes it once. Block windows re-read halo rows, so
real traffic is higher: this is a floor."""


def bytes_per_call(z: int, y: int, x: int, itemsize: int = 4) -> int:
    return 2 * z * y * x * itemsize


def bytes_per_step(cell, info) -> float:
    """Whole grid, all chips; one chip on this path."""
    z, y, x = cell.global_zyx()
    return bytes_per_call(z, y, x) / info["step_stride"]
