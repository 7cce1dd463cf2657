"""HBM bytes of the Astaroth wrap path's megakernel
(``ops/pallas_mhd.mhd_substep_wrap_pallas``), per RK3 iteration of
three calls, each array (Z, Y, X) read once or written once (a floor:
block windows re-read halo rows):

- substep 0 reads the 8 fields, writes 8 fields and 8 accumulators w
  (alpha_0 = 0, so w is not read);
- substep 1 reads 8 fields and 8 w, writes 8 fields and 8 w;
- substep 2 reads 8 fields and 8 w, writes 8 fields (nothing reads
  its w).

80 arrays in all."""

FIELDS = 8
ARRAYS_PER_CALL = ((8, 16), (16, 16), (16, 8))   # (read, written)


def bytes_per_call(substep: int, z: int, y: int, x: int,
                   itemsize: int = 4) -> int:
    r, w = ARRAYS_PER_CALL[substep]
    return (r + w) * z * y * x * itemsize


def bytes_per_step(cell, info) -> float:
    """Whole grid, all chips, one iteration (3 calls)."""
    z, y, x = cell.global_zyx()
    return float(sum(bytes_per_call(s, z, y, x) for s in range(3)))
