"""HBM bytes of the Jacobi wrap path's kernel in the ``jacobi3d_lean``
family: the ``jacobi3d`` family's count, over the logical grid."""

from benchmark.kernel_bytes.jacobi3d_wrap import (  # noqa: F401
    bytes_per_call, bytes_per_step)
