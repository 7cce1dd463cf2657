"""The Jacobi heat solver, driven as the ``jacobi3d`` family drives it,
checked against ``reference/jacobi3d_lean.py``: the same semantics,
with temporaries that fit one chip beside the checked state at 750^3,
where the ``jacobi3d`` reference's do not."""

from benchmark.families.jacobi3d import Solver  # noqa: F401
from benchmark.reference import jacobi3d_lean as reference  # noqa: F401
