"""stencil_tpu: a TPU-native distributed 3D stencil / halo-exchange framework.

A brand-new JAX/XLA/Pallas re-design with the capabilities of
cwpearson/stencil (an MPI/CUDA halo-exchange library): automatic
communication-minimizing partitioning of a global 3D grid of multiple
quantities, topology-aware placement, per-direction variable-radius
(face/edge/corner, possibly asymmetric) halo exchange with periodic
boundaries, double-buffered fields, interior/exterior overlap queries,
and reference applications (Jacobi-3D, Astaroth-style MHD).

Instead of MPI ranks + CUDA streams/IPC, the data plane is a 3D
``jax.sharding.Mesh`` over the TPU ICI torus with ``shard_map`` +
``lax.ppermute`` (or Pallas async remote DMA) halo shifts, and the
compute plane is XLA/Pallas kernels.
"""

from .geometry import (Dim3, Rect3, Radius, all_directions, deepened,
                       direction_kind)
from .numerics import Statistics, div_ceil, next_align_of, prime_factors, trimean
from .partition import NodePartition, RankPartition, partition_dims_even
from .topology import Boundary, Topology

__version__ = "0.1.0"

__all__ = [
    "Dim3", "Rect3", "Radius", "all_directions", "deepened",
    "direction_kind",
    "Statistics", "div_ceil", "next_align_of", "prime_factors", "trimean",
    "NodePartition", "RankPartition", "partition_dims_even",
    "Boundary", "Topology",
    "__version__",
]
