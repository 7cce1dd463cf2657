"""Stencil compute ops: XLA-fused kernels and Pallas fast paths.

``PUBLIC_OPS`` is the lint-coverage manifest — the registry metadata
hook the static analyzer's drift guard checks (tests/test_lint.py):
every public op entry point shipped from this package maps to the
``analysis/registry.default_targets()`` name (prefix) that covers it.
Adding a public op without registering an analysis target fails the
guard — new kernels cannot silently escape the lint gate.

Keys are dotted op names rooted at the package; values are the
covering registry-target prefix (usually the same name; families
audited through one representative, e.g. ``jacobi7_wrap2_pallas``
being a steps=2 alias of ``jacobi7_wrapn_pallas``, point at it).
"""

from __future__ import annotations

from typing import Dict

PUBLIC_OPS: Dict[str, str] = {
    # XLA-fused stencil ops (footprint-audited against their Radius)
    "ops.stencil_kernels.jacobi7": "ops.stencil_kernels.jacobi7",
    "ops.stencil_kernels.laplacian27": "ops.stencil_kernels.laplacian27",
    "ops.stencil_kernels.central_diff": "ops.stencil_kernels.central_diff",
    "ops.fd6.der1": "ops.fd6.der1",
    "ops.fd6.der2": "ops.fd6.der2",
    "ops.fd6.der_cross": "ops.fd6.der_cross",
    # Pallas single-chip fast paths (VMEM/tiling-audited)
    "ops.pallas_stencil.jacobi7_pallas": "ops.pallas_stencil.jacobi7_pallas",
    "ops.pallas_stencil.jacobi7_wrap_pallas":
        "ops.pallas_stencil.jacobi7_wrap_pallas",
    "ops.pallas_stencil.jacobi7_wrapn_pallas":
        "ops.pallas_stencil.jacobi7_wrapn_pallas",
    "ops.pallas_stencil.jacobi7_wrap2_pallas":
        "ops.pallas_stencil.jacobi7_wrapn_pallas",  # steps=2 alias
    "ops.pallas_mhd.mhd_substep_wrap_pallas":
        "ops.pallas_mhd.mhd_substep_wrap_pallas",
    "ops.pallas_mhd.mhd_substep01_wrap_pallas":
        "ops.pallas_mhd.mhd_substep01_wrap_pallas",
    # Pallas multi-chip halo / overlap paths (DMA- and VMEM-audited)
    "ops.pallas_halo.jacobi7_halo_pallas":
        "ops.pallas_halo.jacobi7_halo_pallas",
    "ops.pallas_halo.jacobi7_halon_pallas":
        "ops.pallas_halo.jacobi7_halon_pallas",
    "ops.pallas_halo.jacobi7_halo2_pallas":
        "ops.pallas_halo.jacobi7_halon_pallas",  # steps=2 alias
    "ops.pallas_halo.mhd_substep_halo_pallas":
        "ops.pallas_halo.mhd_substep_halo_pallas",
    "ops.pallas_halo.mhd_substep01_halo_pallas":
        "ops.pallas_halo.mhd_substep01_halo_pallas",
    "ops.pallas_overlap.jacobi7_overlap_pallas":
        "ops.pallas_overlap.jacobi7_overlap_pallas",
    "ops.pallas_mhd_overlap.mhd_substep_overlap":
        "ops.pallas_mhd_overlap.mhd_substep_overlap",
    "ops.pallas_mhd_overlap.mhd_substep_overlap_pallas":
        "ops.pallas_mhd_overlap.mhd_substep_overlap",  # inner entry
    "ops.pallas_mhd_overlap.mhd_substep_fixup_pallas":
        "ops.pallas_mhd_overlap.mhd_substep_overlap",  # traced within
}
