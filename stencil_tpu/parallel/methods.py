"""Exchange-strategy flags.

The analog of the reference's Method bitflags
(reference: include/stencil/method.hpp:5-16), which select per-pair
transports (CudaMpi, ColoPackMemcpyUnpack, CudaMemcpyPeer, CudaKernel,
...). On TPU there is no rank/IPC/MPI distinction — XLA SPMD owns the
wire — so the strategies select *how the halo data rides the ICI*:

* ``PpermuteSlab``  — one ``lax.ppermute`` per axis-direction per
  quantity (the default; XLA may combine collectives).
* ``PpermutePacked`` — all quantities packed into one buffer per
  axis-direction, one ``ppermute`` each (the DevicePacker analog,
  reference: src/packer.cu:10-44).
* ``PallasDMA``     — Pallas ``make_async_remote_copy`` ring DMA
  (the manual-transport analog; enables true comm/compute overlap).
* ``AllGather``     — per-axis ``all_gather`` then slice (control
  strategy for benchmarking, like the reference's method sweeps).
* ``Auto``          — no transport at all: a request that the exchange
  autotuner (:mod:`stencil_tpu.tuning`) measure the machine and pick
  the fastest runnable configuration — the analog of the reference's
  measured per-pair transport routing (src/stencil.cu:371-458) and of
  TEMPI's transparent measured-faster substitution.
"""

from __future__ import annotations

import enum
from typing import Callable, Optional, Set, Tuple


class Method(enum.Flag):
    """Bitmask of allowed exchange strategies
    (reference: include/stencil/method.hpp:5-16 to_string at :31-74)."""

    NONE = 0
    PpermuteSlab = 1
    PpermutePacked = 2
    PallasDMA = 4
    AllGather = 8
    Auto = 16
    Default = PpermuteSlab

    def __str__(self) -> str:  # reference: method.hpp to_string
        names = ["PpermuteSlab", "PpermutePacked", "PallasDMA",
                 "AllGather", "Auto"]
        parts = [n for n in names if Method[n] in self]
        return "|".join(parts) if parts else "none"


#: transport flags in routing-priority order (Auto is not a transport)
METHOD_PRIORITY: Tuple["Method", ...] = (
    Method.PallasDMA, Method.PpermutePacked, Method.PpermuteSlab,
    Method.AllGather)


#: strategies whose data plane supports narrow halo wire formats
#: (wire_format="bf16"): the slab/packed ppermute engines convert at
#: the send boundary and widen on arrival; the RDMA and all-gather
#: paths ship raw storage bytes
WIRE_CAPABLE: Tuple["Method", ...] = (Method.PpermuteSlab,
                                      Method.PpermutePacked)


def method_supports_wire_format(m: "Method") -> bool:
    """Can this strategy carry a NARROWING halo wire format?"""
    return m in WIRE_CAPABLE


# (requested, fallback) pairs already warned about — the orchestrator
# consults pick_method several times per realize(); warn once per fact
_warned: Set[Tuple[int, int]] = set()


def pick_method(methods: "Method",
                runnable: Optional[Callable[["Method"], bool]] = None
                ) -> "Method":
    """Choose the single strategy the exchange will use this run, by
    priority (the analog of the reference's per-pair transport routing,
    src/stencil.cu:371-458 — on TPU every pair rides the same ICI, so
    one strategy is picked globally).

    PallasDMA (explicit inter-chip RDMA, parallel/pallas_exchange.py)
    wins when requested — it is the opt-in manual-transport path, like
    the reference's direct-write Colo* methods. Every strategy runs
    on the installed JAX (PallasDMA natively on a TPU, and through the
    distributed Pallas interpreter off it), so by default the
    highest-priority requested strategy wins. ``runnable`` narrows
    that: a requested strategy it rejects is skipped with a logged
    warning in favor of the next accepted one, or ``Method.Default``
    when none is accepted.
    """
    requested = [m for m in METHOD_PRIORITY if m in methods]
    if not requested:
        if Method.Auto in methods:
            raise ValueError(
                "Method.Auto carries no transport — resolve it first "
                "via DistributedDomain.autotune()/realize() (the "
                "autotuner replaces Auto with the measured winner)")
        raise ValueError(f"no usable method in {methods}")
    skipped = []
    for m in requested:
        if runnable is None or runnable(m):
            if skipped:
                _warn_fallback(skipped, m)
            return m
        skipped.append(m)
    fallback = Method.Default
    _warn_fallback(skipped, fallback)
    return fallback


def _warn_fallback(skipped, chosen: "Method") -> None:
    from ..utils.logging import LOG_WARN

    key = (sum(m.value for m in skipped), chosen.value)
    if key in _warned:
        return
    _warned.add(key)
    names = "|".join(m.name or "?" for m in skipped)
    LOG_WARN(f"requested exchange method(s) {names} were rejected "
             f"by the runnable predicate; falling back to {chosen}")
