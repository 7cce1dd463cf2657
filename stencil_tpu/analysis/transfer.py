"""Checker 8: host-transfer audit — no host escapes inside hot loops.

A step or segment program must stay on the device: the run loops' only
sanctioned readbacks are the async probe trace and the checkpoint
boundary copies, both of which live OUTSIDE the jitted step program
and poll ``is_ready`` instead of blocking. Anything host-shaped
*inside* the compiled hot path — a ``jax.debug.print`` left over from
debugging, a ``pure_callback``/``io_callback`` escape, an
infeed/outfeed, a ``device_put`` onto host memory — serializes the
step pipeline on a host round-trip every dispatch (the silent-fallback
failure mode TEMPI instruments against, arXiv:2012.14363). This
checker walks each registered entry point's jaxpr (tracing only,
nothing executes) and flags every such escape as an ERROR.

The static gate has a runtime twin: :func:`hot_loop_transfer_guard`
wraps the fused-segment dispatch in ``resilience/driver.py`` and
``serving/service.py`` with ``jax.transfer_guard("disallow")``, so an
*implicit* host↔device (or cross-device reshard) transfer that only
materializes at dispatch time fails loudly in CI's chaos/service
smokes instead of shipping as a latency cliff. Sanctioned movements
are explicit by construction — ``jax.device_put`` with the mesh
sharding (see ``parallel/megastep.metric_base_vec`` and the ensemble
parameter plumbing). ``STENCIL_ALLOW_TRANSFERS=1`` is the operator
escape hatch.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
from typing import Any, Callable, Dict, List, Sequence, Tuple

from .jaxprs import iter_eqns, trace
from .report import ERROR, Finding

#: jaxpr primitives that round-trip through the host per dispatch
HOST_ESCAPE_PRIMS: Dict[str, str] = {
    "pure_callback": "a Python callback runs on host every dispatch",
    "io_callback": "an I/O callback runs on host every dispatch",
    "debug_callback": "jax.debug.callback stalls on host I/O",
    "debug_print": "jax.debug.print stalls on host I/O",
    "infeed": "infeed blocks the step on host-fed data",
    "outfeed": "outfeed pushes device data at the host mid-step",
}

#: the env var that disables the runtime transfer guard
ALLOW_TRANSFERS_ENV = "STENCIL_ALLOW_TRANSFERS"


def hot_loop_transfer_guard():
    """The runtime guard the fused-segment dispatch sites run under:
    ``jax.transfer_guard("disallow")`` — implicit transfers raise,
    explicit ``jax.device_put`` stays allowed — unless
    ``STENCIL_ALLOW_TRANSFERS=1`` opts out."""
    if os.environ.get(ALLOW_TRANSFERS_ENV, "") == "1":
        return contextlib.nullcontext()
    import jax

    return jax.transfer_guard("disallow")


@dataclasses.dataclass
class TransferSpec:
    """A hot-path program plus its (normally empty) escape allowance.

    ``allow`` names jaxpr primitives from :data:`HOST_ESCAPE_PRIMS`
    the target is sanctioned to contain — no shipped target declares
    any; the knob exists so a future, deliberately host-coupled
    program documents its exception in the registry instead of
    weakening the checker."""

    fn: Callable
    args: Sequence[Any]
    allow: Tuple[str, ...] = ()


@dataclasses.dataclass
class TransferTarget:
    name: str
    build: Callable[[], TransferSpec]

    checker = "transfer"


def _device_put_host_kinds(eqn) -> List[str]:
    """Host-memory destinations of a ``device_put`` eqn (TPU host
    offload: ``TransferToMemoryKind('pinned_host')`` and friends)."""
    kinds: List[str] = []
    for key in ("devices", "device", "srcs", "src"):
        v = eqn.params.get(key)
        items = v if isinstance(v, (tuple, list)) else [v]
        for item in items:
            kind = getattr(item, "memory_kind", None)
            if kind is not None and "host" in str(kind):
                kinds.append(str(kind))
    return kinds


def collect_escapes(fn: Callable, args: Sequence[Any]
                    ) -> Tuple[Dict[str, int], List[str], int]:
    """Trace ``fn`` and walk every (nested) eqn: returns the host-
    escape primitive counts, host-memory device_put kinds, and the
    total device_put count."""
    closed = trace(fn, *args)
    escapes: Dict[str, int] = {}
    host_puts: List[str] = []
    n_device_put = 0
    for eqn in iter_eqns(closed.jaxpr):
        name = eqn.primitive.name
        if name in HOST_ESCAPE_PRIMS:
            escapes[name] = escapes.get(name, 0) + 1
        elif name == "device_put":
            n_device_put += 1
            host_puts.extend(_device_put_host_kinds(eqn))
    return escapes, host_puts, n_device_put


def check_transfer(target: TransferTarget) -> Tuple[List[Finding], Dict]:
    """Prove the target's traced program contains no host escape."""
    try:
        spec = target.build()
    except Exception as e:  # noqa: BLE001
        return [Finding("transfer", target.name,
                        f"target build failed: {type(e).__name__}: {e}")], {}
    try:
        escapes, host_puts, n_device_put = collect_escapes(spec.fn,
                                                           spec.args)
    except Exception as e:  # noqa: BLE001
        return [Finding("transfer", target.name,
                        f"trace failed: {type(e).__name__}: {e}")], {}

    metrics = {"host_escapes": dict(sorted(escapes.items())),
               "device_puts": n_device_put}
    findings: List[Finding] = []
    for name, count in sorted(escapes.items()):
        if name in spec.allow:
            continue
        findings.append(Finding(
            "transfer", target.name,
            f"hot path contains {count}x {name} — "
            f"{HOST_ESCAPE_PRIMS[name]}; the only sanctioned readbacks "
            f"are the async probe trace and checkpoint boundary "
            f"copies, which live outside the compiled step", ERROR))
    for kind in host_puts:
        findings.append(Finding(
            "transfer", target.name,
            f"device_put onto host memory ({kind}) inside the step "
            f"program — a host round-trip per dispatch", ERROR))
    return findings, metrics
