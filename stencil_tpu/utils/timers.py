"""Wall-clock timing utilities.

The analog of the reference's Timer/rt wrappers (reference:
include/stencil/timer.hpp:21-39, rt.hpp:9-37) adapted to async XLA
dispatch: a timed region ends in ``device_sync`` so it measures the
device's work, not the enqueue.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict

import jax


def device_sync(tree: Any) -> None:
    """Wait until every computation producing ``tree``'s leaves is done
    (``jax.block_until_ready``)."""
    jax.block_until_ready(tree)


class Timer:
    """Accumulating wall timer (reference: timer.hpp:21-39)."""

    def __init__(self) -> None:
        self.seconds = 0.0
        self._t0 = 0.0

    def start(self) -> None:
        self._t0 = time.perf_counter()

    def stop(self) -> float:
        dt = time.perf_counter() - self._t0
        self.seconds += dt
        return dt


def time_fn(fn: Callable, *args, sync: Any = None, **kw) -> float:
    """Time one call including device completion (the rt::time analog,
    reference: rt.hpp:9-22): argument evaluation is excluded, the
    returned value (or ``sync``) is waited on."""
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    device_sync(out if sync is None else sync)
    return time.perf_counter() - t0


# global accumulators, the timers::cudaRuntime / timers::mpi analog
# (reference: src/timer.cpp:13-16)
timers: Dict[str, Timer] = {}


def get_timer(name: str) -> Timer:
    return timers.setdefault(name, Timer())
