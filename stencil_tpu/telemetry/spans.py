"""Structured spans: one tree, three audiences.

A telemetry span (campaign -> segment -> exchange/compute/checkpoint/
tune) is simultaneously:

* a ``jax.named_scope`` — ops traced inside it carry the span name
  into the XLA metadata, so the span tree lines up with compiled-op
  names in an XLA profile;
* a ``jax.profiler.TraceAnnotation`` — the host wall-time range shows
  on the profiler timeline (the NVTX-range analog the reference library
  puts on every stream);
* an exportable record with a stable id (``<run>/<n>``), parent id,
  begin/end timestamps, and attributes — dumped as Chrome trace-event
  JSON (:meth:`Tracer.export_chrome_trace`) loadable in Perfetto or
  ``chrome://tracing``, no profiler session required.

The first two come from wrapping :func:`..utils.profiling.scope`
(which the repo already used ad hoc); the third is what was missing —
an in-process record a service can export per run.

:class:`Tracer` is thread-safe: each thread keeps its own span stack
(``threading.local``), finished spans land in one bounded ring. A
span starts on the host clock the profiler stamps ``TraceMe`` events
with (``CLOCK_REALTIME``, ``time.time_ns``), so an exported span and
the profiler's event for it line up (a profile's own times are
relative to its ``profile_start_time``, the "Task Environment"
plane); its duration is measured on the monotonic clock.

The solvers name their layers from one table, :data:`SCOPES`.
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Dict, Iterator, List, Optional, Sequence

import jax

from ..utils.profiling import scope
from .events import new_run_id

RUN = "stencil.run"
BLOCK = "stencil.block"
KERNEL = "stencil.kernel"
EXCHANGE = "stencil.exchange"
INTERIOR = "stencil.interior"

#: Every name the solvers put on their layers, and what reads it.
#: Host spans are :meth:`Tracer.span` records and ``TraceAnnotation``
#: ranges on the profiler's host clock; the three in-graph scopes are
#: ``jax.named_scope``s (:func:`layer_scope`) opened while a step
#: program traces, so each compiled op under one carries the name in
#: its ``op_name`` metadata (``tf_op`` in a profile). The copies XLA
#: adds to carry a ``fori_loop``'s state sit under none of them.
SCOPES: Dict[str, str] = {
    RUN: "host span around Jacobi3D.run / Astaroth.run, attr `iters`: "
         "the host dispatch of one chunk of steps; a profile's device "
         "idle gaps inside it are dispatch. On the Jacobi wrap and halo "
         "paths also `loop_steps` (steps in the copy-free two-call loop "
         "body of models.jacobi.run_loop). On the wrap path also "
         "`tail_steps` (the rest). On the halo path also "
         "`group_steps` (steps the pair-kernel calls advance), "
         "`tail_steps` (the single-step tail) and `wire_bytes` (bytes "
         "the chunk's slab exchanges put on the interconnect, summed "
         "over the mesh)",
    BLOCK: "host span around block(): the host waiting for the device; "
           "idle gaps inside it are the host waking up",
    KERNEL: "in-graph: the stencil arithmetic — every pallas_call "
            "(named after its function without `_pallas`), the XLA "
            "path's jacobi7 and sources, mhd_rates and the RK update; "
            "read by kernel_scope_ms_per_step",
    EXCHANGE: "in-graph: halo exchange — the ppermute/all-gather "
              "sweeps with their packing and unpacking, the slabs fed "
              "to the halo kernels, the temporal deep exchange, the "
              "PallasDMA kernel; read by exchange_scope_ms_per_step and "
              "exchange_scope_exposed_ms_per_step",
    INTERIOR: "in-graph: the interior sliced out of the padded field and "
              "written back into it (dynamic_update_slice); read by "
              "interior_writeback_ms_per_step",
}
#: the in-graph scopes; ops under none of them are read by
#: unscoped_ms_per_step
LAYER_SCOPES = (KERNEL, EXCHANGE, INTERIOR)

@contextmanager
def layer_scope(name: str) -> Iterator[None]:
    """``jax.named_scope(name)`` for one of :data:`LAYER_SCOPES`, as a
    context manager or a decorator (a fresh scope per call, so a
    decorated function may be traced re-entrantly or from two threads).
    Trace-time only: no run-time cost."""
    if name not in LAYER_SCOPES:
        raise ValueError(f"{name!r} is not one of {LAYER_SCOPES}")
    with jax.named_scope(name):
        yield


def _now_s() -> float:
    """Seconds on the profiler's host clock (``TraceMe`` stamps
    ``CLOCK_REALTIME``)."""
    return time.time_ns() * 1e-9


@dataclasses.dataclass
class Span:
    """One finished (or live) span."""

    name: str
    span_id: str
    parent_id: Optional[str]
    start_s: float          # seconds on the profiler's host clock
    end_s: Optional[float] = None
    thread: int = 0
    attrs: Dict = dataclasses.field(default_factory=dict)

    @property
    def duration_s(self) -> float:
        return (self.end_s - self.start_s) if self.end_s is not None \
            else 0.0


class Tracer:
    """Thread-safe in-process span recorder with Perfetto export."""

    #: export identity keys — span attrs may not shadow them (the
    #: same contract as ``EventLog.RESERVED``)
    RESERVED = frozenset(("span_id", "parent_id"))

    def __init__(self, run_id: Optional[str] = None,
                 capacity: int = 65536) -> None:
        self.run_id = run_id or new_run_id()
        self._lock = threading.Lock()
        self._counter = 0
        self._local = threading.local()
        self._finished: deque = deque(maxlen=int(capacity))
        self._dropped = 0
        #: when the tracer was made, on the spans' clock (metadata)
        self.epoch_unix = _now_s()

    # -- recording ------------------------------------------------------
    def _stack(self) -> List[Span]:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _new_id(self) -> str:
        with self._lock:
            n = self._counter
            self._counter += 1
        return f"{self.run_id}/{n}"

    def current_span_id(self) -> Optional[str]:
        st = self._stack()
        return st[-1].span_id if st else None

    @contextmanager
    def span(self, name: str, **attrs) -> Iterator[Span]:
        """Open a child span of the thread's current span. Inside the
        block, traced ops get the span name as a ``named_scope`` and
        host time shows as a ``TraceAnnotation`` (via
        ``utils.profiling.scope``)."""
        bad = self.RESERVED.intersection(attrs)
        if bad:
            raise ValueError(
                f"span attrs may not shadow identity keys: {sorted(bad)}")
        st = self._stack()
        sp = Span(name=name, span_id=self._new_id(),
                  parent_id=st[-1].span_id if st else None,
                  start_s=_now_s(),
                  thread=threading.get_ident(), attrs=dict(attrs))
        st.append(sp)
        t0 = time.perf_counter_ns()
        try:
            with scope(name):
                yield sp
        finally:
            # the duration on the monotonic clock: a step of the wall
            # clock never makes it negative
            sp.end_s = sp.start_s + (time.perf_counter_ns() - t0) * 1e-9
            st.pop()
            with self._lock:
                if len(self._finished) == self._finished.maxlen:
                    self._dropped += 1
                self._finished.append(sp)

    def finished(self) -> List[Span]:
        with self._lock:
            return list(self._finished)

    @property
    def dropped(self) -> int:
        """Finished spans evicted from the ring — truncation is never
        silent (exported parent ids may reference evicted spans)."""
        with self._lock:
            return self._dropped

    def clear(self) -> None:
        with self._lock:
            self._finished.clear()
            self._dropped = 0

    # -- export ---------------------------------------------------------
    def chrome_trace(self) -> Dict:
        """The Chrome trace-event payload (``ph: "X"`` complete events,
        microsecond timestamps) Perfetto and chrome://tracing load."""
        events = []
        pid = os.getpid()
        for sp in self.finished():
            args = {"span_id": sp.span_id}
            if sp.parent_id is not None:
                args["parent_id"] = sp.parent_id
            args.update({k: v for k, v in sp.attrs.items()
                         if isinstance(v, (str, int, float, bool))
                         or v is None})
            events.append({
                "name": sp.name, "cat": "stencil_tpu", "ph": "X",
                "ts": round(sp.start_s * 1e6, 3),
                "dur": round(sp.duration_s * 1e6, 3),
                "pid": pid, "tid": sp.thread, "args": args,
            })
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "otherData": {"run": self.run_id,
                              "epoch_unix_s": self.epoch_unix,
                              "dropped_spans": self.dropped,
                              "tool": "stencil_tpu.telemetry"}}

    def export_chrome_trace(self, path: str) -> None:
        with open(path, "w", encoding="utf-8") as f:
            json.dump(self.chrome_trace(), f, indent=1)


def validate_chrome_trace(payload) -> List[str]:
    """Structural validation against the trace-event format (the CI
    gate for exported traces). Accepts the payload dict or a path.
    Returns human-readable problems (empty = loads in Perfetto)."""
    problems: List[str] = []
    if isinstance(payload, (str, os.PathLike)):
        try:
            with open(payload, encoding="utf-8") as f:
                payload = json.load(f)
        except (OSError, ValueError) as e:
            return [f"cannot load trace: {type(e).__name__}: {e}"]
    if not isinstance(payload, dict):
        return ["top level must be a JSON object"]
    events = payload.get("traceEvents")
    if not isinstance(events, list):
        return ["missing traceEvents array"]
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            problems.append(f"event {i}: not an object")
            continue
        if not isinstance(ev.get("name"), str):
            problems.append(f"event {i}: missing name")
        ph = ev.get("ph")
        if not isinstance(ph, str) or not ph:
            problems.append(f"event {i}: missing ph")
        for key in ("ts",) + (("dur",) if ph == "X" else ()):
            v = ev.get(key)
            if not isinstance(v, (int, float)) or isinstance(v, bool) \
                    or v < 0:
                problems.append(f"event {i}: missing/invalid {key!r}")
        for key in ("pid", "tid"):
            if not isinstance(ev.get(key), int):
                problems.append(f"event {i}: missing/invalid {key!r}")
    return problems


_TRACER = Tracer()


def get_tracer() -> Tracer:
    """The process-default tracer (run loops; services own their own)."""
    return _TRACER


def set_tracer(tracer: Tracer) -> Tracer:
    """Swap the process-default tracer; returns the previous one."""
    global _TRACER
    prev, _TRACER = _TRACER, tracer
    return prev
