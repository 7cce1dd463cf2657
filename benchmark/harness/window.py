"""The measured window: a closed loop of chunks, each one host call
that advances the state and the wait for the device to finish it."""

import statistics
import time
import traceback
from dataclasses import dataclass, field

import jax


@dataclass
class Window:
    durations_s: list = field(default_factory=list)
    attempted: int = 0
    failed: int = 0
    wall_s: float = 0.0
    error: str = ""


def run(solver, seconds: float) -> Window:
    """Chunks back to back until ``seconds`` have passed; the window is
    the wall time from the first chunk's call to the last chunk's end.
    A chunk that raises ends the window (the state behind it is
    unknown) and counts as failed."""
    w = Window()
    with jax.profiler.TraceAnnotation("bench.window"):
        start = time.perf_counter()
        end = start
        while end - start < seconds:
            t0 = time.perf_counter()
            w.attempted += 1
            try:
                with jax.profiler.TraceAnnotation("bench.run"):
                    solver.run_chunk()
                with jax.profiler.TraceAnnotation("bench.block"):
                    solver.block()
            except Exception:  # noqa: BLE001 - reported, then the run ends
                w.failed += 1
                w.error = traceback.format_exc()
                end = time.perf_counter()
                break
            end = time.perf_counter()
            w.durations_s.append(end - t0)
    w.wall_s = end - start
    return w


def p90(values) -> float:
    """The 90th percentile, interpolated between order statistics
    (``statistics.quantiles``, inclusive)."""
    if len(values) < 2:
        return float(values[0])
    return statistics.quantiles(values, n=10, method="inclusive")[8]
