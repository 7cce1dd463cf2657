"""One run of one cell: set-up, the measured window, the comparison
with the plain reference, and the result line."""

import argparse
import gc
import json
import os
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path

from benchmark.harness import compare, device, spec, window

#: chunks driven in set-up through the window's own call, then checked
CHECK_CHUNKS = 2
ROOT = Path(__file__).resolve().parents[2]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description="stencil_tpu chip benchmark")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump-trace", default="",
                    help="also write the reduced trace here (JSON)")
    return ap.parse_args(argv)


def compile_cache(root: Path) -> None:
    """JAX's persistent cache at a fixed path inside the checkout, for
    every program, so only a checkout's first run compiles."""
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      str(root / ".jax_compile_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def check(family, cell, seed: int, steps: int, snapshot, dev) -> float:
    ref = family.reference
    gzyx = cell.global_zyx()
    init = ref.initial_state(seed, gzyx, dev)
    want = ref.advance(ref.initial_state(seed, gzyx, dev), steps,
                       cell.config)
    return compare.gap_over_change(snapshot, want, init)


def set_up(family, cell, devices, seed: int, t_start: float):
    """The solver built and seeded, driven through the checked chunks
    and one more; returns (solver, the checked state, setup seconds)."""
    k = int(cell.traffic["steps_per_chunk"])
    t_devices = time.perf_counter()
    solver = family.Solver(cell, devices, seed)
    t0 = time.perf_counter()
    solver.run_chunk()
    solver.block()
    first_chunk_s = time.perf_counter() - t0
    for _ in range(CHECK_CHUNKS - 1):
        solver.run_chunk()
        solver.block()
    t_snap = time.perf_counter()
    snapshot = solver.snapshot()
    # one more chunk after the host read-back: the first call after it
    # ran up to 100 ms slow on the chip, which belongs to set-up
    solver.run_chunk()
    solver.block()
    setup_s = time.perf_counter() - t_start
    log(f"[setup] cell={cell.name} grid_zyx={cell.global_zyx()} "
        f"chips={cell.chips} {json.dumps(solver.info())} first_chunk_s="
        f"{first_chunk_s:.4f} (compile + {k} steps) setup_s={setup_s:.4f}")
    log(f"[setup] phases_s: jax_and_devices={t_devices - t_start:.3f} "
        f"build_and_state={t0 - t_devices:.3f} check_chunks="
        f"{t_snap - t0:.3f} snapshot_and_warmup="
        f"{setup_s - (t_snap - t_start):.3f}")
    return solver, snapshot, setup_s


def measure(solver, seconds: float, trace_dir):
    """The window, traced into ``trace_dir`` where given; then the
    device's memory peak and whether the final state is finite."""
    import jax

    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    try:
        win = window.run(solver, seconds)
    finally:
        if trace_dir:
            jax.profiler.stop_trace()
    if win.error:
        log(f"[window] a chunk raised:\n{win.error}")
    elif not solver.finite():
        log("[window] the final state is not finite")
        win.failed = win.attempted
    d = win.durations_s
    spread = "-" if not d else (
        "/".join(f"{f(d) * 1e3:.4f}" for f in (min, statistics.median, max))
        + f" slowest at chunk {d.index(max(d))}")
    log(f"[window] chunks={win.attempted} failed={win.failed} "
        f"wall_s={win.wall_s:.6f} chunk_ms min/median/max={spread}")
    return win


def end_to_end(cell, win, setup_s: float) -> dict:
    steps = (win.attempted - win.failed) * int(
        cell.traffic["steps_per_chunk"])
    cells = 1
    for v in cell.global_zyx():
        cells *= v
    values = {
        "gcell_updates_per_s": (cells * steps / win.wall_s / 1e9
                                if win.wall_s > 0 else 0.0),
        "chunk_ms_p90": (window.p90(win.durations_s) * 1e3
                         if win.durations_s else 0.0),
        "setup_s": setup_s,
    }
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}


def per_layer(bench, cell, info, steps: int, trace_dir: str,
              device_kind: str, dump: str):
    """The cell's per-layer metrics from the trace (a metric whose
    reader finds nothing is left out), busy and window seconds, and
    the breakdown."""
    from benchmark.harness import layers, trace

    tr = trace.load(trace_dir)
    if dump:
        trace.dump(tr, dump, trace_dir)
    ctx = layers.Context(tr, cell, info, steps, bench, device_kind)
    metrics = {}
    for m in cell.per_layer:
        v = bench.module("metrics", m["name"]).reduce(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    return metrics, ctx.busy_s(), ctx.window_s(), ctx.breakdown()


def main(argv, t_start: float, root: Path = ROOT,
         require=device.require_devices) -> int:
    args = parse(argv)
    bench = spec.Benchmark(root)
    cell = bench.cell(args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    compile_cache(root)
    devices = require(cell.chips)
    family = bench.module("families", cell.config["family"])
    k = int(cell.traffic["steps_per_chunk"])

    solver, snapshot, setup_s = set_up(family, cell, devices, args.seed,
                                       t_start)
    info = solver.info()
    trace_dir = (tempfile.mkdtemp(prefix="bench_trace_") if args.trace
                 else None)
    win = measure(solver, args.seconds, trace_dir)
    mem = device.memory_peak_bytes(devices)
    log(f"[device] memory_peak_bytes={mem}")
    solver.close()
    del solver
    gc.collect()

    t_ref = time.perf_counter()
    gap = check(family, cell, args.seed, CHECK_CHUNKS * k, snapshot,
                devices[0])
    log(f"[check] reference {CHECK_CHUNKS * k} steps took "
        f"{time.perf_counter() - t_ref:.3f} s")
    checks = {"gap_over_change": {
        "value": gap, "limit": cell.config["limits"]["gap_over_change"]}}
    correct = (win.attempted > 0 and win.failed == 0
               and all(c["value"] <= c["limit"] for c in checks.values()))

    dev_info = {**device.describe(devices), "memory_peak_bytes": mem}
    breakdown = None
    if trace_dir:
        metrics, dev_info["busy_s"], dev_info["window_s"], breakdown = \
            per_layer(bench, cell, info,
                      (win.attempted - win.failed) * k, trace_dir,
                      devices[0].device_kind, args.dump_trace)
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        metrics = end_to_end(cell, win, setup_s)

    for name, c in checks.items():
        log(f"[check] {name} = {c['value']!r} limit {c['limit']!r} -> "
            f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}")
    log(f"[check] chunks attempted={win.attempted} failed={win.failed} "
        f"limit 0 -> {'ok' if win.failed == 0 else 'FAIL'}")
    out = {"correct": correct, "attempted": win.attempted,
           "failed": win.failed, "metrics": metrics, "device": dev_info}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {**checks,
                     "failed_chunks": {"value": win.failed, "limit": 0}}
    print(json.dumps(out), flush=True)
    return 0
