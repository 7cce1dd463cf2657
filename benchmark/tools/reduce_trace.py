"""Reduce a saved trace (``run.py --dump-trace``) to the per-layer
metrics again, offline: for checking a change to a metric's rules
against a trace already taken.

    python benchmark/tools/reduce_trace.py <cell> <steps> <trace.json> \
        [kernel_path] [step_stride]
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv) -> int:
    from pathlib import Path

    from benchmark.harness import layers, spec, trace

    cell_name, steps, path = argv[0], int(argv[1]), argv[2]
    info = {"kernel_path": argv[3] if len(argv) > 3 else "wrap",
            "step_stride": int(argv[4]) if len(argv) > 4 else 2}
    bench = spec.Benchmark(Path(__file__).resolve().parents[2])
    cell = bench.cell(cell_name)
    tr = trace.load_dump(path)
    ctx = layers.Context(tr, cell, info, steps, bench, "TPU v5 lite")
    for m in cell.per_layer:
        print(m["name"], bench.module("metrics", m["name"]).reduce(ctx))
    print("busy_s", ctx.busy_s(), "window_s", ctx.window_s())
    print(json.dumps(ctx.breakdown(), indent=0))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
