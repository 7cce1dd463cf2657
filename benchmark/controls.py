"""The readings the correctness limits are set from, on the chip, at a
cell's own size. Not run by the benchmark's runs.

- program: the cell's set-up as ``run.py`` makes it (build, seeded
  state, the checked chunks through ``run(k)``), then the comparison
  with the float32 reference; the largest over a dozen seeds or more
  is the lower reading.
- control: the reference itself in the program's place, computed in
  bfloat16 (the precision below the configuration's float32), against
  the float32 reference; the smallest over three seeds or more is the
  upper reading.

    python3 benchmark/controls.py --workload <cell> --seeds 1,2,3 \
        --control-seeds 4,5,6
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def main(argv) -> int:
    import gc

    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import compare, device, runner, spec

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    bench = spec.Benchmark(runner.ROOT)
    cell = bench.cell(args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    runner.compile_cache(runner.ROOT)
    devices = device.require_devices(cell.chips)
    family = bench.module("families", cell.config["family"])
    ref = family.reference
    steps = runner.CHECK_CHUNKS * int(cell.traffic["steps_per_chunk"])
    gzyx = cell.global_zyx()
    out = {"workload": cell.name, "program": {}, "control": {}}
    for seed in [int(s) for s in args.seeds.split(",") if s]:
        t0 = time.perf_counter()
        solver = family.Solver(cell, devices, seed)
        for _ in range(runner.CHECK_CHUNKS):
            solver.run_chunk()
            solver.block()
        snap = solver.snapshot()
        solver.close()
        del solver
        gc.collect()
        gap = runner.check(family, cell, seed, steps, snap, devices[0])
        out["program"][seed] = gap
        print(f"program seed={seed} gap_over_change={gap!r} "
              f"({time.perf_counter() - t0:.1f} s)", flush=True)
    for seed in [int(s) for s in args.control_seeds.split(",") if s]:
        low = ref.advance(ref.initial_state(seed, gzyx, devices[0]), steps,
                          cell.config, dtype=jnp.bfloat16)
        got = {q: np.asarray(v) for q, v in low.items()}
        del low
        init = ref.initial_state(seed, gzyx, devices[0])
        want = ref.advance(ref.initial_state(seed, gzyx, devices[0]),
                           steps, cell.config)
        gap = compare.gap_over_change(got, want, init)
        out["control"][seed] = gap
        print(f"control(bfloat16) seed={seed} gap_over_change={gap!r}",
              flush=True)
    if out["program"]:
        out["lower"] = max(out["program"].values())
    if out["control"]:
        out["upper"] = min(out["control"].values())
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
