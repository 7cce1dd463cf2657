#!/usr/bin/env python
"""What binds the fused kernels? Limiter evidence on hardware.

``--model jacobi`` (default) answers the round-4 question (BASELINE.json):
the temporally blocked pair kernel hit 298 iters/s at 512^3 against a
~500 iters/s HBM-traffic bound, so something other than traffic now
binds. ``--model mhd`` asks the same question of the MHD megakernel
(21.3 iters/s at 256^3 vs a ~2x higher traffic bound). One run gathers:

1. streaming ceiling: an elementwise-copy pass over the same arrays
   (the chip's practical HBM GB/s for this shape);
2. a ladder: jacobi wrap at temporal depths 1/2/3/4, or MHD at
   {sequential, substep-0+1 pair} x {f32, bf16} — if rates saturate
   while per-iteration traffic keeps dropping, the limiter is
   compute/issue, not HBM;
3. per-pass model: effective GB/s of each rung vs the ceiling — a rung
   whose per-PASS bandwidth sits well under the ceiling names the
   in-core pipeline (compute, DMA descriptors, grid overhead) as the
   binder; one that tracks the ceiling names traffic;
4. optional --trace DIR: wraps one timed window in
   ``jax.profiler.trace`` for TensorBoard-level confirmation.

Prints one CSV row per experiment plus a LIMITER line with the
verdict. Reference ethos: measure, then optimize
(scripts/summit/512node_jacobi3d.sh).
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _stream_ceiling(n: int, tag: str) -> float:
    """Practical HBM GB/s for this shape: out = in + 1 (read + write)."""
    import jax
    import jax.numpy as jnp

    from stencil_tpu.utils.timers import device_sync

    item = 4  # f32
    x = jnp.zeros((n, n, n), jnp.float32)
    copy = jax.jit(lambda a: a + 1.0)
    y = copy(x)
    device_sync(y)
    t0 = time.perf_counter()
    reps = 20
    for _ in range(reps):
        y = copy(y)
    device_sync(y)
    dt = (time.perf_counter() - t0) / reps
    ceiling = 2 * n * n * n * item / dt / 1e9
    print(f"{tag},stream,{n},{ceiling:.1f} GB/s,{dt * 1e3:.3f} ms/pass")
    return ceiling


def _verdict(tag: str, rows, ceiling: float, sat: bool,
             deeper: str) -> None:
    best = max(rows, key=lambda r: r[1])
    frac = best[2] / ceiling if ceiling else 0
    if sat and frac < 0.7:
        verdict = ("rate saturates across rungs at {:.0%} of the "
                   "stream ceiling: COMPUTE/ISSUE-bound — {} won't "
                   "help; spend on in-core work (VPU ops per point, "
                   "DMA descriptor count, grid shape)"
                   .format(frac, deeper))
    elif frac >= 0.7:
        verdict = ("best rung runs at {:.0%} of the stream ceiling: "
                   "HBM-TRAFFIC-bound — {} still pays"
                   .format(frac, deeper))
    else:
        verdict = ("rates still rising at {:.0%} of ceiling: mixed — "
                   "keep laddering".format(frac))
    print(f"{tag},LIMITER,{best[0]} best "
          f"({best[1]:.1f} iters/s),{verdict}")


def _mhd_ladder(args) -> None:
    """MHD rungs: {sequential, pair} x {f32, bf16}, elision-aware
    traffic model (BASELINE.json: 80 field-volumes/iter sequential, 48
    pair, halved for bf16 storage; ring refetch excluded, so the
    effective-GB/s figures are lower bounds)."""
    import jax
    import jax.numpy as jnp

    from stencil_tpu.models.astaroth import Astaroth
    from stencil_tpu.numerics import trimean

    on_tpu = jax.default_backend() == "tpu"
    n = args.size or (256 if on_tpu else 32)
    iters = args.iters or (40 if on_tpu else 4)
    ceiling = _stream_ceiling(n, "profile_mhd")
    rows = []
    for pair in (False, True):
        for dtype, item in ((jnp.float32, 4), (jnp.bfloat16, 2)):
            label = (f"{'pair' if pair else 'seq'}-"
                     f"{'bf16' if item == 2 else 'f32'}")
            os.environ["STENCIL_MHD_PAIR"] = "1" if pair else "0"
            m = Astaroth(n, n, n, mesh_shape=(1, 1, 1),
                         devices=jax.devices()[:1], kernel="wrap",
                         dtype=dtype)
            m.init()
            m.run(2)
            m.block()
            window = max(iters // 4, 1)
            rates = []
            for _ in range(4):
                t0 = time.perf_counter()
                m.run(window)
                m.block()
                rates.append(window / (time.perf_counter() - t0))
            if args.trace and pair and item == 4:
                with jax.profiler.trace(args.trace):
                    m.run(window)
                    m.block()
                print(f"profile_mhd,trace,{args.trace}")
            rate = trimean(rates)
            # dead-w-elided model, in single-field n^3 volumes per
            # iteration (BASELINE.json: 80 sequential, 48 pair)
            volumes = 48.0 if pair else 80.0
            gbs = rate * volumes * n * n * n * item / 1e9
            rows.append((label, rate, gbs))
            print(f"profile_mhd,wrap,{n},{label},"
                  f"{rate:.1f} iters/s,{gbs:.1f} GB/s-effective")
            del m
    # saturation: does the pair rung fail to beat sequential at the
    # same dtype (traffic dropped 80->48 but rate stayed put)?
    sat = all(abs(p[1] - s[1]) < 0.15 * s[1]
              for s, p in ((rows[0], rows[2]), (rows[1], rows[3])))
    _verdict("profile_mhd", rows, ceiling, sat,
             "more substep fusion / bf16")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", choices=("jacobi", "mhd"),
                    default="jacobi")
    ap.add_argument("--size", type=int, default=0,
                    help="cube edge (jacobi: 512 on TPU, 64 off; "
                         "mhd: 256 / 32)")
    ap.add_argument("--iters", type=int, default=0)
    ap.add_argument("--trace", default="",
                    help="capture a jax.profiler trace of one window "
                         "into this directory")
    ap.add_argument("--fake-cpu", type=int, default=0, metavar="N")
    args = ap.parse_args()
    from stencil_tpu.utils.config import apply_fake_cpu, enable_compile_cache
    apply_fake_cpu(args.fake_cpu)
    enable_compile_cache()

    if args.model == "mhd":
        _mhd_ladder(args)
        return

    import jax
    import jax.numpy as jnp

    from stencil_tpu.models.jacobi import Jacobi3D
    from stencil_tpu.numerics import trimean

    on_tpu = jax.default_backend() == "tpu"
    n = args.size or (512 if on_tpu else 64)
    iters = args.iters or (120 if on_tpu else 8)
    item = 4  # f32

    ceiling = _stream_ceiling(n, "profile_wrap")

    # --- 2./3. depth ladder ------------------------------------------
    rows = []
    for depth in (1, 2, 3, 4):
        os.environ["STENCIL_WRAP_STEPS"] = str(depth)
        if depth == 1:
            os.environ["STENCIL_DISABLE_WRAP2"] = "1"
        else:
            os.environ.pop("STENCIL_DISABLE_WRAP2", None)
        j = Jacobi3D(n, n, n, mesh_shape=(1, 1, 1),
                     devices=jax.devices()[:1], kernel="wrap",
                     dtype=jnp.float32)
        j.init()
        j.run(depth * 2)
        j.block()
        window = max(iters // 4, depth)
        window -= window % depth
        rates = []
        for wi in range(4):
            t0 = time.perf_counter()
            j.run(window)
            j.block()
            rates.append(window / (time.perf_counter() - t0))
        if args.trace and depth == 2:
            # traced window runs EXTRA and is excluded from the rate
            # stats: profiler overhead would skew the depth-2 row and
            # could flip the LIMITER verdict
            with jax.profiler.trace(args.trace):
                j.run(window)
                j.block()
            print(f"profile_wrap,trace,{args.trace}")
        rate = trimean(rates)
        # per-iteration HBM traffic of the depth-N kernel ~ (1 read +
        # 1 write pass + ring refetch) / N; ring refetch small at 512
        passes_per_iter = 2.0 / depth
        gbs = rate * passes_per_iter * n * n * n * item / 1e9
        rows.append((f"depth {depth}", rate, gbs))
        print(f"profile_wrap,wrap,{n},depth {depth},"
              f"{rate:.1f} iters/s,{gbs:.1f} GB/s-effective")
        del j

    sat = all(abs(rows[i][1] - rows[i - 1][1]) < 0.15 * rows[i - 1][1]
              for i in range(2, len(rows)))
    _verdict("profile_wrap", rows, ceiling, sat,
             "deeper temporal blocking or bf16")


if __name__ == "__main__":
    main()
