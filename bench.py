#!/usr/bin/env python
"""Headline benchmark: Jacobi-3D iteration rate + halo-exchange bandwidth.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "extra": {...}}

North-star metric: jacobi3d iters/sec at 512^3, radius 1, measured with
the reference's statistics (trimean over sample windows,
bin/statistics.hpp analog). It runs in this one process, on the TPU
only: with no TPU, or on any failure, it exits non-zero and prints no
result.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

SIZE, ITERS, WARMUP, N_EXCHANGE = 512, 200, 10, 50


def main() -> None:
    import jax
    import numpy as np

    from stencil_tpu.utils.config import enable_compile_cache
    enable_compile_cache()
    dev0 = jax.devices()[0]
    if dev0.platform != "tpu":
        sys.exit(f"bench.py measures on a TPU only; JAX found "
                 f"{dev0.platform!r}")

    from stencil_tpu.models.jacobi import Jacobi3D
    from stencil_tpu.numerics import trimean
    from stencil_tpu.parallel.mesh import default_mesh_shape
    from stencil_tpu.utils.timers import device_sync

    ndev = len(jax.devices())
    mesh_shape = default_mesh_shape(ndev)
    j = Jacobi3D(SIZE, SIZE, SIZE, mesh_shape=mesh_shape, dtype=np.float32)
    j.init()
    j.run(WARMUP)
    j.block()

    # iteration rate: several timed windows, trimean (reference
    # statistics schema, bin/statistics.hpp:6-19)
    window = max(ITERS // 4, 1)
    rates = []
    for _ in range(4):
        t0 = time.perf_counter()
        j.run(window)
        j.block()
        rates.append(window / (time.perf_counter() - t0))
    iters_per_sec = trimean(rates)

    # exchange-only bandwidth: cross-device bytes only (axes with mesh
    # count 1 are local wraps, not wire traffic) — same accounting as
    # DistributedDomain's byte counters
    dd = j.dd
    total_halo_bytes = dd.exchange_bytes_total()
    ex = dd._exchange_fn
    out = ex(dd.curr)  # compile
    device_sync(out)
    t0 = time.perf_counter()
    for _ in range(N_EXCHANGE):
        out = ex(out)
    device_sync(out)
    ex_s = (time.perf_counter() - t0) / N_EXCHANGE

    print(json.dumps({
        "metric": f"jacobi3d_{SIZE}c_iters_per_sec",
        "value": round(iters_per_sec, 2),
        "unit": "iters/s",
        "extra": {
            "devices": ndev,
            "mesh": tuple(mesh_shape),
            "platform": dev0.platform,
            "device_kind": dev0.device_kind,
            "kernel_path": j.kernel_path,
            # On one chip there is no wire traffic — report null, not a
            # misleading 0.0 bandwidth.
            "exchange_GBps": (round(total_halo_bytes / ex_s / 1e9, 2)
                              if total_halo_bytes else None),
            "exchange_s": round(ex_s, 6),
            "halo_bytes_per_exchange": total_halo_bytes,
        },
    }))


if __name__ == "__main__":
    main()
