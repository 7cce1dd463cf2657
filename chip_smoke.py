#!/usr/bin/env python
"""Chip smoke: the Jacobi and MHD solvers on a TPU, end to end.

Runs the main path once through the public entry points (``Jacobi3D``,
``Astaroth``) at the sizes users deploy, checks each result against its
oracle, and prints as its LAST line exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Earlier lines are for humans. With no TPU, or when any phase raises or
misses its tolerance, it exits non-zero and prints no such line. It has
no CPU branch. Everything runs in this one process.

    python chip_smoke.py               # one chip: phases (a) Jacobi, (b) MHD
    python chip_smoke.py --four-chips  # all four chips vs one, nothing else
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# (a) the reference's jacobi3d 512^3 deployment (~1 GiB of fields)
JACOBI_N, JACOBI_CHECK_STEPS, JACOBI_RUN_STEPS = 512, 4, 200
# (b) BASELINE.json's "256^3/chip" MHD row: 8 fields, radius 3
MHD_N, MHD_CHECK_ITERS, MHD_RUN_ITERS = 256, 3, 20
# Jacobi vs its float64 dense oracle (__graft_entry__._check_jacobi)
JACOBI_TOL = 1e-5
# MHD fast path vs the XLA path. tests/test_astaroth.py holds float64
# fields to rtol=1e-11, atol=1e-13; the same tolerance counted in units
# of the dtype's epsilon, for float32 fields
_EPS_RATIO = 1.1920929e-07 / 2.220446049250313e-16
MHD_RTOL, MHD_ATOL = 1e-11 * _EPS_RATIO, 1e-13 * _EPS_RATIO


def _log(msg: str) -> None:
    print(msg, flush=True)


def _assert_on_tpu(arrays, want_devices: int) -> list:
    """Every array lives on TPU devices, ``want_devices`` of them;
    returns the sorted device ids."""
    ids = set()
    for a in arrays:
        devs = a.devices()
        bad = [d for d in devs if d.platform != "tpu"]
        if bad:
            raise AssertionError(f"array on non-TPU devices {bad}")
        if len(devs) != want_devices:
            raise AssertionError(
                f"array spans {len(devs)} devices, want {want_devices}")
        ids |= {d.id for d in devs}
    return sorted(ids)


def _errors(got, want, rtol, atol):
    """(max |got - want|, max |got - want| / (atol + rtol |want|)) over
    one array or a dict of them; the second is <= 1 within tolerance."""
    import numpy as np

    if isinstance(got, dict):
        pairs = [_errors(got[q], want[q], rtol, atol) for q in got]
        return tuple(max(p[i] for p in pairs) for i in (0, 1))
    diff = np.abs(np.asarray(got, np.float64) - np.asarray(want, np.float64))
    return (float(diff.max()),
            float((diff / (atol + rtol * np.abs(want))).max()))


def _check(label, got, want, rtol, atol):
    err, ratio = _errors(got, want, rtol, atol)
    _log(f"{label} max abs err {err:.3e} (rtol={rtol:.3g} atol={atol:.3g}"
         f", err/tol {ratio:.3f})")
    if not ratio <= 1.0:
        raise AssertionError(f"{label} off its oracle by {ratio:.3f}x tol")


def _timed(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def _jacobi(n, devices, steps):
    """A ripple-seeded Jacobi3D advanced ``steps`` steps; returns
    (model, first-call seconds, temperature)."""
    import numpy as np

    from stencil_tpu.models.jacobi import Jacobi3D, ripple_field

    j = Jacobi3D(n, n, n, dtype=np.float32, devices=devices, kernel="auto")
    j.dd.set_interior("temp", ripple_field((n, n, n), np.float32))
    first_s = _timed(lambda: (j.run(steps), j.block()))
    return j, first_s, j.temperature()


def _mhd(n, devices, iters, kernel="auto"):
    """An Astaroth model advanced ``iters`` iterations; returns (model,
    first-call seconds, {field: host array})."""
    import numpy as np

    from stencil_tpu.models.astaroth import FIELDS, Astaroth

    m = Astaroth(n, n, n, dtype=np.float32, devices=devices, kernel=kernel)
    m.init()
    first_s = _timed(lambda: (m.run(iters), m.block()))
    return m, first_s, {q: m.field(q) for q in FIELDS}


def _mesh(model):
    return tuple(model.dd.placement.dim())


def phase_jacobi(dev) -> None:
    """(a) Jacobi 512^3 on the Pallas wrap pair kernel vs the dense
    float64 oracle, then a steady run that must stay finite."""
    import numpy as np

    from stencil_tpu.models.jacobi import (_wrap_steps, dense_reference_step,
                                           ripple_field, sphere_geometry)
    from stencil_tpu.ops.pallas_stencil import sublane_tile

    n = JACOBI_N
    j, first_s, got = _jacobi(n, [dev], JACOBI_CHECK_STEPS)
    pair = _wrap_steps(sublane_tile(np.float32))
    _log(f"[jacobi] device_kind={dev.device_kind} mesh={_mesh(j)} "
         f"kernel_path={j.kernel_path} step_stride={j.step_stride}")
    if j.kernel_path != "wrap" or j.step_stride != pair or pair < 2:
        raise AssertionError(
            f"jacobi {n}^3 auto took {j.kernel_path} (stride "
            f"{j.step_stride}), want the wrap pair kernel (stride {pair})")
    _log(f"[jacobi] compile+{JACOBI_CHECK_STEPS} steps {first_s:.3f} s")
    hot, cold, sph_r = sphere_geometry(j.dd.size)
    want = ripple_field((n, n, n), np.float64)
    for _ in range(JACOBI_CHECK_STEPS):
        want = dense_reference_step(want, tuple(hot), tuple(cold), sph_r)
    _check("[jacobi] vs float64 oracle:", got, want, JACOBI_TOL, JACOBI_TOL)
    del want
    steady_s = _timed(lambda: (j.run(JACOBI_RUN_STEPS), j.block()))
    final = j.temperature()
    if not np.isfinite(final).all():
        raise AssertionError("jacobi field went non-finite")
    _assert_on_tpu([j.dd.curr["temp"]], 1)
    _log(f"[jacobi] {JACOBI_RUN_STEPS} steps in {steady_s:.3f} s = "
         f"{JACOBI_RUN_STEPS / steady_s:.2f} steps/s (device_sync timed); "
         f"field finite")


def phase_mhd(dev) -> None:
    """(b) MHD 256^3 on the Pallas wrap megakernel vs the XLA path on
    the same chip, then a longer run that must stay finite."""
    import numpy as np

    from stencil_tpu.models.astaroth import FIELDS

    n = MHD_N
    m, first_s, got = _mhd(n, [dev], MHD_CHECK_ITERS)
    _log(f"[mhd] device_kind={dev.device_kind} mesh={_mesh(m)} "
         f"kernel_path={m.kernel_path}")
    if m.kernel_path != "wrap":
        raise AssertionError(
            f"mhd {n}^3 auto took {m.kernel_path}, want wrap")
    ref, ref_first_s, want = _mhd(n, [dev], MHD_CHECK_ITERS, kernel="xla")
    if ref.kernel_path != "xla":
        raise AssertionError(f"oracle took {ref.kernel_path}")
    del ref
    _log(f"[mhd] compile+{MHD_CHECK_ITERS} iters {first_s:.3f} s (xla "
         f"oracle {ref_first_s:.3f} s)")
    _check("[mhd] vs xla path:", got, want, MHD_RTOL, MHD_ATOL)
    steady_s = _timed(lambda: (m.run(MHD_RUN_ITERS), m.block()))
    for q in FIELDS:
        if not np.isfinite(m.field(q)).all():
            raise AssertionError(f"mhd field {q} went non-finite")
    m.sync_domain()
    _assert_on_tpu(list(m.dd.curr.values()), 1)
    _log(f"[mhd] {MHD_RUN_ITERS} iters in {steady_s:.3f} s = "
         f"{MHD_RUN_ITERS / steady_s:.2f} iters/s; fields finite")


def phase_four_chips(devices) -> None:
    """Jacobi 512^3 and MHD 256^3 sharded over all four chips (the mesh
    auto chooses) vs the same model and seed on one chip."""
    steps = JACOBI_CHECK_STEPS
    j4, s4, got = _jacobi(JACOBI_N, devices, steps)
    ids = _assert_on_tpu([j4.dd.curr["temp"]], len(devices))
    _log(f"[4chip jacobi] mesh={_mesh(j4)} kernel_path={j4.kernel_path} "
         f"devices={ids} compile+{steps} steps {s4:.3f} s")
    del j4
    j1, s1, want = _jacobi(JACOBI_N, devices[:1], steps)
    _log(f"[4chip jacobi] 1-chip reference mesh={_mesh(j1)} "
         f"kernel_path={j1.kernel_path} compile+{steps} steps {s1:.3f} s")
    del j1
    _check("[4chip jacobi] 4 vs 1 chip:", got, want, JACOBI_TOL, JACOBI_TOL)
    del got, want

    iters = MHD_CHECK_ITERS
    m4, s4, got = _mhd(MHD_N, devices, iters)
    m4.sync_domain()
    ids = _assert_on_tpu(list(m4.dd.curr.values()), len(devices))
    _log(f"[4chip mhd] mesh={_mesh(m4)} kernel_path={m4.kernel_path} "
         f"devices={ids} compile+{iters} iters {s4:.3f} s")
    del m4
    m1, s1, want = _mhd(MHD_N, devices[:1], iters)
    _log(f"[4chip mhd] 1-chip reference mesh={_mesh(m1)} "
         f"kernel_path={m1.kernel_path} compile+{iters} iters {s1:.3f} s")
    del m1
    _check("[4chip mhd] 4 vs 1 chip:", got, want, MHD_RTOL, MHD_ATOL)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip sharded phase")
    args = ap.parse_args()

    from stencil_tpu.utils.config import enable_compile_cache

    enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r})",
              file=sys.stderr)
        return 1
    if args.four_chips:
        if len(devices) != 4:
            print(f"chip_smoke: --four-chips needs 4 chips, found "
                  f"{len(devices)}", file=sys.stderr)
            return 1
        phase_four_chips(devices)
    else:
        phase_jacobi(dev)
        phase_mhd(dev)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
