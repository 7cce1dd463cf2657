#!/usr/bin/env python
"""Single-chip kernel A/B bench: wrap vs halo vs xla compute paths.

Measures the fused-kernel iteration rate for Jacobi-3D (512^3 default)
and the Astaroth MHD integrator (256^3 default) on the current backend,
per kernel mode and block shape — the tuning harness behind the
BASELINE.json single-chip configurations (reference's bench ethos:
bin/jacobi3d.cu:383-392 CSV, trimean statistics).

Usage: python scripts/bench_kernels.py [--model jacobi|mhd|both]
       [--size N] [--iters N] [--kernels wrap,halo,xla] [--blocks ...]
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _bench_model(label, ctor, size, iters, kernels, blocks, patch_fn,
                 warmup):
    """Shared sweep loop: construct, optionally patch block shapes, warm
    up, time 4 windows, print one CSV line per kernel. Any one kernel's
    build/compile failure (e.g. a Mosaic scoped-VMEM OOM at an
    aggressive block shape) prints a FAIL line and must not abort the
    rest of the sweep."""
    from stencil_tpu.numerics import trimean

    for kernel in kernels:
        try:
            m = ctor(kernel)
        except ValueError as e:  # unsupported config for this kernel
            print(f"{label},{kernel},SKIP,{_one_line(e)}")
            continue
        except Exception as e:  # kernel build/compile failure
            print(f"{label},{kernel},{size},FAIL,{_one_line(e)}")
            continue
        try:
            if kernel in ("wrap", "halo") and blocks:
                patch_fn(m, kernel, blocks)
            m.init()
            m.run(warmup)
            m.block()
            window = max(iters // 4, 1)
            rates = []
            for _ in range(4):
                t0 = time.perf_counter()
                m.run(window)
                m.block()
                rates.append(window / (time.perf_counter() - t0))
            print(f"{label},{kernel},{size},{trimean(rates):.2f} iters/s,"
                  f"min {min(rates):.2f},max {max(rates):.2f}")
        except Exception as e:
            print(f"{label},{kernel},{size},FAIL,{_one_line(e)}")
        del m


def bench_jacobi(size, iters, kernels, blocks, dtype="f32"):
    import jax
    import jax.numpy as jnp
    from stencil_tpu.models.jacobi import Jacobi3D

    dt = jnp.bfloat16 if dtype == "bf16" else jnp.float32

    def ctor(kernel):
        return Jacobi3D(size, size, size, mesh_shape=(1, 1, 1),
                        devices=jax.devices()[:1], kernel=kernel,
                        dtype=dt)

    _bench_model("jacobi", ctor, size, iters, kernels, blocks,
                 _patch_jacobi_blocks, warmup=5)


def _patch_jacobi_blocks(j, kernel, blocks):
    """Rebuild the step with explicit (bz, by) via functools.partial on
    the kernel module entry (tuning hook, not a public knob)."""
    import functools
    from stencil_tpu.ops import pallas_halo, pallas_stencil

    bz, by = blocks
    if kernel == "wrap":
        # the wrap step runs N-step groups through the wrapn kernel
        # with a single-step tail — patch BOTH so the sweep measures
        # what it reports
        orig1 = pallas_stencil.jacobi7_wrap_pallas
        orign = pallas_stencil.jacobi7_wrapn_pallas
        pallas_stencil.jacobi7_wrap_pallas = functools.partial(
            orig1, block_z=bz, block_y=by)
        pallas_stencil.jacobi7_wrapn_pallas = functools.partial(
            orign, block_z=bz, block_y=by)
        try:
            j._build_wrap_step()
        finally:
            pallas_stencil.jacobi7_wrap_pallas = orig1
            pallas_stencil.jacobi7_wrapn_pallas = orign
    else:
        # the halo path runs N-step groups (jacobi7_halon_pallas, blocks
        # from fit_pair_halo_blocks) with a single-step tail — ONE
        # resolved (bz, by) decision drives both, so a measurement is
        # never a hybrid of swept-group + default-tail shapes (or vice
        # versa). Swept shapes are honored as-given (the sweep's whole
        # point); only a shape whose byte model exceeds the kernel's
        # actual 64 MiB scoped-VMEM compile ceiling — certain to fail —
        # is replaced by the default fit, with a visible stderr note so
        # the CSV row is not silently mislabeled.
        orig = pallas_halo.jacobi7_halo_pallas
        orig_fit = pallas_halo.fit_pair_halo_blocks
        from stencil_tpu.ops.pallas_stencil import sublane_tile_bytes
        hard = 64 * 2**20   # pallas_halo kernels' vmem_limit_bytes
        resolved = {}

        def _fit_swept(Z, Y, X, item, steps=2):
            cand = (pallas_halo._shrink_block(Z, bz),
                    pallas_halo._shrink_block(Y, by,
                                              sublane_tile_bytes(item)))
            if (pallas_halo._pair_block_bytes(cand[0], cand[1], X, item,
                                              steps) > hard):
                fb = orig_fit(Z, Y, X, item, steps)
                print(f"swept blocks {cand} exceed the {hard >> 20} MiB "
                      f"scoped-VMEM ceiling; measuring fallback {fb}",
                      file=sys.stderr)
                cand = fb
            resolved["blocks"] = cand
            return cand

        def _tail(*a, **kw):
            blk = resolved.get("blocks", (bz, by))
            kw.setdefault("block_z", blk[0])
            kw.setdefault("block_y", blk[1])
            return orig(*a, **kw)

        pallas_halo.jacobi7_halo_pallas = _tail
        pallas_halo.fit_pair_halo_blocks = _fit_swept
        try:
            j._build_halo_step()
        finally:
            pallas_halo.jacobi7_halo_pallas = orig
            pallas_halo.fit_pair_halo_blocks = orig_fit


def bench_mhd(size, iters, kernels, blocks, dtype="f32"):
    import jax
    import jax.numpy as jnp
    from stencil_tpu.models.astaroth import Astaroth

    dt = jnp.bfloat16 if dtype == "bf16" else jnp.float32

    def ctor(kernel):
        return Astaroth(size, size, size, mesh_shape=(1, 1, 1),
                        devices=jax.devices()[:1], kernel=kernel,
                        dtype=dt)

    _bench_model("mhd", ctor, size, iters, kernels, blocks,
                 _patch_mhd_blocks, warmup=2)


def _patch_mhd_blocks(m, kernel, blocks):
    import functools
    import sys
    from stencil_tpu.ops import pallas_mhd

    bz, by = blocks
    # the kernels snap non-tile-multiple blocks down to the dtype's
    # sublane tile (16-row for bf16): say so, or the CSV row would be
    # labeled with a shape that was never measured (same stderr note
    # the jacobi sweep prints on a substituted blocking)
    local = m.dd.local_size
    tile = pallas_mhd.mhd_tile(m._dtype)
    actual = pallas_mhd._fit_blocks(local.z, local.y, bz, by, tile)
    if actual != (bz, by):
        print(f"note: blocks {bz},{by} snapped to "
              f"{actual[0]},{actual[1]} (dtype tile {tile}, local "
              f"{local.z}x{local.y})", file=sys.stderr)
    if kernel == "wrap":
        # patch the fused substep-0+1 kernel too (STENCIL_MHD_PAIR=1
        # runs it for two of the three substeps)
        orig = pallas_mhd.mhd_substep_wrap_pallas
        orig01 = pallas_mhd.mhd_substep01_wrap_pallas
        pallas_mhd.mhd_substep_wrap_pallas = functools.partial(
            orig, block_z=bz, block_y=by)
        pallas_mhd.mhd_substep01_wrap_pallas = functools.partial(
            orig01, block_z=bz, block_y=by)
        try:
            m._build_wrap_step()
        finally:
            pallas_mhd.mhd_substep_wrap_pallas = orig
            pallas_mhd.mhd_substep01_wrap_pallas = orig01
    else:
        m._halo_blocks = (bz, by)
        m._build_halo_step()


def _one_line(e: Exception) -> str:
    """First line of an exception message, CSV-safe."""
    msg = f"{type(e).__name__}: {e}".splitlines()[0]
    return msg.replace(",", ";")


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", default="both",
                    choices=("jacobi", "mhd", "both"))
    ap.add_argument("--size", type=int, default=0,
                    help="cube edge (default 512 jacobi / 256 mhd)")
    ap.add_argument("--iters", type=int, default=0)
    ap.add_argument("--kernels", default="wrap,halo,xla")
    ap.add_argument("--blocks", default="",
                    help="bz,by override for pallas kernels")
    ap.add_argument("--dtype", default="f32", choices=("f32", "bf16"),
                    help="field dtype (bf16 halves HBM traffic; MHD "
                         "bf16 stores half-width, computes f32)")
    ap.add_argument("--fake-cpu", type=int, default=0, metavar="N",
                    help="run on N virtual CPU devices (smoke mode)")
    args = ap.parse_args()
    kernels = args.kernels.split(",")
    from stencil_tpu.utils.config import apply_fake_cpu, enable_compile_cache
    apply_fake_cpu(args.fake_cpu)
    enable_compile_cache()
    blocks = (tuple(int(v) for v in args.blocks.split(","))
              if args.blocks else None)

    import jax
    on_tpu = jax.default_backend() == "tpu"
    if args.model in ("jacobi", "both"):
        size = args.size or (512 if on_tpu else 32)
        iters = args.iters or (200 if on_tpu else 4)
        bench_jacobi(size, iters, kernels, blocks, args.dtype)
    if args.model in ("mhd", "both"):
        size = args.size or (256 if on_tpu else 16)
        iters = args.iters or (20 if on_tpu else 2)
        bench_mhd(size, iters, kernels, blocks, args.dtype)


if __name__ == "__main__":
    main()
