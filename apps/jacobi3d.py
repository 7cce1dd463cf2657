#!/usr/bin/env python
"""Weak-scaling Jacobi-3D heat demo.

Reference parity: bin/jacobi3d.cu — the global grid is the per-device
size scaled by the subdomain grid (weak scaling,
bin/jacobi3d.cu:181-205); CSV result line
``bin,methods,devices,x,y,z,bytes_x,bytes_y,bytes_z,min (s),trimean (s)``
(schema analog of bin/jacobi3d.cu:383-392).
"""

import argparse
import sys

from _common import (KERNEL_CHOICES, add_bench_record_flags,
                     add_dcn_flags, add_device_flags, add_dtype_flags,
                     add_method_flags, add_placement_flags,
                     apply_device_flags, csv_line, dcn_from_args,
                     dcn_mesh_shape, dtype_from_args,
                     emit_bench_artifacts, methods_from_args,
                     placement_from_args, sampled_steps_per_s)


def _run_resilient(j, args) -> None:
    """The chaos-smoke entry: drive the solver under the recovery
    driver with the seeded faults from the --chaos-* flags, then emit
    a summary line and (optionally) the event-log JSON artifact."""
    from stencil_tpu.resilience import (FaultPlan, HaloCorruption,
                                        NaNInjection, Preemption,
                                        ResiliencePolicy,
                                        TransientSaveFailure)

    plan = FaultPlan(seed=args.chaos_seed)
    if args.chaos_nan:
        plan.nans.append(NaNInjection(step=args.chaos_nan))
    if args.chaos_halo:
        plan.halos.append(HaloCorruption(step=args.chaos_halo))
    if args.chaos_save_fail:
        plan.save_failures.append(
            TransientSaveFailure(step=args.chaos_save_fail))
    if args.chaos_preempt:
        plan.preemptions.append(Preemption(step=args.chaos_preempt))
    policy = ResiliencePolicy(check_every=args.check_every,
                              ckpt_every=args.ckpt_every,
                              max_retries=args.max_retries,
                              base_delay=0.01,
                              fuse_segments=args.fuse_segments)
    report = j.run_resilient(args.iters, policy=policy,
                             ckpt_dir=args.ckpt_dir or None,
                             faults=plan)
    if args.events_json:
        report.write(args.events_json)
    print(csv_line("jacobi3d-resilient", methods_label(args),
                   report.steps, report.rollbacks, report.save_retries,
                   len(report.degradations),
                   int(report.preempted), report.final_config))


def methods_label(args) -> str:
    return str(methods_from_args(args))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--x", type=int, default=128, help="per-device x size")
    ap.add_argument("--y", type=int, default=128)
    ap.add_argument("--z", type=int, default=128)
    ap.add_argument("--iters", "-n", type=int, default=30)
    ap.add_argument("--batch", type=int, default=10,
                    help="iterations per timing sample (fused loop)")
    ap.add_argument("--prefix", default="", help="output prefix")
    ap.add_argument("--json-out", default="", metavar="PATH",
                    help="write the timed run's bench record (steps/s "
                         "+ byte model) as a JSON artifact")
    ap.add_argument("--paraview", action="store_true")
    ap.add_argument("--period", type=int, default=0,
                    help="paraview dump every N samples")
    add_dtype_flags(ap)
    ap.add_argument("--kernel", default="auto", choices=KERNEL_CHOICES,
                    help="compute path: fused Pallas (wrap: single-chip "
                         "periodic; halo: multi-chip slab layout), XLA "
                         "slicing (xla), padded-layout Pallas (pallas), "
                         "or pick by hardware (auto)")
    ap.add_argument("--exchange-every", type=int, default=0, metavar="S",
                    help="communication-avoiding temporal blocking: one "
                         "depth-S halo exchange per S iterations (the "
                         "XLA path fuses S sub-steps on shrinking "
                         "windows; the wrap/halo fast paths set their "
                         "in-kernel step count to S)")
    add_method_flags(ap)
    add_placement_flags(ap)
    add_dcn_flags(ap)
    add_device_flags(ap)
    add_bench_record_flags(ap)
    res = ap.add_argument_group(
        "resilience", "run under the checkpoint-rollback recovery "
        "driver (stencil_tpu/resilience); the --chaos-* flags inject "
        "seeded faults so recovery paths can be smoked in CI")
    res.add_argument("--resilient", action="store_true",
                     help="run --iters iterations under run_resilient "
                          "instead of the timed benchmark loop")
    res.add_argument("--ckpt-dir", default="",
                     help="checkpoint/resume directory (preempted runs "
                          "resume from it on the next invocation)")
    res.add_argument("--ckpt-every", type=int, default=10)
    res.add_argument("--check-every", type=int, default=1,
                     help="health-sentinel boundary cadence (steps); "
                          "with --fuse-segments this is also the "
                          "megastep segment length")
    res.add_argument("--fuse-segments",
                     action=argparse.BooleanOptionalAction,
                     default=True,
                     help="megastep execution (default on): dispatch "
                          "ONE fused program per check_every boundary "
                          "with the health probe trace in-graph "
                          "(parallel/megastep.py); "
                          "--no-fuse-segments restores the per-step "
                          "dispatch loop")
    res.add_argument("--max-retries", type=int, default=3)
    res.add_argument("--events-json", default="",
                     help="write the resilience event log (JSON) here")
    res.add_argument("--chaos-nan", type=int, default=0, metavar="STEP",
                     help="inject one NaN into shard 0 after STEP")
    res.add_argument("--chaos-halo", type=int, default=0, metavar="STEP",
                     help="corrupt a halo cell after STEP")
    res.add_argument("--chaos-save-fail", type=int, default=0,
                     metavar="STEP", help="the checkpoint save at STEP "
                     "raises transient IOErrors (retried)")
    res.add_argument("--chaos-preempt", type=int, default=0,
                     metavar="STEP", help="deliver SIGTERM after STEP")
    res.add_argument("--chaos-seed", type=int, default=0)
    args = ap.parse_args()
    apply_device_flags(args)
    dtype = dtype_from_args(args)

    import jax

    from stencil_tpu.models.jacobi import Jacobi3D
    from stencil_tpu.ops.pallas_stencil import on_tpu
    from stencil_tpu.parallel.mesh import (default_mesh_shape,
                                           default_mesh_shape_xfree)

    ndev = len(jax.devices())
    # halo-capable paths want the lane (x) axis unsharded; "auto" only
    # selects them on TPU, so keep the cube-like mesh off-TPU
    xfree = (args.kernel == "halo"
             or (args.kernel == "auto" and on_tpu()))
    mesh_shape = (dcn_mesh_shape(args, xfree)
                  or (default_mesh_shape_xfree(ndev) if xfree
                      else default_mesh_shape(ndev)))
    # weak scaling: global = local x mesh (bin/jacobi3d.cu:181-205)
    gx, gy, gz = (args.x * mesh_shape.x, args.y * mesh_shape.y,
                  args.z * mesh_shape.z)
    methods = methods_from_args(args)
    j = Jacobi3D(gx, gy, gz, mesh_shape=mesh_shape,
                 dtype=dtype,
                 methods=methods,
                 placement=placement_from_args(args),
                 output_prefix=args.prefix, kernel=args.kernel,
                 exchange_every=args.exchange_every or None,
                 **dcn_from_args(args))
    j.init()
    if args.paraview:
        j.dd.write_paraview(args.prefix + "jacobi3d_init")

    if args.resilient:
        _run_resilient(j, args)
        return

    samples = max(args.iters // args.batch, 1)
    n = 0

    def one():
        nonlocal n
        j.run(args.batch)
        n += 1
        if args.paraview and args.period and n % args.period == 0:
            j.dd.write_paraview(f"{args.prefix}jacobi3d_{n}")

    # the one shared warmup/measure/block contract (_common)
    stats, sps = sampled_steps_per_s(one, j.block, samples, args.batch)
    b = j.dd.exchange_bytes_per_axis()
    # honest exchange-cost estimate for the built path (the fused fast
    # paths never call dd.exchange(); see Jacobi3D.exchange_stats):
    # exchange seconds and wire bytes per ITERATION
    xstats = j.exchange_stats()
    ex_s = j.measure_exchange_seconds()
    print(csv_line("jacobi3d", methods, ndev, gx, gy, gz,
                   b["x"], b["y"], b["z"],
                   f"{stats.min() / args.batch:.6e}",
                   f"{stats.trimean() / args.batch:.6e}",
                   xstats["path"], int(xstats["bytes_per_iteration"]),
                   f"{ex_s:.6e}"))
    # tiling-plan provenance for the ledger: when a Pallas kernel path
    # ran, record the block shapes the VMEM planner prescribed for this
    # shard geometry (observatory records then group real-TPU numbers
    # against the shapes that produced them)
    tiling_plan = None
    if "xla" not in xstats["path"]:
        try:
            from stencil_tpu.parallel.mesh import mesh_dim
            from stencil_tpu.tuning import (geometry_from_domain,
                                            tiling_record)

            tiling_plan = tiling_record(
                geometry_from_domain(j.dd, mesh_dim(j.dd.mesh)))
        except Exception as e:  # noqa: BLE001 — provenance best-effort
            print(f"jacobi3d: tiling provenance unavailable: {e}",
                  file=sys.stderr)
    emit_bench_artifacts(
        args,
        {"bench": "jacobi3d",
         **({"tiling_plan": tiling_plan} if tiling_plan else {}),
         "config": {"grid": [gx, gy, gz], "devices": ndev,
                    "mesh": list(mesh_shape), "kernel": xstats["path"],
                    "methods": str(methods),
                    "dtype": str(dtype.__name__ if hasattr(dtype, "__name__")
                                 else dtype),
                    "exchange_every": args.exchange_every or 1},
         "metrics": {"steps_per_s": sps,
                     "min_step_s": stats.min() / args.batch,
                     "trimean_step_s": stats.trimean() / args.batch,
                     "bytes_per_iteration_model":
                         float(xstats["bytes_per_iteration"]),
                     "exchange_s_per_iteration": ex_s}},
        "jacobi3d")
    if args.paraview:
        j.dd.write_paraview(args.prefix + "jacobi3d_final")


if __name__ == "__main__":
    main()
