#!/usr/bin/env python
"""Halo-exchange micro-benchmark with face/edge/corner radius control.

Reference parity: bin/bench_exchange.cu — ``--fr/--er/--cr`` radius
flags, reports trimean seconds and trimean B/s
(bin/bench_exchange.cu:58-64,86-100).

Temporal blocking: ``--exchange-every 1,4`` sweeps communication-
avoiding depths. Each depth is measured two ways: (a) the classic
per-exchange timing on a domain built with ``set_exchange_every(s)``
(deep slabs), and (b) an honest steps/s of the REAL blocked hot path —
``Jacobi3D(exchange_every=s)``'s fused run loop, which pays the
redundant ring compute and the deeper slabs that blocking actually
costs. The amortized byte model (the same source the static analyzer
cross-checks against HLO) is printed next to the measured numbers;
``--json-out`` archives the comparison (the CI bench-smoke artifact).

Only ``csv_line`` rows go to stdout (the CSV artifact); the sweep
commentary goes to stderr.
"""

import argparse
import sys

from _common import (add_bench_record_flags, add_device_flags,
                     add_method_flags, apply_device_flags, csv_line,
                     emit_bench_artifacts, grouped_steps_per_s,
                     methods_from_args, timed_samples)


def _parse_depths(text: str):
    """Comma list of depths to sweep. Plain integers are uniform
    depths; ``axis=value`` tokens (``z=4,y=1,x=1``) merge into ONE
    per-axis asymmetric candidate appended after the uniform sweep."""
    ints = set()
    axes = {}
    for t in (t.strip() for t in text.split(",")):
        if not t:
            continue
        if "=" in t:
            k, v = t.split("=", 1)
            k = k.strip().lower()
            if k not in ("x", "y", "z"):
                raise SystemExit(f"--exchange-every axis token wants "
                                 f"x=/y=/z=, got {t!r}")
            axes[k] = int(v)
        else:
            ints.add(int(t))
    depths = sorted(ints)
    if axes:
        depths.append(axes)
    bad = any(s < 1 for s in ints) or any(v < 1 for v in axes.values())
    if not depths or bad:
        raise SystemExit(f"--exchange-every wants depths >= 1, got {text!r}")
    return depths


def _depth_max(s) -> int:
    return max(s.values()) if isinstance(s, dict) else int(s)


def _depth_label(s) -> str:
    """Stable config label: uniform depths keep the bare integer (the
    historical trajectory key); per-axis depths read ``x.y.z``."""
    if isinstance(s, dict):
        from stencil_tpu.geometry import normalize_depths
        d = normalize_depths(s)
        return f"{d.x}.{d.y}.{d.z}"
    return str(s)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--x", type=int, default=128, help="per-device x size")
    ap.add_argument("--y", type=int, default=128)
    ap.add_argument("--z", type=int, default=128)
    ap.add_argument("--fr", type=int, default=2, help="face radius")
    ap.add_argument("--er", type=int, default=2, help="edge radius")
    ap.add_argument("--cr", type=int, default=2, help="corner radius")
    ap.add_argument("--fields", type=int, default=1)
    ap.add_argument("--iters", "-n", type=int, default=30)
    ap.add_argument("--exchange-every", default="1", metavar="S[,S...]",
                    help="temporal-blocking depths to sweep (comma "
                         "list; 1 = the classic per-step exchange; "
                         "axis=value tokens like z=4,y=1,x=1 merge "
                         "into one per-axis asymmetric candidate)")
    ap.add_argument("--wire-layout", default="slab", metavar="L[,L...]",
                    help="halo wire message layouts (comma list of "
                         "slab,irredundant): the first is the sweep's "
                         "layout; each EXTRA layout races per-exchange "
                         "seconds + the blocked Jacobi steps/s against "
                         "the sweep baseline at its smallest depth")
    ap.add_argument("--json-out", default="", metavar="PATH",
                    help="write the steps/s + byte-model comparison "
                         "as a JSON artifact")
    ap.add_argument("--metrics-json", default="", metavar="PATH",
                    help="also record the measured numbers as a "
                         "telemetry metrics snapshot (gauges "
                         "stencil_bench_steps_per_s{exchange_every=}, "
                         "stencil_bench_bytes_per_step_model{...}) so "
                         "BENCH_*.json and the metrics surface agree "
                         "on one figure")
    ap.add_argument("--fuse-segments", action="store_true",
                    help="race megastep execution (ONE fused dispatch "
                         "per --check-every steps, health probe trace "
                         "in-graph; parallel/megastep.py) against the "
                         "per-step dispatch loop on the same Jacobi "
                         "problem")
    ap.add_argument("--check-every", type=int, default=8,
                    help="megastep segment length for --fuse-segments")
    ap.add_argument("--autotune", action="store_true",
                    help="run the exchange autotuner (measured plan, "
                         "stencil_tpu/tuning) and compare tuned vs "
                         "Method.Default steps/s on the real blocked "
                         "Jacobi loop")
    ap.add_argument("--tune-cache", default="", metavar="PATH",
                    help="plan cache file for --autotune (default: "
                         "$STENCIL_TUNE_CACHE or "
                         "~/.cache/stencil_tpu/plans.json)")
    add_method_flags(ap)
    add_device_flags(ap)
    add_bench_record_flags(ap)
    args = ap.parse_args()
    apply_device_flags(args)

    import jax
    import numpy as np

    from stencil_tpu.distributed import DistributedDomain
    from stencil_tpu.geometry import Radius
    from stencil_tpu.models.jacobi import Jacobi3D
    from stencil_tpu.parallel.mesh import default_mesh_shape
    from stencil_tpu.utils.timers import device_sync

    from stencil_tpu.parallel.packing import WIRE_LAYOUTS

    ndev = len(jax.devices())
    mesh_shape = default_mesh_shape(ndev)
    gx, gy, gz = (args.x * mesh_shape.x, args.y * mesh_shape.y,
                  args.z * mesh_shape.z)
    depths = _parse_depths(args.exchange_every)
    layouts = [t.strip() for t in args.wire_layout.split(",") if t.strip()]
    bad = [t for t in layouts if t not in WIRE_LAYOUTS]
    if not layouts or bad:
        raise SystemExit(f"--wire-layout wants a comma list from "
                         f"{WIRE_LAYOUTS}, got {args.wire_layout!r}")
    primary_layout = layouts[0]

    def jacobi_steps_per_s(methods, s, layout=primary_layout):
        """Honest steps/s of the REAL blocked hot path: the Jacobi
        model's fused run loop (deep exchange + sub-steps incl. the
        redundant ring compute) under the given configuration, measured
        by the one shared warmup/measure/block contract
        (``_common.grouped_steps_per_s``)."""
        j = Jacobi3D(gx, gy, gz, mesh_shape=mesh_shape,
                     dtype=np.float32, kernel="xla", methods=methods,
                     exchange_every=s if _depth_max(s) > 1 else None,
                     wire_layout=layout)
        j.init()
        n, dt, sps = grouped_steps_per_s(j.run, j.block, args.iters,
                                         group=_depth_max(s))
        return n, dt, sps, j

    def make_domain(layout=primary_layout, s=1):
        dd = DistributedDomain(gx, gy, gz)
        dd.set_mesh_shape(mesh_shape)
        dd.set_radius(Radius.face_edge_corner(args.fr, args.er, args.cr))
        dd.set_methods(methods_from_args(args))
        dd.set_wire_layout(layout)
        if _depth_max(s) > 1:
            dd.set_exchange_every(s)
        for i in range(args.fields):
            dd.add_data(f"q{i}", np.float32)
        dd.realize()
        return dd

    results = []
    link_classes = None  # baseline depth's classified link map
    for s in depths:
        dd = make_domain(s=s)

        # per-exchange timing (the classic bench line, now per config)
        stats = timed_samples(dd.exchange, lambda: device_sync(dd.curr),
                              args.iters)
        per_ex = dd.exchange_bytes_total()
        tm = stats.trimean()
        print(csv_line("bench_exchange", dd.methods, ndev,
                       args.x, args.y, args.z, args.fr, args.er, args.cr,
                       args.fields, _depth_label(s), per_ex,
                       f"{tm:.6e}", f"{per_ex / tm:.6e}"))

        # honest steps/s: the REAL blocked hot path (deep exchange +
        # fused sub-steps incl. the redundant ring compute), via the
        # Jacobi model's radius-1 run loop on the same grid
        n, dt, _, j = jacobi_steps_per_s(methods_from_args(args), s)
        xs = j.exchange_stats()
        row_extra = {}
        if isinstance(s, dict):
            d = j.dd.exchange_depths
            row_extra["depths"] = [d.x, d.y, d.z]
        results.append({
            "exchange_every": (s if isinstance(s, int)
                               else _depth_label(s)),
            **row_extra,
            "steps": n,
            "seconds": dt,
            "steps_per_s": n / dt,
            "exchange_rounds_per_step": xs["rounds_per_iteration"],
            "bytes_per_exchange_model": per_ex,
            "amortized_bytes_per_step_model":
                dd.exchange_bytes_amortized_per_step(),
            "jacobi_bytes_per_step_model": xs["bytes_per_iteration"],
            "trimean_exchange_s": tm,
        })
        print(f"bench_exchange steps: s={_depth_label(s)} "
              f"steps/s={n / dt:.3f} (jacobi blocked loop) "
              f"rounds/step={xs['rounds_per_iteration']:.3f} "
              f"amortized={dd.exchange_bytes_amortized_per_step():.0f}"
              f"B/step (model)", file=sys.stderr)

        if s == depths[0]:
            # link observatory: classify the baseline configuration's
            # modeled traffic matrix against the deployed device order
            # and pair it with the measured per-exchange seconds —
            # per-link B/step + achieved/fitted-peak utilization (the
            # ROADMAP item 3 placement signal, live on every bench)
            from stencil_tpu.observatory.linkmap import \
                link_attribution_for
            link = link_attribution_for(dd)
            if link is not None:
                # ONE derived block feeds all three surfaces (the
                # JSON payload, the metrics gauges, the ledger
                # stamp): utilization = the link's B/s during the
                # measured exchange round over its fitted peak
                total = sum(link["bytes_per_step"].values()) or 1.0
                link_classes = {
                    f"{axis}/{klass}": {
                        "bytes_per_step": b,
                        "share": b / total,
                        "utilization": (b * _depth_max(s) / tm)
                        / link["peak_bytes_per_s"].get(axis, 1e30),
                    }
                    for (axis, klass), b
                    in sorted(link["bytes_per_step"].items())}

    layout_cmp = None
    if len(layouts) > 1:
        # wire-layout race: each extra layout re-runs the smallest
        # swept depth's two measurements (per-exchange seconds on the
        # domain, blocked Jacobi steps/s on the real hot path) and is
        # reported as a ratio against the sweep baseline in results[0].
        # Bytes come from the SAME per-layout model the static analyzer
        # pins against HLO, so the bytes ratio is exact, not sampled.
        base = results[0]
        s0 = depths[0]
        layout_cmp = {"baseline_layout": primary_layout,
                      "exchange_every": s0, "races": {}}
        for layout in layouts[1:]:
            dd = make_domain(layout=layout, s=s0)
            stats = timed_samples(dd.exchange,
                                  lambda: device_sync(dd.curr),
                                  args.iters)
            tm = stats.trimean()
            per_ex = dd.exchange_bytes_total()
            n, dt, sps, _ = jacobi_steps_per_s(
                methods_from_args(args), s0, layout=layout)
            bytes_ratio = (per_ex
                           / (base["bytes_per_exchange_model"] or 1))
            sps_ratio = sps / base["steps_per_s"]
            layout_cmp["races"][layout] = {
                "bytes_per_exchange_model": per_ex,
                "bytes_ratio": bytes_ratio,
                "trimean_exchange_s": tm,
                "exchange_s_ratio": tm / base["trimean_exchange_s"],
                "steps_per_s": sps,
                "steps_per_s_ratio": sps_ratio,
            }
            print(csv_line("bench_exchange_layout", layout,
                           primary_layout, s0, per_ex,
                           f"{tm:.6e}", f"{bytes_ratio:.4f}",
                           f"{sps_ratio:.3f}"))
            print(f"bench_exchange layout: {layout} "
                  f"{per_ex}B/exchange "
                  f"({bytes_ratio:.3f}x {primary_layout} bytes) "
                  f"{sps:.3f} steps/s "
                  f"(x{sps_ratio:.2f} blocked loop)",
                  file=sys.stderr)

    autotune_cmp = None
    if args.autotune:
        # tune for the Jacobi hot-path problem itself (radius 1, one
        # f32 field — the configuration the steps/s claim is about),
        # then race the MEASURED plan against the static Method.Default
        # on the real blocked loop
        from stencil_tpu.distributed import DistributedDomain
        from stencil_tpu.parallel.methods import Method
        from stencil_tpu.utils.profiling import autotune_report

        dd = DistributedDomain(gx, gy, gz)
        dd.set_mesh_shape(mesh_shape)
        dd.set_radius(1)
        dd.add_data("temp", np.float32)
        plan = dd.autotune(cache_path=args.tune_cache or None)
        print(autotune_report(plan), file=sys.stderr)

        # reuse the sweep's s=1 row as the baseline when it already
        # measured exactly Method.Default (no method flags, depth 1
        # swept) instead of re-compiling the same configuration
        base_row = next(
            (r for r in results if r["exchange_every"] == 1
             and methods_from_args(args) == Method.Default), None)
        if base_row is not None:
            base_sps = base_row["steps_per_s"]
        else:
            _, _, base_sps, _ = jacobi_steps_per_s(Method.Default, 1)
        tuned_m = Method[plan.config.method]
        _, _, tuned_sps, _ = jacobi_steps_per_s(
            tuned_m, plan.config.exchange_every)
        autotune_cmp = {
            "plan": plan.to_record(),
            "default_steps_per_s": base_sps,
            "tuned_steps_per_s": tuned_sps,
            "tuned_over_default": tuned_sps / base_sps,
        }
        print(csv_line("bench_exchange_autotune", plan.config.key(),
                       plan.provenance, f"{base_sps:.3f}",
                       f"{tuned_sps:.3f}",
                       f"{tuned_sps / base_sps:.3f}"))
        print(f"bench_exchange autotune: tuned {plan.config.key()} "
              f"({plan.provenance}) {tuned_sps:.3f} steps/s vs default "
              f"{base_sps:.3f} steps/s "
              f"(x{tuned_sps / base_sps:.2f})", file=sys.stderr)

    fused_cmp = None
    if args.fuse_segments:
        # fused megastep vs the per-step dispatch loop the megastep
        # replaced (resilience/driver.py's stepwise mode at
        # check_every=1): one jitted STEP dispatch + one health-probe
        # dispatch per Python iteration on the baseline side, ONE
        # fused dispatch per k steps with the same per-step probes
        # riding in-graph on the megastep side. Same problem, same
        # health coverage — only the host/device boundary moves. The
        # race runs the per-device smoke size on ONE device: that is
        # the dispatch-bound regime the megastep targets (on the
        # multi-threaded fake CPU mesh, in-program thread sync — which
        # fusion cannot remove — swamps the dispatch signal). Three
        # legs, one per newly-fused carry contract: XLA Jacobi, the
        # full PIC state (particle lanes + overflow column in-graph),
        # and Astaroth's temporal path (w carry under lcm(3, s)
        # grouping) — the trajectory for the latter two was empty
        # before the segment compiler.
        from _common import megastep_race

        k = max(args.check_every, 1)
        n = max(args.iters, k)
        n -= n % k
        dev1 = jax.devices()[:1]

        from stencil_tpu.models.astaroth import Astaroth
        from stencil_tpu.models.pic import Pic
        from stencil_tpu.resilience.health import HealthSentinel

        def leg(name, make_engine, make_sentinel, fields_fn, **extra):
            sps, fps, ratio = megastep_race(make_engine, make_sentinel,
                                            fields_fn, k, n)
            row = {"check_every": k, "steps": n,
                   "stepwise_steps_per_s": sps,
                   "fused_steps_per_s": fps,
                   "fused_over_stepwise": ratio, **extra}
            print(csv_line(f"bench_exchange_megastep_{name}", k, n,
                           f"{sps:.3f}", f"{fps:.3f}",
                           f"{ratio:.3f}"))
            print(f"bench_exchange megastep[{name}]: fused[k={k}] "
                  f"{fps:.3f} steps/s vs per-step dispatch "
                  f"{sps:.3f} steps/s (x{ratio:.2f})",
                  file=sys.stderr)
            return row

        def mk_jacobi():
            j = Jacobi3D(args.x, args.y, args.z, mesh_shape=(1, 1, 1),
                         devices=dev1, dtype=np.float32, kernel="xla",
                         methods=methods_from_args(args))
            j.init()
            return j

        def mk_pic():
            # a dispatch-bound particle count: enough to exercise the
            # full deposit/gather/migrate step, small enough that the
            # host round-trip (not compute) sets stepwise steps/s
            return Pic(args.x, args.y, args.z, 256,
                       mesh_shape=(1, 1, 1), devices=dev1,
                       dtype=np.float32, deposition="cic")

        ast_s = 2

        def mk_astaroth():
            a = Astaroth(args.x, args.y, args.z, mesh_shape=(1, 1, 1),
                         devices=dev1, dtype=np.float32, kernel="xla",
                         exchange_every=ast_s)
            a.init()
            return a

        fused_cmp = leg("jacobi", mk_jacobi,
                        lambda e: HealthSentinel(e.dd),
                        lambda e: e.dd.curr)
        fused_cmp["pic"] = leg("pic", mk_pic,
                               lambda e: e.make_sentinel(),
                               lambda e: e.state)
        fused_cmp["astaroth_temporal"] = leg(
            "astaroth", mk_astaroth, lambda e: HealthSentinel(e.dd),
            lambda e: e.dd.curr, exchange_every=ast_s)
        # keep the legacy CSV row shape for dashboards parsing it
        print(csv_line("bench_exchange_megastep", k, n,
                       f"{fused_cmp['stepwise_steps_per_s']:.3f}",
                       f"{fused_cmp['fused_steps_per_s']:.3f}",
                       f"{fused_cmp['fused_over_stepwise']:.3f}"))

    if args.json_out:
        base = results[0]
        results_by_s = {str(r["exchange_every"]): r for r in results}
        comparison = {
            "bench": "bench_exchange",
            "mesh": list(mesh_shape),
            "per_device_size": [args.x, args.y, args.z],
            "radius": [args.fr, args.er, args.cr],
            "fields": args.fields,
            "configs": results,
            # headline ratios vs the smallest swept depth (pass 1 in
            # --exchange-every for a true per-step-exchange baseline):
            # exchange rounds per step drop exactly s-fold; amortized
            # bytes stay ~flat (the deep slabs repay the skipped
            # rounds); steps/s includes the redundant ring compute
            "baseline_exchange_every": base["exchange_every"],
            "rounds_per_step_ratio": {
                k: r["exchange_rounds_per_step"]
                / base["exchange_rounds_per_step"]
                for k, r in results_by_s.items()},
            "steps_per_s_ratio": {
                k: r["steps_per_s"] / base["steps_per_s"]
                for k, r in results_by_s.items()},
            # the halo message geometry the whole sweep rode — the
            # ledger stamps this into config (post-fingerprint) so
            # observatory queries can split slab vs irredundant runs
            "wire_layout": primary_layout,
        }
        if layout_cmp is not None:
            comparison["wire_layout_race"] = layout_cmp
        if autotune_cmp is not None:
            comparison["autotune"] = autotune_cmp
        if fused_cmp is not None:
            comparison["fused"] = fused_cmp
        if link_classes is not None:
            # per-(axis, link_class) byte shares + utilization — the
            # SAME derived block lands in this JSON, the metrics
            # snapshot below, and (as config.link_classes provenance)
            # the ledger record
            comparison["link_classes"] = link_classes
        # one payload, two artifacts: the legacy JSON plus the
        # observatory ledger records derived from it (same converter
        # the backfill CLI runs on the committed BENCH_*.json history)
        emit_bench_artifacts(args, comparison, "bench_exchange")
        print(f"bench_exchange: wrote {args.json_out}", file=sys.stderr)

    if args.metrics_json:
        # one number, two artifacts: the SAME steps/s measured above
        # lands in a telemetry metrics snapshot, so dashboards scraped
        # from the metrics surface and the committed BENCH_*.json can
        # never disagree
        from stencil_tpu.telemetry import MetricsRegistry

        reg = MetricsRegistry()
        g_sps = reg.gauge("stencil_bench_steps_per_s",
                          "measured steps/s of the blocked Jacobi "
                          "loop, by temporal depth")
        g_bps = reg.gauge("stencil_bench_bytes_per_step_model",
                          "amortized exchange B/step (analytic model, "
                          "HLO-cross-checked)")
        for r in results:
            s_label = str(r["exchange_every"])
            g_sps.set(r["steps_per_s"], exchange_every=s_label)
            g_bps.set(r["amortized_bytes_per_step_model"],
                      exchange_every=s_label)
        if autotune_cmp is not None:
            g_tuned = reg.gauge("stencil_bench_tuned_steps_per_s",
                                "steps/s of the measured tuned plan "
                                "vs Method.Default")
            g_tuned.set(autotune_cmp["tuned_steps_per_s"],
                        config="tuned")
            g_tuned.set(autotune_cmp["default_steps_per_s"],
                        config="default")
        if fused_cmp is not None:
            g_fused = reg.gauge(
                "stencil_bench_fused_steps_per_s",
                "megastep race: steps/s by dispatch mode (fused = "
                "one program per check_every steps incl. the "
                "in-graph probe trace; stepwise = one step + one "
                "probe dispatch per step)")
            ck = str(fused_cmp["check_every"])
            g_fused.set(fused_cmp["fused_steps_per_s"],
                        mode="fused", check_every=ck)
            g_fused.set(fused_cmp["stepwise_steps_per_s"],
                        mode="stepwise", check_every=ck)
        if link_classes is not None:
            # the link observatory's two gauges, set from the SAME
            # derived block the JSON pins (CI asserts exact equality
            # between the two surfaces)
            from stencil_tpu.observatory.linkmap import (
                METRIC_LINK_BYTES_PER_STEP, METRIC_LINK_UTILIZATION)
            g_lb = reg.gauge(METRIC_LINK_BYTES_PER_STEP,
                             "modeled wire B/step per mesh axis and "
                             "link class (observatory/linkmap.py)")
            g_lu = reg.gauge(METRIC_LINK_UTILIZATION,
                             "achieved/fitted-peak utilization per "
                             "mesh axis and link class")
            for key, row in link_classes.items():
                axis, klass = key.split("/")
                g_lb.set(row["bytes_per_step"], axis=axis,
                         link_class=klass)
                g_lu.set(row["utilization"], axis=axis,
                         link_class=klass)
        reg.write_snapshot(args.metrics_json)
        print(f"bench_exchange: metrics snapshot -> "
              f"{args.metrics_json}", file=sys.stderr)


if __name__ == "__main__":
    main()
