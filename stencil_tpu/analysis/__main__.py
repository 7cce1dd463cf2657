"""CLI entry: ``python -m stencil_tpu.analysis``.

Exit status: 0 when every checked invariant holds, 1 when any
error-severity finding exists, 2 on usage errors. ``--json PATH``
writes the machine-readable report (schema in ``report.py``) for CI
artifacts. ``--only NAME`` (or the legacy spelling ``--checker``)
restricts the run to one checker when NAME is a checker name, or to
the registry targets matching NAME as a glob pattern otherwise
(``--only 'telemetry.*'``); repeatable, and the two forms compose
(checker filter AND target filter). ``--list`` enumerates the
checkers plus the registry target counts per group and exits.
Positional arguments are fixture module paths (files defining
``TARGETS``) checked INSTEAD of the shipped registry — the
negative-control hook: the CLI must exit nonzero on every fixture
under ``tests/fixtures/lint/``.
"""

from __future__ import annotations

import argparse
import sys
from typing import List, Optional

from ..utils.naming import glob_match as _match


def _setup_backend() -> None:
    """Analysis is pure tracing/lowering: force a small virtual-CPU
    mesh so the shard_map targets resolve their axes without touching
    accelerators (mirrors tests/conftest.py)."""
    try:
        from stencil_tpu.utils.config import apply_fake_cpu

        apply_fake_cpu(8)
    except RuntimeError:
        pass  # backend already initialized; use whatever exists


def main(argv: Optional[List[str]] = None) -> int:
    from . import CHECKER_DOC, CHECKERS

    parser = argparse.ArgumentParser(
        prog="python -m stencil_tpu.analysis",
        description="stencil-lint: static halo-radius / DMA-discipline "
                    "/ collective-permutation / HLO-lowering / "
                    "cost-model / VMEM / donation / host-transfer / "
                    "recompile / prescriptive-tiling / link-traffic / "
                    "RDMA-schedule-certification / "
                    "precision-certification checks (no execution)")
    parser.add_argument("fixtures", nargs="*",
                        help="fixture module paths (files defining "
                             "TARGETS) to check instead of the shipped "
                             "registry")
    parser.add_argument("--json", metavar="PATH",
                        help="write the JSON report here")
    parser.add_argument("--only", "--checker", action="append",
                        dest="only", metavar="CHECKER|GLOB",
                        help="run only this checker (exact checker "
                             "name) or only the targets matching this "
                             "glob pattern, e.g. 'telemetry.*' "
                             "(repeatable; forms compose)")
    parser.add_argument("--list", action="store_true", dest="list_",
                        help="list the available checkers and the "
                             "registry target counts per group, then "
                             "exit")
    parser.add_argument("--plan-tiling", metavar="GLOB",
                        dest="plan_tiling",
                        help="print the ranked VMEM block-shape plan "
                             "(shape, footprint bytes, amplification, "
                             "legality) for the analysis.tiling.* "
                             "targets matching GLOB; --json writes the "
                             "machine-readable plan report instead of "
                             "the findings artifact")
    parser.add_argument("-q", "--quiet", action="store_true",
                        help="suppress the per-target OK lines")
    args = parser.parse_args(argv)

    if args.list_:
        for name in CHECKERS:
            print(f"  {name:<12} {CHECKER_DOC[name]}")
        from .registry import default_targets

        targets = default_targets()
        groups: dict = {}
        for t in targets:
            g = t.name.split(".", 1)[0]
            groups.setdefault(g, {})
            groups[g][t.checker] = groups[g].get(t.checker, 0) + 1
        print(f"\n  {len(targets)} registry targets by group:")
        for g in sorted(groups):
            per = " ".join(f"{c}={n}"
                           for c, n in sorted(groups[g].items()))
            print(f"    {g:<12} {sum(groups[g].values()):>3}  ({per})")
        return 0

    checkers = [v for v in (args.only or []) if v in CHECKERS]
    patterns = [v for v in (args.only or []) if v not in CHECKERS]

    _setup_backend()

    if args.plan_tiling:
        import json as _json

        from .registry import default_targets
        from .tiling import plan_tiling_report, render_plan_table

        tiling = [t for t in default_targets() if t.checker == "tiling"]
        chosen = [t for t in tiling
                  if _match(t.name, args.plan_tiling)
                  or _match(t.name.replace("analysis.tiling.", "", 1),
                            args.plan_tiling)]
        if not chosen:
            print(f"stencil-lint: no tiling targets match "
                  f"{args.plan_tiling!r} ({len(tiling)} registered "
                  f"under analysis.tiling.*)", file=sys.stderr)
            return 2
        report = plan_tiling_report(chosen)
        print(render_plan_table(report))
        if args.json:
            with open(args.json, "w", encoding="utf-8") as fh:
                _json.dump({"tool": "stencil-lint", "mode": "plan-tiling",
                            "plans": report}, fh, indent=2)
            print(f"stencil-lint: tiling plan report written to "
                  f"{args.json}")
        return 0

    from . import run_targets
    from .registry import default_targets, load_targets

    try:
        if args.fixtures:
            targets = []
            for path in args.fixtures:
                targets.extend(load_targets(path))
        else:
            targets = default_targets()
    except (ImportError, ValueError, OSError) as e:
        print(f"stencil-lint: cannot load targets: {e}", file=sys.stderr)
        return 2

    if patterns:
        # EVERY pattern must match something: a typo'd glob among
        # several must fail the run, not silently drop its coverage
        unmatched = [p for p in patterns
                     if not any(_match(t.name, p) for t in targets)]
        if unmatched:
            print(f"stencil-lint: no targets match {unmatched} "
                  f"(values that are not checker names filter target "
                  f"names by glob)", file=sys.stderr)
            return 2
        targets = [t for t in targets
                   if any(_match(t.name, p) for p in patterns)]
    if checkers and not any(t.checker in checkers for t in targets):
        # a checker filter + glob that intersect to nothing would be a
        # vacuously green run — the same silent coverage drop the
        # unmatched-glob guard above refuses
        print(f"stencil-lint: the --only filters select no targets "
              f"(checkers {checkers} x {len(targets)} matched "
              f"target(s))", file=sys.stderr)
        return 2

    report = run_targets(targets, checkers=checkers or None)

    if not args.quiet:
        flagged = {f.target.split(":", 1)[0] for f in report.findings}
        for name in report.targets_checked:
            if name not in flagged:
                print(f"  OK   {name}")
    for f in report.findings:
        tag = "ERROR" if f.severity == "error" else "warn "
        print(f"  {tag} {f}")
    n_err, n_warn = len(report.errors), len(report.warnings)
    timing = " ".join(f"{k}={v:.2f}s"
                      for k, v in sorted(report.checker_seconds.items()))
    print(f"stencil-lint: {len(report.targets_checked)} targets, "
          f"{n_err} error(s), {n_warn} warning(s)"
          + (f" [{timing}]" if timing else ""))

    if args.json:
        with open(args.json, "w", encoding="utf-8") as fh:
            fh.write(report.to_json())
        print(f"stencil-lint: JSON report written to {args.json}")

    return 0 if report.ok else 1


if __name__ == "__main__":
    sys.exit(main())
