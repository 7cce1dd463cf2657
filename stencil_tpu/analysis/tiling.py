"""Checker 10: prescriptive VMEM tiling — the block-shape planner.

The VMEM audit (:mod:`.vmem`, checker 6) *flags* a Pallas kernel whose
blocks overflow VMEM or break the (sublane, 128) tile rules; this
module makes that model *prescriptive*: given a kernel's per-block-shape
byte model (either an analytic one the kernel module declares, or one
derived positionally from a traced ``pallas_call``'s ``GridMapping``),
it enumerates every legal candidate block shape —

* (sublane, 128)-tile-aligned: ``block_y`` a multiple of the dtype's
  sublane tile (``ops.pallas_stencil.sublane_tile_bytes``); the lane
  dim stays the full array extent in every shipped kernel, so lane
  alignment is the array's own;
* grid-divisible: ``block_z | Z`` and ``block_y | Y`` (no ragged tail
  tiles on the hot path);
* double-buffer footprint under budget: streamed blocks x2 pipeline
  buffers (+ held in-kernel windows where the kernel's model declares
  them) within the PHYSICAL per-core VMEM (a raised
  ``vmem_limit_bytes`` postpones the failure from the Mosaic check to
  the allocator — exactly the SNIPPETS.md 512^3 failure mode — so the
  planner never trusts it)

— prices each by modeled HBM traffic (read amplification: streamed
input bytes per main-stream output element, the ``1 + 2/block_z +
2/block_y`` family documented on ``ops/pallas_stencil.py``), and
returns a ranked :class:`TilingPlan`. The Pallas kernel modules route
their default block selection through :func:`plan_blocks` /
:func:`snap_blocks` (no more silent power-of-two halving), the VMEM
checker attaches each finding's concrete ``suggestion`` from
:func:`suggest_for_eqn`, and the registry's ``analysis.tiling.*``
targets audit every shipped kernel at 256^3- and 512^3-per-device
shapes — trace-only, so tier-1 on CPU proves the production-size story
the 8^3 bench trajectory never could (ROADMAP item 6).

Budget convention: SELECTION uses :data:`TILE_SELECT_BUDGET_BYTES`
(14 MiB — physical VMEM minus slack for semaphores/compiler
temporaries, the ``ops/pallas_halo.py`` precedent), AUDIT uses the full
physical :data:`vmem.VMEM_BUDGET_BYTES` (16 MiB). Selection being the
stricter of the two is what makes the plan -> audit round trip sound:
every planner-emitted shape passes ``check_vmem`` by construction.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from .jaxprs import iter_eqns, trace
from .report import ERROR, WARNING, Finding
from .vmem import VMEM_BUDGET_BYTES, audit_pallas_call, sublane_tile

#: kernel-side block-selection budget: physical VMEM minus slack for
#: semaphores / compute temporaries the byte models do not count (the
#: ops/pallas_halo precedent, now the one shared constant)
TILE_SELECT_BUDGET_BYTES = 14 * 2**20

LANE = 128


class TilingInfeasibleError(ValueError):
    """No legal block shape exists for this kernel at this budget.

    ``reason`` names the binding constraint (alignment, divisibility,
    or the VMEM footprint of the minimal aligned block)."""

    def __init__(self, kernel: str, reason: str):
        super().__init__(f"{kernel}: no legal block shape — {reason}")
        self.kernel = kernel
        self.reason = reason


@dataclasses.dataclass(frozen=True)
class ShapeOption:
    """One legal candidate block shape, priced."""

    block_z: int
    block_y: int
    footprint_bytes: int
    #: modeled HBM read amplification: streamed input bytes per
    #: main-stream output element (1.0 = every input byte read once)
    amplification: float

    def to_dict(self) -> Dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class TilingPlan:
    """The planner's output for one kernel at one array shape: every
    legal candidate, ranked cheapest-traffic first (ties prefer the
    fatter ``block_y``, then the fatter ``block_z`` — fatter lanes mean
    fewer, fatter edge DMAs; the judge-measured 512^3 fast point
    (8, 128) falls out of exactly this rule)."""

    kernel: str
    array_zyx: Tuple[int, int, int]
    itemsize: int
    budget_bytes: int
    options: List[ShapeOption]
    #: aligned+divisible candidates rejected by the budget alone
    over_budget: int = 0
    #: binding constraint when ``options`` is empty
    infeasible: Optional[str] = None

    @property
    def best(self) -> Optional[ShapeOption]:
        return self.options[0] if self.options else None

    def blocks(self) -> Tuple[int, int]:
        """The prescribed (block_z, block_y); raises
        :class:`TilingInfeasibleError` when nothing is legal."""
        if not self.options:
            raise TilingInfeasibleError(
                self.kernel, self.infeasible or "empty candidate space")
        return self.options[0].block_z, self.options[0].block_y

    def to_dict(self) -> Dict:
        return {
            "kernel": self.kernel,
            "array_zyx": list(self.array_zyx),
            "itemsize": self.itemsize,
            "budget_bytes": self.budget_bytes,
            "options": [o.to_dict() for o in self.options],
            "over_budget": self.over_budget,
            "infeasible": self.infeasible,
        }


def _divisors(n: int) -> List[int]:
    out = [d for d in range(1, n + 1) if n % d == 0]
    return out


def plan_blocks(kernel: str, Z: int, Y: int, X: int, itemsize: int,
                elems: Callable[[int, int], Tuple[int, int, int]], *,
                n_streams: int = 1,
                sublane_z: int = 1, sublane_y: Optional[int] = None,
                min_z: int = 1,
                cap_z: Optional[int] = None, cap_y: Optional[int] = None,
                budget: int = TILE_SELECT_BUDGET_BYTES,
                scratch_bytes: int = 0,
                max_options: int = 8) -> TilingPlan:
    """Synthesize the ranked legal block shapes for one kernel.

    ``elems(bz, by) -> (in_elems, out_elems, held_elems)`` is the
    kernel's byte model per lane column (x itemsize x X applied here):
    streamed input/output block elements (doubled for the pipeline's
    two buffers) and held in-kernel window elements (allocated once).
    It must count at least what the traced ``GridMapping`` will show,
    so legality here implies a clean ``check_vmem`` — the plan -> audit
    round-trip contract, property-tested in tests/test_tiling.py.

    ``n_streams`` is the number of main-block input streams (8 for the
    MHD kernels), normalizing ``amplification`` to 1.0 = perfect.
    ``cap_z``/``cap_y`` bound candidates above (the caller's requested
    ceiling); ``sublane_*``/``min_z`` bound them below. An empty legal
    set yields ``options=[]`` with the binding constraint named in
    ``infeasible`` (:meth:`TilingPlan.blocks` raises it).
    """
    esub = sublane_y if sublane_y is not None else sublane_tile(itemsize)
    # a ceiling below the alignment floor means "the smallest legal
    # shape" (bf16 doubles the sublane tile past the f32-sized caps)
    cz = min(max(cap_z, sublane_z, min_z), Z) if cap_z else Z
    cy = min(max(cap_y, esub), Y) if cap_y else Y
    bzs = [d for d in _divisors(Z)
           if d % max(sublane_z, 1) == 0 and min_z <= d <= cz]
    bys = [d for d in _divisors(Y) if d % max(esub, 1) == 0 and d <= cy]
    plan = TilingPlan(kernel=kernel, array_zyx=(Z, Y, X),
                      itemsize=itemsize, budget_bytes=int(budget),
                      options=[])
    if not bzs or not bys:
        which = []
        if not bzs:
            which.append(f"no block_z divides Z={Z} with "
                         f"{min_z} <= block_z <= {cz}"
                         + (f" as a multiple of {sublane_z}"
                            if sublane_z > 1 else ""))
        if not bys:
            which.append(f"no block_y divides Y={Y} as a multiple of "
                         f"the sublane tile {esub} with block_y <= {cy}")
        plan.infeasible = "; ".join(which) + " (alignment/divisibility)"
        return plan

    scored: List[ShapeOption] = []
    best_over = None  # (footprint, bz, by) of the cheapest illegal shape
    over = 0
    for bz in bzs:
        for by in bys:
            ein, eout, eheld = elems(bz, by)
            footprint = (itemsize * X * (2 * (int(ein) + int(eout))
                                         + int(eheld))
                         + int(scratch_bytes))
            if footprint > budget:
                over += 1
                if best_over is None or footprint < best_over[0]:
                    best_over = (footprint, bz, by)
                continue
            amp = float(ein) / float(max(n_streams, 1) * bz * by)
            scored.append(ShapeOption(bz, by, footprint, round(amp, 4)))
    plan.over_budget = over
    if not scored:
        fp, bz, by = best_over  # at least one aligned candidate existed
        plan.infeasible = (
            f"VMEM footprint is the binding constraint: even the "
            f"cheapest aligned block ({bz}, {by}) stages {fp} B against "
            f"the {budget} B budget at array ({Z}, {Y}, {X}) "
            f"x{itemsize} B")
        return plan
    scored.sort(key=lambda o: (o.amplification, -o.block_y, -o.block_z))
    plan.options = scored[:max(int(max_options), 1)]
    return plan


# ---------------------------------------------------------------------------
# explicit-request snapping + the once-per-fact replacement warning
# (the silent-degradation fix: a shrunk block shape now SAYS so)

_WARNED: set = set()


def _warn_once(key: Tuple, msg: str) -> None:
    if key in _WARNED:
        return
    _WARNED.add(key)
    from ..utils.logging import LOG_WARN

    LOG_WARN(msg)


def reset_warnings() -> None:
    """Test hook: forget which replacements were already warned."""
    _WARNED.clear()


def snap_blocks(kernel: str, Z: int, Y: int,
                requested_z: int, requested_y: int, *,
                sublane_z: int = 1, sublane_y: int = 1,
                min_z: int = 1) -> Tuple[int, int]:
    """Snap an EXPLICITLY requested (block_z, block_y) to the nearest
    legal-alignment shape at or below it (budget deliberately NOT
    applied: an operator sweeping block shapes asked to measure exactly
    that configuration, Mosaic errors included). When the request had
    to be replaced, ``LOG_WARN`` fires ONCE per (kernel, array, request)
    — the old halving loops shrank silently. Raises
    :class:`TilingInfeasibleError` when no aligned divisor exists."""
    bzs = [d for d in _divisors(Z)
           if d % max(sublane_z, 1) == 0
           and min_z <= d <= max(int(requested_z), min_z)]
    bys = [d for d in _divisors(Y)
           if d % max(sublane_y, 1) == 0 and d <= max(int(requested_y),
                                                      sublane_y)]
    if not bzs or not bys:
        raise TilingInfeasibleError(
            kernel, f"requested blocks ({requested_z}, {requested_y}) "
                    f"have no aligned divisor for array Z={Z}, Y={Y} "
                    f"(sublanes z%{sublane_z}, y%{sublane_y}, "
                    f"block_z >= {min_z})")
    bz, by = max(bzs), max(bys)
    if (bz, by) != (int(requested_z), int(requested_y)):
        _warn_once(
            (kernel, Z, Y, int(requested_z), int(requested_y)),
            f"{kernel}: requested block shape ({requested_z}, "
            f"{requested_y}) replaced by ({bz}, {by}) — the request "
            f"does not divide/align array (Z={Z}, Y={Y}); pass a "
            f"legal shape (python -m stencil_tpu.analysis "
            f"--plan-tiling) to silence")
    return bz, by


# ---------------------------------------------------------------------------
# the generic (trace-derived) model: a parametric footprint read
# straight off a pallas_call's GridMapping, for kernels the planner
# has no analytic model for — powers the VMEM checker's `suggestion`
# and the --plan-tiling report


def _block_dims(bm) -> Tuple[int, ...]:
    from .vmem import _block_dim

    return tuple(_block_dim(b) for b in bm.block_shape)


def plan_from_grid_mapping(eqn, budget: int = TILE_SELECT_BUDGET_BYTES,
                           kernel: str = "<kernel>"
                           ) -> Optional[TilingPlan]:
    """Derive a parametric block-shape model positionally from a traced
    ``pallas_call``: the first rank-3 VMEM *output* block's leading two
    dims are the (block_z, block_y) knobs; every other VMEM block's
    dims co-vary where they equal the reference's (dim 0 with block_z,
    dim 1 with block_y) and stay constant otherwise. Returns ``None``
    when no unambiguous parameterization exists (a squeezed/plane
    kernel whose reference dims are 1 — every single-row segment would
    alias the knob)."""
    import numpy as np

    gm = eqn.params.get("grid_mapping")
    if gm is None:
        return None
    try:
        n_out = int(gm.num_outputs)
    except (AttributeError, TypeError):
        n_out = 1
    from .vmem import _space_name

    blocks = []  # (dims, itemsize, is_output, array_shape)
    for i, bm in enumerate(gm.block_mappings):
        aval = bm.block_aval
        if _space_name(aval) in ("semaphore", "smem", "any"):
            continue
        arr = bm.array_aval
        try:
            isz = np.dtype(arr.dtype).itemsize
        except TypeError:
            continue
        is_out = i >= len(gm.block_mappings) - n_out
        blocks.append((_block_dims(bm), isz, is_out,
                       tuple(int(d) for d in arr.shape)))
    ref = next(((d, a) for d, _isz, is_out, a in blocks
                if is_out and len(d) == 3), None)
    if ref is None:
        return None
    (bz0, by0, _lx0), (Z, Y, X) = ref
    if bz0 <= 1 or by0 <= 1:
        return None  # ambiguous: constant-1 segments would alias the knob

    # VMEM scratch (constant in the block shape)
    kj = eqn.params.get("jaxpr")
    kj = kj.jaxpr if hasattr(kj, "jaxpr") else kj
    from .vmem import _aval_bytes

    scratch = 0
    n_lead = gm.num_index_operands + len(gm.block_mappings)
    for v in list(getattr(kj, "invars", []))[n_lead:]:
        aval = v.aval
        if _space_name(aval) != "vmem":
            continue
        shape = getattr(aval, "shape", None)
        dtype = getattr(aval, "dtype", None)
        if shape is not None and dtype is not None:
            scratch += _aval_bytes(shape, dtype)

    itemsize = max(isz for _d, isz, _o, _a in blocks)

    def scaled(dims, bz, by):
        n = 1
        for ax, d in enumerate(dims):
            if ax == 0 and d == bz0:
                d = bz
            elif ax == 1 and d == by0:
                d = by
            n *= d
        return n

    def elems(bz, by):
        ein = eout = 0
        for dims, isz, is_out, _a in blocks:
            # normalize foreign itemsizes into the plan's element unit
            n = scaled(dims, bz, by) * isz / itemsize / X
            if is_out:
                eout += n
            else:
                ein += n
        return ein, eout, 0

    n_streams = sum(1 for d, _isz, is_out, _a in blocks
                    if not is_out and len(d) == 3
                    and d[0] == bz0 and d[1] == by0)
    return plan_blocks(kernel, Z, Y, X, itemsize, elems,
                       n_streams=max(n_streams, 1),
                       sublane_y=sublane_tile(itemsize),
                       budget=budget)


def suggest_for_eqn(eqn, budget: int = TILE_SELECT_BUDGET_BYTES,
                    kernel: str = "<kernel>") -> str:
    """The concrete prescription attached to every VMEM finding: the
    best legal shape, or the named binding constraint, or the honest
    admission that no parametric model is derivable."""
    try:
        plan = plan_from_grid_mapping(eqn, budget, kernel)
    except Exception as e:  # noqa: BLE001 — suggestions never kill audits
        return (f"suggestion unavailable (planner failed: "
                f"{type(e).__name__}: {e})")
    if plan is None:
        return ("no parametric block-shape model derivable from this "
                "grid mapping (plane/squeezed kernel) — re-tile the "
                "kernel or shrink the per-device array")
    if plan.best is not None:
        o = plan.best
        return (f"suggestion: block shape ({o.block_z}, {o.block_y}) "
                f"fits {o.footprint_bytes} B <= {plan.budget_bytes} B "
                f"at amplification {o.amplification}")
    return f"infeasible at this budget — {plan.infeasible}"


# ---------------------------------------------------------------------------
# checker 10: the registry-facing tiling audit


@dataclasses.dataclass
class TilingSpec:
    """A traceable entry point audited at a production per-device
    shape against the PHYSICAL VMEM budget (declared
    ``vmem_limit_bytes`` raises are deliberately ignored — a raise
    defers the overflow from the Mosaic check to the allocator)."""

    fn: Callable
    args: Sequence[Any]
    budget_bytes: int = VMEM_BUDGET_BYTES
    expect_pallas: bool = True


@dataclasses.dataclass
class TilingTarget:
    """``expect`` is the registered verdict for this shape:

    * ``"legal"`` — the build must succeed and every contained
      ``pallas_call`` must pass the full audit (footprint, tile
      alignment, grid divisibility); any finding is an ERROR carrying
      the planner's concrete suggestion;
    * ``"infeasible"`` — the planner/kernel must REFUSE this size:
      either building/tracing raises :class:`TilingInfeasibleError`
      (the kernel-side planner declining — the silent-degradation fix
      proven at production size) or the audit flags the shape. A clean
      pass means the pinned expectation went stale and must be
      promoted to "legal" in review.
    """

    name: str
    build: Callable[[], TilingSpec]
    expect: str = "legal"

    checker = "tiling"


def _audit_shapes(spec: TilingSpec, target_name: str
                  ) -> Tuple[List[Finding], Dict]:
    """Trace and audit every pallas_call at the physical budget,
    suggestions attached; mirrors check_vmem's walk but never honors
    declared vmem_limit raises."""
    findings: List[Finding] = []
    metrics: Dict[str, Dict] = {"kernels": {}}
    closed = trace(spec.fn, *spec.args)
    n_seen: Dict[str, int] = {}
    for eqn in iter_eqns(closed.jaxpr):
        if eqn.primitive.name != "pallas_call":
            continue
        info = eqn.params.get("name_and_src_info")
        kname = getattr(info, "name", None) or str(info) or "<kernel>"
        n_seen[kname] = n_seen.get(kname, 0) + 1
        if n_seen[kname] > 1:
            kname = f"{kname}#{n_seen[kname]}"
        f, m = audit_pallas_call(eqn, spec.budget_bytes, kname,
                                 target_name, honor_kernel_limit=False)
        f = [dataclasses.replace(x, checker="tiling") for x in f]
        if f:
            sug = suggest_for_eqn(eqn, min(TILE_SELECT_BUDGET_BYTES,
                                           spec.budget_bytes), kname)
            f = [dataclasses.replace(x, message=f"{x.message}; {sug}")
                 for x in f]
            m["suggestion"] = sug
        plan = plan_from_grid_mapping(eqn, min(TILE_SELECT_BUDGET_BYTES,
                                               spec.budget_bytes), kname)
        if plan is not None:
            m["plan"] = plan.to_dict()
        findings.extend(f)
        metrics["kernels"][kname] = m
    if spec.expect_pallas and not metrics["kernels"]:
        findings.append(Finding(
            "tiling", target_name,
            "expected pallas_call kernels but none traced — the tiling "
            "audit would be vacuous here", WARNING))
    return findings, metrics


def check_tiling(target: TilingTarget) -> Tuple[List[Finding], Dict]:
    try:
        spec = target.build()
    except TilingInfeasibleError as e:
        if target.expect == "infeasible":
            # the kernel-side planner refused this size at build time
            return [], {"infeasible": str(e),
                        "verdict": "refused-at-build"}
        return [Finding(
            "tiling", target.name,
            f"planner refused a shape registered as legal: {e}")], {}
    except Exception as e:  # noqa: BLE001
        return [Finding("tiling", target.name,
                        f"target build failed: {type(e).__name__}: {e}")], {}

    if target.expect == "infeasible":
        # the build ran, so the planner did NOT refuse: the audit must
        # flag the shape, else the pinned expectation is stale
        try:
            findings, metrics = _audit_shapes(spec, target.name)
        except TilingInfeasibleError as e:
            return [], {"infeasible": str(e), "verdict": "refused-at-trace"}
        except Exception as e:  # noqa: BLE001
            return [Finding("tiling", target.name,
                            f"trace failed: {type(e).__name__}: {e}")], {}
        real = [f for f in findings if f.severity == ERROR]
        if not real:
            return [Finding(
                "tiling", target.name,
                "registered as infeasible at this per-device shape but "
                "the kernel now tiles legally — promote the registry "
                "expectation to \"legal\"")], metrics
        metrics["expected_findings"] = [str(f) for f in real]
        metrics["verdict"] = "flagged-as-expected"
        return [], metrics

    try:
        findings, metrics = _audit_shapes(spec, target.name)
    except TilingInfeasibleError as e:
        return [Finding(
            "tiling", target.name,
            f"planner refused a shape registered as legal: {e}")], {}
    except Exception as e:  # noqa: BLE001
        return [Finding("tiling", target.name,
                        f"trace failed: {type(e).__name__}: {e}")], {}
    metrics["verdict"] = "legal" if not findings else "flagged"
    return findings, metrics


# ---------------------------------------------------------------------------
# the --plan-tiling report (CLI): ranked plan tables per target


def plan_tiling_report(targets: Sequence[TilingTarget]) -> Dict[str, Dict]:
    """Per-target planner report for ``--plan-tiling``: each contained
    kernel's actual blocks, audit verdict at the physical budget, and
    the ranked legal candidates (or the named binding constraint)."""
    out: Dict[str, Dict] = {}
    for t in targets:
        entry: Dict[str, Any] = {}
        try:
            spec = t.build()
        except TilingInfeasibleError as e:
            out[t.name] = {"infeasible": str(e)}
            continue
        except Exception as e:  # noqa: BLE001
            out[t.name] = {"error": f"{type(e).__name__}: {e}"}
            continue
        try:
            findings, metrics = _audit_shapes(spec, t.name)
        except Exception as e:  # noqa: BLE001
            out[t.name] = {"error": f"{type(e).__name__}: {e}"}
            continue
        entry["expect"] = t.expect
        entry["findings"] = [str(f) for f in findings]
        entry["kernels"] = metrics.get("kernels", {})
        out[t.name] = entry
    return out


def render_plan_table(report: Dict[str, Dict]) -> str:
    """Human table over :func:`plan_tiling_report`'s dict."""
    lines: List[str] = []
    hdr = (f"  {'target':<58} {'kernel':<24} {'footprint':>12} "
           f"{'amp':>6}  verdict / best shape")
    lines.append(hdr)
    for name, entry in sorted(report.items()):
        if "infeasible" in entry:
            lines.append(f"  {name:<58} {'-':<24} {'-':>12} {'-':>6}  "
                         f"INFEASIBLE (planner refused): "
                         f"{entry['infeasible']}")
            continue
        if "error" in entry:
            lines.append(f"  {name:<58} {'-':<24} {'-':>12} {'-':>6}  "
                         f"ERROR: {entry['error']}")
            continue
        flagged = bool(entry.get("findings"))
        for kname, m in entry.get("kernels", {}).items():
            plan = m.get("plan") or {}
            best = (plan.get("options") or [None])[0]
            verdict = "FLAGGED" if flagged else "ok"
            if plan.get("infeasible"):
                tail = f"infeasible: {plan['infeasible']}"
            elif best:
                tail = (f"best ({best['block_z']}, {best['block_y']}) "
                        f"@ {best['footprint_bytes']} B")
            else:
                tail = "no parametric model"
            amp = best["amplification"] if best else "-"
            lines.append(
                f"  {name:<58} {kname:<24} "
                f"{m.get('vmem_estimate_bytes', '-'):>12} {amp!s:>6}  "
                f"{verdict}  {tail}")
    return "\n".join(lines)
