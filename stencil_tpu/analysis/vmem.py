"""Checker 6: static Pallas VMEM / tiling audit.

A Pallas TPU kernel fails (or silently crawls) for memory reasons a
jaxpr-level checker never sees: its working set — the VMEM-resident
blocks plus scratch, doubled by the pipeline's double buffering — must
fit the ~16 MiB per-core VMEM, and its blocks should respect the
(8, 128) f32 register tiling (sublane x lane; 16/32 sublanes for 2/1
byte dtypes) or Mosaic pads every block on every grid step. This
checker reads those properties straight off every ``pallas_call``'s
``GridMapping`` at trace time — no TPU, no Mosaic, no execution:

* **VMEM footprint** — sum of VMEM-space block bytes (ANY/HBM and
  SMEM operands excluded) x2 when the grid pipelines (>1 step), plus
  VMEM scratch from the kernel jaxpr; ERROR over the budget
  (default 16 MiB, or the kernel's own ``vmem_limit_bytes`` when its
  compiler params raise it);
* **tile alignment** — for rank>=2 VMEM blocks, the lane (last) dim
  must be a multiple of 128 OR span the whole array dim (un-tiled is
  the only choice then); the sublane dim likewise against the dtype's
  sublane tile (8 f32 / 16 bf16 / 32 int8);
* **grid divisibility** — every VMEM block dim must divide the array
  dim it tiles: a ragged last tile means masked partial blocks on the
  hot path.

Semaphores are bytes-free here; SMEM has its own (unchecked, ~1 MiB)
budget and scalar-prefetch operands are tiny — excluded by design.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Sequence, Tuple

from .jaxprs import iter_eqns, trace
from .report import Finding, WARNING

VMEM_BUDGET_BYTES = 16 * 1024 * 1024  # per-core VMEM, v4/v5 ballpark

LANE = 128


def sublane_tile(itemsize: int) -> int:
    """Sublane tile rows for an element size: (8,128) holds 32-bit
    lanes; narrower dtypes pack 2/4 rows per register row."""
    return max(8, 8 * (4 // max(1, itemsize)))


@dataclasses.dataclass
class VmemSpec:
    """A traceable entry point containing >= 1 ``pallas_call``.

    Reuses the dma targets' builder convention (``fn(*args)`` traced
    abstractly); ``budget_bytes`` overrides the default VMEM budget
    (kernels that raise ``vmem_limit_bytes`` via compiler params get
    that limit automatically). ``expect_pallas`` guards against the
    audit passing vacuously after a refactor.
    """

    fn: Callable
    args: Sequence[Any]
    budget_bytes: int = VMEM_BUDGET_BYTES
    expect_pallas: bool = True


@dataclasses.dataclass
class VmemTarget:
    name: str
    build: Callable[[], VmemSpec]

    checker = "vmem"


def _space_name(aval: Any) -> str:
    """Memory space of a MemRef aval: 'vmem' (None/default), 'smem',
    'any' (HBM), 'semaphore', ..."""
    s = str(getattr(aval, "memory_space", None) or "")
    if "sem" in str(aval) and ("semaphore" in str(aval)
                               or "barrier" in str(aval)):
        return "semaphore"
    if not s or s == "None":
        return "vmem"
    return s.lower()


def _aval_bytes(shape: Sequence[int], dtype: Any) -> int:
    import numpy as np

    n = 1
    for d in shape:
        n *= int(d)
    try:
        return n * np.dtype(dtype).itemsize
    except TypeError:
        return 0  # semaphore or other unsized element types


def _grid_steps(grid: Sequence[Any]) -> int:
    steps = 1
    for g in grid:
        try:
            steps *= int(g)
        except (TypeError, ValueError):
            return 2  # traced grid dim: assume pipelined
    return steps


def _kernel_limit(params: dict, default: int) -> int:
    """The kernel's own vmem_limit_bytes (compiler params), else the
    default budget — a kernel that *declares* a raised limit is audited
    against what it asked for."""
    cp = params.get("compiler_params") or {}
    values = list(cp.values()) if isinstance(cp, dict) else [cp]
    for v in values:
        limit = getattr(v, "vmem_limit_bytes", None)
        if limit is None and isinstance(v, dict):
            limit = v.get("vmem_limit_bytes")
        if limit:
            return int(limit)
    return default


def _block_dim(b) -> int:
    """Concrete extent of one block dim (``Blocked``/``Element`` carry
    it as ``block_size``): squeezed dims (``None`` in the BlockSpec,
    ``Squeezed`` in the GridMapping) occupy one array slice per grid
    step."""
    try:
        return int(getattr(b, "block_size", b))
    except (TypeError, ValueError):
        return 1


def audit_pallas_call(eqn, budget: int, kname: str, target_name: str,
                      honor_kernel_limit: bool = True
                      ) -> Tuple[List[Finding], Dict]:
    """Audit one pallas_call eqn: footprint, alignment, divisibility.

    ``honor_kernel_limit=False`` audits against ``budget`` verbatim —
    the tiling checker's physical-VMEM mode, where a kernel's own
    raised ``vmem_limit_bytes`` is exactly the thing being distrusted
    (a raise defers the overflow from the Mosaic check to the
    allocator; see analysis/tiling.py)."""
    import numpy as np

    findings: List[Finding] = []
    gm = eqn.params.get("grid_mapping")
    if gm is None:
        return [Finding("vmem", target_name,
                        f"kernel '{kname}': pallas_call carries no "
                        f"grid_mapping on this JAX; VMEM audit "
                        f"unavailable", WARNING)], {}
    if honor_kernel_limit:
        budget = _kernel_limit(eqn.params, budget)
    steps = _grid_steps(tuple(gm.grid))
    block_bytes = 0
    n_vmem_blocks = 0

    def err(msg: str) -> None:
        findings.append(Finding("vmem", f"{target_name}:{kname}", msg))

    for bm in gm.block_mappings:
        aval = bm.block_aval
        space = _space_name(aval)
        if space in ("semaphore", "smem", "any"):
            continue
        arr = bm.array_aval
        block = tuple(_block_dim(b) for b in bm.block_shape)
        dtype = np.dtype(arr.dtype)
        block_bytes += _aval_bytes(block, dtype)
        n_vmem_blocks += 1
        label = (f"block {block} of {arr.dtype.name}"
                 f"[{','.join(str(d) for d in arr.shape)}]")
        if len(block) >= 1:
            lane_b, lane_a = block[-1], int(arr.shape[-1])
            if len(block) >= 2 and lane_b % LANE and lane_b != lane_a:
                err(f"{label}: lane (last) dim {lane_b} is neither a "
                    f"multiple of {LANE} nor the full array extent "
                    f"{lane_a} — every grid step pays a partial-lane "
                    f"tile")
            if len(block) >= 2:
                sub = sublane_tile(dtype.itemsize)
                sub_b, sub_a = block[-2], int(arr.shape[-2])
                if sub_b % sub and sub_b != sub_a:
                    err(f"{label}: sublane dim {sub_b} is neither a "
                        f"multiple of the ({sub}, {LANE}) "
                        f"{arr.dtype.name} tile nor the full array "
                        f"extent {sub_a}")
        for ax, (b, a) in enumerate(zip(block, arr.shape)):
            if b and int(a) % int(b):
                err(f"{label}: dim {ax} block {b} does not divide the "
                    f"array extent {a} — ragged last tile (masked "
                    f"partial blocks on the hot path)")

    # VMEM scratch: kernel-jaxpr invars past the block operands
    scratch_bytes = 0
    kj = eqn.params.get("jaxpr")
    kj = kj.jaxpr if hasattr(kj, "jaxpr") else kj
    n_lead = gm.num_index_operands + len(gm.block_mappings)
    for v in list(getattr(kj, "invars", []))[n_lead:]:
        aval = v.aval
        if _space_name(aval) != "vmem":
            continue
        shape = getattr(aval, "shape", None)
        dtype = getattr(aval, "dtype", None)
        if shape is not None and dtype is not None:
            scratch_bytes += _aval_bytes(shape, dtype)

    double = 2 if steps > 1 else 1
    total = block_bytes * double + scratch_bytes
    metrics = {
        "grid": [int(g) if not hasattr(g, "aval") else "?"
                 for g in gm.grid],
        "vmem_block_bytes": block_bytes,
        "vmem_scratch_bytes": scratch_bytes,
        "pipeline_buffers": double,
        "vmem_estimate_bytes": total,
        "budget_bytes": budget,
        "vmem_blocks": n_vmem_blocks,
    }
    if total > budget:
        err(f"estimated VMEM footprint {total} B ({n_vmem_blocks} "
            f"blocks x{double} pipeline buffers + {scratch_bytes} B "
            f"scratch) exceeds the {budget} B budget — the kernel "
            f"cannot stage its working set")
    return findings, metrics


def check_vmem(target: VmemTarget) -> Tuple[List[Finding], Dict]:
    try:
        spec = target.build()
    except Exception as e:  # noqa: BLE001
        return [Finding("vmem", target.name,
                        f"target build failed: {type(e).__name__}: {e}")], {}
    try:
        closed = trace(spec.fn, *spec.args)
    except Exception as e:  # noqa: BLE001
        return [Finding("vmem", target.name,
                        f"trace failed: {type(e).__name__}: {e}")], {}

    findings: List[Finding] = []
    metrics: Dict[str, Dict] = {"kernels": {}}
    n_seen: Dict[str, int] = {}
    for eqn in iter_eqns(closed.jaxpr):
        if eqn.primitive.name != "pallas_call":
            continue
        info = eqn.params.get("name_and_src_info")
        kname = getattr(info, "name", None) or str(info) or "<kernel>"
        n_seen[kname] = n_seen.get(kname, 0) + 1
        if n_seen[kname] > 1:
            kname = f"{kname}#{n_seen[kname]}"
        try:
            f, m = audit_pallas_call(eqn, spec.budget_bytes, kname,
                                     target.name)
        except Exception as e:  # noqa: BLE001 - unknown GridMapping
            # shapes must degrade to a finding, never kill the run
            f, m = [Finding(
                "vmem", f"{target.name}:{kname}",
                f"VMEM audit failed on this kernel's grid mapping: "
                f"{type(e).__name__}: {e}", WARNING)], {}
        if f and any(x.severity != WARNING for x in f):
            # prescriptive mode: every real finding carries the block-
            # shape planner's concrete fix (analysis/tiling.py),
            # planned against whatever budget THIS audit used (never
            # looser — a suggestion must satisfy the budget it was
            # flagged against)
            from .tiling import TILE_SELECT_BUDGET_BYTES, suggest_for_eqn

            audited = m.get("budget_bytes", spec.budget_bytes)
            sug = suggest_for_eqn(eqn, min(TILE_SELECT_BUDGET_BYTES,
                                           audited), kernel=kname)
            f = [dataclasses.replace(x, message=f"{x.message}; {sug}")
                 if x.severity != WARNING else x for x in f]
            m = dict(m)
            m["suggestion"] = sug
        findings.extend(f)
        metrics["kernels"][kname] = m
    if spec.expect_pallas and not metrics["kernels"]:
        findings.append(Finding(
            "vmem", target.name,
            "expected pallas_call kernels but none traced — the VMEM "
            "audit would be vacuous here", WARNING))
    return findings, metrics
