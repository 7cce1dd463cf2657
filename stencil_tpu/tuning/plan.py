"""Plan data model: candidates, fingerprints, and the tuned Plan.

The reference library routes every halo message over the fastest
measured transport for its src/dst pair (reference:
src/stencil.cu:371-458) and records the decision in per-rank plan
files. The TPU analog is a whole-program choice: ONE exchange
configuration — (Method, overlap, exchange_every) — serves every pair
because XLA SPMD owns the wire. A :class:`Plan` is that choice plus
everything needed to trust and reuse it: the measured alpha-beta
coefficients, the per-candidate costs, a provenance tag
(tuned/cached/default), and a fingerprint of the machine+problem the
measurements are valid for.

Fingerprint semantics: two runs share a plan iff their fingerprint
inputs match — device platform and count, mesh shape, slice count,
global grid, full 26-direction radius, quantity names and dtypes,
boundary, and the library version (a new library may lower the same
exchange differently, so plans do not survive upgrades). Anything else
(iteration counts, output prefixes, CLI flags) is deliberately NOT
fingerprinted.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from ..geometry import Dim3, Radius, all_directions

SCHEMA_VERSION = 1

#: temporal-blocking depths the tuner sweeps by default
DEFAULT_DEPTHS: Tuple[int, ...] = (1, 2, 4, 8)

#: the strategies a plan may select, in generation (tie-break) order
PLAN_METHODS: Tuple[str, ...] = ("PpermuteSlab", "PpermutePacked",
                                 "PallasDMA", "AllGather")

#: strategies supporting deep-carry allocations / uneven shards /
#: the zero-Dirichlet exterior (mirrors DistributedDomain.realize)
_PPERMUTE = ("PpermuteSlab", "PpermutePacked")


@dataclasses.dataclass(frozen=True)
class Candidate:
    """One point of the configuration space the tuner sweeps.

    ``wire_format`` is the halo wire dtype choice
    (``parallel.exchange.WIRE_FORMATS``): "f32" is the identity wire;
    the narrowing formats ("bf16", "e4m3", "e5m2") shrink the wire
    bytes on the ppermute engines and only realize behind a safe
    :class:`~stencil_tpu.analysis.precision.PrecisionCertificate`
    (the ``make_exchange`` gate). ``wire_layout`` is the message
    layout ("slab" | "irredundant", ``parallel.packing.WIRE_LAYOUTS``):
    "irredundant" sends every halo cell exactly once on the ppermute
    engines (corner/edge cells stop transiting multiple sweeps)."""

    method: str
    exchange_every: int = 1
    overlap: bool = False
    wire_format: str = "f32"
    wire_layout: str = "slab"
    #: per-axis temporal depths (x, y, z) — None means the symmetric
    #: ``exchange_every`` on every axis. A non-uniform tuple (e.g.
    #: ``(1, 1, 4)``) deepens only the named axes (DCN-crossing faces
    #: amortize while ICI faces exchange every step); serialized in the
    #: key as a dot-separated depth ``s=1.1.4``
    depths: Optional[Tuple[int, int, int]] = None

    def depths_xyz(self) -> Tuple[int, int, int]:
        """The effective (x, y, z) depths — ``depths`` or the symmetric
        fill of ``exchange_every``."""
        return (self.depths if self.depths is not None
                else (self.exchange_every,) * 3)

    def key(self) -> str:
        d = self.depths
        if d is not None and len(set(d)) > 1:
            tag = f"{self.method}[s={d[0]}.{d[1]}.{d[2]}"
        else:
            tag = f"{self.method}[s={self.exchange_every}"
        if self.overlap:
            tag += ",overlap"
        if self.wire_format != "f32":
            tag += f",wire={self.wire_format}"
        if self.wire_layout != "slab":
            tag += f",layout={self.wire_layout}"
        return tag + "]"

    @staticmethod
    def from_key(key: str) -> "Candidate":
        method, _, rest = key.partition("[")
        rest = rest.rstrip("]")
        parts = rest.split(",")
        sval = parts[0].split("=")[1]
        depths: Optional[Tuple[int, int, int]] = None
        if "." in sval:
            dx, dy, dz = (int(v) for v in sval.split("."))
            depths = (dx, dy, dz)
            s = max(depths)
        else:
            s = int(sval)
        wire = "f32"
        layout = "slab"
        for p in parts[1:]:
            if p.startswith("wire="):
                wire = p.split("=", 1)[1]
            elif p.startswith("layout="):
                layout = p.split("=", 1)[1]
        return Candidate(method, s, "overlap" in parts[1:], wire, layout,
                         depths)


@dataclasses.dataclass(frozen=True)
class TuneGeometry:
    """The per-shard geometry every cost/feasibility rule consumes.

    ``shard_interior_zyx`` is the CAPACITY interior (ceil sizes — the
    slabs that actually ride the wire); ``min_interior_zyx`` is the
    smallest shard (one less along remainder axes), which bounds the
    feasible blocking depth exactly like realize()'s check.
    """

    shard_interior_zyx: Tuple[int, int, int]
    min_interior_zyx: Tuple[int, int, int]
    radius: Radius
    counts: Dim3
    elem_sizes: Tuple[int, ...]
    uneven: bool = False
    nonperiodic: bool = False
    #: per-quantity dtype names — the packed engine groups launches by
    #: DTYPE, not element size (f32 + i32 pack separately); empty means
    #: unknown and the cost model falls back to distinct element sizes
    dtype_strs: Tuple[str, ...] = ()

    @property
    def dtype_groups(self) -> "int | None":
        return len(set(self.dtype_strs)) if self.dtype_strs else None


def candidate_feasible(cand: Candidate, geom: TuneGeometry) -> bool:
    """The realize()-equivalent feasibility rules, applied up front so
    the tuner never measures a configuration the orchestrator would
    reject."""
    if cand.method not in PLAN_METHODS:
        return False
    if cand.method not in _PPERMUTE:
        if cand.exchange_every > 1 or geom.uneven or geom.nonperiodic:
            return False
        if cand.overlap:
            return False
        # narrow wire formats and the irredundant layout ride the
        # ppermute engines only (parallel.methods.WIRE_CAPABLE)
        if cand.wire_format != "f32":
            return False
        if cand.wire_layout != "slab":
            return False
    if cand.exchange_every < 1:
        return False
    depths = cand.depths_xyz()
    if any(d < 1 for d in depths) or max(depths) != cand.exchange_every:
        return False
    if len(set(depths)) > 1:
        # asymmetric depths ride the ppermute engines with the slab
        # layout and no overlap (temporal_shard_steps' declines)
        if cand.method not in _PPERMUTE or cand.overlap:
            return False
        if cand.wire_layout != "slab":
            return False
        # each axis depth must divide the group length (refresh cadence)
        if any(max(depths) % d for d in depths):
            return False
    # the (per-axis) deepened radius must fit the SMALLEST shard on
    # every face
    mz, my, mx = geom.min_interior_zyx
    min_xyz = (mx, my, mz)
    for a in range(3):
        need = depths[a] * max(geom.radius.face(a, -1),
                               geom.radius.face(a, 1))
        if need > min_xyz[a]:
            return False
    return True


def candidate_space(geom: TuneGeometry,
                    depths: Sequence[int] = DEFAULT_DEPTHS,
                    overlap_options: Sequence[bool] = (False,),
                    runnable: Optional[Callable] = None,
                    wire_formats: Sequence[str] = ("f32",),
                    wire_layouts: Sequence[str] = ("slab",)
                    ) -> List[Candidate]:
    """Every feasible, runnable configuration, in deterministic
    tie-break order (method priority x depth ascending x overlap off
    first x full-precision wire first). ``runnable`` narrows
    the strategies swept (default: all of them). ``wire_formats`` is opt-in:
    the default sweeps only the identity "f32" wire; pass
    ``("f32", "bf16")`` to also rank the certified half-width wire on
    the ppermute engines. ``wire_layouts`` is likewise opt-in: pass
    ``("slab", "irredundant")`` to also rank the each-cell-once
    message layout (``parallel.packing``).

    ``depths`` entries may be plain ints (symmetric blocking) or
    per-axis specs — a ``{"z": 4}``-style dict or an (x, y, z)
    tuple — which become asymmetric candidates (``Candidate.depths``,
    keys like ``PpermuteSlab[s=1.1.4]``)."""
    from ..geometry import normalize_depths
    from ..parallel.methods import Method

    uniform = set()
    asym = set()
    for d in depths:
        if isinstance(d, int):
            uniform.add(int(d))
        else:
            nd = normalize_depths(d)
            if nd.x == nd.y == nd.z:
                uniform.add(nd.x)
            else:
                asym.add((nd.x, nd.y, nd.z))
    out: List[Candidate] = []
    for name in PLAN_METHODS:
        if runnable is not None and not runnable(Method[name]):
            continue
        specs = ([(s, None) for s in sorted(uniform)]
                 + [(max(d), d) for d in sorted(asym)])
        for s, dxyz in specs:
            for ovl in overlap_options:
                for wf in wire_formats:
                    for wl in wire_layouts:
                        cand = Candidate(name, s, bool(ovl), str(wf),
                                         str(wl), dxyz)
                        if candidate_feasible(cand, geom):
                            out.append(cand)
    return out


# ---------------------------------------------------------------------------
# VMEM tiling candidates (the Pallas block-shape tuning axis)

#: sustained HBM bytes/s one core can stream — a TPU-v4-ballpark
#: constant (the tuner's pingpong fit calibrates the WIRE, not HBM;
#: ranking block shapes only needs a monotone price, and amplification
#: differences dominate any bandwidth rescale)
DEFAULT_HBM_BYTES_PER_S = 1.2e12


@dataclasses.dataclass(frozen=True)
class TilingCandidate:
    """One planner-legal Pallas block shape, priced by the static VMEM
    planner (``analysis/tiling.py``): the double-buffered footprint it
    stages and the modeled HBM read amplification its edge refetches
    cost. The tuner ranks these exactly like exchange methods — the
    calibrated model orders, the plan record carries the winner — so
    ``Method.Auto`` ships a tile shape the same way it ships an
    exchange strategy."""

    block_z: int
    block_y: int
    footprint_bytes: int = 0
    amplification: float = 1.0

    def key(self) -> str:
        return f"tile[bz={self.block_z},by={self.block_y}]"


def tiling_candidate_space(geom: TuneGeometry,
                           kernel: str = "jacobi7_halo_pallas",
                           cap_z: int = 16, cap_y: int = 128
                           ) -> List[TilingCandidate]:
    """Every planner-legal block shape for the production multi-device
    Pallas kernel (the Jacobi halo kernel — the SNIPPETS.md 512^3
    failure's kernel) at this shard geometry, planner-ranked. Empty
    when the planner proves the shard infeasible (the model then
    declines the Pallas path; ``Plan.tiling`` records the constraint)."""
    from ..analysis.tiling import plan_blocks
    from ..ops.pallas_halo import _jacobi_halo_elems
    from ..ops.pallas_stencil import sublane_tile_bytes

    z, y, x = geom.shard_interior_zyx
    isz = max(geom.elem_sizes) if geom.elem_sizes else 4
    esub = sublane_tile_bytes(isz)
    if y % esub:
        esub = 1
    plan = plan_blocks(kernel, z, y, x, isz, _jacobi_halo_elems(esub),
                       sublane_y=esub, cap_z=cap_z, cap_y=cap_y)
    return [TilingCandidate(o.block_z, o.block_y, o.footprint_bytes,
                            o.amplification) for o in plan.options]


def rank_tiling_candidates(geom: TuneGeometry,
                           candidates: Optional[
                               Sequence[TilingCandidate]] = None,
                           hbm_bytes_per_s: float = DEFAULT_HBM_BYTES_PER_S
                           ) -> List[Tuple[float, TilingCandidate]]:
    """Rank legal tile shapes by modeled HBM seconds per step:
    ``(amplification + 1) x interior bytes / bandwidth`` (one amplified
    read pass + one write pass), cheapest first; ties prefer the fatter
    ``block_y`` then ``block_z`` (fatter lane-aligned DMAs)."""
    cands = (list(candidates) if candidates is not None
             else tiling_candidate_space(geom))
    z, y, x = geom.shard_interior_zyx
    isz = max(geom.elem_sizes) if geom.elem_sizes else 4
    interior_bytes = z * y * x * isz
    ranked = [((c.amplification + 1.0) * interior_bytes
               / float(hbm_bytes_per_s), c) for c in cands]
    ranked.sort(key=lambda t: (t[0], -t[1].block_y, -t[1].block_z))
    return ranked


def tiling_record(geom: TuneGeometry) -> Dict[str, Dict]:
    """The ``Plan.tiling`` payload: the prescribed block shape (and its
    planner metrics) per production Pallas kernel for this geometry —
    what a fleet pre-baking plans ships, and what the observatory
    ledger stamps bench records with so future real-TPU numbers group
    against the shapes that produced them. The kernels re-derive the
    identical shape deterministically from the same planner, so the
    record is provenance, not a second source of truth."""
    ranked = rank_tiling_candidates(geom)
    if not ranked:
        return {"jacobi7_halo_pallas": {
            "infeasible": "no planner-legal block shape at this shard "
                          "geometry (see analysis.tiling targets)"}}
    modeled_s, c = ranked[0]
    return {"jacobi7_halo_pallas": {
        "block": [c.block_z, c.block_y],
        "footprint_bytes": c.footprint_bytes,
        "amplification": c.amplification,
        "modeled_hbm_s_per_step": modeled_s,
    }}


# ---------------------------------------------------------------------------
# particle-migration candidates (the PIC workload's tuning axis)


@dataclasses.dataclass(frozen=True)
class MigrationCandidate:
    """One point of the particle-migration configuration space: the
    per-shard SoA ``capacity`` (HBM cost, receive headroom) and the
    per-direction wire ``budget`` (the static message size — the whole
    wire bill of the dynamic exchange, ``analysis/costmodel.
    migration_wire_bytes_per_shard``)."""

    capacity: int
    budget: int

    def key(self) -> str:
        return f"migrate[cap={self.capacity},budget={self.budget}]"


def migration_candidate_space(particles_per_shard: int,
                              capacities: Optional[Sequence[int]] = None,
                              budgets: Optional[Sequence[int]] = None
                              ) -> List[MigrationCandidate]:
    """The (capacity, budget) grid the migration tuner ranks. Defaults
    sweep power-of-two headrooms over the mean fill (capacity 1.25x-4x
    the per-shard particle count; budgets from capacity/32 up to
    capacity) — a candidate must at minimum hold the uniform fill."""
    n = max(int(particles_per_shard), 1)
    if capacities is None:
        capacities = sorted({max(8, int(n * f))
                             for f in (1.25, 1.5, 2.0, 4.0)})
    out: List[MigrationCandidate] = []
    for cap in capacities:
        if cap < n:
            continue
        bs = (budgets if budgets is not None
              else sorted({max(1, cap // d) for d in (32, 16, 8, 4, 2, 1)}))
        for b in bs:
            if 1 <= b <= cap:
                out.append(MigrationCandidate(int(cap), int(b)))
    return out


def migration_candidate_feasible(cand: MigrationCandidate,
                                 particles_per_shard: int,
                                 max_crossing_fraction: float,
                                 headroom: float = 1.5) -> bool:
    """Overflow-safety gate: the budget must hold the worst expected
    per-direction flux (``particles_per_shard x max_crossing_fraction``,
    padded by ``headroom`` for clumping) and the capacity must carry
    the uniform fill with the same ``headroom`` factor of slack for
    arrival imbalance — an overflowing plan DROPS particles (the
    in-graph counter reports it), so the tuner never ranks one, no
    matter how cheap its wire bill."""
    n = max(int(particles_per_shard), 1)
    need_budget = int(n * float(max_crossing_fraction)
                      * float(headroom)) + 1
    if cand.budget < need_budget:
        return False
    if cand.capacity < int(n * float(headroom)):
        return False
    return True


def rank_migration_candidates(particles_per_shard: int, n_fields: int,
                              counts, elem_size: int,
                              max_crossing_fraction: float = 0.25,
                              coeffs=None,
                              candidates: Optional[
                                  Sequence[MigrationCandidate]] = None,
                              headroom: float = 1.5
                              ) -> List[Tuple[float, MigrationCandidate]]:
    """Rank feasible migration configurations by the calibrated
    alpha-beta wire cost per step (``analysis/costmodel.
    migration_step_seconds``), cheapest first; capacity breaks ties
    (smaller = less HBM). The winner is the smallest overflow-safe
    budget — wire bytes scale linearly with the budget, so safety, not
    speed, is the binding constraint. Raises when nothing is feasible
    (the flux outruns every candidate: shrink dt or grow capacity)."""
    cands = (list(candidates) if candidates is not None
             else migration_candidate_space(particles_per_shard))
    from ..analysis.costmodel import migration_step_seconds

    ranked: List[Tuple[float, MigrationCandidate]] = []
    for c in cands:
        if not migration_candidate_feasible(
                c, particles_per_shard, max_crossing_fraction, headroom):
            continue
        ranked.append((migration_step_seconds(
            n_fields, c.budget, counts, elem_size, coeffs), c))
    if not ranked:
        raise ValueError(
            f"no feasible migration candidate for "
            f"{particles_per_shard} particles/shard at crossing "
            f"fraction {max_crossing_fraction} (budgets too small "
            f"everywhere — raise capacity or lower the flux)")
    ranked.sort(key=lambda t: (t[0], t[1].capacity, t[1].budget))
    return ranked


# ---------------------------------------------------------------------------
# fingerprint


def radius_signature(radius: Radius) -> List[List[int]]:
    """Canonical 26-direction serialization (x,y,z,dir -> r rows)."""
    return [[d.x, d.y, d.z, radius.dir(d)] for d in all_directions()]


def fingerprint(inputs: Dict) -> str:
    """Stable hash of the fingerprint inputs (sorted-key JSON)."""
    blob = json.dumps(inputs, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()[:32]


def fingerprint_inputs(platform: str, device_count: int,
                       mesh_shape: Sequence[int],
                       grid: Sequence[int], radius: Radius,
                       quantities: Dict[str, str],
                       boundary: str, n_slices: int = 1,
                       library_version: Optional[str] = None,
                       wire_format: str = "f32",
                       wire_layout: str = "slab",
                       exchange_depths: Optional[Sequence[int]] = None,
                       placement: str = "auto") -> Dict:
    """The identity a plan is valid for (see module docstring).
    ``quantities`` maps name -> numpy dtype string. ``wire_format``
    and ``wire_layout`` are part of the identity: a plan tuned for
    the f32 slab wire must never replay onto a bf16 or irredundant
    wire domain (the measured coefficients price a different byte
    bill). ``exchange_depths`` (x, y, z) and ``placement`` join the
    identity only when NON-default (non-uniform depths / mode other
    than "auto") so fingerprints of symmetric auto-placed domains —
    and every plan cached before these axes existed — are unchanged."""
    if library_version is None:
        from .. import __version__ as library_version
    out = {
        "platform": str(platform),
        "device_count": int(device_count),
        "mesh_shape": [int(v) for v in mesh_shape],
        "grid": [int(v) for v in grid],
        "radius": radius_signature(radius),
        "quantities": {str(k): str(v) for k, v in quantities.items()},
        "boundary": str(boundary),
        "n_slices": int(n_slices),
        "library_version": str(library_version),
        "wire_format": str(wire_format),
        "wire_layout": str(wire_layout),
    }
    if exchange_depths is not None and len(set(exchange_depths)) > 1:
        out["exchange_depths"] = [int(v) for v in exchange_depths]
    if str(placement) != "auto":
        out["placement"] = str(placement)
    return out


# ---------------------------------------------------------------------------
# the Plan


@dataclasses.dataclass
class Plan:
    """The autotuner's output: the winning configuration plus the
    evidence (coefficients, per-candidate costs) and provenance."""

    config: Candidate
    fingerprint: str
    #: link class -> {"alpha_s": ..., "beta_bytes_per_s": ...}
    coefficients: Dict[str, Dict[str, float]]
    #: candidate key -> {"predicted_s": ..., "measured_s": ...?}
    costs: Dict[str, Dict[str, float]]
    provenance: str = "tuned"        # tuned | cached | default
    measurements: int = 0            # timer invocations THIS process
    created: float = 0.0
    library_version: str = ""
    fingerprint_inputs: Optional[Dict] = None
    #: predict_exchange_every's calibrated depth-crossover estimate
    #: (observability: what the analytic model alone would have picked)
    predicted_best_depth: Optional[int] = None
    #: kernel -> the VMEM planner's prescribed block shape + metrics
    #: (:func:`tiling_record`) — plan-cache records carry the chosen
    #: tile shape the same way they carry the chosen exchange method
    tiling: Dict[str, Dict] = dataclasses.field(default_factory=dict)
    #: the placement mode the plan was tuned under ("auto" | "qap" |
    #: "trivial") — records from before the placement axis existed
    #: load as "auto" (the then-only behavior)
    placement: str = "auto"

    def to_record(self) -> Dict:
        rec = dataclasses.asdict(self)  # recurses into Candidate
        rec["schema"] = SCHEMA_VERSION
        return rec

    @staticmethod
    def from_record(rec: Dict) -> "Plan":
        cfg = rec["config"]
        depths = cfg.get("depths")  # pre-per-axis records lack the key
        return Plan(
            config=Candidate(str(cfg["method"]),
                             int(cfg["exchange_every"]),
                             bool(cfg.get("overlap", False)),
                             str(cfg.get("wire_format", "f32")),
                             str(cfg.get("wire_layout", "slab")),
                             tuple(int(v) for v in depths)
                             if depths is not None else None),
            fingerprint=str(rec["fingerprint"]),
            coefficients=dict(rec.get("coefficients", {})),
            costs=dict(rec.get("costs", {})),
            provenance=str(rec.get("provenance", "tuned")),
            measurements=int(rec.get("measurements", 0)),
            created=float(rec.get("created", 0.0)),
            library_version=str(rec.get("library_version", "")),
            fingerprint_inputs=rec.get("fingerprint_inputs"),
            predicted_best_depth=rec.get("predicted_best_depth"),
            tiling=dict(rec.get("tiling", {})),
            placement=str(rec.get("placement", "auto")),
        )
