"""Communication-avoiding temporal blocking: one deep exchange feeds
``s`` fused stencil steps.

The per-step halo exchange is the wire cost the rest of this library
works to hide; this module stops paying it every step. One exchange
ships a depth-``s*r`` halo (``geometry.Radius.deepened``), then ``s``
stencil applications run locally on a *shrinking valid region*: each
sub-step consumes one base-radius ring, so sub-step ``k`` computes the
window ``interior + (s-1-k)*r`` and the final sub-step lands exactly on
the interior. Halo-ring cells are recomputed redundantly — the same
values their owner shard computes, so ``s``-blocked stepping is
numerically identical to step-by-step stepping (the classic
communication-avoiding trade: ``s``x fewer exchange rounds for a thin
ring of redundant compute and deeper slabs; compare the reference's
single-depth per-step exchange, src/stencil.cu:1002-1186).

Geometry (per axis, padded array coords; ``p = alloc_steps * r`` pads):

    [0 ......... p | interior capacity C | p ......... alloc)
    sub-step k window:  [p - m*r_lo,  p + C + m*r_hi),  m = s-1-k

Uneven (+-1 remainder) shards keep STATIC capacity-based windows: a
short shard's window reads at most one slack row of garbage at the top,
which only ever contaminates cells *beyond* the validity the next
sub-step requires (the same induction that makes the base exchange's
dead-row placement sound) — so one program serves every shard.

Overlap composition: with ``overlap=True`` the first sub-step splits
into the deep-interior block (computed from PRE-exchange owned data, so
XLA schedules it against the in-flight deep ppermutes — the
``parallel/overlap.py`` trick at temporal depth) plus thin shells of
thickness ``s*r`` computed from the exchanged fields.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
from jax import lax

from ..geometry import DepthsLike, Dim3, Radius, normalize_depths
from ..telemetry.spans import INTERIOR, layer_scope
from .exchange import dispatch_exchange
from .methods import Method

ZERO = Dim3(0, 0, 0)

# a temporal update function: (padded blocks per field, window interior
# dims (x,y,z as Dim3), window offset (x,y,z) in shard-interior coords
# — negative for halo-ring cells recomputed redundantly — and the
# sub-step index k) -> dict of window-shaped outputs for the fields it
# advances. The callee owns per-sub-step sources and boundary values:
# ring cells must receive exactly what their owner shard computes
# (wrap global coords in periodic mode; zero outside the domain in
# Boundary.NONE mode).
TemporalUpdateFn = Callable[[Dict[str, jnp.ndarray], Dim3,
                             Tuple[int, int, int], int],
                            Dict[str, jnp.ndarray]]


def validate_temporal(radius: Radius, local: Dim3, steps: DepthsLike,
                      rem: Dim3 = ZERO) -> None:
    """Feasibility of ``steps``-deep blocking on ``local``-capacity
    shards: every shard's ACTUAL interior must supply the deep slab the
    exchange ships from it (``s_a * r`` rows per side, per axis)."""
    depths = normalize_depths(steps)
    for a in range(3):
        min_interior = local[a] - (1 if rem[a] else 0)
        need = depths[a] * max(radius.face(a, -1), radius.face(a, 1))
        if need and min_interior < need:
            raise ValueError(
                f"temporal blocking depth {depths[a]} needs interior >= "
                f"{need} along axis {'xyz'[a]}, but the smallest shard "
                f"has {min_interior} (grow the grid or lower "
                f"exchange_every)")


def sub_step_windows(radius: Radius, capacity: Dim3, steps: DepthsLike
                     ) -> List[Tuple[Dim3, Dim3]]:
    """The shrinking-window schedule in shard-interior coords: for each
    sub-step ``k`` the (offset, dims) of the region it computes —
    offset components are ``-m_a * r_lo``, dims
    ``capacity + m_a * (r_lo + r_hi)`` with the per-axis extension
    ``m_a(k) = s_a - 1 - (k mod s_a)`` (negative offsets = halo ring).
    With uniform depths ``m = s - 1 - k``; sub-step ``max(s) - 1``
    lands exactly on ``((0,0,0), capacity)``. Per-axis depths saw-tooth:
    each axis's window re-extends right after its own mid-group
    exchange refreshes it (see :func:`temporal_shard_steps`)."""
    depths = normalize_depths(steps)
    out = []
    lo, hi = radius.pad_lo(), radius.pad_hi()
    for k in range(max(depths)):
        m = Dim3(depths.x - 1 - (k % depths.x),
                 depths.y - 1 - (k % depths.y),
                 depths.z - 1 - (k % depths.z))
        off = Dim3(-m.x * lo.x, -m.y * lo.y, -m.z * lo.z)
        dims = Dim3(capacity.x + m.x * (lo.x + hi.x),
                    capacity.y + m.y * (lo.y + hi.y),
                    capacity.z + m.z * (lo.z + hi.z))
        out.append((off, dims))
    return out


def refresh_axes(depths: DepthsLike, k: int) -> List[int]:
    """The axes whose halo an asymmetric group exchanges at sub-step
    ``k``: axis ``a`` is refreshed when ``k % s_a == 0`` (sub-step 0 is
    the full multi-axis exchange; shallow axes re-exchange mid-group
    while deep axes coast on their ring). Uniform depths refresh every
    axis at ``k == 0`` only."""
    depths = normalize_depths(depths)
    return [a for a in range(3) if k % depths[a] == 0]


def _axes_wire_radius(radius: Radius, depths: Dim3,
                      axes: Sequence[int]) -> Radius:
    """Wire radius for a mid-group refresh of ``axes`` only: those
    axes' faces deepen to ``s_a * r``; every other direction is zero,
    so the sequential-sweep engine skips the coasting axes entirely."""
    out = Radius.constant(0)
    for a in axes:
        for side in (-1, 1):
            d = [0, 0, 0]
            d[a] = side
            out.set_dir(tuple(d), depths[a] * radius.face(a, side))
    return out


def _region_blocks(fields: Dict[str, jnp.ndarray], p_lo: Dim3,
                   r_lo: Dim3, r_hi: Dim3, off: Dim3, dims: Dim3
                   ) -> Dict[str, jnp.ndarray]:
    """Slice every field's stencil-read block for the region at
    interior-coords ``off``: padded coords
    ``[p_lo + off - r_lo, p_lo + off + dims + r_hi)``."""
    z0 = p_lo.z + off.z - r_lo.z
    y0 = p_lo.y + off.y - r_lo.y
    x0 = p_lo.x + off.x - r_lo.x
    return {q: lax.slice(
        p, (z0, y0, x0),
        (z0 + r_lo.z + dims.z + r_hi.z,
         y0 + r_lo.y + dims.y + r_hi.y,
         x0 + r_lo.x + dims.x + r_hi.x))
        for q, p in fields.items()}


@layer_scope(INTERIOR)
def _write_region(fields: Dict[str, jnp.ndarray], p_lo: Dim3, off: Dim3,
                  outs: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
    fields = dict(fields)
    for q, val in outs.items():
        fields[q] = lax.dynamic_update_slice(
            fields[q], val,
            (p_lo.z + off.z, p_lo.y + off.y, p_lo.x + off.x))
    return fields


def temporal_shard_steps(fields: Dict[str, jnp.ndarray], radius: Radius,
                         mesh_counts: Dim3, method: Method,
                         update_fn: TemporalUpdateFn, steps: DepthsLike,
                         alloc_steps: Optional[DepthsLike] = None,
                         rem: Dim3 = ZERO,
                         exchange_keys: Optional[Sequence[str]] = None,
                         overlap: bool = False,
                         nonperiodic: bool = False,
                         wire_format=None,
                         wire_layout=None
                         ) -> Dict[str, jnp.ndarray]:
    """One ``steps``-deep blocked group on one shard: a single
    depth-``steps*r`` exchange, then ``steps`` applications of
    ``update_fn`` on the shrinking windows. Must be traced inside
    ``shard_map`` (the ``dispatch_exchange`` contract).

    ``fields``: padded (z,y,x) blocks, allocation pads
    ``alloc_steps * r`` per side (``alloc_steps`` defaults to
    ``steps``; a larger allocation lets tail groups of smaller depth
    run on the same buffers — the exchange then places ``steps*r``
    slabs immediately around the interior).
    ``exchange_keys``: the subset of fields the deep exchange carries
    (default: all). Fields outside it still window-cycle — their ring
    values come from earlier sub-steps' writes, never from the wire
    (e.g. an RK accumulator the group's first sub-step does not read).
    ``overlap``: split sub-step 0 into the pre-exchange deep-interior
    block plus post-exchange shells so the deep exchange hides behind
    compute (even shards only).
    ``wire_format``/``wire_layout``: the deep exchange's halo wire
    format and message layout (see ``parallel.exchange``) — the
    irredundant layout's win is largest here, where slab
    cross-sections grow with ``steps`` but the wire shell does not.

    Per-axis ``steps`` (e.g. ``{"z": 4, "y": 1, "x": 1}`` — deep
    blocking across a DCN axis, per-step exchange on ICI): the group
    runs ``max(steps)`` sub-steps; axis ``a`` is exchanged at depth
    ``s_a * r`` on every sub-step ``k`` with ``k % s_a == 0``
    (:func:`refresh_axes` — sub-step 0 is the full multi-axis
    exchange, mid-group refreshes carry only the shallow axes' faces).
    Each axis's window component saw-tooths with its own
    ``m_a(k) = s_a - 1 - (k mod s_a)``; the slab cross-sections span
    the full padded extents, so a refresh forwards the neighbor's
    coasting-axis ring rows exactly as deep as the next window reads
    (the same SPMD-symmetric induction that makes dead-row placement
    sound). Non-uniform depths decline ``overlap`` and the
    ``"irredundant"`` wire layout loudly — both assume one group-wide
    exchange.
    """
    depths = normalize_depths(steps)
    alloc_d = depths if alloc_steps is None else normalize_depths(alloc_steps)
    if any(not 1 <= depths[a] <= alloc_d[a] for a in range(3)):
        raise ValueError(f"steps={depths} outside [1, {alloc_d}]")
    steps = max(depths)
    uniform = depths.x == depths.y == depths.z
    if overlap and rem != ZERO:
        raise NotImplementedError(
            "overlap composition requires evenly divisible shards")
    if not uniform:
        if overlap:
            raise NotImplementedError(
                f"asymmetric temporal depths {tuple(depths)} decline "
                f"the overlap composition: the sub-step-0 shell split "
                f"assumes one group-wide exchange, not mid-group "
                f"refreshes")
        from .packing import normalize_wire_layout
        if normalize_wire_layout(wire_layout) != "slab":
            raise NotImplementedError(
                f"asymmetric temporal depths {tuple(depths)} decline "
                f"wire_layout {wire_layout!r}: the irredundant "
                f"dedup plan assumes one group-wide exchange whose "
                f"slabs carry the halo-of-halo rows mid-group "
                f"refreshes rely on")
    wire = radius.deepened(depths)
    alloc_r = radius.deepened(alloc_d)
    p_lo, p_hi = alloc_r.pad_lo(), alloc_r.pad_hi()
    r_lo, r_hi = radius.pad_lo(), radius.pad_hi()
    any_p = next(iter(fields.values()))
    cap = Dim3(any_p.shape[2] - p_lo.x - p_hi.x,
               any_p.shape[1] - p_lo.y - p_hi.y,
               any_p.shape[0] - p_lo.z - p_hi.z)
    validate_temporal(radius, cap, depths, rem)

    keys = sorted(fields) if exchange_keys is None else list(exchange_keys)
    pre = dict(fields)
    exchanged = dispatch_exchange({q: fields[q] for q in keys}, wire,
                                  mesh_counts, method, rem=rem,
                                  alloc_radius=alloc_r,
                                  nonperiodic=nonperiodic,
                                  wire_format=wire_format,
                                  wire_layout=wire_layout)
    out = dict(fields)
    out.update(exchanged)

    windows = sub_step_windows(radius, cap, depths)
    k0 = 0
    inner_dims = cap - r_lo - r_hi
    if overlap and not inner_dims.any_lt(1):
        # sub-step 0 as inner + shells: the inner block reads only
        # pre-exchange owned points, so it carries no data dependence
        # on the deep ppermutes and XLA may run it while slabs fly
        w_off, w_dims = windows[0]
        regions = [(Dim3(r_lo.x, r_lo.y, r_lo.z), inner_dims, pre)]
        for a in range(3):
            for side in (-1, 1):
                t = steps * radius.face(a, side)
                if t == 0:
                    continue
                off = [w_off.x, w_off.y, w_off.z]
                dims = [w_dims.x, w_dims.y, w_dims.z]
                if side == -1:
                    dims[a] = t
                else:
                    off[a] = cap[a] - r_hi[a]
                    dims[a] = t
                regions.append((Dim3(*off), Dim3(*dims), out))
        pieces = []
        for off, dims, src in regions:
            blocks = _region_blocks(src, p_lo, r_lo, r_hi, off, dims)
            pieces.append((off, update_fn(blocks, dims,
                                          (off.x, off.y, off.z), 0)))
        for off, outs in pieces:
            out = _write_region(out, p_lo, off, outs)
        k0 = 1

    for k in range(k0, steps):
        if k > 0 and not uniform:
            # mid-group refresh: the shallow axes re-exchange at their
            # own depth while deep axes coast on their remaining ring
            axes = [a for a in refresh_axes(depths, k)
                    if radius.wire_rows(a)]
            if axes:
                mid = _axes_wire_radius(radius, depths, axes)
                refreshed = dispatch_exchange(
                    {q: out[q] for q in keys}, mid, mesh_counts, method,
                    rem=rem, alloc_radius=alloc_r,
                    nonperiodic=nonperiodic, wire_format=wire_format,
                    wire_layout=wire_layout)
                out.update(refreshed)
        off, dims = windows[k]
        blocks = _region_blocks(out, p_lo, r_lo, r_hi, off, dims)
        outs = update_fn(blocks, dims, (off.x, off.y, off.z), k)
        out = _write_region(out, p_lo, off, outs)
    return out
