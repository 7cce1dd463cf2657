"""Stencil compute kernels on padded (z,y,x) shards.

XLA-native equivalents of the reference's application kernels
(reference: bin/jacobi3d.cu:40-85 stencil_kernel). Each kernel takes a
halo-padded shard and produces interior values; slicing-based neighbor
access lowers to fused XLA ops (the VPU does the adds; no gather). A
Pallas version of the hot kernels lives in ``pallas_stencil.py``.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp
from jax import lax

from ..geometry import Dim3, Radius
from ..telemetry.spans import INTERIOR, KERNEL, layer_scope


def shifted(padded: jnp.ndarray, off: Tuple[int, int, int],
            pad_lo: Dim3, interior: Dim3) -> jnp.ndarray:
    """Interior-shaped view of ``padded`` shifted by ``off`` (x,y,z
    direction vector): element [k,j,i] = padded[k+oz, j+oy, i+ox] in
    interior coordinates."""
    ox, oy, oz = off
    return lax.slice(
        padded,
        (pad_lo.z + oz, pad_lo.y + oy, pad_lo.x + ox),
        (pad_lo.z + oz + interior.z, pad_lo.y + oy + interior.y,
         pad_lo.x + ox + interior.x))


@layer_scope(KERNEL)
def jacobi7(padded: jnp.ndarray, radius: Radius, interior: Dim3) -> jnp.ndarray:
    """7-point Jacobi average: (sum of 6 face neighbors) / 6
    (reference: bin/jacobi3d.cu:65-80)."""
    lo = radius.pad_lo()
    acc = shifted(padded, (1, 0, 0), lo, interior)
    acc = acc + shifted(padded, (-1, 0, 0), lo, interior)
    acc = acc + shifted(padded, (0, 1, 0), lo, interior)
    acc = acc + shifted(padded, (0, -1, 0), lo, interior)
    acc = acc + shifted(padded, (0, 0, 1), lo, interior)
    acc = acc + shifted(padded, (0, 0, -1), lo, interior)
    return acc * (1.0 / 6.0)


@layer_scope(KERNEL)
def laplacian27(padded: jnp.ndarray, radius: Radius, interior: Dim3,
                weights=None) -> jnp.ndarray:
    """27-point weighted stencil (radius-1 box) — exercises edge/corner
    halo data; default weights are the standard 27-point Laplacian."""
    lo = radius.pad_lo()
    if weights is None:
        # face 6/26? use canonical 27-pt laplacian weights
        w_center, w_face = -88.0 / 26.0, 6.0 / 26.0
        w_edge, w_corner = 3.0 / 26.0, 2.0 / 26.0
    else:
        w_center, w_face, w_edge, w_corner = weights
    out = w_center * shifted(padded, (0, 0, 0), lo, interior)
    for dx in (-1, 0, 1):
        for dy in (-1, 0, 1):
            for dz in (-1, 0, 1):
                n = (dx != 0) + (dy != 0) + (dz != 0)
                if n == 0:
                    continue
                w = (w_face, w_edge, w_corner)[n - 1]
                out = out + w * shifted(padded, (dx, dy, dz), lo, interior)
    return out


@layer_scope(KERNEL)
def central_diff(padded: jnp.ndarray, axis: int, radius: Radius,
                 interior: Dim3) -> jnp.ndarray:
    """Second-order central difference along grid ``axis`` (0=x, 1=y,
    2=z): ``(p[i+1] - p[i-1]) / 2`` over the interior — the radius-1
    gradient component the PIC mini-app's field gather interpolates
    (``models/pic.py`` computes ``E = -grad rho`` from the deposited
    charge). ``radius`` is the ALLOCATION radius of ``padded`` (the
    slices reach one cell past the interior along ``axis`` only)."""
    lo = radius.pad_lo()
    plus = [0, 0, 0]
    plus[axis] = 1
    minus = [0, 0, 0]
    minus[axis] = -1
    return (shifted(padded, tuple(plus), lo, interior)
            - shifted(padded, tuple(minus), lo, interior)) * 0.5


@layer_scope(INTERIOR)
def read_interior(padded: jnp.ndarray, radius: Radius,
                  interior: Dim3,
                  stored: Optional[Dim3] = None) -> jnp.ndarray:
    """The interior of a padded shard, halos dropped; with ``stored``,
    the interior at the low corner of an array of that larger extent
    (the aligned layout a kernel steps, ``pallas_stencil.wrap_layout``)
    in one pass, its rows past the interior the shard's high halo, then
    zeros."""
    if stored is None or stored == interior:
        return shifted(padded, (0, 0, 0), radius.pad_lo(), interior)
    return lax.pad(padded, jnp.zeros((), padded.dtype),
                   _crop(padded.shape, radius, stored))


@layer_scope(INTERIOR)
def write_interior(padded: jnp.ndarray, interior_vals: jnp.ndarray,
                   radius: Radius,
                   interior: Optional[Dim3] = None) -> jnp.ndarray:
    """Place interior-shaped values into a padded shard (halos keep
    their previous contents). Values in a larger stored layout
    (``read_interior(stored=...)``) with ``interior`` given make the
    whole shard anew in one pass instead: the interior from them, the
    halos from their padding rows or zeros (stale either way)."""
    lo = radius.pad_lo()
    if interior is not None and interior_vals.shape != (
            interior.z, interior.y, interior.x):
        stored = Dim3(*interior_vals.shape[::-1])
        return lax.pad(interior_vals.astype(padded.dtype),
                       jnp.zeros((), padded.dtype),
                       [(-a, -b, 0) for a, b, _ in
                        _crop(padded.shape, radius, stored)])
    return lax.dynamic_update_slice(padded, interior_vals.astype(padded.dtype),
                                    (lo.z, lo.y, lo.x))


def _crop(padded_zyx, radius: Radius, stored: Dim3):
    """``lax.pad`` config taking a padded shard of ``padded_zyx`` to a
    ``stored`` extent whose origin is the interior's first cell."""
    lo = radius.pad_lo()
    return [(-l, s - (p - l), 0) for p, l, s in
            zip(padded_zyx, (lo.z, lo.y, lo.x),
                (stored.z, stored.y, stored.x))]


def global_coords(origin_xyz, interior: Dim3):
    """(z, y, x) broadcastable global-coordinate arrays for a shard's
    interior — the Accessor "friendly coordinates" analog for
    masks/sources (reference: include/stencil/accessor.hpp:31-45).
    ``origin_xyz`` is an (ox, oy, oz) triple; traced scalars are fine
    (e.g. derived from ``lax.axis_index`` inside shard_map)."""
    ox, oy, oz = origin_xyz
    gz = oz + jnp.arange(interior.z)[:, None, None]
    gy = oy + jnp.arange(interior.y)[None, :, None]
    gx = ox + jnp.arange(interior.x)[None, None, :]
    return gz, gy, gx
