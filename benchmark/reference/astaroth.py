"""Plain reference of the Astaroth MHD integrator, independent of the
program: 8 fields, 6th-order central and cross derivatives, Williamson
low-storage RK3 (cwpearson/stencil astaroth/user_kernels.h:383-453,
astaroth/integration.cuh:14-38), periodic in every axis.

Written with ``jnp.roll`` in x and y and slices of a z-block that
carries 3 wrapped planes on each side, so a grid larger than the
temporaries one chip holds is computed block by block."""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import SingleDeviceSharding

from benchmark.harness import seeded

FIELDS = ("lnrho", "uux", "uuy", "uuz", "ax", "ay", "az", "ss")
R = 3
ALPHA = (0.0, -5.0 / 9.0, -153.0 / 128.0)
BETA = (1.0 / 3.0, 15.0 / 16.0, 8.0 / 15.0)
D1 = (3.0 / 4.0, -3.0 / 20.0, 1.0 / 60.0)
D2_C, D2 = -49.0 / 18.0, (3.0 / 2.0, -3.0 / 20.0, 1.0 / 90.0)
DX = (270.0 / 720.0, -27.0 / 720.0, 2.0 / 720.0)
BLOCK_Z = 64


def initial_field(name: str, key, coords, global_zyx):
    """Seeded noise in the shape of the source's start
    (astaroth/astaroth.cu:509-528): vector potential and entropy in
    [-1, 1), log density 0.5 +- 0.1, velocity +- 0.1 (the source's
    radial shell has amplitude 1)."""
    u = 2.0 * seeded.uniform(key,
                             *coords, global_zyx) - 1.0
    if name == "lnrho":
        return 0.5 + 0.1 * u
    if name.startswith("uu"):
        return 0.1 * u
    return u


def initial_state(seed: int, global_zyx, device):
    coords = seeded.interior_coords(global_zyx)
    keys = {q: seeded.stream_key(seed, i) for i, q in enumerate(FIELDS)}
    make = jax.jit(lambda keys: {q: initial_field(q, keys[q], coords,
                                                  global_zyx)
                                 for q in FIELDS},
                   out_shardings=SingleDeviceSharding(device))
    return make(keys)


class _Block:
    """One z-block of a field with R wrapped planes above and below:
    shifted views of its interior, and the derivatives built from
    them."""

    def __init__(self, ext, inv_ds, nz):
        self.e, self.inv, self.nz = ext, inv_ds, nz

    def at(self, dx=0, dy=0, dz=0):
        v = lax.dynamic_slice_in_dim(self.e, R + dz, self.nz, 0)
        if dx or dy:
            v = jnp.roll(v, (-dy, -dx), (1, 2))
        return v

    def _off(self, axis, i):
        off = [0, 0, 0]
        off[axis] = i
        return self.at(*off)

    def d1(self, axis):
        acc = sum(c * (self._off(axis, i) - self._off(axis, -i))
                  for i, c in enumerate(D1, 1))
        return acc * self.inv[axis]

    def d2(self, axis):
        acc = D2_C * self.at() + sum(
            c * (self._off(axis, i) + self._off(axis, -i))
            for i, c in enumerate(D2, 1))
        return acc * self.inv[axis] ** 2

    def dxy(self, a, b):
        if a == b:
            return self.d2(a)

        def at2(sa, sb):
            off = [0, 0, 0]
            off[a], off[b] = sa, sb
            return self.at(*off)

        acc = sum(c * (at2(i, i) + at2(-i, -i) - at2(i, -i) - at2(-i, i))
                  for i, c in enumerate(DX, 1))
        return acc * self.inv[a] * self.inv[b]

    def lap(self):
        return self.d2(0) + self.d2(1) + self.d2(2)


def _rates(b, p, dt_):
    """Right-hand sides (astaroth/user_kernels.h:383-453), all in
    ``dt_``; ``b`` maps field name to its _Block, axes 0/1/2 = x/y/z."""
    c = functools.partial(jnp.asarray, dtype=dt_)
    lnrho, ss = b["lnrho"], b["ss"]
    U = [b["uux"], b["uuy"], b["uuz"]]
    A = [b["ax"], b["ay"], b["az"]]
    u = [q.at() for q in U]
    g_lnrho = [lnrho.d1(i) for i in range(3)]
    g_ss = [ss.d1(i) for i in range(3)]
    gu = [[U[i].d1(k) for k in range(3)] for i in range(3)]   # du_i/dx_k
    div_u = gu[0][0] + gu[1][1] + gu[2][2]

    def dot(x, y):
        return x[0] * y[0] + x[1] * y[1] + x[2] * y[2]

    def cross(x, y):
        return [x[1] * y[2] - x[2] * y[1], x[2] * y[0] - x[0] * y[2],
                x[0] * y[1] - x[1] * y[0]]

    S = [[None] * 3 for _ in range(3)]
    for i in range(3):
        others = [gu[k][k] for k in range(3) if k != i]
        S[i][i] = (c(2.0 / 3.0) * gu[i][i]
                   - c(1.0 / 3.0) * (others[0] + others[1]))
        for k in range(i + 1, 3):
            S[i][k] = S[k][i] = c(0.5) * (gu[i][k] + gu[k][i])

    grad_div_A = [sum(A[k].dxy(i, k) for k in range(3)) for i in range(3)]
    lap_A = [q.lap() for q in A]
    j = [c(1.0 / p["mu0"]) * (grad_div_A[i] - lap_A[i]) for i in range(3)]
    B = [A[2].d1(1) - A[1].d1(2), A[0].d1(2) - A[2].d1(0),
         A[1].d1(0) - A[0].d1(1)]

    out = {"lnrho": -dot(u, g_lnrho) - div_u}
    uxB = cross(u, B)
    for i, q in enumerate(("ax", "ay", "az")):
        out[q] = uxB[i] + c(p["eta"]) * lap_A[i]

    gamma, cp = p["gamma"], p["cp_sound"]
    lnrho_d = lnrho.at() - c(p["lnrho0"])
    cs2 = c(p["cs_sound"] ** 2) * jnp.exp(c(gamma / cp) * ss.at()
                                          + c(gamma - 1.0) * lnrho_d)
    inv_rho = jnp.exp(-lnrho.at())
    grad_div_u = [sum(U[k].dxy(i, k) for k in range(3)) for i in range(3)]
    jxB = cross(j, B)
    for i, q in enumerate(("uux", "uuy", "uuz")):
        adv = dot(gu[i], u)
        out[q] = (-adv
                  - cs2 * (c(1.0 / cp) * g_ss[i] + g_lnrho[i])
                  + inv_rho * jxB[i]
                  + c(p["nu_visc"]) * (U[i].lap()
                                       + c(1.0 / 3.0) * grad_div_u[i]
                                       + c(2.0) * dot(S[i], g_lnrho))
                  + c(p["zeta"]) * grad_div_u[i])

    lnT = c(p["lnT0"]) + c(gamma / cp) * ss.at() + c(gamma - 1.0) * lnrho_d
    rho = jnp.exp(lnrho.at())
    inv_pT = jnp.exp(-lnrho.at() - lnT)
    SS = sum(S[i][k] * S[i][k] for i in range(3) for k in range(3))
    visc_heat = (c(p["eta"] * p["mu0"]) * dot(j, j)
                 + c(2.0 * p["nu_visc"]) * rho * SS
                 + c(p["zeta"]) * rho * div_u * div_u)
    # heat conduction with chi = 0.001 / (rho cp)
    # (astaroth/user_kernels.h:441-449)
    first = (c(gamma / cp) * ss.lap() + c(gamma - 1.0) * lnrho.lap())
    second = [c(gamma / cp) * g_ss[i] + c(gamma - 1.0) * g_lnrho[i]
              for i in range(3)]
    third = [c(gamma) * (c(1.0 / cp) * g_ss[i] + g_lnrho[i]) - g_lnrho[i]
             for i in range(3)]
    chi = c(0.001) * jnp.exp(-lnrho.at()) * c(1.0 / cp)
    heat = c(cp) * chi * (first + dot(second, third))
    out["ss"] = -dot(u, g_ss) + inv_pT * visc_heat + heat
    return out


@functools.partial(jax.jit, static_argnums=(3, 4), donate_argnums=(0, 1))
def _substep(u, w, ab, params, dtype):
    """One RK3 substep w = alpha w + dt F(u); u += beta w over the
    whole grid, z-block by z-block."""
    params = dict(params)
    Z, Y, X = u[FIELDS[0]].shape
    nz = min(BLOCK_Z, Z)
    inv = (1.0 / params["dsx"], 1.0 / params["dsy"], 1.0 / params["dsz"])
    inv = tuple(jnp.asarray(v, dtype) for v in inv)
    alpha, beta = ab[0].astype(dtype), ab[1].astype(dtype)
    dt_ = jnp.asarray(params["dt"], dtype)
    ext = {q: jnp.concatenate([u[q][Z - R:], u[q], u[q][:R]], 0)
           for q in FIELDS}

    def block(k, uw):
        u_out, w_out = uw
        z0 = k * nz
        b = {q: _Block(lax.dynamic_slice_in_dim(ext[q], z0, nz + 2 * R, 0),
                       inv, nz) for q in FIELDS}
        f = _rates(b, params, dtype)
        for q in FIELDS:
            wq = (alpha * lax.dynamic_slice_in_dim(w_out[q], z0, nz, 0)
                  + dt_ * f[q])
            uq = b[q].at() + beta * wq
            w_out[q] = lax.dynamic_update_slice_in_dim(w_out[q], wq, z0, 0)
            u_out[q] = lax.dynamic_update_slice_in_dim(u_out[q], uq, z0, 0)
        return u_out, w_out

    return lax.fori_loop(0, Z // nz, block, (dict(u), dict(w)))


def advance(state, iters: int, cfg, dtype=jnp.float32):
    """``iters`` RK3 iterations (3 substeps each) from ``state``
    (consumed) computed in ``dtype``; returns float32 fields."""
    dtype = jnp.dtype(dtype)
    params = tuple(sorted(cfg["params"].items()))
    u = {q: v.astype(dtype) for q, v in state.items()}
    w = {q: jnp.zeros_like(v) for q, v in u.items()}
    z = u[FIELDS[0]].shape[0]
    if z % min(BLOCK_Z, z):
        raise ValueError(f"z extent {z} is not a multiple of {BLOCK_Z}")
    for _ in range(iters):
        for s in range(3):
            ab = jnp.asarray([ALPHA[s], BETA[s]], jnp.float32)
            u, w = _substep(u, w, ab, params, dtype)
    return {q: v.astype(jnp.float32) for q, v in u.items()}
