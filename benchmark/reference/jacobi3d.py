"""Plain reference of the Jacobi heat solver, independent of the
program: the 7-point periodic average with a hot (T=1) and a cold
(T=0) sphere re-imposed after every step (cwpearson/stencil
bin/jacobi3d.cu:40-85, spheres at x/3 and 2x/3, radius x/10), written
with ``jnp.roll`` on the whole global grid."""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import SingleDeviceSharding

from benchmark.harness import seeded

FIELDS = ("temp",)


def initial_field(name: str, key, coords, global_zyx):
    """U[0, 1): the source starts at a uniform 0.5, which cannot show
    an exchange that reads the wrong neighbour."""
    return seeded.uniform(key,
                          *coords, global_zyx)


def initial_state(seed: int, global_zyx, device):
    coords = seeded.interior_coords(global_zyx)
    keys = {q: seeded.stream_key(seed, i) for i, q in enumerate(FIELDS)}
    make = jax.jit(lambda keys: {q: initial_field(q, keys[q], coords,
                                                  global_zyx)
                                 for q in FIELDS},
                   out_shardings=SingleDeviceSharding(device))
    return make(keys)


def _spheres(global_zyx):
    z, y, x = global_zyx
    hot = (z // 2, y // 2, x // 3)
    cold = (z // 2, y // 2, 2 * x // 3)
    return hot, cold, x // 10


@functools.partial(jax.jit, static_argnums=(1, 2), donate_argnums=0)
def _advance(t, steps: int, dtype):
    t = t.astype(dtype)
    hot, cold, r = _spheres(t.shape)
    gz = lax.broadcasted_iota(jnp.int32, t.shape, 0)
    gy = lax.broadcasted_iota(jnp.int32, t.shape, 1)
    gx = lax.broadcasted_iota(jnp.int32, t.shape, 2)

    def inside(c):
        return ((gz - c[0]) ** 2 + (gy - c[1]) ** 2
                + (gx - c[2]) ** 2) <= r * r

    in_hot, in_cold = inside(hot), inside(cold)
    one, zero, sixth = (jnp.asarray(v, dtype) for v in (1.0, 0.0, 6.0))

    def step(_, u):
        s = (jnp.roll(u, 1, 0) + jnp.roll(u, -1, 0) + jnp.roll(u, 1, 1)
             + jnp.roll(u, -1, 1) + jnp.roll(u, 1, 2) + jnp.roll(u, -1, 2))
        s = s / sixth
        return jnp.where(in_hot, one, jnp.where(in_cold, zero, s))

    return lax.fori_loop(0, steps, step, t)


def advance(state, steps: int, cfg, dtype=jnp.float32):
    """``steps`` steps from ``state`` (consumed) computed in ``dtype``;
    returns float32 fields."""
    return {"temp": _advance(state["temp"], steps,
                             jnp.dtype(dtype)).astype(jnp.float32)}
