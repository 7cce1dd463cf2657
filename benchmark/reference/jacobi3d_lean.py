"""Plain reference of the Jacobi heat solver for a grid whose check has
to fit one chip beside the state it checks: the semantics and the
arithmetic of ``reference/jacobi3d.py`` (the same seeded start, the six
neighbours summed in the same order, the hot and cold spheres re-imposed
after every step), with each step reading its neighbours from one
periodically padded copy of the grid (``jnp.pad(mode="wrap")``) where
that file rolls six. Compiled for a v5e at 750^3, the rolled form holds
10.98 GB of temporaries and this one 1.73 GB."""

import functools

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference.jacobi3d import (FIELDS, _spheres,  # noqa: F401
                                          initial_field, initial_state)


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
        p = jnp.pad(u, 1, mode="wrap")

        def neighbour(axis, d):
            at = [slice(1, n + 1) for n in u.shape]
            at[axis] = slice(1 + d, u.shape[axis] + 1 + d)
            return p[tuple(at)]

        s = (neighbour(0, -1) + neighbour(0, 1) + neighbour(1, -1)
             + neighbour(1, 1) + neighbour(2, -1) + neighbour(2, 1))
        s = s / sixth
        return jnp.where(in_hot, one, jnp.where(in_cold, zero, s))

    return lax.fori_loop(0, steps, step, t)


def advance(state, steps: int, cfg, dtype=jnp.float32):
    """``steps`` steps from ``state`` (consumed) computed in ``dtype``;
    returns float32 fields."""
    return {"temp": _advance(state["temp"], steps,
                             jnp.dtype(dtype)).astype(jnp.float32)}
