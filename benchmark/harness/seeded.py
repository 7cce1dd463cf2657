"""Initial state from ``--seed``: a counter-based hash of the global
cell index, so any block of the grid (one chip's padded shard, a
reference's z-block) gets the same values without the whole grid
being made in one place. A uniform field cannot expose an exchange
that reads the wrong neighbour, so every field varies cell to cell."""

import jax.numpy as jnp
import numpy as np

_M1, _M2 = 0x85EBCA6B, 0xC2B2AE35
_MASK = 0xFFFFFFFF


def _fmix32_int(h: int) -> int:
    h &= _MASK
    h ^= h >> 16
    h = (h * _M1) & _MASK
    h ^= h >> 13
    h = (h * _M2) & _MASK
    h ^= h >> 16
    return h


def stream_key(seed: int, stream: int) -> np.uint32:
    """32-bit key of one field's stream; ``seed`` may exceed 32 bits."""
    seed %= 1 << 64
    lo, hi = seed & _MASK, seed >> 32
    return np.uint32(_fmix32_int(_fmix32_int(lo ^ 0x9E3779B9)
                                 ^ _fmix32_int(hi + 0x7F4A7C15)
                                 ^ _fmix32_int(stream * 0x632BE5AB + 1)))


def _fmix32(h):
    h = h ^ (h >> 16)
    h = h * jnp.uint32(_M1)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(_M2)
    return h ^ (h >> 16)


def uniform(key, gz, gy, gx, global_zyx):
    """U[0, 1) float32 at global cell coordinates. ``key`` is a uint32
    scalar, passed in as an argument so that one compiled program
    serves every seed; ``gz``/``gy``/``gx`` are int32 arrays that
    broadcast against each other, each within its axis' extent."""
    _, gy_n, gx_n = global_zyx
    if int(np.prod(global_zyx)) >= 1 << 32:
        raise ValueError(f"grid {global_zyx} exceeds 32-bit cell indices")
    idx = ((gz.astype(jnp.uint32) * jnp.uint32(gy_n)
            + gy.astype(jnp.uint32)) * jnp.uint32(gx_n)
           + gx.astype(jnp.uint32))
    key = jnp.asarray(key, jnp.uint32)
    h = _fmix32(_fmix32(idx ^ key) + key)
    return (h >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -24)


def interior_coords(global_zyx, z0: int = 0, nz=None):
    """Broadcastable global coordinates of the z-block [z0, z0 + nz)
    of the unpadded grid (z wraps, so a block may reach past either
    end)."""
    gz_n, gy_n, gx_n = global_zyx
    nz = gz_n if nz is None else nz
    gz = ((jnp.arange(nz, dtype=jnp.int32) + z0) % gz_n)[:, None, None]
    gy = jnp.arange(gy_n, dtype=jnp.int32)[None, :, None]
    gx = jnp.arange(gx_n, dtype=jnp.int32)[None, None, :]
    return gz, gy, gx


def padded_coords(global_zyx, mesh_zyx, local_zyx, lo_zyx, raw_zyx):
    """Global coordinates of every cell of a padded sharded layout, and
    which cells are interior: shard ``b`` of an axis holds ``raw``
    cells, of which ``lo`` halo cells come first."""
    coords, inside = [], None
    for a in range(3):
        p = jnp.arange(mesh_zyx[a] * raw_zyx[a], dtype=jnp.int32)
        o = p % raw_zyx[a] - lo_zyx[a]
        g = ((p // raw_zyx[a]) * local_zyx[a] + o) % global_zyx[a]
        shape = [1, 1, 1]
        shape[a] = -1
        coords.append(g.reshape(shape))
        m = ((o >= 0) & (o < local_zyx[a])).reshape(shape)
        inside = m if inside is None else inside & m
    return tuple(coords), inside
