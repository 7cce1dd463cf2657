"""The comparison that decides ``correct``: the program's state after
the checked chunks against the plain reference's, from the same seeded
start, as a share of how far the reference moved the state."""

import jax.numpy as jnp
import numpy as np


def gap_over_change(got: dict, ref: dict, init: dict) -> float:
    """max over fields and cells of |program - reference|, over max
    over fields and cells of |reference - start|. A program that leaves
    the state unchanged reads exactly 1. One scale for all fields: in
    float32 at the source's time step some fields move by a few ulps
    only, and a share of so small a change is rounding, not a fault."""
    change = max(float(jnp.max(jnp.abs(ref[q] - init[q]))) for q in ref)
    gap = 0.0
    for q in ref:
        want = np.asarray(ref[q], np.float32)
        have = np.asarray(got[q], np.float32)
        if have.shape != want.shape:
            return float("inf")
        d = np.abs(have - want)
        if not np.isfinite(d).all():
            return float("inf")
        gap = max(gap, float(d.max()))
    return gap / change if change > 0 else float("inf")
