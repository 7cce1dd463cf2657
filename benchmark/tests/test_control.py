"""The control, at a size a test run can hold: the reference computed
in bfloat16, in the program's place, fails the limit that the program
itself passes (on the chip at the cells' own sizes:
``benchmark/controls.py``, readings in PERF.md)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import compare, runner, spec
from benchmark.tests.helpers import make_checkout


@pytest.mark.parametrize("cell", ["jacobi3d-16.1chip",
                                  "astaroth-16.4chip-weak"])
def test_bfloat16_control_fails_the_limit(tmp_path, cell):
    bench = spec.Benchmark(make_checkout(tmp_path))
    c = bench.cell(cell)
    ref = bench.module("families", c.config["family"]).reference
    steps = runner.CHECK_CHUNKS * int(c.traffic["steps_per_chunk"])
    dev = jax.devices()[0]
    gzyx = c.global_zyx()
    limit = c.config["limits"]["gap_over_change"]
    for seed in (11, 2**31 + 5):
        init = ref.initial_state(seed, gzyx, dev)
        want = ref.advance(ref.initial_state(seed, gzyx, dev), steps,
                           c.config)
        low = ref.advance(ref.initial_state(seed, gzyx, dev), steps,
                          c.config, dtype=jnp.bfloat16)
        got = {q: np.asarray(v) for q, v in low.items()}
        assert compare.gap_over_change(got, want, init) > 10 * limit
