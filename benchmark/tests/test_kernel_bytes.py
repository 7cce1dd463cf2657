"""Each kernel's bytes against a hand count at the cells' sizes."""

from benchmark.harness import spec
from benchmark.tests.helpers import REPO

BENCH = spec.Benchmark(REPO)


def test_jacobi_pair_kernel_512():
    kb = BENCH.module("kernel_bytes", "jacobi3d_wrap")
    # one f32 512^3 array is 2^27 cells * 4 B = 512 MiB; read + write
    assert kb.bytes_per_call(512, 512, 512) == 2 * 512 ** 3 * 4 == 2 ** 30
    cell = BENCH.cell("jacobi3d-512.1chip")
    assert kb.bytes_per_step(cell, {"step_stride": 2}) == 2 ** 29
    assert kb.bytes_per_step(cell, {"step_stride": 1}) == 2 ** 30


def test_mhd_wrap_megakernel_256():
    kb = BENCH.module("kernel_bytes", "astaroth_wrap")
    a = 256 ** 3 * 4                     # one f32 field, 64 MiB
    assert kb.bytes_per_call(0, 256, 256, 256) == 24 * a
    assert kb.bytes_per_call(1, 256, 256, 256) == 32 * a
    assert kb.bytes_per_call(2, 256, 256, 256) == 24 * a
    cell = BENCH.cell("astaroth-256.1chip")
    assert kb.bytes_per_step(cell, {}) == 80 * a == 5368709120
