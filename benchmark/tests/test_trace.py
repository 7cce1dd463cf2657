"""The reduction from a device trace to the per-layer metrics: exact
values on a hand-made trace, the interval arithmetic against a
brute-force count, and pinned values on a small trace recorded on the
chip (``fixtures/``, cut from runs of PR 22)."""

import json
from pathlib import Path

import pytest

from benchmark.harness import layers, spec, trace
from benchmark.tests.helpers import REPO

FIX = Path(__file__).resolve().parent / "fixtures"
KERN = ('%body.1 = f32[256,256,256]{2,1,0} custom-call(f32[256,256,256]'
        '{2,1,0} %p), custom_call_target="tpu_custom_call"')
FUS = ('%fusion.2 = f32[256,256,256]{2,1,0} fusion(f32[262,262,262]{2,1,0} '
       '%p)')
HALO = ('%dynamic-update-slice.3 = f32[262,262,262]{2,1,0} dynamic-update-'
        'slice(f32[262,262,262]{2,1,0} %a, f32[262,262,3]{2,1,0} %b)')
PERM = '%collective-permute-done.4 = f32[100]{0} collective-permute-done()'
LOOP = ('%while.5 = (s32[], f32[256,256,256]{2,1,0}) while((s32[], '
        'f32[256,256,256]) %t)')


def ev(name, t0, t1, cat):
    return {"name": name, "t0": t0, "t1": t1, "hlo_category": cat}


def hand_trace():
    """Window 0-1000 ns, 2 steps, 2 chips. Chip 0: kernel 100-400,
    halo 350-500 (50 ns under the kernel), permute 600-700, loop
    0-1000 (a container, not counted). Chip 1: kernel 0-200, fusion
    300-600, permute 550-650 (50 ns under the fusion)."""
    return trace.Trace(
        {0: [ev(KERN, 100, 400, "custom-call"),
             ev(HALO, 350, 500, "dynamic-update-slice"),
             ev(PERM, 600, 700, "collective-permute-done")],
         1: [ev(KERN, 0, 200, "custom-call"),
             ev(FUS, 300, 600, "loop fusion"),
             ev(PERM, 550, 650, "collective-permute-done")]},
        [("bench.window", 0, 1000), ("bench.run", 0, 50),
         ("bench.block", 50, 990)])


def context(tr, cell="astaroth-256.4chip-weak", steps=2,
            info=None):
    bench = spec.Benchmark(REPO)
    return layers.Context(tr, bench.cell(cell),
                          info or {"kernel_path": "xla"}, steps, bench,
                          "TPU v5 lite")


def metric(name):
    return spec.Benchmark(REPO).module("metrics", name)


def test_opcode_and_containers():
    assert trace.opcode(KERN) == "custom-call"
    assert trace.opcode(HALO) == "dynamic-update-slice"
    assert trace.opcode(LOOP) == "while"
    assert trace.opcode("not an op") == ""
    assert trace.short(KERN) == "%body.1 custom-call[tpu_custom_call]"


def test_hand_trace_values():
    ctx = context(hand_trace())
    # kernel: chip 0 300 ns, chip 1 200 + 300 ns -> 400 ns / 2 steps
    assert metric("kernel_ms_per_step").reduce(ctx) == \
        pytest.approx(400 / 2 / 1e6)
    # exchange: chip 0 150 + 100, chip 1 100 -> 175 ns / 2 steps
    assert metric("exchange_ms_per_step").reduce(ctx) == \
        pytest.approx(175 / 2 / 1e6)
    # exposed: chip 0 100 + 100, chip 1 50 -> 125 ns / 2 steps
    assert metric("exchange_exposed_ms_per_step").reduce(ctx) == \
        pytest.approx(125 / 2 / 1e6)
    # busy: chip 0 100-500, 600-700 = 500; chip 1 0-200, 300-650 = 550
    assert ctx.busy_s() == pytest.approx(525e-9)
    assert ctx.window_s() == pytest.approx(1000e-9)
    assert metric("device_idle_pct").reduce(ctx) == pytest.approx(47.5)
    b = ctx.breakdown()
    assert b["device_ops"][0] == ["%body.1 custom-call[tpu_custom_call]",
                                  pytest.approx(250e-9)]
    assert b["idle_gaps"][0] == ["chip1:bench.block", pytest.approx(350e-9)]
    assert ["chip0:bench.run", pytest.approx(100e-9)] in b["idle_gaps"]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_kernel_hbm_pct_from_bytes_file():
    tr = trace.Trace({0: [ev(KERN, 0, 4_000_000, "custom-call")]},
                     [("bench.window", 0, 5_000_000)])
    ctx = context(tr, "jacobi3d-512.1chip", steps=2,
                  info={"kernel_path": "wrap", "step_stride": 2})
    # 2^30 B per pair call = 2 steps; 2 ms per step
    want = 100 * (2**29) / 2e-3 / 819e9
    assert metric("kernel_hbm_pct").reduce(ctx) == pytest.approx(want)
    # no bytes file for the XLA path: no share, never a zero
    ctx = context(tr, "astaroth-256.4chip-weak", steps=2)
    assert metric("kernel_hbm_pct").reduce(ctx) is None


def test_nothing_to_read_gives_nothing():
    tr = trace.Trace({0: [ev(FUS, 0, 10, "loop fusion")]},
                     [("bench.window", 0, 100)])
    ctx = context(tr)
    assert metric("exchange_ms_per_step").reduce(ctx) is None
    assert metric("exchange_exposed_ms_per_step").reduce(ctx) is None


def test_interval_arithmetic_against_brute_force():
    import random

    rng = random.Random(5)
    for _ in range(50):
        a = [(s, s + rng.randint(1, 30)) for s in
             (rng.randint(0, 200) for _ in range(8))]
        b = [(s, s + rng.randint(1, 30)) for s in
             (rng.randint(0, 200) for _ in range(5))]
        cover_a = {t for x, y in a for t in range(x, y)}
        cover_b = {t for x, y in b for t in range(x, y)}
        assert trace.length(a) == len(cover_a)
        assert trace.length(trace.subtract(a, b)) == len(cover_a - cover_b)


@pytest.mark.parametrize("name", sorted(p.stem for p in FIX.glob("*.json")))
def test_recorded_trace(name):
    d = json.loads((FIX / f"{name}.json").read_text())
    tr = trace.Trace.from_json(d["trace"])
    ctx = context(tr, d["cell"], d["steps"], d["info"])
    for m, want in d["expect"].items():
        got = (metric(m).reduce(ctx) if m not in ("busy_s", "window_s")
               else getattr(ctx, m)())
        assert got == pytest.approx(want, rel=1e-9), m
