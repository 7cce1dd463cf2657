"""``kernel_pad_pct``: the cells the Pallas calls under ``stencil.kernel``
step beyond the chip's share of the grid, read from their result
shapes, on a hand-made trace and on recorded one-chip traces: a kernel
that steps the grid as it is, and one that steps a padded extent."""

import json

import pytest

from benchmark.harness import trace
from benchmark.tests.test_trace import FIX, context, metric

SCOPE = "jit(steps)/while/body/stencil.kernel/jacobi7_wrapn/pallas_call:"


def call(shape, t0, t1, tf_op=SCOPE):
    e = {"name": f'%jacobi7_wrapn.8 = {shape} custom-call(f32[1]{{0}} '
                 f'%p), custom_call_target="tpu_custom_call"',
         "t0": t0, "t1": t1, "hlo_category": "custom-call"}
    if tf_op:
        e["tf_op"] = tf_op
    return e


def test_padded_result_shapes():
    """Two calls on a 750 x 760 x 750 stored extent (750^3 logical), a
    tuple-shaped call stepping the grid as it is, and calls that do not
    count: one outside the scope, one that is no Pallas call."""
    pad = "f32[750,760,750]{2,1,0:T(8,128)}"
    tr = trace.Trace(
        {0: [call(pad, 0, 10), call(pad, 20, 30),
             call("(f32[750,750,750]{2,1,0}, f32[750,750,750]{2,1,0})",
                  40, 50),
             call(pad, 60, 70, tf_op="jit(steps)/stencil.interior/pad:"),
             dict(call(pad, 80, 90), name="%fusion.1 = "
                  "f32[750,760,750]{2,1,0} fusion(%p)")]},
        [("bench.window", 0, 100)])
    ctx = context(tr, "jacobi3d-750.1chip", steps=2,
                  info={"kernel_path": "wrap", "step_stride": 2})
    want = 100 * ((2 * 760 + 2 * 750) / (4 * 750) - 1)
    assert metric("kernel_pad_pct").reduce(ctx) == pytest.approx(want)


def test_nothing_to_read_gives_nothing():
    tr = trace.Trace({0: [call("f32[8]{0}", 0, 10, tf_op="")]},
                     [("bench.window", 0, 100)])
    ctx = context(tr, "jacobi3d-750.1chip", steps=2,
                  info={"kernel_path": "wrap", "step_stride": 2})
    assert metric("kernel_pad_pct").reduce(ctx) is None


@pytest.mark.parametrize("name,want", [
    ("jacobi3d-512.1chip.scoped", 0.0),
    # the pair kernel on the 750 x 760 x 750 stored extent
    ("jacobi3d-750.1chip.scoped", 100 * (760 / 750 - 1)),
])
def test_recorded_traces(name, want):
    d = json.loads((FIX / f"{name}.json").read_text())
    ctx = context(trace.Trace.from_json(d["trace"]), d["cell"], d["steps"],
                  d["info"])
    assert metric("kernel_pad_pct").reduce(ctx) == pytest.approx(want)
