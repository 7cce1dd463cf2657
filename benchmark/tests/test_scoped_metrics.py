"""The per-layer metrics that read the program's own names (the
``stencil.*`` named scopes in each op's ``tf_op``, and the host spans):
exact values on a hand-made trace, nothing on the recorded traces of a
program that names nothing (the fixtures with no ``stencil.*`` span),
and the clock slack between the program's spans and the device ops on
the traces recorded from a program that does."""

import json

import pytest

from benchmark.harness import program_spans, trace
from benchmark.tests.test_trace import FIX, context, ev, metric

SCOPED = ("kernel_scope_ms_per_step", "exchange_scope_ms_per_step",
          "exchange_scope_exposed_ms_per_step",
          "interior_writeback_ms_per_step", "unscoped_ms_per_step")
BODY = "jit(shard_iters)/shard_map/while/body/"


def op(name, t0, t1, scope=None):
    e = ev(f"%{name} = f32[8]{{0}} fusion()", t0, t1, "loop fusion")
    if scope is not None:
        e["tf_op"] = f"{BODY}{scope}/{name}:" if scope else f"{BODY}{name}:"
    return e


def scoped_trace():
    """Window 0-1000 ns, 2 steps, 2 chips. Chip 0: kernel 100-400,
    exchange 350-500 (50 ns under the kernel), exchange 600-700, a
    copy with no tf_op 700-800, interior 800-900. Chip 1: kernel 0-200
    and 300-600, exchange 550-650 (50 ns under the kernel), an op
    with an unscoped tf_op 900-950."""
    return trace.Trace(
        {0: [op("kern.1", 100, 400, "stencil.kernel/jacobi7_wrapn"),
             op("slab.2", 350, 500, "stencil.exchange"),
             op("perm.3", 600, 700, "stencil.exchange"),
             op("copy.4", 700, 800),
             op("dus.5", 800, 900, "stencil.interior")],
         1: [op("kern.1", 0, 200, "stencil.kernel/jacobi7_wrapn"),
             op("rates.6", 300, 600, "stencil.kernel"),
             op("perm.3", 550, 650, "stencil.exchange"),
             op("loop.7", 900, 950, "")]},
        [("bench.window", 0, 1000), ("bench.run", 0, 50),
         ("bench.block", 50, 990), ("stencil.run", 5, 45),
         ("stencil.block", 55, 985)])


def test_scoped_trace_values():
    ctx = context(scoped_trace())
    per_step = {
        # chip 0 300 ns, chip 1 200 + 300 ns -> 400 ns / 2 steps
        "kernel_scope_ms_per_step": 400 / 2,
        # chip 0 150 + 100, chip 1 100 -> 175 ns / 2 steps
        "exchange_scope_ms_per_step": 175 / 2,
        # chip 0 100 + 100, chip 1 50 -> 125 ns / 2 steps
        "exchange_scope_exposed_ms_per_step": 125 / 2,
        # chip 0 100, chip 1 none: a chip with nothing to read -> None
        "interior_writeback_ms_per_step": None,
        # chip 0 the copy 100, chip 1 the unscoped op 50 -> 75 / 2
        "unscoped_ms_per_step": 75 / 2,
    }
    for name, ns in per_step.items():
        want = None if ns is None else pytest.approx(ns / 1e6)
        assert metric(name).reduce(ctx) == want, name


def test_unscoped_reads_zero_when_everything_is_scoped():
    tr = trace.Trace({0: [op("kern.1", 0, 10, "stencil.kernel")]},
                     [("bench.window", 0, 100)])
    assert metric("unscoped_ms_per_step").reduce(context(tr)) == 0.0


def unnamed_fixtures():
    return sorted(p.stem for p in FIX.glob("*.json")
                  if not program_spans.chunks(
                      json.loads(p.read_text())["trace"]["spans"]))


def named_fixtures():
    return sorted(p.stem for p in FIX.glob("*.json")
                  if program_spans.chunks(
                      json.loads(p.read_text())["trace"]["spans"]))


def load(name):
    d = json.loads((FIX / f"{name}.json").read_text())
    return d, trace.Trace.from_json(d["trace"])


@pytest.mark.parametrize("name", unnamed_fixtures())
def test_program_that_names_nothing_gives_nothing(name):
    """A program with no layer scopes leaves every scoped
    metric out of the result line rather than reading 0 or the whole
    busy time."""
    d, tr = load(name)
    ctx = context(tr, d["cell"], d["steps"], d["info"])
    for m in SCOPED:
        assert metric(m).reduce(ctx) is None, m


def test_chunks_pair_each_run_with_its_block():
    spans = [("stencil.run", 0, 10), ("stencil.block", 12, 50),
             ("stencil.run", 52, 60), ("stencil.block", 61, 99),
             ("stencil.run", 100, 110)]
    assert program_spans.chunks(spans) == [
        (spans[0], spans[1]), (spans[2], spans[3])]


def test_clock_slack_on_hand_trace():
    tr = scoped_trace()
    slack = program_spans.clock_slack(tr)
    # one chunk: chip 0's ops span 100-900, chip 1's 0-950 inside the
    # run span 5-45 and block span 55-985
    assert slack[0] == {"start": 95, "end": 85, "chunks": 1}
    assert slack[1] == {"start": -5, "end": 35, "chunks": 1}


#: how far a profile's device clock may run ahead of its host clock:
#: on a v5e the Jacobi trace's ops start up to 0.91 ms before the
#: ``stencil.run`` span that dispatched them (PERF.md, Findings), so a
#: host span places a device gap only to within this
CLOCK_OFFSET_NS = 1_000_000


@pytest.mark.parametrize("name", named_fixtures())
def test_recorded_clock_slack(name):
    """On a chip trace of a program that opens its spans, every chunk's
    device ops lie inside its ``stencil.run`` start and ``stencil.block``
    end, on every recorded chip, to within the clocks' offset; the two
    margins together, which the offset does not move, are positive."""
    d, tr = load(name)
    slack = program_spans.clock_slack(tr)
    assert slack and set(slack) == set(tr.ops)
    for dev, s in slack.items():
        assert s["chunks"] == len(program_spans.chunks(tr.spans))
        assert min(s["start"], s["end"]) >= -CLOCK_OFFSET_NS, (dev, s)
        assert s["start"] + s["end"] > 0, (dev, s)
