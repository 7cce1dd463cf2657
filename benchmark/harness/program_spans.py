"""The program's own host spans (``stencil.run``, ``stencil.block``, ...)
read from a profile, and what they say about the trace: which device
ops belong to which chunk, and how far the host and device clocks
agree.

A profile's host events and device ops share one clock, the
nanoseconds since the session started, so a chunk's device ops must
start after its ``stencil.run`` span starts (the host dispatched them)
and end before its ``stencil.block`` span ends (the host saw them
finish). The smallest margin over a run's chunks is the clock slack; a
negative one is the clocks' offset, and bounds how well an idle gap can
be put down to a host span."""

import bisect
import glob

from benchmark.harness import trace

PROGRAM_PREFIX = "stencil."


def load(xplane_path: str) -> list:
    """[(name, t0, t1)] of the program's host spans in one
    ``.xplane.pb`` (or the one under a trace directory), sorted by
    start."""
    from jax.profiler import ProfileData

    if not xplane_path.endswith(".xplane.pb"):
        xplane_path = glob.glob(f"{xplane_path}/**/*.xplane.pb",
                                recursive=True)[0]
    spans = []
    for plane in ProfileData.from_file(xplane_path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans.extend((e.name, e.start_ns, e.end_ns) for e in line.events
                         if e.name.startswith(PROGRAM_PREFIX))
    spans.sort(key=lambda s: s[1])
    return spans


def chunks(spans, prefix: str = PROGRAM_PREFIX) -> list:
    """[(run span, block span)] in order: each ``<prefix>run`` with the
    first ``<prefix>block`` that starts after it ends (the harness's
    own pair with ``prefix="bench."``)."""
    runs = [s for s in spans if s[0] == f"{prefix}run"]
    blocks = [s for s in spans if s[0] == f"{prefix}block"]
    out, j = [], 0
    for r in runs:
        while j < len(blocks) and blocks[j][1] < r[2]:
            j += 1
        if j == len(blocks):
            break
        out.append((r, blocks[j]))
        j += 1
    return out


def split_ops(evs, ch, reach: float = 5e6) -> list:
    """One chip's ops (``[event]``) split by chunk (``chunks(...)``):
    chunk i and i + 1 part in the middle of the longest device idle
    stretch within ``reach`` ns of the host's boundary between them
    (chunk i's block end, chunk i + 1's run start). The device is idle
    between chunks for the host's dispatch and wake-up, so the split
    does not lean on the two clocks agreeing. Ops further than
    ``reach`` outside the chunks are left out."""
    busy = trace.union((e["t0"], e["t1"]) for e in evs)
    idle = [(a[1], b[0]) for a, b in zip(busy, busy[1:])]
    cuts = []
    for (_, b0), (r1, _) in zip(ch, ch[1:]):
        near = [g for g in idle
                if g[1] > b0[2] - reach and g[0] < r1[1] + reach]
        g = max(near, key=lambda g: g[1] - g[0], default=(b0[2], r1[1]))
        cuts.append((g[0] + g[1]) / 2)
    lo, hi = ch[0][0][1] - reach, ch[-1][1][2] + reach
    out = [[] for _ in ch]
    for e in evs:
        mid = (e["t0"] + e["t1"]) / 2
        if lo <= mid <= hi:
            out[bisect.bisect_right(cuts, mid)].append(e)
    return out


def clock_slack(tr, spans=None) -> dict:
    """Per chip, the smallest margin (ns) over the chunks by which the
    first device op follows its ``stencil.run`` start (``start``) and
    the last one precedes its ``stencil.block`` end (``end``), with the
    number of chunks that had ops. A negative margin is an offset
    between the host and device clocks: the two margins of one chunk
    move by it in opposite directions, so their sum does not."""
    ch = chunks(spans if spans is not None else tr.spans)
    if not ch:
        return {}
    out = {}
    for d, evs in tr.ops.items():
        per = [(r[1], b[2], ops) for (r, b), ops
               in zip(ch, split_ops(evs, ch)) if ops]
        if per:
            out[d] = {"start": min(min(e["t0"] for e in ops) - t0
                                   for t0, _, ops in per),
                      "end": min(t1 - max(e["t1"] for e in ops)
                                 for _, t1, ops in per),
                      "chunks": len(per)}
    return out
