"""The halo exchange's rate on the interconnect, per chip: the bytes a
chip receives per step over the exchange's device time per step.

The bytes are read from the program's own transfers: each op under the
``stencil.exchange`` named scope that is a ``collective-permute``, or
the ``collective-permute-done`` that ends an asynchronous one, counts
the result shape in its HLO text, every array of a combined (tuple)
result; the ``-start`` is skipped, so no transfer counts twice. The
time is the union of the intervals of all ops under the scope, as
``exchange_scope_ms_per_step`` reads it. On a (1, 1, 4) mesh each chip
exchanges with its two z neighbours, so this is one chip's rate over
its two links together. None where no transfer runs under the scope."""

import re

from benchmark.harness import trace

UNIT, LAYER, MOVES, SOURCE = "GB/s", "exchange", "gcell_updates_per_s", \
    "device_trace"

RULES = {"include": {"tf_op": [r"(^|/)stencil\.exchange/"]}}
TRANSFERS = ("collective-permute", "collective-permute-done")


def result_bytes(name: str) -> int:
    """Bytes of every array in the result shape of the HLO instruction
    ``name`` (``%x = <shape> opcode(...)``)."""
    m = re.match(r"%\S+ = (.*?) " + re.escape(trace.opcode(name)) + r"\(",
                 name)
    total = 0
    for dtype, dims in re.findall(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]",
                                  m.group(1)):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        bits = 8 if dtype == "pred" else int(re.search(r"\d+", dtype)[0])
        total += n * bits // 8
    return total


def wire_bytes_per_step(ctx):
    """Bytes received per step, averaged over chips; None where some
    chip has no transfer under the scope or no step ran."""
    if not ctx.ops or ctx.steps <= 0:
        return None
    # an op's name is its HLO text and fixes its stats: one verdict a name
    counted = {}

    def nbytes(e):
        if e["name"] not in counted:
            counted[e["name"]] = (
                result_bytes(e["name"])
                if trace.opcode(e["name"]) in TRANSFERS
                and trace.matches(e, RULES) else 0)
        return counted[e["name"]]

    per_dev = []
    for evs in ctx.ops.values():
        n = sum(nbytes(e) for e in evs
                if "collective-permute" in e["name"])
        if not n:
            return None
        per_dev.append(n)
    return sum(per_dev) / len(per_dev) / ctx.steps


def reduce(ctx):
    nbytes = wire_bytes_per_step(ctx)
    ms = ctx.ms_per_step(RULES)
    if not nbytes or not ms:
        return None
    return nbytes / (ms / 1e3) / 1e9
