"""How many more cells the stencil kernels step than the grid they
advance holds: the cells in the result shapes of the Pallas calls under
the ``stencil.kernel`` named scope, over the logical cells of a chip's
share of the grid (as many shares as a call has results), minus one, in
percent, over every such call of the window. A kernel that steps the
grid as it is reads 0; one that steps a stored extent padded up to its
tiles reads what the padding costs. None where no Pallas call carries
the scope."""

import re

from benchmark.harness import trace

UNIT, LAYER, MOVES, SOURCE = "%", "kernels", "gcell_updates_per_s", \
    "device_trace"

PALLAS = {"include": {"name": [r'custom_call_target="tpu_custom_call"']}}
SCOPED = {"include": {"tf_op": [r"(^|/)stencil\.kernel/"]}}
ARRAY = re.compile(r"[a-z][a-z0-9]*\[([0-9,]*)\]")


def result_cells(name: str):
    """(cells, arrays) of an op's result shape, read from its HLO text
    ``%name = <shape> opcode(...)``; a tuple shape counts each array."""
    shape = name.partition(" = ")[2].partition(" custom-call(")[0]
    cells = arrays = 0
    for dims in ARRAY.findall(shape):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        cells += n
        arrays += 1
    return cells, arrays


def reduce(ctx):
    logical = 1
    for v in ctx.cell.global_zyx():
        logical *= v
    logical /= ctx.cell.chips
    cells = shares = 0
    for evs in ctx.ops.values():
        for e in evs:
            if trace.matches(e, PALLAS) and trace.matches(e, SCOPED):
                c, a = result_cells(e["name"])
                cells += c
                shares += a
    if not shares:
        return None
    return 100.0 * (cells / (shares * logical) - 1.0)
