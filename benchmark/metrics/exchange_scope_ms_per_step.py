"""Device time of the halo exchange per step, averaged over chips, read
from the program's own names: the union of the intervals of the ops
whose ``tf_op`` lies under the ``stencil.exchange`` named scope (the
collective transfers, and the slicing, packing and writing of halo
slabs around them). The scoped twin of ``exchange_ms_per_step``, which
reads HLO shapes. None where no op carries the scope."""

UNIT, LAYER, MOVES, SOURCE = "ms/step", "exchange", \
    "gcell_updates_per_s", "device_trace"

RULES = {"include": {"tf_op": [r"(^|/)stencil\.exchange/"]}}


def reduce(ctx):
    return ctx.ms_per_step(RULES)
