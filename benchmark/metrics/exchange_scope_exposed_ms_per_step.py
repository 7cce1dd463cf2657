"""The part of the scoped exchange's device time per step, averaged
over chips, in which no other op runs on that chip: exchange that
compute does not hide. Same rules as ``exchange_scope_ms_per_step``;
the scoped twin of ``exchange_exposed_ms_per_step``."""

UNIT, LAYER, MOVES, SOURCE = "ms/step", "exchange", \
    "gcell_updates_per_s", "device_trace"

RULES = {"include": {"tf_op": [r"(^|/)stencil\.exchange/"]}}


def reduce(ctx):
    return ctx.ms_per_step(RULES, exposed=True)
