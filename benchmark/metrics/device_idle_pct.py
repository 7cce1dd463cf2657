"""Device idle share of the traced window: 1 - (union of the intervals
in which any operation runs on a chip) / window, averaged over chips."""

UNIT, LAYER, MOVES, SOURCE = "%", "device", "gcell_updates_per_s", \
    "device_trace"


def reduce(ctx):
    w = ctx.window_s()
    if w <= 0 or not ctx.ops:
        return None
    return 100.0 * (1.0 - ctx.busy_s() / w)
