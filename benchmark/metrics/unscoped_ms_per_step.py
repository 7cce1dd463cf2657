"""Device time per step, averaged over chips, of the ops under none of
the program's layer scopes (``stencil.kernel``, ``stencil.exchange``,
``stencil.interior``): what XLA adds on its own, such as the copies of
a loop's carried state, and ops with no ``tf_op`` at all. Zero where
every op is scoped; None where no op carries a layer scope, as in a
program that opens none (everything would read as unscoped)."""

from benchmark.harness import trace

UNIT, LAYER, MOVES, SOURCE = "ms/step", "device", "gcell_updates_per_s", \
    "device_trace"

SCOPED = {"include": {"tf_op": [r"(^|/)stencil\.(kernel|exchange|"
                                r"interior)/"]}}


def reduce(ctx):
    if not ctx.ops or ctx.steps <= 0:
        return None
    if not any(ctx.intervals(d, SCOPED) for d in ctx.ops):
        return None
    per_dev = [trace.length(ctx.intervals(d, SCOPED, invert=True))
               for d in ctx.ops]
    return sum(per_dev) / len(per_dev) / ctx.steps / 1e6
