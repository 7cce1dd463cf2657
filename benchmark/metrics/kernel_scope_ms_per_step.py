"""Device time of the stencil kernels per step, averaged over chips,
read from the program's own names: the union of the intervals of the
ops whose ``tf_op`` lies under the ``stencil.kernel`` named scope (every
Pallas call, named after its function, and the XLA path's stencil and
Runge-Kutta arithmetic). The scoped twin of ``kernel_ms_per_step``,
which reads HLO shapes. None where no op carries the scope, as in a
program that opens none."""

UNIT, LAYER, MOVES, SOURCE = "ms/step", "kernels", "gcell_updates_per_s", \
    "device_trace"

RULES = {"include": {"tf_op": [r"(^|/)stencil\.kernel/"]}}


def reduce(ctx):
    return ctx.ms_per_step(RULES)
