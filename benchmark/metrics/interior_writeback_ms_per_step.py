"""Device time per step, averaged over chips, of moving the interior
between the padded field and the arrays the kernels step: the ops whose
``tf_op`` lies under the ``stencil.interior`` named scope (the slice of
the interior out of the padded field and its ``dynamic_update_slice``
back, and on the XLA path the Runge-Kutta update's write into the
padded field). None where no op carries the scope."""

UNIT, LAYER, MOVES, SOURCE = "ms/step", "domain", \
    "gcell_updates_per_s", "device_trace"

RULES = {"include": {"tf_op": [r"(^|/)stencil\.interior/"]}}


def reduce(ctx):
    return ctx.ms_per_step(RULES)
