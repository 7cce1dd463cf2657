"""Device time of the stencil kernels per step, averaged over chips:
the union of the intervals of every Pallas call (the wrap and halo
paths) and of every XLA fusion that is not halo work (the XLA path's
right-hand-side fusions). Copies, slices and the write-back of the
interior are left out, and show in the breakdown."""

UNIT, LAYER, MOVES, SOURCE = "ms/step", "kernels", "gcell_updates_per_s", \
    "device_trace"

RULES = {
    "include": {
        "name": [r'custom_call_target="tpu_custom_call"'],
        "hlo_category": [r"fusion$"],
    },
    "exclude": {
        "name": [r"^%\S+ = \w+\[(\d,\d+,\d+|\d+,\d,\d+|\d+,\d+,\d)\]",
                 r"^%\S+ = \w+\[\d+\]\{0"],
    },
}


def reduce(ctx):
    return ctx.ms_per_step(RULES)
