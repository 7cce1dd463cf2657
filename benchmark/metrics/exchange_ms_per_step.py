"""Device time of the halo exchange per step, averaged over chips: the
union of the intervals of the collective transfers and of the ops that
pack, copy or write halo slabs. The program opens no named scope
around its exchange yet, so the rules read the ops' HLO: a halo slab
is a 3-D array with one dimension of 1-7 cells (a radius, or two), a
packed wire buffer a 1-D array."""

UNIT, LAYER, MOVES, SOURCE = "ms/step", "exchange", \
    "gcell_updates_per_s", "device_trace"

RULES = {
    "include": {
        "hlo_category": [r"^collective-permute", r"^all-to-all",
                         r"^all-gather", r"^send", r"^recv"],
        "name": [r"^%\S+ = \w+\[(\d,\d+,\d+|\d+,\d,\d+|\d+,\d+,\d)\]",
                 r"dynamic-update-slice\(\w+\[\d+,\d+,\d+\]\S* %\S+, "
                 r"\w+\[(\d,\d+,\d+|\d+,\d,\d+|\d+,\d+,\d)\]",
                 r"^%\S+ = \w+\[\d+\]\{0"],
    },
    "exclude": {"name": [r'custom_call_target="tpu_custom_call"']},
}


def reduce(ctx):
    return ctx.ms_per_step(RULES)
