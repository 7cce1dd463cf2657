"""The part of the halo exchange's device time per step, averaged over
chips, in which no other op runs on that chip: exchange that compute
does not hide. Same rules as ``exchange_ms_per_step``."""

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
    return ctx.ms_per_step(RULES, exposed=True)
