"""The kernels' HBM traffic as a share of the chip's peak bandwidth:
the bytes the path's kernels must move per step (each blocked operand
read once, each result written once: a floor on real traffic, from
``kernel_bytes/<family>_<kernel path>.py``) over the Pallas calls'
device time per step, over the peak. None where the path has no bytes
file, as the XLA path has none yet (PERF.md, Open questions)."""

UNIT, LAYER, MOVES, SOURCE = "%", "kernels", "gcell_updates_per_s", \
    "device_trace"

RULES = {"include": {"name": [r'custom_call_target="tpu_custom_call"']}}


def reduce(ctx):
    kb = ctx.kernel_bytes()
    if kb is None:
        return None
    ms = ctx.ms_per_step(RULES)
    if ms is None:
        return None
    per_chip = kb.bytes_per_step(ctx.cell, ctx.info) / ctx.cell.chips
    return 100.0 * per_chip / (ms / 1e3) / ctx.peaks()["hbm_bytes_per_s"]
