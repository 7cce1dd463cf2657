"""Cut a recorded-trace fixture (``benchmark/tests/fixtures/``) from a
trace saved by ``run.py --dump-trace``: chunks ``first..last`` of the
window, the device ops of the first ``chips`` chips, the harness's and
the program's host spans, and the value of every per-layer metric of
the cell on the cut (``expect``). Also prints, for the whole saved
window, the clock slack between the program's spans and the device ops
and the longest idle gaps labelled by the innermost host span.

    python benchmark/tools/cut_fixture.py <trace.json> <cell> <first> \
        <last> <out.json> '<info json>' [chips]
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

#: longest op name kept, except for Pallas calls (their rule reads the
#: custom-call target at the end of the text)
NAME_CHARS = 400


def cut(tr, first: int, last: int, chips: int):
    from benchmark.harness import program_spans, trace

    every = program_spans.chunks(tr.spans)
    ch = every[first:last + 1]
    if not ch:
        raise ValueError("no program chunks in that range")
    lo, hi = ch[0][0][1], ch[-1][1][2]
    ops = {}
    for d in sorted(tr.ops)[:chips]:
        split = program_spans.split_ops(tr.ops[d], every)[first:last + 1]
        ops[d] = [dict(e, name=e["name"] if "tpu_custom_call" in e["name"]
                       else e["name"][:NAME_CHARS])
                  for part in split for e in part]
    spans = [("bench.window", lo, hi)] + [
        s for s in tr.spans if s[0] != "bench.window" and s[2] > lo
        and s[1] < hi]
    return trace.Trace(ops, spans), len(ch)


def main(argv) -> int:
    from pathlib import Path

    from benchmark.harness import (layers, program_spans, spec, trace,
                                   window)

    path, cell_name, first, last, out = argv[:5]
    info = json.loads(argv[5])
    chips = int(argv[6]) if len(argv) > 6 else 1
    bench = spec.Benchmark(Path(__file__).resolve().parents[2])
    cell = bench.cell(cell_name)
    tr = trace.load_dump(path)
    tr.spans = sorted(tr.spans + program_spans.load(path + ".xplane.pb"),
                      key=lambda s: s[1])
    print("clock slack ns (whole window):",
          json.dumps(program_spans.clock_slack(tr)))
    ctx = layers.Context(tr, cell, info, 1, bench, "TPU v5 lite")
    print("idle gaps (whole window):",
          json.dumps(ctx.breakdown(top=20)["idle_gaps"]))
    chunk_s = [(b[2] - r[1]) / 1e9
               for r, b in program_spans.chunks(tr.spans, "bench.")]
    if chunk_s:
        print(f"traced chunk_ms_p90 {window.p90(chunk_s) * 1e3!r} over "
              f"{len(chunk_s)} chunks")

    small, n = cut(tr, int(first), int(last), chips)
    steps = n * int(cell.traffic["steps_per_chunk"])
    ctx = layers.Context(small, cell, info, steps, bench, "TPU v5 lite")
    expect = {}
    for m in cell.per_layer:
        v = bench.module("metrics", m["name"]).reduce(ctx)
        if v is not None:
            expect[m["name"]] = v
    expect["busy_s"], expect["window_s"] = ctx.busy_s(), ctx.window_s()
    d = {"source": f"cut from a chip trace ({os.path.basename(path)}), "
                   f"chunks {first}..{last}, {len(small.ops)} chips, op "
                   f"names other than Pallas calls cut to {NAME_CHARS} "
                   f"characters",
         "cell": cell_name, "steps": steps, "info": info, "expect": expect,
         "trace": small.to_json()}
    with open(out, "w") as f:
        json.dump(d, f)
    print(json.dumps(expect, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
