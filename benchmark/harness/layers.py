"""What a per-layer metric reads: the traced window's device
operations on each chip, the harness's host spans, the cell, and the
kernel path the program took. Each metric file holds its own
classification rules; this holds the arithmetic they share."""

import json
from collections import defaultdict

from benchmark.harness import device, trace


class Context:
    def __init__(self, tr, cell, info: dict, steps: int, bench,
                 device_kind: str):
        self.cell, self.info, self.steps = cell, info, steps
        self.bench, self.device_kind = bench, device_kind
        self.window = tr.window()
        lo, hi = self.window if self.window else (0, 0)
        self.spans = [s for s in tr.spans if s[0] != "bench.window"]
        self.ops = {d: [dict(e, t0=max(e["t0"], lo), t1=min(e["t1"], hi))
                        for e in evs if min(e["t1"], hi) > max(e["t0"], lo)]
                    for d, evs in tr.ops.items()}
        self._memo = {}

    def _matches(self, e, rules, rkey) -> bool:
        # an op's name is its HLO text and fixes its stats, so the
        # verdict is the same for every event of one name
        key = (rkey, e["name"])
        if key not in self._memo:
            self._memo[key] = trace.matches(e, rules)
        return self._memo[key]

    # -- shared arithmetic ------------------------------------------------
    def intervals(self, dev, rules=None, invert=False):
        """Intervals of the ops on chip ``dev`` that match ``rules``
        (all ops where None; those that do not match with ``invert``)."""
        rkey = json.dumps(rules, sort_keys=True)
        return [(e["t0"], e["t1"]) for e in self.ops[dev]
                if rules is None or self._matches(e, rules, rkey) != invert]

    def ms_per_step(self, rules, exposed=False):
        """Time covered by the ops matching ``rules``, per step,
        averaged over chips; with ``exposed``, only the part of it in
        which no other op runs. None where no op matches on some chip
        or no step ran."""
        if not self.ops or self.steps <= 0:
            return None
        per_dev = []
        for d in self.ops:
            hit = self.intervals(d, rules)
            if not hit:
                return None
            if exposed:
                hit = trace.subtract(hit, self.intervals(d, rules,
                                                         invert=True))
            per_dev.append(trace.length(hit))
        return sum(per_dev) / len(per_dev) / self.steps / 1e6

    def busy_s(self) -> float:
        if not self.ops:
            return 0.0
        return sum(trace.length(self.intervals(d)) for d in self.ops) \
            / len(self.ops) / 1e9

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9 if self.window \
            else 0.0

    def peaks(self) -> dict:
        return device.peaks(self.device_kind)

    def kernel_bytes(self):
        """``kernel_bytes/<family>_<kernel path>.py`` or None."""
        name = f"{self.cell.config['family']}_{self.info['kernel_path']}"
        return self.bench.module("kernel_bytes", name, required=False)

    # -- breakdown for the ledger --------------------------------------
    def breakdown(self, top: int = 10) -> dict:
        per_op = defaultdict(float)
        for evs in self.ops.values():
            for e in evs:
                per_op[trace.short(e["name"])] += (e["t1"] - e["t0"]) / 1e9
        n = max(len(self.ops), 1)
        ops = sorted(((k, v / n) for k, v in per_op.items()),
                     key=lambda kv: -kv[1])[:top]
        gaps = []
        lo, hi = self.window if self.window else (0, 0)
        for d in self.ops:
            busy = trace.union(self.intervals(d))
            edges = [lo] + [t for iv in busy for t in iv] + [hi]
            for a, b in zip(edges[::2], edges[1::2]):
                if b > a:
                    gaps.append((f"chip{d}:{self._host_at((a + b) / 2)}",
                                 (b - a) / 1e9))
        gaps.sort(key=lambda g: -g[1])
        return {"device_ops": [list(o) for o in ops],
                "idle_gaps": [list(g) for g in gaps[:top]]}

    def _host_at(self, t) -> str:
        inner = [s for s in self.spans if s[1] <= t <= s[2]]
        if not inner:
            return "between host spans"
        return min(inner, key=lambda s: s[2] - s[1])[0]
