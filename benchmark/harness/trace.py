"""The profiler's device trace, reduced to what the per-layer metrics
read: each chip's operations as (name, start, end, stats), the host
spans the harness opened, and interval arithmetic over them.

Times are nanoseconds on the trace's own clock."""

import glob
import json
import re
from dataclasses import dataclass, field

from benchmark.harness import xplane

DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."
#: stats XLA attaches to each op's metadata that the rules may read
STAT_KEYS = ("hlo_category", "tf_op", "bytes_accessed", "flops")


@dataclass
class Trace:
    ops: dict = field(default_factory=dict)     # device id -> [event]
    spans: list = field(default_factory=list)   # [(name, t0, t1)]

    def window(self):
        """(t0, t1) of the harness's ``bench.window`` span."""
        w = [s for s in self.spans if s[0] == "bench.window"]
        return (w[0][1], w[0][2]) if w else None

    def to_json(self) -> dict:
        return {"ops": {str(k): v for k, v in self.ops.items()},
                "spans": self.spans}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls({int(k): [dict(e) for e in v]
                    for k, v in d["ops"].items()},
                   [tuple(s) for s in d["spans"]])


def load(trace_dir: str) -> Trace:
    """Read the one ``.xplane.pb`` a ``jax.profiler`` trace wrote."""
    from jax.profiler import ProfileData

    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane file under {trace_dir}, "
                           f"found {paths}")
    data = ProfileData.from_file(paths[0])
    meta = xplane.event_metadata(paths[0], DEVICE_PLANE.search)
    tr = Trace()
    for plane in data.planes:
        m = DEVICE_PLANE.search(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                md = meta.get(plane.name, {})
                tr.ops.setdefault(int(m.group(1)), []).extend(
                    {"name": e.name, "t0": e.start_ns, "t1": e.end_ns,
                     **{k: v for k, v in md.get(e.name, {}).items()
                        if k in STAT_KEYS}}
                    for e in line.events)
            elif plane.name.startswith("/host:"):
                tr.spans.extend((e.name, e.start_ns, e.end_ns)
                                for e in line.events
                                if e.name.startswith(HOST_PREFIX))
    for d, evs in tr.ops.items():
        tr.ops[d] = sorted((e for e in evs
                            if opcode(e["name"]) not in CONTAINERS),
                           key=lambda e: e["t0"])
    tr.spans.sort(key=lambda s: s[1])
    return tr


def opcode(name: str) -> str:
    """The HLO opcode of an op event, whose name is the instruction's
    text ``%name = <shape> opcode(operands), attrs``; '' otherwise."""
    head, sep, rest = name.partition(" = ")
    if not sep or not head.startswith("%"):
        return ""
    if rest.startswith("("):            # tuple shape: skip to its end
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.partition(" ")[2]
    m = re.match(r"\s*([a-z][a-z0-9-]*)\(", rest)
    return m.group(1) if m else ""


#: ops that only contain other ops of the same trace line
CONTAINERS = ("while", "conditional", "call")


def short(name: str) -> str:
    """``%name opcode`` (and a custom call's target) for the ledger."""
    op = opcode(name)
    if not op:
        return name[:80]
    tag = ""
    if op == "custom-call":
        m = re.search(r'custom_call_target="([^"]+)"', name)
        tag = f"[{m.group(1)}]" if m else ""
    return f"{name.partition(' = ')[0]} {op}{tag}"


def union(intervals):
    """Disjoint sorted intervals covering the same points."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def length(intervals) -> float:
    return float(sum(b - a for a, b in union(intervals)))


def subtract(intervals, minus):
    """The parts of ``intervals`` that no interval of ``minus`` covers."""
    out = []
    minus = union(minus)
    for a, b in union(intervals):
        cur = a
        for c, d in minus:
            if d <= cur or c >= b:
                continue
            if c > cur:
                out.append((cur, c))
            cur = max(cur, d)
            if cur >= b:
                break
        if cur < b:
            out.append((cur, b))
    return out


def matches(ev: dict, rules: dict) -> bool:
    """A rule set is ``{"include": {field: [regex, ...]}, "exclude":
    {...}}`` over an op's ``name`` (its HLO text) and its metadata
    stats: an op matches when some regex of some ``include`` field
    matches and no regex of any ``exclude`` field does."""
    def any_hit(group):
        for key, pats in group.items():
            v = ev.get(key)
            if v is not None and any(re.search(p, str(v)) for p in pats):
                return True
        return False

    return any_hit(rules.get("include", {})) and \
        not any_hit(rules.get("exclude", {}))


def load_dump(path: str) -> Trace:
    """A trace saved by ``run.py --dump-trace``, with the op metadata of
    the ``.xplane.pb`` saved beside it merged in where it is there."""
    import os

    with open(path) as f:
        tr = Trace.from_json(json.load(f))
    raw = path + ".xplane.pb"
    meta = (xplane.event_metadata(raw, DEVICE_PLANE.search)
            if os.path.exists(raw) else {})
    for d in list(tr.ops):
        md = meta.get(f"/device:TPU:{d}", {})
        tr.ops[d] = [{**e, **{k: v for k, v in md.get(e["name"], {}).items()
                              if k in STAT_KEYS}}
                     for e in tr.ops[d]
                     if opcode(e["name"]) not in CONTAINERS]
    return tr


def survey(trace_dir: str, per_line: int = 3) -> list:
    """Every plane and line of the raw trace with its first events and
    all their stats: what a reader looks at before writing rules."""
    from jax.profiler import ProfileData

    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = list(line.events)
            out.append({"plane": plane.name, "line": line.name,
                        "events": len(evs),
                        "first": [{"name": e.name, "t0": e.start_ns,
                                   "dur": e.duration_ns,
                                   "stats": {k: str(v) for k, v in
                                             dict(e.stats or {}).items()}}
                                  for e in evs[:per_line]]})
    return out


def dump(tr: Trace, path: str, trace_dir: str = "") -> None:
    d = tr.to_json()
    if trace_dir:
        d["survey"] = survey(trace_dir)
        import shutil
        shutil.copy(glob.glob(f"{trace_dir}/**/*.xplane.pb",
                              recursive=True)[0], path + ".xplane.pb")
    with open(path, "w") as f:
        json.dump(d, f)
