"""BENCHMARK.json and the files it names, found by name: a cell names
a configuration (``configs/*.json``) and a traffic mix
(``traffic/<traffic>.json``); a configuration names its family
(``families/<family>.py``, ``reference/<family>.py``); a per-layer
metric is ``metrics/<name>.py``; a kernel's bytes are
``kernel_bytes/<family>_<kernel path>.py``. A later PR adds files
and entries and edits none."""

import importlib.util
import json
import re
from dataclasses import dataclass
from pathlib import Path

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")


@dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: list
    per_layer: list

    def global_zyx(self) -> tuple:
        """The global grid (z, y, x): fixed (strong scaling), or the
        per-chip grid grown along ``scale_axis`` (weak scaling)."""
        cfg = self.config
        if "grid_xyz" in cfg:
            x, y, z = cfg["grid_xyz"]
        else:
            x, y, z = cfg["grid_xyz_per_chip"]
            axis = cfg["scale_axis"]
            x, y, z = (v * (self.chips if axis == a else 1)
                       for v, a in zip((x, y, z), "xyz"))
        return (z, y, x)


class Benchmark:
    def __init__(self, root: Path):
        self.root = Path(root)
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())
        self.dir = self.root / self.spec["paths"][0]

    def _entry(self, key: str, name: str) -> dict:
        for e in self.spec[key]:
            if e["name"] == name:
                return e
        raise KeyError(f"no {key} entry named {name!r} in BENCHMARK.json")

    def cell(self, name: str) -> Cell:
        w = self._entry("workloads", name)
        cfg_entry = self._entry("configs", w["config"])
        config = json.loads((self.root / cfg_entry["file"]).read_text())
        traffic = json.loads(
            (self.dir / "traffic" / f"{w['traffic']}.json").read_text())

        def applies(m):
            return "workloads" not in m or name in m["workloads"]

        return Cell(name, config, traffic, int(w["chips"]),
                    [m for m in self.spec["end_to_end"] if applies(m)],
                    [m for m in self.spec["per_layer"] if applies(m)])

    def module(self, kind: str, name: str, required: bool = True):
        """``<kind>/<name>.py`` under the benchmark's directory, or None
        where it is absent and not ``required``."""
        path = self.dir / kind / f"{name}.py"
        if not path.exists():
            if required:
                raise FileNotFoundError(path)
            return None
        return load_module(path, f"bench_{kind}_{name}")


def load_module(path: Path, modname: str):
    spec = importlib.util.spec_from_file_location(
        re.sub(r"[^A-Za-z0-9_]", "_", modname), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def problems(spec: dict) -> list:
    """Names and units that break the contract's character rules."""
    out = []

    def name_ok(v, what):
        if not (isinstance(v, str) and NAME.fullmatch(v)):
            out.append(f"{what} {v!r} is not a valid name")

    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = set()
        for e in spec[key]:
            name_ok(e["name"], f"{key} name")
            if e["name"] in seen:
                out.append(f"{key} name {e['name']!r} repeats")
            seen.add(e["name"])
    for c in spec["configs"]:
        for k in c["reduced"]:
            name_ok(k, "reduced key")
    for w in spec["workloads"]:
        name_ok(w["config"], "config")
        name_ok(w["traffic"], "traffic")
        if len(w["why"]) > 200 or "\n" in w["why"] or "\t" in w["why"]:
            out.append(f"why of {w['name']} breaks the one-line rule")
    metrics = spec["end_to_end"] + spec["per_layer"]
    if len({m["name"] for m in metrics}) != len(metrics):
        out.append("two metrics share a name")
    for m in metrics:
        if not UNIT.fullmatch(m["unit"]):
            out.append(f"unit {m['unit']!r} of {m['name']} is not valid")
        if m["better"] not in ("lower", "higher"):
            out.append(f"better of {m['name']} is {m['better']!r}")
    return out
