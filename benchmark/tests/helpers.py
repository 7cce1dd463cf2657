"""A checkout in a temporary directory: the real benchmark files, a
BENCHMARK.json of tiny cells, and their configurations, so that the
harness runs end to end on the CPU."""

import json
import shutil
from pathlib import Path

import jax

BENCH = Path(__file__).resolve().parent.parent
REPO = BENCH.parent

TINY = {
    "jacobi3d-16": ("jacobi3d-512", {"grid_xyz": [16, 16, 16]}),
    "astaroth-16-per-chip": ("astaroth-256-per-chip",
                             {"grid_xyz_per_chip": [16, 16, 16]}),
}
CELLS = [("jacobi3d-16.1chip", "jacobi3d-16", "closed-k32", 1),
         ("astaroth-16.1chip", "astaroth-16-per-chip", "closed-k2", 1),
         ("astaroth-16.4chip-weak", "astaroth-16-per-chip", "closed-k1", 4)]


def make_checkout(tmp: Path) -> Path:
    """``tmp`` laid out as a checkout: the benchmark directory copied,
    a BENCHMARK.json naming the tiny cells."""
    shutil.copytree(BENCH, tmp / "benchmark",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"] = []
    for name, (base, change) in TINY.items():
        cfg = json.loads((BENCH / "configs" / f"{base}.json").read_text())
        cfg.update(change, name=name)
        path = f"benchmark/configs/{name}.json"
        (tmp / path).write_text(json.dumps(cfg))
        spec["configs"].append({"name": name, "source": cfg["source"][:200],
                                "file": path, "reduced": cfg["reduced"],
                                "why": "tiny CPU test size"})
    spec["workloads"] = [{"name": n, "config": c, "traffic": t, "chips": k,
                          "why": "tiny CPU test size"}
                         for n, c, t, k in CELLS]
    for m in spec["per_layer"]:
        m.pop("workloads", None)
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


def cpu_devices(chips: int):
    return jax.devices()[:chips]


def off_chip(monkeypatch):
    """What the CPU cannot report (memory statistics), and no
    persistent compile cache for CPU programs."""
    from benchmark.harness import device, runner
    monkeypatch.setattr(device, "memory_peak_bytes", lambda devices: 0)
    monkeypatch.setattr(runner, "compile_cache", lambda root: None)
