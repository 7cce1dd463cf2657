"""BENCHMARK.json keeps to the contract's form, every name it gives
resolves to a file, and a configuration, a traffic mix, a cell and a
per-layer metric are added by adding files and entries only."""

import json
import statistics

import pytest

from benchmark.harness import spec
from benchmark.tests.helpers import REPO

SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
BENCH = spec.Benchmark(REPO)


def test_names_units_and_keys():
    assert spec.problems(SPEC) == []
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(SPEC["paths"][0] + "/")
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert "bound" not in m
        assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}
    assert len(json.dumps(SPEC)) < 64 * 1024


def test_check_budget_fits_24_cells():
    n = 24
    total = ((2 + 14 * n) * (SPEC["run_seconds"] + 60) + n * 2 * 90
             + 1200)
    assert 1 <= SPEC["run_seconds"] <= 51 and total <= 43200


def test_four_chip_cells_at_most_half():
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_resolves(cell):
    c = BENCH.cell(cell)
    assert BENCH.module("families", c.config["family"]).Solver
    assert int(c.traffic["steps_per_chunk"]) >= 1
    assert c.config["limits"]["gap_over_change"] > 0
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert c.per_layer


@pytest.mark.parametrize("m", SPEC["per_layer"], ids=lambda m: m["name"])
def test_metric_file_agrees_with_entry(m):
    mod = BENCH.module("metrics", m["name"])
    assert (mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE) == \
        (m["unit"], m["layer"], m["moves"], m["source"])
    for w in m.get("workloads", []):
        BENCH.cell(w)


def test_add_cell_config_metric_by_files_only(tmp_path):
    """A later PR's additions, made in a copy: a new configuration
    file, traffic file, metric file and BENCHMARK.json entries. The
    harness finds them by name, with no code edited."""
    import shutil

    shutil.copytree(REPO / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    s = json.loads(json.dumps(SPEC))
    cfg = json.loads((REPO / "benchmark/configs/jacobi3d-512.json")
                     .read_text())
    cfg.update(name="jacobi3d-750-per-chip", grid_xyz_per_chip=[750] * 3,
               scale_axis="z")
    del cfg["grid_xyz"]
    (tmp_path / "benchmark/configs/jacobi3d-750-per-chip.json").write_text(
        json.dumps(cfg))
    (tmp_path / "benchmark/traffic/closed-k8.json").write_text(
        json.dumps({"loop": "closed", "steps_per_chunk": 8}))
    (tmp_path / "benchmark/metrics/fixture_metric.py").write_text(
        'UNIT, LAYER, MOVES, SOURCE = "ms", "device", '
        '"gcell_updates_per_s", "device_trace"\n'
        "def reduce(ctx):\n    return 1.5\n")
    s["configs"].append({"name": "jacobi3d-750-per-chip", "source": "x",
                         "file": "benchmark/configs/jacobi3d-750-per-chip"
                                 ".json", "reduced": [], "why": "fixture"})
    s["workloads"].append({"name": "jacobi3d-750.4chip-weak",
                           "config": "jacobi3d-750-per-chip",
                           "traffic": "closed-k8", "chips": 4,
                           "why": "fixture"})
    s["per_layer"].append({"name": "fixture_metric", "unit": "ms",
                           "better": "lower", "source": "device_trace",
                           "layer": "device",
                           "moves": "gcell_updates_per_s",
                           "workloads": ["jacobi3d-750.4chip-weak"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(s))
    b = spec.Benchmark(tmp_path)
    c = b.cell("jacobi3d-750.4chip-weak")
    assert c.global_zyx() == (3000, 750, 750)
    assert c.traffic["steps_per_chunk"] == 8
    assert [m["name"] for m in c.per_layer] == ["fixture_metric"]
    assert b.module("metrics", "fixture_metric").reduce(None) == 1.5
    assert b.module("kernel_bytes", "jacobi3d_halo", required=False) is None
    assert spec.problems(s) == []


def test_bad_names_are_reported():
    s = json.loads(json.dumps(SPEC))
    s["workloads"][0]["name"] = "bad name"
    s["end_to_end"][0]["unit"] = "tokens per second"
    found = spec.problems(s)
    assert any("bad name" in p for p in found)
    assert any("tokens per second" in p for p in found)


def test_p90_is_inclusive_quantile():
    from benchmark.harness import window
    v = [float(i) for i in range(1, 101)]
    assert window.p90(v) == statistics.quantiles(v, n=10,
                                                 method="inclusive")[8]
    assert window.p90([3.0]) == 3.0
