"""The 750^3-per-chip configuration (``jacobi3d-750.1chip``): its file
against the configuration it shares a family with, and a tiny cell of
it with the same unaligned traits (y no multiple of 8, x no multiple
of 128, odd z) run end to end on the CPU through the harness, on the
wrap pair kernel the chip takes, kernels interpreted."""

import json
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import runner, spec
from benchmark.tests.helpers import (REPO, cpu_devices, make_checkout,
                                     off_chip)

BENCH = spec.Benchmark(REPO)
CELL = "jacobi3d-750.1chip"
CONFIG = "jacobi3d-750-per-chip"


def _cfg(name):
    return json.loads((REPO / BENCH._entry("configs", name)["file"])
                      .read_text())


def test_750_config_is_the_512_physics_at_the_reference_share():
    """Grid, field, physics and limit as the 512^3 configuration; one
    rank's 750^3 share of a weak-scaled run, ``ranks`` and the initial
    state listed as cut; checked against the lean reference."""
    one, share = _cfg("jacobi3d-512"), _cfg(CONFIG)
    described = {"name", "source", "deployment", "assumed", "guarantees",
                 "reduced", "grid_xyz", "grid_xyz_per_chip", "scale_axis",
                 "family", "reference"}
    assert share["family"] == "jacobi3d_lean"
    for key in set(one) - described:
        assert share[key] == one[key], key
    assert share["grid_xyz_per_chip"] == [750, 750, 750]
    assert share["reduced"] == ["initial_state", "ranks"]
    assert set(share["assumed"]) >= set(share["reduced"])
    cell = BENCH.cell(CELL)
    assert cell.chips == 1 and cell.global_zyx() == (750, 750, 750)
    assert BENCH._entry("configs", CONFIG)["reduced"] == share["reduced"]


def test_tiny_unaligned_cell_on_cpu(tmp_path, capsys, monkeypatch):
    off_chip(monkeypatch)
    from stencil_tpu.ops import pallas_stencil
    monkeypatch.setattr(pallas_stencil, "on_tpu", lambda: True)
    monkeypatch.setattr(pallas_stencil, "default_interpret", lambda: True)
    root = make_checkout(tmp_path)
    s = json.loads((root / "BENCHMARK.json").read_text())
    cfg = _cfg(CONFIG)
    cfg.update(name="jacobi3d-150x30x25-per-chip",
               grid_xyz_per_chip=[150, 30, 25])
    file = "benchmark/configs/jacobi3d-150x30x25-per-chip.json"
    (root / file).write_text(json.dumps(cfg))
    s["configs"].append({"name": cfg["name"], "source": cfg["source"][:200],
                         "file": file, "reduced": cfg["reduced"],
                         "why": "tiny CPU test size"})
    s["workloads"].append({"name": "jacobi3d-150x30x25.1chip",
                           "config": cfg["name"], "traffic": "closed-k32",
                           "chips": 1, "why": "tiny CPU test size"})
    (root / "BENCHMARK.json").write_text(json.dumps(s))
    rc = runner.main(["--workload", "jacobi3d-150x30x25.1chip", "--seed",
                      str(2**31 + 750), "--seconds", "0.5", "--trace", "0"],
                     time.perf_counter(), root=root, require=cpu_devices)
    out, err = capsys.readouterr()
    assert rc == 0
    res = json.loads(out.strip().splitlines()[-1])
    assert res["correct"], err[-3000:]
    assert res["checks"]["gap_over_change"]["value"] < 1e-5
    info = json.loads(re.search(r"chips=1 (\{.*\}) first_chunk_s", err)[1])
    assert info == {"kernel_path": "wrap", "step_stride": 2,
                    "mesh_xyz": [1, 1, 1]}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_lean_reference_is_the_jacobi3d_reference(dtype):
    """The same seeded start and the same steps, bit for bit, on a grid
    with the 750^3 share's unaligned traits, in the configuration's
    precision and in the control's."""
    from benchmark.reference import jacobi3d, jacobi3d_lean

    gzyx = (25, 30, 150)
    dev = jax.devices()[0]
    want = jacobi3d.advance(jacobi3d.initial_state(2**33 + 7, gzyx, dev),
                            5, None, dtype)
    got = jacobi3d_lean.advance(
        jacobi3d_lean.initial_state(2**33 + 7, gzyx, dev), 5, None, dtype)
    np.testing.assert_array_equal(np.asarray(got["temp"]),
                                  np.asarray(want["temp"]))
