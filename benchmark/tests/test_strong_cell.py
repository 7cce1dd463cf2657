"""The Jacobi strong-scaling cell (``jacobi3d-512.4chip-strong``): the
halo kernel's bytes against a hand count, the exchange's wire rate on
hand-made and recorded traces, and a tiny four-device cell run end to
end on the CPU, on the XLA path ``auto`` takes there and on the halo
path it takes on the chip."""

import json
import re
import time

import pytest

from benchmark.harness import runner, spec, trace
from benchmark.tests.helpers import (REPO, cpu_devices, make_checkout,
                                     off_chip)
from benchmark.tests.test_scoped_metrics import load
from benchmark.tests.test_trace import context, metric

BENCH = spec.Benchmark(REPO)
CELL = "jacobi3d-512.4chip-strong"
CONFIG = "jacobi3d-512-strong-4chip"
WIRE = "exchange_wire_gb_per_s"


def test_each_config_and_traffic_pair_once():
    pairs = [(w["config"], w["traffic"])
             for w in BENCH.spec["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_strong_config_runs_the_one_chip_grid_on_four_ranks():
    """The strong-scaling deployment is the one-chip configuration's
    grid, field, physics and limit split over four ranks: only what
    names and describes it differs, and its source names the rank
    count."""
    def load_cfg(name):
        return json.loads((REPO / BENCH._entry("configs", name)["file"])
                          .read_text())

    one, four = load_cfg("jacobi3d-512"), load_cfg(CONFIG)
    described = {"name", "source", "deployment", "assumed", "guarantees",
                 "ranks"}
    assert set(four) - described == set(one) - described
    for key in set(one) - described:
        assert four[key] == one[key], key
    assert four["ranks"] == BENCH.cell(CELL).chips == 4
    assert four["source"] != one["source"]
    assert BENCH._entry("configs", CONFIG)["source"] == four["source"]


def test_halo_kernel_bytes_512_over_4_chips():
    kb = BENCH.module("kernel_bytes", "jacobi3d_halo")
    cell = BENCH.cell(CELL)
    info = {"kernel_path": "halo", "step_stride": 2, "mesh_xyz": [1, 1, 4]}
    # one 128 x 512 x 512 f32 shard is 128 MiB, read and written; its z
    # slabs give 2 rows of 512 x 512 each, its y slabs 128 + 4 rows of
    # 8 x 512 each
    per_call = (2 * 128 * 512 * 512 + 2 * 2 * 512 * 512
                + 2 * 132 * 8 * 512) * 4
    assert kb.bytes_per_call(128, 512, 512, 2) == per_call == 276955136
    assert kb.bytes_per_step(cell, info) == 4 * per_call / 2 == 553910272
    # the single-step kernel: 1-row z slabs, y slabs not z-extended
    assert kb.bytes_per_call(128, 512, 512, 1) == \
        (2 * 128 * 512 * 512 + 2 * 512 * 512 + 2 * 128 * 8 * 512) * 4


def op(name, t0, t1, tf_op):
    return {"name": name, "t0": t0, "t1": t1, "tf_op": tf_op}


SCOPE = "jit(shard_steps)/shard_map/while/body/stencil.exchange/ppermute:"
SYNC = ("%collective-permute.1 = f32[2,512,512]{2,1,0:T(8,128)} "
        "collective-permute(f32[2,512,512]{2,1,0} %a), channel_id=1")
START = ("%collective-permute-start.2 = (f32[2,512,512]{2,1,0:T(8,128)}, "
         "f32[2,512,512]{2,1,0:T(8,128)S(1)}, u32[]{:S(2)}, u32[]{:S(2)}) "
         "collective-permute-start(f32[2,512,512]{2,1,0} %b)")
DONE = ("%collective-permute-done.2 = f32[2,512,512]{2,1,0:T(8,128)S(1)} "
        "collective-permute-done((f32[2,512,512]{2,1,0}, f32[2,512,512]"
        "{2,1,0}, u32[], u32[]) %collective-permute-start.2)")
TUPLE = ("%collective-permute-done.3 = (f32[4,8]{1,0}, bf16[16]{0}) "
         "collective-permute-done(((f32[4,8]{1,0}, bf16[16]{0}), (f32[4,8]"
         "{1,0}, bf16[16]{0}), u32[], u32[]) %collective-permute-start.3)")
SLAB = ("%fusion.4 = f32[8,512,512]{2,1,0} fusion(f32[2,512,512]{2,1,0} "
        "%collective-permute-done.2), kind=kLoop")
MiB2 = 2 * 512 * 512 * 4


def test_wire_rate_on_hand_events():
    """Window 0-10000 ns, 2 steps, 2 chips. Chip 0: a synchronous
    permute 0-1000, an async pair (start 1000-1100, done 1100-3000), a
    combined two-array done 3000-3500 and a slab fusion 3500-4000, all
    under the scope, and a permute outside it 5000-6000. Chip 1 the
    same transfers, back to back from 0 to 3500, and no fusion."""
    outside = "jit(shard_steps)/shard_map/while/body/ppermute:"
    evs = [op(SYNC, 0, 1000, SCOPE), op(START, 1000, 1100, SCOPE),
           op(DONE, 1100, 3000, SCOPE), op(TUPLE, 3000, 3500, SCOPE)]
    tr = trace.Trace(
        {0: evs + [op(SLAB, 3500, 4000, SCOPE),
                   op(SYNC.replace(".1 =", ".9 ="), 5000, 6000, outside)],
         1: [dict(e) for e in evs]},
        [("bench.window", 0, 10000)])
    ctx = context(tr, CELL, steps=2)
    # 2 MiB sync + 2 MiB done + 4x8 f32 and 16 bf16, the start skipped
    per_chip = 2 * MiB2 + 4 * 8 * 4 + 16 * 2
    wire = BENCH.module("metrics", WIRE)
    assert wire.wire_bytes_per_step(ctx) == per_chip / 2
    # the scope's union: chip 0 0-4000, chip 1 0-3500 -> 3750 ns / 2 steps
    ms = metric("exchange_scope_ms_per_step").reduce(ctx)
    assert ms == pytest.approx(3750 / 2 / 1e6)
    assert wire.reduce(ctx) == pytest.approx(
        per_chip / 2 / (3750 / 2 * 1e-9) / 1e9)


def test_wire_rate_needs_a_scoped_transfer_on_every_chip():
    tr = trace.Trace({0: [op(SLAB, 0, 10, SCOPE)],
                      1: [op(SYNC, 0, 10, SCOPE)]},
                     [("bench.window", 0, 100)])
    assert metric(WIRE).reduce(context(tr, CELL, steps=1)) is None


def test_wire_bytes_on_recorded_4chip_trace():
    """A chip trace of ``astaroth-256.4chip-weak``, one RK3 iteration:
    each chip receives what ``Astaroth.exchange_stats()`` claims a shard
    sends an iteration, the bytes of three radius-3 exchanges of the 8
    packed fields along z, each 2 directions x 3 rows of the padded 262
    x 262 face (the CPU twin of this check compiles the program:
    ``tests/test_halo_strong.py``); the program before the layer scopes
    gives nothing."""
    d, tr = load("astaroth-256.4chip-weak.scoped")
    ctx = context(tr, d["cell"], d["steps"], d["info"])
    wire = BENCH.module("metrics", WIRE)
    assert wire.wire_bytes_per_step(ctx) == 3 * 2 * 3 * 262 * 262 * 8 * 4
    ms = d["expect"]["exchange_scope_ms_per_step"]
    assert wire.reduce(ctx) == pytest.approx(
        39538944 / (ms / 1e3) / 1e9, rel=1e-12)
    d, tr = load("astaroth-256.4chip-weak")
    assert wire.reduce(context(tr, d["cell"], d["steps"], d["info"])) \
        is None


def test_wire_bytes_on_recorded_strong_trace():
    """A chip trace of this cell, one run(32) chunk: each chip receives
    its two z neighbours' 2 boundary rows of 512 x 512 float32 a pair
    call, 2 MiB a step, which is ``Jacobi3D.exchange_stats()`` (8 MiB a
    step over the mesh) divided by the 4 chips."""
    d, tr = load(f"{CELL}.scoped")
    ctx = context(tr, d["cell"], d["steps"], d["info"])
    assert d["steps"] == 32
    assert BENCH.module("metrics", WIRE).wire_bytes_per_step(ctx) == \
        2 * 2 * 512 * 512 * 4 / 2 == 2097152


@pytest.mark.parametrize("path", ["xla", "halo"])
def test_tiny_strong_cell_on_cpu(tmp_path, capsys, monkeypatch, path):
    """``jacobi3d-16.4chip-strong`` through the harness on four CPU
    devices: ``auto`` takes the XLA path here; with the process made to
    read as a TPU (kernels still interpreted) it takes the chip's: the
    halo pair kernel on the x-free (1, 1, 4) mesh."""
    off_chip(monkeypatch)
    if path == "halo":
        from stencil_tpu.ops import pallas_halo, pallas_stencil
        monkeypatch.setattr(pallas_stencil, "on_tpu", lambda: True)
        monkeypatch.setattr(pallas_halo, "default_interpret", lambda: True)
    root = make_checkout(tmp_path)
    s = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((REPO / BENCH._entry("configs", CONFIG)["file"])
                     .read_text())
    cfg.update(name="jacobi3d-16-strong-4chip", grid_xyz=[16, 16, 16])
    file = "benchmark/configs/jacobi3d-16-strong-4chip.json"
    (root / file).write_text(json.dumps(cfg))
    s["configs"].append({"name": cfg["name"], "source": cfg["source"],
                         "file": file, "reduced": cfg["reduced"],
                         "why": "tiny CPU test size"})
    s["workloads"].append({"name": "jacobi3d-16.4chip-strong",
                           "config": cfg["name"], "traffic": "closed-k32",
                           "chips": 4, "why": "tiny CPU test size"})
    (root / "BENCHMARK.json").write_text(json.dumps(s))
    rc = runner.main(["--workload", "jacobi3d-16.4chip-strong", "--seed",
                      str(2**31 + 777), "--seconds", "0.5", "--trace", "0"],
                     time.perf_counter(), root=root, require=cpu_devices)
    out, err = capsys.readouterr()
    assert rc == 0
    res = json.loads(out.strip().splitlines()[-1])
    assert res["correct"], err[-3000:]
    assert res["attempted"] >= 1 and res["device"]["count"] == 4
    assert set(res["metrics"]) == {"gcell_updates_per_s", "chunk_ms_p90",
                                   "setup_s"}
    info = json.loads(re.search(r"chips=4 (\{.*\}) first_chunk_s", err)[1])
    assert info["kernel_path"] == path
    if path == "halo":
        assert info == {"kernel_path": "halo", "step_stride": 2,
                        "mesh_xyz": [1, 1, 4]}
