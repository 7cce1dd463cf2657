"""The harness end to end on the CPU, with the look for a chip
skipped: every cell runs, the comparison passes, the result line has
the contract's keys."""

import json
import time

import pytest

from benchmark.harness import runner
from benchmark.tests.helpers import (CELLS, cpu_devices, make_checkout,
                                     off_chip)


@pytest.mark.parametrize("cell", [c[0] for c in CELLS])
def test_run_cell_on_cpu(tmp_path, capsys, monkeypatch, cell):
    off_chip(monkeypatch)
    root = make_checkout(tmp_path)
    rc = runner.main(["--workload", cell, "--seed", str(2**31 + 12345),
                      "--seconds", "0.5", "--trace", "0"],
                     time.perf_counter(),
                     root=root, require=cpu_devices)
    out, err = capsys.readouterr()
    assert rc == 0
    res = json.loads(out.strip().splitlines()[-1])
    assert list(res)[-1] == "checks"
    assert res["correct"], err[-3000:]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"gcell_updates_per_s", "chunk_ms_p90",
                                   "setup_s"}
    assert err.strip().splitlines()[-1].startswith("[check] chunks")
