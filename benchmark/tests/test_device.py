"""The peaks table, and the refusal to measure without a chip."""

import os
import subprocess
import sys

import pytest

from benchmark.harness import device
from benchmark.tests.helpers import REPO


def test_v5e_peaks():
    p = device.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    assert p["bf16_flops_per_s"] == 197e12


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        device.peaks("TPU v9 imaginary")


def test_no_tpu_exits_nonzero_with_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, str(REPO / "benchmark/run.py"), "--workload",
         "jacobi3d-512.1chip", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=REPO, env=env, capture_output=True,
        text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_too_few_chips_exits(monkeypatch):
    import jax

    class Dev:
        platform = "tpu"

    monkeypatch.setattr(jax, "devices", lambda: [Dev()])
    with pytest.raises(SystemExit) as e:
        device.require_devices(4)
    assert e.value.code != 0
