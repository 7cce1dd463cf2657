"""The comparison fails a broken program. The harness runs on the CPU
with the look for a chip skipped, and the timed path broken
underneath: each fault a cell can have reads ``correct`` false."""

import json
import time

import jax.numpy as jnp
import pytest

from benchmark.harness import runner
from benchmark.tests.helpers import cpu_devices, make_checkout, off_chip


def _state_unchanged(monkeypatch, model_cls):
    monkeypatch.setattr(model_cls, "run", lambda self, iters: None)


def _wrap_run(monkeypatch, model_cls, after):
    """``after(model, before)`` edits the state ``run`` produced;
    ``before`` is a copy of the state it started from."""
    orig = model_cls.run

    def run(self, iters):
        self.sync_domain() if hasattr(self, "sync_domain") else None
        before = {q: jnp.array(a) for q, a in self.dd.curr.items()}
        orig(self, iters)
        if hasattr(self, "sync_domain"):
            self.sync_domain()
        after(self, before)

    monkeypatch.setattr(model_cls, "run", run)


def _half_left_out(model, before):
    """The upper half of the grid (in z) is not advanced."""
    for q, a in model.dd.curr.items():
        z = a.shape[0]
        keep = jnp.arange(z)[:, None, None] >= z // 2
        model.dd.curr[q] = jnp.where(keep, before[q], a)


def _answer_altered(model, before):
    """One value of the produced state is off by 1% of its field's
    scale."""
    q = sorted(model.dd.curr)[0]
    a = model.dd.curr[q]
    model.dd.curr[q] = a.at[4, 5, 6].add(0.01 * jnp.max(jnp.abs(a)))


def _exchange_left_out(monkeypatch):
    import stencil_tpu.models.astaroth as astaroth
    monkeypatch.setattr(astaroth, "dispatch_exchange",
                        lambda fields, *a, **k: fields)


def _models():
    from stencil_tpu.models.astaroth import Astaroth
    from stencil_tpu.models.jacobi import Jacobi3D
    return {"jacobi3d": Jacobi3D, "astaroth": Astaroth}


FAULTS = [
    ("jacobi3d-16.1chip", "jacobi3d", "unchanged"),
    ("jacobi3d-16.1chip", "jacobi3d", "half"),
    ("jacobi3d-16.1chip", "jacobi3d", "altered"),
    ("astaroth-16.1chip", "astaroth", "unchanged"),
    ("astaroth-16.1chip", "astaroth", "half"),
    ("astaroth-16.1chip", "astaroth", "altered"),
    ("astaroth-16.4chip-weak", "astaroth", "unchanged"),
    ("astaroth-16.4chip-weak", "astaroth", "exchange"),
    ("astaroth-16.4chip-weak", "astaroth", "altered"),
]


def run_cell(root, cell, capsys):
    rc = runner.main(["--workload", cell, "--seed", "4000000007",
                      "--seconds", "0.3", "--trace", "0"],
                     time.perf_counter(), root=root, require=cpu_devices)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell,family,fault", FAULTS)
def test_fault_reads_incorrect(tmp_path, capsys, monkeypatch, cell, family,
                               fault):
    off_chip(monkeypatch)
    root = make_checkout(tmp_path)
    model = _models()[family]
    if fault == "unchanged":
        _state_unchanged(monkeypatch, model)
    elif fault == "half":
        _wrap_run(monkeypatch, model, _half_left_out)
    elif fault == "altered":
        _wrap_run(monkeypatch, model, _answer_altered)
    else:
        _exchange_left_out(monkeypatch)
    res = run_cell(root, cell, capsys)
    assert res["correct"] is False
    gap = res["checks"]["gap_over_change"]
    assert gap["value"] > gap["limit"]
    if fault == "unchanged":
        assert gap["value"] == pytest.approx(1.0)
