"""Test configuration: force an 8-device virtual CPU mesh.

Mirrors the reference's strategy of simulating multi-GPU / multi-node
without a cluster (SURVEY.md section 4): the reference oversubscribes one
GPU (test/test_exchange.cu:52 `dd.set_gpus({0,0})`); we fake an 8-device
mesh on CPU via XLA_FLAGS (read when the backend initializes, which no
test module does at import).
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavy interpret-mode Pallas parity tests (minutes each). "
        "The smoke tier (ci/run_ci.sh default) runs -m 'not slow'; the "
        "full tier and a bare pytest run everything.")
