"""``key = value`` configuration loader.

The analog of the reference's astaroth.conf parser
(reference: astaroth/astaroth_utils.cu acLoadConfig,
astaroth/astaroth.conf): lines of ``name = value`` with ``//`` and
``/* */`` comments; int-valued names and real-valued names are kept in
separate tables like AcMeshInfo's int_params/real_params.
"""

from __future__ import annotations

import os
import re
from typing import Dict, Tuple


def apply_fake_cpu(n: int) -> None:
    """Point JAX at ``n`` virtual CPU devices (the analog of the
    reference's GPU oversubscription, test/test_exchange.cu:52). Must
    run before anything initializes the XLA backend; shared by the app
    CLIs (--fake-cpu) and the bench/CI harnesses."""
    if n:
        import jax
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", n)


#: the cache directory used when JAX_COMPILATION_CACHE_DIR is unset: a
#: fixed path inside the checkout (gitignored), because the directory
#: is part of every cache key — a path that moves never hits
REPO_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_compile_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory ("" when off). The one place the repo's entry points
    (chip_smoke.py, apps, __graft_entry__.py) configure it.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it
    and this sets no other directory; otherwise the cache goes to
    :data:`REPO_COMPILE_CACHE`. Off when cpu is the primary requested
    platform: CPU compiles are fast and the tests would churn the disk.
    Reads the REQUESTED platform list, not the backend, so it never
    forces backend init (multihost wiring must still run first,
    parallel/multihost.py:36)."""
    import jax

    primary = str(jax.config.jax_platforms or "").split(",")[0].strip()
    if primary == "cpu":
        return ""
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache:
        cache = REPO_COMPILE_CACHE
        jax.config.update("jax_compilation_cache_dir", cache)
    # cache every program that takes noticeable compile time
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return cache


def load_config(path: str) -> Tuple[Dict[str, int], Dict[str, float]]:
    """Parse a conf file into (int_params, real_params)."""
    with open(path) as f:
        text = f.read()
    return parse_config(text)


def parse_config(text: str) -> Tuple[Dict[str, int], Dict[str, float]]:
    text = re.sub(r"/\*.*?\*/", "", text, flags=re.S)
    int_params: Dict[str, int] = {}
    real_params: Dict[str, float] = {}
    for line in text.splitlines():
        line = line.split("//")[0].strip()
        if not line or "=" not in line:
            continue
        name, _, val = line.partition("=")
        name = name.strip()
        val = val.strip()
        if not name or not val:
            continue
        try:
            if re.fullmatch(r"[+-]?\d+", val):
                int_params[name] = int(val)
            else:
                real_params[name] = float(val)
        except ValueError:
            continue  # non-numeric values are ignored, as in the reference
    return int_params, real_params
