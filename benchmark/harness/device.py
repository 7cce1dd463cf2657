"""The chip: which devices a cell runs on, their peaks, their memory."""

import json
import sys
from pathlib import Path

PEAKS = Path(__file__).resolve().parent.parent / "peaks.json"


class NoChip(SystemExit):
    """No accelerator, or fewer chips than the cell asks for: the run
    exits non-zero and prints no result."""


def require_devices(chips: int):
    """The first ``chips`` TPU devices, or NoChip. There is no CPU
    branch: a number from the CPU is never a device metric."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"benchmark: no TPU (JAX found {devices[0].platform!r})",
              file=sys.stderr)
        raise NoChip(3)
    if len(devices) < chips:
        print(f"benchmark: the cell needs {chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        raise NoChip(3)
    return devices[:chips]


def peaks(device_kind: str, path: Path = PEAKS) -> dict:
    """The published peaks of ``device_kind``; a device that is not in
    the table is an error, not a default."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"{path}; add its published numbers there")
    return table[device_kind]


def describe(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip since the process began."""
    return max(int(d.memory_stats()["peak_bytes_in_use"]) for d in devices)
