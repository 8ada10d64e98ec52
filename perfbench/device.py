"""The device a run measures, and its published peaks."""

from __future__ import annotations

import json
import pathlib

PEAKS_FILE = pathlib.Path(__file__).with_name("peaks.json")


class DeviceError(RuntimeError):
    """No accelerator, too few chips, or a device with no known peaks."""


def require(chips: int, platform: str = "tpu"):
    """The devices of this cell; raises unless JAX finds ``chips`` of
    ``platform``. A run never falls back to the CPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != platform:
        raise DeviceError(
            f"no {platform.upper()}: JAX found {devices[0].platform} "
            f"({len(devices)} device(s)); this benchmark runs on the chip only"
        )
    if len(devices) < chips:
        raise DeviceError(f"the cell needs {chips} chips, JAX found "
                          f"{len(devices)}")
    return devices[:chips]


def peaks_for(device_kind: str) -> dict:
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise DeviceError(f"no peaks known for device kind {device_kind!r}; "
                          f"known: {sorted(table)}")
    return table[device_kind]


def describe(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices) -> int | None:
    """Peak bytes in use on the fullest chip, where the backend says."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None
