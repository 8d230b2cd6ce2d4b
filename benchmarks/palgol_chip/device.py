"""Which device a run is on, what it has compiled, and its peaks."""

from __future__ import annotations

import json
from pathlib import Path

import jax

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class NoChip(SystemExit):
    """Raised where JAX finds no TPU, or fewer chips than a cell asks for."""


def tpu_devices(count: int):
    """The first ``count`` TPU devices; raises :class:`NoChip` otherwise."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU found (JAX platform {devices[0].platform!r})")
    if len(devices) < count:
        raise NoChip(f"{count} chips requested, {len(devices)} found")
    return devices[:count]


class CompileClock:
    """Counts the executables JAX obtains, and the seconds it spends on
    them, while it is registered. JAX reports one backend-compile event per
    executable, whether XLA compiles it or the persistent cache loads it."""

    def __init__(self):
        self.seconds = 0.0
        self.count = 0

    def __call__(self, event: str, duration: float, **kwargs) -> None:
        if event == BACKEND_COMPILE:
            self.seconds += duration
            self.count += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self)
        return False


def peak_bytes(devices) -> int:
    """``peak_bytes_in_use`` of the fullest device (0 where unreported)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def describe(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def peaks(device_kind: str, table: Path) -> dict:
    """The published peaks of ``device_kind`` from ``peaks.json``; a kind
    missing from the table is an error, not a default."""
    entries = json.loads(table.read_text())["devices"]
    if device_kind not in entries:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {table.name}"
        )
    return entries[device_kind]
