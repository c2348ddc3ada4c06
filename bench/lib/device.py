"""The benchmark runs on the chip or not at all."""
from __future__ import annotations

import jax


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_chips(n: int) -> list:
    backend = jax.default_backend()
    if backend != "tpu":
        raise NoChip(f"needs a TPU; JAX's default backend is {backend!r}")
    devices = jax.devices()
    if len(devices) < n:
        raise NoChip(f"the cell asks for {n} chips; JAX sees {len(devices)}")
    return devices[:n]


def describe(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices)}


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip (0 where the backend keeps no
    statistics)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)
