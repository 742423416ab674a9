"""The device check and the table of peaks, keyed by JAX's device_kind."""
from __future__ import annotations

import json
from pathlib import Path

from bench.lib.registry import BenchError

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(kind: str) -> dict:
    """The published peaks of one chip of this kind; an unknown kind is an
    error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if kind not in table:
        raise BenchError(f"no peaks for device kind {kind!r} in {PEAKS_FILE}")
    return table[kind]


def check_device(chips: int) -> dict:
    """Platform, kind and count as JAX reports them. Fails unless JAX's
    default backend is a TPU with at least ``chips`` devices; it never
    falls back to the CPU."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:          # JAX_PLATFORMS names no usable backend
        raise BenchError(f"no TPU: {e}") from e
    platform = devs[0].platform
    if platform != "tpu":
        raise BenchError(f"no TPU: JAX's default device is {platform!r}")
    if len(devs) < chips:
        raise BenchError(f"{chips} chips asked for, {len(devs)} found")
    return {"platform": platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
