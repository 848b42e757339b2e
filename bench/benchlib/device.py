"""The accelerator a run is on: the check that it is there, what JAX calls
it, and its peak memory."""

from __future__ import annotations

import sys


class NoChip(SystemExit):
    pass


def require(chips: int) -> dict:
    """Device info of this run; exits non-zero unless JAX sees at least
    ``chips`` TPU devices (no fallback to the CPU)."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    if info["platform"] != "tpu" or len(devs) < chips:
        print(
            f"bench: needs {chips} TPU chip(s); JAX found {len(devs)} {info['platform']} device(s)",
            file=sys.stderr,
        )
        raise NoChip(3)
    return info


def memory_peak_bytes(devices=None) -> int | None:
    """Peak bytes in use on the fullest device, where the backend says."""
    import jax

    peaks = []
    for d in devices or jax.devices():
        st = d.memory_stats() or {}
        if "peak_bytes_in_use" in st:
            peaks.append(int(st["peak_bytes_in_use"]))
    return max(peaks) if peaks else None
