"""The chip: find it or stop, and count what JAX compiles.

The benchmark never falls back to the CPU. `require_chip` exits with
code 2, before any result is printed, when JAX finds no TPU or fewer
chips than the cell asks for.
"""
from __future__ import annotations

import os
import sys
import time
from typing import Any, Dict


def process_age_s() -> float:
    """Seconds since this process started, from ``/proc/self/stat``."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        start_ticks = int(fields[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.process_time()


def require_chip(chips: int):
    """The first TPU device, or exit 2 with no result line."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        devices, err = [], e
    else:
        err = None
    platform = devices[0].platform if devices else f"none ({err})"
    if not devices or platform != "tpu" or len(devices) < chips:
        print(f"bench: this cell needs {chips} TPU chip(s); JAX found "
              f"{len(devices)} device(s) on platform {platform}",
              file=sys.stderr)
        sys.exit(2)
    return devices[0]


def device_info(dev, chips: int) -> Dict[str, Any]:
    """The contract's ``device`` object, with the peak on the fullest of
    the chips the cell uses."""
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": chips, "memory_peak_bytes": max(peaks)}


class CompileLog:
    """Backend-compile seconds and count, and persistent-cache hits and
    misses, from JAX's monitoring events."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax.monitoring as mon
        self.compile_s = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == self.COMPILE:
            self.compile_s += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == self.HIT:
            self.hits += 1
        elif event == self.MISS:
            self.misses += 1

    def snapshot(self) -> Dict[str, float]:
        return {"compile_s": self.compile_s, "compiles": self.compiles,
                "cache_hits": self.hits, "cache_misses": self.misses}

    def since(self, snap: Dict[str, float]) -> Dict[str, float]:
        now = self.snapshot()
        return {k: now[k] - snap[k] for k in now}
