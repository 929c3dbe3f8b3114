"""Process start time and the backend compiler's clock."""
from __future__ import annotations

import os
import time

import jax


def process_start_epoch() -> float:
    """Wall-clock time this process started (Linux ``/proc``), else now."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - uptime + start_ticks / os.sysconf(
            "SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.time()


class CompileClock:
    """Backend compiles (XLA, and Mosaic for Pallas kernels) and
    persistent-cache hits and misses, from JAX's monitoring events. A
    persistent-cache hit compiles nothing."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    HITS = "/jax/compilation_cache/cache_hits"
    MISSES = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == self.COMPILE:
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == self.HITS:
            self.cache_hits += 1
        elif event == self.MISSES:
            self.cache_misses += 1

    def snapshot(self) -> dict:
        """Counts so far."""
        return {"compiles": self.compiles,
                "compile_s": round(self.seconds, 3),
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}
