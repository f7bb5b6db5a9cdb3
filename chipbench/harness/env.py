"""The process a cell runs in: where it reads and writes inside the
checkout, its compile cache, the chips it may use, and its clocks.

Everything the benchmark writes stays inside the checkout: JAX's
persistent compilation cache at a fixed path (the path is part of the
cache key, so it never moves), and traces under ``chipbench/.out``.
"""
from __future__ import annotations

import gc
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(BENCH_DIR, ".jax_cache")
OUT_DIR = os.path.join(BENCH_DIR, ".out")

_T_IMPORT = time.perf_counter()


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def prepare() -> None:
    """Call before JAX is imported: the compile cache inside the
    checkout (also for the program, which honours the variable), TPU
    runtime logs off (they would go to a fixed path under /tmp), and the
    program's sources on the import path."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ["TPU_LOG_DIR"] = "disabled"
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def configure_jax() -> None:
    """Every compiled program goes to the persistent cache, however
    quickly it compiled, so a later run of the cell loads it."""
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def chips(n: int, *, require_chip: bool = True) -> list:
    """The first ``n`` devices; `NoChip` where JAX has no TPU or fewer
    than ``n`` of them."""
    import jax

    devices = jax.devices()
    if require_chip:
        if devices[0].platform != "tpu":
            raise NoChip(f"JAX's platform is {devices[0].platform!r}, not tpu")
        if len(devices) < n:
            raise NoChip(f"the cell needs {n} chips, JAX sees {len(devices)}")
    elif len(devices) < n:
        devices = devices * n  # CPU rehearsal: replicas share the one device
    return list(devices[:n])


def device_report(devices) -> dict:
    """The device block of the result line; the peak is that of the
    fullest chip."""
    import jax

    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    first = devices[0]
    return {"platform": first.platform, "kind": first.device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": peak}


def process_age_s() -> float:
    """Seconds since this process started (from /proc; from this module's
    import where /proc is missing)."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - int(fields[19]) / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - _T_IMPORT


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


class CompileCounter:
    """JAX's compile requests and persistent-cache hits, from its own
    monitoring events.  A request that is not a hit compiled."""

    REQUEST = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax.monitoring as mon

        self.requests = 0
        self.hits = 0
        self.seconds = 0.0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **kwargs):
        if event == self.REQUEST:
            self.requests += 1
            self.seconds += duration_secs

    def _event(self, event, **kwargs):
        if event == self.HIT:
            self.hits += 1

    def snapshot(self) -> tuple[int, int, float]:
        return self.requests, self.hits, self.seconds


class GcWatch:
    """The garbage collector's pauses over the window, with when the
    longest began: one thing a host stall can be put down to."""

    def __init__(self):
        self.pauses = []     # (start perf_counter, seconds, generation)
        self._t0 = None
        gc.callbacks.append(self._gc)

    def _gc(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.pauses.append((self._t0, time.perf_counter() - self._t0,
                                info.get("generation")))
            self._t0 = None

    def start(self) -> None:
        self.pauses.clear()

    def report(self, window_start: float) -> dict:
        longest = max(self.pauses, key=lambda p: p[1], default=None)
        return {
            "gc_pauses": len(self.pauses),
            "gc_full": sum(1 for p in self.pauses if p[2] == 2),
            "gc_total_ms": sum(p[1] for p in self.pauses) * 1e3,
            "gc_max_ms": longest[1] * 1e3 if longest else 0.0,
            "gc_max_at_s": longest[0] - window_start if longest else None,
        }
