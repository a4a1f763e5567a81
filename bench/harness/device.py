"""The chip: refuse anything but a TPU with enough chips, keep JAX's
persistent compilation cache inside the checkout, count the programs JAX
compiles or loads, and read the device's memory peak."""
from __future__ import annotations

import pathlib
import threading

CACHE_DIR = pathlib.Path(".bench_cache") / "jax"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    pass


def require_tpu(chips: int):
    """Import JAX and return its devices; raise `NoChip` on any other
    backend or too few chips.  Never falls back to the CPU."""
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        raise NoChip(f"JAX found no TPU (backend {backend!r})")
    devices = jax.devices()
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices[:chips]


def enable_compile_cache(root: pathlib.Path) -> pathlib.Path:
    """Persistent cache at a fixed path inside the checkout, every program
    cached however fast it compiled, so only a cell's first run there
    compiles.  No size cap: a cap set for a machine-wide cache (the
    `JAX_COMPILATION_CACHE_MAX_SIZE` environment variable) turns on LRU
    eviction, whose bookkeeping then fails every write to this one."""
    import jax
    path = (root / CACHE_DIR).resolve()
    path.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


class ProgramCounter:
    """Counts backend compile requests (fresh compiles and persistent
    cache loads alike) through `jax.monitoring`, and of them the
    persistent cache's hits and misses."""

    def __init__(self):
        self._lock = threading.Lock()
        self.n = 0
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0

    def on_duration(self, event, duration, **_):
        if event == BACKEND_COMPILE_EVENT:
            with self._lock:
                self.n += 1
                self.seconds += duration

    def on_event(self, event, **_):
        if event in (CACHE_HIT_EVENT, CACHE_MISS_EVENT):
            with self._lock:
                if event == CACHE_HIT_EVENT:
                    self.hits += 1
                else:
                    self.misses += 1

    def install(self) -> "ProgramCounter":
        import jax
        jax.monitoring.register_event_duration_secs_listener(
            self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)
        return self


def describe(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)
