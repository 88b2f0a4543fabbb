"""The accelerator, the compile cache and the compile clock."""
from __future__ import annotations

import os

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def configure(root) -> str:
    """JAX's persistent compile cache at a fixed place: the directory
    `JAX_COMPILATION_CACHE_DIR` names, else `.jax_cache/` in the checkout.
    Every program is cached, however fast it compiled."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(root / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def chips(n: int):
    """The first `n` TPU devices; never a CPU."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoAccelerator(f"JAX found no accelerator: {e}") from None
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"JAX found no TPU (platform "
                            f"{devs[0].platform!r}); this benchmark runs on "
                            f"the chip only")
    if len(devs) < n:
        raise NoAccelerator(f"the cell asks for {n} chips, JAX found "
                            f"{len(devs)}")
    return devs[:n]


def describe(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest chip."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


class CompileClock:
    """Seconds spent in backend compiles (persistent-cache reads
    included), backend compiles counted, and persistent-cache hits."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == BACKEND_COMPILE:
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == CACHE_HIT:
            self.hits += 1

    def snapshot(self) -> tuple:
        return self.seconds, self.compiles, self.hits
