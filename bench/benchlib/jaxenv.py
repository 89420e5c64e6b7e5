"""JAX set-up for a run: the compile cache, the device check, compile
counting and the memory peak."""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CompileClock", "NoDevice", "enable_compile_cache",
           "find_devices", "memory_peak_bytes"]


class NoDevice(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache, at ``JAX_COMPILATION_CACHE_DIR``
    when that is set and otherwise at ``<root>/.jax_cache`` (a fixed path:
    the path is part of the cache key).  Every program is cached, however
    small or quick to compile, so that only a checkout's first run
    compiles."""
    import jax
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache:
        cache = str(root / ".jax_cache")
        Path(cache).mkdir(exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache


def find_devices(chips: int, platform: str = "tpu") -> dict:
    """The device block of the result line; raises :class:`NoDevice`
    unless JAX finds at least ``chips`` devices of ``platform``."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != platform:
        raise NoDevice(f"no TPU: JAX found {len(devices)} "
                       f"{dev.platform!r} device(s) ({dev.device_kind})")
    if len(devices) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found "
                       f"{len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def memory_peak_bytes(count: int):
    """Peak bytes in use on the fullest of the first ``count`` devices, or
    None where the backend keeps no such statistic."""
    import jax
    peaks = []
    for dev in jax.devices()[:count]:
        stats = dev.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class CompileClock:
    """Programs built while open, from JAX's own events: ``count`` and
    ``seconds`` of ``backend_compile_duration`` (raised for a compile and
    for a load from the persistent cache alike), and ``cache_hits``, the
    loads among them."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.seconds = 0.0
        self.count = 0
        self.cache_hits = 0

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1

    def _event(self, event: str, **_) -> None:
        if event == self.HIT:
            self.cache_hits += 1

    def __enter__(self) -> "CompileClock":
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)
