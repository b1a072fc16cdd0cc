"""The chip: the check that one is there, the compile cache, compile
counting and the memory peak."""
from __future__ import annotations

import os
import threading
from pathlib import Path


def pin_compile_cache(cache_root: Path) -> str:
    """Keep JAX's persistent compilation cache at a fixed path inside the
    checkout, with no size cap for this process (a capped LRU evicts the
    large BLS entries before the next run reads them).  Must run before
    JAX is imported; the system takes the directory from the environment."""
    path = str(cache_root / "jax")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    return path


def require_chips(count: int) -> dict:
    """The device block of the result; raises SystemExit where JAX finds
    no TPU or fewer chips than the cell asks for."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"benchmark: JAX found no TPU (platform "
                         f"{devices[0].platform!r}); a cell runs on the chip")
    if len(devices) < count:
        raise SystemExit(f"benchmark: the cell asks for {count} chips, "
                         f"JAX found {len(devices)}")
    return describe(count)


def describe(count: int) -> dict:
    import jax
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": count}


def memory_peak_bytes(count: int) -> int | None:
    """Peak bytes in use on the fullest of the cell's chips, as the
    runtime reports it (None where it reports nothing)."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:count]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class CompileWatch:
    """Counts backend compiles (a persistent-cache load counts too: JAX
    times it inside the same event) and persistent-cache hits and misses
    in this process."""

    def __init__(self):
        import jax.monitoring as jm
        self._lock = threading.Lock()
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0

        def on_duration(event: str, duration: float, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                with self._lock:
                    self.compiles += 1
                    self.compile_s += duration

        def on_event(event: str, **kw):
            with self._lock:
                if event == "/jax/compilation_cache/cache_hits":
                    self.cache_hits += 1
                elif event == "/jax/compilation_cache/cache_misses":
                    self.cache_misses += 1

        jm.register_event_duration_secs_listener(on_duration)
        jm.register_event_listener(on_event)

    def snapshot(self) -> dict:
        with self._lock:
            return {"compiles": self.compiles, "compile_s": self.compile_s,
                    "cache_hits": self.cache_hits,
                    "cache_misses": self.cache_misses}

    def since(self, before: dict) -> dict:
        now = self.snapshot()
        return {k: now[k] - before[k] for k in now}
