"""JAX runtime accounting: compile counts/seconds + host<->device bytes.

graftlint's recompile-hazard and device-transfer rules catch these
hazards *statically*; this module is the dynamic complement.  A runtime
recompile storm (a shape leak past the memoized ``jit(shard_map)``
factories) or an unaccounted host round-trip at a shard boundary becomes
an observable counter, not a silent 12-minute stall.

Three entry points:

- :func:`track_compiles` wraps a jitted callable: each call compares the
  jit trace-cache size before/after (``_cache_size`` on modern jax) —
  growth means XLA compiled a new program and ``jax_compile_total``
  increments.  Where ``_cache_size`` is unavailable it falls back to
  abstract-shape bookkeeping (a fresh ``(shape, dtype)`` signature counts
  as a compile).  Compile *seconds* come from ``jax.monitoring`` duration
  events.
- :func:`host_readback` is THE sanctioned device->host crossing for
  ``parallel/`` (the device-transfer lint rule rejects bare
  ``np.asarray`` on device values there): it counts the bytes into
  ``jax_transfer_device_to_host_bytes_total`` and returns the numpy
  array.
- :func:`account_transfer` records an explicit host->device placement
  (``parallel.mesh.shard_batch`` routes through it).

Import-light: jax is only touched lazily (tier-1 lint/tracing tests run
without it) and the metrics feed goes through ``sys.modules`` like
``tracing._observe_metric``.
"""
from __future__ import annotations

import sys
import threading

_lock = threading.Lock()
_counters = {
    "compiles": 0,
    "compile_seconds": 0.0,
    "h2d_bytes": 0,
    "d2h_bytes": 0,
    "cache_hits": 0,
    "cache_misses": 0,
}
_monitoring_installed = False


def snapshot() -> dict:
    """Copy of the process-local counters (independent of prometheus)."""
    with _lock:
        return dict(_counters)


def _metrics():
    return sys.modules.get("lighthouse_tpu.api.metrics_defs")


def _record_compile(n: int, seconds: float, program: str) -> None:
    with _lock:
        _counters["compiles"] += n
        _counters["compile_seconds"] += seconds
    md = _metrics()
    if md is not None:
        md.count("jax_compile_total", n)
        if seconds:
            md.count("jax_compile_seconds_total", seconds)
    from . import tracing
    tracing.annotate(jax_compiled=program)


def account_transfer(nbytes: int, direction: str = "h2d") -> None:
    """Record an accounted host<->device transfer of `nbytes`."""
    key = "d2h_bytes" if direction == "d2h" else "h2d_bytes"
    nbytes = int(nbytes or 0)
    with _lock:
        _counters[key] += nbytes
    md = _metrics()
    if md is not None:
        md.count("jax_transfer_device_to_host_bytes_total" if key ==
                 "d2h_bytes" else "jax_transfer_host_to_device_bytes_total",
                 nbytes)


def host_readback(x):
    """Sanctioned device->host readback: np.asarray(x) with the bytes
    accounted.  parallel/ code MUST use this instead of bare np.asarray
    (enforced by the device-transfer lint rule)."""
    import numpy as np
    account_transfer(getattr(x, "nbytes", 0), "d2h")
    return np.asarray(x)


def _record_cache_event(hit: bool) -> None:
    """Persistent-compile-cache hit/miss accounting (tests and the
    jax.monitoring listener both land here)."""
    key = "cache_hits" if hit else "cache_misses"
    with _lock:
        _counters[key] += 1
    md = _metrics()
    if md is not None:
        md.count("jax_compile_cache_hits_total" if hit
                 else "jax_compile_cache_misses_total", 1)


def install_monitoring() -> bool:
    """Route jax.monitoring compile-duration + persistent-compile-cache
    events into the catalog.  Idempotent; returns True."""
    global _monitoring_installed
    if _monitoring_installed:
        return True
    import jax.monitoring as jm

    def _on_duration(event: str, duration: float, **kw) -> None:
        # tracing, lowering and backend compile; a persistent-cache hit
        # reports its retrieval under /jax/compilation_cache/ instead
        if event.startswith("/jax/core/compile/"):
            with _lock:
                _counters["compile_seconds"] += duration
            md = _metrics()
            if md is not None:
                md.count("jax_compile_seconds_total", duration)

    def _on_event(event: str, **kw) -> None:
        # the persistent compile cache announces itself through bare
        # events: cache_hits (compiler.py), cache_misses
        # (compilation_cache.py)
        if event == "/jax/compilation_cache/cache_hits":
            _record_cache_event(True)
        elif event == "/jax/compilation_cache/cache_misses":
            _record_cache_event(False)

    jm.register_event_duration_secs_listener(_on_duration)
    jm.register_event_listener(_on_event)
    _monitoring_installed = True
    return True


def _abstract_key(args, kwargs):
    """Hashable (shape, dtype) signature of a call — the fallback
    trace-cache key when the jitted callable exposes no _cache_size."""
    def one(a):
        shape = getattr(a, "shape", None)
        if shape is not None:
            return ("arr", tuple(shape), str(getattr(a, "dtype", "?")))
        if isinstance(a, (list, tuple)):
            return ("seq", tuple(one(x) for x in a))
        return ("val", type(a).__name__)
    return (tuple(one(a) for a in args),
            tuple(sorted((k, one(v)) for k, v in kwargs.items())))


class TrackedJit:
    """Wrapper around a jitted callable that detects runtime recompiles.

    ``fn._cache_size()`` growth across a call is authoritative (it counts
    exactly the lowered-and-compiled programs); the shape-signature set
    is the fallback.  Compile seconds come from the jax.monitoring
    listener this installs.
    """

    def __init__(self, name: str, fn):
        self.name = name
        self._fn = fn
        self._keys: set = set()
        install_monitoring()

    def _cache_size(self):
        size = getattr(self._fn, "_cache_size", None)
        if size is None:
            return None
        try:
            return size()
        except Exception:
            return None

    def __call__(self, *args, **kwargs):
        before = self._cache_size()
        key = None
        if before is None:
            key = _abstract_key(args, kwargs)
        out = self._fn(*args, **kwargs)
        after = self._cache_size()
        if after is not None:
            compiled = after > (before or 0)
        else:
            compiled = key not in self._keys
            self._keys.add(key)
        if compiled:
            # seconds arrive through the jax.monitoring listener
            _record_compile(1, 0.0, self.name)
            md = _metrics()
            if md is not None and after is not None:
                md.gauge("jax_jit_cache_entries", after)
        return out

    def __getattr__(self, name):
        return getattr(self._fn, name)


def track_compiles(name: str, fn) -> TrackedJit:
    """Wrap a jitted callable for compile accounting (use inside the
    memoized factories so the wrapper is built once per program)."""
    return TrackedJit(name, fn)
