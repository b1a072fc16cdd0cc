"""graftgauge device/HBM memory ledger (ISSUE 17).

A timing means little without the device that ran it and the HBM it
used.  This module is that instrument: a per-device snapshot (platform,
chip count, ``memory_stats()`` HBM bytes where the runtime exposes
them, host RSS + CoW chunk accounting) sampled once per
slot into the graftwatch rings, an attribution registry tagging device
arrays by owning subsystem, and an :func:`hbm_watermark` scope that
stamps HBM high-water deltas onto the enclosing graftscope span.

Honesty contract (the whole point): where HBM stats are unavailable —
the XLA CPU backend returns ``memory_stats() = None`` — every surface
says ``"unavailable"`` explicitly instead of guessing, and the
``hbm_headroom`` SLO reads as unevaluable-not-breached.  jax is only
looked at through ``sys.modules``: a process that never initialized a
backend (lint rigs, the doctor's probe) never pays backend init for a
ledger read.
"""
from __future__ import annotations

import os
import subprocess
import sys
import threading
import time
import weakref

#: marker used wherever a device stat cannot be read on this platform
UNAVAILABLE = "unavailable"


def _jax():
    """The already-imported jax module, or None.  The ledger NEVER
    triggers backend initialization on its own: if nothing else in the
    process touched jax, there is no device state worth reporting."""
    return sys.modules.get("jax")


def _cow_stats() -> dict | None:
    cow = sys.modules.get("lighthouse_tpu.containers.cow")
    if cow is None:
        return None
    try:
        return dict(cow.STATS)
    except Exception:  # pragma: no cover - best effort
        return None


def _metrics():
    return sys.modules.get("lighthouse_tpu.api.metrics_defs")


# -- HBM stats ---------------------------------------------------------------


def device_memory_stats() -> list[dict] | None:
    """Per-device ``memory_stats()`` rows, or None when no backend is
    live or the platform exposes none (XLA CPU)."""
    jax = _jax()
    if jax is None:
        return None
    try:
        devices = jax.devices()
    except Exception:
        return None
    rows = []
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        rows.append({
            "id": int(getattr(d, "id", len(rows))),
            "kind": str(getattr(d, "device_kind", "?")),
            "bytes_in_use": int(stats.get("bytes_in_use", 0)),
            "bytes_limit": int(stats.get("bytes_limit", 0)),
            "peak_bytes_in_use": int(stats.get("peak_bytes_in_use", 0)),
        })
    return rows or None


def hbm_bytes() -> tuple[int, int] | None:
    """(total bytes_in_use, total bytes_limit) across devices, or None
    where the platform has no HBM accounting.  Tests monkeypatch this
    to drive deterministic watermark/SLO scenarios."""
    rows = device_memory_stats()
    if not rows:
        return None
    return (sum(r["bytes_in_use"] for r in rows),
            sum(r["bytes_limit"] for r in rows))


# -- the ledger snapshot ------------------------------------------------------


def ledger_snapshot() -> dict:
    """One JSON-ready per-device + host memory snapshot.

    ``platform``/``chip_count`` come from the live backend when one is
    initialized; ``hbm`` is the per-device stats list or the explicit
    ``"unavailable"`` marker — never a silent omission."""
    out: dict = {"platform": UNAVAILABLE, "device_kind": UNAVAILABLE,
                 "chip_count": 0, "hbm": UNAVAILABLE}
    jax = _jax()
    if jax is not None:
        try:
            devices = jax.devices()
            out["platform"] = str(jax.default_backend())
            out["chip_count"] = len(devices)
            if devices:
                out["device_kind"] = str(getattr(devices[0], "device_kind",
                                                 "?"))
        except Exception as exc:
            out["platform"] = UNAVAILABLE
            out["error"] = repr(exc)
    rows = device_memory_stats()
    if rows:
        out["hbm"] = rows
    # host side: RSS + the PR-8 CoW chunk accounting (chunk *bytes* are
    # tracked at materialize/fork time by containers/cow.py)
    host: dict = {}
    try:
        import resource
        host["rss_bytes"] = (resource.getrusage(resource.RUSAGE_SELF)
                             .ru_maxrss * 1024)
    except Exception:  # pragma: no cover - resource is POSIX-only
        host["rss_bytes"] = None
    cow = _cow_stats()
    if cow is not None:
        host["cow"] = cow
    out["host"] = host
    out["attribution"] = attributed_bytes()
    return out


# -- attribution registry -----------------------------------------------------

_attr_lock = threading.Lock()
#: (owner, label) -> list of (weakref-or-None, nbytes); the weakref lets
#: the registry report LIVE bytes, the nbytes snapshot keeps the record
#: meaningful for objects that refuse weak references
_attr: dict[tuple[str, str], list] = {}
#: (owner, label) -> peak concurrent bytes ever attributed
_attr_peak: dict[tuple[str, str], int] = {}


def attribute(owner: str, label: str, *arrays) -> None:
    """Tag device/host arrays as owned by ``owner`` (a subsystem name,
    e.g. ``parallel.bls``).  Liveness is tracked by weakref where the
    array type allows it, so ``attributed_bytes`` reports what is still
    resident, not what was ever allocated."""
    key = (owner, label)
    with _attr_lock:
        entries = _attr.setdefault(key, [])
        # drop dead entries so repeated tagging never grows unbounded
        entries[:] = [e for e in entries
                      if e[0] is None or e[0]() is not None]
        for a in arrays:
            nbytes = int(getattr(a, "nbytes", 0) or 0)
            try:
                ref = weakref.ref(a)
            except TypeError:
                ref = None
            entries.append((ref, nbytes))
        live = sum(e[1] for e in entries
                   if e[0] is None or e[0]() is not None)
        if live > _attr_peak.get(key, 0):
            _attr_peak[key] = live


def attributed_bytes() -> dict:
    """{owner: {label: {"live_bytes", "peak_bytes"}}} over the registry."""
    out: dict = {}
    with _attr_lock:
        for (owner, label), entries in _attr.items():
            live = sum(e[1] for e in entries
                       if e[0] is None or e[0]() is not None)
            out.setdefault(owner, {})[label] = {
                "live_bytes": live,
                "peak_bytes": _attr_peak.get((owner, label), live),
            }
    return out


def reset_attribution() -> None:
    with _attr_lock:
        _attr.clear()
        _attr_peak.clear()


# -- span watermarks ----------------------------------------------------------


class hbm_watermark:
    """Context manager stamping the HBM high-water delta of a device
    section onto the enclosing graftscope span (``parallel/`` wraps its
    sharded pipelines in one).  Where HBM stats are unavailable the
    span is annotated ``hbm_delta_bytes="unavailable"`` — the absence
    is recorded, not skipped."""

    def __init__(self, owner: str):
        self.owner = owner
        self.delta_bytes: int | str = UNAVAILABLE
        self._before: tuple[int, int] | None = None

    def __enter__(self):
        self._before = hbm_bytes()
        return self

    def __exit__(self, *exc):
        from . import tracing
        after = hbm_bytes()
        if self._before is None or after is None:
            tracing.annotate(hbm_owner=self.owner,
                             hbm_delta_bytes=UNAVAILABLE)
            return False
        self.delta_bytes = after[0] - self._before[0]
        tracing.annotate(hbm_owner=self.owner,
                         hbm_delta_bytes=int(self.delta_bytes),
                         hbm_bytes_in_use=int(after[0]))
        return False


# -- the per-slot publish (graftwatch tick) -----------------------------------


def publish() -> None:
    """Feed the device + host gauges once per slot (called from
    ``graftwatch.on_slot`` right after ``occupancy.publish``).  Cheap:
    one /proc read, one getrusage, and — only when a jax backend is
    already live — one ``memory_stats()`` pass.  Never raises."""
    md = _metrics()
    if md is None:  # metrics layer not loaded: nothing to feed
        return
    try:
        stats = hbm_bytes()
        if stats is not None:
            md.gauge("device_hbm_bytes_in_use", float(stats[0]))
            md.gauge("device_hbm_bytes_limit", float(stats[1]))
        # host-memory trajectory in the rings, not just on-demand
        # snapshots (ISSUE 17 satellite)
        from ..utils import system_health
        system_health.sample_gauges()
    except Exception:  # pragma: no cover - never kill the slot task
        pass


# -- flight-dump section ------------------------------------------------------


def flight_section() -> dict:
    """``doc["device"]`` for the flight recorder: the ledger snapshot
    plus roofline + compile-cache accounting.  Never raises."""
    try:
        out = ledger_snapshot()
    except Exception as exc:  # pragma: no cover - never block a dump
        return {"error": repr(exc)}
    try:
        from . import roofline
        out["roofline"] = roofline.snapshot()
    except Exception as exc:  # pragma: no cover
        out["roofline"] = {"error": repr(exc)}
    try:
        from . import jax_accounting
        counters = jax_accounting.snapshot()
        out["compile_cache"] = {
            "hits": counters.get("cache_hits", 0),
            "misses": counters.get("cache_misses", 0),
        }
    except Exception as exc:  # pragma: no cover
        out["compile_cache"] = {"error": repr(exc)}
    return out


# -- staged device-health probe ----------------------------------------------

_PROBE_STAGES = [("import", "import jax"),
                 ("devices", "import jax; jax.devices()")]


def staged_probe(timeout: int = 90, cwd: str | None = None) -> dict:
    """Staged accelerator-acquisition probe: how far does JAX get on
    this host, under default init and under ``JAX_PLATFORMS=tpu``?
    Each stage is its own subprocess with a hard timeout, so a wedged
    libtpu acquisition can't hang the caller — the record says exactly
    which stage died and how long it took.  ``tools/obs/doctor.py
    --probe`` runs it."""
    out: dict = {"timeout_s": timeout}
    for label, extra in (("default", {}),
                         ("forced_tpu", {"JAX_PLATFORMS": "tpu"})):
        stage_env = {**os.environ, **extra}
        stage_reached = None
        stages = {}
        for stage, code in _PROBE_STAGES:
            stage_reached = stage
            t0 = time.perf_counter()
            try:
                proc = subprocess.run(
                    [sys.executable, "-c", code], env=stage_env, cwd=cwd,
                    capture_output=True, text=True, timeout=timeout)
                rc = proc.returncode
            except subprocess.TimeoutExpired:
                rc = None
            wall = round(time.perf_counter() - t0, 2)
            stages[stage] = {"wall_s": wall, "rc": rc}
            if rc != 0:
                break
        out[label] = {"stage_reached": stage_reached, "stages": stages}
    return out
