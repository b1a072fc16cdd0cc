"""graftgauge roofline accounting (ISSUE 17).

Each compiled XLA program's ``cost_analysis()`` (FLOPs, bytes accessed)
plus a measured wall time yields achieved FLOP/s, arithmetic intensity
(FLOPs/byte) and utilization-of-peak against a peak table keyed by
``device_kind`` — every roofline record carries the platform it ran on
and the peak it was scored against.

:func:`track_roofline` is the wrapper the memoized ``jit(shard_map)``
factories in ``parallel/`` build their programs with (graftlint's
compile-budget rule flags factories that bypass it).  It extends
``jax_accounting.track_compiles``:

- the FIRST call per abstract (shape, dtype) signature routes through
  AOT ``lower().compile()`` so the compile is paid exactly once, its
  wall time feeds the compile counters, and ``cost_analysis()`` comes
  for free off the compiled executable;
- the next few calls are timed with a ``block_until_ready`` barrier
  (measured wall time, not dispatch time); steady-state calls after
  that pass through untouched so instrumentation never lingers on the
  hot path;
- where AOT lowering is impossible (exotic call signatures) the program
  falls back to the plain :class:`~.jax_accounting.TrackedJit` path and
  its roofline record says ``cost: "unavailable"``.
"""
from __future__ import annotations

import sys
import threading
import time

from . import jax_accounting

#: timed (blocking) calls per program signature after the compile call;
#: everything after runs unbarriered
SAMPLE_CALLS = 3

#: nominal peaks the utilization ratio is scored against, keyed by the
#: exact ``device_kind`` JAX reports.  A kind missing here is an error,
#: never scored against another row.
PEAKS: dict[str, dict] = {
    "TPU v5 lite": {
        "flops_per_sec": 197e12, "mem_bytes_per_sec": 819e9,
        "label": "TPU v5e (bf16 MXU, nominal)",
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 819 GB/s HBM"},
    "cpu": {
        "flops_per_sec": 200e9, "mem_bytes_per_sec": 50e9,
        "label": "CPU (nominal AVX2 envelope)",
        "source": "deliberately generous several-core AVX2 envelope, so "
                  "a CPU run never flatters its utilization"},
}


def peak_for(device_kind: str) -> dict:
    """The peak row for ``device_kind``; raises KeyError for a device
    the table does not know."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peak recorded for device kind "
                       f"{device_kind!r}; add it to obs/roofline.PEAKS "
                       f"with its source")
    return dict(PEAKS[device_kind], match=device_kind)


def _metrics():
    return sys.modules.get("lighthouse_tpu.api.metrics_defs")


def _normalize_cost(ca) -> dict | None:
    """cost_analysis() returns a dict (or a 1-list of dicts on some
    backends); pull out the two numbers the roofline needs."""
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    flops = ca.get("flops")
    nbytes = ca.get("bytes accessed")
    if flops is None and nbytes is None:
        return None
    return {"flops": float(flops or 0.0),
            "bytes_accessed": float(nbytes or 0.0)}


def _arg_label(args) -> str:
    parts = []
    for a in args:
        shape = getattr(a, "shape", None)
        if shape is not None:
            dt = str(getattr(a, "dtype", "?"))
            parts.append(f"{dt}[{','.join(str(s) for s in shape)}]")
        else:
            parts.append(type(a).__name__)
    return ",".join(parts)


class _Program:
    """Per-(wrapper, abstract signature) accounting."""

    __slots__ = ("label", "compiled", "cost", "calls", "timed_calls",
                 "timed_seconds", "platform", "device_kind")

    def __init__(self, label):
        self.label = label
        self.compiled = None
        self.cost: dict | None = None
        self.calls = 0
        self.timed_calls = 0
        self.timed_seconds = 0.0
        self.platform = "?"
        self.device_kind = "?"

    def record(self) -> dict:
        out: dict = {"shapes": self.label, "calls": self.calls,
                     "platform": self.platform,
                     "device_kind": self.device_kind}
        if self.cost is None:
            out["cost"] = "unavailable"
            return out
        out.update(self.cost)
        peak = peak_for(self.device_kind)
        out["peak"] = peak["label"]
        if self.timed_calls and self.timed_seconds > 0:
            per_call = self.timed_seconds / self.timed_calls
            achieved = self.cost["flops"] / per_call
            out["wall_seconds_per_call"] = per_call
            out["achieved_flops_per_sec"] = achieved
            out["utilization_of_peak"] = achieved / peak["flops_per_sec"]
            if self.cost["bytes_accessed"] > 0:
                out["arithmetic_intensity"] = (
                    self.cost["flops"] / self.cost["bytes_accessed"])
                out["achieved_bytes_per_sec"] = (
                    self.cost["bytes_accessed"] / per_call)
        return out


class RooflineJit:
    """Roofline-accounted jitted callable (see module docstring)."""

    def __init__(self, name: str, fn):
        self.name = name
        self._fn = fn
        self._tracked = jax_accounting.track_compiles(name, fn)
        self._programs: dict = {}
        self._lock = threading.Lock()

    def _entry(self, key, args, kwargs) -> _Program:
        prog = _Program(_arg_label(args))
        try:
            import jax
            prog.platform = str(jax.default_backend())
            devs = jax.devices()
            if devs:
                prog.device_kind = str(getattr(devs[0], "device_kind",
                                               "?"))
            compiled = self._fn.lower(*args, **kwargs).compile()
            prog.compiled = compiled
            prog.cost = _normalize_cost(compiled.cost_analysis())
            # the AOT path bypasses TrackedJit's cache detection, so
            # count the program here; its seconds reach the counters
            # through the jax.monitoring listener
            jax_accounting._record_compile(1, 0.0, self.name)
        except Exception:
            prog.compiled = None        # fall back to the plain jit path
            prog.cost = None
        with self._lock:
            self._programs[key] = prog
        return prog

    def __call__(self, *args, **kwargs):
        key = jax_accounting._abstract_key(args, kwargs)
        with self._lock:
            prog = self._programs.get(key)
        if prog is None:
            prog = self._entry(key, args, kwargs)
        prog.calls += 1
        if prog.compiled is None:
            return self._tracked(*args, **kwargs)
        if prog.timed_calls < SAMPLE_CALLS:
            import jax
            t0 = time.perf_counter()
            out = prog.compiled(*args, **kwargs)
            jax.block_until_ready(out)
            wall = time.perf_counter() - t0
            with self._lock:
                prog.timed_calls += 1
                prog.timed_seconds += wall
            self._publish(prog)
            return out
        return prog.compiled(*args, **kwargs)

    def _publish(self, prog: _Program) -> None:
        rec = prog.record()
        util = rec.get("utilization_of_peak")
        md = _metrics()
        if md is not None and util is not None:
            md.gauge("roofline_utilization_ratio", float(util))

    def records(self) -> list[dict]:
        with self._lock:
            progs = list(self._programs.values())
        return [p.record() for p in progs]

    def __getattr__(self, name):
        return getattr(self._fn, name)


_lock = threading.Lock()
_REGISTRY: dict[str, RooflineJit] = {}


def track_roofline(name: str, fn) -> RooflineJit:
    """Wrap a jitted callable with roofline + compile accounting (use
    inside the memoized factories so the wrapper is built once per
    program — same contract as ``track_compiles``, which this wraps)."""
    rj = RooflineJit(name, fn)
    with _lock:
        _REGISTRY[name] = rj
    return rj


def snapshot() -> dict:
    """{program name: [per-signature roofline records]} over every
    tracked program."""
    with _lock:
        wrappers = dict(_REGISTRY)
    return {name: rj.records() for name, rj in wrappers.items()}


def reset() -> None:
    with _lock:
        _REGISTRY.clear()
