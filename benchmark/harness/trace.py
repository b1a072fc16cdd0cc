"""The profiler traces of a window's slices, reduced to device busy time,
time per device program, program counts and idle gaps named by the host
span open in them.

A trace is JAX's ``.xplane.pb``, read with ``jax.profiler.ProfileData``.
Device planes are named ``/device:TPU:<n>``; on each, the
``XLA Modules`` line holds one event per program run and the ``XLA Ops``
line one per operation.  Host planes hold the host threads' events,
among them the ``TraceAnnotation`` spans the harness puts around its own
calls, named with :data:`PREFIX`.
"""
from __future__ import annotations

import re
from pathlib import Path

#: the harness's own annotations start with this; a traced slice of the
#: window starts with :data:`WINDOW`
PREFIX = "benchmark:"
WINDOW = PREFIX + "window"
DISPATCH = "tpu::System::Execute"
#: device time of operations outside every program run the slice recorded
OUTSIDE = "(outside a recorded program)"
_MODULE_ID = re.compile(r"\(\d+\)$")
_DEVICE = re.compile(r"/device:(TPU|GPU):\d+$")


def program_name(event_name: str) -> str:
    """A module event's program: ``jit_final_exponentiation(42)`` ->
    ``final_exponentiation``."""
    name = _MODULE_ID.sub("", event_name)
    return name[4:] if name.startswith("jit_") else name


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def _events(line):
    return [(ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns))
            for ev in line.events]


def find_xspace(directory: Path) -> Path:
    found = sorted(Path(directory).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def load(path: Path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(path))


def start(directory: Path) -> None:
    """Start the profiler: device and host events, no Python tracer."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(directory), profiler_options=opts)


def reduce(profile, chips: int = 1, spans=(), window_perf_s: float = 0.0,
           seconds: float | None = None) -> dict:
    """Reduce one traced slice: see :func:`reduce_events`."""
    host, planes = [], []
    for plane in profile.planes:
        if _DEVICE.match(plane.name):
            planes.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += _events(line)
    planes = sorted(planes, key=lambda p: int(p.name.rsplit(":", 1)[1]))
    devices = []
    for plane in planes[:chips]:
        modules, ops = [], []
        for line in plane.lines:
            if line.name == "XLA Modules":
                modules += _events(line)
            elif line.name == "XLA Ops":
                ops += [(float(ev.start_ns),
                         float(ev.start_ns + ev.duration_ns))
                        for ev in line.events]
        devices.append((modules, ops))
    return reduce_events(host, devices, spans, window_perf_s, seconds)


def reduce_events(host: list, devices: list, spans=(),
                  window_perf_s: float = 0.0,
                  seconds: float | None = None) -> dict:
    """Busy and window seconds (averaged over the ``devices``), device
    seconds and run counts per program, and the idle gaps of the window
    with the innermost span open at each gap's middle, among the
    harness's annotations and the system's spans.

    ``host`` holds the host events (name, start, end); each device is
    (program runs, operations): the ``XLA Modules`` events (name, start,
    end) and the ``XLA Ops`` intervals (start, end).  Busy time is the
    union of both, so an operation of a program that began before the
    slice counts; its time goes to :data:`OUTSIDE`.

    The window starts where the host annotation :data:`WINDOW` starts and
    lasts ``seconds`` (by default, as long as that annotation).  Device
    clocks are not the host's: each device's events are shifted onto the
    host clock by the least shift that starts every program run no
    earlier than its dispatch (``tpu::System::Execute``, in order), then
    clipped to the window.  ``spans`` are the system's own (kind, start,
    end) spans on the ``time.perf_counter`` clock, whose reading at the
    window's start is ``window_perf_s``; they join the trace's host
    events there.  Times are nanoseconds on the trace's clock, reported
    in seconds."""
    window = [(lo, hi) for name, lo, hi in host if name == WINDOW]
    if not devices or not window:
        return {}
    w_lo, w_hi = window[0]
    if seconds is not None:
        w_hi = w_lo + seconds * 1e9
    shifted = [(kind, w_lo + (lo - window_perf_s) * 1e9,
                w_lo + (hi - window_perf_s) * 1e9) for kind, lo, hi in spans]
    dispatches = sorted(lo for name, lo, _ in host if name == DISPATCH)
    busy_s, programs, runs, gaps = 0.0, {}, {}, []

    def clip(intervals, shift):
        return [(max(lo + shift, w_lo), min(hi + shift, w_hi))
                for lo, hi in intervals
                if hi + shift > w_lo and lo + shift < w_hi]

    for modules, ops in devices:
        shift = clock_shift([lo for _, lo, _ in modules], dispatches)
        names = [name for name, lo, hi in modules
                 if hi + shift > w_lo and lo + shift < w_hi]
        runs_ = clip([(lo, hi) for _, lo, hi in modules], shift)
        in_programs = sum(hi - lo for lo, hi in union(runs_))
        busy = union(runs_ + clip(ops, shift))
        busy_ns = sum(hi - lo for lo, hi in busy)
        busy_s += busy_ns / 1e9
        for name, (lo, hi) in zip(names, runs_):
            prog = program_name(name)
            programs[prog] = programs.get(prog, 0.0) + (hi - lo) / 1e9
            runs[prog] = runs.get(prog, 0) + 1
        if busy_ns > in_programs:
            programs[OUTSIDE] = programs.get(OUTSIDE, 0.0) + \
                (busy_ns - in_programs) / 1e9
        edges = [w_lo] + [x for iv in busy for x in iv] + [w_hi]
        gaps += [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    named = []
    spans_ = [(n[len(PREFIX):], lo, hi) for n, lo, hi in host
              if n != WINDOW and n.startswith(PREFIX)] + shifted
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:10]:
        mid = (a + b) / 2
        open_ = [(hi - lo, n) for n, lo, hi in spans_ if lo <= mid <= hi]
        named.append([min(open_)[1] if open_ else "(no span)",
                      (b - a) / 1e9])
    return {"busy_s": busy_s / len(devices),
            "window_s": (w_hi - w_lo) / 1e9,
            "programs": programs, "runs": runs, "idle_gaps": named}


def combine(parts: list[dict]) -> dict:
    """The slices of one window as one reduction: busy and window seconds,
    program seconds and runs summed, the ten longest idle gaps kept."""
    parts = [p for p in parts if p]
    if not parts:
        return {}
    out = {"busy_s": sum(p["busy_s"] for p in parts),
           "window_s": sum(p["window_s"] for p in parts),
           "programs": {}, "runs": {},
           "idle_gaps": sorted((g for p in parts for g in p["idle_gaps"]),
                               key=lambda g: -g[1])[:10]}
    for p in parts:
        for key in ("programs", "runs"):
            for name, v in p[key].items():
                out[key][name] = out[key].get(name, 0) + v
    return out


def clock_shift(starts: list[float], dispatches: list[float]) -> float:
    """The shift onto the host clock of a device whose program runs start
    at ``starts``, given the host's ``dispatches``: the least that
    puts each run after its dispatch, pairing them in order (0 where the
    counts differ)."""
    if not starts or len(starts) != len(dispatches):
        return 0.0
    return max(d - s for d, s in zip(dispatches, sorted(starts)))


def breakdown(reduced: dict) -> dict:
    """The result line's ``breakdown``: the ten programs that took most
    device time and the ten longest idle gaps."""
    top = sorted(reduced["programs"].items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": reduced["idle_gaps"][:10]}
