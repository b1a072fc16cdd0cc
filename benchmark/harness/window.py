"""What every traffic kind's runner shares: the run's context, the
measured window with its traced slices, and the outcome a runner returns.

A traffic file names its ``kind``; the runner is ``kinds/<kind>.py``,
found by name (``spec.kind``).  Its ``run(run: Run) -> Outcome`` makes
the inputs from the seed, warms up, drives the window and compares with
the reference; ``run.py`` turns the outcome into the result line.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from pathlib import Path

from harness import trace


@dataclass
class Outcome:
    """What one run measured and compared."""
    metrics: dict                      # end-to-end values by name
    attempted: int
    failed: int
    checks: dict                       # name -> (value, limit)
    traced: dict = field(default_factory=dict)   # work the slices covered
    spans: list = field(default_factory=list)
    slices: list = field(default_factory=list)   # (trace dir, start, s)
    notes: dict = field(default_factory=dict)
    memory_peak_bytes: int | None = None


class Run:
    """One run's context: the cell's configuration and traffic, the seed,
    the window's length, whether it is traced, and the clocks."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, seconds: int,
                 traced: bool, cache: Path, t0: float, watch, chips: int):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.seconds, self.traced, self.cache = seconds, traced, cache
        self.t0, self.watch, self.chips = t0, watch, chips
        self.reference_s = 0.0

    def window(self) -> "Window":
        return Window(self)


class Window:
    """The measured window: ``--seconds`` long, with the system's span ring
    cleared and a full garbage collection made at its start.

    In a traced run the profiler runs only in slices of the window: the
    device's trace buffers hold ~6.3M operation events (~13 s of a gossip
    batch), and the profiler takes tens of seconds to stop after even a
    short slice of one.  A runner opens and closes a slice between requests
    (:meth:`start_slice`, :meth:`stop_slice`), or for a fixed time from
    now (:meth:`timed_slice`).  In an untraced run these do nothing."""

    def __init__(self, run: Run):
        self.run = run
        self.slices: list[tuple[Path, float, float]] = []
        self.stops_s: list[float] = []
        self._open: tuple[Path, float] | None = None
        self._threads: list[threading.Thread] = []

    def __enter__(self):
        import gc

        from lighthouse_tpu.obs import tracing
        run = self.run
        gc.collect()
        self.before = run.watch.snapshot()
        tracing.clear()
        self.start = time.perf_counter()
        self.setup_s = self.start - run.t0 - run.reference_s
        self.deadline = self.start + run.seconds
        return self

    def open(self) -> bool:
        return time.perf_counter() < self.deadline

    def start_slice(self) -> None:
        """Start the profiler; the slice begins at the annotation
        :data:`trace.WINDOW`."""
        import jax
        if not self.run.traced or self._open:
            return
        directory = self.run.cache / "trace" / f"{time.time_ns()}"
        trace.start(directory)
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            self._open = (directory, time.perf_counter())

    def stop_slice(self) -> None:
        import jax
        if not self._open:
            return
        directory, start = self._open
        end = time.perf_counter()
        jax.profiler.stop_trace()
        self.slices.append((directory, start, end - start))
        self.stops_s.append(time.perf_counter() - end)
        self._open = None

    def slice_s(self) -> float:
        """How long the open slice has run (0 where none is open)."""
        return time.perf_counter() - self._open[1] if self._open else 0.0

    def timed_slice(self, seconds: float) -> None:
        """Start a slice now and stop it from a thread ``seconds`` later,
        inside whatever request runs then."""
        if not self.run.traced:
            return
        self.start_slice()
        thread = threading.Timer(seconds, self.stop_slice)
        thread.start()
        self._threads.append(thread)

    def slice_record(self) -> list[list[float]]:
        """Each slice's start after the window's, its length and how long
        the profiler took to stop, in seconds."""
        return [[start - self.start, seconds, stop_s] for (_, start, seconds),
                stop_s in zip(self.slices, self.stops_s)]

    def traced_count(self, done_times: list[float]) -> int:
        """How many requests completed inside a traced slice."""
        return sum(any(s <= t <= s + d for _, s, d in self.slices)
                   for t in done_times)

    def __exit__(self, *exc):
        from lighthouse_tpu.obs import tracing
        self.end = time.perf_counter()
        for thread in self._threads:
            thread.join()
        self.stop_slice()
        self.compiles = self.run.watch.since(self.before)
        self.spans = [(s.kind, s.start, s.end) for s in tracing.snapshot()
                      if s.start >= self.start]


def span_means_ms(spans: list) -> dict:
    """Mean milliseconds and count of each kind of (kind, start, end)
    span."""
    by_kind: dict[str, list[float]] = {}
    for kind, start, end in spans:
        by_kind.setdefault(kind, []).append(end - start)
    return {k: [1000 * sum(v) / len(v), len(v)]
            for k, v in sorted(by_kind.items())}


def annotate(name: str):
    """A host span of the harness's own in the profiler's trace."""
    import jax
    return jax.profiler.TraceAnnotation(trace.PREFIX + name)
