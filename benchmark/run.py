#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

From the root of a checkout.  The cell (``BENCHMARK.json``) names a
configuration and a traffic mix; the mix's ``kind`` names its runner,
``kinds/<kind>.py``.  One process holds the cell's chips: set-up and
warm-up, then a window of ``--seconds``, then the comparison with the
plain reference.  With ``--trace 0`` the result carries the cell's
end-to-end metrics; with ``--trace 1`` slices of the window run under
the profiler and the result carries the per-layer metrics that
``metrics/<name>.py`` read from those traces and the system's spans.

Earlier lines of standard output are JSON records of set-up and the
window; the last line is the result.  The numbers compared, each with
its limit, are the last lines of standard error and the result's last
key.  Where JAX finds no TPU, or fewer chips than the cell asks for, the
run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = BENCH / ".cache"
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(ROOT))

from harness import device, spec  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run_cell(args, bench: dict, overrides: dict | None = None,
             require_chip: bool = True, t0: float | None = None) -> dict:
    """Run the cell and return its result line.  ``overrides`` replace
    keys of the configuration and the traffic (tests run the same path
    at small sizes, with ``require_chip`` off, on the CPU)."""
    from harness import trace, window

    cell = spec.workload(bench, args.workload)
    cfg = {**spec.config(bench, ROOT, cell["config"]),
           **(overrides or {}).get("config", {})}
    traffic = {**spec.traffic(cell["traffic"]),
               **(overrides or {}).get("traffic", {})}
    dev = (device.require_chips(cell["chips"]) if require_chip
           else device.describe(cell["chips"]))
    run = window.Run(cfg, traffic, args.seed, args.seconds, bool(args.trace),
                     CACHE, T0 if t0 is None else t0, device.CompileWatch(),
                     cell["chips"])
    out = spec.kind(traffic["kind"])(run)
    for key in ("setup", "window", "reference", "errors"):
        if key in out.notes:
            print(json.dumps({key: out.notes[key]}), flush=True)

    result = {"correct": out.failed == 0 and all(
        value <= limit for value, limit in out.checks.values()),
        "attempted": out.attempted, "failed": out.failed, "metrics": {},
        "device": {**dev, "memory_peak_bytes": out.memory_peak_bytes}}
    if not args.trace:
        for m in spec.end_to_end(bench, cell["name"]):
            result["metrics"][m["name"]] = {"value": out.metrics[m["name"]],
                                            "unit": m["unit"]}
    else:
        reduced = trace.combine([
            trace.reduce(trace.load(trace.find_xspace(directory)),
                         cell["chips"], out.spans, start, seconds)
            for directory, start, seconds in out.slices])
        for directory, _, _ in out.slices:
            shutil.rmtree(directory, ignore_errors=True)
        print(json.dumps({"trace": {"slices": len(out.slices), **{
            k: reduced.get(k) for k in ("busy_s", "window_s", "runs")}}}),
            flush=True)
        readings = Readings(out.spans, reduced, out.traced)
        for m in spec.per_layer(bench, cell["name"]):
            value = spec.reader(m["name"])(readings)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        if reduced:
            result["device"]["busy_s"] = reduced["busy_s"]
            result["device"]["window_s"] = reduced["window_s"]
            result["breakdown"] = trace.breakdown(reduced)
    result["checks"] = {name: {"value": value, "limit": limit}
                        for name, (value, limit) in out.checks.items()}
    return result


class Readings:
    """What a per-layer metric reader reads: the system's spans in the
    window as (kind, start, end) on the ``perf_counter`` clock, the traced
    slices' reduction (``trace.combine``; empty where no device plane was
    traced) and the work those slices covered (``Outcome.traced``)."""

    def __init__(self, spans, trace_reduced, traced):
        self.spans = spans
        self.trace = trace_reduced
        self.traced = traced


def main(argv=None) -> int:
    args = parse(argv)
    bench = spec.load(ROOT)
    device.pin_compile_cache(CACHE)
    result = run_cell(args, bench)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
