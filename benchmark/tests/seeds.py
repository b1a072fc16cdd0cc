#!/usr/bin/env python3
"""Readings of a cell's compared numbers over many seeds, in one process
on the chip, for the program as it is, or with its control or another
fault of ``faults.py`` planted underneath.

    python3 benchmark/tests/seeds.py --workload <name> --seconds <s> \
        [--seeds 11,12] [--fault <name> --fault-seeds 13,14]

Each seed runs the cell's whole path (set-up, warm-up, a window of
``--seconds``, the comparison): first the ``--seeds`` as the program
is, then the ``--fault-seeds`` with the fault planted.  Programs
compiled for the first seed stay in the process, so later seeds set up
faster.  One JSON line per seed: the seed, the fault, ``correct`` and
the compared numbers.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent))
sys.path.insert(2, str(Path(__file__).resolve().parent))

import run  # noqa: E402
from harness import device, spec  # noqa: E402


def main(argv=None) -> int:
    import pytest

    import faults

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--fault", default=None,
                    help="a function of faults.py, e.g. pairing_skipped")
    ap.add_argument("--fault-seeds", default="")
    args = ap.parse_args(argv)
    bench = spec.load(run.ROOT)
    device.pin_compile_cache(run.CACHE)
    runs = [(None, s) for s in args.seeds.split(",") if s] + \
        [(args.fault, s) for s in args.fault_seeds.split(",") if s]
    for fault, seed in runs:
        with pytest.MonkeyPatch.context() as mp:
            if fault:
                getattr(faults, fault)(mp)
            cell_args = run.parse(["--workload", args.workload, "--seed",
                                   str(seed), "--seconds", str(args.seconds)])
            t0 = time.perf_counter()
            result = run.run_cell(cell_args, bench, t0=t0)
            print(json.dumps({"seed": int(seed), "fault": fault,
                              "correct": result["correct"],
                              "attempted": result["attempted"],
                              "failed": result["failed"],
                              "metrics": result["metrics"],
                              "checks": result["checks"],
                              "wall_s": time.perf_counter() - t0}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
