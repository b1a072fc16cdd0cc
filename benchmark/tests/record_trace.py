#!/usr/bin/env python3
"""Record the small profiler trace that ``test_trace.py`` reduces.

    python3 benchmark/tests/record_trace.py <out.xplane.pb>

On the chip: inside the harness's window annotation, two runs of a named
jitted program, a few eager operations, and a host-side pause with its
own annotation, so the trace has device busy time, program names, eager
runs and a named idle gap.
"""
from __future__ import annotations

import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import trace  # noqa: E402


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp

    @jax.jit
    def square_sum(x):
        return (x * x).sum(axis=0)

    x = jnp.ones((1024, 1024), jnp.float32)
    square_sum(x).block_until_ready()            # compile outside
    (x + 1).block_until_ready()
    tmp = Path(tempfile.mkdtemp())
    try:
        trace.start(tmp)
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            for _ in range(2):
                square_sum(x).block_until_ready()
            with jax.profiler.TraceAnnotation(trace.PREFIX + "host_pause"):
                time.sleep(0.05)
            (x + 1).block_until_ready()
        jax.profiler.stop_trace()
        shutil.copy(trace.find_xspace(tmp), out)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
