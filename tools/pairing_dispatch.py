"""Host time of one batch pairing check's dispatch, on the default device.

Times warm calls of ``ops.bls12_381.pairing_check_batch`` at the BLS
batches' pairing shape (129 pairs: 128 message lanes and the aggregate's):
``return_ms``, the host's return, once every program and eager operation
of the check is dispatched and nothing is read, and ``ready_ms``, the
verdict's ready time; then ``fp12_eq`` against one, called eagerly on a
final exponentiation's output as the check's tail once did, alone.
Prints one JSON line.

    python tools/pairing_dispatch.py [--pairs 129] [--reps 5]
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def _timed(call, reps: int) -> dict:
    """Host return and ready time of ``call()``, in ms, per warm rep."""
    call().block_until_ready()                     # compile, warm
    out = {"return_ms": [], "ready_ms": []}
    for _ in range(reps):
        t0 = time.perf_counter()
        result = call()
        t1 = time.perf_counter()
        result.block_until_ready()
        t2 = time.perf_counter()
        out["return_ms"].append(round((t1 - t0) * 1e3, 3))
        out["ready_ms"].append(round((t2 - t0) * 1e3, 3))
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--pairs", type=int, default=129)
    ap.add_argument("--reps", type=int, default=5)
    args = ap.parse_args()

    from lighthouse_tpu.utils import compile_cache
    compile_cache.configure()
    import jax
    import numpy as np

    from lighthouse_tpu.crypto.bls12_381 import G1_GENERATOR, G2_GENERATOR
    import lighthouse_tpu.ops.bls12_381 as k

    n = args.pairs
    (gx, gy), (hx, hy) = G1_GENERATOR.to_affine(), G2_GENERATOR.to_affine()
    px, py, qx, qy = (jax.device_put(np.broadcast_to(a, (n,) + a.shape[1:]))
                      for a in (k.fp_encode([int(gx)]),
                                k.fp_encode([int(gy)]),
                                k.fp2_encode([hx]), k.fp2_encode([hy])))
    mask = np.ones(n, bool)
    mask[n // 2:-1] = False                        # padding lanes
    check = _timed(lambda: k.pairing_check_batch(px, py, qx, qy, mask),
                   args.reps)
    out = k.final_exponentiation(
        k.fp12_product(k.miller_loop_batch(px, py, qx, qy)))
    eq = _timed(lambda: k.fp12_eq(out[None], k.fp12_one_like((1,)))[0],
                args.reps)
    print(json.dumps({"device": jax.devices()[0].device_kind, "pairs": n,
                      "pairing_check_batch": check, "fp12_eq_eager": eq}))


if __name__ == "__main__":
    main()
