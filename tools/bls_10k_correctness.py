"""Flagship-batch correctness run: n=10,000 signature sets through the
static-shape TPU pipeline (VERDICT r3 "next" #1c).

The 10k gossip batch (BASELINE.md config 3) had never been executed at
size anywhere before round 4; this runs it on whatever platform jax
selects, exercising the exact [10240]-lane programs the TPU bench uses:

  python tools/bls_10k_correctness.py            # writes PERF_10K_CPU.json

Checks BOTH polarities — a masking bug that silently identity-masks real
lanes would pass the positive check alone.
"""
import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
os.environ.setdefault("LHTPU_BLS_LANES", "10240")
from lighthouse_tpu.utils import compile_cache  # noqa: E402

compile_cache.configure()

N = int(os.environ.get("LHTPU_10K_N", "10000"))
OUT = os.environ.get("LHTPU_10K_OUT",
                     os.path.join(_REPO, "PERF_10K_CPU.json"))


def main():
    import jax
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.crypto.bls import SignatureSet
    from lighthouse_tpu.crypto.bls.cpp_backend import CppBackend
    from lighthouse_tpu.crypto.bls.tpu_backend import static_lanes

    signer = CppBackend()
    # LHTPU_10K_SHARED=<G>: spread the N sets over G distinct messages,
    # modelling a real gossip attestation batch (~128 distinct
    # AttestationData per 10k attestations — PERF_MODEL.md §3.1); the
    # same-message aggregation then runs the hash/Miller stages at the
    # SMALL static shape.  Default: all messages distinct (worst case).
    shared = int(os.environ.get("LHTPU_10K_SHARED", "0"))
    t0 = time.perf_counter()
    sets = []
    for i in range(N):
        mi = (i % shared) if shared else i
        msg = mi.to_bytes(32, "little")
        sk = 1000 + i
        sets.append(SignatureSet(signer.sign(sk, msg),
                                 [signer.sk_to_pk(sk)], msg))
    sign_s = time.perf_counter() - t0

    tpu = bls.set_backend("tpu")
    t0 = time.perf_counter()
    ok = tpu.verify_signature_sets(sets)
    cold_s = time.perf_counter() - t0

    # LHTPU_10K_FAST=1: one pass only (the XLA CPU fallback runs ~4
    # sigs/s, so the 3-pass protocol is ~2.5 h; the negative polarity is
    # covered at smaller lanes by tests + the driver dryrun)
    fast = bool(os.environ.get("LHTPU_10K_FAST"))
    if fast:
        ok_warm, warm_s = ok, cold_s
        rejected, neg_s = None, 0.0
    else:
        t0 = time.perf_counter()
        ok_warm = tpu.verify_signature_sets(sets)
        warm_s = time.perf_counter() - t0

        # negative: corrupt ONE mid-batch message; batch must fail
        bad = list(sets)
        k = N // 2
        bad[k] = SignatureSet(bad[k].signature, bad[k].pubkeys,
                              b"\xee" * 32)
        t0 = time.perf_counter()
        rejected = not tpu.verify_signature_sets(bad)
        neg_s = time.perf_counter() - t0

    rec = {
        "n_sigs": N,
        "distinct_messages": shared or N,
        "lanes": static_lanes(),
        "platform": jax.default_backend(),
        "verify_ok": bool(ok) and bool(ok_warm),
        # None (json null) when LHTPU_10K_FAST skipped the negative pass
        "reject_ok": None if rejected is None else bool(rejected),
        "sign_seconds": round(sign_s, 1),
        "cold_seconds": round(cold_s, 1),
        "warm_seconds": round(warm_s, 1),
        "negative_seconds": round(neg_s, 1),
        "warm_sigs_per_sec": round(N / warm_s, 2),
    }
    line = json.dumps(rec)
    print(line)
    with open(OUT, "w") as f:
        f.write(line + "\n")


if __name__ == "__main__":
    main()
