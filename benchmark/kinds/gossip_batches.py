"""Traffic kind ``gossip_batches``: batches of gossip attestation signature
sets verified one after another through the crypto backend, the next
submitted when the verdict returns.

The traffic file sets the batch (``sets_per_batch`` over
``messages_per_batch`` AttestationData roots of ``committees_per_slot``
committees a slot), a ring of ``ring`` distinct batches with batch
``corrupt_batch`` invalid at ``corrupt_position``, and the warm-up twin
of batch 0, invalid at ``warmup_corrupt_position``.  A traced run traces
the first ``trace_seconds`` of the window's first batch: the host's parse
and preparation of the sets, then the first device stages.
"""
from __future__ import annotations

import time

from harness import bls_gen, device
from harness.window import Outcome, Run, annotate, span_means_ms
from reference import bls


def run(run: Run) -> Outcome:
    from lighthouse_tpu.crypto import bls as program_bls
    from lighthouse_tpu.crypto.bls import SignatureSet
    from lighthouse_tpu.crypto.bls12_381.curve import G1Point

    cfg, traffic = run.cfg, run.traffic
    threads = traffic["host_threads"]
    t = time.perf_counter()
    bls.lib(run.cache / "native")
    sks, pks = bls_gen.pool_keys(cfg["pool_keys"], threads)
    ring = bls_gen.ring(cfg, traffic, run.seed, sks, pks, threads)
    n = traffic["sets_per_batch"]
    warm = bls_gen.corrupted(ring[0],
                             int(n * traffic["warmup_corrupt_position"]))
    sets = [[SignatureSet(sig, [pk], msg) for sig, pk, msg in batch]
            for batch in ring + [warm]]
    sign_s = time.perf_counter() - t

    backend = program_bls.set_backend(cfg["crypto_backend"])
    stages = []
    t = time.perf_counter()
    if cfg["crypto_backend"] == "tpu":
        # the node's validator pubkey cache, loaded at start-up
        for pk in pks:
            backend._pk_cache[pk] = G1Point(*bls.g1_affine(pk))
        stages = warm_stage_programs(backend, sets[-1], threads)
    programs_s = time.perf_counter() - t
    t = time.perf_counter()
    warm_verdict = program_bls.verify_signature_sets(sets[-1])
    warm_batch_s = time.perf_counter() - t

    done = []
    with run.window() as w:
        i = 0
        while w.open():
            b = i % len(ring)
            if i == 0:
                w.timed_slice(traffic["trace_seconds"])
            t0 = time.perf_counter()
            with annotate("verify_batch"):
                verdict = program_bls.verify_signature_sets(sets[b])
            done.append((b, verdict, t0, time.perf_counter()))
            i += 1
    peak = device.memory_peak_bytes(run.chips)
    seen = sorted({b for b, *_ in done})
    rands = [(2 * i + 1) * 0x9E3779B97F4A7C15 % 2**64 | 1 for i in range(n)]
    t = time.perf_counter()
    expected = {b: bls.verify_sets(ring[b], rands, threads) for b in seen}
    expected_warm = bls.verify_sets(warm, rands, threads)
    reference_s = time.perf_counter() - t
    mismatched = sum(verdict != expected[b] for b, verdict, *_ in done)
    checks = {"verdict_mismatch": (mismatched, 0),
              "warmup_verdict_mismatch": (int(warm_verdict != expected_warm),
                                          0)}
    span = done[-1][3] - done[0][2]
    return Outcome(
        metrics={"gossip_sets_per_s": n * len(done) / span,
                 "setup_s": w.setup_s},
        attempted=len(done), failed=mismatched, checks=checks,
        spans=w.spans, slices=w.slices, memory_peak_bytes=peak,
        notes={"setup": {"sign_s": sign_s, "stage_programs": len(stages),
                         "programs_s": programs_s,
                         "warm_batch_s": warm_batch_s},
               "window": {"seconds": w.end - w.start, "batches": len(done),
                          "batch_s": [d[3] - d[2] for d in done],
                          **w.compiles, "spans_ms": span_means_ms(w.spans),
                          "slices": w.slice_record()},
               "reference": {"seconds": reference_s, "verdicts": {
                   str(b): v for b, v in expected.items()}}})


def warm_stage_programs(backend, sets, threads: int) -> list[str]:
    """Compile, or load from the persistent cache, the BLS stage programs
    that a verify of ``sets`` dispatches, and no other shape: the jitted
    stages found by tracing the backend's device half on this batch's own
    host preparation, compiled in the backend's start-up threads."""
    from concurrent.futures import ThreadPoolExecutor

    import jax
    import numpy as np

    from lighthouse_tpu.crypto.bls import tpu_backend as tb
    from lighthouse_tpu.ops import bls12_381 as k

    small, big = tb.lane_options()
    lanes = small if len(sets) <= small else big
    prep = tb.host_prepare(*tb.parse_sets(backend, sets), lanes, small)
    arrays = {name: v for name, v in prep.items()
              if isinstance(v, np.ndarray)}
    jit_type = type(k.final_exponentiation)
    jitted = {f.__name__: f for f in vars(k).values()
              if isinstance(f, jit_type)}
    traced = jax.make_jaxpr(lambda a: list(
        tb.device_checks({**prep, **a}, lanes)))(arrays)
    jobs = {}
    for eqn in traced.eqns:
        name = eqn.params.get("name")
        if name in jitted:
            args = tuple(jax.ShapeDtypeStruct(
                v.aval.shape, v.aval.dtype, weak_type=v.aval.weak_type)
                for v in eqn.invars)
            jobs[(name, tuple((a.shape, a.dtype) for a in args))] = \
                (jitted[name], args)
    todo = [job for key, job in jobs.items() if key not in _WARMED]
    with ThreadPoolExecutor(min(threads, tb.COMPILE_THREADS)) as pool:
        list(pool.map(lambda job: job[0].lower(*job[1]).compile(), todo))
    _WARMED.update(jobs)
    return sorted({name for name, _ in jobs})


#: stage programs already compiled in this process (a later run of
#: ``tests/seeds.py`` in the same process finds them warm)
_WARMED: set = set()
