"""Traffic kind ``signed_block_stream``: ``block_stream`` with every block
signature real, verified by the system's BLS backend inside the import.

The registry's keys are real (``harness/signed_gen.py``), and the node
loads them into its backend's pubkey table at start-up
(``load_pubkeys``); the BLS stage programs load meanwhile.  The traffic
file sets the segment and what each block carries as ``block_stream``'s
does.  In warm-up two twins of the first block after the settled ones
are submitted first (``signed_gen.twins``): in each one signature is
another set's valid one, in the batch's first half in one and its second
half in the other, and the node must refuse both and then import the
valid block.  A traced run
profiles, first in its window, one block's signature sets through the
backend's parsing, device input preparation and pubkey sums, with
nothing else running, and leaves that out of the window's spans: a
profile stopped while an import runs on took ~234 s to stop, and one
that holds a whole 128-lane batch 190 s and ~17 GB of host memory, on a
one-chip v5e host.  After the window the frozen C++ reference verifies
the signatures of each distinct block the window imported, and of the
twins.
"""
from __future__ import annotations

import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from harness import chain_gen, device, signed_gen
from harness.window import Outcome, Run, annotate, span_means_ms
from kinds.block_stream import network_spec
from reference import altair, bls


def run(run: Run) -> Outcome:
    from lighthouse_tpu.beacon_processor import (
        BeaconProcessor, Work, WorkType,
    )
    from lighthouse_tpu.chain.builder import BeaconChainBuilder
    from lighthouse_tpu.chain.execution import MockExecutionLayer
    from lighthouse_tpu.crypto import bls as program_bls
    from lighthouse_tpu.obs import tracing
    from lighthouse_tpu.utils.slot_clock import ManualSlotClock

    cfg, traffic = run.cfg, run.traffic
    p = cfg["preset"]
    backend = program_bls.set_backend(cfg["crypto_backend"])
    if not hasattr(backend, "load_pubkeys"):
        raise SystemExit("benchmark: the system's BLS backend has no "
                         "pubkey table to load (load_pubkeys)")
    spec = network_spec(cfg)
    threads = traffic["host_threads"]

    # the stage programs a full block's batch dispatches, compiled (or
    # loaded) in threads while the keys and the table are made; the
    # states are built after, as their Python work contends with the
    # programs' tracing for the interpreter lock
    t0 = time.perf_counter()
    bls.lib(run.cache / "native")
    with ThreadPoolExecutor(1) as background:
        programs = background.submit(
            warm_stage_programs, backend,
            signed_gen.block_keys(cfg, traffic), cfg["validators"],
            threads) \
            if cfg["crypto_backend"] == "tpu" else None

        t = time.perf_counter()
        sks, pubkeys = signed_gen.validator_keys(
            cfg["validators"], threads, run.cache / "native")
        keys_s = time.perf_counter() - t
        progress("keys", keys_s)

        # the node's validator pubkey cache, filled in bulk at start-up
        t = time.perf_counter()
        loaded = backend.load_pubkeys(pubkeys)
        table_s = time.perf_counter() - t
        progress("table", table_s)

        stages = programs.result() if programs else []
    programs_s = time.perf_counter() - t0
    program_loads = run.watch.snapshot()    # compiles and cache loads
    progress("programs", programs_s)

    t = time.perf_counter()
    ref, plain_anchor = signed_gen.anchor_state(
        cfg, pubkeys, run.seed, traffic["start_slot_in_epoch"])
    ref_anchor_root = ref.root()
    plain, plain_twins = signed_gen.signed_segment(ref, traffic, run.seed,
                                                   sks)
    del sks
    run.reference_s = time.perf_counter() - t
    progress("reference", run.reference_s)

    t = time.perf_counter()
    anchor = chain_gen.program_state(ref, spec)
    del ref
    anchor_root = anchor.hash_tree_root()
    anchor_block = chain_gen.program_block(plain_anchor, spec)
    blocks = [signed(b, spec) for b in plain]
    twins = [signed(b, spec) for b in plain_twins]
    clock = plain[-1]["slot"]
    anchor_s = time.perf_counter() - t
    progress("anchor", anchor_s)

    proc = BeaconProcessor(num_workers=traffic["processor_workers"])
    proc.start()

    def import_one(chain, signed_block):
        done, out = threading.Event(), {}

        def work():
            try:
                out["root"] = chain.process_block(signed_block)
            except Exception as exc:           # a refused block
                out["error"] = repr(exc)
            finally:
                done.set()

        t0 = time.perf_counter()
        proc.submit(Work(kind=WorkType.GOSSIP_BLOCK, run=work))
        if not done.wait(traffic["import_timeout_s"]):
            out["error"] = "import did not finish"
        latency = time.perf_counter() - t0
        ok = "error" not in out and chain.fork_choice.contains_block(
            out["root"])
        return latency, ok, out.get("error") or out["root"]

    errors, settled = [], []

    def settled_chain():
        """A fresh chain on a copy of the anchor state, with its first
        ``settle_imports`` blocks imported; each import's latency and the
        system's spans in it are kept for the record."""
        chain = (BeaconChainBuilder(spec)
                 .weak_subjectivity_anchor(anchor.copy(), anchor_block)
                 .slot_clock(ManualSlotClock(0, spec.seconds_per_slot,
                                             current_slot=clock))
                 .execution_layer(MockExecutionLayer())
                 .build())
        for signed_block in blocks[:traffic["settle_imports"]]:
            mark = time.perf_counter()
            latency, ok, err = import_one(chain, signed_block)
            if not ok:
                errors.append(f"settle: {err}")
            spans: dict[str, float] = {}
            for s in tracing.snapshot():
                if s.start >= mark:
                    spans[s.kind] = spans.get(s.kind, 0.0) + \
                        1000 * (s.end - s.start)
            settled.append([1000 * latency, spans])
        return chain

    try:
        # warm-up: a settled chain, the twins, then the rest of the
        # segment, which the window imports again on fresh chains: every
        # shape of the window is compiled (the state root's updates vary
        # by block); the window starts by re-anchoring
        t = time.perf_counter()
        chain = settled_chain()
        twin_imports = [import_one(chain, twin)[1:] for twin in twins]
        for signed_block in blocks[traffic["settle_imports"]:]:
            _, ok, err = import_one(chain, signed_block)
            if not ok:
                errors.append(f"warm-up: {err}")
        pos = len(blocks)
        warm_imports = pos + len(twins)
        warm_s = time.perf_counter() - t
        progress("warm-up", warm_s)
        warm_settled = list(settled)
        settled.clear()

        latencies, reanchored, imported, probe = [], [], set(), []
        with run.window() as w:
            if run.traced:
                # the probe's time and spans are not the window's
                t = time.perf_counter()
                trace_pubkey_sums(w, backend, plain[-1], pubkeys)
                probe.append((t, time.perf_counter()))
                w.deadline += probe[0][1] - t
            while w.open():
                if pos == len(blocks):
                    t = time.perf_counter()
                    with annotate("re_anchor"):
                        chain = settled_chain()
                    pos = traffic["settle_imports"]
                    reanchored.append((t, time.perf_counter()))
                with annotate("import_block"):
                    latency, ok, got = import_one(chain, blocks[pos])
                latencies.append(latency)
                if ok:
                    imported.add(pos)
                else:
                    errors.append(f"slot {plain[pos]['slot']}: {got}")
                pos += 1
    finally:
        proc.stop()
    progress("window", w.end - w.start)
    peak = device.memory_peak_bytes(run.chips)
    last = plain[pos - 1]
    post = chain._state_for(got) if ok else None

    # the reference's verdicts on the window's blocks and on the twins
    t = time.perf_counter()
    rng = np.random.default_rng((run.seed, 2))
    verdicts = {plain[i]["slot"]: signed_gen.verify_block(
        plain[i], pubkeys, rng) for i in sorted(imported)}
    twin_refs = [signed_gen.verify_block(b, pubkeys, rng)
                 for b in plain_twins]
    reference_s = time.perf_counter() - t
    checks = {
        "anchor_root_mismatch": (int(anchor_root != ref_anchor_root), 0),
        "blocks_refused": (len(errors), 0),
        "last_root_mismatch": (int(
            not ok or got != altair.block_root(last, p) or post is None
            or post.hash_tree_root() != last["state_root"]), 0),
        "reference_verdict_mismatch": (
            sum(not v for v in verdicts.values()) + sum(twin_refs), 0),
        "invalid_block_accepted": (
            sum(ok for ok, _ in twin_imports), 0),
    }
    window_errors = sum(e.startswith("slot") for e in errors)
    spans = [sp for sp in w.spans
             if not any(a <= sp[1] <= b for a, b in reanchored + probe)]
    return Outcome(
        metrics={"block_import_ms": 1000 * sum(latencies) / len(latencies),
                 "setup_s": w.setup_s},
        attempted=len(latencies), failed=window_errors, checks=checks,
        traced={"pk_aggregate_bytes": signed_gen.aggregated_bytes(
            cfg, traffic)},
        spans=spans, slices=w.slices, memory_peak_bytes=peak,
        notes={"setup": {"reference_s": run.reference_s, "keys_s": keys_s,
                         "anchor_s": anchor_s, "table_s": table_s,
                         "table_rows": loaded,
                         "stage_programs": len(stages),
                         "programs_s": programs_s,
                         "program_loads": program_loads, "warmup_s": warm_s,
                         "warmup_imports": warm_imports,
                         "twins": [got if not ok else "accepted"
                                   for ok, got in twin_imports],
                         "settle_ms": warm_settled},
               "window": {"seconds": w.end - w.start, "blocks":
                          len(latencies), "reanchors": len(reanchored),
                          "reanchor_s": sum(b - a for a, b in reanchored),
                          "probe_s": sum(b - a for a, b in probe),
                          **w.compiles, "spans_ms": span_means_ms(spans),
                          "slices": w.slice_record(),
                          "latencies_ms": [1000 * x for x in latencies],
                          "settle_ms": settled},
               "reference": {"seconds": reference_s, "verdicts": {
                   str(s): v for s, v in verdicts.items()},
                   "twins": twin_refs},
               "errors": errors[:5]})


def progress(phase: str, seconds: float) -> None:
    """A line on standard error as each set-up phase ends."""
    print(f"signed_block_stream: {phase} took {seconds:.1f} s",
          file=sys.stderr, flush=True)


def trace_pubkey_sums(w, backend, block: dict, pubkeys: np.ndarray
                      ) -> None:
    """Profile one slice: ``block``'s signature sets through the backend's
    set parsing, device input preparation and device pubkey sums, as its
    import's batch starts (``tpu_backend._verify_chunk``), until the sums
    are on the host's side of the device.  Nothing else runs meanwhile,
    so the profiler stops in seconds."""
    import jax

    from lighthouse_tpu.crypto.bls import SignatureSet
    from lighthouse_tpu.crypto.bls import tpu_backend as tb
    from lighthouse_tpu.obs import tracing

    sets = [SignatureSet(sig, [bytes(pk) for pk in pubkeys[signers]], msg)
            for sig, (signers, msg) in zip(signed_gen.block_signatures(block),
                                           block["sets"])]
    small, big = tb.lane_options()
    lanes = small if len(sets) <= small else big
    w.start_slice()
    prep = tb.host_prepare(*tb.parse_sets(backend, sets), lanes, small)
    prep["pk_table"] = backend.table.arrays()
    jax.block_until_ready(tb.pubkey_sums(prep, lanes))
    tracing.wait_device_spans()
    w.stop_slice()


def signed(block: dict, spec):
    """The system's ``SignedBeaconBlock`` for a plain block, with the
    block's own proposal signature."""
    out = chain_gen.program_block(block, spec)
    return type(out)(message=out.message, signature=block["signature"])


def warm_stage_programs(backend, keys: list[int], rows: int,
                        threads: int) -> list[str]:
    """Compile, or load from the persistent cache, the BLS stage programs
    that verifying a block's signature sets dispatches, and no other
    shape (``tpu_backend.compile_stage_programs``): sets shaped as a
    block's (``keys`` table rows each, a distinct message each), with
    the pubkey table at its shape once ``rows`` keys are loaded.  Needs
    neither the keys nor the table's rows, so it runs while they are
    made."""
    from lighthouse_tpu.crypto.bls import SignatureSet
    from lighthouse_tpu.crypto.bls import tpu_backend as tb
    from lighthouse_tpu.crypto.bls.pubkey_table import loaded_shape
    from lighthouse_tpu.crypto.bls12_381 import G1_GENERATOR, g1_compress

    sig = bls.sign_hashed(bls.hash_to_g2(b"shape"), 1)
    _, sig_xs, flags, _, _ = tb.parse_sets(backend, [SignatureSet(
        sig, [g1_compress(G1_GENERATOR)], b"")])
    n = len(keys)
    parsed = ([None] * n, sig_xs * n, flags * n,
              [bytes([j % 256, j // 256]) * 16 for j in range(n)],
              [np.zeros(m, np.int32) for m in keys])
    small, big = tb.lane_options()
    lanes = small if n <= small else big
    prep = {**tb.host_prepare(*parsed, lanes, small),
            "pk_table": loaded_shape(rows)}
    compiled = tb.compile_stage_programs(
        [(prep, lanes)], min(threads, tb.COMPILE_THREADS))
    return sorted({name for name, _ in compiled})
