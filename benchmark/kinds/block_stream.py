"""Traffic kind ``block_stream``: a segment of blocks imported back to back
through ``BeaconProcessor`` as gossip blocks, one in flight.

The traffic file sets the segment (``start_slot_in_epoch``,
``segment_blocks``) and what each block carries (``attesting_committees``,
``attesting_bits``, ``sync_bits``: shares of the prior slot's committees,
of each committee's bits and of the sync committee).  When the segment is
spent, a fresh chain is anchored on a copy of the anchor state and its
first ``settle_imports`` blocks are imported outside the latencies: a
fresh chain's first imports pay one-off work (the anchor's store
migration, caches built on first use) that a running node does not pay
per block.
"""
from __future__ import annotations

import threading
import time

from harness import chain_gen, device
from harness.window import Outcome, Run, annotate, span_means_ms
from reference import altair


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
    program_bls.set_backend(cfg["crypto_backend"])
    spec = network_spec(cfg)

    t = time.perf_counter()
    ref, plain_anchor = chain_gen.anchor_state(
        cfg, cfg["validators"], run.seed, traffic["start_slot_in_epoch"])
    ref_anchor_root = ref.root()
    plain = chain_gen.segment(ref, traffic, run.seed)
    run.reference_s = time.perf_counter() - t

    t = time.perf_counter()
    anchor = chain_gen.program_state(ref, spec)
    del ref
    anchor_root = anchor.hash_tree_root()   # device trees, shared by copies
    anchor_block = chain_gen.program_block(plain_anchor, spec)
    blocks = [chain_gen.program_block(b, spec) for b in plain]
    clock = plain[-1]["slot"]
    anchor_s = time.perf_counter() - t

    proc = BeaconProcessor(num_workers=traffic["processor_workers"])
    proc.start()

    def import_one(chain, signed):
        done, out = threading.Event(), {}

        def work():
            try:
                out["root"] = chain.process_block(signed)
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
        for signed in blocks[:traffic["settle_imports"]]:
            mark = time.perf_counter()
            latency, ok, err = import_one(chain, signed)
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
        # warm-up: one pass over the segment, then a settled chain's
        # imports until one compiles nothing
        t = time.perf_counter()
        chain = settled_chain()
        for signed in blocks[traffic["settle_imports"]:]:
            _, ok, err = import_one(chain, signed)
            if not ok:
                errors.append(f"warm-up: {err}")
        chain = settled_chain()
        pos = traffic["settle_imports"]
        while pos < len(blocks):
            before = run.watch.snapshot()
            _, ok, err = import_one(chain, blocks[pos])
            pos += 1
            if not ok:
                errors.append(f"warm-up: {err}")
            if run.watch.since(before)["compiles"] == 0:
                break
        warm_imports = len(blocks) + pos
        warm_s = time.perf_counter() - t
        warm_settled = list(settled)
        settled.clear()

        latencies, done_times, reanchored = [], [], []
        with run.window() as w:
            w.start_slice()
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
                done_times.append(time.perf_counter())
                if w.slice_s() >= traffic["trace_seconds"]:
                    w.stop_slice()
                if not ok:
                    errors.append(f"slot {plain[pos]['slot']}: {got}")
                pos += 1
    finally:
        proc.stop()
    peak = device.memory_peak_bytes(run.chips)
    # the last import's block root and post-state root, as the system
    # holds them, against the reference's
    last = plain[pos - 1]
    post = chain._state_for(got) if ok else None
    checks = {
        "anchor_root_mismatch": (int(anchor_root != ref_anchor_root), 0),
        "blocks_refused": (len(errors), 0),
        "last_root_mismatch": (int(
            not ok or got != altair.block_root(last, p) or post is None
            or post.hash_tree_root() != last["state_root"]), 0),
    }
    window_errors = sum(e.startswith("slot") for e in errors)
    # the system's spans of the window's imports, not of re-anchoring
    spans = [sp for sp in w.spans
             if not any(a <= sp[1] <= b for a, b in reanchored)]
    return Outcome(
        metrics={"block_import_ms": 1000 * sum(latencies) / len(latencies),
                 "setup_s": w.setup_s},
        attempted=len(latencies), failed=window_errors, checks=checks,
        traced={"blocks": w.traced_count(done_times)}, spans=spans,
        slices=w.slices, memory_peak_bytes=peak,
        notes={"setup": {"reference_s": run.reference_s,
                         "anchor_s": anchor_s, "warmup_s": warm_s,
                         "warmup_imports": warm_imports,
                         "settle_ms": warm_settled},
               "window": {"seconds": w.end - w.start, "blocks":
                          len(latencies), "reanchors": len(reanchored),
                          "reanchor_s": sum(b - a for a, b in reanchored),
                          **w.compiles, "spans_ms": span_means_ms(spans),
                          "slices": w.slice_record(),
                          "latencies_ms": [1000 * x for x in latencies],
                          "settle_ms": settled},
               "errors": errors[:5]})


def network_spec(cfg: dict):
    """The system's chain spec for the configuration's network, checked
    against the preset the configuration states."""
    from lighthouse_tpu.specs import chain_spec
    spec = getattr(chain_spec, f"{cfg['network']}_spec")()
    for key, value in cfg["preset"].items():
        got = getattr(spec.preset, key.lower(), value)
        if got != value:
            raise SystemExit(f"benchmark: the system's {cfg['network']} "
                             f"preset has {key}={got}, the configuration "
                             f"{value}")
    return spec
