"""graftscope tier-1 gate (obs/ tracing + JAX accounting + metrics).

Four layers:
1. tracing core — span nesting, the ring, Chrome export round-trip with
   monotonic properly-nested ts/dur, slot-anchored roots;
2. cross-thread propagation — ThreadGroup spawns and beacon-processor
   work-queue hops must keep one trace id end to end, and a harness
   ``process_gossip_block`` must yield ONE trace covering gossip-verify
   through db-write;
3. catalog completeness — every span kind maps to a declared histogram,
   and every declared histogram is fed by some code path (span kind or
   direct observe) or explicitly listed in ``EXTERNALLY_FED``;
4. runtime accounting — jax_compile_total increments on a forced shape
   change, host_readback counts transfer bytes, and the whole metrics
   catalog is a true no-op with prometheus stubbed out.
"""
import importlib
import json
import subprocess
import sys
import threading
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from lighthouse_tpu import obs  # noqa: E402
from lighthouse_tpu.api import metrics, metrics_defs  # noqa: E402
from lighthouse_tpu.obs import report as obs_report  # noqa: E402
from lighthouse_tpu.obs import tracing  # noqa: E402

SRC_FILES = sorted((REPO / "lighthouse_tpu").rglob("*.py"))


# -- 1. tracing core ---------------------------------------------------------

def test_span_nesting_ids_and_ring():
    obs.clear()
    with obs.span("block_import", slot=7) as root:
        assert obs.current_span() is root
        with obs.span("batch_signature") as child:
            assert child.trace_id == root.trace_id
            assert child.parent_id == root.span_id
    spans = obs.snapshot()
    assert [s.kind for s in spans] == ["batch_signature", "block_import"]
    assert spans[1].parent_id is None
    assert spans[1].attrs["slot"] == 7
    assert obs.current_span() is None


def test_span_rejects_unknown_kind():
    with pytest.raises(AssertionError, match="unknown span kind"):
        obs.span("no_such_stage")


def test_span_records_error_attr():
    obs.clear()
    with pytest.raises(ValueError):
        with obs.span("gossip_verify"):
            raise ValueError("boom")
    (s,) = obs.snapshot()
    assert s.attrs["error"] == "ValueError"


def test_ring_wraps_without_losing_order():
    ring = tracing.SpanRing(capacity=8)
    for i in range(20):
        s = tracing.Span("t", f"s{i}", None, "db_write")
        ring.push(s)
    got = [s.span_id for s in ring.snapshot()]
    assert got == [f"s{i}" for i in range(12, 20)]


def test_chrome_trace_roundtrips_and_nests():
    obs.clear()
    with obs.span("block_import"):
        with obs.span("state_transition"):
            with obs.span("tree_hash", slot=0):
                pass
        with obs.span("state_root"):
            pass
    doc = json.loads(json.dumps(obs.chrome_trace()))
    events = doc["traceEvents"]
    assert len(events) == 4
    by_id = {e["args"]["span_id"]: e for e in events}
    for e in events:
        assert e["ph"] == "X" and e["ts"] >= 0 and e["dur"] >= 0
        parent = e["args"].get("parent_id")
        if parent is not None:
            p = by_id[parent]
            # proper nesting: child interval inside the parent interval
            assert e["ts"] >= p["ts"] - 1e-9
            assert e["ts"] + e["dur"] <= p["ts"] + p["dur"] + 1e-6
    # monotonic: sorted by ts the root comes first
    ordered = sorted(events, key=lambda e: e["ts"])
    assert ordered[0]["name"] == "block_import"


def test_root_span_is_slot_anchored():
    from lighthouse_tpu.utils.slot_clock import ManualSlotClock
    clock = ManualSlotClock(0, 6, current_slot=11)
    clock.set_seconds_into_slot(2.5)
    tracing.set_slot_clock(clock)
    try:
        obs.clear()
        with obs.span("block_pipeline"):
            with obs.span("gossip_verify"):
                pass
        spans = {s.kind: s for s in obs.snapshot()}
        root = spans["block_pipeline"]
        assert root.attrs["slot"] == 11
        assert root.attrs["slot_offset_s"] == pytest.approx(2.5)
        # child spans don't repeat the anchor
        assert "slot_offset_s" not in spans["gossip_verify"].attrs
    finally:
        tracing.set_slot_clock(None)


# -- 2. cross-thread / cross-queue propagation -------------------------------

def test_threadgroup_spawn_propagates_trace():
    from lighthouse_tpu.utils.threads import ThreadGroup
    obs.clear()
    g = ThreadGroup("test")
    with obs.span("block_import") as root:
        g.spawn(_child_span)
        assert not g.join_all(timeout=5)
        root_ids = (root.trace_id, root.span_id)
    spans = {s.kind: s for s in obs.snapshot()}
    assert spans["db_write"].trace_id == root_ids[0]
    assert spans["db_write"].parent_id == root_ids[1]
    assert spans["db_write"].thread_id != spans["block_import"].thread_id


def _child_span():
    with obs.span("db_write"):
        pass


def test_beacon_processor_work_propagates_trace():
    from lighthouse_tpu.beacon_processor import (
        BeaconProcessor, Work, WorkType,
    )
    obs.clear()
    proc = BeaconProcessor(num_workers=2)
    proc.start()
    try:
        seen = {}

        def job():
            seen["ctx"] = obs.current_context()
            with obs.span("db_write"):
                pass

        with obs.span("block_import") as root:
            proc.submit(Work(WorkType.STATUS, job))
            assert proc.wait_idle(timeout=10)
            root_ids = (root.trace_id, root.span_id)
    finally:
        proc.stop()
    # the worker saw the submitting thread's trace
    assert seen["ctx"][0] == root_ids[0]
    spans = [s for s in obs.snapshot() if s.trace_id == root_ids[0]]
    kinds = {s.kind for s in spans}
    assert "processor_work" in kinds      # the queue-hop span itself
    assert "db_write" in kinds
    pw = next(s for s in spans if s.kind == "processor_work")
    assert pw.attrs["work_kind"] == "STATUS"


def test_wait_idle_returns_with_the_work_span_recorded(monkeypatch):
    import time

    from lighthouse_tpu.beacon_processor import (
        BeaconProcessor, Work, WorkType,
    )
    record = tracing._record

    def slow_record(s):
        if s.kind == "processor_work":
            time.sleep(0.3)
        record(s)

    monkeypatch.setattr(tracing, "_record", slow_record)
    obs.clear()
    proc = BeaconProcessor(num_workers=1)
    proc.start()
    try:
        proc.submit(Work(WorkType.STATUS, lambda: None))
        assert proc.wait_idle(timeout=10)
        kinds = [s.kind for s in obs.snapshot()]
    finally:
        proc.stop()
    assert kinds == ["processor_work"]


def _fresh_harness(validators=32):
    from lighthouse_tpu.chain.harness import BeaconChainHarness
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.specs import minimal_spec
    bls.set_backend("fake")
    return BeaconChainHarness(minimal_spec(), validators)


BLOCK_STAGES = {"gossip_verify", "batch_signature", "state_transition",
                "state_root", "fork_choice", "db_write"}


def test_process_gossip_block_is_one_trace_with_all_stages():
    """Acceptance gate: one harness block import yields ONE trace whose
    child spans cover every pipeline stage, and the report CLI's summary
    renders a p50/p95 table for them."""
    h = _fresh_harness()
    h.advance_slot()
    signed, _post = h.produce_signed_block()
    obs.clear()
    h.chain.process_gossip_block(signed)
    spans = obs.snapshot()
    roots = [s for s in spans if s.parent_id is None]
    assert len(roots) == 1 and roots[0].kind == "block_pipeline"
    trace_id = roots[0].trace_id
    in_trace = [s for s in spans if s.trace_id == trace_id]
    kinds = {s.kind for s in in_trace}
    assert BLOCK_STAGES <= kinds, kinds
    assert "block_import" in kinds
    # every stage span belongs to THE one trace
    for s in spans:
        if s.kind in BLOCK_STAGES:
            assert s.trace_id == trace_id
    # the per-stage report renders
    table = obs_report.render_table(obs_report.summarize_spans(in_trace))
    for stage in BLOCK_STAGES:
        assert stage in table
    # slot-anchored root (harness clock registered by the chain)
    assert "slot_offset_s" in roots[0].attrs


def test_log_records_carry_trace_ids():
    import logging
    from lighthouse_tpu.utils.log_buffer import LogBuffer
    buf = LogBuffer()
    log = logging.getLogger("lighthouse_tpu.test_tracing")
    log.addHandler(buf)
    log.setLevel(logging.INFO)
    try:
        with obs.span("block_import") as s:
            log.info("inside the trace")
            ids = (s.trace_id, s.span_id)
        log.info("outside any trace")
    finally:
        log.removeHandler(buf)
    inside, outside = buf.tail(2)
    assert inside["trace_id"] == ids[0] and inside["span_id"] == ids[1]
    assert "trace_id" not in outside


# -- 3. catalog completeness -------------------------------------------------

def test_every_span_kind_maps_to_a_declared_histogram():
    for kind, metric in tracing.SPAN_KINDS.items():
        assert metric in metrics_defs.CATALOG, (kind, metric)
        assert metrics_defs.CATALOG[metric][0] == "hist", (kind, metric)


def test_every_catalog_histogram_is_fed_or_external():
    """Closes the declared-but-never-fed gap: each histogram must be
    observed by a span kind that is actually opened somewhere, by a
    direct observe/timed call site, or be explicitly EXTERNALLY_FED."""
    sources = {}
    for f in SRC_FILES:
        sources[str(f)] = f.read_text()
    # span kinds opened anywhere (span("kind" ...))
    used_kinds = set()
    for path, text in sources.items():
        for kind in tracing.SPAN_KINDS:
            if f'span("{kind}"' in text or f"span('{kind}'" in text:
                used_kinds.add(kind)
    kind_of = {metric: kind for kind, metric in tracing.SPAN_KINDS.items()}
    unfed = []
    for name, (kind, _help) in metrics_defs.CATALOG.items():
        if kind != "hist":
            continue
        if name in metrics_defs.EXTERNALLY_FED:
            continue
        span_kind = kind_of.get(name)
        if span_kind is not None and span_kind in used_kinds:
            continue
        if any(f'"{name}"' in text for path, text in sources.items()
               if not path.endswith("api/metrics_defs.py")
               and not path.endswith("obs/tracing.py")):
            continue
        unfed.append(name)
    assert not unfed, f"declared but never fed: {unfed}"


def test_every_catalog_gauge_is_fed_or_external():
    """PR-9 satellite: the same completeness gate for gauges.  A gauge
    feeding site is an actual ``gauge("name", ...)`` / ``set_gauge(...)``
    call (regex, not a bare name mention — graftwatch's SLO registry and
    the doctor reference gauge *names* without feeding them), outside
    metrics_defs/metrics themselves; EXTERNALLY_FED is honored."""
    import re as _re
    sources = {}
    for f in SRC_FILES:
        sources[str(f)] = f.read_text()
    unfed = []
    for name, (kind, _help) in metrics_defs.CATALOG.items():
        if kind != "gauge":
            continue
        if name in metrics_defs.EXTERNALLY_FED:
            continue
        pat = _re.compile(
            r"(?:gauge|set_gauge)\(\s*\n?\s*[\"']" + _re.escape(name))
        if any(pat.search(text) for path, text in sources.items()
               if not path.endswith("api/metrics_defs.py")
               and not path.endswith("api/metrics.py")):
            continue
        unfed.append(name)
    assert not unfed, f"gauges declared but never set: {unfed}"


def test_externally_fed_entries_are_justified_and_declared():
    for name, why in metrics_defs.EXTERNALLY_FED.items():
        assert name in metrics_defs.CATALOG
        assert why.strip()


# -- 4. runtime accounting + metrics fallback --------------------------------

def test_host_readback_accounts_transfer_bytes():
    import numpy as np
    before = obs.jax_counters()["d2h_bytes"]
    out = obs.host_readback(np.ones(64, dtype=np.uint8))
    assert out.shape == (64,)
    assert obs.jax_counters()["d2h_bytes"] == before + 64
    before_h2d = obs.jax_counters()["h2d_bytes"]
    obs.account_transfer(128, "h2d")
    assert obs.jax_counters()["h2d_bytes"] == before_h2d + 128


def test_forced_shape_change_increments_jax_compile_total():
    """Runtime recompile detection: a tracked jit program called with a
    fresh input shape must bump jax_compile_total exactly once, and a
    repeat call must not."""
    import jax
    import jax.numpy as jnp
    f = obs.track_compiles("test.tracked", jax.jit(lambda x: x + 1))
    c0 = obs.jax_counters()["compiles"]
    f(jnp.ones(4))
    assert obs.jax_counters()["compiles"] == c0 + 1
    f(jnp.ones(4))                       # cache hit: no compile
    assert obs.jax_counters()["compiles"] == c0 + 1
    f(jnp.ones(8))                       # forced shape change
    assert obs.jax_counters()["compiles"] == c0 + 2


def test_sharded_merkleize_shape_change_is_observable():
    """The real parallel/ factory path: a different leaf count is a new
    program through the memoized jit(shard_map) — the compile counter
    must see it (the dynamic complement of recompile-hazard)."""
    import jax
    import numpy as np
    from lighthouse_tpu.ops import sha256 as k
    from lighthouse_tpu.parallel import (
        batch_mesh, shard_batch, sharded_merkleize,
    )
    assert len(jax.devices()) == 8
    mesh = batch_mesh(8)
    rng = np.random.default_rng(5)

    def run(n):
        raw = rng.integers(0, 256, size=(n, 32), dtype=np.uint8)
        leaves = k.chunks_to_words(raw.tobytes())
        return sharded_merkleize(mesh, shard_batch(mesh,
                                                   k.jnp_asarray(leaves)))

    h2d0 = obs.jax_counters()["h2d_bytes"]
    run(64)
    assert obs.jax_counters()["h2d_bytes"] > h2d0   # accounted placement
    c1 = obs.jax_counters()["compiles"]
    run(64)                                # same shape: cached
    assert obs.jax_counters()["compiles"] == c1
    run(128)                               # forced shape change
    assert obs.jax_counters()["compiles"] > c1


def test_bls_factory_shape_change_increments_compile_counter():
    """parallel/bls.py acceptance demonstration: a forced input-shape
    change through the sharded pairing factory increments
    jax_compile_total.  Compile-heavy on the CPU backend, so gated like
    the other sharded-BLS tests (the memoization identity check below
    runs un-gated)."""
    import os

    from lighthouse_tpu.obs.roofline import RooflineJit
    from lighthouse_tpu.parallel import batch_mesh
    from lighthouse_tpu.parallel.bls import _miller_product_fn

    mesh = batch_mesh(8)
    fn = _miller_product_fn(mesh, "batch")
    # factories are roofline-wrapped (compile accounting + cost records)
    assert isinstance(fn, RooflineJit)
    assert _miller_product_fn(mesh, "batch") is fn   # memoized

    if not os.environ.get("LHTPU_SLOW_TESTS"):
        pytest.skip("compile-heavy; set LHTPU_SLOW_TESTS=1 to run")
    import numpy as np
    from lighthouse_tpu.crypto.bls12_381 import (
        G1_GENERATOR, hash_to_g2, keygen_interop, sign, sk_to_pk,
    )
    from lighthouse_tpu.parallel import sharded_pairing_check

    def pairs(reps):
        g1s, g2s = [], []
        for i in range(reps):
            sk = keygen_interop(i + 1)
            msg = bytes([i]) * 32
            g1s += [G1_GENERATOR.neg(), sk_to_pk(sk)]
            g2s += [sign(sk, msg), hash_to_g2(msg)]
        import lighthouse_tpu.ops.bls12_381 as k
        px = k.fp_encode([int(p.to_affine()[0]) for p in g1s])
        py = k.fp_encode([int(p.to_affine()[1]) for p in g1s])
        qx = k.fp2_encode([p.to_affine()[0] for p in g2s])
        qy = k.fp2_encode([p.to_affine()[1] for p in g2s])
        return px, py, qx, qy

    assert bool(np.asarray(sharded_pairing_check(mesh, *pairs(4))))
    c0 = obs.jax_counters()["compiles"]
    assert bool(np.asarray(sharded_pairing_check(mesh, *pairs(4))))
    assert obs.jax_counters()["compiles"] == c0      # cached shape
    assert bool(np.asarray(sharded_pairing_check(mesh, *pairs(8))))
    assert obs.jax_counters()["compiles"] > c0       # forced shape change


def test_metrics_are_true_noops_without_prometheus(monkeypatch):
    """Satellite: with prometheus_client absent the whole catalog must
    import and run without touching the registry.  Since graftwatch,
    the helpers still mirror into obs.timeseries when it is loaded —
    the TRUE-no-op guarantee (never read the clock, zero dict churn)
    holds for a bare interpreter with NEITHER prometheus NOR the
    graftwatch sampler, i.e. pure crypto/ssz library users."""
    monkeypatch.setitem(sys.modules, "prometheus_client", None)
    importlib.reload(metrics)
    try:
        assert metrics._HAVE_PROM is False
        assert metrics.REGISTRY is None
        assert metrics.Histogram is None
        # the full catalog registers (as a no-op) and every helper runs
        assert metrics_defs.register_catalog() == len(metrics_defs.CATALOG)
        for name, (kind, _help) in metrics_defs.CATALOG.items():
            if kind == "counter":
                metrics_defs.count(name)
            elif kind == "gauge":
                metrics_defs.gauge(name, 1.0)
            else:
                metrics_defs.observe(name, 0.01)
                with metrics_defs.timed(name):
                    pass
        # graftwatch loaded -> timers DO read the clock (the sampler
        # needs durations even on a prometheus-free node)
        t = metrics.start_timer("beacon_block_processing_seconds")
        assert t._t0 is not None
        t.stop()
        # bare interpreter: hide the sampler too -> true no-op
        monkeypatch.setitem(sys.modules,
                            "lighthouse_tpu.obs.timeseries", None)
        t = metrics.start_timer("beacon_block_processing_seconds")
        assert t._t0 is None                 # never read the clock
        t.observe_duration()
        t.stop()
        with metrics.timer("beacon_block_processing_seconds"):
            pass
        # spans still work and still feed nothing
        obs.clear()
        with obs.span("block_import"):
            pass
        assert metrics._metrics == {}        # zero dict churn
    finally:
        monkeypatch.delitem(sys.modules, "prometheus_client",
                            raising=False)
        importlib.reload(metrics)
        metrics_defs.register_catalog()
    assert metrics._HAVE_PROM is True


def test_start_timer_records_one_observation():
    metrics_defs.register_catalog()
    t = metrics.start_timer("beacon_block_processing_db_write_seconds")
    t.observe_duration()
    t.observe_duration()                     # second stop is a no-op
    from prometheus_client import generate_latest
    text = generate_latest(metrics.REGISTRY).decode()
    assert "beacon_block_processing_db_write_seconds" in text


# -- report CLI ---------------------------------------------------------------

def test_trace_report_cli_renders_table(tmp_path):
    obs.clear()
    with obs.span("block_import"):
        with obs.span("state_root"):
            pass
    trace = tmp_path / "trace.json"
    trace.write_text(json.dumps(obs.chrome_trace()))
    out = subprocess.run(
        [sys.executable, str(REPO / "tools" / "trace" / "report.py"),
         str(trace)], capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert "block_import" in out.stdout and "state_root" in out.stdout
    assert "p95 ms" in out.stdout
    out_json = subprocess.run(
        [sys.executable, str(REPO / "tools" / "trace" / "report.py"),
         "--json", str(trace)], capture_output=True, text=True, timeout=60)
    data = json.loads(out_json.stdout)
    assert data["block_import"]["count"] == 1


def test_trace_report_cli_rejects_garbage(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text("not json")
    out = subprocess.run(
        [sys.executable, str(REPO / "tools" / "trace" / "report.py"),
         str(bad)], capture_output=True, text=True, timeout=60)
    assert out.returncode == 2


def test_tracing_http_endpoint_serves_chrome_trace():
    from lighthouse_tpu.api.backend import ApiBackend
    from lighthouse_tpu.api.http_server import BeaconApiServer
    import urllib.request
    h = _fresh_harness()
    h.extend_chain(2)
    srv = BeaconApiServer(ApiBackend(h.chain))
    srv.start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        with urllib.request.urlopen(base + "/lighthouse/tracing") as r:
            doc = json.loads(r.read())
        assert "traceEvents" in doc
        names = {e["name"] for e in doc["traceEvents"]}
        assert "block_import" in names
        with urllib.request.urlopen(
                base + "/lighthouse/tracing/summary") as r:
            summary = json.loads(r.read())["data"]
        assert "block_import" in summary
        with urllib.request.urlopen(base + "/lighthouse/tracing/jax") as r:
            jx = json.loads(r.read())["data"]
        assert "compiles" in jx
    finally:
        srv.stop()
