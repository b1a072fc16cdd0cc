"""graftpath: cross-node stitching, critical-path extraction, the
propagation SLO lifecycle, the differential profiler, and the CLI
surfaces (`report.py --critpath`, `simulator --dump-trace`).

The critical-path golden pins the walk over a hand-built DAG with a
fork (two overlapping children), a join, a queue-wait hop and one
cross-node propagation edge, so every refactor of obs/critpath.py must
reproduce the exact segment sequence and stage table.
"""
import json
import subprocess
import sys
from pathlib import Path

from lighthouse_tpu import obs
from lighthouse_tpu.obs import causal, critpath, doctor, flight, slo, timeseries
from lighthouse_tpu.obs.capture import scenario_capture
from lighthouse_tpu.specs import minimal_spec
from lighthouse_tpu.testing import simulator

REPO = Path(__file__).resolve().parents[1]
FIXTURES = Path(__file__).parent / "trace_fixtures"


# -- synthetic component ------------------------------------------------------


def _synthetic_spans():
    """Publish on n0, pipeline on n1 with a fork (gossip_verify overlaps
    batch_signature), a join into a queued processor_work hop, and the
    import chain.  All times are exact multiples of 5 ms so the golden
    is stable under float rounding."""
    SV = critpath.SpanView
    return [
        SV("tA", "P", None, "gossip_publish", 0.000, 0.010,
           {"message_id": "m1", "node": "n0"}),
        SV("tB", "R", None, "block_pipeline", 0.020, 0.100,
           {"message_id": "m1", "node": "n1"}),
        SV("tB", "C1", "R", "gossip_verify", 0.020, 0.040),
        SV("tB", "C2", "R", "batch_signature", 0.025, 0.055),
        SV("tB", "W", "R", "processor_work", 0.060, 0.095,
           {"queue_wait_s": 0.005}),
        SV("tB", "I", "W", "block_import", 0.065, 0.090),
        SV("tB", "S", "I", "state_transition", 0.070, 0.085),
    ]


def test_stitch_joins_traces_on_message_id():
    comps = causal.stitch(_synthetic_spans())
    assert len(comps) == 1
    (comp,) = comps
    assert comp.trace_ids() == ["tA", "tB"]
    assert comp.node_labels() == ["n0", "n1"]
    assert comp.edges == [("P", "R", "propagation")]


def test_stitch_is_invariant_under_input_order():
    spans = _synthetic_spans()
    a = causal.stitch(spans)
    b = causal.stitch(list(reversed(spans)))
    assert [c.edges for c in a] == [c.edges for c in b]
    assert [[s.span_id for s in c.spans] for c in a] == \
           [[s.span_id for s in c.spans] for c in b]


def test_critical_path_golden_fork_join_queue():
    (comp,) = causal.stitch(_synthetic_spans())
    rep = critpath.component_report(comp)
    assert rep["total_ms"] == 100.0
    assert rep["terminal"]["kind"] == "block_pipeline"
    assert rep["terminal"]["node"] == "n1"
    # chronological segments: the fork's LONGER branch (batch_signature,
    # not gossip_verify) is on the path, the queue hop precedes the
    # worker's service time, and the propagation edge bridges the nodes
    assert [(s["kind"], s["type"], s["dur_ms"])
            for s in rep["segments"]] == [
        ("gossip_publish", "self", 10.0),
        ("block_pipeline", "propagation", 10.0),
        ("block_pipeline", "self", 5.0),
        ("batch_signature", "self", 30.0),
        ("processor_work", "queue", 5.0),
        ("processor_work", "self", 5.0),
        ("block_import", "self", 5.0),
        ("state_transition", "self", 15.0),
        ("block_import", "self", 5.0),
        ("processor_work", "self", 5.0),
        ("block_pipeline", "self", 5.0),
    ]
    assert rep["stages"] == {
        "batch_signature": {"count": 1, "self_ms": 30.0,
                            "queue_wait_ms": 0.0, "service_ms": 30.0},
        "block_import": {"count": 1, "self_ms": 10.0,
                         "queue_wait_ms": 0.0, "service_ms": 25.0},
        "block_pipeline": {"count": 1, "self_ms": 10.0,
                           "queue_wait_ms": 0.0, "service_ms": 80.0},
        "gossip_publish": {"count": 1, "self_ms": 10.0,
                           "queue_wait_ms": 0.0, "service_ms": 10.0},
        "processor_work": {"count": 1, "self_ms": 10.0,
                           "queue_wait_ms": 5.0, "service_ms": 35.0},
        "state_transition": {"count": 1, "self_ms": 15.0,
                             "queue_wait_ms": 0.0, "service_ms": 15.0},
    }
    # self + queue + cross-node wait account for the whole latency
    assert sum(s["dur_ms"] for s in rep["segments"]) == rep["total_ms"]
    rendered = critpath.render_critical_path(rep, "synthetic")
    assert rendered.splitlines()[0] == \
        "synthetic: 100.000 ms ending in block_pipeline on n1"
    assert "cross-node hops: 1 (propagation), 10.000 ms waiting" in rendered


def test_critical_path_empty_capture():
    rep = critpath.critical_path([])
    assert rep == {"total_ms": 0.0, "terminal": None, "segments": [],
                   "stages": {}}


# -- stitcher determinism over two seeded fleet runs --------------------------


def _fleet_capture():
    spec = minimal_spec(altair_fork_epoch=0)
    with scenario_capture() as trace:
        net = simulator.LocalNetwork(spec, 2, 48, topology="mesh")
        try:
            net.run_slots(spec.preset.slots_per_epoch)
            # every node imports every block before the capture closes
            assert net._wait_convergence(timeout=60)
        finally:
            net.stop()
    return trace


def _by_slot(spans, digest) -> dict:
    """The propagation digest keyed by block slot, checking that each
    slot holds one published root.  A root hashes the block's contents,
    and which attestations a proposer packs depends on the order gossip
    delivered them; the slot, its publisher and its importers do not."""
    slot_of = {s.attrs["block_root"]: s.attrs["slot"] for s in spans
               if s.kind == "block_import" and "block_root" in s.attrs}
    assert set(digest) <= set(slot_of), "a published root never imported"
    slots = [slot_of[root] for root in digest]
    assert len(set(slots)) == len(slots), "two published roots in a slot"
    return {slot_of[root]: rec for root, rec in digest.items()}


def test_stitcher_digest_deterministic_across_seeded_runs():
    """Two identical fleet runs must stitch to the SAME propagation
    digest slot by slot — publisher and per-root importer sets are
    structural, so wall-clock jitter must not leak into them."""
    t1, t2 = _fleet_capture(), _fleet_capture()
    d1 = causal.propagation_digest(t1.spans)
    d2 = causal.propagation_digest(t2.spans)
    assert d1, "fleet run published no blocks with causal annotations"
    assert _by_slot(t1.spans, d1) == _by_slot(t2.spans, d2)
    # every published block reached (at least) the non-proposing node
    assert all(rec["importers"] for rec in d1.values())
    comps = causal.stitch(t1.spans)
    cross = [c for c in comps if len(c.node_labels()) >= 2]
    assert cross, "no cross-node stitched component in a 2-node mesh"
    assert any(e[2] == "propagation" for c in cross for e in c.edges)


# -- propagation SLO lifecycle ------------------------------------------------


def _propagation_engine(budget_s=1.0):
    s = timeseries.SlotSampler(window=16)
    eng = slo.SLOEngine(s, slos=[
        o for o in slo.default_slos(propagation_p95_s=budget_s)
        if o.name == "propagation_p95"])
    return s, eng


def test_propagation_slo_open_and_resolve():
    s, eng = _propagation_engine(budget_s=1.0)
    s.sample(1)                                # silence: unevaluable
    eng.evaluate(1)
    assert eng.open_incidents() == []
    assert eng.status()["propagation_p95"]["last_detail"] == \
        "no propagation traffic this slot"

    s.record("hist", "block_propagation_seconds", 0.05)
    s.sample(2)                                # fast propagation: clean
    eng.evaluate(2)
    assert eng.open_incidents() == []

    s.record("hist", "block_propagation_seconds", 3.0)
    s.sample(3)                                # over budget: opens
    opened = eng.evaluate(3)
    assert [i.slo for i in opened] == ["propagation_p95"]

    s.record("hist", "block_propagation_seconds", 0.05)
    s.sample(4)                                # clean slot 1 of 2
    eng.evaluate(4)
    assert eng.open_incidents()
    s.sample(5)                                # silence also counts clean
    eng.evaluate(5)
    assert eng.open_incidents() == []
    (inc,) = eng.incidents_for("propagation_p95")
    assert inc.opened_slot == 3
    assert inc.resolved_slot == 5
    assert inc.worst_value == 3.0


# -- differential profiler ----------------------------------------------------


def _run_tool(*argv):
    return subprocess.run([sys.executable, *map(str, argv)],
                          capture_output=True, text=True, timeout=120)


def test_diff_tool_blames_the_stage_that_moved():
    out = _run_tool(REPO / "tools" / "obs" / "diff.py", "--json",
                    FIXTURES / "trace_old.json", FIXTURES / "trace_new.json")
    assert out.returncode == 0, out.stderr
    diff = json.loads(out.stdout)
    # +25 ms of state_transition surfaces in the stage totals AND as the
    # top critical-path move (its parents inflate the stage total sum)
    assert diff["total_delta_ms"] == 75.0
    by_stage = {s["stage"]: s["delta_total_ms"] for s in diff["stages"]}
    assert by_stage["state_transition"] == 25.0
    cp = diff["critical_path"]
    assert (cp["old_total_ms"], cp["new_total_ms"]) == (100.0, 125.0)
    assert cp["moved"][0]["stage"] == "state_transition"
    assert cp["moved"][0]["delta_self_ms"] == 25.0

    table = _run_tool(REPO / "tools" / "obs" / "diff.py",
                      FIXTURES / "trace_old.json", FIXTURES / "trace_new.json")
    assert table.returncode == 0, table.stderr
    assert "critical path: 100.000 ms -> 125.000 ms (+25.000 ms)" \
        in table.stdout
    assert "state_transition: self 30.000 -> 55.000 ms (+25.000)" \
        in table.stdout


def test_diff_tool_rejects_garbage(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text("{nope")
    out = _run_tool(REPO / "tools" / "obs" / "diff.py",
                    bad, FIXTURES / "trace_new.json")
    assert out.returncode == 2


# -- report --critpath --------------------------------------------------------


def test_trace_report_critpath_flag():
    out = _run_tool(REPO / "tools" / "trace" / "report.py", "--critpath",
                    FIXTURES / "trace_new.json")
    assert out.returncode == 0, out.stderr
    first = out.stdout.splitlines()[0]
    assert first == "slowest block trace: 125.000 ms " \
                    "ending in block_pipeline on n1"
    assert "cross-node hops: 1 (propagation)" in out.stdout
    as_json = _run_tool(REPO / "tools" / "trace" / "report.py",
                        "--critpath", "--json", FIXTURES / "trace_new.json")
    rep = json.loads(as_json.stdout)
    assert rep["nodes"] == ["n0", "n1"]
    assert rep["block_roots"] == ["aa11"]


def test_trace_report_critpath_empty_capture_exits_2(tmp_path):
    empty = tmp_path / "empty.json"
    empty.write_text(json.dumps({"data": []}))
    out = _run_tool(REPO / "tools" / "trace" / "report.py",
                    "--critpath", empty)
    assert out.returncode == 2
    assert "no spans in capture" in out.stderr


# -- simulator --dump-trace helper --------------------------------------------


def test_write_stitched_trace_one_pid_per_node(tmp_path):
    path = simulator.write_stitched_trace(str(tmp_path / "fleet.json"),
                                          _synthetic_spans())
    doc = json.loads(Path(path).read_text())
    procs = {ev["args"]["name"] for ev in doc["traceEvents"]
             if ev["name"] == "process_name"}
    assert procs == {"n0", "n1"}
    slices = [ev for ev in doc["traceEvents"] if ev["ph"] == "X"]
    assert len(slices) == len(_synthetic_spans())
    # the propagation edge renders as a Perfetto flow arrow pair
    assert {ev["ph"] for ev in doc["traceEvents"]
            if ev.get("cat") == "graftpath"} == {"s", "f"}


# -- flight recorder carries the worst trace ----------------------------------


class _StubWatch:
    def __init__(self, sampler, engine):
        self.sampler = sampler
        self.engine = engine

    def chains(self):
        return []

    def processors(self):
        return []

    def servings(self):
        return []

    def replays(self):
        return []


def test_flight_dump_carries_worst_trace_critpath(tmp_path):
    import time as _time
    obs.clear()
    with obs.span("gossip_publish", message_id="mf", node="n0"):
        pass
    with obs.span("block_pipeline", message_id="mf", node="n1"):
        with obs.span("block_import", root=b"\xaa" * 32):
            _time.sleep(0.02)
    s = timeseries.SlotSampler(window=8)
    s.record("gauge", "beacon_head_slot", 1)
    s.sample(1)
    eng = slo.SLOEngine(s)
    eng.evaluate(1)
    rec = flight.FlightRecorder(_StubWatch(s, eng), dump_dir=str(tmp_path))
    doc = doctor.load(rec.dump(reason="unit"))
    cp = doc["critpath"]
    assert cp["segments"] and cp["total_ms"] > 0
    assert cp["nodes"] == ["n0", "n1"]
    assert cp["block_roots"] == ["aa" * 32]
    rendered = doctor.render(doctor.diagnose(doc))
    assert "worst block trace across 2 node(s)" in rendered
