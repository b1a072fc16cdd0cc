"""graftwatch: slot sampler rings, SLO incident lifecycle, flight dump
round-trip, the doctor golden file and the SLO->CATALOG cross-check."""
import json
import os

import pytest

from lighthouse_tpu import obs
from lighthouse_tpu.api.metrics_defs import CATALOG
from lighthouse_tpu.obs import doctor, flight, graftwatch, slo, timeseries
from lighthouse_tpu.obs.capture import scenario_capture

FIXTURES = os.path.join(os.path.dirname(__file__), "graftwatch_fixtures")


# -- slot sampler -------------------------------------------------------------


def test_sampler_ring_bounds_and_slot_alignment():
    s = timeseries.SlotSampler(window=8)
    for slot in range(1, 21):
        s.record("counter", "beacon_block_imported_total", 2)
        s.record("gauge", "beacon_head_slot", slot - 1)
        s.sample(slot)
    assert s.latest_slot() == 20
    slots, vals = s.series("beacon_block_imported_total")
    # bounded at the window, holding exactly the newest 8 slots
    assert list(slots) == list(range(13, 21))
    assert [float(v) for v in vals] == [2.0] * 8
    gslots, gvals = s.series("beacon_head_slot")
    assert [int(v) for v in gvals] == [sl - 1 for sl in gslots]


def test_sampler_counter_delta_and_gauge_persistence():
    s = timeseries.SlotSampler(window=8)
    s.record("counter", "beacon_block_imported_total", 5)
    s.record("gauge", "beacon_head_slot", 3)
    s.sample(1)
    # counters are per-slot deltas; gauges persist their last set value
    assert s.latest("beacon_block_imported_total") == 5.0
    s.sample(2)
    assert s.latest("beacon_block_imported_total") == 0.0
    assert s.latest("beacon_head_slot") == 3.0
    assert s.counter_total("beacon_block_imported_total") == 5.0


def test_sampler_histogram_percentiles_and_same_slot_merge():
    s = timeseries.SlotSampler(window=8)
    for v in range(1, 11):
        s.record("hist", "beacon_block_pipeline_seconds", float(v))
    s.sample(1)
    for v in range(11, 21):
        s.record("hist", "beacon_block_pipeline_seconds", float(v))
    s.sample(1)                              # same slot: rows merge
    slots, _ = s.series("beacon_block_pipeline_seconds.count")
    assert list(slots) == [1]
    assert s.latest("beacon_block_pipeline_seconds.count") == 20.0
    # on merge the latest drained batch (11..20) stands in for the
    # slot's percentiles; the count still accumulates
    assert s.latest("beacon_block_pipeline_seconds.p50") == 16.0
    assert s.latest("beacon_block_pipeline_seconds.p95") == 20.0


def test_sampler_backwards_slot_resets():
    s = timeseries.SlotSampler(window=8)
    for slot in (1, 2, 3):
        s.record("counter", "beacon_block_imported_total", 1)
        s.sample(slot)
    s.sample(1)                              # a fresh harness at slot 1
    slots, _ = s.series("beacon_block_imported_total")
    assert list(slots) == [1]
    assert s.latest_slot() == 1


# -- SLO engine / incidents ---------------------------------------------------


def test_incident_lifecycle_open_worsen_resolve():
    s = timeseries.SlotSampler(window=16)
    state = {"value": 0.0}

    def check(ctx):
        v = state["value"]
        return v, v > 1.0, f"synthetic {v}"

    eng = slo.SLOEngine(s, slos=[
        slo.SLO("synthetic", "beacon_head_slot", 1.0, "test", check,
                resolve_after=2)])
    fired = []
    eng.on_open.append(fired.append)

    eng.evaluate(1)
    assert eng.open_incidents() == []
    state["value"] = 2.0
    opened = eng.evaluate(2)
    assert [i.slo for i in opened] == ["synthetic"]
    assert fired == opened
    state["value"] = 4.0                     # worse while open
    assert eng.evaluate(3) == []             # no second open
    state["value"] = 0.0
    eng.evaluate(4)                          # clean slot 1 of 2
    assert eng.open_incidents()
    eng.evaluate(5)                          # clean slot 2: resolves
    assert eng.open_incidents() == []
    (inc,) = eng.incidents_for("synthetic")
    assert inc.opened_slot == 2
    assert inc.resolved_slot == 5
    assert inc.worst_value == 4.0
    assert not inc.open


def test_broken_check_never_kills_evaluation():
    s = timeseries.SlotSampler(window=8)

    def boom(_ctx):
        raise RuntimeError("broken check")

    eng = slo.SLOEngine(s, slos=[
        slo.SLO("broken", "beacon_head_slot", 1.0, "test", boom)])
    assert eng.evaluate(1) == []
    assert "check error" in eng.status()["broken"]["last_detail"]


def test_every_default_slo_watches_a_catalog_metric():
    # tier-1 gate: an SLO naming a metric the catalog doesn't declare
    # would silently never see data
    for objective in slo.default_slos():
        assert objective.metric in CATALOG, (
            f"SLO {objective.name!r} watches {objective.metric!r} "
            "which is not in api/metrics_defs.CATALOG")


def _sync_progress_engine():
    s = timeseries.SlotSampler(window=32)
    eng = slo.SLOEngine(s, slos=[
        o for o in slo.default_slos(sync_floor_blocks=1.0,
                                    sync_stall_slots=3)
        if o.name == "sync_progress"])
    return s, eng


def test_sync_progress_slo_clean_when_not_syncing():
    s, eng = _sync_progress_engine()
    for slot in range(1, 6):
        s.record("gauge", "sync_state", 0)     # synced the whole time
        s.sample(slot)
        eng.evaluate(slot)
    assert eng.open_incidents() == []
    assert eng.status()["sync_progress"]["last_detail"] == "not syncing"


def test_sync_progress_slo_opens_after_consecutive_stalled_slots():
    s, eng = _sync_progress_engine()
    for slot in (1, 2):                        # syncing and importing
        s.record("gauge", "sync_state", 1)
        s.record("counter", "sync_range_blocks_imported_total", 8)
        s.sample(slot)
        eng.evaluate(slot)
    assert eng.open_incidents() == []
    for slot in (3, 4):                        # two stalled slots: grace
        s.record("gauge", "sync_state", 1)
        s.sample(slot)
        eng.evaluate(slot)
    assert eng.open_incidents() == []
    s.record("gauge", "sync_state", 1)         # third consecutive: breach
    s.sample(5)
    opened = eng.evaluate(5)
    assert [i.slo for i in opened] == ["sync_progress"]


def test_sync_progress_slo_stall_run_resets_on_progress_or_sync_end():
    s, eng = _sync_progress_engine()
    for slot in (1, 2):                        # 2 stalled syncing slots
        s.record("gauge", "sync_state", 1)
        s.sample(slot)
        eng.evaluate(slot)
    s.record("gauge", "sync_state", 1)         # progress: run resets
    s.record("counter", "sync_range_blocks_imported_total", 3)
    s.sample(3)
    eng.evaluate(3)
    for slot in (4, 5):                        # only 2 stalled again
        s.record("gauge", "sync_state", 1)
        s.sample(slot)
        eng.evaluate(slot)
    assert eng.open_incidents() == []
    s.record("gauge", "sync_state", 0)         # sync finished: clean
    s.sample(6)
    eng.evaluate(6)
    assert eng.open_incidents() == []


def test_graftwatch_backwards_slot_resets_engine_and_sampler():
    w = graftwatch.get()
    w.reset()
    w.on_slot(5)
    w.on_slot(6)
    assert w.sampler.latest_slot() == 6
    w.on_slot(2)                             # new network starting over
    assert w.sampler.latest_slot() == 2
    assert w.engine.all_incidents() == []


# -- capture scoping ----------------------------------------------------------


def test_scenario_capture_excludes_prior_and_later_spans():
    with obs.span("gossip_verify"):
        pass                                 # before the capture window
    with scenario_capture() as trace:
        with obs.span("gossip_verify"):
            pass
    with obs.span("gossip_verify"):
        pass                                 # after the capture window
    assert trace.count("gossip_verify") == 1


def test_sequential_captures_stay_disjoint():
    with scenario_capture() as t1:
        with obs.span("block_import"):
            pass
    with scenario_capture() as t2:
        with obs.span("block_import"):
            pass
        with obs.span("block_import"):
            pass
    assert t1.count("block_import") == 1
    assert t2.count("block_import") == 2


# -- flight dump + doctor -----------------------------------------------------


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


def _storm_watch():
    """16 deterministic slots with a slot-8..11 storm (same shape as the
    checked-in fixture)."""
    s = timeseries.SlotSampler(window=32)
    eng = slo.SLOEngine(s)
    for slot in range(1, 17):
        storm = 8 <= slot <= 11
        for _ in range(4):
            s.record("hist", "beacon_block_pipeline_seconds",
                     7.0 if storm else 0.05)
        s.record("counter", "beacon_block_imported_total", 2)
        if storm:
            s.record("counter", "jax_compile_total", 3)
        if slot == 9:
            s.record("counter",
                     "beacon_processor_work_dropped_total", 5)
        s.record("gauge", "beacon_head_slot", slot - 1)
        s.record("gauge", "beacon_processor_queue_length",
                 40 if storm else 2)
        s.sample(slot)
        eng.evaluate(slot)
    return _StubWatch(s, eng)


def test_flight_dump_round_trips_through_doctor(tmp_path):
    rec = flight.FlightRecorder(_storm_watch(), dump_dir=str(tmp_path))
    path = rec.dump(reason="unit")
    assert rec.last_path == path
    # strict JSON: a NaN/Infinity literal anywhere is a bug
    text = open(path).read()
    json.loads(text, parse_constant=lambda c: pytest.fail(
        f"non-finite literal {c!r} in dump"))
    diag = doctor.diagnose(doctor.load(path))
    assert diag["incidents"]
    assert all(i["correlations"] for i in diag["incidents"])


def test_flight_dump_carries_recovery_report_to_doctor(tmp_path,
                                                       monkeypatch):
    from lighthouse_tpu.chain import persistence
    report = {"restored": True, "fork_choice_rebuilt": True,
              "repairs": ["head item stale (seq 3 < fork-choice seq 4); "
                          "derived head from fork choice"],
              "op_pool_skipped": 2, "head_walked_back": 0, "seq": 4}
    monkeypatch.setattr(persistence, "LAST_RECOVERY", report)
    rec = flight.FlightRecorder(_storm_watch(), dump_dir=str(tmp_path))
    diag = doctor.diagnose(doctor.load(rec.dump(reason="unit")))
    assert diag["recovery"]["fork_choice_rebuilt"] is True
    assert diag["recovery"]["repairs"] == report["repairs"]
    rendered = doctor.render(diag)
    assert "fork choice REBUILT" in rendered
    assert "derived head from fork choice" in rendered


def test_doctor_golden_report():
    path = os.path.join(FIXTURES, "dump_v1.json")
    diag = doctor.diagnose(doctor.load(path))
    assert [i["slo"] for i in diag["incidents"]] == [
        "block_pipeline_p95", "jax_compile_steady",
        "processor_shedding"]
    assert all(i["correlations"] for i in diag["incidents"])
    rendered = doctor.render(diag)
    golden = open(os.path.join(FIXTURES,
                               "dump_v1_report.txt")).read()
    assert rendered.strip() == golden.strip()


def test_doctor_rejects_garbage_and_wrong_version(tmp_path):
    p = tmp_path / "not.json"
    p.write_text("{nope")
    with pytest.raises(doctor.DoctorError) as ei:
        doctor.load(str(p))
    assert ei.value.exit_code == 2

    p2 = tmp_path / "future.json"
    p2.write_text(json.dumps({"format": "graftwatch-dump",
                              "version": flight.FORMAT_VERSION + 1}))
    with pytest.raises(doctor.DoctorError) as ei:
        doctor.load(str(p2))
    assert ei.value.exit_code == 3

