"""graftgauge: the device/HBM ledger, roofline accounting, span
watermarks, the hbm_headroom / compile_cache_hit_ratio SLOs, and the
flight-dump device section (golden-pinned through the doctor)."""
import json
import os

import pytest

from lighthouse_tpu.obs import (
    device, doctor, jax_accounting, roofline, slo, timeseries, tracing,
)

FIXTURES = os.path.join(os.path.dirname(__file__), "graftwatch_fixtures")


@pytest.fixture(autouse=True)
def _clean_registries():
    device.reset_attribution()
    roofline.reset()
    yield
    device.reset_attribution()
    roofline.reset()


# -- ledger snapshot ----------------------------------------------------------


def test_ledger_snapshot_on_cpu_backend():
    import jax
    import jax.numpy as jnp

    jnp.zeros(1).block_until_ready()        # make sure a backend is live
    snap = device.ledger_snapshot()
    assert snap["platform"] == jax.default_backend()
    assert snap["chip_count"] == len(jax.devices())
    # the honesty contract: XLA CPU exposes no memory_stats, and the
    # ledger says so explicitly instead of guessing
    if jax.default_backend() == "cpu":
        assert snap["hbm"] == device.UNAVAILABLE
    assert snap["host"]["rss_bytes"] > 0
    json.dumps(snap)                         # JSON-ready, always


def test_ledger_snapshot_without_jax_in_process(monkeypatch):
    # lint rigs and the doctor's probe never import jax; the ledger must
    # not trigger backend init on their behalf
    monkeypatch.setattr(device, "_jax", lambda: None)
    snap = device.ledger_snapshot()
    assert snap["platform"] == device.UNAVAILABLE
    assert snap["chip_count"] == 0
    assert snap["hbm"] == device.UNAVAILABLE


def test_attribution_registry_tracks_liveness():
    import numpy as np

    a = np.zeros(1024, dtype=np.uint8)
    b = np.zeros(2048, dtype=np.uint8)
    device.attribute("parallel.test", "bufs", a, b)
    rec = device.attributed_bytes()["parallel.test"]["bufs"]
    assert rec["live_bytes"] == 3072
    assert rec["peak_bytes"] == 3072
    del b                                    # weakref drops the dead one
    rec = device.attributed_bytes()["parallel.test"]["bufs"]
    assert rec["live_bytes"] == 1024
    assert rec["peak_bytes"] == 3072         # peak is sticky


# -- roofline accounting ------------------------------------------------------


def test_roofline_wrapper_emits_cost_for_toy_program():
    import jax
    import jax.numpy as jnp

    rj = roofline.track_roofline(
        "test.toy_matmul", jax.jit(lambda x: x @ x))
    x = jnp.ones((64, 64), dtype=jnp.float32)
    for _ in range(roofline.SAMPLE_CALLS + 1):
        out = rj(x)
    assert out.shape == (64, 64)
    (rec,) = rj.records()
    assert rec["platform"] == jax.default_backend()
    assert rec["flops"] > 0
    assert rec["bytes_accessed"] > 0
    assert rec["wall_seconds_per_call"] > 0
    assert rec["achieved_flops_per_sec"] > 0
    assert 0 < rec["utilization_of_peak"]
    assert rec["arithmetic_intensity"] == pytest.approx(
        rec["flops"] / rec["bytes_accessed"])
    # the wrapper is in the global registry the flight dump reads
    assert "test.toy_matmul" in roofline.snapshot()


def test_roofline_falls_back_when_aot_lowering_fails():
    # a plain Python callable has no .lower(): the wrapper must degrade
    # to the tracked path and say cost "unavailable", not raise
    rj = roofline.track_roofline("test.unlowerable", lambda x: x * 2)
    assert rj(21) == 42
    (rec,) = rj.records()
    assert rec["cost"] == "unavailable"


def test_peak_table_is_keyed_by_exact_device_kind():
    peak = roofline.peak_for("TPU v5 lite")
    assert peak["match"] == "TPU v5 lite"
    assert peak["flops_per_sec"] == 197e12 and peak["source"]
    assert roofline.peak_for("cpu")["match"] == "cpu"
    # an unknown kind is an error, never scored as a CPU or as a v5e
    for kind in ("TPU v5e", "TPU v4", "tpu", ""):
        with pytest.raises(KeyError):
            roofline.peak_for(kind)


# -- span watermarks ----------------------------------------------------------


def test_hbm_watermark_stamps_span_delta(monkeypatch):
    readings = iter([(100, 1000), (400, 1000)])
    monkeypatch.setattr(device, "hbm_bytes", lambda: next(readings))
    with tracing.span("bls_batch_verify") as s:
        with device.hbm_watermark("parallel.bls") as wm:
            pass
    assert wm.delta_bytes == 300
    assert s.attrs["hbm_owner"] == "parallel.bls"
    assert s.attrs["hbm_delta_bytes"] == 300
    assert s.attrs["hbm_bytes_in_use"] == 400


def test_hbm_watermark_explicit_unavailable(monkeypatch):
    monkeypatch.setattr(device, "hbm_bytes", lambda: None)
    with tracing.span("tree_hash") as s:
        with device.hbm_watermark("parallel.merkle"):
            pass
    # absence is recorded, not skipped
    assert s.attrs["hbm_delta_bytes"] == device.UNAVAILABLE


# -- SLOs ---------------------------------------------------------------------


def _hbm_engine():
    s = timeseries.SlotSampler(window=16)
    objective = [o for o in slo.default_slos()
                 if o.name == "hbm_headroom"]
    assert objective, "hbm_headroom SLO not registered"
    return s, slo.SLOEngine(s, slos=objective)


def test_hbm_headroom_slo_unevaluable_without_stats():
    s, eng = _hbm_engine()
    for slot in range(1, 5):
        s.sample(slot)
        assert eng.evaluate(slot) == []
    assert eng.open_incidents() == []
    assert "unavailable" in eng.status()["hbm_headroom"]["last_detail"]


def test_hbm_headroom_slo_opens_and_resolves():
    s, eng = _hbm_engine()

    def tick(slot, in_use):
        s.record("gauge", "device_hbm_bytes_in_use", in_use)
        s.record("gauge", "device_hbm_bytes_limit", 1000.0)
        s.sample(slot)
        return eng.evaluate(slot)

    assert tick(1, 500.0) == []              # 50% headroom: clean
    opened = tick(2, 950.0)                  # 5% < the 10% budget
    assert [i.slo for i in opened] == ["hbm_headroom"]
    assert "GiB in use" in opened[0].detail
    tick(3, 980.0)                           # worse while open
    assert eng.open_incidents()
    tick(4, 200.0)                           # clean slot 1 of 2
    tick(5, 200.0)                           # clean slot 2: resolves
    assert eng.open_incidents() == []
    (inc,) = eng.incidents_for("hbm_headroom")
    assert inc.opened_slot == 2
    assert inc.resolved_slot == 5


def test_compile_cache_slo_warms_up_then_evaluates():
    s = timeseries.SlotSampler(window=32)
    objective = [o for o in slo.default_slos(compile_cache_warmup_slots=2)
                 if o.name == "compile_cache_hit_ratio"]
    eng = slo.SLOEngine(s, slos=objective)

    def tick(slot, hits, misses):
        s.record("counter", "jax_compile_cache_hits_total", hits)
        s.record("counter", "jax_compile_cache_misses_total", misses)
        s.sample(slot)
        return eng.evaluate(slot)

    assert tick(1, 0, 3) == []               # warmup: all misses is fine
    assert tick(2, 0, 3) == []
    opened = tick(3, 1, 5)                   # past warmup, ratio ~0.07
    assert [i.slo for i in opened] == ["compile_cache_hit_ratio"]


def test_compile_cache_events_feed_counters():
    before = jax_accounting.snapshot()
    jax_accounting._record_cache_event(hit=True)
    jax_accounting._record_cache_event(hit=False)
    after = jax_accounting.snapshot()
    assert after["cache_hits"] == before["cache_hits"] + 1
    assert after["cache_misses"] == before["cache_misses"] + 1


# -- flight dump / doctor -----------------------------------------------------


def test_flight_section_shape_and_json_ready():
    sec = device.flight_section()
    assert "roofline" in sec
    assert set(sec["compile_cache"]) >= {"hits", "misses"}
    json.dumps(sec)


def test_doctor_device_golden_report():
    path = os.path.join(FIXTURES, "dump_v1_device.json")
    diag = doctor.diagnose(doctor.load(path))
    dev = diag["device"]
    assert dev["platform"] == "tpu"
    assert dev["compile_cache"] == {"hits": 11, "misses": 3}
    rendered = doctor.render(diag)
    golden = open(os.path.join(FIXTURES,
                               "dump_v1_device_report.txt")).read()
    assert rendered.strip() == golden.strip()


def test_doctor_renders_nothing_for_pre_device_dumps():
    # the PR-17 contract shared with the sync section: older dumps lack
    # doc["device"] and the report stays byte-identical
    doc = {"version": 1, "reason": "old", "slot": 1,
           "timeseries": {"slots": [], "series": {}}, "incidents": []}
    rendered = doctor.render(doctor.diagnose(doc))
    assert "device:" not in rendered


# -- staged probe -------------------------------------------------------------


def test_staged_probe_reports_stage_reached(monkeypatch):
    monkeypatch.setattr(device, "_PROBE_STAGES",
                        [("ok", "print('fine')"),
                         ("boom", "import sys; sys.exit(3)"),
                         ("never", "print('unreached')")])
    probe = device.staged_probe(timeout=60)
    for label in ("default", "forced_tpu"):
        rec = probe[label]
        assert rec["stage_reached"] == "boom"
        assert rec["stages"]["ok"]["rc"] == 0
        assert rec["stages"]["boom"]["rc"] == 3
        assert "never" not in rec["stages"]
