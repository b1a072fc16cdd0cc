"""The trace reduction on a small trace recorded on a v5e chip by
``record_trace.py``: two runs of ``square_sum`` and one eager add inside
the window annotation, with a 50 ms host pause annotated ``host_pause``."""
from pathlib import Path

import pytest

from harness import trace

TINY = Path(__file__).with_name("data") / "tiny_tpu.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(trace.load(TINY))


def test_busy_time_is_inside_the_window(reduced):
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    assert reduced["window_s"] >= 0.05          # the host pause alone


def test_programs_are_named_and_counted(reduced):
    assert reduced["runs"]["square_sum"] == 2
    assert reduced["programs"]["square_sum"] > 0
    assert sum(reduced["runs"].values()) == 3   # and the eager add


def test_longest_idle_gap_is_named_by_the_host_span_open_in_it(reduced):
    name, seconds = reduced["idle_gaps"][0]
    assert name == "host_pause"
    assert seconds >= 0.045


def test_system_spans_join_the_host_events(reduced):
    # a span placed on the perf_counter clock over the whole window is
    # the innermost one open in a gap only where no annotation is
    again = trace.reduce(trace.load(TINY), spans=[("outer", 0.0, 1e6)],
                         window_perf_s=0.0)
    assert again["idle_gaps"][0][0] == "host_pause"
    assert again["busy_s"] == reduced["busy_s"]


def test_breakdown_has_at_most_ten_of_each(reduced):
    b = trace.breakdown(reduced)
    assert {name for name, _ in b["device_ops"]} == {"square_sum", "add"}
    seconds = [t for _, t in b["device_ops"]]
    assert seconds == sorted(seconds, reverse=True)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_union_and_program_names():
    assert trace.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert trace.program_name("jit_final_exponentiation(42)") == \
        "final_exponentiation"
    assert trace.program_name("fusion.3") == "fusion.3"


def test_a_trace_with_no_device_plane_reduces_to_nothing(tmp_path):
    import jax
    import jax.numpy as jnp
    trace.start(tmp_path)
    with jax.profiler.TraceAnnotation(trace.WINDOW):
        (jnp.ones(8) + 1).block_until_ready()
    jax.profiler.stop_trace()
    assert trace.reduce(trace.load(trace.find_xspace(tmp_path))) == {}


def test_an_op_of_a_program_begun_before_the_slice_counts_as_busy():
    # the slice runs 0-100 ns; a program dispatched before it shows only
    # its operations (10-40), then one recorded program runs (60-70)
    host = [(trace.WINDOW, 0.0, 100.0), (trace.DISPATCH, 55.0, 56.0)]
    device = ([("jit_final_exponentiation(7)", 60.0, 70.0)],
              [(10.0, 40.0), (60.0, 70.0)])
    r = trace.reduce_events(host, [device])
    assert r["busy_s"] == pytest.approx(40e-9)
    assert r["programs"] == pytest.approx(
        {"final_exponentiation": 10e-9, trace.OUTSIDE: 30e-9})
    assert r["runs"] == {"final_exponentiation": 1}


def test_slices_combine_into_one_reduction(reduced):
    both = trace.combine([reduced, {}, reduced])
    assert both["busy_s"] == pytest.approx(2 * reduced["busy_s"])
    assert both["window_s"] == pytest.approx(2 * reduced["window_s"])
    assert both["runs"]["square_sum"] == 4
    assert len(both["idle_gaps"]) <= 10
    assert both["idle_gaps"][0][0] == "host_pause"
    assert trace.combine([{}, {}]) == {}


def test_device_clock_is_shifted_onto_the_dispatches():
    assert trace.clock_shift([10.0, 20.0], [12.0, 25.0]) == 5.0
    assert trace.clock_shift([10.0], [12.0, 25.0]) == 0.0
