"""Stage spans inside the BLS backend and the block import.

- BLS: with the stage kernels swapped for cheap stand-ins of the right
  shapes (no BLS compile), a verify records the host stages and the five
  device stages inside ``bls_batch_verify``, device stages in order and
  never overlapping; a failed first check stops at ``bls_decompress``;
  ``make_jaxpr`` over the device half records nothing; a verify feeds
  the constant-ladder counters with each program's static counts.
- A node built on ``tpu`` compiles its stage programs after loading its
  registry's keys: its first multi-key verify compiles none of them.
- Clearing the ring first records every device span watched before it.
- Block import: the four new kinds nest in ``block_import``.
- Profiler clock: a host span holds a ``lighthouse_tpu:<kind>``
  annotation of the same length in a JAX profile.
- The benchmark's readers of these spans, on synthetic span lists.
"""
import importlib.util
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from lighthouse_tpu.obs import tracing  # noqa: E402

DEVICE_STAGES = ["bls_decompress", "bls_subgroup", "bls_hash_to_g2",
                 "bls_rlc", "bls_pairing"]
HOST_STAGES = ["bls_parse", "bls_prepare", "bls_scalars"]
IMPORT_STAGES = ["pre_state", "signature_sets", "post_import",
                 "head_update"]


# -- BLS backend -------------------------------------------------------------

@pytest.fixture
def stand_ins(monkeypatch):
    """The stage kernels of ``ops.bls12_381`` as shape-true stand-ins;
    ``stand_ins["on_curve"]`` sets what decompression reports."""
    import jax
    import jax.numpy as jnp

    from lighthouse_tpu.ops import bls12_381 as k

    state = {"on_curve": True}

    def decompress(x, flags):
        x = jnp.asarray(x)
        return x + 0, jnp.full(x.shape[0], state["on_curve"])

    stubs = {
        "g2_decompress_batch": decompress,
        "g2_in_subgroup_batch": lambda x, y, z: jnp.ones(x.shape[0], bool),
        "hash_to_g2_batch_from_u":
            lambda u0, u1: (jnp.asarray(u0), jnp.asarray(u1),
                            jnp.asarray(u0)),
        "jacobian_to_affine_fp2": lambda x, y, z: (x + 0, y + 0),
        "jacobian_to_affine_fp": lambda x, y, z: (x + 0, y + 0),
        "g1_scalar_mul_jit":
            lambda x, y, z, bits: (jnp.asarray(x), jnp.asarray(y),
                                   jnp.asarray(z)),
        "g2_scalar_mul_jit": lambda x, y, z, bits: (x, y, z),
        "g1_segment_sum":
            lambda x, y, z, starts, ends: (x[jnp.asarray(ends)],
                                           y[jnp.asarray(ends)],
                                           z[jnp.asarray(ends)]),
        "g2_sum": lambda x, y, z: (x[0], y[0], z[0]),
        "pairing_check_batch":
            lambda px, py, qx, qy, mask=None: jnp.any(jnp.asarray(mask)),
    }
    for name, fn in stubs.items():
        monkeypatch.setattr(k, name, fn)
    yield state
    # a real kernel traced meanwhile called the stand-ins; JAX's caches
    # would hand that program to later tests of this process
    jax.clear_caches()


@pytest.fixture
def tpu(monkeypatch):
    """A fresh ``TpuBackend`` as the current backend."""
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.crypto.bls.tpu_backend import TpuBackend
    backend = TpuBackend()
    monkeypatch.setattr(bls, "_current", backend)
    return backend


def _sets():
    from lighthouse_tpu.crypto.bls import SignatureSet
    from lighthouse_tpu.crypto.bls12_381 import (
        G1_GENERATOR, G2_GENERATOR, g1_compress, g2_compress,
    )
    sig, pk = g2_compress(G2_GENERATOR), g1_compress(G1_GENERATOR)
    return [SignatureSet(sig, [pk], m) for m in (b"a", b"b", b"a")]


def _verify():
    from lighthouse_tpu.crypto import bls
    tracing.clear()
    verdict = bls.verify_signature_sets(_sets())
    return verdict, tracing.snapshot()


def test_verify_records_host_and_device_stages_in_the_batch_span(
        stand_ins, tpu):
    verdict, spans = _verify()
    assert verdict is True
    (batch,) = [s for s in spans if s.kind == "bls_batch_verify"]
    inside = [s for s in spans if s is not batch]
    assert sorted({s.kind for s in inside}) == sorted(
        HOST_STAGES + DEVICE_STAGES)
    assert [s.kind for s in inside].count("bls_scalars") == 2
    for s in inside:
        assert s.parent_id == batch.span_id, s.kind
        assert s.trace_id == batch.trace_id
        assert batch.start <= s.start <= s.end <= batch.end, s.kind
    # the host waited for the watcher: every device span landed in the
    # ring before the batch span closed
    assert spans[-1] is batch


def test_device_spans_are_in_order_and_do_not_overlap(stand_ins, tpu):
    _, spans = _verify()
    device = [s for s in spans if s.kind in DEVICE_STAGES]
    assert [s.kind for s in device] == DEVICE_STAGES
    for before, after in zip(device, device[1:]):
        assert before.end <= after.start
    watcher = {s.thread_name for s in device}
    assert watcher == {"device-span-watcher"}


def test_failed_decompression_records_only_its_stage(stand_ins, tpu):
    stand_ins["on_curve"] = False
    verdict, spans = _verify()
    assert verdict is False
    kinds = [s.kind for s in spans]
    assert [k for k in kinds if k in DEVICE_STAGES] == ["bls_decompress"]
    assert "bls_scalars" not in kinds


def test_make_jaxpr_over_the_device_half_records_nothing(
        stand_ins, tpu, monkeypatch):
    import jax

    from lighthouse_tpu.crypto.bls import tpu_backend as tb
    small, big = tb.lane_options()
    prep = tb.host_prepare(*tb.parse_sets(tpu, _sets()), small, small)
    arrays = {n: v for n, v in prep.items() if isinstance(v, np.ndarray)}
    monkeypatch.setattr(tracing, "_watcher", None)
    tracing.clear()
    jax.make_jaxpr(lambda a: list(
        tb.device_checks({**prep, **a}, small)))(arrays)
    assert tracing.snapshot() == []
    assert tracing._watcher is None             # no watcher started


def test_verify_counts_constant_ladder_steps_and_additions(stand_ins, tpu):
    """Each dispatch of a program with a constant ladder adds its static
    steps and additions: the subgroup check's |u|, the cofactor
    clearing's k1 and k2, the Miller loop's |x|."""
    from lighthouse_tpu.api import metrics, metrics_defs
    from lighthouse_tpu.ops import bls12_381 as k
    names = ("bls_const_ladder_steps_total", "bls_const_ladder_adds_total")
    assert all(name in metrics_defs.CATALOG for name in names)
    before = [metrics.counter_value(name) for name in names]
    verdict, _ = _verify()
    assert verdict is True
    steps, adds = (metrics.counter_value(name) - was
                   for name, was in zip(names, before))
    constants = [k._U_ABS2, k._BP_K1, k._BP_K2, abs(k.X_PARAM)]
    assert [k.ladder_counts(c) for c in constants] == [
        (63, 5), (127, 37), (63, 6), (63, 5)]
    assert (steps, adds) == (63 + 127 + 63 + 63, 5 + 37 + 6 + 5)


def test_multi_key_sets_add_the_pubkey_sum_stage_first(stand_ins, tpu,
                                                       monkeypatch):
    """A set of several keys puts the ``bls_pk_aggregate`` device stage
    (table gather, bucket sums) before decompression and feeds the
    aggregation counters: keys summed, key lanes left empty."""
    import jax.numpy as jnp

    from lighthouse_tpu.api import metrics
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.crypto.bls import SignatureSet
    from lighthouse_tpu.crypto.bls import tpu_backend as tb
    from lighthouse_tpu.crypto.bls12_381 import G1_GENERATOR, g1_compress
    from lighthouse_tpu.ops import bls12_381 as k
    monkeypatch.setattr(
        k, "g1_bucket_sum",
        lambda x, y, live, starts, ends, multi:
        (x[0, :len(multi)], y[0, :len(multi)], x[0, :len(multi)],
         jnp.ones(len(multi), bool)))
    names = ("bls_pubkeys_aggregated_total", "bls_key_lanes_padded_total")
    before = [metrics.counter_value(name) for name in names]
    sets = _sets()
    sets.append(SignatureSet(sets[0].signature, [
        g1_compress(G1_GENERATOR.mul(m)) for m in (2, 3, 4)], b"c"))
    tracing.clear()
    assert bls.verify_signature_sets(sets) is True
    device = [s.kind for s in tracing.snapshot()
              if s.kind in DEVICE_STAGES + ["bls_pk_aggregate"]]
    assert device == ["bls_pk_aggregate"] + DEVICE_STAGES
    depth, buckets = tb.key_shape()
    keys, padded = (metrics.counter_value(name) - was
                    for name, was in zip(names, before))
    # every set's keys are summed once one set has several: the three
    # generator sets' and the multi-key set's, four distinct keys
    assert (keys, padded) == (6, depth * buckets - 6)
    assert tpu.table.size == 4


def test_a_node_compiles_no_stage_program_on_its_first_multi_key_verify(
        stand_ins, monkeypatch):
    """``ClientBuilder`` fills the pubkey table before it compiles the
    stage programs, so the table gather is compiled at the loaded shape
    (a registry past the first block of rows grows the table)."""
    import threading
    import time

    import jax.monitoring

    from lighthouse_tpu.client.builder import ClientBuilder, ClientConfig
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.crypto.bls import SignatureSet
    from lighthouse_tpu.crypto.bls import pubkey_table
    from lighthouse_tpu.ops import bls12_381 as k
    from lighthouse_tpu.specs import minimal_spec

    from lighthouse_tpu.state_transition import interop_genesis_state

    spec = minimal_spec(altair_fork_epoch=0)
    count = pubkey_table.block_rows() + 8
    monkeypatch.setattr(bls, "_current", None)
    bls.set_backend("cpp")              # the genesis keys, natively
    genesis = interop_genesis_state(       # slot 0 now: no catching up
        spec, [bls.keygen_interop(i) for i in range(count)],
        genesis_time=int(time.time()))
    client = ClientBuilder(spec).with_config(ClientConfig(
        crypto_backend="tpu", http_enabled=False,
        genesis_state=genesis)).build()
    compiled, verifying = [], []         # names as "jit(<function>)"
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, fun_name="", **kw: compiled.append(
            fun_name.removeprefix("jit(").removesuffix(")"))
        if verifying and event == "/jax/core/compile/backend_compile_duration"
        else None)
    try:
        backend = bls.get_backend()
        assert backend.table.size == count
        keys = [bytes(pk) for pk in
                client.chain.head().head_state.validators.pubkeys[-3:]]
        sig = bls.PythonBackend().sign(1, b"m")
        verifying.append(True)
        bls.verify_signature_sets([SignatureSet(sig, keys, b"m")])
        verifying.clear()
    finally:
        client.env.shutdown("test over")      # the per-slot timer ends
        client.stop()
        client.processor.stop()
        for t in threading.enumerate():
            if t.name == "timer":
                t.join(10)
    stages = {name for name, f in vars(k).items()
              if isinstance(f, type(k.final_exponentiation))}
    assert "g1_table_gather" in stages
    assert not stages & set(compiled), compiled


def test_device_span_of_a_failed_stage_still_ends(monkeypatch):
    import jax

    def fail(outputs):
        raise RuntimeError("device lost")

    monkeypatch.setattr(jax, "block_until_ready", fail)
    tracing.clear()
    with tracing.span("bls_batch_verify") as batch:
        stage = tracing.device_span("bls_pairing")
        stage.watch(jax.numpy.ones(2))
        tracing.wait_device_spans()
    (s,) = [s for s in tracing.snapshot() if s.kind == "bls_pairing"]
    assert s.attrs["error"] == "RuntimeError"
    assert s.parent_id == batch.span_id and s.end >= s.start


def test_clear_lands_the_device_spans_watched_before_it(monkeypatch):
    """A stage still running when the ring is cleared is recorded before
    the clear returns: it cannot land among a later reader's spans."""
    import threading

    import jax

    ready = threading.Event()
    real = jax.block_until_ready

    def slow(outputs):
        ready.wait(10)
        return real(outputs)

    monkeypatch.setattr(jax, "block_until_ready", slow)
    stage = tracing.device_span("bls_pairing")
    stage.watch(jax.numpy.ones(2))
    threading.Timer(0.2, ready.set).start()
    tracing.clear()
    assert stage.recorded.is_set()
    tracing.wait_device_spans()
    assert tracing.snapshot() == []


# -- block import ------------------------------------------------------------

def test_block_import_stage_spans_nest_in_block_import():
    from lighthouse_tpu.chain.harness import BeaconChainHarness
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.specs import minimal_spec
    bls.set_backend("fake")
    h = BeaconChainHarness(minimal_spec(), 32)
    h.advance_slot()
    signed, _post = h.produce_signed_block()
    tracing.clear()
    h.chain.process_gossip_block(signed)
    spans = tracing.snapshot()
    by_id = {s.span_id: s for s in spans}
    (imp,) = [s for s in spans if s.kind == "block_import"]

    def ancestors(s):
        while s.parent_id in by_id:
            s = by_id[s.parent_id]
            yield s.kind

    for kind in IMPORT_STAGES:
        (s,) = [s for s in spans if s.kind == kind]
        assert "block_import" in ancestors(s), kind
        assert imp.start <= s.start <= s.end <= imp.end
    (batch,) = [s for s in spans if s.kind == "batch_signature"]
    for kind in ("pre_state", "signature_sets"):
        (s,) = [s for s in spans if s.kind == kind]
        assert s.parent_id == batch.span_id
    for kind in ("post_import", "head_update"):
        (s,) = [s for s in spans if s.kind == kind]
        assert s.parent_id == imp.span_id
    (db,) = [s for s in spans if s.kind == "db_write"]
    (post,) = [s for s in spans if s.kind == "post_import"]
    (head,) = [s for s in spans if s.kind == "head_update"]
    assert db.end <= post.start <= post.end <= head.start


# -- profiler clock ----------------------------------------------------------

def test_host_span_is_an_annotation_of_the_same_length(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData
    double = jax.jit(lambda x: 2 * x + 1)
    double(jnp.ones(8)).block_until_ready()       # compiled outside
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.span("db_write") as s:
            double(jnp.ones(8)).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (xspace,) = tmp_path.rglob("*.xplane.pb")
    events = [ev for plane in ProfileData.from_file(str(xspace)).planes
              for line in plane.lines for ev in line.events
              if ev.name == tracing.PROFILER_PREFIX + "db_write"]
    assert len(events) == 1
    assert abs(events[0].duration_ns / 1e9 - s.duration) < 1e-3


# -- the benchmark's readers -------------------------------------------------

def _reader(name):
    path = REPO / "benchmark" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


class _Readings:
    def __init__(self, spans):
        self.spans = spans


# two batches: host stages 0.1 + 0.2 + 2 x 0.05 s in the first, 0.1 +
# 0.3 + 2 x 0.05 in the second; each device stage 1 s in one, 3 s in the
# other
BATCHES = [("bls_batch_verify", 0.0, 10.0), ("bls_batch_verify", 20.0, 30.0),
           ("bls_parse", 0.0, 0.1), ("bls_prepare", 0.1, 0.3),
           ("bls_scalars", 3.0, 3.05), ("bls_scalars", 3.1, 3.15),
           ("bls_parse", 20.0, 20.1), ("bls_prepare", 20.1, 20.4),
           ("bls_scalars", 23.0, 23.05), ("bls_scalars", 23.1, 23.15)] + [
    (kind, lo + i, lo + i + d)
    for lo, d in ((0.3, 1.0), (20.4, 3.0))
    for i, kind in enumerate(DEVICE_STAGES)]


@pytest.mark.parametrize("name, want", [
    ("bls.host_ms", 1000 * (0.4 + 0.5) / 2),
    ("bls.decompress_ms", 2000.0), ("bls.subgroup_ms", 2000.0),
    ("bls.hash_ms", 2000.0), ("bls.rlc_ms", 2000.0),
    ("bls.pairing_ms", 2000.0)])
def test_bls_stage_readers(name, want):
    assert _reader(name)(_Readings(BATCHES)) == pytest.approx(want)


# two imports; the first holds a nested pair inside batch_signature, an
# adjacent state_transition and a head update, inside a processor span
# that holds the import
IMPORTS = [("processor_work", -0.1, 1.1), ("block_import", 0.0, 1.0),
           ("batch_signature", 0.1, 0.3), ("pre_state", 0.1, 0.15),
           ("signature_sets", 0.15, 0.25), ("state_transition", 0.3, 0.5),
           ("post_import", 0.5, 0.55), ("head_update", 0.6, 0.7),
           ("block_import", 2.0, 2.4), ("pre_state", 2.0, 2.05),
           ("signature_sets", 2.05, 2.1), ("post_import", 2.1, 2.2),
           ("head_update", 2.3, 2.31)]


@pytest.mark.parametrize("name, want", [
    ("pre_state.block_ms", 1000 * (0.05 + 0.05) / 2),
    ("sig_sets.block_ms", 1000 * (0.1 + 0.05) / 2),
    ("post_import.block_ms", 1000 * (0.05 + 0.1) / 2),
    ("head.block_ms", 1000 * (0.1 + 0.01) / 2),
    # the first: 1.0 less [0.1, 0.55] and [0.6, 0.7]; the second: 0.4
    # less [2.0, 2.2] and [2.3, 2.31]
    ("import.self_ms", 1000 * ((1.0 - 0.55) + (0.4 - 0.21)) / 2)])
def test_import_stage_readers(name, want):
    assert _reader(name)(_Readings(IMPORTS)) == pytest.approx(want)


@pytest.mark.parametrize("name", [
    "bls.host_ms", "bls.decompress_ms", "bls.subgroup_ms", "bls.hash_ms",
    "bls.rlc_ms", "bls.pairing_ms", "pre_state.block_ms",
    "sig_sets.block_ms", "post_import.block_ms", "head.block_ms"])
def test_readers_report_nothing_without_their_spans(name):
    # a program without the stage spans: only the enclosing ones
    spans = [("bls_batch_verify", 0.0, 1.0), ("block_import", 2.0, 3.0)]
    assert _reader(name)(_Readings(spans)) is None


def test_every_new_span_kind_is_read_by_a_benchmark_metric():
    texts = "".join(p.read_text() for p in
                    (REPO / "benchmark" / "metrics").glob("*.py"))
    for kind in HOST_STAGES + DEVICE_STAGES + IMPORT_STAGES:
        assert f'"{kind}"' in texts, kind
