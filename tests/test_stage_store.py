"""The stage programs' start-up store (``ops/stages.py``).

- A program compiled from the store runs its registered executable,
  with the jit's results, and a second start-up reads its exported
  module back without exporting it again.
- Calls the executable cannot take (tracers, other shapes) go to the
  jit; each argument signature has its own stored module.
- ``compile_stage_programs`` keeps the list of a prep's programs in the
  store: a later start-up finds them without tracing the device half,
  and the verify that follows compiles none of them; with a kernel
  swapped for a stand-in it neither reads nor writes the store.
"""
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from test_stage_spans import _sets, stand_ins, tpu  # noqa: E402,F401


@pytest.fixture
def stage_programs():
    """The stage programs of ``ops.bls12_381``, their registries emptied
    after the test."""
    from lighthouse_tpu.ops import bls12_381 as k
    from lighthouse_tpu.ops import stages
    programs = [f for f in vars(k).values() if isinstance(f, stages.Stage)]
    yield k
    for f in programs:
        f._loaded.clear()


def _points(n: int):
    from lighthouse_tpu.ops import bls12_381 as k
    return (k.fp_encode(list(range(3, 3 + n))),
            k.fp_encode(list(range(20, 20 + n))),
            k.fp_encode([1, 2] * (n // 2)))


def _types(arrays):
    import jax
    return tuple(jax.ShapeDtypeStruct(a.shape, a.dtype) for a in arrays)


def test_stored_program_runs_its_executable_and_reloads_it(
        stage_programs, tmp_path, monkeypatch):
    from lighthouse_tpu.ops import stages
    k = stage_programs
    program = k.jacobian_to_affine_fp
    points = _points(4)
    want = [np.asarray(a) for a in program(*points)]
    stages.compile_stored(program, _types(points), tmp_path)
    assert [p.suffix for p in tmp_path.iterdir()] == [".exported"]

    def no_jit(*args):
        raise AssertionError("the jit ran")

    with monkeypatch.context() as patch:
        patch.setattr(program, "_jit", no_jit)
        assert all(np.array_equal(g, w)
                   for g, w in zip(program(*points), want))

    # a second start-up: the module is read back, not exported again
    program._loaded.clear()
    with monkeypatch.context() as patch:
        patch.setattr(stages.export, "export", no_jit)
        stages.compile_stored(program, _types(points), tmp_path)
    assert len(program._loaded) == 1
    assert all(np.array_equal(g, w) for g, w in zip(program(*points), want))


def test_calls_the_executable_cannot_take_go_to_the_jit(
        stage_programs, tmp_path):
    import jax

    from lighthouse_tpu.ops import stages
    k = stage_programs
    program = k.jacobian_to_affine_fp
    stages.compile_stored(program, _types(_points(4)), tmp_path)
    jaxpr = jax.make_jaxpr(program)(*_points(4))
    assert [e.params.get("name") for e in jaxpr.eqns] == \
        ["jacobian_to_affine_fp"]
    wide = _points(8)
    got = program(*wide)
    want = program._jit(*wide)
    assert all(np.array_equal(g, w) for g, w in zip(got, want))
    # another signature is another module
    stages.compile_stored(program, _types(wide), tmp_path)
    assert len(list(tmp_path.glob("jacobian_to_affine_fp-*"))) == 2
    assert len(program._loaded) == 2


def test_start_up_reads_the_stage_list_and_verify_compiles_nothing(
        stand_ins, tpu, stage_programs, tmp_path, monkeypatch):
    """With the heavy kernels swapped for stand-ins, the pubkey sums'
    programs (table gather, bucket sums, merge) are the stage programs
    of a multi-key batch.  The store is this test's own, so it may hold
    programs traced through the stand-ins."""
    import jax
    import jax.monitoring

    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.crypto.bls import SignatureSet
    from lighthouse_tpu.crypto.bls import tpu_backend as tb
    from lighthouse_tpu.crypto.bls12_381 import G1_GENERATOR, g1_compress
    from lighthouse_tpu.ops import stages

    monkeypatch.setattr(stages, "store_dir", lambda: tmp_path)
    monkeypatch.setattr(stages, "pristine", lambda: True)
    sets = _sets()
    sets.append(SignatureSet(sets[0].signature, [
        g1_compress(G1_GENERATOR.mul(m)) for m in (2, 3, 4)], b"c"))
    tpu.table.rows_of([pk for s in sets for pk in s.pubkeys])
    small, _ = tb.lane_options()
    prep = {**tb.host_prepare(*tb.parse_sets(tpu, sets), small, small),
            "pk_table": tpu.table.arrays()}
    first = sorted(name for name, _ in tb.compile_stage_programs(
        [(prep, small)]))
    assert "g1_bucket_sum" in first
    assert len(list(tmp_path.glob("list-*.json"))) == 1

    # a later start-up: nothing is traced
    for f in vars(stage_programs).values():
        if isinstance(f, stages.Stage):
            f._loaded.clear()

    def no_trace(*args, **kwargs):
        raise AssertionError("the device half was traced")

    with monkeypatch.context() as patch:
        patch.setattr(jax, "make_jaxpr", no_trace)
        again = tb.compile_stage_programs([(prep, small)])
    assert sorted(name for name, _ in again) == first

    compiled = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, fun_name="", **kw: compiled.append(
            fun_name.removeprefix("jit(").removesuffix(")"))
        if event == "/jax/core/compile/backend_compile_duration" else None)
    assert bls.verify_signature_sets(sets) is True
    assert not set(first) & set(compiled), compiled


def test_a_swapped_kernel_keeps_start_up_off_the_store(
        stand_ins, tpu, stage_programs, tmp_path, monkeypatch):
    from lighthouse_tpu.crypto.bls import SignatureSet
    from lighthouse_tpu.crypto.bls import tpu_backend as tb
    from lighthouse_tpu.crypto.bls12_381 import G1_GENERATOR, g1_compress
    from lighthouse_tpu.ops import stages

    monkeypatch.setattr(stages, "store_dir", lambda: tmp_path)
    assert not stages.pristine()
    sets = _sets() + [SignatureSet(_sets()[0].signature, [
        g1_compress(G1_GENERATOR.mul(m)) for m in (2, 3)], b"c")]
    small, _ = tb.lane_options()
    prep = {**tb.host_prepare(*tb.parse_sets(tpu, sets), small, small),
            "pk_table": tpu.table.arrays()}
    assert tb.compile_stage_programs([(prep, small)])
    assert not list(tmp_path.iterdir())
    assert not any(f._loaded for f in vars(stage_programs).values()
                   if isinstance(f, stages.Stage))
