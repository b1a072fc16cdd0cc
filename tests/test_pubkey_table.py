"""The tpu backend's device pubkey table and the device sums of multi-key
signature sets.

- The bulk loader validates keys off the pure-Python path (garbage,
  identity and off-subgroup keys get no row) and its rows hold the
  oracle's affine coordinates; loading again adds nothing.
- The device sums (``pubkey_sums``: table gather, bucket sums across
  chunks) equal the native library's aggregates on sets of 1, 2, 37 and
  512 keys, with a key not yet in the table; a set of pk and -pk is
  flagged as the identity.
- Verdicts: the ``tpu`` backend equals the ``python`` and ``cpp``
  backends on mixed batches.  Its compile-heavy signature stages
  (decompression, subgroup check, hashing, pairing: unchanged by the
  table and ~10 minutes of CPU compiles) are the ``cpp`` backend's
  pairing applied to the device's pubkey sums; everything before them
  runs as in a verify (parse, table lookups and growth, preparation,
  the device sums and their identity check).  The benchmark's CPU run
  of ``signed_block_stream`` covers the whole device path.
- A minimal-preset chain whose blocks carry real signatures imports on
  ``tpu`` through ``process_block``; its invalid twin is refused with
  ``INVALID_SIGNATURE``.
"""
import copy
import secrets

import numpy as np
import pytest

from lighthouse_tpu.crypto import bls
from lighthouse_tpu.crypto.bls import SignatureSet
from lighthouse_tpu.crypto.bls import tpu_backend as tb
from lighthouse_tpu.crypto.bls.cpp_backend import CppBackend
from lighthouse_tpu.crypto.bls.pubkey_table import PubkeyTable, _validate
from lighthouse_tpu.crypto.bls12_381 import g1_compress
from lighthouse_tpu.crypto.bls12_381.curve import G1Point
from lighthouse_tpu.crypto.bls12_381.fields import P
from lighthouse_tpu.ops import bls12_381 as k

CPP = CppBackend()
KEYS = [CPP.sk_to_pk(5000 + i) for i in range(600)]
UNKNOWN = [CPP.sk_to_pk(9000 + i) for i in range(3)]
INFINITY = b"\xc0" + b"\x00" * 47


def negated(pk: bytes) -> bytes:
    """-pk: the same x with the other y."""
    return bytes([pk[0] ^ 0x20]) + pk[1:]


def off_subgroup_keys(count: int) -> list:
    """Compressed points on the curve but outside G1 (by the definition,
    [r]P != O), from x = 1 up."""
    from lighthouse_tpu.crypto.bls12_381.curve import B_G1, Point
    from lighthouse_tpu.crypto.bls12_381.fields import Fp
    out, x = [], 1
    while len(out) < count:
        y = (Fp(x) ** 3 + B_G1).sqrt()
        if y is not None:
            pt = Point.from_affine(Fp(x), y, B_G1)
            if not pt.in_subgroup():
                out.append(g1_compress(pt))
        x += 1
    return out


def off_subgroup_key() -> bytes:
    return off_subgroup_keys(1)[0]


def decode_points(x, y, z) -> list:
    """Jacobian device limbs -> affine (x, y) ints per lane (None for the
    identity)."""
    out = []
    for a, b, c in zip(k.fp_decode(x), k.fp_decode(y), k.fp_decode(z)):
        if c == 0:
            out.append(None)
            continue
        zi = pow(c, -1, P)
        out.append((a * zi * zi % P, b * zi * zi * zi % P))
    return out


def compressed(point) -> bytes:
    return g1_compress(G1Point(*point))


# -- the table ---------------------------------------------------------------

def test_bulk_load_validates_keys_and_holds_their_coordinates():
    bad = [b"\x03" * 48, INFINITY, off_subgroup_key(), b"\x80" * 47]
    table = PubkeyTable()
    registry = np.frombuffer(b"".join(KEYS[:40]), np.uint8).reshape(40, 48)
    assert table.load(registry) == 40
    assert table.load(KEYS[30:50] + bad) == 10
    assert table.load(KEYS[:50] + bad) == 0          # nothing new
    assert table.size == 50 and set(bad) <= table.invalid
    assert table.rows_of([KEYS[49], KEYS[0]]).tolist() == [49, 0]
    assert table.rows_of([KEYS[0], bad[2]]) is None
    assert table.rows_of([UNKNOWN[0]]).tolist() == [50]      # grown
    x, y = (np.asarray(a)[:51] for a in table.arrays())
    got = decode_points(x, y, np.broadcast_to(k.FP_ONE, x.shape))
    want = KEYS[:50] + UNKNOWN[:1]
    assert [compressed(p) for p in got] == want
    valid, xy, invalid = _validate(want[:3] + bad[:1])
    assert valid == want[:3] and invalid == {bad[0]} and xy.shape == (3, 96)


def test_native_g1_check_agrees_with_the_definition():
    """The native KeyValidate's G1 check (the endomorphism sigma with its
    constant beta) accepts keys in G1 and refuses on-curve points outside
    it, as [r]P does, in the single-key call and in the bulk loader."""
    inside = KEYS[:4] + [negated(KEYS[4])]
    outside = off_subgroup_keys(4)
    assert all(CPP.validate_pubkey(pk) for pk in inside)
    assert not any(CPP.validate_pubkey(pk) for pk in outside)
    valid, _, invalid = _validate(inside + outside)
    assert valid == inside and invalid == set(outside)


def test_concurrent_growth_gives_each_key_one_row():
    """Threads (more than cores) naming overlapping new keys at once: each
    key gets one row, rows are dense, and each row holds its key."""
    import os
    import sys
    import threading
    table = PubkeyTable()
    keys = KEYS[100:300]
    got, errors = {}, []

    def worker(i):
        try:
            part = keys[i * 10:i * 10 + 60]
            got[i] = dict(zip(part, table.rows_of(part).tolist()))
        except Exception as exc:           # reported below
            errors.append(exc)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(max(16, 2 * (os.cpu_count() or 1)))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
    finally:
        sys.setswitchinterval(interval)
    assert not errors and not any(t.is_alive() for t in threads)
    named = {key for i in got for key in got[i]}
    assert table.size == len(named) == len(set(table.rows.values()))
    assert sorted(table.rows.values()) == list(range(table.size))
    for part in got.values():
        assert all(table.rows[key] == row for key, row in part.items())
    x, y = (np.asarray(a)[:table.size] for a in table.arrays())
    by_row = {row: key for key, row in table.rows.items()}
    points = decode_points(x, y, np.broadcast_to(k.FP_ONE, x.shape))
    assert [compressed(p) for p in points] == [by_row[r] for r in
                                                range(table.size)]


# -- device sums -------------------------------------------------------------

def sets_of_sizes(sizes, message=b"m"):
    out, at = [], 0
    for i, n in enumerate(sizes):
        out.append(SignatureSet(CPP.sign(7, message + bytes([i])),
                                KEYS[at:at + n], message + bytes([i])))
        at += n
    return out


def device_sums(backend, pks, sig_xs, flags, msgs, rows):
    """The device pubkey sums of one parsed chunk, per set in the order
    of ``msgs`` (affine ints, None for the identity), with their identity
    flags and the preparation."""
    small, _ = tb.lane_options()
    prep = tb.host_prepare(pks, sig_xs, flags, msgs, rows, small, small)
    if "agg_rows" in prep:
        prep["pk_table"] = backend.table.arrays()
    px, py, pz, ok = tb.pubkey_sums(prep, small)
    gid = {}
    order = sorted(range(len(msgs)),
                   key=lambda i: gid.setdefault(msgs[i], len(gid)))
    points = decode_points(px, py, pz)
    by_set = [None] * len(msgs)
    for pos, i in enumerate(order):
        by_set[i] = points[pos]
    return by_set, ok, prep


def test_device_sums_equal_the_native_aggregates():
    backend = tb.TpuBackend()
    backend.load_pubkeys(KEYS[:550])
    sets = sets_of_sizes([1, 2, 37, 512])
    sets.append(SignatureSet(sets[0].signature, [KEYS[0]] + UNKNOWN[:2],
                             b"u"))
    got, ok, prep = device_sums(backend, *tb.parse_sets(backend, sets))
    # 512 keys in buckets of 4 cross chunks on the CPU's 4 x 32 shape
    assert prep["agg_rows"].shape[0] > 1
    assert prep["agg_keys"] == 1 + 2 + 37 + 512 + 3
    assert bool(np.asarray(ok).all())
    for s, point in zip(sets, got):
        assert compressed(point) == CPP.aggregate_public_keys(s.pubkeys)
    assert backend.table.size == 550 + 2 + 2     # grown by four keys


def test_a_set_summing_to_the_identity_is_flagged():
    backend = tb.TpuBackend()
    sets = sets_of_sizes([2, 3])
    sets.append(SignatureSet(sets[0].signature,
                             [KEYS[9], negated(KEYS[9])], b"zero"))
    got, ok, _ = device_sums(backend, *tb.parse_sets(backend, sets))
    assert got[2] is None
    ok = np.asarray(ok)
    assert not ok.all() and ok.sum() == len(ok) - 1


# -- verdicts ----------------------------------------------------------------

@pytest.fixture
def device_sums_then_cpp_pairing(monkeypatch):
    """``TpuBackend`` verifying a chunk up to and including the device
    pubkey sums, then the batch equation by the ``cpp`` backend on those
    sums (one aggregated key per set)."""
    def verify_chunk(self, pks, sig_xs, sig_flags, msgs, rows, lanes):
        got, ok, _ = device_sums(self, pks, sig_xs, sig_flags, msgs, rows)
        if ok is not None and not np.asarray(ok).all():
            return False
        sigs = [(c1 | 1 << 383 | flag << 381).to_bytes(48, "big")
                + c0.to_bytes(48, "big")
                for (c0, c1), flag in zip(sig_xs, sig_flags)]
        return CPP._verify_sets_raw(
            [(sig, [compressed(p)], msg)
             for sig, p, msg in zip(sigs, got, msgs)],
            [secrets.randbits(64) | 1 for _ in sigs])

    monkeypatch.setattr(tb.TpuBackend, "_verify_chunk", verify_chunk)


def python_backend():
    """A ``python`` backend whose point cache holds the keys, as a node's
    persisted cache would (pure-Python decompression is ~40 ms a key)."""
    py = bls.PythonBackend()
    keys = KEYS + UNKNOWN
    valid, xy, _ = _validate(keys)
    for key, row in zip(valid, xy):
        py._pk_cache[key] = G1Point(int.from_bytes(row[:48], "big"),
                                    int.from_bytes(row[48:], "big"))
    return py


def signed_sets(sizes):
    out, at = [], 0
    for i, n in enumerate(sizes):
        msg = b"block %d" % i
        sk = sum(range(5000 + at, 5000 + at + n))
        out.append(SignatureSet(CPP.sign(sk, msg), KEYS[at:at + n], msg))
        at += n
    return out


def batch(case: str) -> list:
    sets = signed_sets([1, 2, 37, 512])
    if case == "invalid_set":           # another set's valid signature
        sets[2] = SignatureSet(sets[1].signature, sets[2].pubkeys,
                               sets[2].message)
    elif case == "identity_sum":
        sets.append(SignatureSet(sets[0].signature,
                                 [KEYS[0], negated(KEYS[0])], b"zero"))
    elif case == "unknown_key":         # signed by keys not yet loaded
        sets.append(SignatureSet(CPP.sign(9000 + 9001, b"new"),
                                 UNKNOWN[:2], b"new"))
    elif case == "invalid_key_bytes":
        sets.append(SignatureSet(sets[0].signature,
                                 [KEYS[0], b"\x03" * 48], b"bad"))
    return sets


@pytest.mark.parametrize("case,verdict", [
    ("valid", True), ("invalid_set", False), ("identity_sum", False),
    ("unknown_key", True), ("invalid_key_bytes", False)])
def test_tpu_verdicts_equal_python_and_cpp(device_sums_then_cpp_pairing,
                                           case, verdict):
    sets = batch(case)
    tpu = tb.TpuBackend()
    tpu.load_pubkeys(KEYS[:552])
    assert tpu.verify_signature_sets(sets) is verdict
    assert CPP.verify_signature_sets(sets) is verdict
    assert python_backend().verify_signature_sets(sets) is verdict


# -- a signed chain ----------------------------------------------------------

def test_signed_minimal_chain_imports_on_tpu(device_sums_then_cpp_pairing,
                                             monkeypatch):
    from lighthouse_tpu.chain.errors import INVALID_SIGNATURE, BlockError
    from lighthouse_tpu.chain.harness import BeaconChainHarness
    from lighthouse_tpu.specs import minimal_spec

    monkeypatch.setattr(bls, "_current", None)
    bls.set_backend("cpp")
    spec = minimal_spec(altair_fork_epoch=0)
    producer = BeaconChainHarness(spec, 64)
    importer = BeaconChainHarness(spec, 64)    # the same genesis
    blocks = []
    for _ in range(3):
        producer.advance_slot()
        signed, _ = producer.produce_signed_block()
        producer.chain.process_block(signed)
        producer.attest_to_head()
        blocks.append(signed)
    last = blocks[-1].message
    atts = last.body.attestations
    assert len(atts) >= 2 and sum(atts[0].aggregation_bits) > 1
    assert sum(last.body.sync_aggregate.sync_committee_bits) > 1
    # the twin: one attestation carries another's valid signature, and
    # the proposal is signed anew so only the batch can refuse it
    twin = copy.deepcopy(last)
    twin.body.attestations[0].signature = atts[1].signature
    twin_signed = producer.sign_block(twin, producer.chain.head().head_state)

    tpu = bls.set_backend("tpu")
    importer.set_slot(last.slot)
    for signed in blocks[:-1]:
        importer.chain.process_block(signed)
    with pytest.raises(BlockError) as refused:
        importer.chain.process_block(twin_signed)
    assert refused.value.kind == INVALID_SIGNATURE
    root = importer.chain.process_block(blocks[-1])
    assert importer.chain.fork_choice.contains_block(root)
    assert tpu.table.size > 0                  # grown from the blocks
