"""Native batch atomicity + torn-tail recovery, driven from Python.

Complements tests/test_crash_recovery.py (whole-process kills) by attacking
the log file itself: every truncation point inside the final batch record
must drop that batch WHOLE — prior records intact, no partial batch ever
visible.  Plus the pure-Python rollback contracts of MemoryStore and the
KeyValueStore default.
"""
from __future__ import annotations

import shutil

import pytest

from lighthouse_tpu.store import (
    KeyValueStore, MemoryStore, NativeKvStore, StoreError,
)


# -- MemoryStore / default rollback ------------------------------------------


def test_memory_store_batch_rolls_back_on_failure():
    s = MemoryStore()
    s.put(b"a", b"old-a")
    s.put(b"b", b"old-b")
    with pytest.raises(StoreError):
        s.do_atomically([("put", b"a", b"new-a"),
                         ("delete", b"b", None),
                         ("frobnicate", b"c", b"boom")])
    assert s.get(b"a") == b"old-a"
    assert s.get(b"b") == b"old-b"
    assert s.get(b"c") is None


def test_memory_store_batch_applies_whole():
    s = MemoryStore()
    s.put(b"b", b"old-b")
    s.do_atomically([("put", b"a", b"v1"), ("delete", b"b", None)])
    assert s.get(b"a") == b"v1"
    assert s.get(b"b") is None


class _DictStore(KeyValueStore):
    """Minimal backend exercising the trait's DEFAULT do_atomically."""

    def __init__(self):
        self._d: dict[bytes, bytes] = {}

    def get(self, key):
        return self._d.get(key)

    def put(self, key, value):
        self._d[key] = bytes(value)

    def delete(self, key):
        self._d.pop(key, None)


def test_default_do_atomically_rolls_back_prefix():
    s = _DictStore()
    s.put(b"a", b"old-a")
    s.put(b"b", b"old-b")
    with pytest.raises(StoreError):
        s.do_atomically([("put", b"a", b"new-a"),
                         ("put", b"fresh", b"x"),
                         ("delete", b"b", None),
                         ("bogus", b"z", None)])
    assert s.get(b"a") == b"old-a"
    assert s.get(b"b") == b"old-b"
    assert s.get(b"fresh") is None


# -- native batch replay -----------------------------------------------------


def _open(path) -> NativeKvStore:
    return NativeKvStore(path)


def test_native_batch_survives_reopen(tmp_path):
    path = tmp_path / "kv.db"
    s = _open(path)
    s.put(b"single", b"pre-existing")
    s.do_atomically([("put", b"k1", b"v1"),
                     ("put", b"k2", b"v2" * 100),
                     ("delete", b"single", None)], fsync=True)
    s.close()
    s = _open(path)
    assert s.get(b"k1") == b"v1"
    assert s.get(b"k2") == b"v2" * 100
    assert s.get(b"single") is None
    s.close()


def _seed_store(path) -> tuple[int, int]:
    """A store whose LAST record is a 3-op batch.  Returns (good_end,
    total): byte offsets bracketing that final record."""
    s = _open(path)
    s.put(b"keep1", b"value-one")
    s.put(b"keep2", b"value-two" * 7)
    s.sync()
    good_end = path.stat().st_size
    s.do_atomically([("put", b"batch1", b"bv1"),
                     ("put", b"batch2", b"bv2" * 31),
                     ("delete", b"keep1", None)], fsync=True)
    s.close()
    return good_end, path.stat().st_size


def _assert_batch_dropped_whole(path):
    s = _open(path)
    try:
        # the torn batch vanished entirely: its delete never applied, its
        # puts never surfaced
        assert s.get(b"keep1") == b"value-one"
        assert s.get(b"keep2") == b"value-two" * 7
        assert s.get(b"batch1") is None
        assert s.get(b"batch2") is None
        # and the log accepts new writes cleanly after recovery
        s.put(b"after", b"ok")
        assert s.get(b"after") == b"ok"
    finally:
        s.close()


def _truncation_points(good_end: int, total: int, exhaustive: bool):
    if exhaustive:
        return range(good_end, total)
    # sampled: the interesting boundaries — header-only, mid-payload, one
    # byte short of commit
    span = total - good_end
    return sorted({good_end, good_end + 1, good_end + 4, good_end + 11,
                   good_end + 12, good_end + span // 2, total - 1})


def _run_torn_tail(tmp_path, exhaustive: bool):
    base = tmp_path / "base.db"
    good_end, total = _seed_store(base)
    assert total > good_end + 12          # header + payload really landed
    for cut in _truncation_points(good_end, total, exhaustive):
        torn = tmp_path / "torn.db"
        shutil.copyfile(base, torn)
        with open(torn, "r+b") as f:
            f.truncate(cut)
        _assert_batch_dropped_whole(torn)
        torn.unlink()


def test_native_torn_tail_sampled(tmp_path):
    _run_torn_tail(tmp_path, exhaustive=False)


@pytest.mark.slow
def test_native_torn_tail_every_byte_boundary(tmp_path):
    _run_torn_tail(tmp_path, exhaustive=True)


def test_native_bit_flip_in_batch_drops_it(tmp_path):
    base = tmp_path / "base.db"
    good_end, total = _seed_store(base)
    for pos in (good_end, good_end + 2, good_end + 8,
                (good_end + total) // 2, total - 1):
        flipped = tmp_path / "flip.db"
        shutil.copyfile(base, flipped)
        raw = bytearray(flipped.read_bytes())
        raw[pos] ^= 0x01
        flipped.write_bytes(bytes(raw))
        _assert_batch_dropped_whole(flipped)
        flipped.unlink()


def _op(key: bytes, value: bytes) -> bytes:
    """One put of a kv_write_batch payload: key and value lengths, then
    both."""
    return (len(key).to_bytes(4, "little") + len(value).to_bytes(4, "little")
            + key + value)


_ONE_OP = _op(b"a", b"1")


@pytest.mark.parametrize("payload", [
    b"\xff\xff\xff\x7f" + b"junk",                     # absurd op count
    (2).to_bytes(4, "little") + _ONE_OP,                # fewer ops than said
    (1).to_bytes(4, "little") + (100).to_bytes(4, "little")
    + (1).to_bytes(4, "little") + b"ab",                # key past the end
    (1).to_bytes(4, "little") + _ONE_OP + b"x",         # trailing bytes
], ids=["absurd_count", "count_over_ops", "key_past_end", "trailing_bytes"])
def test_native_invalid_batch_payload_rejected(tmp_path, payload):
    """kv_write_batch validates the payload BEFORE touching the log: a
    malformed frame returns an error and leaves the store unchanged."""
    s = _open(tmp_path / "kv.db")
    s.put(b"k", b"v")
    lib = s._lib
    rc = lib.kv_write_batch(s._h, payload, len(payload), 0)
    assert rc != 0
    assert s.get(b"k") == b"v"
    assert s.get(b"a") is None
    s.do_atomically([("put", b"k2", b"v2")])     # store still writable
    assert s.get(b"k2") == b"v2"
    s.close()
