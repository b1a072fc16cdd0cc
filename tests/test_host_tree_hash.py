"""Host-native (SHA-NI) incremental tree hashing: the no-accelerator
twin of the device merkle kernels (reference: ethereum_hashing +
tree_hash's update_tree_hash_cache).  Cross-checked against the XLA
path, with incremental-vs-rebuild and copy-on-write coverage."""
import numpy as np
import pytest

from lighthouse_tpu.containers import state as st
from lighthouse_tpu.containers.state import BalancesColumn, ValidatorRegistry
from lighthouse_tpu.utils import native_hash as nh

LIMIT = 2**40

pytestmark = pytest.mark.skipif(nh.get_lib() is None,
                                reason="native hasher unavailable")


@pytest.fixture(autouse=True)
def _restore_dispatch():
    old = st._USE_HOST_HASH
    yield
    st._USE_HOST_HASH = old


def _registry(n, rng):
    vr = ValidatorRegistry.__new__(ValidatorRegistry)
    vr.pubkeys = rng.integers(0, 256, size=(n, 48), dtype=np.uint8)
    vr.withdrawal_credentials = rng.integers(0, 256, size=(n, 32),
                                             dtype=np.uint8)
    vr.effective_balance = rng.integers(0, 2**40, size=n, dtype=np.uint64)
    vr.slashed = rng.integers(0, 2, size=n).astype(bool)
    vr.activation_eligibility_epoch = rng.integers(0, 2**30, size=n,
                                                   dtype=np.uint64)
    vr.activation_epoch = rng.integers(0, 2**30, size=n, dtype=np.uint64)
    vr.exit_epoch = rng.integers(0, 2**30, size=n, dtype=np.uint64)
    vr.withdrawable_epoch = rng.integers(0, 2**30, size=n, dtype=np.uint64)
    vr._dirty = True
    vr._root_cache = None
    vr._device_leaves = None
    vr._device_tree = None
    vr._dirty_rows = None
    return vr


@pytest.mark.parametrize("n", [1, 2, 7, 64, 1000])
def test_host_matches_device_registry(n):
    rng = np.random.default_rng(n)
    vr = _registry(n, rng)
    st._USE_HOST_HASH = False
    device_root = vr.hash_tree_root(LIMIT)
    st._USE_HOST_HASH = True
    vr._root_cache = None
    vr._dirty = True
    vr._dirty_rows = None
    vr._host_tree = None
    assert vr.hash_tree_root(LIMIT) == device_root


def test_incremental_update_equals_rebuild():
    rng = np.random.default_rng(3)
    vr = _registry(300, rng)
    st._USE_HOST_HASH = True
    vr.hash_tree_root(LIMIT)
    for i in (0, 150, 299):
        vr.set_field(i, "exit_epoch", 42)
    vr._root_cache = None
    incremental = vr.hash_tree_root(LIMIT)
    vr._host_tree = None
    vr._dirty_rows = None
    vr._root_cache = None
    vr._dirty = True
    assert vr.hash_tree_root(LIMIT) == incremental


def test_copy_on_write_isolates_clones():
    rng = np.random.default_rng(4)
    vr = _registry(50, rng)
    st._USE_HOST_HASH = True
    parent_root = vr.hash_tree_root(LIMIT)
    clone = vr.copy()
    clone.set_field(0, "effective_balance", 7)
    clone._root_cache = None
    clone_root = clone.hash_tree_root(LIMIT)
    assert clone_root != parent_root
    vr._root_cache = None
    vr._dirty = True
    assert vr.hash_tree_root(LIMIT) == parent_root


def test_balances_host_matches_device_and_incremental():
    rng = np.random.default_rng(5)
    vals = rng.integers(0, 2**40, size=997, dtype=np.uint64)
    st._USE_HOST_HASH = False
    device_root = BalancesColumn(vals.copy()).hash_tree_root(LIMIT)
    st._USE_HOST_HASH = True
    bc = BalancesColumn(vals.copy())
    assert bc.hash_tree_root(LIMIT) == device_root
    bc.set(13, 999)
    bc.set(996, 1)
    incremental = bc.hash_tree_root(LIMIT)
    rebuilt = BalancesColumn(bc.values.copy()).hash_tree_root(LIMIT)
    assert incremental == rebuilt


def test_host_tree_primitive_and_threaded_root():
    rng = np.random.default_rng(6)
    chunks = rng.integers(0, 256, size=(100, 32), dtype=np.uint8)
    tree = nh.HostTree(chunks, 2**16)
    from lighthouse_tpu.ssz import merkleize_chunks
    want = merkleize_chunks([bytes(c) for c in chunks], 2**16)
    assert tree.root() == want
    # update one chunk == rebuild
    chunks[42] = rng.integers(0, 256, size=32, dtype=np.uint8)
    tree.update(np.array([42]), chunks[42:43])
    assert tree.root() == nh.HostTree(chunks, 2**16).root()
    # the threaded dense root (forced threads) matches the single pass
    leaves = rng.integers(0, 256, size=(1 << 15) * 32, dtype=np.uint8)
    assert nh.merkle_root_pow2(bytes(leaves), threads=4) == \
        nh.merkle_root_pow2(bytes(leaves), threads=1)


def test_host_hashing_without_the_library(monkeypatch):
    """Where the native library cannot be built, the host-hashed columns
    hash with hashlib and give the library's roots, incremental and
    copy-on-write paths included."""
    rng = np.random.default_rng(7)
    vr = _registry(300, rng)
    vals = rng.integers(0, 2**40, size=513, dtype=np.uint64)
    st._USE_HOST_HASH = True

    def roots(vr):
        root = vr.hash_tree_root(LIMIT)
        clone = vr.copy()
        clone.set_field(7, "exit_epoch", 42)
        clone._root_cache = None
        bc = BalancesColumn(vals.copy())
        bal = bc.hash_tree_root(LIMIT)
        bc.set(512, 1)
        return root, clone.hash_tree_root(LIMIT), bal, \
            bc.hash_tree_root(LIMIT)

    want = roots(vr)
    monkeypatch.setattr(nh, "get_lib", lambda: None)
    fresh = _registry(300, np.random.default_rng(7))
    assert roots(fresh) == want
