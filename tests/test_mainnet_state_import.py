"""A column-built, anchored mainnet state importing blocks through the
node's own path (``BeaconProcessor`` -> ``chain.process_block``).  Each
block claims the state root of a separate host-hashed copy, so an import
accepted with the device trees shows that their roots equal an
independent host root."""
import pytest

from lighthouse_tpu.beacon_processor import BeaconProcessor, Work, WorkType
from lighthouse_tpu.chain.builder import BeaconChainBuilder
from lighthouse_tpu.chain.execution import MockExecutionLayer
from lighthouse_tpu.containers import state as st
from lighthouse_tpu.crypto import bls
from lighthouse_tpu.ops.merkle_tree import DeviceTree
from lighthouse_tpu.specs.chain_spec import mainnet_spec
from lighthouse_tpu.state_transition import (
    VerifySignatures, per_block_processing, process_slots,
)
from lighthouse_tpu.testing.mainnet_state import (
    anchor_block, build_beacon_state, build_import_block,
)
from lighthouse_tpu.utils.slot_clock import ManualSlotClock

N_VALIDATORS = 4096
BLOCKS = 3


@pytest.mark.parametrize("hashing", ["device", "host"])
def test_anchored_mainnet_state_imports_blocks_to_host_roots(monkeypatch,
                                                             hashing):
    # the state's pubkeys are random bytes, not keys: the fake backend
    monkeypatch.setattr(bls, "_current", None)
    bls.set_backend("fake")
    monkeypatch.setattr(st, "_USE_HOST_HASH", hashing == "host")
    spec = mainnet_spec()
    slot0 = 100_000 * spec.preset.slots_per_epoch + 2
    state = build_beacon_state(N_VALIDATORS, slot0)
    signed_anchor = anchor_block(state)
    host = state.copy()
    chain = (BeaconChainBuilder(spec)
             .weak_subjectivity_anchor(state, signed_anchor)
             .slot_clock(ManualSlotClock(0, spec.seconds_per_slot,
                                         current_slot=slot0 + BLOCKS - 1))
             .execution_layer(MockExecutionLayer())
             .build())
    proc = BeaconProcessor(num_workers=2)
    proc.start()
    roots = []
    try:
        for i in range(BLOCKS):
            # the claimed root: the host copy, hashed on the host
            st._USE_HOST_HASH = True
            process_slots(host, slot0 + i)
            signed = build_import_block(host)
            per_block_processing(host, signed, VerifySignatures.FALSE)
            signed.message.state_root = host.hash_tree_root()
            st._USE_HOST_HASH = hashing == "host"
            out = {}

            def run(signed=signed, out=out):
                try:
                    out["root"] = chain.process_block(signed)
                except BaseException as exc:
                    out["error"] = exc
                    raise

            assert proc.submit(Work(kind=WorkType.GOSSIP_BLOCK, run=run))
            assert proc.wait_idle(timeout=600)
            assert "error" not in out, out.get("error")
            assert chain.fork_choice.contains_block(out["root"])
            post = chain._state_for(out["root"])
            assert post.slot == slot0 + i
            assert post.hash_tree_root() == signed.message.state_root
            roots.append(signed.message.state_root)
    finally:
        proc.stop()
    assert len(set(roots)) == BLOCKS
    tree = chain._state_for(out["root"]).validators._device_tree
    assert isinstance(tree, DeviceTree) == (hashing == "device")
