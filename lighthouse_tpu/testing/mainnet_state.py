"""Mainnet-scale Altair states and blocks, built column-wise.

Per-deposit genesis is O(n) Python loops, so mainnet-sized states (the
epoch-processing and block-import tests) are built straight into the SoA
columns.  Pubkeys are random bytes (not curve points): blocks built here
carry structurally valid signatures for the ``fake`` BLS backend only.
"""
from __future__ import annotations

import numpy as np

#: the structurally valid (infinity-flagged) signature every block here
#: carries; only the ``fake`` backend accepts it
FAKE_SIG = b"\x80" + b"\x00" * 95


def build_state_columns(n: int):
    """(ValidatorRegistry, balances) for n active 32 ETH validators."""
    from ..containers.state import ValidatorRegistry
    from ..specs.constants import FAR_FUTURE_EPOCH
    rng = np.random.default_rng(7)
    vr = ValidatorRegistry.__new__(ValidatorRegistry)
    vr.pubkeys = rng.integers(0, 256, size=(n, 48), dtype=np.uint8)
    vr.withdrawal_credentials = rng.integers(0, 256, size=(n, 32),
                                             dtype=np.uint8)
    vr.effective_balance = np.full(n, 32 * 10**9, dtype=np.uint64)
    vr.slashed = np.zeros(n, dtype=bool)
    vr.activation_eligibility_epoch = np.zeros(n, dtype=np.uint64)
    vr.activation_epoch = np.zeros(n, dtype=np.uint64)
    vr.exit_epoch = np.full(n, FAR_FUTURE_EPOCH, dtype=np.uint64)
    vr.withdrawable_epoch = np.full(n, FAR_FUTURE_EPOCH, dtype=np.uint64)
    vr._dirty = True
    vr._root_cache = None
    vr._device_leaves = None
    vr._device_tree = None
    vr._dirty_rows = None
    balances = rng.integers(31 * 10**9, 33 * 10**9, size=n, dtype=np.uint64)
    return vr, balances


def build_beacon_state(n: int, slot: int):
    """Full altair BeaconState with n validators on the mainnet preset.
    Participation is shaped like a live mainnet epoch: previous epoch
    fully attested, current epoch attested for the slots already
    elapsed."""
    from ..containers import get_types
    from ..containers.state import BeaconState
    from ..specs.chain_spec import ForkName, mainnet_spec
    spec = mainnet_spec()
    T = get_types(spec.preset)
    state = BeaconState(T, spec, ForkName.ALTAIR)
    rng = np.random.default_rng(7)
    vr, balances = build_state_columns(n)
    # ETH1-credential prefix so the (capella+) withdrawal sweep has real
    # matches; harmless pre-capella
    vr.withdrawal_credentials[:, 0] = 0x01
    state.validators = vr
    state.balances = balances
    state.slot = slot
    epoch = slot // T.preset.slots_per_epoch
    state.fork = T.Fork(previous_version=spec.altair_fork_version,
                        current_version=spec.altair_fork_version,
                        epoch=0)
    state.latest_block_header = T.BeaconBlockHeader(
        slot=slot - 1, proposer_index=0, parent_root=b"\x11" * 32,
        state_root=b"\x22" * 32, body_root=b"\x33" * 32)
    state.block_roots = rng.integers(
        0, 256, size=state.block_roots.shape, dtype=np.uint8)
    state.state_roots = rng.integers(
        0, 256, size=state.state_roots.shape, dtype=np.uint8)
    state.randao_mixes = rng.integers(
        0, 256, size=state.randao_mixes.shape, dtype=np.uint8)
    state.previous_epoch_participation = np.full(n, 0b0111, np.uint8)
    cur = np.zeros(n, np.uint8)
    elapsed = slot % T.preset.slots_per_epoch
    attested = rng.random(n) < elapsed / T.preset.slots_per_epoch
    cur[attested] = 0b0111
    state.current_epoch_participation = cur
    state.inactivity_scores = np.zeros(n, np.uint64)
    state.previous_justified_checkpoint = T.Checkpoint(
        epoch=epoch - 2, root=b"\x44" * 32)
    state.current_justified_checkpoint = T.Checkpoint(
        epoch=epoch - 1, root=b"\x55" * 32)
    state.finalized_checkpoint = T.Checkpoint(
        epoch=epoch - 2, root=b"\x44" * 32)
    state.justification_bits = [True, True, True, True]
    pubkeys = [bytes(vr.pubkeys[i]) for i in range(
        T.preset.sync_committee_size)]
    state.current_sync_committee = T.SyncCommittee(
        pubkeys=pubkeys, aggregate_pubkey=pubkeys[0])
    state.next_sync_committee = T.SyncCommittee(
        pubkeys=pubkeys, aggregate_pubkey=pubkeys[0])
    return state


def anchor_block(state):
    """A signed block at ``state.slot - 1`` whose header becomes the
    state's latest header, so a weak-subjectivity anchor on (state,
    block) agrees with the parent root of the next imported block."""
    from ..specs.chain_spec import ForkName
    from ..ssz import htr
    T = state.T
    slot = state.slot - 1
    body = T.BeaconBlockBody[ForkName.ALTAIR](
        randao_reveal=FAKE_SIG, eth1_data=state.eth1_data,
        graffiti=b"\x00" * 32)
    block = T.BeaconBlock[ForkName.ALTAIR](
        slot=slot, proposer_index=0, parent_root=b"\x11" * 32,
        state_root=b"\x22" * 32, body=body)
    state.latest_block_header = T.BeaconBlockHeader(
        slot=slot, proposer_index=0, parent_root=b"\x11" * 32,
        state_root=b"\x22" * 32, body_root=htr(body))
    return T.SignedBeaconBlock[ForkName.ALTAIR](message=block,
                                                signature=FAKE_SIG)


def build_import_block(state):
    """A block at state.slot with full attestation coverage of the prior
    slot and a full sync aggregate — the per-slot worst case the STF
    envelope must absorb.  Its ``state_root`` is left zero for the
    caller to fill."""
    from ..specs.chain_spec import ForkName
    from ..ssz import htr
    from ..state_transition.helpers import (
        committee_cache, get_beacon_proposer_index,
    )
    T = state.T
    slot = state.slot
    epoch = state.current_epoch()
    cache = committee_cache(state, epoch)
    att_slot = slot - 1
    target_root = state.get_block_root(epoch)
    head_root = state.get_block_root_at_slot(att_slot)
    data_tpl = dict(
        slot=att_slot, beacon_block_root=head_root,
        source=state.current_justified_checkpoint,
        target=T.Checkpoint(epoch=epoch, root=target_root))
    attestations = []
    for index in range(cache.committees_per_slot):
        committee = cache.committee(att_slot, index)
        attestations.append(T.Attestation(
            aggregation_bits=[True] * len(committee),
            data=T.AttestationData(index=index, **data_tpl),
            signature=FAKE_SIG))
    sync_aggregate = T.SyncAggregate(
        sync_committee_bits=[True] * T.preset.sync_committee_size,
        sync_committee_signature=FAKE_SIG)
    proposer = get_beacon_proposer_index(state)
    body = T.BeaconBlockBody[ForkName.ALTAIR](
        randao_reveal=FAKE_SIG, eth1_data=state.eth1_data,
        graffiti=b"\x00" * 32, attestations=attestations)
    body.sync_aggregate = sync_aggregate
    block = T.BeaconBlock[ForkName.ALTAIR](
        slot=slot, proposer_index=proposer,
        parent_root=htr(state.latest_block_header),
        state_root=b"\x00" * 32, body=body)
    return T.SignedBeaconBlock[ForkName.ALTAIR](message=block,
                                                signature=FAKE_SIG)
