"""Faults planted in the system under test, underneath a run of the
harness: each makes a sound run's answers wrong in one way, and the
harness's comparison with the reference has to read ``correct`` false.

Each takes a ``pytest.MonkeyPatch`` and patches the system's module.
A cell's control is one of them: the system with one guarantee its
configuration states broken (``sync_rewards_skipped`` for the block
stream, ``pairing_skipped`` for the gossip batches), run on the chip by
``seeds.py --fault <name>``.
"""
from __future__ import annotations

import itertools


def sync_rewards_skipped(mp):
    """Block import without the sync aggregate's rewards: post-state
    roots depart from the spec's."""
    from lighthouse_tpu.state_transition import block
    mp.setattr(block, "process_sync_aggregate", lambda *a, **k: None)


def state_unchanged(mp):
    """The import's state transition returns the state unchanged."""
    from lighthouse_tpu.chain import block_verification
    mp.setattr(block_verification, "per_block_processing",
               lambda *a, **k: None)


def half_attestations(mp):
    """Half of a block's attestations (odd committees) left out."""
    from lighthouse_tpu.state_transition import block
    orig = block.process_attestation

    def process_attestation(state, att, *a, **k):
        if att.data.index % 2 == 0:
            orig(state, att, *a, **k)

    mp.setattr(block, "process_attestation", process_attestation)


def root_altered(mp):
    """The state root altered where it is produced."""
    from lighthouse_tpu.containers.state import BeaconState
    orig = BeaconState.hash_tree_root
    mp.setattr(BeaconState, "hash_tree_root",
               lambda self: bytes([orig(self)[0] ^ 1]) + orig(self)[1:])


def pairing_skipped(mp):
    """Batch verification without the pairing check: every set's point
    decodes and lies in the subgroup, and the batch equation is never
    tested."""
    from lighthouse_tpu.crypto.bls import tpu_backend
    orig = tpu_backend.device_checks
    mp.setattr(tpu_backend, "device_checks",
               lambda prep, lanes: itertools.islice(orig(prep, lanes), 2))


def half_batch(mp):
    """Half of each batch left out: the verdict covers its first half."""
    from lighthouse_tpu.crypto import bls
    orig = bls.verify_signature_sets
    mp.setattr(bls, "verify_signature_sets",
               lambda sets: orig(sets[:len(sets) // 2]))


def second_half_batch(mp):
    """Half of each batch left out: the verdict covers its second half."""
    from lighthouse_tpu.crypto import bls
    orig = bls.verify_signature_sets
    mp.setattr(bls, "verify_signature_sets",
               lambda sets: orig(sets[len(sets) // 2:]))


def verdict_flipped(mp):
    """The verdict altered where it is produced."""
    from lighthouse_tpu.crypto import bls
    orig = bls.verify_signature_sets
    mp.setattr(bls, "verify_signature_sets", lambda sets: not orig(sets))

