"""Block-stream traffic: a mainnet-shaped Altair anchor state and a
segment of full blocks, made from the seed.

The state is built column-wise, as ``lighthouse_tpu/testing/mainnet_state``
builds it (copied here so a change there cannot move the yardstick).
Every block field the system checks (proposer, committees, parent and
state roots) comes from the plain reference in ``reference/altair.py``;
the system under test imports a block only if it agrees.
"""
from __future__ import annotations

import numpy as np

from reference import altair

#: the structurally valid (infinity-flagged) signature every block here
#: carries; only the ``fake`` BLS backend accepts it
FAKE_SIG = b"\x80" + b"\x00" * 95
EMPTY_SYNC_SIG = b"\xc0" + b"\x00" * 95


def anchor_state(cfg: dict, n: int, seed: int, slot_in_epoch: int
                 ) -> tuple[altair.State, dict]:
    """The anchor (reference state, anchor block) for ``n`` active 32 ETH
    validators at the ``slot_in_epoch``-th slot of an epoch.  The previous
    epoch is fully attested and the current one for the slots already
    elapsed, as on a live network; the seed draws keys, balances, roots
    and which validators attested."""
    p = cfg["preset"]
    spe = p["SLOTS_PER_EPOCH"]
    epoch = cfg["anchor_epoch"]
    slot = epoch * spe + slot_in_epoch
    rng = np.random.default_rng(seed)
    wc = rng.integers(0, 256, size=(n, 32), dtype=np.uint8)
    wc[:, 0] = 0x01
    validators = {
        "pubkeys": rng.integers(0, 256, size=(n, 48), dtype=np.uint8),
        "withdrawal_credentials": wc,
        "effective_balance": np.full(n, p["MAX_EFFECTIVE_BALANCE"], np.uint64),
        "slashed": np.zeros(n, bool),
        "activation_eligibility_epoch": np.zeros(n, np.uint64),
        "activation_epoch": np.zeros(n, np.uint64),
        "exit_epoch": np.full(n, altair.FAR_FUTURE_EPOCH, np.uint64),
        "withdrawable_epoch": np.full(n, altair.FAR_FUTURE_EPOCH, np.uint64),
    }
    cur = np.zeros(n, np.uint8)
    cur[rng.choice(n, size=n * slot_in_epoch // spe, replace=False)] = 0b111
    version = bytes.fromhex(cfg["fork_version"])
    body = {"randao_reveal": FAKE_SIG, "eth1_data": (b"\x00" * 32, 0,
                                                     b"\x00" * 32),
            "graffiti": b"\x00" * 32, "attestations": [],
            "sync_committee_bits": np.zeros(p["SYNC_COMMITTEE_SIZE"], bool),
            "sync_committee_signature": EMPTY_SYNC_SIG}
    block = {"slot": slot - 1, "proposer_index": 0,
             "parent_root": b"\x11" * 32, "state_root": b"\x22" * 32,
             "body": body}
    sync = (validators["pubkeys"][:p["SYNC_COMMITTEE_SIZE"]].copy(),
            validators["pubkeys"][0].tobytes())
    state = altair.State(
        p,
        genesis_time=0, genesis_validators_root=b"\x00" * 32, slot=slot,
        fork=(version, version, 0),
        latest_block_header={**_header_fields(block),
                             "body_root": altair.body_root(body, p)},
        block_roots=rng.integers(0, 256, size=(
            p["SLOTS_PER_HISTORICAL_ROOT"], 32), dtype=np.uint8),
        state_roots=rng.integers(0, 256, size=(
            p["SLOTS_PER_HISTORICAL_ROOT"], 32), dtype=np.uint8),
        historical_roots=[],
        eth1_data=body["eth1_data"], eth1_data_votes=[],
        eth1_deposit_index=0,
        validators=validators,
        balances=rng.integers(p["MAX_EFFECTIVE_BALANCE"] - 10**9,
                              p["MAX_EFFECTIVE_BALANCE"] + 10**9, size=n,
                              dtype=np.uint64),
        randao_mixes=rng.integers(0, 256, size=(
            p["EPOCHS_PER_HISTORICAL_VECTOR"], 32), dtype=np.uint8),
        slashings=np.zeros(p["EPOCHS_PER_SLASHINGS_VECTOR"], np.uint64),
        previous_epoch_participation=np.full(n, 0b111, np.uint8),
        current_epoch_participation=cur,
        justification_bits=[True] * 4,
        previous_justified_checkpoint=(epoch - 2, b"\x44" * 32),
        current_justified_checkpoint=(epoch - 1, b"\x55" * 32),
        finalized_checkpoint=(epoch - 2, b"\x44" * 32),
        inactivity_scores=np.zeros(n, np.uint64),
        current_sync_committee=sync, next_sync_committee=sync,
    )
    return state, block


def _header_fields(block: dict) -> dict:
    return {k: block[k] for k in ("slot", "proposer_index", "parent_root",
                                  "state_root")}


def full_block(state: altair.State, traffic: dict,
               rng: np.random.Generator) -> dict:
    """A block at ``state.slot`` carrying attestations of the prior slot
    and a sync aggregate, as much of them as the traffic file's shares
    say (``attesting_committees``, ``attesting_bits``, ``sync_bits``; 1.0
    each is the per-slot worst case of an import); which committees and
    bits are drawn from ``rng``.  Its ``state_root`` is filled by
    :func:`segment`."""
    p = state.p
    slot, epoch = state.slot, state.epoch()
    att_slot = slot - 1
    data = {"slot": att_slot,
            "beacon_block_root": state.block_root_at_slot(att_slot),
            "source": state.current_justified_checkpoint,
            "target": (epoch, state.block_root(epoch))}
    count = state.committees_per_slot(epoch)
    picked = sorted(rng.permutation(count)[
        :round(count * traffic["attesting_committees"])].tolist())
    atts = [{"aggregation_bits": _bits(len(state.committee(att_slot, i)),
                                       traffic["attesting_bits"], rng, 1),
             "data": {**data, "index": i}, "signature": FAKE_SIG}
            for i in picked]
    sync = _bits(p["SYNC_COMMITTEE_SIZE"], traffic["sync_bits"], rng, 0)
    body = {"randao_reveal": FAKE_SIG, "eth1_data": state.eth1_data,
            "graffiti": b"\x00" * 32, "attestations": atts,
            "sync_committee_bits": sync,
            "sync_committee_signature": FAKE_SIG if sync.any()
            else EMPTY_SYNC_SIG}
    return {"slot": slot, "proposer_index": state.proposer_index(),
            "parent_root": altair.header_root(state.latest_block_header),
            "state_root": b"\x00" * 32, "body": body}


def _bits(size: int, share: float, rng: np.random.Generator,
          least: int) -> np.ndarray:
    """``size`` bits, ``share`` of them (at least ``least``) set at
    places drawn from ``rng``."""
    bits = np.zeros(size, bool)
    bits[rng.permutation(size)[:max(least, round(size * share))]] = True
    return bits


def segment(anchor: altair.State, traffic: dict, seed: int) -> list[dict]:
    """``segment_blocks`` blocks at consecutive slots from the anchor's,
    each carrying the reference's post-state root.  The anchor is not
    changed."""
    state = anchor.copy()
    rng = np.random.default_rng((seed, 1))
    out, roots = [], {}
    for _ in range(traffic["segment_blocks"]):
        if out:
            altair.process_slots(state, out[-1]["slot"] + 1, roots)
        block = full_block(state, traffic, rng)
        altair.process_block(state, block)
        block["state_root"] = roots[state.slot] = state.root()
        out.append(block)
    return out


# -- the system's objects, from the plain values -------------------------------

def program_state(ref: altair.State, spec):
    """The system's ``BeaconState`` holding the reference state's values."""
    from lighthouse_tpu.containers import get_types
    from lighthouse_tpu.containers.state import BeaconState, ValidatorRegistry
    from lighthouse_tpu.specs.chain_spec import ForkName
    T = get_types(spec.preset)
    st = BeaconState(T, spec, ForkName.ALTAIR)
    vr = ValidatorRegistry(0)
    for name, col in ref.validators.items():
        setattr(vr, name, col.copy())
    st.validators = vr
    st.balances = ref.balances.copy()
    st.genesis_time = ref.genesis_time
    st.genesis_validators_root = ref.genesis_validators_root
    st.slot = ref.slot
    prev, cur, epoch = ref.fork
    st.fork = T.Fork(previous_version=prev, current_version=cur, epoch=epoch)
    st.latest_block_header = T.BeaconBlockHeader(**ref.latest_block_header)
    st.block_roots = ref.block_roots.copy()
    st.state_roots = ref.state_roots.copy()
    st.randao_mixes = ref.randao_mixes.copy()
    st.slashings = ref.slashings.copy()
    st.eth1_data = _eth1(T, ref.eth1_data)
    st.previous_epoch_participation = ref.previous_epoch_participation.copy()
    st.current_epoch_participation = ref.current_epoch_participation.copy()
    st.inactivity_scores = ref.inactivity_scores.copy()
    st.justification_bits = list(ref.justification_bits)
    for name in ("previous_justified_checkpoint",
                 "current_justified_checkpoint", "finalized_checkpoint"):
        e, r = getattr(ref, name)
        setattr(st, name, T.Checkpoint(epoch=e, root=r))
    for name in ("current_sync_committee", "next_sync_committee"):
        pks, agg = getattr(ref, name)
        setattr(st, name, T.SyncCommittee(
            pubkeys=[bytes(r) for r in pks], aggregate_pubkey=agg))
    return st


def _eth1(T, e):
    deposit_root, deposit_count, block_hash = e
    return T.Eth1Data(deposit_root=deposit_root, deposit_count=deposit_count,
                      block_hash=block_hash)


def program_block(block: dict, spec):
    """The system's ``SignedBeaconBlock`` for a plain block."""
    from lighthouse_tpu.containers import get_types
    from lighthouse_tpu.specs.chain_spec import ForkName
    T = get_types(spec.preset)
    b = block["body"]

    def checkpoint(cp):
        return T.Checkpoint(epoch=cp[0], root=cp[1])

    atts = [T.Attestation(
        aggregation_bits=[bool(x) for x in a["aggregation_bits"]],
        data=T.AttestationData(
            slot=a["data"]["slot"], index=a["data"]["index"],
            beacon_block_root=a["data"]["beacon_block_root"],
            source=checkpoint(a["data"]["source"]),
            target=checkpoint(a["data"]["target"])),
        signature=a["signature"]) for a in b["attestations"]]
    body = T.BeaconBlockBody[ForkName.ALTAIR](
        randao_reveal=b["randao_reveal"], eth1_data=_eth1(T, b["eth1_data"]),
        graffiti=b["graffiti"], attestations=atts)
    body.sync_aggregate = T.SyncAggregate(
        sync_committee_bits=[bool(x) for x in b["sync_committee_bits"]],
        sync_committee_signature=b["sync_committee_signature"])
    msg = T.BeaconBlock[ForkName.ALTAIR](
        slot=block["slot"], proposer_index=block["proposer_index"],
        parent_root=block["parent_root"], state_root=block["state_root"],
        body=body)
    return T.SignedBeaconBlock[ForkName.ALTAIR](message=msg,
                                                signature=FAKE_SIG)
