"""Plain Altair block processing (consensus-specs ``specs/altair``).

The yardstick of the block-import cell: an independent model of
``process_slots`` (without epoch processing) and ``process_block`` for
blocks with attestations and a sync aggregate and no other operations,
and of the ``BeaconState`` root.  It imports no code of the system under
test; the state is plain numpy columns (:class:`State`) and a block is
a plain dict (:func:`block_root`).  Signatures are not verified: the
cell runs the block signatures on the ``fake`` backend.
"""
from __future__ import annotations

import copy
from hashlib import sha256
from math import isqrt

import numpy as np

from . import ssz

TIMELY_SOURCE, TIMELY_TARGET, TIMELY_HEAD = 0, 1, 2
FLAG_WEIGHTS = (14, 26, 14)
SYNC_REWARD_WEIGHT = 2
PROPOSER_WEIGHT = 8
WEIGHT_DENOMINATOR = 64
DOMAIN_BEACON_PROPOSER = b"\x00\x00\x00\x00"
DOMAIN_BEACON_ATTESTER = b"\x01\x00\x00\x00"
FAR_FUTURE_EPOCH = 2**64 - 1


class State:
    """An Altair ``BeaconState`` as plain values and numpy columns.

    ``p`` is the preset: the consensus-specs names and values, read from
    the configuration file.  The validator registry, the previous
    epoch's participation, the inactivity scores, the slashings and the
    sync committees are only read by block processing inside an epoch,
    so their roots are computed once.
    """

    def __init__(self, p: dict, **fields):
        self.p = p
        self.__dict__.update(fields)
        # caches of values that block processing inside an epoch never
        # changes; copies share them
        self._fixed_roots: dict[str, bytes] = {}
        self._perm: dict[bytes, np.ndarray] = {}
        self._active: dict = {}

    def copy(self) -> "State":
        out = copy.copy(self)
        for k, v in self.__dict__.items():
            if isinstance(v, np.ndarray):
                setattr(out, k, v.copy())
            elif isinstance(v, (list, dict)) and not k.startswith("_"):
                setattr(out, k, copy.deepcopy(v))
        return out

    # -- accessors ---------------------------------------------------------
    def epoch(self) -> int:
        return self.slot // self.p["SLOTS_PER_EPOCH"]

    def active(self, epoch: int) -> np.ndarray:
        out = self._active.get(epoch)
        if out is None:
            v = self.validators
            out = self._active[epoch] = np.nonzero(
                (v["activation_epoch"] <= epoch) & (epoch < v["exit_epoch"]))[0]
        return out

    def total_active_balance(self) -> int:
        key = ("total", self.epoch())
        out = self._active.get(key)
        if out is None:
            eb = self.validators["effective_balance"][self.active(key[1])]
            out = self._active[key] = max(
                self.p["EFFECTIVE_BALANCE_INCREMENT"],
                int(eb.sum(dtype=np.uint64)))
        return out

    def base_reward_per_increment(self) -> int:
        p = self.p
        return (p["EFFECTIVE_BALANCE_INCREMENT"] * p["BASE_REWARD_FACTOR"]
                // isqrt(self.total_active_balance()))

    def randao_mix(self, epoch: int) -> bytes:
        return bytes(self.randao_mixes[
            epoch % self.p["EPOCHS_PER_HISTORICAL_VECTOR"]])

    def block_root_at_slot(self, slot: int) -> bytes:
        if not slot < self.slot <= slot + self.p["SLOTS_PER_HISTORICAL_ROOT"]:
            raise ValueError(f"no block root of slot {slot} at {self.slot}")
        return bytes(self.block_roots[
            slot % self.p["SLOTS_PER_HISTORICAL_ROOT"]])

    def block_root(self, epoch: int) -> bytes:
        return self.block_root_at_slot(epoch * self.p["SLOTS_PER_EPOCH"])

    def seed(self, epoch: int, domain: bytes) -> bytes:
        p = self.p
        mix = self.randao_mix(epoch + p["EPOCHS_PER_HISTORICAL_VECTOR"]
                              - p["MIN_SEED_LOOKAHEAD"] - 1)
        return sha256(domain + epoch.to_bytes(8, "little") + mix).digest()

    def committees_per_slot(self, epoch: int) -> int:
        p = self.p
        return max(1, min(p["MAX_COMMITTEES_PER_SLOT"],
                          len(self.active(epoch)) // p["SLOTS_PER_EPOCH"]
                          // p["TARGET_COMMITTEE_SIZE"]))

    def committee(self, slot: int, index: int) -> np.ndarray:
        p = self.p
        epoch = slot // p["SLOTS_PER_EPOCH"]
        active = self.active(epoch)
        seed = self.seed(epoch, DOMAIN_BEACON_ATTESTER)
        perm = self._perm.get(seed)
        if perm is None:
            perm = self._perm[seed] = shuffled_positions(
                len(active), seed, p["SHUFFLE_ROUND_COUNT"])
        per_slot = self.committees_per_slot(epoch)
        i = (slot % p["SLOTS_PER_EPOCH"]) * per_slot + index
        count = per_slot * p["SLOTS_PER_EPOCH"]
        n = len(active)
        return active[perm[n * i // count:n * (i + 1) // count]]

    def proposer_index(self) -> int:
        p = self.p
        epoch = self.epoch()
        seed = sha256(self.seed(epoch, DOMAIN_BEACON_PROPOSER)
                      + self.slot.to_bytes(8, "little")).digest()
        active = self.active(epoch)
        total = len(active)
        i = 0
        while True:
            cand = int(active[shuffled_index(i % total, total, seed,
                                             p["SHUFFLE_ROUND_COUNT"])])
            byte = sha256(seed + (i // 32).to_bytes(8, "little")).digest()[
                i % 32]
            eb = int(self.validators["effective_balance"][cand])
            if eb * 255 >= p["MAX_EFFECTIVE_BALANCE"] * byte:
                return cand
            i += 1

    # -- root --------------------------------------------------------------
    def _fixed(self, name: str, fn) -> bytes:
        root = self._fixed_roots.get(name)
        if root is None:
            root = self._fixed_roots[name] = fn()
        return root

    def field_roots(self) -> list[bytes]:
        p = self.p
        limit = p["VALIDATOR_REGISTRY_LIMIT"]
        return [
            ssz.uint64(self.genesis_time),
            self.genesis_validators_root,
            ssz.uint64(self.slot),
            fork_root(self.fork),
            header_root(self.latest_block_header),
            ssz.roots_vector(self.block_roots),
            ssz.roots_vector(self.state_roots),
            ssz.list_of_roots(list(self.historical_roots),
                              p["HISTORICAL_ROOTS_LIMIT"]),
            eth1_data_root(self.eth1_data),
            ssz.list_of_roots([eth1_data_root(v)
                               for v in self.eth1_data_votes],
                              p["EPOCHS_PER_ETH1_VOTING_PERIOD"]
                              * p["SLOTS_PER_EPOCH"]),
            ssz.uint64(self.eth1_deposit_index),
            self._fixed("validators",
                        lambda: validators_root(self.validators, limit)),
            ssz.uint64_list(self.balances, limit),
            ssz.roots_vector(self.randao_mixes),
            self._fixed("slashings",
                        lambda: ssz.uint64_vector(self.slashings)),
            self._fixed("previous_epoch_participation",
                        lambda: ssz.uint8_list(
                            self.previous_epoch_participation, limit)),
            ssz.uint8_list(self.current_epoch_participation, limit),
            ssz.bitvector(self.justification_bits),
            checkpoint_root(self.previous_justified_checkpoint),
            checkpoint_root(self.current_justified_checkpoint),
            checkpoint_root(self.finalized_checkpoint),
            self._fixed("inactivity_scores",
                        lambda: ssz.uint64_list(self.inactivity_scores,
                                                limit)),
            self._fixed("current_sync_committee", lambda: sync_committee_root(
                *self.current_sync_committee)),
            self._fixed("next_sync_committee", lambda: sync_committee_root(
                *self.next_sync_committee)),
        ]

    def root(self) -> bytes:
        return ssz.container(*self.field_roots())


# -- shuffling -------------------------------------------------------------

def shuffled_index(index: int, count: int, seed: bytes, rounds: int) -> int:
    """``compute_shuffled_index``, one index."""
    for r in range(rounds):
        rb = bytes([r])
        pivot = int.from_bytes(sha256(seed + rb).digest()[:8],
                               "little") % count
        flip = (pivot + count - index) % count
        pos = max(index, flip)
        src = sha256(seed + rb + (pos // 256).to_bytes(4, "little")).digest()
        if (src[(pos % 256) // 8] >> (pos % 8)) & 1:
            index = flip
    return index


def shuffled_positions(count: int, seed: bytes, rounds: int) -> np.ndarray:
    """``compute_shuffled_index(i, count, seed)`` for every ``i``."""
    idx = np.arange(count, dtype=np.int64)
    buckets = (count + 255) // 256
    for r in range(rounds):
        rb = bytes([r])
        pivot = int.from_bytes(sha256(seed + rb).digest()[:8],
                               "little") % count
        flip = (pivot + count - idx) % count
        pos = np.maximum(idx, flip)
        src = b"".join([sha256(seed + rb + b.to_bytes(4, "little")).digest()
                        for b in range(buckets)])
        bits = np.unpackbits(np.frombuffer(src, np.uint8), bitorder="little")
        idx = np.where(bits[pos] == 1, flip, idx)
    return idx


# -- container roots ---------------------------------------------------------

def checkpoint_root(cp) -> bytes:
    epoch, root = cp
    return ssz.container(ssz.uint64(epoch), root)


def fork_root(fork) -> bytes:
    prev, cur, epoch = fork
    return ssz.container(ssz.pack(prev), ssz.pack(cur), ssz.uint64(epoch))


def eth1_data_root(e) -> bytes:
    deposit_root, deposit_count, block_hash = e
    return ssz.container(deposit_root, ssz.uint64(deposit_count), block_hash)


def header_root(h: dict) -> bytes:
    return ssz.container(ssz.uint64(h["slot"]), ssz.uint64(h["proposer_index"]),
                         h["parent_root"], h["state_root"], h["body_root"])


def sync_committee_root(pubkeys: np.ndarray, aggregate: bytes) -> bytes:
    pk_roots = _pubkey_roots(pubkeys)
    return ssz.container(ssz.merkleize(pk_roots, len(pubkeys)),
                         ssz.bytes_vector(aggregate))


def _pubkey_roots(pubkeys: np.ndarray) -> bytes:
    padded = np.zeros((len(pubkeys), 64), np.uint8)
    padded[:, :48] = pubkeys
    return ssz.hash_pairs(padded.tobytes())


def _u64_chunks(col: np.ndarray) -> np.ndarray:
    out = np.zeros((len(col), 32), np.uint8)
    out[:, :8] = np.ascontiguousarray(col, "<u8").view(np.uint8).reshape(
        -1, 8)
    return out


def validators_root(v: dict, limit: int) -> bytes:
    n = len(v["pubkeys"])
    leaves = np.zeros((n, 8, 32), np.uint8)
    leaves[:, 0] = np.frombuffer(_pubkey_roots(v["pubkeys"]),
                                 np.uint8).reshape(n, 32)
    leaves[:, 1] = v["withdrawal_credentials"]
    leaves[:, 2] = _u64_chunks(v["effective_balance"])
    leaves[:, 3, 0] = v["slashed"].astype(np.uint8)
    for j, name in enumerate(("activation_eligibility_epoch",
                              "activation_epoch", "exit_epoch",
                              "withdrawable_epoch")):
        leaves[:, 4 + j] = _u64_chunks(v[name])
    level = leaves.tobytes()
    for _ in range(3):
        level = ssz.hash_pairs(level)
    return ssz.mix_in_length(ssz.merkleize(level, limit), n)


def attestation_data_root(d: dict) -> bytes:
    return ssz.container(ssz.uint64(d["slot"]), ssz.uint64(d["index"]),
                         d["beacon_block_root"], checkpoint_root(d["source"]),
                         checkpoint_root(d["target"]))


def body_root(body: dict, p: dict) -> bytes:
    empty = lambda limit: ssz.list_of_roots([], limit)  # noqa: E731
    atts = [ssz.container(
        ssz.bitlist(a["aggregation_bits"], p["MAX_VALIDATORS_PER_COMMITTEE"]),
        attestation_data_root(a["data"]), ssz.bytes_vector(a["signature"]))
        for a in body["attestations"]]
    return ssz.container(
        ssz.bytes_vector(body["randao_reveal"]),
        eth1_data_root(body["eth1_data"]),
        body["graffiti"],
        empty(p["MAX_PROPOSER_SLASHINGS"]),
        empty(p["MAX_ATTESTER_SLASHINGS"]),
        ssz.list_of_roots(atts, p["MAX_ATTESTATIONS"]),
        empty(p["MAX_DEPOSITS"]),
        empty(p["MAX_VOLUNTARY_EXITS"]),
        ssz.container(ssz.bitvector(body["sync_committee_bits"]),
                      ssz.bytes_vector(body["sync_committee_signature"])))


def block_root(block: dict, p: dict) -> bytes:
    """``hash_tree_root(BeaconBlock)``: the header's root with the body's."""
    return header_root({**block, "body_root": body_root(block["body"], p)})


def signing_root(object_root: bytes, domain: bytes) -> bytes:
    return ssz.container(object_root, domain)


def compute_domain(domain_type: bytes, fork_version: bytes,
                   genesis_validators_root: bytes) -> bytes:
    fork_data = ssz.container(ssz.pack(fork_version), genesis_validators_root)
    return domain_type + fork_data[:28]


# -- transition ----------------------------------------------------------------

def process_slots(state: State, slot: int, roots: dict | None = None) -> None:
    """Advance to ``slot`` inside one epoch.  ``roots`` maps a slot to
    the state's root at that slot where the caller already has it."""
    p = state.p
    while state.slot < slot:
        if (state.slot + 1) % p["SLOTS_PER_EPOCH"] == 0:
            raise ValueError("the reference models no epoch processing")
        prev = (roots or {}).get(state.slot) or state.root()
        i = state.slot % p["SLOTS_PER_HISTORICAL_ROOT"]
        state.state_roots[i] = np.frombuffer(prev, np.uint8)
        if state.latest_block_header["state_root"] == b"\x00" * 32:
            state.latest_block_header["state_root"] = prev
        state.block_roots[i] = np.frombuffer(
            header_root(state.latest_block_header), np.uint8)
        state.slot += 1


def process_block(state: State, block: dict) -> None:
    p = state.p
    body = block["body"]
    # block header
    if block["slot"] != state.slot or \
            block["slot"] <= state.latest_block_header["slot"]:
        raise ValueError("block slot")
    proposer = state.proposer_index()
    if block["proposer_index"] != proposer:
        raise ValueError(f"proposer {block['proposer_index']} != {proposer}")
    if block["parent_root"] != header_root(state.latest_block_header):
        raise ValueError("parent root")
    if state.validators["slashed"][proposer]:
        raise ValueError("proposer slashed")
    state.latest_block_header = {
        "slot": block["slot"], "proposer_index": proposer,
        "parent_root": block["parent_root"], "state_root": b"\x00" * 32,
        "body_root": body_root(body, p)}
    # randao
    epoch = state.epoch()
    mix = np.bitwise_xor(
        np.frombuffer(state.randao_mix(epoch), np.uint8),
        np.frombuffer(sha256(body["randao_reveal"]).digest(), np.uint8))
    state.randao_mixes[epoch % p["EPOCHS_PER_HISTORICAL_VECTOR"]] = mix
    # eth1 data
    state.eth1_data_votes.append(body["eth1_data"])
    period = p["EPOCHS_PER_ETH1_VOTING_PERIOD"] * p["SLOTS_PER_EPOCH"]
    if state.eth1_data_votes.count(body["eth1_data"]) * 2 > period:
        state.eth1_data = body["eth1_data"]
    # operations: no deposits are due, and the block carries none
    if min(p["MAX_DEPOSITS"],
           state.eth1_data[1] - state.eth1_deposit_index) != 0:
        raise ValueError("deposits due")
    for att in body["attestations"]:
        process_attestation(state, att, proposer)
    process_sync_aggregate(state, body["sync_committee_bits"], proposer)


def process_attestation(state: State, att: dict, proposer: int) -> None:
    p = state.p
    data = att["data"]
    cur, prev = state.epoch(), max(state.epoch() - 1, 0)
    target_epoch = data["target"][0]
    if target_epoch not in (prev, cur) or \
            target_epoch != data["slot"] // p["SLOTS_PER_EPOCH"]:
        raise ValueError("attestation target epoch")
    if not (data["slot"] + p["MIN_ATTESTATION_INCLUSION_DELAY"] <= state.slot
            <= data["slot"] + p["SLOTS_PER_EPOCH"]):
        raise ValueError("attestation inclusion window")
    if data["index"] >= state.committees_per_slot(target_epoch):
        raise ValueError("committee index")
    committee = state.committee(data["slot"], data["index"])
    bits = np.asarray(att["aggregation_bits"], dtype=bool)
    if len(bits) != len(committee):
        raise ValueError("aggregation bits length")
    attesting = committee[bits]
    if not len(attesting):
        raise ValueError("empty attestation")
    delay = state.slot - data["slot"]
    justified = (state.current_justified_checkpoint if target_epoch == cur
                 else state.previous_justified_checkpoint)
    if tuple(data["source"]) != tuple(justified):
        raise ValueError("attestation source")
    target = data["target"][1] == state.block_root(target_epoch)
    head = target and \
        data["beacon_block_root"] == state.block_root_at_slot(data["slot"])
    flags = []
    if delay <= isqrt(p["SLOTS_PER_EPOCH"]):
        flags.append(TIMELY_SOURCE)
    if target and delay <= p["SLOTS_PER_EPOCH"]:
        flags.append(TIMELY_TARGET)
    if head and delay == p["MIN_ATTESTATION_INCLUSION_DELAY"]:
        flags.append(TIMELY_HEAD)
    part = (state.current_epoch_participation if target_epoch == cur
            else state.previous_epoch_participation)
    increments = (state.validators["effective_balance"][attesting]
                  // p["EFFECTIVE_BALANCE_INCREMENT"]).astype(object)
    per_increment = state.base_reward_per_increment()
    numerator = 0
    for flag in flags:
        new = (part[attesting] >> flag) & 1 == 0
        numerator += int(increments[new].sum()) * per_increment \
            * FLAG_WEIGHTS[flag]
        part[attesting] |= np.uint8(1 << flag)
    denominator = (WEIGHT_DENOMINATOR - PROPOSER_WEIGHT) * WEIGHT_DENOMINATOR \
        // PROPOSER_WEIGHT
    state.balances[proposer] += np.uint64(numerator // denominator)


def process_sync_aggregate(state: State, bits, proposer: int) -> None:
    p = state.p
    increments = state.total_active_balance() // p["EFFECTIVE_BALANCE_INCREMENT"]
    total_base = state.base_reward_per_increment() * increments
    max_participant = total_base * SYNC_REWARD_WEIGHT // WEIGHT_DENOMINATOR \
        // p["SLOTS_PER_EPOCH"]
    participant = max_participant // p["SYNC_COMMITTEE_SIZE"]
    proposer_reward = participant * PROPOSER_WEIGHT // (WEIGHT_DENOMINATOR
                                                         - PROPOSER_WEIGHT)
    members = sync_committee_indices(state)
    bal = state.balances
    for index, bit in zip(members.tolist(), np.asarray(bits, bool).tolist()):
        if bit:
            bal[index] += np.uint64(participant)
            bal[proposer] += np.uint64(proposer_reward)
        else:
            bal[index] -= np.uint64(min(participant, int(bal[index])))


def sync_committee_indices(state: State) -> np.ndarray:
    """The validator index of each sync committee pubkey (its first
    occurrence in the registry, as ``list.index`` finds it)."""
    cached = state._fixed_roots.get("_sync_indices")
    if cached is not None:
        return cached
    pks = np.ascontiguousarray(state.validators["pubkeys"]).view("V48").ravel()
    want = np.ascontiguousarray(state.current_sync_committee[0]).view(
        "V48").ravel()
    rows = np.nonzero(np.isin(pks, want))[0]
    first: dict[bytes, int] = {}
    for r in rows.tolist():
        first.setdefault(pks[r].tobytes(), r)
    out = np.array([first[w.tobytes()] for w in want], dtype=np.int64)
    state._fixed_roots["_sync_indices"] = out
    return out
