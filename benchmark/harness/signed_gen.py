"""Signed block traffic: real keys for the whole registry and full Altair
blocks whose every signature is real, on the benchmark's own reference
(``reference/bls.py``, ``reference/altair.py``).

The validator keys come from a constant, never from the seed, as the
gossip pool's do: consecutive secret keys, whose pubkeys ``keygen.cpp``
computes one generator addition apart.  The seed draws what
``chain_gen`` draws (balances, roots, participation, committees and
bits).  An aggregate is signed as
``(sum of its signers' secret keys) * H(m)``: one G2 multiplication per
set.  The blocks are ``chain_gen.full_block``'s, signed before the
reference processes them (the randao reveal moves the state, and every
signature is in the body root).
"""
from __future__ import annotations

import ctypes as C
import hashlib
import os
import subprocess
from pathlib import Path

import numpy as np

from harness import chain_gen
from reference import altair, bls, ssz

DOMAIN_RANDAO = b"\x02\x00\x00\x00"
DOMAIN_SYNC_COMMITTEE = b"\x07\x00\x00\x00"


def validator_keys(count: int, threads: int, cache: Path
                   ) -> tuple[list[int], np.ndarray]:
    """Secret keys ``sk0 + i`` (``sk0`` from a constant) and their
    (count, 48) compressed pubkeys, computed by ``keygen.cpp`` on the
    reference's curve code: one addition of the generator per key."""
    sk0 = int.from_bytes(hashlib.sha256(b"benchmark validator keys").digest(),
                         "big") % (bls.R - count - 1) + 1
    out = np.empty((count, 48), np.uint8)
    _keygen(cache).bench_consecutive_pks(
        sk0.to_bytes(32, "big"), count, out.ctypes.data, threads)
    return [sk0 + i for i in range(count)], out


_KEYGEN = Path(__file__).with_name("keygen.cpp")


def _keygen(cache: Path):
    """The key generator's library, built into ``cache`` on first use,
    keyed by its source and the reference's."""
    key = hashlib.sha256(_KEYGEN.read_bytes() + bls._SRC.read_bytes()
                         + " ".join(bls._FLAGS).encode()).hexdigest()[:16]
    path = cache / f"libbenchkeygen-{key}.so"
    if not path.exists():
        cache.mkdir(parents=True, exist_ok=True)
        tmp = path.with_name(f"{path.name}.{os.getpid()}.tmp")
        subprocess.run(["g++", *bls._FLAGS, "-o", str(tmp), str(_KEYGEN)],
                       check=True, capture_output=True)
        os.replace(tmp, path)
    lib = C.CDLL(str(path))
    lib.bench_consecutive_pks.argtypes = [C.c_char_p, C.c_size_t,
                                          C.c_void_p, C.c_size_t]
    lib.bench_consecutive_pks.restype = C.c_int
    return lib


def anchor_state(cfg: dict, pubkeys: np.ndarray, seed: int,
                 slot_in_epoch: int) -> tuple[altair.State, dict]:
    """``chain_gen.anchor_state`` with the registry's pubkeys (and so the
    sync committee's) replaced by real ones."""
    ref, block = chain_gen.anchor_state(cfg, len(pubkeys), seed,
                                        slot_in_epoch)
    size = cfg["preset"]["SYNC_COMMITTEE_SIZE"]
    ref.validators["pubkeys"] = pubkeys.copy()
    sync = (pubkeys[:size].copy(), pubkeys[0].tobytes())
    ref.current_sync_committee = ref.next_sync_committee = sync
    return ref, block


def _domain(state: altair.State, domain_type: bytes) -> bytes:
    return altair.compute_domain(domain_type, state.fork[1],
                                 state.genesis_validators_root)


def _sign(sk: int, message: bytes) -> bytes:
    return bls.sign_hashed(bls.hash_to_g2(message), sk)


def _signed_body(state: altair.State, block: dict, sks: list[int]
                 ) -> list[tuple[np.ndarray, bytes]]:
    """Sign ``block``'s randao reveal, attestations and sync aggregate for
    real, in place; returns each set's signers and message, in the
    system's order (randao, attestations, sync aggregate)."""
    p, body = state.p, block["body"]
    slot, epoch = block["slot"], state.epoch()
    sets = [(np.array([block["proposer_index"]]), altair.signing_root(
        ssz.uint64(epoch), _domain(state, DOMAIN_RANDAO)))]
    for a in body["attestations"]:
        d = a["data"]
        signers = state.committee(d["slot"], d["index"])[
            np.asarray(a["aggregation_bits"], bool)]
        sets.append((signers, altair.signing_root(
            altair.attestation_data_root(d),
            _domain(state, altair.DOMAIN_BEACON_ATTESTER))))
    bits = np.asarray(body["sync_committee_bits"], bool)
    if bits.any():
        sets.append((altair.sync_committee_indices(state)[bits],
                     altair.signing_root(state.block_root_at_slot(slot - 1),
                                         _domain(state,
                                                 DOMAIN_SYNC_COMMITTEE))))
    sigs = [_sign(sum(sks[i] for i in signers.tolist()) % bls.R, msg)
            for signers, msg in sets]
    body["randao_reveal"] = sigs[0]
    for a, sig in zip(body["attestations"], sigs[1:]):
        a["signature"] = sig
    if bits.any():
        body["sync_committee_signature"] = sigs[-1]
    return sets


def _finish(state: altair.State, block: dict, sks: list[int],
            sets: list) -> dict:
    """Process ``block`` on ``state``, fill its state root and sign it;
    the proposal's set leads ``block["sets"]``."""
    altair.process_block(state, block)
    block["state_root"] = state.root()
    msg = altair.signing_root(altair.block_root(block, state.p),
                              _domain(state, altair.DOMAIN_BEACON_PROPOSER))
    block["signature"] = _sign(sks[block["proposer_index"]], msg)
    block["sets"] = [(np.array([block["proposer_index"]]), msg)] + sets
    return block


def signed_segment(anchor: altair.State, traffic: dict, seed: int,
                   sks: list[int]) -> tuple[list[dict], list[dict]]:
    """``segment_blocks`` signed blocks from the anchor's slot, as
    ``chain_gen.segment`` makes them, and two twins of block
    ``settle_imports`` (:func:`twins`), each with its own post-state root
    and proposal signature, so only its signatures can refuse it.  Each
    block's ``sets`` lists the signers and message of each signature,
    for :func:`verify_block`."""
    state = anchor.copy()
    rng = np.random.default_rng((seed, 1))
    out, roots, invalid = [], {}, []
    for _ in range(traffic["segment_blocks"]):
        if out:
            altair.process_slots(state, out[-1]["slot"] + 1, roots)
        block = chain_gen.full_block(state, traffic, rng)
        sets = _signed_body(state, block, sks)
        if len(out) == traffic["settle_imports"]:
            invalid = [_finish(state.copy(), twin, sks, sets)
                       for twin in twins(block)]
        _finish(state, block, sks, sets)
        roots[state.slot] = block["state_root"]
        out.append(block)
    return out, invalid


def twins(block: dict) -> list[dict]:
    """Two copies of a signed ``block`` whose batch is invalid in one
    place, where every point still decodes: in the first its first
    attestation carries the second's valid signature (a set in the
    batch's first half), in the second its last set (the sync aggregate,
    else the last attestation) carries the one before's (the batch's
    second half).  A verifier that leaves out either half of a batch
    imports one of them."""
    def copy() -> dict:
        return {**block, "body": {**block["body"], "attestations": [
            dict(a) for a in block["body"]["attestations"]]}}

    first, last = copy(), copy()
    atts = first["body"]["attestations"]
    atts[0]["signature"] = atts[1]["signature"]
    body, atts = last["body"], last["body"]["attestations"]
    if np.asarray(body["sync_committee_bits"], bool).any():
        body["sync_committee_signature"] = atts[-1]["signature"]
    else:
        atts[-1]["signature"] = atts[-2]["signature"]
    return [first, last]


def block_signatures(block: dict) -> list[bytes]:
    """The signature of each of ``block["sets"]``, in its order."""
    body = block["body"]
    sigs = [block["signature"], body["randao_reveal"]] + \
        [a["signature"] for a in body["attestations"]]
    if len(block["sets"]) > len(sigs):
        sigs.append(body["sync_committee_signature"])
    return sigs


def verify_block(block: dict, pubkeys: np.ndarray, rng) -> bool:
    """The reference's verdict on every signature of ``block``: one batch
    of its sets, each set's pubkeys those of its signers in the registry
    (``pubkeys``), its signature the block's own."""
    sigs = block_signatures(block)
    n = len(sigs)
    counts = [len(signers) for signers, _ in block["sets"]]
    msgs = [msg for _, msg in block["sets"]]
    keys = pubkeys[np.concatenate([s for s, _ in block["sets"]])]
    rands = (rng.integers(0, 2**63, n, dtype=np.uint64) | 1).tolist()
    return bls._lib.bls_verify_signature_sets(
        n, b"".join(sigs), keys.tobytes(), (C.c_uint32 * n)(*counts),
        b"".join(msgs), (C.c_uint32 * n)(*[len(m) for m in msgs]),
        bls.DST, len(bls.DST), (C.c_uint64 * n)(*rands)) == 1


def block_keys(cfg: dict, traffic: dict) -> list[int]:
    """Keys of each signature set of a full block, in the system's order,
    from the traffic's shares and the preset as ``chain_gen.full_block``
    draws them: the proposal and the randao reveal (one key each), the
    attestations (committees of ``validators / (slots x committees per
    slot)`` keys) and the sync aggregate where a bit is set."""
    p = cfg["preset"]
    spe = p["SLOTS_PER_EPOCH"]
    per_slot = max(1, min(p["MAX_COMMITTEES_PER_SLOT"], cfg["validators"]
                          // spe // p["TARGET_COMMITTEE_SIZE"]))
    size = cfg["validators"] // (spe * per_slot)
    committees = round(per_slot * traffic["attesting_committees"])
    keys = [1, 1] + [max(1, round(size * traffic["attesting_bits"]))] * \
        committees + [round(p["SYNC_COMMITTEE_SIZE"] * traffic["sync_bits"])]
    return [n for n in keys if n]


def aggregated_bytes(cfg: dict, traffic: dict) -> int:
    """Bytes a full block's device pubkey sums must move: each key of the
    block's sets (:func:`block_keys`) read from the device table (affine
    x and y, 32 int32 limbs each) and each set's sum written (Jacobian
    x, y, z)."""
    keys = block_keys(cfg, traffic)
    limb_bytes = 32 * 4
    return sum(keys) * 2 * limb_bytes + len(keys) * 3 * limb_bytes
