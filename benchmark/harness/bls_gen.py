"""Gossip-attestation traffic: batches of single-key signature sets over
the AttestationData of whole slots, signed by a fixed pool of keys.

Copied in spirit from ``chip_smoke.py``'s attestation signing roots and
threaded signer, on the benchmark's own SSZ and BLS reference, so a
change to the system cannot move the yardstick.
"""
from __future__ import annotations

import hashlib
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from reference import altair, bls


def pool_keys(count: int, threads: int) -> tuple[list[int], list[bytes]]:
    """The key pool: secret keys from a constant, never from the seed, so
    every seed signs with the same keys."""
    sks = [int.from_bytes(hashlib.sha256(
        b"benchmark key pool" + i.to_bytes(4, "little")).digest(),
        "big") % (bls.R - 1) + 1 for i in range(count)]
    with ThreadPoolExecutor(threads) as pool:
        return sks, list(pool.map(bls.sk_to_pk, sks))


def signing_roots(cfg: dict, traffic: dict, rng: np.random.Generator,
                  batch: int) -> list[bytes]:
    """Signing roots of the AttestationData of batch ``batch``: every
    committee of two successive slots, one head vote per slot
    (pre-Electra, the committee index is signed)."""
    spe = cfg["preset"]["SLOTS_PER_EPOCH"]
    per_slot = traffic["committees_per_slot"]
    slots = traffic["messages_per_batch"] // per_slot
    slot0 = cfg["anchor_epoch"] * spe + slots * batch
    domain = altair.compute_domain(altair.DOMAIN_BEACON_ATTESTER,
                                   bytes.fromhex(cfg["fork_version"]),
                                   traffic["genesis_validators_root"])
    roots = []
    for s in range(slots):
        slot = slot0 + s
        epoch = slot // spe
        data = {"slot": slot, "beacon_block_root": rng.bytes(32),
                "source": (epoch - 1, traffic["source_root"]),
                "target": (epoch, traffic["target_roots"][epoch])}
        for index in range(per_slot):
            roots.append(altair.signing_root(
                altair.attestation_data_root({**data, "index": index}),
                domain))
    return roots


def ring(cfg: dict, traffic: dict, seed: int, sks: list[int],
         pks: list[bytes], threads: int) -> list[list[tuple]]:
    """The ring of distinct batches of (signature, pubkey, message) sets.
    Which pool key signs which set is drawn from the seed; one batch of
    the ring carries a valid signature of another set in one set, so it
    must verify False."""
    rng = np.random.default_rng(seed)
    spe = cfg["preset"]["SLOTS_PER_EPOCH"]
    epochs = {(cfg["anchor_epoch"] * spe + s) // spe
              for s in range(traffic["ring"] * traffic["messages_per_batch"]
                             // traffic["committees_per_slot"])}
    traffic = {**traffic, "genesis_validators_root": rng.bytes(32),
               "source_root": rng.bytes(32),
               "target_roots": {e: rng.bytes(32) for e in sorted(epochs)}}
    n = traffic["sets_per_batch"]
    out = []
    with ThreadPoolExecutor(threads) as pool:
        for b in range(traffic["ring"]):
            msgs = signing_roots(cfg, traffic, rng, b)
            points = list(pool.map(bls.hash_to_g2, msgs))
            keys = rng.permutation(len(sks))[:n].tolist()
            which = [i % len(msgs) for i in range(n)]
            sigs = list(pool.map(bls.sign_hashed,
                                 [points[j] for j in which],
                                 [sks[k] for k in keys]))
            out.append([(sigs[i], pks[keys[i]], msgs[which[i]])
                        for i in range(n)])
    k = int(n * traffic["corrupt_position"])
    out[traffic["corrupt_batch"]] = corrupted(out[traffic["corrupt_batch"]], k)
    return out


def corrupted(batch: list[tuple], k: int) -> list[tuple]:
    """``batch`` with set ``k`` carrying set ``k + 1``'s signature: every
    point decodes and checks, the batch equation does not."""
    bad = list(batch)
    bad[k] = (batch[k + 1][0],) + tuple(batch[k][1:])
    return bad
