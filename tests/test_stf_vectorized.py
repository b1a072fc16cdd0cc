"""Vectorized-vs-scalar STF equivalence (the mainnet-envelope PR's gate).

The attestation/withdrawal/pending-deposit hot paths became masked numpy
column sweeps; these tests pin them against the PRE-vectorization scalar
logic, embedded here verbatim as oracles, on randomized small states
across forks.  Equality is asserted on the FULL state hash_tree_root, so
a divergence anywhere (participation byte, balance, queue ordering,
withdrawal index) fails loudly.
"""
import random

import numpy as np
import pytest

from lighthouse_tpu.crypto import bls
from lighthouse_tpu.specs.chain_spec import ForkName, minimal_spec
from lighthouse_tpu.specs.constants import (
    FAR_FUTURE_EPOCH, PARTICIPATION_FLAG_WEIGHTS, PROPOSER_WEIGHT,
    WEIGHT_DENOMINATOR,
)
from lighthouse_tpu.state_transition import VerifySignatures
from lighthouse_tpu.state_transition.block import (
    get_attestation_participation_flag_indices, get_expected_withdrawals,
    process_attestation,
)
from lighthouse_tpu.state_transition.epoch import (
    _apply_pending_deposit, _process_pending_deposits,
)
from lighthouse_tpu.state_transition.helpers import (
    add_flag, compute_start_slot_at_epoch, get_activation_exit_churn_limit,
    get_base_reward_altair, get_beacon_proposer_index,
    get_indexed_attestation, get_total_active_balance,
    has_compounding_withdrawal_credential, has_eth1_withdrawal_credential,
    has_execution_withdrawal_credential, has_flag, increase_balance,
)
from lighthouse_tpu.state_transition.slot import process_slots
from lighthouse_tpu.testing.state_harness import StateHarness

bls.set_backend("fake")

SPECS = {
    "altair": dict(altair_fork_epoch=0),
    "capella": dict(altair_fork_epoch=0, bellatrix_fork_epoch=0,
                    capella_fork_epoch=0),
    "electra": dict(altair_fork_epoch=0, bellatrix_fork_epoch=0,
                    capella_fork_epoch=0, deneb_fork_epoch=0,
                    electra_fork_epoch=0),
}


# ---------------------------------------------------------------------------
# oracles: the scalar logic exactly as it was before vectorization
# ---------------------------------------------------------------------------

def scalar_attestation_tail(state, attestation):
    """Pre-PR altair+ tail of process_attestation: per-index participation
    flag update + proposer-reward accumulation (assumes the attestation
    already passed the shared validation, which is unchanged)."""
    data = attestation.data
    indexed = get_indexed_attestation(state, attestation)
    inclusion_delay = state.slot - data.slot
    flag_indices = get_attestation_participation_flag_indices(
        state, data, inclusion_delay)
    if data.target.epoch == state.current_epoch():
        participation = state.current_epoch_participation
    else:
        participation = state.previous_epoch_participation
    total_active = get_total_active_balance(state)
    proposer_reward_numerator = 0
    touched = []
    for index in indexed.attesting_indices:
        current = int(participation[index])
        for fi in flag_indices:
            if not has_flag(current, fi):
                current = add_flag(current, fi)
                proposer_reward_numerator += get_base_reward_altair(
                    state, index, total_active) \
                    * PARTICIPATION_FLAG_WEIGHTS[fi]
        if current != int(participation[index]):
            participation[index] = current
            touched.append(index)
    if touched:
        state.mark_participation_dirty(
            touched, participation is state.current_epoch_participation)
    denom = (WEIGHT_DENOMINATOR - PROPOSER_WEIGHT) * WEIGHT_DENOMINATOR \
        // PROPOSER_WEIGHT
    increase_balance(state, get_beacon_proposer_index(state),
                     proposer_reward_numerator // denom)


def scalar_get_expected_withdrawals(state):
    """Pre-PR get_expected_withdrawals: per-validator python sweep."""
    p = state.T.preset
    T = state.T
    epoch = state.current_epoch()
    withdrawal_index = state.next_withdrawal_index
    validator_index = state.next_withdrawal_validator_index
    withdrawals = []
    processed_partials = 0
    if state.fork_name >= ForkName.ELECTRA:
        for w in state.pending_partial_withdrawals:
            if w.withdrawable_epoch > epoch or len(withdrawals) == \
                    p.max_pending_partials_per_withdrawals_sweep:
                break
            v = state.validators.view(w.validator_index)
            has_excess = int(state.balances[w.validator_index]) > \
                p.min_activation_balance
            if (v.exit_epoch == FAR_FUTURE_EPOCH
                    and v.effective_balance >= p.min_activation_balance
                    and has_excess):
                withdrawable = min(
                    int(state.balances[w.validator_index])
                    - p.min_activation_balance, w.amount)
                withdrawals.append(T.Withdrawal(
                    index=withdrawal_index,
                    validator_index=w.validator_index,
                    address=v.withdrawal_credentials[12:],
                    amount=withdrawable))
                withdrawal_index += 1
            processed_partials += 1
    n = len(state.validators)
    bound = min(n, p.max_validators_per_withdrawals_sweep)
    for _ in range(bound):
        v = state.validators.view(validator_index)
        balance = int(state.balances[validator_index])
        if state.fork_name >= ForkName.ELECTRA:
            partially_withdrawn = sum(
                w.amount for w in withdrawals
                if w.validator_index == validator_index)
            balance -= partially_withdrawn
            max_eb = (p.max_effective_balance_electra
                      if has_compounding_withdrawal_credential(
                          v.withdrawal_credentials)
                      else p.min_activation_balance)
        else:
            max_eb = p.max_effective_balance
        fully = (has_execution_withdrawal_credential(
                     v.withdrawal_credentials)
                 if state.fork_name >= ForkName.ELECTRA
                 else has_eth1_withdrawal_credential(
                     v.withdrawal_credentials))
        if fully and v.withdrawable_epoch <= epoch and balance > 0:
            withdrawals.append(T.Withdrawal(
                index=withdrawal_index, validator_index=validator_index,
                address=v.withdrawal_credentials[12:], amount=balance))
            withdrawal_index += 1
        elif fully and v.effective_balance == max_eb and balance > max_eb:
            withdrawals.append(T.Withdrawal(
                index=withdrawal_index, validator_index=validator_index,
                address=v.withdrawal_credentials[12:],
                amount=balance - max_eb))
            withdrawal_index += 1
        if len(withdrawals) == p.max_withdrawals_per_payload:
            break
        validator_index = (validator_index + 1) % n
    return withdrawals, processed_partials


def scalar_process_pending_deposits(state):
    """Pre-PR _process_pending_deposits: per-deposit gate checks in the
    loop instead of the precomputed stop index."""
    from lighthouse_tpu.specs.constants import GENESIS_SLOT
    next_epoch = state.current_epoch() + 1
    available = state.deposit_balance_to_consume + \
        get_activation_exit_churn_limit(state)
    processed_amount = 0
    next_deposit_index = 0
    postponed = []
    churn_reached = False
    finalized_slot = compute_start_slot_at_epoch(
        state.finalized_checkpoint.epoch, state.slots_per_epoch)
    max_per_epoch = state.T.preset.max_pending_deposits_per_epoch
    for deposit in state.pending_deposits:
        if (state.deposit_requests_start_index != FAR_FUTURE_EPOCH
                and deposit.slot > GENESIS_SLOT
                and state.eth1_deposit_index <
                state.deposit_requests_start_index):
            break
        if deposit.slot > finalized_slot:
            break
        if next_deposit_index >= max_per_epoch:
            break
        v_index = state.validators.index_of(deposit.pubkey)
        if v_index is not None:
            view = state.validators.view(v_index)
            if view.withdrawable_epoch < next_epoch:
                _apply_pending_deposit(state, deposit)
                next_deposit_index += 1
                continue
            if view.exit_epoch < FAR_FUTURE_EPOCH:
                postponed.append(deposit)
                next_deposit_index += 1
                continue
        if processed_amount + deposit.amount > available:
            churn_reached = True
            break
        processed_amount += deposit.amount
        _apply_pending_deposit(state, deposit)
        next_deposit_index += 1
    state.pending_deposits = \
        state.pending_deposits[next_deposit_index:] + postponed
    if churn_reached:
        state.deposit_balance_to_consume = available - processed_amount
    else:
        state.deposit_balance_to_consume = 0


# ---------------------------------------------------------------------------
# randomized state fixtures
# ---------------------------------------------------------------------------

def _advanced_harness(fork_kwargs, n=64, slots=5):
    h = StateHarness(minimal_spec(**fork_kwargs), n)
    process_slots(h.state, slots)
    return h


def _randomize_participation(state, rng):
    n = len(state.validators)
    state.previous_epoch_participation = rng.integers(
        0, 8, size=n, dtype=np.uint64).astype(np.uint8)
    state.current_epoch_participation = rng.integers(
        0, 8, size=n, dtype=np.uint64).astype(np.uint8)


def _subsetted(att, rng, T, electra):
    """Copy of an aggregated attestation with a random non-empty subset of
    its aggregation bits."""
    bits = list(att.aggregation_bits)
    keep = [bool(rng.integers(0, 2)) for _ in bits]
    if not any(keep):
        keep[int(rng.integers(0, len(keep)))] = True
    new_bits = [b and k for b, k in zip(bits, keep)]
    if electra:
        return T.AttestationElectra(
            aggregation_bits=new_bits, data=att.data,
            signature=att.signature, committee_bits=att.committee_bits)
    return T.Attestation(aggregation_bits=new_bits, data=att.data,
                         signature=att.signature)


# ---------------------------------------------------------------------------
# property tests
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("forkname", list(SPECS))
def test_attestation_vectorized_matches_scalar(forkname):
    rng = np.random.default_rng(hash(forkname) % 2**32)
    h = _advanced_harness(SPECS[forkname])
    state = h.state
    electra = state.fork_name >= ForkName.ELECTRA
    _randomize_participation(state, rng)
    atts = h.produce_attestations(state, state.slot - 1,
                                  state.get_block_root_at_slot(
                                      state.slot - 1))
    for trial in range(8):
        att = _subsetted(atts[int(rng.integers(0, len(atts)))], rng,
                         h.T, electra)
        a = state.copy()
        process_attestation(a, att, VerifySignatures.FALSE)
        b = state.copy()
        scalar_attestation_tail(b, att)
        assert a.hash_tree_root() == b.hash_tree_root(), \
            f"{forkname} trial {trial}: vectorized != scalar"
        # mutate the base between trials so flags accumulate differently
        state = a


@pytest.mark.parametrize("forkname", ["capella", "electra"])
def test_withdrawals_vectorized_matches_scalar(forkname):
    rng = np.random.default_rng(hash("w" + forkname) % 2**32)
    h = _advanced_harness(SPECS[forkname])
    T = h.T
    for trial in range(10):
        state = h.state.copy()
        v = state.validators
        n = len(v)
        epoch = state.current_epoch()
        # random credential prefixes: BLS (no withdrawal), eth1,
        # compounding (meaningful only post-electra)
        prefixes = rng.choice([0x00, 0x01, 0x02], size=n,
                              p=[0.2, 0.5, 0.3])
        for i in range(n):
            wc = bytearray(bytes(v.withdrawal_credentials[i]))
            wc[0] = int(prefixes[i])
            v.set_field(i, "withdrawal_credentials", bytes(wc))
            if rng.random() < 0.3:      # some fully-withdrawable
                v.set_field(i, "withdrawable_epoch", int(epoch))
        p = state.T.preset
        state.balances = rng.integers(
            p.max_effective_balance - 2 * 10**9,
            p.max_effective_balance + 2 * 10**9, size=n,
            dtype=np.uint64)
        state.next_withdrawal_validator_index = int(rng.integers(0, n))
        if forkname == "electra":
            state.pending_partial_withdrawals = [
                T.PendingPartialWithdrawal(
                    validator_index=int(rng.integers(0, n)),
                    amount=int(rng.integers(1, 10**9)),
                    withdrawable_epoch=int(rng.integers(
                        max(0, epoch - 1), epoch + 2)))
                for _ in range(int(rng.integers(0, 4)))]
        got = get_expected_withdrawals(state)
        want = scalar_get_expected_withdrawals(state)
        assert got[1] == want[1], f"trial {trial}: partial count"
        assert len(got[0]) == len(want[0]), f"trial {trial}: length"
        for g, w in zip(got[0], want[0]):
            assert g == w, f"trial {trial}: {g} != {w}"


def test_pending_deposits_vectorized_matches_scalar():
    rng = np.random.default_rng(5)
    h = _advanced_harness(SPECS["electra"])
    random.seed(5)
    for trial in range(10):
        state = h.state.copy()
        T = h.T
        n = len(state.validators)
        fin_slot = compute_start_slot_at_epoch(
            state.finalized_checkpoint.epoch, state.slots_per_epoch)
        deposits = []
        for _ in range(int(rng.integers(0, 12))):
            if rng.random() < 0.7:      # known validator
                i = int(rng.integers(0, n))
                pk = bytes(state.validators.pubkeys[i])
                if rng.random() < 0.3:  # make some exited/withdrawable
                    state.validators.set_field(
                        i, "exit_epoch", state.current_epoch())
                    if rng.random() < 0.5:
                        state.validators.set_field(
                            i, "withdrawable_epoch",
                            state.current_epoch())
            else:
                pk = bytes(rng.integers(0, 256, 48, dtype=np.uint8))
            deposits.append(T.PendingDeposit(
                pubkey=pk,
                withdrawal_credentials=b"\x01" + b"\x00" * 31,
                amount=int(rng.integers(10**9, 64 * 10**9)),
                signature=b"\x80" + b"\x00" * 95,
                slot=int(rng.integers(0, fin_slot + 3))))
        state.pending_deposits = deposits
        state.deposit_balance_to_consume = int(rng.integers(0, 10**9))
        a = state.copy()
        _process_pending_deposits(a)
        b = state.copy()
        scalar_process_pending_deposits(b)
        assert a.hash_tree_root() == b.hash_tree_root(), \
            f"trial {trial}: vectorized != scalar"


def test_sparse_shuffle_matches_full_permutation():
    """compute_shuffled_index_batch(pos) == compute_shuffled_indices()[pos]
    for every size class (single element, partial block, multi-block) —
    the proposer path swaps between them on validator-set size."""
    from lighthouse_tpu.state_transition.shuffle import (
        compute_shuffled_index, compute_shuffled_index_batch,
        compute_shuffled_indices)
    rng = np.random.default_rng(7)
    for n in (1, 2, 255, 256, 257, 5000, 40_000):
        seed = bytes(rng.integers(0, 256, 32, dtype=np.uint8))
        sigma = compute_shuffled_indices(n, seed, 90)
        pos = rng.integers(0, n, size=min(n, 257))
        got = compute_shuffled_index_batch(pos, n, seed, 90)
        assert np.array_equal(sigma[pos], got), n
        # and both agree with the scalar spec transform
        for p in pos[:3]:
            assert compute_shuffled_index(int(p), n, seed, 90) == sigma[p]


def test_proposer_index_sparse_path_matches_dense(monkeypatch):
    """compute_proposer_index through the sparse (no full permutation)
    path returns the same proposer as the dense path: lower the batch
    size so a small harness state crosses the n > 8*batch threshold."""
    import lighthouse_tpu.state_transition.helpers as helpers
    h = StateHarness(minimal_spec(), 300)
    state = h.state
    # perturb effective balances so rejection sampling actually rejects
    rng = np.random.default_rng(11)
    idx = rng.integers(0, 300, size=150)
    state.validators.effective_balance[idx] = 16 * 10**9
    dense = [get_beacon_proposer_index(state, state.slot + s)
             for s in range(8)]
    monkeypatch.setattr(helpers, "_SAMPLE_BATCH", 32)
    state._proposer_cache = {}
    sparse = [get_beacon_proposer_index(state, state.slot + s)
              for s in range(8)]
    assert dense == sparse


@pytest.mark.slow
def test_epoch_processing_64k_smoke():
    """64k-validator mainnet-preset epoch: the vectorized envelope paths
    run end-to-end on a large SoA state and rotate participation."""
    from lighthouse_tpu.state_transition import per_epoch_processing
    from lighthouse_tpu.testing.mainnet_state import build_beacon_state
    slot = 100_000 * 32 + 2
    state = build_beacon_state(64 * 1024, slot)
    state.slot = (slot // 32) * 32 + 31
    before_cur = state.current_epoch_participation.copy()
    per_epoch_processing(state)
    # participation rotated: previous epoch now holds what was current
    assert np.array_equal(state.previous_epoch_participation, before_cur)
    assert not state.current_epoch_participation.any()
