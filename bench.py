"""Headline benchmark (run on the TPU chip).

Prints ONE JSON line on stdout, always — even on backend failure.

The parent process never imports jax: it launches each measurement in a
child subprocess with a bounded timeout, one child after another, so
each in turn holds the chip.  The device children (tree hash, BLS, MXU
modes) fail where JAX finds no TPU; the host-only children (STF, replay,
serve) run on the CPU by design.  On a child failure the parent emits an
error record.

Metrics (BASELINE.md north stars):
- default: BeaconState tree_hash_root at 1M validators (<200 ms target;
  vs_baseline = 200/ms).
- LHTPU_BENCH=bls: batched RLC signature verification throughput
  (>=4x blst target; vs_baseline = sigs_per_sec / (4 * blst_sigs_per_sec)
  would be the strict reading; we report sigs_per_sec / blst baseline so
  >=4.0 meets the target).
- LHTPU_BENCH=serve / --serve: Beacon-API serving-tier req/s on the VC
  hot path (duties + attestation_data) at 1M validators vs the uncached
  unit cost, plus the api_request span p95 (>=10x target; ISSUE 12).
- LHTPU_BENCH=replay / --replay: graftflow epochs_replayed_per_sec,
  sequential vs the epoch-pipelined replay engine at 1M validators with
  per-stage occupancy, bit-exact head asserted (>=2x target; ISSUE 14).
"""
import json
import os
import subprocess
import sys
import time

_REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _REPO)

N_VALIDATORS = 1_000_000
TARGET_MS = 200.0

N_SIGS = 10000     # BASELINE.md config 3: the 10k gossip batch
# blst on the reference's recommended 4-core node: ~0.38 ms/pairing
# single-thread => ~8.7k sigs/s across 4 cores on a 10k batch (BASELINE.md);
# the >=4x target means >= ~35k sigs/s on one chip.  When the native C++
# pairing backend is available we measure the host baseline instead of
# trusting this constant (see _measured_host_baseline).
BLST_BASELINE_SIGS_PER_SEC = 8700.0


# --------------------------------------------------------------------------
# child: actual measurement (imports jax)
# --------------------------------------------------------------------------

def bench_tree_hash():
    """Cached-tree-hash semantics (update_tree_hash_cache): per-rep, mutate
    1024 validators + 1024 balances, then recompute the state-root-dominant
    columns.  Both columns are device-resident with dirty-row scatter."""
    from lighthouse_tpu.testing.mainnet_state import build_state_columns
    import numpy as np
    from lighthouse_tpu.containers.state import BalancesColumn
    vr, balances = build_state_columns(N_VALIDATORS)
    bc = BalancesColumn(balances)
    vrl = 2**40
    rng = np.random.default_rng(11)

    def run():
        rows = rng.integers(0, N_VALIDATORS, size=1024)
        for i in rows:
            vr.set_field(int(i), "effective_balance", 31 * 10**9)
        brows = rng.integers(0, N_VALIDATORS, size=1024)
        bc.set_many(brows, np.full(1024, 32 * 10**9, dtype=np.uint64))
        v_root = vr.hash_tree_root(vrl)
        b_root = bc.hash_tree_root(vrl)
        return v_root, b_root

    from lighthouse_tpu import obs
    with obs.span("bench_stage", stage="tree_hash_warmup"):
        run()  # warm up compiles + build the device-resident leaves
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        with obs.span("bench_stage", stage="tree_hash_rep"):
            run()
        times.append((time.perf_counter() - t0) * 1000)
    return min(times)


def bench_bls():
    """The real gossip-batch workload end-to-end through the backend API:
    n compressed signature sets -> device decompression, psi subgroup
    checks, SSWU hash-to-G2, RLC scaling, n+1 Miller loops, one final
    exponentiation.  Sets are signed by the native C++ backend (fast,
    byte-compatible), so the timed path is exactly
    attestation_verification's verify_signature_sets.

    Batch size: BASELINE.md config 3 is a 10k-signature gossip batch."""
    n = int(os.environ.get("LHTPU_BENCH_NSIGS", N_SIGS))
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.crypto.bls import SignatureSet
    try:
        from lighthouse_tpu.crypto.bls.cpp_backend import CppBackend
        signer = CppBackend()
    except Exception:
        signer = bls.set_backend("python")
    sets = []
    for i in range(n):
        msg = i.to_bytes(32, "little")
        sk = 1000 + i
        sets.append(SignatureSet(signer.sign(sk, msg),
                                 [signer.sk_to_pk(sk)], msg))
    from lighthouse_tpu import obs
    tpu = bls.set_backend("tpu")
    with obs.span("bench_stage", stage="bls_warmup"):
        assert tpu.verify_signature_sets(sets), "bench batch must verify"
    times = []
    for _ in range(2):
        t0 = time.perf_counter()
        with obs.span("bench_stage", stage="bls_verify"):
            assert tpu.verify_signature_sets(sets)
        times.append(time.perf_counter() - t0)
    secs = min(times)
    return n / secs, n


def bench_mont_mul_modes():
    """Measured mont_mul throughput per LHTPU_BIGINT_MXU lowering.

    PERF_MODEL.md §3.2's MXU re-limb was 'modeled, not measured' (VERDICT
    r4 weak #2) — this measures it: a chained fori_loop of K dependent
    Montgomery products over a [B, 32] batch, best-of-3, for mode 0 (int32
    VPU columns), 1 (all-int8 digit space) and 2 (hybrid: const REDC
    matmuls only).  One small program per mode, so it fits the child
    budget even cold."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from lighthouse_tpu.ops import bigint as bi

    B = 1 << 16
    K = 32
    rng = np.random.default_rng(3)
    x = rng.integers(0, 1 << bi.LIMB_BITS, size=(B, bi.NLIMBS),
                     dtype=np.int32)
    x[:, -1] = rng.integers(0, 0x1A0, size=B)    # keep values < 2p

    def chain(v):
        return lax.fori_loop(0, K, lambda i, acc: bi.mont_mul(acc, v), v)

    from lighthouse_tpu import obs
    out = {}
    try:
        for mode in (0, 1, 2):
            bi.set_mxu_mode(mode)
            f = jax.jit(chain)
            with obs.span("bench_stage", stage=f"mont_mul_mode{mode}_warm"):
                f(x).block_until_ready()         # compile + warm
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                with obs.span("bench_stage",
                              stage=f"mont_mul_mode{mode}"):
                    f(x).block_until_ready()
                best = min(best, time.perf_counter() - t0)
            out[mode] = B * K / best
    finally:
        bi.set_mxu_mode(0)
    return out


class _ServeBackend:
    """Chainless duties/attestation_data provider over one big built
    state — the computations the serving tier fronts, with their honest
    uncached cost (the proposer cache only ever holds the most recent
    slot, so an epoch of proposer duties is slots_per_epoch full
    shuffle+sample computations)."""

    def __init__(self, state):
        self.state = state
        self.T = state.T

    def get_proposer_duties(self, epoch):
        from lighthouse_tpu.state_transition.helpers import (
            get_beacon_proposer_index,
        )
        st = self.state
        spe = self.T.preset.slots_per_epoch
        start = epoch * spe
        return [(s, get_beacon_proposer_index(st, s))
                for s in range(start, start + spe)]

    def attestation_data(self, slot, committee_index):
        from lighthouse_tpu.state_transition.helpers import (
            get_committee_count_per_slot,
        )
        st = self.state
        T = self.T
        spe = T.preset.slots_per_epoch
        epoch = slot // spe
        cps = get_committee_count_per_slot(st, epoch)
        if committee_index >= cps:
            raise ValueError("committee index out of range")
        return T.AttestationData(
            slot=slot, index=committee_index,
            beacon_block_root=st.get_block_root_at_slot(slot - 1),
            source=st.current_justified_checkpoint,
            target=T.Checkpoint(epoch=epoch,
                                root=st.get_block_root(epoch)))


def bench_serving():
    """Serving-tier req/s on the VC hot path (duties + attestation_data)
    against the 1M-validator mainnet state (ISSUE 12).  Host-side: the
    tier is locks + dicts + memcpy, no accelerator involved.  Measures
    the uncached unit cost (direct compute + encode, what every request
    paid before the tier) against the same request mix through the
    ServingTier, and reports the api_request span p95."""
    from lighthouse_tpu.testing.mainnet_state import build_beacon_state
    import threading

    from lighthouse_tpu import obs
    from lighthouse_tpu.api.serving import ServingTier
    from lighthouse_tpu.ssz import serialize
    n = int(os.environ.get("LHTPU_BENCH_SERVE_N",
                           os.environ.get("LHTPU_BENCH_STF_N",
                                          N_VALIDATORS)))
    slot = 100_000 * 32 + 2
    state = build_beacon_state(n, slot)
    backend = _ServeBackend(state)
    spe = state.T.preset.slots_per_epoch
    epoch = slot // spe

    def produce_duties():
        return json.dumps({"data": [
            {"slot": str(s), "validator_index": str(v), "pubkey": "0x00"}
            for s, v in backend.get_proposer_duties(epoch)]}).encode()

    def produce_att():
        data = backend.attestation_data(slot, 0)
        t = type(data).ssz_type
        return json.dumps(
            {"data": {"ssz": serialize(t, data).hex()}}).encode()

    # uncached baseline: one epoch of proposer duties is spe full
    # proposer computations (per-slot seeds defeat any shuffle reuse),
    # so a single (duties, attestation_data) pair is the honest unit
    k_att = int(os.environ.get("LHTPU_BENCH_SERVE_UNCACHED_ATT", 16))
    t0 = time.perf_counter()
    produce_duties()
    duties_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(k_att):
        produce_att()
    att_s = (time.perf_counter() - t0) / k_att
    uncached_rps = 2.0 / (duties_s + att_s)

    # served: the same 50/50 mix through the tier from a small fleet of
    # threads; the first miss per endpoint pays the computation above,
    # everything after is a coalesced wait or a pre-encoded cache hit
    tier = ServingTier(backend)
    m = int(os.environ.get("LHTPU_BENCH_SERVE_REQUESTS", 2000))
    workers = 8
    per = m // workers

    def fleet():
        for i in range(per):
            if i % 2:
                tier.attestation_data(slot, 0)
            else:
                tier.proposer_duties(epoch)

    t0 = time.perf_counter()
    threads = [threading.Thread(target=fleet) for _ in range(workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    served_s = time.perf_counter() - t0
    served = per * workers
    served_rps = served / served_s

    spans = obs.summarize_spans(obs.snapshot()).get("api_request", {})
    snap = tier.snapshot()
    return {
        "n_validators": n,
        "requests": served,
        "uncached_rps": round(uncached_rps, 3),
        "uncached_duties_ms": round(duties_s * 1000, 1),
        "uncached_attestation_data_ms": round(att_s * 1000, 3),
        "served_rps": round(served_rps, 1),
        "speedup": round(served_rps / uncached_rps, 1),
        "cache_hit_ratio": round(snap["cache_hit_ratio"] or 0.0, 4),
        "coalesced": snap["coalesced"],
        "flights": snap["flights"],
        "shed_total": snap["shed_total"],
        "p50_ms": spans.get("p50_ms"),
        "p95_ms": spans.get("p95_ms"),
    }


def bench_state_transition():
    """Mainnet-envelope STF: per_epoch_processing and full-block
    per_block_processing at N_VALIDATORS on the mainnet preset.  Pure
    host/numpy path (no jax imports beyond the platform label)."""
    from lighthouse_tpu.testing.mainnet_state import (
        build_beacon_state, build_import_block,
    )
    from lighthouse_tpu import obs
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.state_transition import (
        VerifySignatures, per_block_processing, per_epoch_processing,
    )
    n = int(os.environ.get("LHTPU_BENCH_STF_N", N_VALIDATORS))
    # mid-epoch slot far from a sync-committee-period boundary, so the
    # epoch number is realistic but the timed epoch never pays the
    # (cached-in-practice) next-sync-committee sampling
    slot = 100_000 * 32 + 2
    bls.set_backend("fake")
    state = build_beacon_state(n, slot)
    state.validators.index_of(bytes(state.validators.pubkeys[0]))
    sb = build_import_block(state)

    stages = {}
    t0 = time.perf_counter()
    pre = state.copy()
    stages["state_copy_ms"] = round((time.perf_counter() - t0) * 1000, 2)

    # untimed warmup: faults the copied columns in, and primes the
    # shared shuffling cache + pubkey index for every timed rep
    t0 = time.perf_counter()
    per_block_processing(pre.copy(), sb, VerifySignatures.FALSE)
    stages["block_warmup_ms"] = round((time.perf_counter() - t0) * 1000, 2)

    block_ms = {}
    for label, vs in (("signatures_off", VerifySignatures.FALSE),
                      ("signatures_on", VerifySignatures.TRUE)):
        best = float("inf")
        for _ in range(2):
            st = pre.copy()
            t0 = time.perf_counter()
            with obs.span("stf_block", slot=int(sb.message.slot)):
                per_block_processing(st, sb, vs)
            best = min(best, (time.perf_counter() - t0) * 1000)
        block_ms[label] = round(best, 2)
    stages["committees_per_slot"] = \
        len(sb.message.body.attestations)

    ep = pre.copy()
    ep.slot = (slot // 32) * 32 + 31        # epoch boundary semantics
    t0 = time.perf_counter()
    with obs.span("stf_epoch", epoch=int(ep.current_epoch()),
                  n_validators=n):
        per_epoch_processing(ep)
    epoch_ms = (time.perf_counter() - t0) * 1000

    with obs.span("bench_stage", stage="fork_fanout"):
        stages["fork_fanout"] = _bench_fork_fanout(state)
    return {
        "epoch_ms": round(epoch_ms, 1),
        "block_import_ms": block_ms,
        "n_validators": n,
        "sig_backend": "fake",
        "stages": stages,
    }


def _bench_fork_fanout(pre, n_forks=32, mutations_per_fork=4):
    """CoW fork fan-out: ``n_forks`` live copies of one primed state,
    each with a few point mutations (balances scatter + one registry
    set_field), then a per-copy incremental hash_tree_root against the
    SHARED merkle trees.  Reports total extra RSS vs the size of one
    full state (acceptance: <= 15%) and the CoW chunk counters
    (acceptance: chunks_shared >> chunks_materialized)."""
    import gc
    import numpy as np
    from lighthouse_tpu.containers import cow

    def rss_bytes():
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * os.sysconf("SC_PAGESIZE")

    pre.hash_tree_root()        # prime + share the incremental trees
    v = pre.validators
    full_state_mb = (sum(getattr(v, c).nbytes for c in v.COLUMNS)
                     + pre.balances.nbytes + pre.inactivity_scores.nbytes
                     + pre.previous_epoch_participation.nbytes
                     + pre.current_epoch_participation.nbytes) / 1e6
    rng = np.random.default_rng(11)
    n = len(pre.balances)

    def make_fork(i):
        f = pre.copy()
        rows = np.unique(rng.integers(0, n, size=mutations_per_fork))
        f.balances[rows] = f.balances[rows] + np.uint64(1 + i)
        f.validators.set_field(int(rows[0]), "exit_epoch", 500_000 + i)
        return f

    # warmup fork: pays one-time costs (compiled hash programs, lazily
    # built buffers) outside the RSS window
    w = make_fork(999)
    w.hash_tree_root()
    del w
    gc.collect()
    stats0 = dict(cow.STATS)
    rss0 = rss_bytes()
    t0 = time.perf_counter()
    forks = [make_fork(i) for i in range(n_forks)]
    fork_ms = (time.perf_counter() - t0) * 1000
    htr_ms, roots = [], set()
    for f in forks:
        t0 = time.perf_counter()
        roots.add(f.hash_tree_root())
        htr_ms.append((time.perf_counter() - t0) * 1000)
    gc.collect()
    rss_delta_mb = max(0, rss_bytes() - rss0) / 1e6
    delta = {k: cow.STATS[k] - stats0[k] for k in cow.STATS}
    htr_ms.sort()
    return {
        "n_forks": n_forks,
        "mutations_per_fork": mutations_per_fork,
        "distinct_roots": len(roots),
        "fork_plus_mutate_ms_total": round(fork_ms, 2),
        "htr_ms_median": round(htr_ms[len(htr_ms) // 2], 2),
        "htr_ms_max": round(htr_ms[-1], 2),
        "rss_delta_mb": round(rss_delta_mb, 2),
        "full_state_mb": round(full_state_mb, 1),
        "rss_delta_pct_of_state":
            round(100 * rss_delta_mb / full_state_mb, 2),
        "chunks_shared": delta["chunks_shared"],
        "chunks_materialized": delta["chunks_materialized"],
    }


def bench_import_critpath():
    """The REAL import pipeline at N validators: anchor a production
    BeaconChain on the built state (checkpoint-sync builder path), drive
    one worst-case block through the beacon processor's queue into
    ``chain.process_block``, and extract the graftpath critical path —
    queue-wait vs service time per stage (batch_signature,
    state_transition, state_root, db_write).  This is the decomposition
    PERF_MODEL §12 records and ROADMAP item 4 (pipelined import) plans
    against; ``bench_state_transition`` times the bare STF, this times
    what a node actually does between gossip arrival and new head."""
    from lighthouse_tpu.testing.mainnet_state import (
        anchor_block, build_beacon_state, build_import_block,
    )
    from lighthouse_tpu import obs
    from lighthouse_tpu.beacon_processor import (
        BeaconProcessor, Work, WorkType,
    )
    from lighthouse_tpu.chain.builder import BeaconChainBuilder
    from lighthouse_tpu.chain.execution import MockExecutionLayer
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.obs import critpath
    from lighthouse_tpu.specs.chain_spec import mainnet_spec
    from lighthouse_tpu.state_transition import (
        VerifySignatures, per_block_processing,
    )
    from lighthouse_tpu.utils.slot_clock import ManualSlotClock

    n = int(os.environ.get("LHTPU_BENCH_STF_N", N_VALIDATORS))
    slot = 100_000 * 32 + 2
    bls.set_backend("fake")
    spec = mainnet_spec()
    state = build_beacon_state(n, slot)
    signed_anchor = anchor_block(state)
    sb = build_import_block(state)
    # untimed pre-pass fills the block's real post-state root (the
    # import verifies it) and primes caches like the STF bench does
    post = state.copy()
    per_block_processing(post, sb, VerifySignatures.FALSE)
    sb.message.state_root = post.hash_tree_root()
    del post
    chain = (BeaconChainBuilder(spec)
             .weak_subjectivity_anchor(state, signed_anchor)
             .slot_clock(ManualSlotClock(0, spec.seconds_per_slot,
                                         current_slot=slot))
             .execution_layer(MockExecutionLayer())
             .build())
    proc = BeaconProcessor(num_workers=2)
    proc.start()
    try:
        proc.submit(Work(kind=WorkType.GOSSIP_BLOCK,
                         run=lambda: chain.process_block(sb)))
        if not proc.wait_idle(timeout=600):
            raise RuntimeError("import did not finish inside 600s")
    finally:
        proc.stop()
    comp = critpath.worst_component(obs.snapshot(),
                                    kinds=("block_import",))
    if comp is None:
        raise RuntimeError("no block_import trace recorded")
    rep = critpath.component_report(comp)
    qwait = sum(r["queue_wait_ms"] for r in rep["stages"].values())
    return {
        "n_validators": n,
        "sig_backend": "fake",
        "total_ms": rep["total_ms"],
        "terminal": (rep["terminal"] or {}).get("kind"),
        "queue_wait_ms": round(qwait, 3),
        "import_stages": {k: rep["stages"][k]
                          for k in critpath.IMPORT_STAGES
                          if k in rep["stages"]},
        "stages": rep["stages"],
    }


def bench_replay():
    """graftflow (ISSUE 14): epochs replayed per second, sequential
    ``process_chain_segment`` vs the epoch-pipelined ``ReplayEngine``,
    on twin anchored chains at N validators.  The segment is `epochs`
    epochs of light blocks — range-sync and backfill replay *history*,
    which sits far below the gossip worst case ``bench_import_critpath``
    times — built untimed with real claimed state roots (that pass also
    primes the shuffle/pubkey caches both timed runs then share).  The
    pipelined head block root and head state root must be bit-identical
    to the sequential oracle's before any number is reported."""
    from lighthouse_tpu.testing.mainnet_state import (
        FAKE_SIG, anchor_block, build_beacon_state,
    )
    from lighthouse_tpu.chain.builder import BeaconChainBuilder
    from lighthouse_tpu.chain.execution import MockExecutionLayer
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.specs.chain_spec import ForkName, mainnet_spec
    from lighthouse_tpu.ssz import htr
    from lighthouse_tpu.state_transition import (
        VerifySignatures, per_block_processing, process_slots,
    )
    from lighthouse_tpu.state_transition.helpers import (
        get_beacon_proposer_index,
    )
    from lighthouse_tpu.utils.slot_clock import ManualSlotClock

    n = int(os.environ.get("LHTPU_BENCH_STF_N", N_VALIDATORS))
    epochs = int(os.environ.get("LHTPU_BENCH_REPLAY_EPOCHS", 2))
    bls.set_backend("fake")
    spec = mainnet_spec()
    spe = spec.preset.slots_per_epoch
    slot0 = 100_000 * spe            # epoch-aligned anchor
    state = build_beacon_state(n, slot0)
    T = state.T
    sig = FAKE_SIG
    signed_anchor = anchor_block(state)
    anchor_state = state.copy()

    # untimed segment build: one sequential pass computing the claimed
    # state roots the replayed blocks carry
    blocks = []
    work = state
    parent_root = htr(work.latest_block_header)
    sync_agg = T.SyncAggregate(
        sync_committee_bits=[True] * T.preset.sync_committee_size,
        sync_committee_signature=sig)
    for i in range(epochs * spe):
        s = slot0 + 1 + i
        process_slots(work, s)
        body = T.BeaconBlockBody[ForkName.ALTAIR](
            randao_reveal=sig, eth1_data=work.eth1_data,
            graffiti=b"\x00" * 32)
        body.sync_aggregate = sync_agg
        block = T.BeaconBlock[ForkName.ALTAIR](
            slot=s, proposer_index=get_beacon_proposer_index(work),
            parent_root=parent_root, state_root=b"\x00" * 32, body=body)
        sb = T.SignedBeaconBlock[ForkName.ALTAIR](
            message=block, signature=sig)
        per_block_processing(work, sb, VerifySignatures.FALSE)
        block.state_root = work.hash_tree_root()
        parent_root = htr(block)
        blocks.append(sb)
    del work, state

    def _mk_chain():
        return (BeaconChainBuilder(spec)
                .weak_subjectivity_anchor(anchor_state.copy(),
                                          signed_anchor)
                .slot_clock(ManualSlotClock(
                    0, spec.seconds_per_slot,
                    current_slot=slot0 + epochs * spe + 1))
                .execution_layer(MockExecutionLayer())
                .build())

    seq_chain = _mk_chain()
    t0 = time.perf_counter()
    n_seq = seq_chain.process_chain_segment(list(blocks))
    t_seq = time.perf_counter() - t0

    pipe_chain = _mk_chain()
    engine = pipe_chain.replay_engine()
    t0 = time.perf_counter()
    n_pipe = engine.replay_segment(list(blocks))
    t_pipe = time.perf_counter() - t0

    if n_seq != n_pipe:
        raise RuntimeError(f"import counts diverge: {n_seq} vs {n_pipe}")
    hs, hp = seq_chain.head(), pipe_chain.head()
    if hs.head_block_root != hp.head_block_root or \
            hs.head_state.hash_tree_root() != \
            hp.head_state.hash_tree_root():
        raise RuntimeError(
            "pipelined replay diverged from the sequential oracle")
    snap = engine.snapshot()
    last = snap["last_segment"] or {}
    return {
        "n_validators": n,
        "epochs": epochs,
        "blocks": len(blocks),
        "sig_backend": "fake",
        "sequential_s": round(t_seq, 3),
        "pipelined_s": round(t_pipe, 3),
        "epochs_replayed_per_sec": {
            "sequential": round(epochs / t_seq, 3),
            "pipelined": round(epochs / t_pipe, 3),
        },
        "speedup": round(t_seq / t_pipe, 3),
        "stage_occupancy": last.get("occupancy"),
        "queue_high_water": snap["queue_high_water"],
        "sigs_deduped": snap["sigs_deduped"],
        "head_match": True,
    }


def _measured_host_baseline():
    """Measured single-pairing-check cost on the native C++ backend, scaled
    to the reference's 4-core node.  Returns (sigs_per_sec, source) where
    source records whether the number was measured or estimated."""
    try:
        from lighthouse_tpu.crypto.bls import cpp_backend
        per_sec = cpp_backend.measure_pairing_throughput(n=64) * 4.0
    except Exception:
        return BLST_BASELINE_SIGS_PER_SEC, "estimate"
    # blst on the reference node is never SLOWER than our C++ backend —
    # take the max so a weak native build can't flatter vs_baseline
    if per_sec < BLST_BASELINE_SIGS_PER_SEC:
        return BLST_BASELINE_SIGS_PER_SEC, "estimate-floor"
    return per_sec, "measured-cpp-4core"


def _write_trace_artifacts(mode: str, out_dir: str) -> str | None:
    """bench --trace: dump the child's graftscope spans as Chrome-trace
    JSON plus a per-stage summary next to the BENCH_*.json records, so a
    perf PR attaches stage-level evidence, not just end-to-end numbers.
    Returns the trace path (or None when no spans were recorded)."""
    from lighthouse_tpu import obs
    spans = obs.snapshot()
    if not spans:
        return None
    trace_path = os.path.join(out_dir, f"BENCH_TRACE_{mode}.json")
    with open(trace_path, "w") as f:
        json.dump(obs.chrome_trace(spans), f)
    summary = {
        "stages": obs.summarize_spans(spans),
        "jax": obs.jax_counters(),
    }
    with open(os.path.join(out_dir,
                           f"BENCH_TRACE_{mode}_summary.json"), "w") as f:
        json.dump(summary, f, indent=2)
    return trace_path


def _device_block(mode):
    """graftgauge (ISSUE 17): every BENCH record carries a mandatory
    device block — platform + chip count, HBM stats or an explicit
    "unavailable", persistent compile-cache counters, and per-kernel
    roofline records for the mode's headline kernel.  Never raises."""
    from lighthouse_tpu.obs import device, jax_accounting, roofline
    try:
        block = device.ledger_snapshot()
    except Exception as exc:
        return {"error": repr(exc)}
    counters = jax_accounting.snapshot()
    block["compile_cache"] = {"hits": counters.get("cache_hits", 0),
                              "misses": counters.get("cache_misses", 0)}
    if mode == "tree_hash":
        # measure the tree-hash inner kernel explicitly: hash_pairs runs
        # inside shard_map on the sharded path, so it can't carry its
        # own timing wrapper (trace safety) — the bench measures it from
        # outside on a representative batch instead
        try:
            import jax.numpy as jnp
            import numpy as np
            from lighthouse_tpu.ops.sha256 import hash_pairs
            arr = jnp.asarray(np.arange(2048 * 8,
                                        dtype=np.uint32).reshape(2048, 8))
            roofline.measure("tree_hash", hash_pairs, arr)
        except Exception:
            pass
    # fold the mesh programs' roofline records under the mode's headline
    # kernel name; where nothing roofline-wrapped ran (the single-device
    # crypto backend path on the CPU fallback, whose per-shape compiles
    # cost ~10 min each) the record says so explicitly — a cost fetch
    # would blow the child budget, silence would be a lie
    kname, prefix = {"bls": ("bls_batch_verify", "bls."),
                     "tree_hash": ("tree_hash", "merkle.")
                     }.get(mode, (None, "\x00"))
    roof = {}
    for prog, recs in sorted(roofline.snapshot().items()):
        if kname and (prog == kname or prog.startswith(prefix)):
            roof.setdefault(kname, []).extend(
                dict(r, program=prog) for r in recs)
        else:
            roof[prog] = recs
    if kname and kname not in roof:
        roof[kname] = [{"cost": "unavailable",
                        "note": "no roofline-wrapped program ran in "
                                "this mode"}]
    block["roofline"] = roof
    return block


def child_main():
    from lighthouse_tpu.utils import compile_cache
    compile_cache.configure()
    import jax
    platform = jax.default_backend()
    mode = os.environ.get("LHTPU_BENCH", "tree_hash")
    if mode in ("tree_hash", "bls", "mxu") and platform != "tpu":
        raise SystemExit(f"bench {mode}: no TPU (JAX platform "
                         f"{platform!r}); device modes run on the chip")
    if mode == "bls":
        sigs_per_sec, n_sigs = bench_bls()
        baseline, baseline_source = _measured_host_baseline()
        rec = {
            "metric": "bls_batch_verify_throughput",
            "value": round(sigs_per_sec, 1),
            "unit": "sigs/s/chip",
            "vs_baseline": round(sigs_per_sec / baseline, 3),
            "platform": platform,
            "baseline_sigs_per_sec": round(baseline, 1),
            "baseline_source": baseline_source,
            "n_sigs": n_sigs,
        }
    elif mode == "stf":
        stf = bench_state_transition()
        off = stf["block_import_ms"]["signatures_off"]
        rec = {
            "metric": "stf_mainnet_envelope_1m_validators",
            "value": stf["epoch_ms"],
            "unit": "ms",
            # north star: one epoch inside the 12 s slot budget
            "vs_baseline": round(12_000.0 / max(stf["epoch_ms"], 1e-9), 3),
            "platform": platform,
            "epoch_ms_1m": stf["epoch_ms"],
            "block_import_ms_1m": stf["block_import_ms"],
            "block_import_ms_1m_headline": off,
            "n_validators": stf["n_validators"],
            "sig_backend": stf["sig_backend"],
            "stf_stages": stf["stages"],
            "state_copy_ms": stf["stages"]["state_copy_ms"],
            "state_copy_gate_ms": 60.0,
            "state_copy_gate_pass":
                stf["stages"]["state_copy_ms"] <= 60.0,
        }
        # graftpath: the real import pipeline's critical path at the
        # same validator count (PERF_MODEL §12); never let a failure
        # here cost the STF record itself
        if os.environ.get("LHTPU_BENCH_CRITPATH", "1") != "0":
            try:
                rec["import_critpath_1m"] = bench_import_critpath()
            except Exception as exc:
                rec["import_critpath_1m"] = {"error": repr(exc)}
    elif mode == "serve":
        sv = bench_serving()
        rec = {
            "metric": "api_serving_tier",
            "value": sv["speedup"],
            "unit": "speedup_vs_uncached",
            # acceptance gate: >=10x the uncached req/s on the VC hot
            # path, so >=1.0 here meets it
            "vs_baseline": round(sv["speedup"] / 10.0, 3),
            "platform": platform,
            "serve": sv,
        }
    elif mode == "replay":
        rp = bench_replay()
        rec = {
            "metric": "replay_pipeline",
            "value": rp["epochs_replayed_per_sec"]["pipelined"],
            "unit": "epochs/s",
            # acceptance gate: >=2x the sequential import loop at the
            # same validator count, so >=1.0 here meets it
            "vs_baseline": round(rp["speedup"] / 2.0, 3),
            "platform": platform,
            "replay": rp,
            "replay_epochs_per_sec_pipelined":
                rp["epochs_replayed_per_sec"]["pipelined"],
            "replay_speedup": rp["speedup"],
        }
    elif mode == "mxu":
        mm = bench_mont_mul_modes()
        rec = {
            "metric": "mont_mul_mxu_modes",
            "value": round(max(mm[1], mm[2]) / mm[0], 3),
            "unit": "speedup_vs_mode0",
            "vs_baseline": 0.0,
            "platform": platform,
            "mont_mul_per_sec": {f"mode{k}": round(v)
                                 for k, v in mm.items()},
        }
    else:
        ms = bench_tree_hash()
        rec = {
            "metric": "beacon_state_tree_hash_1m_validators",
            "value": round(ms, 2),
            "unit": "ms",
            "vs_baseline": round(TARGET_MS / ms, 3),
            "platform": platform,
        }
    rec["device"] = _device_block(mode)
    if os.environ.get("LHTPU_BENCH_TRACE"):
        trace_path = _write_trace_artifacts(mode, _REPO)
        if trace_path is not None:
            rec["trace_file"] = os.path.basename(trace_path)
    print(json.dumps(rec), flush=True)


# --------------------------------------------------------------------------
# regression gate: bench.py --against <record|auto> [--record <new>]
# --------------------------------------------------------------------------

#: fractional slowdown on any gated metric that fails the gate
REGRESSION_LIMIT = 0.25

#: (record key — dotted for nesting, direction, platform-label key).
#: Accelerator-measured metrics are only comparable when both records
#: ran them on the same platform; the gate skips them (with a note)
#: rather than fail a CPU-fallback run against a TPU record.
GATED_METRICS = [
    ("value", "lower", "platform"),                    # tree-hash ms
    ("bls_sigs_per_sec", "higher", "bls_platform"),
    ("epoch_ms_1m", "lower", None),                    # STF is host-side
    ("block_import_ms_1m.signatures_off", "lower", None),
    ("state_copy_ms", "lower", None),
    ("mxu_mode_speedup", "higher", "mxu_platform"),
    ("replay_epochs_per_sec_pipelined", "higher", None),  # host-side
]


def _get_path(rec, dotted):
    cur = rec
    for part in dotted.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return float(cur) if isinstance(cur, (int, float)) else None


def _device_platform(rec: dict) -> str | None:
    dev = rec.get("device")
    if not isinstance(dev, dict):
        return None
    plat = dev.get("platform")
    return plat if isinstance(plat, str) and plat != "unavailable" \
        else None


def compare_records(old: dict, new: dict,
                    limit: float = REGRESSION_LIMIT) -> dict:
    """Diff two bench records over GATED_METRICS.  Returns a report dict;
    report["ok"] is False when any gated metric regressed past `limit`.

    Device-sensitive metrics (those with a platform-label key) are
    guarded twice: the per-metric platform labels as before, and — since
    graftgauge — the records' mandatory ``device`` blocks.  Disagreeing
    device blocks refuse the comparison outright (``platform_mismatch``);
    records predating the device block (r01–r06) still compare via their
    labels but the report carries a ``platform_notes`` entry flagging
    every accelerator-flagship metric those records measured on the XLA
    CPU fallback."""
    compared, skipped, notes = [], [], []
    dev_old, dev_new = _device_platform(old), _device_platform(new)
    for key, direction, plat_key in GATED_METRICS:
        ov, nv = _get_path(old, key), _get_path(new, key)
        if ov is None or nv is None or ov <= 0 or nv <= 0:
            skipped.append({"metric": key,
                            "why": "missing or non-positive in one record"})
            continue
        if plat_key is not None:
            if dev_old and dev_new and dev_old != dev_new:
                skipped.append({"metric": key,
                                "why": f"platform_mismatch (device "
                                       f"blocks disagree: {dev_old} vs "
                                       f"{dev_new})"})
                continue
            for which, rec_, dev in (("old", old, dev_old),
                                     ("new", new, dev_new)):
                if dev is None and rec_.get(plat_key) == "cpu":
                    notes.append({
                        "metric": key, "record": which,
                        "note": "device-sensitive metric measured on "
                                "the XLA CPU fallback by a record "
                                "predating the graftgauge device block "
                                f"({plat_key}=cpu); not evidence for "
                                "accelerator claims"})
        if plat_key is not None and old.get(plat_key) != new.get(plat_key):
            skipped.append({"metric": key,
                            "why": f"platform mismatch "
                                   f"({old.get(plat_key)} vs "
                                   f"{new.get(plat_key)})"})
            continue
        # normalize both directions to "fraction slower than before"
        change = (nv / ov - 1.0) if direction == "lower" \
            else (ov / nv - 1.0)
        if change > limit:
            status = "regression"
        elif change < 0:
            status = "improvement"
        else:
            status = "within_limit"
        compared.append({"metric": key, "direction": direction,
                         "old": ov, "new": nv,
                         "change_pct": round(100 * change, 1),
                         "status": status})
    regressions = [c["metric"] for c in compared
                   if c["status"] == "regression"]
    report = {"mode": "against", "limit_pct": round(limit * 100, 1),
              "compared": compared, "skipped": skipped,
              "regressions": regressions, "ok": not regressions}
    if notes:
        report["platform_notes"] = notes
    return report


def _unwrap_record(doc: dict) -> dict:
    """Driver-written BENCH_r*.json wraps the bench JSON line under
    "parsed" (alongside rc/tail); accept either shape."""
    if isinstance(doc, dict) and "metric" not in doc \
            and isinstance(doc.get("parsed"), dict):
        return doc["parsed"]
    return doc


def _latest_record_path():
    import glob
    import re
    best, best_n = None, -1
    for p in glob.glob(os.path.join(_REPO, "BENCH_r*.json")):
        m = re.search(r"BENCH_r(\d+)\.json$", p)
        if m and int(m.group(1)) > best_n:
            best, best_n = p, int(m.group(1))
    return best


def _against_main(argv):
    """`--against auto|<old.json>` compares a fresh record (or
    `--record <new.json>`) to a previous one and exits 1 on any >25%
    regression of a gated metric.  Prints the report as JSON."""
    def _arg(flag):
        i = argv.index(flag)
        if i + 1 >= len(argv):
            print(json.dumps({"mode": "against", "ok": False,
                              "error": f"{flag} needs a value"}))
            sys.exit(2)
        return argv[i + 1]

    old_path = _arg("--against")
    if old_path == "auto":
        old_path = _latest_record_path()
        if old_path is None:
            print(json.dumps({"mode": "against", "ok": False,
                              "error": "no BENCH_r*.json record found"}))
            sys.exit(2)
    try:
        with open(old_path) as f:
            old = _unwrap_record(json.load(f))
    except (OSError, ValueError) as exc:
        print(json.dumps({"mode": "against", "ok": False,
                          "error": f"cannot load {old_path}: {exc}"}))
        sys.exit(2)
    if "--record" in argv:
        new_source = _arg("--record")
        try:
            with open(new_source) as f:
                new = _unwrap_record(json.load(f))
        except (OSError, ValueError) as exc:
            print(json.dumps({"mode": "against", "ok": False,
                              "error": f"cannot load {new_source}: {exc}"}))
            sys.exit(2)
    else:
        # fresh measurement: re-run ourselves without --against so the
        # whole fallback orchestration above is reused verbatim
        new_source = "fresh run"
        proc = subprocess.run([sys.executable, os.path.abspath(__file__)],
                              cwd=_REPO, env=dict(os.environ),
                              capture_output=True, text=True)
        new = _parse_record(proc.stdout)
        if new is None:
            print(json.dumps({"mode": "against", "ok": False,
                              "error": "fresh bench run produced no "
                                       "record: " + proc.stderr[-500:]}))
            sys.exit(2)
    limit = float(os.environ.get("LHTPU_BENCH_REGRESSION_LIMIT",
                                 REGRESSION_LIMIT))
    report = compare_records(old, new, limit)
    report["old_file"] = old_path
    report["new_source"] = new_source
    if report["regressions"]:
        # point at the stage-level attribution workflow: capture both
        # versions with --trace, then diff the captures (graftpath)
        report["differential_profile"] = (
            "attribute the regression per stage: run both versions "
            "with `python bench.py --trace`, keep the old "
            "BENCH_TRACE_<mode>.json, then "
            "`python tools/obs/diff.py OLD_TRACE.json "
            "BENCH_TRACE_<mode>.json` shows which stage's critical-"
            "path self-time moved")
    print(json.dumps(report, indent=1))
    sys.exit(0 if report["ok"] else 1)


# --------------------------------------------------------------------------
# parent: orchestration (never imports jax)
# --------------------------------------------------------------------------

def _child_env(host_only):
    """Children run one after another, so each in turn holds the chip;
    host-only children (STF, replay, serve) never touch it."""
    env = dict(os.environ)
    env["LHTPU_BENCH_CHILD"] = "1"
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    if host_only:
        env["JAX_PLATFORMS"] = "cpu"
    return env


def _try_child(host_only, timeout):
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)], cwd=_REPO,
            env=_child_env(host_only), capture_output=True, text=True,
            timeout=timeout)
    except subprocess.TimeoutExpired as e:
        # the child may have printed its record and then wedged at
        # interpreter teardown — salvage it
        out = e.stdout or b""
        rec = _parse_record(out.decode() if isinstance(out, bytes) else out)
        if rec is not None:
            rec["salvaged_after_timeout"] = True
            return rec, None
        return None, "timeout after %ds" % timeout
    rec = _parse_record(proc.stdout)
    if rec is not None:
        return rec, None
    return None, "rc=%d stderr: %s" % (proc.returncode,
                                       proc.stderr[-1500:])


def _parse_record(stdout: str):
    for line in reversed((stdout or "").strip().splitlines()):
        try:
            rec = json.loads(line)
            if isinstance(rec, dict) and "metric" in rec:
                return rec
        except (json.JSONDecodeError, ValueError):
            continue
    return None


def _bls_record():
    """Run the BLS child once, on the chip."""
    prev = os.environ.get("LHTPU_BENCH")
    os.environ["LHTPU_BENCH"] = "bls"
    try:
        rec, _ = _try_child(False, int(os.environ.get(
            "LHTPU_BENCH_BLS_TIMEOUT", 600)))
        return rec if rec is not None and rec.get("value") else None
    finally:
        if prev is None:
            del os.environ["LHTPU_BENCH"]
        else:
            os.environ["LHTPU_BENCH"] = prev


def _stf_record():
    """One bounded child for the mainnet-envelope STF numbers.  The
    workload is host/numpy, so it runs host-only."""
    if os.environ.get("LHTPU_BENCH_STF", "1") == "0":
        return None
    prev = os.environ.get("LHTPU_BENCH")
    os.environ["LHTPU_BENCH"] = "stf"
    try:
        rec, _ = _try_child(True, int(os.environ.get(
            "LHTPU_BENCH_STF_TIMEOUT", 900)))
        return rec
    finally:
        if prev is None:
            del os.environ["LHTPU_BENCH"]
        else:
            os.environ["LHTPU_BENCH"] = prev


def tpu_probe(timeout=90):
    """Staged TPU-acquisition probe, promoted into the shared graftgauge
    device-health section (obs/device.staged_probe; also runnable
    standalone via ``tools/obs/doctor.py --probe``).  The bench feeds
    its child env so the probe sees the same compilation-cache +
    PYTHONPATH setup as the measurement children.  obs.device imports no
    jax at module scope, so the parent stays jax-free."""
    from lighthouse_tpu.obs import device
    env = _child_env(host_only=False)
    env.pop("LHTPU_BENCH_CHILD", None)
    return device.staged_probe(timeout=timeout, env=env, cwd=_REPO)


def _replay_record():
    """One bounded child for the graftflow replay numbers (ISSUE 14).
    Twin anchored chains plus a sequential oracle pass are pure
    host/numpy work, so it runs host-only."""
    if os.environ.get("LHTPU_BENCH_REPLAY", "1") == "0":
        return None
    prev = os.environ.get("LHTPU_BENCH")
    os.environ["LHTPU_BENCH"] = "replay"
    try:
        rec, _ = _try_child(True, int(os.environ.get(
            "LHTPU_BENCH_REPLAY_TIMEOUT", 1200)))
        return rec
    finally:
        if prev is None:
            del os.environ["LHTPU_BENCH"]
        else:
            os.environ["LHTPU_BENCH"] = prev


def _mxu_record():
    """One bounded child for the MXU-mode mont_mul measurement — runs
    LAST so its cold compiles can never cost the flagship records."""
    if os.environ.get("LHTPU_BENCH_MXU", "1") == "0":
        return None
    prev = os.environ.get("LHTPU_BENCH")
    os.environ["LHTPU_BENCH"] = "mxu"
    try:
        rec, _ = _try_child(False, int(os.environ.get(
            "LHTPU_BENCH_MXU_TIMEOUT", 600)))
        return rec
    finally:
        if prev is None:
            del os.environ["LHTPU_BENCH"]
        else:
            os.environ["LHTPU_BENCH"] = prev


def main():
    if "--against" in sys.argv:
        return _against_main(sys.argv)
    if "--trace" in sys.argv:
        # children inherit via _child_env(dict(os.environ)) and write
        # BENCH_TRACE_<mode>.json + _summary.json next to BENCH_*.json
        os.environ["LHTPU_BENCH_TRACE"] = "1"
    host_only = False
    if "--serve" in sys.argv:
        # serving-tier req/s (ISSUE 12): host-side workload
        os.environ["LHTPU_BENCH"] = "serve"
        host_only = True
    if "--replay" in sys.argv:
        # graftflow replay throughput (ISSUE 14): host-side workload
        os.environ["LHTPU_BENCH"] = "replay"
        host_only = True
    if os.environ.get("LHTPU_BENCH_CHILD"):
        return child_main()
    # one bounded child: on the chip for the device modes (it fails
    # where there is none), host-only for serve/replay
    timeout = int(os.environ.get(
        "LHTPU_BENCH_CPU_TIMEOUT" if host_only else "LHTPU_BENCH_TPU_TIMEOUT",
        1500 if host_only else 720))
    rec, err = _try_child(host_only, timeout)
    if rec is not None:
        if (os.environ.get("LHTPU_BENCH", "tree_hash") == "tree_hash"
                and not rec.get("salvaged_after_timeout")):
            # second north star (BLS batch throughput) merged into
            # the same record
            bls_rec = _bls_record()
            if bls_rec is not None and bls_rec.get("value"):
                rec["bls_sigs_per_sec"] = bls_rec["value"]
                rec["bls_vs_baseline"] = bls_rec["vs_baseline"]
                rec["bls_platform"] = bls_rec.get("platform")
                rec["bls_n_sigs"] = bls_rec.get("n_sigs")
                rec["bls_baseline_source"] = \
                    bls_rec.get("baseline_source")
                # fold the BLS child's per-kernel roofline into the
                # merged record's device block (the block itself
                # came from the tree-hash child)
                bdev = bls_rec.get("device")
                if isinstance(rec.get("device"), dict) \
                        and isinstance(bdev, dict):
                    broof = (bdev.get("roofline") or {})
                    rec["device"].setdefault("roofline", {})[
                        "bls_batch_verify"] = broof.get(
                            "bls_batch_verify") or [
                                {"cost": "unavailable"}]
                    rec["device"]["bls_child_platform"] = \
                        bdev.get("platform")
            stf_rec = _stf_record()
            if stf_rec is not None and stf_rec.get("value"):
                rec["epoch_ms_1m"] = stf_rec["epoch_ms_1m"]
                rec["block_import_ms_1m"] = \
                    stf_rec["block_import_ms_1m"]
                rec["stf_n_validators"] = \
                    stf_rec.get("n_validators")
                rec["stf_sig_backend"] = stf_rec.get("sig_backend")
                rec["stf_stages"] = stf_rec.get("stf_stages")
                rec["state_copy_ms"] = stf_rec.get("state_copy_ms")
                rec["state_copy_gate_ms"] = \
                    stf_rec.get("state_copy_gate_ms")
                rec["state_copy_gate_pass"] = \
                    stf_rec.get("state_copy_gate_pass")
                rec["import_critpath_1m"] = \
                    stf_rec.get("import_critpath_1m")
            replay_rec = _replay_record()
            if replay_rec is not None and replay_rec.get("value"):
                rec["replay_epochs_per_sec_pipelined"] = \
                    replay_rec["replay_epochs_per_sec_pipelined"]
                rec["replay_speedup"] = \
                    replay_rec.get("replay_speedup")
                rec["replay"] = replay_rec.get("replay")
            mxu_rec = _mxu_record()
            if mxu_rec is not None and mxu_rec.get("value"):
                rec["mont_mul_per_sec"] = \
                    mxu_rec.get("mont_mul_per_sec")
                rec["mxu_mode_speedup"] = mxu_rec["value"]
                rec["mxu_platform"] = mxu_rec.get("platform")
            if os.environ.get("LHTPU_BENCH_PROBE", "1") != "0":
                rec["tpu_probe"] = tpu_probe()
        print(json.dumps(rec))
        return
    err = ("host" if host_only else "chip") + ": " + err
    metric = {
        "bls": "bls_batch_verify_throughput",
        "stf": "stf_mainnet_envelope_1m_validators",
        "mxu": "mont_mul_mxu_modes",
        "serve": "api_serving_tier",
        "replay": "replay_pipeline",
    }.get(os.environ.get("LHTPU_BENCH", "tree_hash"),
          "beacon_state_tree_hash_1m_validators")
    print(json.dumps({
        "metric": metric,
        "value": None, "unit": "error", "vs_baseline": 0.0,
        "error": err[-1000:],
        # the device block is mandatory on every record; the parent
        # never imports jax, so on total child failure it is honest
        # about knowing nothing
        "device": {"platform": "unavailable", "device_kind": "unavailable",
                   "chip_count": 0, "hbm": "unavailable"},
    }))


if __name__ == "__main__":
    main()
