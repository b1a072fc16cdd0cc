#!/usr/bin/env python3
"""Bring-up smoke of the node's main path on one TPU chip.

    python chip_smoke.py

One process, one chip, mainnet size, no arguments.  Phases, in order:

- device check: JAX must report a TPU, else exit non-zero at once;
- ``import_1m``: three blocks at consecutive slots imported at 2^20
  validators through ``BeaconProcessor`` -> ``chain.process_block``.
  Each block's claimed state root comes from a host copy hashed with the
  SHA-NI host hasher; the import computes the root with the device
  ``DeviceTree`` and rejects a mismatch, so three accepted imports show
  the device roots equal an independent host root;
- ``bls_gossip_10k``: the ``tpu`` crypto backend selected and its stage
  programs compiled in parallel, as ``ClientBuilder.build`` does for
  ``--crypto-backend tpu`` (``TpuBackend.precompile``), then a 10,000-set
  pre-Electra gossip batch (128 distinct messages: 64 committees x 2
  slots), its corrupted twin, both checked against ``CppBackend``, and
  one block-sized batch of 100 distinct messages.

Each phase prints one JSON line; the last line is the ``ok`` line.  Any
failure raises, so the script exits non-zero without it.
"""
from __future__ import annotations

import gc
import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _REPO)

N_VALIDATORS = 1 << 20
IMPORT_BLOCKS = 3
GOSSIP_SETS = 10_000
GOSSIP_MESSAGES = 128          # 64 committees x 2 slots, pre-Electra
BLOCK_SETS = 100
SEED = 21


def check(ok: bool, what: str) -> None:
    """A failed check raises (``assert`` would vanish under ``-O``)."""
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


def require_tpu():
    """The device JAX reports; exits non-zero where it is not a TPU."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{dev.platform!r}); this script runs on the chip")
    return dev


_compiles: list[tuple[str, float]] = []


def watch_compiles() -> None:
    """Record the program name and seconds of every backend compile in
    this process (``jax.monitoring`` events); idempotent."""
    if watch_compiles.on:
        return
    import jax.monitoring as jm

    def on_duration(event: str, duration: float, fun_name="?", **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            _compiles.append((fun_name, duration))

    jm.register_event_duration_secs_listener(on_duration)
    watch_compiles.on = True


watch_compiles.on = False


def program_memory(name: str, compiled) -> dict:
    """The ``memory_analysis()`` bytes of one compiled program."""
    m = compiled.memory_analysis()
    return {"name": name,
            "code_bytes": int(m.generated_code_size_in_bytes),
            "temp_bytes": int(m.temp_size_in_bytes),
            "argument_bytes": int(m.argument_size_in_bytes),
            "output_bytes": int(m.output_size_in_bytes)}


def lead_shape(compiled) -> tuple:
    """Shape of a compiled program's first argument (its lane count)."""
    import jax
    return jax.tree.leaves(compiled.args_info)[0].shape


class Phase:
    """Wall time of one phase, split by the compile seconds and cache
    events that ``obs.jax_accounting`` saw inside it.  Compiles run in
    parallel threads (``precompile_s`` of wall time for
    ``precompile_summed_s`` of compiling) count by their wall time."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        from lighthouse_tpu.obs import jax_accounting
        jax_accounting.install_monitoring()
        watch_compiles()
        self._acct = jax_accounting.snapshot()
        self._n_compiles = len(_compiles)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.wall_s = time.perf_counter() - self._t0

    def record(self, **fields) -> dict:
        import jax
        from lighthouse_tpu.obs import jax_accounting
        acct = jax_accounting.snapshot()
        summed = acct["compile_seconds"] - self._acct["compile_seconds"]
        compile_s = (summed - fields.get("precompile_summed_s", 0.0)
                     + fields.get("precompile_s", 0.0))
        compiles = _compiles[self._n_compiles:]
        slowest = sorted(compiles, key=lambda c: -c[1])[:5]
        stats = jax.devices()[0].memory_stats() or {}
        return {
            "phase": self.name,
            "wall_s": self.wall_s,
            "compile_s": compile_s,
            "compile_summed_s": summed,
            "steady_s": self.wall_s - compile_s,
            **fields,
            "backend_compiles": len(compiles),
            "slowest_compiles": slowest,
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "cache_hits": acct["cache_hits"] - self._acct["cache_hits"],
            "cache_misses": acct["cache_misses"] - self._acct["cache_misses"],
        }


def _host_hashed(fn):
    """Run ``fn`` with the big columns hashed by the host SHA-NI hasher
    (the steering the tests use), then restore the platform's choice."""
    from lighthouse_tpu.containers import state as st
    old = st._USE_HOST_HASH
    st._USE_HOST_HASH = True
    try:
        return fn()
    finally:
        st._USE_HOST_HASH = old


def phase_import_1m(n_validators: int = N_VALIDATORS,
                    blocks: int = IMPORT_BLOCKS) -> dict:
    """Anchor a chain at ``n_validators`` and import ``blocks`` blocks at
    consecutive slots through the beacon processor."""
    from lighthouse_tpu.beacon_processor import (
        BeaconProcessor, Work, WorkType,
    )
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

    # the state's pubkeys are random bytes, not keys: block signatures
    # stay on the fake backend (a real block signature set is ROADMAP R3)
    bls.set_backend("fake")
    spec = mainnet_spec()
    slot0 = 100_000 * spec.preset.slots_per_epoch + 2
    t0 = time.perf_counter()
    state = build_beacon_state(n_validators, slot0)
    signed_anchor = anchor_block(state)
    host = state.copy()
    build_s = time.perf_counter() - t0
    chain = (BeaconChainBuilder(spec)
             .weak_subjectivity_anchor(state, signed_anchor)
             .slot_clock(ManualSlotClock(
                 0, spec.seconds_per_slot,
                 current_slot=slot0 + blocks - 1))
             .execution_layer(MockExecutionLayer())
             .build())
    proc = BeaconProcessor(num_workers=2)
    proc.start()
    imports = []
    try:
        for i in range(blocks):
            def claim():
                process_slots(host, slot0 + i)
                sb = build_import_block(host)
                per_block_processing(host, sb, VerifySignatures.FALSE)
                sb.message.state_root = host.hash_tree_root()
                return sb
            sb = _host_hashed(claim)
            out = {}

            def run(sb=sb, out=out):
                try:
                    out["root"] = chain.process_block(sb)
                except BaseException as exc:
                    out["error"] = exc
                    raise

            t0 = time.perf_counter()
            proc.submit(Work(kind=WorkType.GOSSIP_BLOCK, run=run))
            if not proc.wait_idle(timeout=1200):
                raise RuntimeError(f"block {i} import did not finish")
            import_s = time.perf_counter() - t0
            if "error" in out:
                raise out["error"]
            check(chain.fork_choice.contains_block(out["root"]),
                  f"block {i} imported but fork choice lacks it")
            imports.append({"slot": int(sb.message.slot),
                            "block_root": out["root"].hex(),
                            "state_root": sb.message.state_root.hex(),
                            "import_s": import_s})
    finally:
        proc.stop()
    use_host = st._use_host_hash()
    post = chain._state_for(bytes.fromhex(imports[-1]["block_root"]))
    tree = post.validators._device_tree
    check(use_host is False, "imports hashed on the host, not the device")
    check(isinstance(tree, DeviceTree), "registry holds no DeviceTree")
    return {"n_validators": n_validators, "state_build_s": build_s,
            "imports": imports, "device_roots_accepted": len(imports),
            "use_host_hash": use_host,
            "registry_tree": type(tree).__name__,
            "largest_program": registry_build_memory(tree)}


def registry_build_memory(tree) -> dict:
    """Memory of the registry tree's build, the import's largest program
    (AOT, PR 21): lowered again from the tree's own shape, which the
    executable cache of the import's build answers without compiling."""
    import jax
    import jax.numpy as jnp

    from lighthouse_tpu.ops.merkle_tree import _build_fn

    leaves = jax.ShapeDtypeStruct((tree.dense << tree.pre_levels, 8),
                                  jnp.uint32)
    pks = [jax.ShapeDtypeStruct((tree.dense, 16), jnp.uint32)] \
        if tree.with_pk else []
    n_live = jax.ShapeDtypeStruct((), jnp.int32)
    fn = _build_fn(tree.dense_depth, tree.limit_depth, tree.pre_levels,
                   tree.with_pk)
    return program_memory("registry DeviceTree build",
                          fn.lower(leaves, *pks, n_live).compile())


def _sign_sets(signer, sks, msgs):
    """SignatureSets for (sk, msg) pairs, signed in threads (the native
    signer releases the GIL)."""
    from concurrent.futures import ThreadPoolExecutor

    from lighthouse_tpu.crypto.bls import SignatureSet

    def one(pair):
        sk, msg = pair
        return SignatureSet(signer.sign(sk, msg), [signer.sk_to_pk(sk)], msg)

    with ThreadPoolExecutor(max_workers=os.cpu_count() or 1) as pool:
        return list(pool.map(one, zip(sks, msgs)))


def attestation_signing_roots(n: int, rng) -> list[bytes]:
    """Signing roots of ``n`` distinct mainnet AttestationData: two slots
    of n/2 committees each, one head vote per slot (pre-Electra: the
    committee index is part of the signed data)."""
    from lighthouse_tpu.containers import get_types
    from lighthouse_tpu.specs.chain_spec import (
        compute_domain, compute_signing_root, mainnet_spec,
    )
    from lighthouse_tpu.specs.constants import DOMAIN_BEACON_ATTESTER
    from lighthouse_tpu.ssz import htr
    spec = mainnet_spec()
    T = get_types(spec.preset)
    domain = compute_domain(DOMAIN_BEACON_ATTESTER,
                            spec.altair_fork_version, rng.randbytes(32))
    epoch = 100_000
    slot0 = epoch * spec.preset.slots_per_epoch + 2
    source = T.Checkpoint(epoch=epoch - 1, root=rng.randbytes(32))
    target = T.Checkpoint(epoch=epoch, root=rng.randbytes(32))
    per_slot = -(-n // 2)
    roots = []
    for i in range(n):
        slot = slot0 + i // per_slot
        data = T.AttestationData(
            slot=slot, index=i % per_slot,
            beacon_block_root=bytes([slot & 0xFF]) * 32,
            source=source, target=target)
        roots.append(compute_signing_root(htr(data), domain))
    return roots


def phase_bls_gossip_10k(n_sets: int = GOSSIP_SETS,
                         n_messages: int = GOSSIP_MESSAGES,
                         block_sets: int = BLOCK_SETS,
                         seed: int = SEED) -> dict:
    """The gossip batch, its corrupted twin and a block-sized batch
    through the ``tpu`` backend, each checked against ``CppBackend``."""
    import random

    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.crypto.bls import SignatureSet
    from lighthouse_tpu.crypto.bls.cpp_backend import CppBackend
    from lighthouse_tpu.crypto.bls12_381 import R

    rng = random.Random(seed)
    cpp = CppBackend()
    sks = [rng.randrange(1, R) for _ in range(n_sets)]
    data = attestation_signing_roots(n_messages, rng)
    t0 = time.perf_counter()
    sets = _sign_sets(cpp, sks, [data[i % n_messages]
                                 for i in range(n_sets)])
    # a valid signature of another set: every point checks, the batch
    # equation does not
    bad = list(sets)
    bad[n_sets // 2] = SignatureSet(sets[n_sets // 2 + 1].signature,
                                    sets[n_sets // 2].pubkeys,
                                    sets[n_sets // 2].message)
    block = _sign_sets(cpp, sks[:block_sets],
                       [rng.randbytes(32) for _ in range(block_sets)])
    sign_s = time.perf_counter() - t0

    from lighthouse_tpu.obs import jax_accounting
    jax_accounting.install_monitoring()
    watch_compiles()
    c0 = jax_accounting.snapshot()["compile_seconds"]
    t0 = time.perf_counter()
    # what ClientBuilder.build does for --crypto-backend tpu
    tpu = bls.set_backend("tpu")
    staged = tpu.precompile()
    timed = {"precompile_s": time.perf_counter() - t0,
             "precompile_summed_s":
                 jax_accounting.snapshot()["compile_seconds"] - c0}
    n_compiles = len(_compiles)

    def verify(label, batch):
        t0 = time.perf_counter()
        verdict = tpu.verify_signature_sets(batch)
        timed[label] = time.perf_counter() - t0
        return verdict

    # the corrupted twin does the valid batch's work with every program
    # compiled: its time is the warm 10k batch
    verdicts = {"gossip": verify("gossip_cold_s", sets),
                "gossip_corrupted": verify("gossip_warm_s", bad),
                "block": verify("block_cold_s", block)}
    again = sorted({name for name, _ in _compiles[n_compiles:]}
                   & {f"jit({name})" for name, _ in staged})
    check(not again, f"verify compiled staged programs again: {again}")
    t0 = time.perf_counter()
    reference = {"gossip": cpp.verify_signature_sets(sets),
                 "gossip_corrupted": cpp.verify_signature_sets(bad),
                 "block": cpp.verify_signature_sets(block)}
    cpp_s = time.perf_counter() - t0
    check(verdicts == {"gossip": True, "gossip_corrupted": False,
                       "block": True}, f"tpu verdicts {verdicts}")
    check(reference == verdicts, f"CppBackend verdicts {reference}")
    return {"n_sets": n_sets, "distinct_messages": n_messages,
            "block_sets": block_sets, "staged_programs": len(staged),
            "largest_program": max(
                (program_memory(f"{name}{list(lead_shape(c))}", c)
                 for name, c in staged),
                key=lambda p: p["code_bytes"] + p["temp_bytes"]),
            "verdicts": verdicts,
            "cpp_verdicts": reference, "sign_s": sign_s,
            "cpp_verify_s": cpp_s, **timed}


def main() -> int:
    dev = require_tpu()
    from lighthouse_tpu.utils import compile_cache
    cache_dir = compile_cache.configure()
    import jax
    print(json.dumps({"phase": "device", "platform": dev.platform,
                      "kind": dev.device_kind, "count": len(jax.devices()),
                      "compile_cache": cache_dir}), flush=True)
    for name, fn in (("import_1m", phase_import_1m),
                     ("bls_gossip_10k", phase_bls_gossip_10k)):
        with Phase(name) as ph:
            out = fn()
        print(json.dumps(ph.record(**out)), flush=True)
        gc.collect()    # free the 2^20-validator states before compiling
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
