"""Mesh-sharded BLS multi-pairing.

The reference spreads its RLC batch verification's multi-pairing across
CPU cores inside blst (crypto/bls/src/impls/blst.rs:37-119,
block_signature_verifier.rs:413-414).  The TPU-native analog shards the
(P_i, Q_i) pair batch across the device mesh: each chip runs the Miller
loop on its shard and reduces it to one local Fp12 product, the n_dev
partial products are all-gathered over ICI (n_dev * 1.5 KiB — one tiny
collective), and the shared final exponentiation + identity check runs
replicated.  Scales the 10k-signature gossip batch linearly in chips
without touching DCN.
"""
from __future__ import annotations

import functools

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from ..obs import device
from ..obs.jax_accounting import host_readback
from ..obs.roofline import track_roofline
from ..ops.bls12_381 import (
    fp12_product,
    mask_to_one,
    miller_loop_batch,
    pairing_verdict,
)


_FALLBACK_PARSE_BACKEND = None     # shared cache and table for other backends


def _local_miller_product(px, py, qx, qy):
    fs = miller_loop_batch(px, py, qx, qy)     # [local, 2, 3, 2, 32]
    return fp12_product(fs)[None]              # [1, 2, 3, 2, 32]


def _local_masked_product(lpx, lpy, lqx, lqy, lmask):
    fs = miller_loop_batch(lpx, lpy, lqx, lqy)
    return fp12_product(mask_to_one(fs, lmask))[None]


def _verdict(partials):
    """The shared tail: the chips' partial products into one
    ``pairing_verdict`` program (their product, the final
    exponentiation and the comparison with one)."""
    return pairing_verdict(partials, np.ones(partials.shape[0], bool))


# Memoized jitted programs per (mesh, axis): a fresh jit(shard_map(...))
# per call would rebuild the wrapper — and the shard_map closure under it
# — every time, so every call re-traced (graftlint: recompile-hazard).
# track_roofline() is the dynamic complement: compile accounting (a shape
# leak past the memoization shows up as jax_compile_total) PLUS each
# program's cost_analysis + measured wall time scored against the
# platform peak table (graftgauge) — the compile-budget lint rule flags
# factories here that bypass it.

@functools.lru_cache(maxsize=None)
def _miller_product_fn(mesh: Mesh, axis: str):
    return track_roofline("bls.miller_product", jax.jit(shard_map(
        _local_miller_product, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis)),
        out_specs=P(axis))))


@functools.lru_cache(maxsize=None)
def _masked_product_fn(mesh: Mesh, axis: str):
    return track_roofline("bls.masked_product", jax.jit(shard_map(
        _local_masked_product, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis), P(axis)),
        out_specs=P(axis))))


@functools.lru_cache(maxsize=None)
def _scalar_mul_fns(mesh: Mesh, axis: str):
    import lighthouse_tpu.ops.bls12_381 as k
    g1 = track_roofline("bls.g1_scalar_mul", jax.jit(shard_map(
        k.g1_scalar_mul, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis)),
        out_specs=(P(axis), P(axis), P(axis)))))
    g2 = track_roofline("bls.g2_scalar_mul", jax.jit(shard_map(
        k.g2_scalar_mul, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis)),
        out_specs=(P(axis), P(axis), P(axis)))))
    return g1, g2


def sharded_pairing_check(mesh: Mesh, px, py, qx, qy,
                          axis: str = "batch"):
    """prod_i e(P_i, Q_i) == 1 with the pair batch row-sharded over the
    mesh.  The batch size must divide evenly across mesh[axis].

    STAGED (compile-regime discipline, ops/bls12_381.py): two programs.
    The first is the sharded Miller loop + per-chip local product — its
    out_spec gathers the n_dev partials over ICI (n_dev * 1.5 KiB, one
    tiny collective); the second, ``pairing_verdict``, takes the tiny
    product, the shared final exponentiation and the identity check on
    the gathered result.  One fused program here was the round-2
    ~12-minute compile."""
    with device.hbm_watermark("parallel.bls"):
        device.attribute("parallel.bls", "pairing_inputs", px, py, qx, qy)
        partials = _miller_product_fn(mesh, axis)(px, py, qx,
                                                  qy)  # [n_dev,2,3,2,32]
        return _verdict(partials)


def sharded_verify_signature_sets(mesh: Mesh, sets, lanes: int,
                                  axis: str = "batch",
                                  backend=None) -> bool:
    """The FULL `verify_signature_sets` semantics over the device mesh
    (VERDICT r3 "next" #6): per-set pubkey aggregation (device sums
    over the ``backend``'s pubkey table, a ``TpuBackend``'s; cached
    points for single-key batches), signature parsing + flag handling,
    device
    decompression + psi subgroup checks, same-message grouping, per-lane
    RLC scalar multiplications SHARDED over the mesh, the scaled-signature
    sum via per-shard partial sums gathered over ICI, the segmented
    per-message pubkey sums on the gathered scaled points, and the
    pairing check as two programs: the sharded Miller loop with each
    chip's masked product, then one replicated ``pairing_verdict``.

    `lanes` must be a multiple of mesh[axis].  Returns the verification
    bool; semantics are cross-checked against the single-device
    `TpuBackend` in the driver dryrun and tests/test_parallel.py.
    """
    import lighthouse_tpu.ops.bls12_381 as k
    from lighthouse_tpu.ops import bigint as bi
    from lighthouse_tpu.crypto.bls.tpu_backend import (
        TpuBackend, host_prepare, parse_sets, pubkey_sums,
    )
    from lighthouse_tpu.crypto.bls12_381 import G1_GENERATOR

    if not sets:
        return False
    n_dev = mesh.shape[axis]
    assert lanes % n_dev == 0, "lanes must divide across the mesh"
    if backend is None:
        # share the registered backend's point cache and pubkey table
        # (ADVICE r4: a fresh backend re-paid host prep every call);
        # other backends fall back to ONE module-cached TpuBackend so
        # amortization still holds
        from lighthouse_tpu.crypto.bls import get_backend
        backend = get_backend()
        if not hasattr(backend, "table"):
            global _FALLBACK_PARSE_BACKEND
            if _FALLBACK_PARSE_BACKEND is None:
                _FALLBACK_PARSE_BACKEND = TpuBackend()
            backend = _FALLBACK_PARSE_BACKEND
    parsed = parse_sets(backend, sets)
    if parsed is None:
        return False                  # malformed input: reject, not raise
    assert len(parsed[0]) <= lanes
    # host prep shared with TpuBackend._verify_chunk; the sharded Miller
    # runs at full `lanes` (the shard split must stay even), so no
    # small-message-shape split here
    prep = host_prepare(*parsed, lanes, small=lanes)
    mask = prep["mask"][:-1]          # per-message lanes (aggregate lane
                                      # is appended below)
    if "agg_rows" in prep:
        prep["pk_table"] = backend.table.arrays()
    # multi-key sets' pubkeys summed on the device from the pubkey table
    pkx, pky, pkz, pk_ok = pubkey_sums(prep, lanes)

    # ---- device: replicated validity checks + hash map -----------------
    import jax.numpy as jnp
    sig_x = jnp.asarray(prep["sig_x"])
    sig_y, on_curve = k.g2_decompress_batch(sig_x, prep["flags"])
    # validity gates are the two deliberate mid-pipeline host round-trips;
    # host_readback() is the sanctioned (byte-accounted) crossing — the
    # device-transfer lint rule rejects bare np.asarray here
    if not bool(host_readback(on_curve).all()):
        return False
    one2 = jnp.asarray(np.broadcast_to(k.FP2_ONE, (lanes, 2, bi.NLIMBS)))
    if not bool(host_readback(k.g2_in_subgroup_batch(sig_x, sig_y,
                                                     one2)).all()):
        return False
    if pk_ok is not None and not bool(host_readback(pk_ok).all()):
        return False
    mx, my, mz = k.hash_to_g2_batch_from_u(prep["u0"], prep["u1"])
    msg_x, msg_y = k.jacobian_to_affine_fp2(mx, my, mz)

    # ---- device: SHARDED RLC scalar muls -------------------------------
    bits_pk = k.scalars_to_bits(prep["pk_rands"], 64)
    bits_sig = k.scalars_to_bits(prep["sig_rands"], 64)
    g1_sharded, g2_sharded = _scalar_mul_fns(mesh, axis)
    with device.hbm_watermark("parallel.bls"):
        spx, spy, spz = g1_sharded(jnp.asarray(pkx), jnp.asarray(pky),
                                   jnp.asarray(pkz),
                                   jnp.asarray(bits_pk))
        ssx, ssy, ssz = g2_sharded(sig_x, sig_y, one2,
                                   jnp.asarray(bits_sig))
        device.attribute("parallel.bls", "rlc_scaled_points",
                         spx, spy, spz, ssx, ssy, ssz)

    # scaled-signature aggregate + per-message pubkey segment sums run on
    # the gathered scaled points (ICI gather of [lanes] points)
    ax, ay, az = k.g2_sum(ssx, ssy, ssz)
    gpx, gpy, gpz = k.g1_segment_sum(spx, spy, spz, prep["starts"],
                                     prep["ends"])
    apx, apy = k.jacobian_to_affine_fp(gpx, gpy, gpz)
    aax, aay = k.jacobian_to_affine_fp2(ax, ay, az)

    # ---- device: SHARDED Miller + replicated final exp -----------------
    # pad the (+1 aggregate) pair batch to a mesh multiple with masked
    # identity lanes so the shard split stays even
    total = lanes + 1
    mpad = (-total) % n_dev
    neg_g = G1_GENERATOR.neg().to_affine()
    ngx = k.fp_encode([int(neg_g[0])] * (1 + mpad))
    ngy = k.fp_encode([int(neg_g[1])] * (1 + mpad))
    px = jnp.concatenate([apx, jnp.asarray(ngx)], axis=0)
    py = jnp.concatenate([apy, jnp.asarray(ngy)], axis=0)
    qx = jnp.concatenate([msg_x, jnp.broadcast_to(aax[None],
                                                  (1 + mpad,) +
                                                  aax.shape)], axis=0)
    qy = jnp.concatenate([msg_y, jnp.broadcast_to(aay[None],
                                                  (1 + mpad,) +
                                                  aay.shape)], axis=0)
    full_mask = np.zeros(total + mpad, dtype=bool)
    full_mask[:lanes] = mask
    full_mask[lanes] = True               # the one real aggregate lane

    with device.hbm_watermark("parallel.bls"):
        device.attribute("parallel.bls", "miller_pairs", px, py, qx, qy)
        partials = _masked_product_fn(mesh, axis)(px, py, qx, qy,
                                                  jnp.asarray(full_mask))
        verdict = _verdict(partials)
    return bool(host_readback(verdict))
