"""Measure exact per-signature arithmetic op counts for PERF_MODEL.md.

Monkeypatches the single choke point every field multiplication funnels
through (`ops.bigint._mul_columns`) and runs each stage of the BLS
verification pipeline eagerly (`jax.disable_jit`) at batch 1, so
`lax.scan`s execute their true step counts.  Counts are EXACT dynamic
counts of (a) Fp column-product invocations per lane and (b) int32
multiply-adds inside them (elements x NLIMBS x out_len), the dominant
VPU cost.  Normalize/carry overhead is modeled separately in
PERF_MODEL.md from static analysis.

Run:  env JAX_PLATFORMS=cpu python tools/perf_model.py
"""
import json
import math
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
from lighthouse_tpu.utils import compile_cache  # noqa: E402

compile_cache.configure()

import numpy as np  # noqa: E402

COUNT = {"fp_muls": 0, "int32_muls": 0, "calls": 0}


def main():
    import jax
    import jax.numpy as jnp
    from lighthouse_tpu.ops import bigint as bi
    from lighthouse_tpu.ops import bls12_381 as k
    from lighthouse_tpu.crypto.bls12_381 import G1_GENERATOR, sig as osig
    from lighthouse_tpu.crypto.bls12_381.curve import G2_GENERATOR
    from lighthouse_tpu.crypto.bls12_381 import g2_compress
    from lighthouse_tpu.crypto.bls12_381.hash_to_curve import DST_POP

    orig = bi._mul_columns

    def counting(a, b, out_len):
        n_el = 1
        for d in a.shape[:-1]:
            n_el *= int(d)
        COUNT["fp_muls"] += n_el
        COUNT["int32_muls"] += n_el * bi.NLIMBS * out_len
        COUNT["calls"] += 1
        return orig(a, b, out_len)

    bi._mul_columns = counting
    # the k module aliases fp_mul = bi.mont_mul (jitted); jit would hide
    # scan iterations -> run everything under disable_jit
    stages = {}

    def snap(name):
        stages[name] = dict(COUNT)

    def delta(a, b):
        return {key: stages[b][key] - stages[a][key] for key in COUNT}

    pt = osig.sign(7, b"\x01" * 32)
    cb = g2_compress(pt)
    c1 = int.from_bytes(bytes([cb[0] & 0x1f]) + cb[1:48], "big")
    c0 = int.from_bytes(cb[48:96], "big")
    flags = np.array([bool(cb[0] & 0x20)])

    with jax.disable_jit():
        sig_x = jnp.asarray(k.fp_encode([c0, c1]).reshape(1, 2, bi.NLIMBS))
        one2 = jnp.asarray(np.broadcast_to(k.FP2_ONE, (1, 2, bi.NLIMBS)))
        one1 = np.broadcast_to(k.FP_ONE, (1, bi.NLIMBS))
        snap("t0")

        sig_y, ok = k.g2_decompress_batch(sig_x, flags)
        assert bool(np.asarray(ok).all())
        snap("decompress")

        assert bool(np.asarray(k.g2_in_subgroup_batch(sig_x, sig_y, one2)).all())
        snap("subgroup")

        mx, my, mz = k.hash_to_g2_batch([b"\x01" * 32], DST_POP)
        snap("hash_to_g2")

        msg_x, msg_y = k.jacobian_to_affine_fp2(mx, my, mz)
        snap("affine_msg")

        gx, gy = G1_GENERATOR.to_affine()
        pk_x = k.fp_encode([int(gx)])
        pk_y = k.fp_encode([int(gy)])
        bits = k.scalars_to_bits([(1 << 63) | 12345], 64)
        spx, spy, spz = k.g1_scalar_mul(pk_x, pk_y, one1, bits)
        snap("rlc_g1")

        ssx, ssy, ssz = k.g2_scalar_mul(sig_x, sig_y, one2, bits)
        snap("rlc_g2")

        ax, ay, az = k.g2_sum(ssx, ssy, ssz)
        snap("g2_sum")

        apx, apy = k.jacobian_to_affine_fp(spx, spy, spz)
        aax, aay = k.jacobian_to_affine_fp2(ax[None], ay[None], az[None])
        snap("affine_misc")

        fs = k.miller_loop_batch(
            jnp.concatenate([apx], axis=0), jnp.concatenate([apy], axis=0),
            jnp.asarray(msg_x), jnp.asarray(msg_y))
        snap("miller_1pair")

        prod = k.fp12_product(fs)
        snap("fp12_product")

        out = k.final_exponentiation(prod)
        snap("final_exp")

    order = ["decompress", "subgroup", "hash_to_g2", "affine_msg",
             "rlc_g1", "rlc_g2", "g2_sum", "affine_misc",
             "miller_1pair", "fp12_product", "final_exp"]
    prev = "t0"
    rows = {}
    for name in order:
        rows[name] = delta(prev, name)
        prev = name
    per_lane = ["decompress", "subgroup", "hash_to_g2", "affine_msg",
                "rlc_g1", "rlc_g2", "miller_1pair"]
    shared = ["g2_sum", "affine_misc", "fp12_product", "final_exp"]
    tot_lane = {key: sum(rows[n][key] for n in per_lane) for key in COUNT}
    tot_shared = {key: sum(rows[n][key] for n in shared) for key in COUNT}
    print(json.dumps({"per_stage": rows,
                      "per_lane_total": tot_lane,
                      "shared_total": tot_shared}, indent=1))


if __name__ == "__main__":
    main()
