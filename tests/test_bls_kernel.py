"""TPU BLS12-381 kernels vs the pure-Python oracle."""
import jax
import numpy as np
import pytest

import lighthouse_tpu.ops.bls12_381 as k
from lighthouse_tpu.crypto.bls12_381 import (
    Fp2, G1_GENERATOR, G2_GENERATOR, P, pairing, multi_pairing,
    sk_to_pk, sign, keygen_interop, hash_to_g2,
)
from lighthouse_tpu.crypto.bls12_381.fields import Fp12
from lighthouse_tpu.ops import bigint as bi

rng = np.random.default_rng(21)


def rand_fp2(n):
    return [Fp2(int(rng.integers(0, 2**63)) * int(rng.integers(0, 2**63)) % P,
                int(rng.integers(0, 2**63)) * int(rng.integers(0, 2**63)) % P)
            for _ in range(n)]


def test_fp2_mul_square_inv():
    n = 8
    a = rand_fp2(n)
    b = rand_fp2(n)
    ka, kb = k.fp2_encode(a), k.fp2_encode(b)
    prod = k.fp2_mul(ka, kb)
    sq = k.fp2_square(ka)
    inv = k.fp2_inv(ka)
    for i in range(n):
        want = a[i] * b[i]
        got = k.fp_decode(prod[i])
        assert got == [int(want.c0), int(want.c1)]
        wsq = a[i].square()
        assert k.fp_decode(sq[i]) == [int(wsq.c0), int(wsq.c1)]
        winv = a[i].inv()
        assert k.fp_decode(inv[i]) == [int(winv.c0), int(winv.c1)]


def _encode_g2(points):
    xs, ys = [], []
    for p in points:
        x, y = p.to_affine()
        xs.append(x)
        ys.append(y)
    return k.fp2_encode(xs), k.fp2_encode(ys)


def _encode_g1(points):
    xs, ys = [], []
    for p in points:
        x, y = p.to_affine()
        xs.append(int(x))
        ys.append(int(y))
    return k.fp_encode(xs), k.fp_encode(ys)


def test_g1_scalar_mul_matches():
    scalars = [3, 7, 65537, 2**63 - 25]
    n = len(scalars)
    x, y = _encode_g1([G1_GENERATOR] * n)
    z = np.broadcast_to(k.FP_ONE, (n, bi.NLIMBS))
    sx, sy, sz = k.g1_scalar_mul(x, y, z, k.scalars_to_bits(scalars, 64))
    ax, ay = k.jacobian_to_affine_fp(sx, sy, sz)
    for i, s in enumerate(scalars):
        want = G1_GENERATOR.mul(s).to_affine()
        assert k.fp_decode(ax[i])[0] == int(want[0])
        assert k.fp_decode(ay[i])[0] == int(want[1])


def test_g1_segment_sum_matches():
    # contiguous segments of lengths 1, 4, 7, 2, 10 and 13 (37 lanes, a
    # non-power of two), one lane at infinity, two padding groups
    seglens = [1, 4, 7, 2, 10, 13]
    pts = [G1_GENERATOR.mul(int(s))
           for s in rng.integers(1, 2**40, size=sum(seglens))]
    x, y = _encode_g1(pts)
    z = np.array(np.broadcast_to(k.FP_ONE, (len(pts), bi.NLIMBS)))
    z[5] = 0
    starts = np.zeros(len(pts), np.int32)
    ends = np.zeros(len(seglens) + 2, np.int32)
    pos = 0
    for g, n in enumerate(seglens):
        starts[pos] = 1
        ends[g] = pos + n - 1
        pos += n
    sx, sy, sz = k.g1_segment_sum(x, y, z, starts, ends)
    ax, ay = k.jacobian_to_affine_fp(sx, sy, sz)
    pos = 0
    for g, n in enumerate(seglens):
        want = None
        for i in range(pos, pos + n):
            if i != 5:
                want = pts[i] if want is None else want.add(pts[i])
        pos += n
        wx, wy = want.to_affine()
        assert k.fp_decode(ax[g])[0] == int(wx)
        assert k.fp_decode(ay[g])[0] == int(wy)


def test_g2_add_dbl_matches():
    p2 = G2_GENERATOR.double()
    p3 = p2.add(G2_GENERATOR)
    x, y = _encode_g2([G2_GENERATOR, p2])
    z = np.broadcast_to(k.FP2_ONE, (2, 2, bi.NLIMBS))
    dx, dy, dz = k.g2_dbl(x, y, z)
    ax, ay = k.jacobian_to_affine_fp2(dx, dy, dz)
    want = p2.to_affine()
    assert k.fp_decode(ax[0]) == [int(want[0].c0), int(want[0].c1)]
    # add: G + 2G = 3G
    sx, sy, sz = k.g2_add(x[:1], y[:1], z[:1], x[1:], y[1:], z[1:])
    ax, ay = k.jacobian_to_affine_fp2(sx, sy, sz)
    want3 = p3.to_affine()
    assert k.fp_decode(ax[0]) == [int(want3[0].c0), int(want3[0].c1)]
    assert k.fp_decode(ay[0]) == [int(want3[1].c0), int(want3[1].c1)]


def _f12_to_ints(e):
    out = []
    for c6 in (e.c0, e.c1):
        for c2 in (c6.c0, c6.c1, c6.c2):
            out += [int(c2.c0), int(c2.c1)]
    return out


def test_miller_loop_matches_python():
    """Miller loop only (final exp is covered by the slow test — its scans
    take minutes on the CPU test backend but milliseconds per batch on TPU).
    Each pair's own value, then their product."""
    from lighthouse_tpu.crypto.bls12_381.pairing import miller_loop
    pairs = [(G1_GENERATOR.mul(3), G2_GENERATOR.mul(5)),
             (G1_GENERATOR.mul(2), G2_GENERATOR.mul(9))]
    px, py = _encode_g1([p for p, _ in pairs])
    qx, qy = _encode_g2([q for _, q in pairs])
    fs = k.miller_loop_batch(px, py, qx, qy)
    for i, pair in enumerate(pairs):
        assert k.fp_decode(fs[i]) == _f12_to_ints(miller_loop([pair]))
    prod = k.fp12_product(fs)
    want = miller_loop(pairs)
    assert k.fp_decode(prod) == _f12_to_ints(want)


def _twist_points():
    """On-curve G2 points (y^2 = x^3 + b over Fp2, x = (1, 0), (2, 0)
    ...): outside the subgroup as a rule."""
    from lighthouse_tpu.crypto.bls12_381.curve import B_G2, G2Point
    xx = 0
    while True:
        xx += 1
        yy = (Fp2(xx, 0) * Fp2(xx, 0) * Fp2(xx, 0) + B_G2).sqrt()
        if yy is not None:
            yield G2Point(Fp2(xx, 0), yy)


_const_ladder = jax.jit(k.g2_scalar_mul_const, static_argnums=3)


@pytest.mark.parametrize("name", ["u", "k1", "k2"])
def test_const_ladder_matches_oracle(name):
    """[c]P for the subgroup check's |u| and the cofactor clearing's k1,
    k2: on subgroup points, a point outside the subgroup, one of order
    13 (the ladder meets P == acc and P == -acc there) and infinity."""
    from lighthouse_tpu.crypto.bls12_381.curve import H_EFF_G2, Point, R
    constant = {"u": k._U_ABS2, "k1": k._BP_K1, "k2": k._BP_K2}[name]
    off = next(_twist_points())
    assert not off.mul(R).is_infinity()
    # the twist's 13-torsion is Z/13 x Z/13: [h2 r / 13^2] leaves a
    # point's part of order 13
    order_13 = next(q for q in (p.mul(H_EFF_G2 * R // 13**2)
                                for p in _twist_points())
                    if not q.is_infinity())
    assert order_13.mul(13).is_infinity()
    points = [G2_GENERATOR.mul(int(s)) for s in rng.integers(1, 2**62, 2)]
    points += [off, order_13]
    x, y = _encode_g2(points + [G2_GENERATOR])
    z = np.array(np.broadcast_to(k.FP2_ONE, x.shape))
    z[-1] = 0                                   # the last lane: infinity
    points.append(Point.infinity(G2_GENERATOR.b))
    out = [np.asarray(v).reshape(len(points), 2, -1)
           for v in _const_ladder(x, y, z, constant)]
    for i, point in enumerate(points):
        got = Point(*(Fp2(*k.fp_decode(v[i])) for v in out), G2_GENERATOR.b)
        assert got.eq(point.mul(constant)), (name, i)


def _mont_mul_calls(lowered):
    """The Montgomery products of a lowered program (calls of the
    ``mont_mul`` functions, counted through every other call): in all,
    and per ``while`` loop, with the products of each branch of every
    ``case`` inside that loop."""
    module = lowered.compiler_ir("stablehlo")
    funcs = {str(op.attributes["sym_name"]).strip('"'): op.operation
             for op in module.body.operations}

    def callee(op):
        return str(op.attributes["callee"]).lstrip("@")

    def walk(op):
        for region in op.regions:
            yield from walk_region(region)

    def walk_region(region):
        for block in region.blocks:
            for child in block.operations:
                yield child
                if child.name != "func.call":
                    yield from walk(child)
                elif not callee(child).startswith("mont_mul"):
                    yield from walk(funcs[callee(child)])

    def products(ops):
        return sum(o.name == "func.call" and callee(o).startswith("mont_mul")
                   for o in ops)

    main = funcs["main"]
    return products(walk(main)), [
        (products(walk(loop)),
         [[products(walk_region(r)) for r in case.regions]
          for case in walk(loop) if case.name == "stablehlo.case"])
        for loop in walk(main) if loop.name == "stablehlo.while"]


def _add_step_and_line(f, tx, ty, tz, qx, qy, px, py):
    t, coeffs = k._miller_add_step(tx, ty, tz, qx, qy)
    return k._ell(f, coeffs, px, py), t


def _dbl_step_and_line(f, tx, ty, tz, px, py):
    t, coeffs = k._miller_dbl_step(tx, ty, tz, k._TWO_INV)
    return k._ell(k.fp12_square(f), coeffs, px, py), t


@pytest.mark.parametrize("program", ["g2_in_subgroup_batch",
                                     "miller_loop_batch"])
def test_ladder_addition_sits_under_a_branch(program):
    """The lowered program's one loop holds one two-way branch on the
    constant's bit: one arm computes no product, the other exactly the
    addition's (``g2_add``; the Miller add step and its line), and the
    rest of the loop body exactly the doubling's.  Turned back into
    always-add-then-select, the branch and its empty arm go."""
    fp, fp2, fp12 = (jax.ShapeDtypeStruct(s, np.int32) for s in
                     ((1, 32), (1, 2, 32), (1, 2, 3, 2, 32)))
    if program == "g2_in_subgroup_batch":
        args = (fp2, fp2, fp2)
        addition = jax.jit(k.g2_add).lower(*args, *args)
        doubling = jax.jit(k.g2_dbl).lower(*args)
    else:
        args = (fp, fp, fp2, fp2)
        addition = jax.jit(_add_step_and_line).lower(
            fp12, fp2, fp2, fp2, fp2, fp2, fp, fp)
        doubling = jax.jit(_dbl_step_and_line).lower(
            fp12, fp2, fp2, fp2, fp, fp)
    add_products, _ = _mont_mul_calls(addition)
    dbl_products, _ = _mont_mul_calls(doubling)
    _, loops = _mont_mul_calls(getattr(k, program).lower(*args))
    ((in_loop, cases),) = loops
    (arms,) = cases
    assert sorted(arms) == [0, add_products]
    assert in_loop == dbl_products + add_products


def test_final_exp_matches_python():
    pairs = [(G1_GENERATOR.mul(3), G2_GENERATOR.mul(5))]
    px, py = _encode_g1([p for p, _ in pairs])
    qx, qy = _encode_g2([q for _, q in pairs])
    out = k.final_exponentiation(
        k.fp12_product(k.miller_loop_batch(px, py, qx, qy)))
    want = pairing(*pairs[0])
    assert k.fp_decode(out) == _f12_to_ints(want)


def _check_pairs(name):
    """Two pairs: a signature's check, the same with a wrong message, or
    junk (unrelated points)."""
    if name == "junk":
        pairs = [(G1_GENERATOR.mul(3), G2_GENERATOR.mul(5)),
                 (G1_GENERATOR.mul(2), G2_GENERATOR.mul(9))]
        return (*_encode_g1([p for p, _ in pairs]),
                *_encode_g2([q for _, q in pairs]))
    sk = keygen_interop(3)
    msg = b"\x5a" * 32 if name == "signature" else b"\x5b" * 32
    # e(-g1, sig) * e(pk, h) == 1
    px, py = _encode_g1([G1_GENERATOR.neg(), sk_to_pk(sk)])
    qx, qy = _encode_g2([sign(sk, b"\x5a" * 32), hash_to_g2(msg)])
    return px, py, qx, qy


@pytest.mark.parametrize("pairs,mask,want", [
    ("signature", None, True),
    ("signature", [True, True], True),      # all-true: as mask=None
    ("wrong_message", None, False),
    ("junk", None, False),
    ("junk", [True, True], False),
    ("junk", [False, False], True),         # masked out: the identity
], ids=["signature", "signature-all_lanes", "wrong_message", "junk",
        "junk-all_lanes", "junk-masked_out"])
def test_pairing_check_verifies_signature(pairs, mask, want):
    """Every case at two pairs: one Miller loop and one verdict program
    serve them all."""
    mask = None if mask is None else np.array(mask)
    assert bool(np.asarray(k.pairing_check_batch(*_check_pairs(pairs),
                                                 mask))) is want


@pytest.mark.parametrize("mask", [None, "lanes"])
def test_pairing_check_dispatches_two_programs_and_nothing_eager(mask):
    """At the CPU batches' pairing shape (the message lanes and the
    aggregate's) a check is two stage programs, the Miller loop and the
    verdict, with no eager operation: each top-level equation of its
    trace is one host dispatch."""
    from lighthouse_tpu.crypto.bls.tpu_backend import lane_options
    n = lane_options()[0] + 1
    fp = jax.ShapeDtypeStruct((n, bi.NLIMBS), np.int32)
    fp2 = jax.ShapeDtypeStruct((n, 2, bi.NLIMBS), np.int32)
    lanes = None if mask is None else np.arange(n) % 2 == 0
    jaxpr = jax.make_jaxpr(lambda *a: k.pairing_check_batch(*a, lanes))(
        fp, fp, fp2, fp2)
    assert [(e.primitive.name, e.params.get("name")) for e in jaxpr.eqns] \
        == [("jit", "miller_loop_batch"), ("jit", "pairing_verdict")]


def test_device_g2_decompress_and_subgroup():
    """Batched device decompression + psi subgroup check vs the oracle."""
    import numpy as np
    from lighthouse_tpu.crypto.bls12_381 import g2_compress
    from lighthouse_tpu.crypto.bls12_381 import sig as osig
    from lighthouse_tpu.crypto.bls12_381.curve import B_G2, G2Point, R
    from lighthouse_tpu.crypto.bls12_381.fields import Fp2
    pts = [osig.sign(100 + i, bytes([i]) * 32) for i in range(3)]
    xs, flags = [], []
    for p in pts:
        cb = g2_compress(p)
        xs += [int.from_bytes(cb[48:96], "big"),
               int.from_bytes(bytes([cb[0] & 0x1f]) + cb[1:48], "big")]
        flags.append(bool(cb[0] & 0x20))
    x = k.fp_encode(xs).reshape(3, 2, 32)
    y, ok = k.g2_decompress_batch(x, np.array(flags))
    assert bool(np.asarray(ok).all())
    yl = k.fp_decode(np.asarray(y))
    for i, p in enumerate(pts):
        _, Y = p.to_affine()
        assert (yl[2 * i], yl[2 * i + 1]) == (int(Y.c0), int(Y.c1))
    one2 = np.broadcast_to(k.FP2_ONE, (3, 2, 32))
    assert bool(np.asarray(
        k.g2_in_subgroup_batch(x, y, one2)).all())
    # an on-curve point OUTSIDE the subgroup must be rejected
    xx = 1
    while True:
        rhs = Fp2(xx, 0) * Fp2(xx, 0) * Fp2(xx, 0) + B_G2
        yy = rhs.sqrt()
        if yy is not None:
            break
        xx += 1
    assert not G2Point(Fp2(xx, 0), yy).mul(R).is_infinity()
    bx, by = k.fp2_encode([Fp2(xx, 0)]), k.fp2_encode([yy])
    bo = np.broadcast_to(k.FP2_ONE, (1, 2, 32))
    assert not bool(np.asarray(
        k.g2_in_subgroup_batch(bx, by, bo)).any())


def test_device_hash_to_g2_matches_oracle():
    """SSWU + isogeny + B-P cofactor on device == oracle hash_to_g2."""
    import numpy as np
    from lighthouse_tpu.crypto.bls12_381.hash_to_curve import DST_POP
    msgs = [b"", b"abc", b"\x00" * 32]
    x, y, z = k.hash_to_g2_batch(msgs, DST_POP)
    ax, ay = k.jacobian_to_affine_fp2(x, y, z)
    axl, ayl = k.fp_decode(np.asarray(ax)), k.fp_decode(np.asarray(ay))
    for i, m in enumerate(msgs):
        X, Y = hash_to_g2(m).to_affine()
        assert (axl[2 * i], axl[2 * i + 1], ayl[2 * i], ayl[2 * i + 1]) == \
            (int(X.c0), int(X.c1), int(Y.c0), int(Y.c1))


@pytest.mark.parametrize("mode", [1, 2])
def test_mxu_digit_modes_through_curve_ops(mode):
    """The LHTPU_BIGINT_MXU digit lowerings push exactly through the tower
    and curve layers (fp2 mul/inv, G1 scalar mul) — small programs, always
    run; the full pairing under mode 1 is the gated slow test below."""
    a = rand_fp2(4)
    b = rand_fp2(4)
    try:
        bi.set_mxu_mode(mode)
        prod = k.fp2_mul(k.fp2_encode(a), k.fp2_encode(b))
        inv = k.fp2_inv(k.fp2_encode(a))
        for i in range(4):
            want = a[i] * b[i]
            assert k.fp_decode(prod[i]) == [int(want.c0), int(want.c1)]
            winv = a[i].inv()
            assert k.fp_decode(inv[i]) == [int(winv.c0), int(winv.c1)]
        scalars = [5, 2**61 - 1]
        x, y = _encode_g1([G1_GENERATOR] * 2)
        z = np.broadcast_to(k.FP_ONE, (2, bi.NLIMBS))
        sx, sy, sz = k.g1_scalar_mul(x, y, z, k.scalars_to_bits(scalars, 64))
        ax, ay = k.jacobian_to_affine_fp(sx, sy, sz)
        for i, s in enumerate(scalars):
            want = G1_GENERATOR.mul(s).to_affine()
            assert k.fp_decode(ax[i])[0] == int(want[0])
            assert k.fp_decode(ay[i])[0] == int(want[1])
    finally:
        bi.set_mxu_mode(0)


def test_mxu_mode_full_pairing_slow():
    """Full pairing check under LHTPU_BIGINT_MXU=1 (gated: cold compiles of
    the Miller/final-exp programs take minutes on the CPU test backend)."""
    import os
    if not os.environ.get("LHTPU_SLOW_TESTS"):
        pytest.skip("full-pairing MXU-mode test (set LHTPU_SLOW_TESTS=1)")
    sk = keygen_interop(5)
    pk = sk_to_pk(sk)
    msg = b"\x77" * 32
    sig = sign(sk, msg)
    h = hash_to_g2(msg)
    try:
        bi.set_mxu_mode(1)
        px, py = _encode_g1([G1_GENERATOR.neg(), pk])
        qx, qy = _encode_g2([sig, h])
        assert bool(np.asarray(k.pairing_check_batch(px, py, qx, qy)))
        qx2, qy2 = _encode_g2([sig, hash_to_g2(b"\x78" * 32)])
        assert not bool(np.asarray(k.pairing_check_batch(px, py, qx2, qy2)))
    finally:
        bi.set_mxu_mode(0)
