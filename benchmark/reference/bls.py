"""Plain BLS12-381 signatures: the yardstick of the gossip cell.

``bls12_381.cpp`` is a frozen copy of the system's C++ host backend (a
6x64-limb Montgomery implementation, byte-compatible with blst), plus a
signer that takes the message already hashed to G2.  It is built on
first use into the benchmark's ignored cache directory, keyed by its
source and flags, and shares no code with the device kernels under test.
"""
from __future__ import annotations

import ctypes as C
import hashlib
import os
import subprocess
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

DST = b"BLS_SIG_BLS12381G2_XMD:SHA-256_SSWU_RO_POP_"
P = int("1a0111ea397fe69a4b1ba7b6434bacd764774b84f38512bf6730d2a0f6b0f624"
        "1eabfffeb153ffffb9feffffffffaaab", 16)
R = int("73eda753299d7d483339d80809a1d80553bda402fffe5bfeffffffff00000001",
        16)
_SRC = Path(__file__).with_name("bls12_381.cpp")
_FLAGS = ("-O3", "-std=c++17", "-shared", "-fPIC", "-pthread")
_lib = None


def _build(cache: Path) -> Path:
    key = hashlib.sha256(_SRC.read_bytes() + " ".join(_FLAGS).encode()
                         ).hexdigest()[:16]
    out = cache / f"libbenchbls-{key}.so"
    if not out.exists():
        cache.mkdir(parents=True, exist_ok=True)
        tmp = out.with_name(f"{out.name}.{os.getpid()}.tmp")
        subprocess.run(["g++", *_FLAGS, "-o", str(tmp), str(_SRC)],
                       check=True, capture_output=True)
        os.replace(tmp, out)
    return out


def lib(cache: Path):
    """The reference library, built into ``cache`` if need be."""
    global _lib
    if _lib is None:
        _lib = C.CDLL(str(_build(cache)))
        u32p, u64p = C.POINTER(C.c_uint32), C.POINTER(C.c_uint64)
        _lib.bls_selftest.argtypes = []
        _lib.bls_selftest.restype = C.c_int
        _lib.bls_sk_to_pk.argtypes = [C.c_char_p, C.c_char_p]
        _lib.bls_sk_to_pk.restype = C.c_int
        _lib.bls_hash_to_g2.argtypes = [C.c_char_p, C.c_size_t, C.c_char_p,
                                        C.c_size_t, C.c_char_p]
        _lib.bls_hash_to_g2.restype = C.c_int
        _lib.bench_sign_hashed.argtypes = [C.c_char_p, C.c_char_p,
                                           C.c_char_p]
        _lib.bench_sign_hashed.restype = C.c_int
        _lib.bls_verify_signature_sets.restype = C.c_int
        _lib.bls_verify_signature_sets.argtypes = [
            C.c_size_t, C.c_char_p, C.c_char_p, u32p, C.c_char_p, u32p,
            C.c_char_p, C.c_size_t, u64p]
        if _lib.bls_selftest() != 0:
            raise RuntimeError("reference BLS library failed its self-test")
    return _lib


def sk_to_pk(sk: int) -> bytes:
    out = C.create_string_buffer(48)
    _lib.bls_sk_to_pk(sk.to_bytes(32, "big"), out)
    return out.raw


def hash_to_g2(msg: bytes) -> bytes:
    out = C.create_string_buffer(96)
    _lib.bls_hash_to_g2(msg, len(msg), DST, len(DST), out)
    return out.raw


def sign_hashed(h96: bytes, sk: int) -> bytes:
    out = C.create_string_buffer(96)
    if _lib.bench_sign_hashed(h96, sk.to_bytes(32, "big"), out):
        raise ValueError("message point does not decompress")
    return out.raw


def g1_affine(pk: bytes) -> tuple[int, int]:
    """Affine (x, y) of a compressed G1 point known to be valid."""
    x = int.from_bytes(bytes([pk[0] & 0x1F]) + pk[1:], "big")
    y = pow((x * x * x + 4) % P, (P + 1) // 4, P)
    if (y > (P - 1) // 2) != bool(pk[0] & 0x20):
        y = P - y
    return x, y


def verify_sets(sets: list[tuple[bytes, bytes, bytes]], rands: list[int],
                threads: int) -> bool:
    """Batch verification of (signature, pubkey, message) sets, split
    into ``threads`` chunks verified in parallel: true when every chunk
    verifies."""
    step = -(-len(sets) // threads)

    def one(lo: int) -> bool:
        part = sets[lo:lo + step]
        n = len(part)
        return _lib.bls_verify_signature_sets(
            n, b"".join(s[0] for s in part), b"".join(s[1] for s in part),
            (C.c_uint32 * n)(*[1] * n), b"".join(s[2] for s in part),
            (C.c_uint32 * n)(*[len(s[2]) for s in part]), DST, len(DST),
            (C.c_uint64 * n)(*rands[lo:lo + step])) == 1

    with ThreadPoolExecutor(threads) as pool:
        return all(pool.map(one, range(0, len(sets), step)))
