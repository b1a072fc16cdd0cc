"""Plain SSZ merkleization (consensus-specs ``ssz/simple-serialize.md``).

The benchmark's own hasher: ``hashlib`` SHA-256 and numpy packing, no
code of the system under test.  Values are plain Python: ints, bytes and
numpy arrays.
"""
from __future__ import annotations

from hashlib import sha256

import numpy as np

ZERO_HASHES = [b"\x00" * 32]
for _ in range(64):
    ZERO_HASHES.append(sha256(ZERO_HASHES[-1] * 2).digest())


def depth_of(limit_chunks: int) -> int:
    return max(limit_chunks - 1, 0).bit_length()


def merkleize(chunks: bytes, limit_chunks: int) -> bytes:
    """Root of ``chunks`` (concatenated 32-byte chunks) padded with zero
    chunks to ``limit_chunks`` leaves, rounded up to a power of two."""
    if len(chunks) % 32 or len(chunks) // 32 > max(limit_chunks, 1):
        raise ValueError("chunks exceed the limit or are not whole")
    depth = depth_of(limit_chunks)
    if not chunks:
        return ZERO_HASHES[depth]
    layer = chunks
    for d in range(depth):
        if (len(layer) // 32) % 2:
            layer += ZERO_HASHES[d]
        layer = b"".join([sha256(layer[i:i + 64]).digest()
                          for i in range(0, len(layer), 64)])
    return layer


def mix_in_length(root: bytes, length: int) -> bytes:
    return sha256(root + length.to_bytes(32, "little")).digest()


def pack(data: bytes) -> bytes:
    """Right-pad serialized basic values to whole chunks."""
    return data + b"\x00" * (-len(data) % 32)


def uint64(v: int) -> bytes:
    return int(v).to_bytes(8, "little") + b"\x00" * 24


def container(*field_roots: bytes) -> bytes:
    return merkleize(b"".join(field_roots), len(field_roots))


def bytes_vector(data: bytes) -> bytes:
    """Root of a ``ByteVector`` (Bytes4, Bytes48, Bytes96...)."""
    return merkleize(pack(data), (len(data) + 31) // 32)


def uint64_list(values: np.ndarray, limit: int) -> bytes:
    data = pack(np.ascontiguousarray(values, dtype="<u8").tobytes())
    return mix_in_length(merkleize(data, (limit * 8 + 31) // 32),
                         len(values))


def uint64_vector(values: np.ndarray) -> bytes:
    data = pack(np.ascontiguousarray(values, dtype="<u8").tobytes())
    return merkleize(data, (len(values) * 8 + 31) // 32)


def uint8_list(values: np.ndarray, limit: int) -> bytes:
    data = pack(np.ascontiguousarray(values, dtype=np.uint8).tobytes())
    return mix_in_length(merkleize(data, (limit + 31) // 32), len(values))


def roots_vector(rows: np.ndarray) -> bytes:
    return merkleize(np.ascontiguousarray(rows, np.uint8).tobytes(),
                     len(rows))


def list_of_roots(roots: list[bytes], limit: int) -> bytes:
    return mix_in_length(merkleize(b"".join(roots), limit), len(roots))


def bitvector(bits) -> bytes:
    return merkleize(pack(_bits_bytes(bits)), (len(bits) + 255) // 256)


def bitlist(bits, limit: int) -> bytes:
    return mix_in_length(merkleize(pack(_bits_bytes(bits)),
                                   (limit + 255) // 256), len(bits))


def _bits_bytes(bits) -> bytes:
    if not len(bits):
        return b""
    return np.packbits(np.asarray(bits, dtype=np.uint8),
                       bitorder="little").tobytes()


def hash_pairs(buf: bytes) -> bytes:
    """One tree level: SHA-256 of each 64-byte pair of ``buf``."""
    return b"".join([sha256(buf[i:i + 64]).digest()
                     for i in range(0, len(buf), 64)])
