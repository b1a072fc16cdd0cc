"""The tpu backend's validator pubkey table: every known pubkey as one row
of affine G1 coordinates in Montgomery limbs on the device, the
``ValidatorPubkeyCache`` analog (beacon_chain/src/validator_pubkey_cache.rs)
held where the aggregation of a set's keys runs.

A bulk load at start-up (:meth:`PubkeyTable.load`) decompresses and
validates the registry's compressed keys (KeyValidate: decodes, not the
identity, in G1) in the native library's threads, converts them to limbs
with numpy and writes them to the device in blocks of :func:`block_rows`
rows.  A key first named by a signature set later is added the same way,
as a deposit adds a validator.  A key that fails validation gets no row:
a set naming it verifies False.  Loading and growing are the
``bls_pk_table`` host span; the table's row count is the
``bls_pubkey_table_rows`` gauge.
"""
from __future__ import annotations

import ctypes as C
import os
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np

from ...obs import tracing
from ...ops import bigint as bi


def block_rows() -> int:
    """Rows written to the device per write (one compiled shape): 65,536
    on accelerators, 256 on the XLA CPU fallback."""
    return 65536 if jax.default_backend() != "cpu" else 256


def be48_to_limbs(b: np.ndarray) -> np.ndarray:
    """(n, 48) big-endian field elements -> (n, 32) 12-bit limbs, least
    significant first (``ops.bigint``'s layout, not Montgomery)."""
    le = b[:, ::-1].astype(np.int32).reshape(len(b), 16, 3)
    v = le[..., 0] | (le[..., 1] << 8) | (le[..., 2] << 16)
    return np.stack([v & bi.LIMB_MASK, v >> bi.LIMB_BITS],
                    axis=-1).reshape(len(b), bi.NLIMBS)


@jax.jit
def _write_block(tx, ty, bx, by, start):
    """Rows ``start..start+len(bx)`` of the table set to ``bx``, ``by``
    (plain limbs) in Montgomery form.  Not donated: a verify on another
    thread may still read the arrays it replaces."""
    put = jax.lax.dynamic_update_slice
    return (put(tx, bi.mont_from_int_limbs(bx), (start, 0)),
            put(ty, bi.mont_from_int_limbs(by), (start, 0)))


class PubkeyTable:
    """Compressed pubkey -> row, and the rows on the device.  Rows are
    published after the arrays that hold them, so a reader that finds a
    row reads arrays holding it."""

    def __init__(self):
        self._lock = threading.Lock()
        self.rows: dict[bytes, int] = {}
        self.invalid: set[bytes] = set()
        self.size = 0
        self._xy = None                 # ([capacity, NLIMBS] int32,) * 2

    def arrays(self):
        """The device arrays (x, y), allocated on first use."""
        with self._lock:
            if self._xy is None:
                self._xy = _grown(None, block_rows())
            return self._xy

    def load(self, pubkeys) -> int:
        """Add each key of ``pubkeys`` (a list of 48-byte keys, or an
        (n, 48) uint8 array such as a registry's pubkey column) that has
        no row and has not failed validation; returns the rows added."""
        if isinstance(pubkeys, list):
            keys = pubkeys
        else:                           # a registry's (n, 48) column
            buf = np.ascontiguousarray(pubkeys, np.uint8).tobytes()
            keys = [buf[i:i + 48] for i in range(0, len(buf), 48)]
        with tracing.span("bls_pk_table"), self._lock:
            rows, invalid = self.rows, self.invalid
            fresh = list(dict.fromkeys(
                k for k in keys if k not in rows and k not in invalid))
            valid, xy, bad = _validate(fresh)
            self.invalid = invalid | bad
            m = len(valid)
            if m:
                block = block_rows()
                blocks = -(-m // block)
                tx, ty = _grown(self._xy, self.size + blocks * block)
                lx = np.zeros((blocks * block, bi.NLIMBS), np.int32)
                ly = np.zeros_like(lx)
                lx[:m] = be48_to_limbs(xy[:, :48])
                ly[:m] = be48_to_limbs(xy[:, 48:])
                for b in range(blocks):
                    part = slice(b * block, (b + 1) * block)
                    tx, ty = _write_block(tx, ty, lx[part], ly[part],
                                          jnp.int32(self.size + b * block))
                self._xy = (tx, ty)
                rows.update(zip(valid, range(self.size, self.size + m)))
                self.size = self.size + m
        md = sys.modules.get("lighthouse_tpu.api.metrics_defs")
        if md is not None:
            md.gauge("bls_pubkey_table_rows", self.size)
        return m

    def rows_of(self, pubkeys: list) -> np.ndarray | None:
        """The rows of ``pubkeys``, adding keys the table does not hold;
        None where a key fails validation."""
        get = self.rows.get
        out = [get(k) for k in pubkeys]
        if None in out:
            self.load([k for k, r in zip(pubkeys, out) if r is None])
            out = [get(k) for k in pubkeys]
            if None in out:
                return None
        return np.asarray(out, np.int32)


def loaded_shape(rows: int) -> tuple:
    """The shapes of :meth:`PubkeyTable.arrays` once a new table has
    loaded ``rows`` keys, for compiling its readers before the load."""
    block = block_rows()
    cap = max(1, -(-rows // block)) * block
    return (jax.ShapeDtypeStruct((cap, bi.NLIMBS), jnp.int32),) * 2


def _grown(xy, need: int):
    """Table arrays with room for ``need`` rows: whole blocks, a quarter
    more than before when they grow (a new capacity compiles the gather
    anew)."""
    cap = 0 if xy is None else xy[0].shape[0]
    if need <= cap:
        return xy
    block = block_rows()
    new = -(-max(need, cap + cap // 4) // block) * block
    pad = jnp.zeros((new - cap, bi.NLIMBS), jnp.int32)
    if xy is None:
        return pad, pad + 0
    return tuple(jnp.concatenate([a, pad]) for a in xy)


def _validate(keys: list) -> tuple[list, np.ndarray, set]:
    """KeyValidate ``keys`` in the native library's threads: the valid
    keys, their affine x || y (48 bytes big-endian each) and the set of
    invalid keys."""
    from .cpp_backend import get_lib
    bad = {k for k in keys if len(k) != 48}
    keys = [k for k in keys if k not in bad]
    n = len(keys)
    xy = np.empty((n, 96), np.uint8)
    ok = np.empty(n, np.uint8)
    if n:
        get_lib().bls_g1_decompress_batch(
            n, C.c_char_p(b"".join(keys)), xy.ctypes.data, ok.ctypes.data,
            len(os.sched_getaffinity(0)))
    good = ok.astype(bool)
    bad.update(k for k, g in zip(keys, good) if not g)
    return [k for k, g in zip(keys, good) if g], xy[good], bad
