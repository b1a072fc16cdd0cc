"""Device-resident incremental merkle tree — milhouse on TPU.

The reference keeps the BeaconState's big lists in `milhouse` persistent
trees with lazily-flushed tree-hash caches so `update_tree_hash_cache`
rehashes only dirty paths (/root/reference/consensus/types/src/
beacon_state.rs:2031-2046, Cargo.toml:180).  This module is the
TPU-native equivalent: every tree level lives in HBM as a u32[2^l, 8]
array and the whole root computation is ONE XLA program per tree shape —

- ``build``: leaves -> all levels + capped root, levels unrolled inside a
  single jit (no per-level dispatch, no per-level compile),
- ``update``: scatter R dirty leaves, walk the R dirty paths up the tree
  with gather -> batched hash64 -> scatter per level, then fold the
  static zero-subtree caps.  Steady-state work is O(R * depth) hashes
  instead of O(N) — sub-millisecond at R=1024 on a v5e chip.

Why one fused program matters here: a TPU compile costs seconds per
program.  The round-1 design jitted each level separately — ~23
shape-specialized compiles per tree.  With this layout a full
1M-validator tree costs 2 compiles total.

Optional ``pre_levels``: the validator registry's leaf is itself the
root of a tiny 8-chunk subtree (7 hashes per validator).  Passing
``pre_levels=3`` folds those levels inside the same program, so a
registry update moves only the dirty validators' field chunks
host->device and everything else stays on device.

Trees are updated functionally (new level arrays) unless the caller
owns the buffers exclusively, in which case the donating variant
aliases them in place (64 MB of levels at 1M validators — donation
avoids a full copy per block).
"""
from __future__ import annotations

import functools

import numpy as np

from .sha256 import (
    ZERO_HASH_WORDS,
    hash64,
    jnp_asarray,
)


def _jnp():
    import jax.numpy as jnp
    return jnp


def _fold_pre(nodes, pre_levels, pk_blocks):
    """Fold per-leaf subtrees: optionally hash 64-byte pubkey blocks into
    chunk 0 of each leaf's chunk group, then ``pre_levels`` pair folds."""
    if pk_blocks is not None:
        unit = 1 << pre_levels
        chunks = nodes.reshape(-1, unit, 8)
        chunks = chunks.at[:, 0].set(hash64(pk_blocks))
        nodes = chunks.reshape(-1, 8)
    for _ in range(pre_levels):
        nodes = hash64(nodes.reshape(nodes.shape[0] // 2, 16))
    return nodes


def _cap_root(root, dense_depth, limit_depth):
    if dense_depth >= limit_depth:
        return root
    jnp = _jnp()
    from .sha256 import _fold_zero_caps
    return _fold_zero_caps(
        root, jnp.asarray(ZERO_HASH_WORDS[dense_depth:limit_depth]))


@functools.lru_cache(maxsize=None)
def _build_fn(dense_depth: int, limit_depth: int, pre_levels: int,
              with_pk: bool):
    """One jitted program: (leaves[, pk_blocks], n_live) -> (levels, root).

    ``n_live`` (traced scalar — no recompile as the registry grows):
    leaves at index >= n_live are list padding and must be ZERO chunks at
    the post-fold level (SSZ pads the list's leaf level with zero chunks,
    not with roots of zero subtrees) — only relevant when pre_levels > 0
    folds happen inside the program.
    """
    import jax
    import jax.numpy as jnp

    def levels_and_root(leaves, pk_blocks, n_live):
        nodes = _fold_pre(leaves, pre_levels, pk_blocks)
        if pre_levels > 0:
            live = (jnp.arange(nodes.shape[0]) < n_live)[:, None]
            nodes = jnp.where(live, nodes, jnp.uint32(0))
        levels = [nodes]
        for _ in range(dense_depth):
            nodes = hash64(nodes.reshape(nodes.shape[0] // 2, 16))
            levels.append(nodes)
        root = _cap_root(levels[-1][0], dense_depth, limit_depth)
        return tuple(levels), root

    # named, so a device trace names the program
    if with_pk:
        def tree_build(leaves, pk_blocks, n_live):
            return levels_and_root(leaves, pk_blocks, n_live)
    else:
        def tree_build(leaves, n_live):
            return levels_and_root(leaves, None, n_live)
    return jax.jit(tree_build)


@functools.lru_cache(maxsize=None)
def _update_fn(dense_depth: int, limit_depth: int, pre_levels: int,
               with_pk: bool, donate: bool):
    """One jitted program: (levels, rows, new_pre_leaves[, pk_blocks])
    -> (levels, root).

    rows: i32[R] dirty leaf indices (duplicates allowed only with
    identical leaf words — distinct values for one row would race in the
    scatter), new_leaves: u32[R * 2**pre_levels, 8] replacement words.
    """
    import jax

    def tree_update(levels, rows, new_leaves, pk_blocks=None):
        nodes = _fold_pre(new_leaves, pre_levels, pk_blocks)
        levels = list(levels)
        levels[0] = levels[0].at[rows].set(nodes)
        idx = rows
        for lvl in range(dense_depth):
            parent = idx >> 1
            pairs = levels[lvl].reshape(-1, 16)[parent]   # [R, 16]
            levels[lvl + 1] = levels[lvl + 1].at[parent].set(hash64(pairs))
            idx = parent
        root = _cap_root(levels[-1][0], dense_depth, limit_depth)
        return tuple(levels), root

    # named, so a device trace names the program
    return jax.jit(tree_update, donate_argnums=(0,) if donate else ())


class DeviceTree:
    """Incremental merkle tree over ``n_leaves`` chunk leaves, padded to
    a dense power-of-two subtree and zero-capped to ``limit`` leaves.

    With ``pre_levels=p`` the public leaf unit is a 2^p-chunk subtree:
    ``build``/``update`` take ``2^p`` chunk words per leaf and fold them
    on device.
    """

    def __init__(self, n_leaves: int, limit: int, pre_levels: int = 0,
                 with_pk: bool = False):
        self.n = int(n_leaves)
        self.limit_depth = max(0, (int(limit) - 1).bit_length())
        dense = 1 if self.n <= 1 else 1 << (self.n - 1).bit_length()
        self.dense_depth = (dense - 1).bit_length()
        self.dense = dense
        self.pre_levels = int(pre_levels)
        self.with_pk = bool(with_pk)
        self.levels: tuple | None = None
        self.root_words = None
        self._shared = False

    # -- sharing (structural copies must not see donated buffers) --------
    def share(self) -> "DeviceTree":
        """A second owner of the same immutable level buffers.  Both
        owners are flagged so their next update runs the non-donating
        program (donation would free buffers the other still needs)."""
        other = DeviceTree(self.n, 1, self.pre_levels, self.with_pk)
        other.limit_depth = self.limit_depth
        other.dense_depth = self.dense_depth
        other.dense = self.dense
        other.levels = self.levels
        other.root_words = self.root_words
        self._shared = True
        other._shared = True
        return other

    def _pad_unit(self, words, count: int, want: int):
        """Zero-pad a [count * unit, 8] word array to [want * unit, 8]."""
        jnp = _jnp()
        unit = 1 << self.pre_levels
        arr = jnp_asarray(words)
        if count != want:
            pad = jnp.zeros(((want - count) * unit, 8), jnp.uint32)
            arr = jnp.concatenate([arr, pad], axis=0)
        return arr

    def build(self, pre_leaf_words, pk_blocks=None) -> None:
        """pre_leaf_words: u32[n * 2**pre_levels, 8] (host or device);
        short arrays are zero-padded to the dense width.  With
        ``with_pk``, pk_blocks u32[n, 16] hashes into chunk 0 of each
        leaf's chunk group on device."""
        jnp = _jnp()
        leaves = self._pad_unit(pre_leaf_words, self.n, self.dense)
        n_live = jnp.int32(self.n)
        fn = _build_fn(self.dense_depth, self.limit_depth, self.pre_levels,
                       self.with_pk)
        if self.with_pk:
            pk = jnp_asarray(pk_blocks)
            if self.n != self.dense:
                pad = jnp.zeros((self.dense - self.n, 16), jnp.uint32)
                pk = jnp.concatenate([pk, pad], axis=0)
            self.levels, self.root_words = fn(leaves, pk, n_live)
        else:
            self.levels, self.root_words = fn(leaves, n_live)
        self._shared = False

    def update(self, rows: np.ndarray, pre_leaf_words,
               pk_blocks=None) -> None:
        """rows: leaf indices; pre_leaf_words: u32[R * 2**pre_levels, 8].

        Duplicate rows are allowed only when they carry identical leaf
        words (the internal power-of-two padding repeats row[0]); distinct
        values for the same row would make the scatter nondeterministic.
        An empty ``rows`` is a no-op.
        """
        jnp = _jnp()
        rows = np.asarray(rows, dtype=np.int32)
        r = len(rows)
        if r == 0:
            return
        target = 1 << (r - 1).bit_length() if r > 1 else 1
        words = np.asarray(pre_leaf_words)
        if target != r:
            unit = 1 << self.pre_levels
            rows = np.concatenate([rows, np.full(target - r, rows[0],
                                                 dtype=np.int32)])
            words = np.concatenate(
                [words, np.tile(words[:unit], (target - r, 1))])
            if pk_blocks is not None:
                pk_blocks = np.concatenate(
                    [pk_blocks, np.tile(pk_blocks[:1], (target - r, 1))])
        fn = _update_fn(self.dense_depth, self.limit_depth, self.pre_levels,
                        self.with_pk, donate=not self._shared)
        args = [self.levels, jnp.asarray(rows), jnp_asarray(words)]
        if self.with_pk:
            args.append(jnp_asarray(pk_blocks))
        self.levels, self.root_words = fn(*args)
        self._shared = False

    def root(self) -> bytes:
        from .sha256 import words_to_chunks
        return words_to_chunks(np.asarray(self.root_words))
