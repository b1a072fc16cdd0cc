"""Mesh-sharded merkleization.

The 1M-validator hash tree splits naturally: each device merkleizes its
contiguous leaf shard (a complete subtree, since shards are power-of-two
sized), then the per-device subtree roots are all-gathered over ICI and the
small top tree is computed replicated. One collective of n_devices * 32 bytes
per tree — pure ICI, no DCN.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from ..obs import device
from ..obs.roofline import track_roofline
from ..ops.sha256 import hash_pairs, merkleize_dense


def _subtree_then_top(local_leaves: jax.Array, subtree_depth: int,
                      top_depth: int, axis: str) -> jax.Array:
    """Runs inside shard_map: local subtree root -> all_gather -> top tree."""
    root = merkleize_dense(local_leaves, subtree_depth)  # [8]
    roots = jax.lax.all_gather(root, axis)                  # [n, 8]
    top = roots
    for _ in range(top_depth):
        top = hash_pairs(top)
    return top[0:1]


@functools.lru_cache(maxsize=None)
def _sharded_merkleize_fn(mesh: Mesh, subtree_depth: int, top_depth: int,
                          axis: str):
    """Memoized jitted program per (mesh, depths): a fresh
    jit(shard_map(...)) per call would re-trace every call
    (graftlint: recompile-hazard).  track_roofline() makes any leak past
    the memoization an observable jax_compile_total increment and scores
    the program's cost_analysis against the platform peak (graftgauge)."""
    fn = shard_map(
        functools.partial(_subtree_then_top, subtree_depth=subtree_depth,
                          top_depth=top_depth, axis=axis),
        mesh=mesh,
        in_specs=(P(axis, None),),
        out_specs=P(axis, None),
    )
    return track_roofline(
        f"merkle.subtree_d{subtree_depth}_t{top_depth}", jax.jit(fn))


def sharded_merkleize(mesh: Mesh, leaves: jax.Array,
                      axis: str = "batch") -> jax.Array:
    """Merkleize u32[N, 8] leaves sharded over the mesh (N and N/n_devices
    must be powers of two). Returns the root u32[8] (replicated)."""
    n = leaves.shape[0]
    n_dev = mesh.shape[axis]
    assert n % n_dev == 0
    local = n // n_dev
    assert local & (local - 1) == 0, "leaf shard must be a power of two"
    subtree_depth = (local - 1).bit_length()
    top_depth = (n_dev - 1).bit_length()

    # each shard returns the (identical) root; take shard 0's copy
    with device.hbm_watermark("parallel.merkle"):
        device.attribute("parallel.merkle", "leaves", leaves)
        out = _sharded_merkleize_fn(mesh, subtree_depth, top_depth,
                                    axis)(leaves.reshape(n, 8))
    return out[0]


def sharded_state_root_step(mesh: Mesh, validator_leaves: jax.Array,
                            balance_leaves: jax.Array,
                            axis: str = "batch"):
    """The sharded 'full step' over the two dominant BeaconState columns:
    validators (8 chunks each, pre-flattened) + balances, each merkleized
    across the mesh; returns (validators_root, balances_root)."""
    v_root = sharded_merkleize(mesh, validator_leaves, axis)
    b_root = sharded_merkleize(mesh, balance_leaves, axis)
    return v_root, b_root
