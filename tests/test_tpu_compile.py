"""Compile the main path's device programs for one described v5e chip.

Nothing runs: the TPU compiler installed here compiles for a chip that
is described, not attached, and refuses what the chip would refuse
(tiling, fast memory, a program that does not fit HBM).  The topology is
described inside a fixture, never at import time: one test worker loads
the TPU library and holds it until it exits.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from lighthouse_tpu.ops import bls12_381 as k
from lighthouse_tpu.ops import bigint as bi
from lighthouse_tpu.ops.merkle_tree import _build_fn, _update_fn

#: one v5e chip's HBM (Google Cloud documentation, "TPU v5e": 16 GB)
HBM_BYTES = 16 * 10**9
DEPTH = 20                       # 2^20 validators
LIMIT_DEPTH = 40                 # VALIDATOR_REGISTRY_LIMIT = 2^40


@pytest.fixture(scope="module")
def one_chip():
    """A described v5e chip, with the persistent compile cache off (an
    entry compiled for a described chip cannot be read back here)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)
    compilation_cache.reset_cache()
    if log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)
    else:
        os.environ["TPU_LOG_DIR"] = log_dir


def _compile_fits(fn, *args):
    m = fn.lower(*args).compile().memory_analysis()
    total = (m.temp_size_in_bytes + m.argument_size_in_bytes
             + m.output_size_in_bytes + m.generated_code_size_in_bytes)
    assert total < HBM_BYTES, f"{total} bytes do not fit one chip"
    return m


def test_registry_tree_build_1m(one_chip):
    u32 = jnp.uint32
    m = _compile_fits(
        _build_fn(DEPTH, LIMIT_DEPTH, 3, True),
        jax.ShapeDtypeStruct((8 << DEPTH, 8), u32, sharding=one_chip),
        jax.ShapeDtypeStruct((1 << DEPTH, 16), u32, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip))
    assert m.argument_size_in_bytes >= (8 << DEPTH) * 32


def test_registry_tree_update_1024_rows(one_chip):
    u32 = jnp.uint32
    levels = tuple(jax.ShapeDtypeStruct((1 << (DEPTH - i), 8), u32,
                                        sharding=one_chip)
                   for i in range(DEPTH + 1))
    _compile_fits(
        _update_fn(DEPTH, LIMIT_DEPTH, 3, True, True), levels,
        jax.ShapeDtypeStruct((1024,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((1024 * 8, 8), u32, sharding=one_chip),
        jax.ShapeDtypeStruct((1024, 16), u32, sharding=one_chip))


def test_balances_tree_build_1m(one_chip):
    # 2^20 uint64 balances pack 4 per chunk: 2^18 chunk leaves under a
    # limit of 2^40 * 8 / 32 chunks
    _compile_fits(
        _build_fn(DEPTH - 2, LIMIT_DEPTH - 2, 0, False),
        jax.ShapeDtypeStruct((1 << (DEPTH - 2), 8), jnp.uint32,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip))


def test_bls_g1_scalar_mul_128_lanes(one_chip):
    fp = jax.ShapeDtypeStruct((128, bi.NLIMBS), jnp.int32, sharding=one_chip)
    _compile_fits(k.g1_scalar_mul_jit, fp, fp, fp,
                  jax.ShapeDtypeStruct((128, 64), jnp.int32,
                                       sharding=one_chip))


def test_bls_subgroup_check_branches_on_the_bit_128_lanes(one_chip):
    """The chip's compiler keeps the |u| ladder's addition under one
    branch inside the loop: no pass turns it back into a select."""
    fp2 = jax.ShapeDtypeStruct((128, 2, bi.NLIMBS), jnp.int32,
                               sharding=one_chip)
    compiled = k.g2_in_subgroup_batch.lower(fp2, fp2, fp2).compile()
    branches = [line for line in compiled.as_text().splitlines()
                if " conditional(" in line]
    assert len(branches) == 1, branches
    assert "/while/body/" in branches[0]


def test_bls_pubkey_table_gather_and_write_2_20_rows(one_chip):
    """The device pubkey table at 2^20 validators (268 MB): one block's
    key lanes gathered from it, one block of rows written to it."""
    from lighthouse_tpu.crypto.bls.pubkey_table import _write_block
    table = jax.ShapeDtypeStruct((1 << DEPTH, bi.NLIMBS), jnp.int32,
                                 sharding=one_chip)
    rows = jax.ShapeDtypeStruct((16, 2304), jnp.int32, sharding=one_chip)
    m = _compile_fits(k.g1_table_gather, table, table, rows)
    assert m.argument_size_in_bytes >= 2 * (1 << DEPTH) * bi.NLIMBS * 4
    block = jax.ShapeDtypeStruct((65536, bi.NLIMBS), jnp.int32,
                                 sharding=one_chip)
    _compile_fits(_write_block, table, table, block, block,
                  jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip))


def test_bls_bucket_sum_one_block_of_keys(one_chip):
    """The per-set pubkey sums of a full block at 2^20 validators: 16 x
    2,304 key lanes onto 128 set lanes (``tpu_backend.key_shape``)."""
    def shape(*dims, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    keys = shape(16, 2304, bi.NLIMBS)
    _compile_fits(k.g1_bucket_sum, keys, keys,
                  shape(16, 2304, dtype=jnp.bool_), shape(2304),
                  shape(128), shape(128, dtype=jnp.bool_))
