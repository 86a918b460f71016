"""Compile every main-path Pallas kernel for a described TPU v5e.

Nothing runs: each case traces a kernel through ``repro.kernels.ops``
with ``impl="pallas"`` at the TPC-H SF1 widths the served path uses, with
x64 on as the Weld runtime sets it, and compiles it for one chip of a
``v5e:2x2`` topology that the TPU compiler describes without a device.
The compiled HLO must carry the Mosaic kernel (``tpu_custom_call``).
This catches what interpret mode cannot: tilings Mosaic refuses, 64-bit
values it cannot lower, and scratch that does not fit.

The topology is described inside a fixture, never at import: only one
process may hold the TPU library at a time.
"""
import os

import jax
import jax.numpy as jnp
import pytest

import repro.core.runtime  # noqa: F401  (x64 on, as in the program)
from repro.kernels import hash_table, ops

#: TPC-H SF1 table widths: lineitem, supplier, partsupp rows.
LINEITEM, SUPPLIER, PARTSUPP = 6_001_215, 10_000, 800_000
#: distinct ship dates 1992-01-01..1998-12-31 (the dense group-by key).
SHIP_DAYS = 2_557


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile is written to the persistent cache but can
    # never be read back without the chip: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _f32(n):
    return (n,), jnp.float32


def _i32(n):
    return (n,), jnp.int32


#: kernel -> (call through kops with impl="pallas", operand shapes)
CASES = {
    "filter_reduce_sum": (
        lambda x, p: ops.filter_reduce_sum(x, p, impl="pallas"),
        [_f32(LINEITEM), ((LINEITEM,), jnp.bool_)]),
    "filter_reduce_sum_i32": (
        lambda x, p: ops.filter_reduce_sum(x, p, impl="pallas"),
        [_i32(LINEITEM), ((LINEITEM,), jnp.bool_)]),
    "filter_reduce_sum_multi": (
        lambda v, p: ops.filter_reduce_sum_multi(v, p, impl="pallas"),
        [((4, LINEITEM), jnp.float32), ((LINEITEM,), jnp.bool_)]),
    "segment_sum": (
        lambda s, v: ops.segment_sum(s, v, SHIP_DAYS, impl="pallas"),
        [_i32(LINEITEM), _f32(LINEITEM)]),
    "segment_sum_vectors": (
        lambda s, v: ops.segment_sum_vectors(s, v, SHIP_DAYS,
                                             impl="pallas"),
        [_i32(LINEITEM), ((LINEITEM, 2), jnp.float32)]),
    # Q1's exact group route: 6 dense keys, three narrow values and two
    # int64 ones (the discounted price and the charge)
    "group_sum_exact": (
        lambda k, a, b, c, d, e: ops.group_sum_exact(
            k, [a, b, c, d, e], 6, impl="pallas"),
        [_i32(LINEITEM)] * 4 + [((LINEITEM,), jnp.int64)] * 2),
    "map_elementwise": (
        lambda a, b: ops.map_elementwise(lambda x, y: x * (1.0 - y) + x,
                                         [a, b], impl="pallas"),
        [_f32(LINEITEM), _f32(LINEITEM)]),
    "hash_to_slot": (
        lambda k: ops.hash_to_slot(k, hash_table.table_size(SUPPLIER),
                                   impl="pallas"),
        [_i32(SUPPLIER)]),
    "hash_to_slot_max_cap": (
        lambda k: ops.hash_to_slot(
            k, hash_table.table_size(hash_table.MAX_CAP), impl="pallas"),
        [_i32(PARTSUPP)]),
    "dict_probe": (
        lambda t, c, q: ops.dict_probe(t, c, q, impl="pallas"),
        [_i32(SUPPLIER), ((), jnp.int64), _i32(LINEITEM)]),
    "group_build": (
        lambda k: ops.group_build(k, SUPPLIER, impl="pallas"),
        [_i32(PARTSUPP)]),
    "group_probe": (
        lambda t, o, c, q: ops.group_probe(t, o, c, q, impl="pallas"),
        [_i32(SUPPLIER), _i32(SUPPLIER + 1), ((), jnp.int64),
         _i32(SUPPLIER)]),
}


@pytest.mark.parametrize("kernel", sorted(CASES))
def test_kernel_compiles_for_v5e(kernel, one_chip):
    assert jax.config.jax_enable_x64
    fn, shapes = CASES[kernel]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
