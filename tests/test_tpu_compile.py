"""Compile rehearsals of the stack kernels for a described TPU v5e.

No chip is needed: the TPU compiler compiles for a ``v5e:2x2`` topology
that is described, not attached.  The compiles catch what interpret mode
cannot — Mosaic's block-shape tiling rules, scalar-memory dtypes — at the
shapes NUTS gives the kernels: depth 14 (``max_tree_depth=10``), 1024
chains, and ``F`` = 1 (scalars), 2 (uint32 PRNG keys), 100 (positions).

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every pytest worker imports every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.stack_ops import kernel

DEPTH, LANES = 14, 1024
WIDTHS = [(1, jnp.float32), (2, jnp.uint32), (100, jnp.float32)]
WIDTH_IDS = [f"F{f}-{jnp.dtype(d).name}" for f, d in WIDTHS]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes) -> str:
    # Compiles for a described chip are written to the persistent cache
    # but cannot be read back without one; keep the cache out of it.
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return jax.jit(fn).lower(*shapes).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("f,dtype", WIDTHS, ids=WIDTH_IDS)
def test_masked_push_compiles(one_chip, f, dtype):
    text = _compiled_text(
        lambda s, p, v, m: kernel.masked_push(s, p, v, m, interpret=False),
        _sds((DEPTH, LANES, 1, f), dtype, one_chip),
        _sds((LANES,), jnp.int32, one_chip),
        _sds((LANES, 1, f), dtype, one_chip),
        _sds((LANES,), jnp.bool_, one_chip),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("f,dtype", WIDTHS, ids=WIDTH_IDS)
def test_masked_peek_compiles(one_chip, f, dtype):
    text = _compiled_text(
        lambda s, p: kernel.masked_peek(s, p, interpret=False),
        _sds((DEPTH, LANES, 1, f), dtype, one_chip),
        _sds((LANES,), jnp.int32, one_chip),
    )
    assert "tpu_custom_call" in text
