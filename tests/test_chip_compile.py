"""The engine's main-path executables compile for a TPU v5e.

No chip is needed: the TPU compiler installed with jax compiles for a
described ``v5e:2x2`` topology, and refuses there what the chip would
refuse (a kernel Mosaic cannot lower, a program past the device's memory).
Each test compiles one executable family at the smallest shape that still
exercises it, and checks its temporaries plus arguments fit one chip's
16 GB of HBM.  The paper-size (2^24) compile is too slow for the suite and
is made by hand; see ``chip_smoke.py``.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and pytest-xdist workers import
every test file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SortEngine
from repro.data.distributions import make_array

HBM_BYTES = 16e9  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # A described-topology compile is written to the persistent cache but
    # cannot be read back without a chip; keep the cache out of the way.
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fits_one_chip(compiled) -> None:
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < HBM_BYTES


def test_sim_sort_compiles_for_v5e(one_chip):
    n = 1 << 16
    eng = SortEngine()
    plan = eng.plan(make_array("random", n, seed=0))
    assert plan.path == "sim"
    fn = eng._get_sim_fn(n, plan.capacity, plan.method, np.int32, False)
    compiled = fn.lower(
        _spec((n,), jnp.int32, one_chip), _spec((), jnp.int32, one_chip)
    ).compile()
    _fits_one_chip(compiled)


@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32])
def test_row_sort_compiles_for_v5e(one_chip, dtype):
    # the default segment row backend (vmapped jnp.sort), as Sortd runs it
    eng = SortEngine()
    B, L = 8, 1024
    plan = eng.plan_segments(np.zeros(L, np.dtype(dtype)), [L])
    assert plan.method == "bitonic"
    fn = eng._get_sim_fn(L, 0, plan.method, dtype, True)
    compiled = fn.lower(
        _spec((B, L), dtype, one_chip), _spec((B,), jnp.int32, one_chip)
    ).compile()
    _fits_one_chip(compiled)


def test_pairs_sort_compiles_for_v5e(one_chip):
    n = 1 << 12
    fn = SortEngine()._get_pairs_fn(n, jnp.int32, jnp.int32)
    compiled = fn.lower(
        _spec((n,), jnp.int32, one_chip),
        _spec((n,), jnp.int32, one_chip),
        _spec((), jnp.int32, one_chip),
    ).compile()
    _fits_one_chip(compiled)


def test_top_k_compiles_for_v5e(one_chip):
    n = 1 << 16
    eng = SortEngine()
    x = make_array("random", n, seed=0)
    plan = eng.plan_top_k(x, n // 2)
    assert plan.path == "sim"
    keep = eng._plan_top_k_info(x, n // 2)[1]["keep_exec"]
    fn = eng._get_topk_fn(n, plan.capacity, keep, np.int32)
    compiled = fn.lower(
        _spec((n,), jnp.int32, one_chip), _spec((), jnp.int32, one_chip)
    ).compile()
    _fits_one_chip(compiled)
