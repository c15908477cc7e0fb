"""Unsigned and narrow key dtypes end-to-end (ISSUE 3 satellite):
``estimate_stats``, the ``_sim_fill``/``_sim_low`` sentinels, bucket-id
arithmetic across signed ranges, and ``sort_many`` bucketing for
uint32/int8 — including the all-max/all-min sentinel-collision edges."""

import hashlib
import pathlib

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import OHHCTopology, SortEngine, estimate_stats
from repro.core.engine import _sim_fill, _sim_low
from repro.core.ohhc_sort import ohhc_sort_host
from repro.data.distributions import ALL_DISTRIBUTIONS, key_space_max, local_keys, make_array

pytestmark = pytest.mark.conformance

TOPO = OHHCTopology(1, "full")
NARROW = ("int8", "int16", "uint8", "uint16", "uint32")


# ------------------------------------------------------------- generator
@pytest.mark.parametrize("dtype", ("int8", "int16", "int64", "uint32", "float32"))
@pytest.mark.parametrize("dist", ALL_DISTRIBUTIONS)
def test_make_array_respects_dtype_range(dtype, dist):
    x = make_array(dist, 2000, seed=1, dtype=np.dtype(dtype))
    assert x.dtype == np.dtype(dtype)
    assert x.min() >= 0
    assert int(x.max()) <= key_space_max(dtype)
    if dist == "sorted":
        assert np.all(np.diff(x.astype(np.int64)) >= 0)  # no wrap broke the order


def test_make_array_int32_matches_historical_generator():
    # The dtype generalisation must not move the paper-default arrays.
    x = make_array("random", 1000, seed=42)
    rng = np.random.default_rng(42)
    ref = rng.integers(0, np.iinfo(np.int32).max, 1000, dtype=np.int64)
    np.testing.assert_array_equal(x, np.clip(ref, 0, np.iinfo(np.int32).max).astype(np.int32))


# SHA-256 of make_array("local", 4099, seed=11, dtype) as generated before
# the local branch moved into local_keys: the move must not change a byte.
LOCAL_SHA256 = {
    "int8": "f5800722a51ae146c279fc8b5294e2a2b3608eb8be5665b9dc13f2e9b953ba79",
    "int16": "0c15a74af343c01f647c845510fa2de4d310b05ccda10146c05bbae83510946d",
    "int32": "fc7fcdadad952fe5adb4764bc29c2c718aa2b9e5f29d591489e6b5c95fa43b10",
    "int64": "7865113fa9dc94cb71c9657fae6a115d12a9b2aa266a20610baf3978d8b111e4",
    "uint8": "edd975dd3467c7692384008eae86f039733819890dd8bb187c874a70e199c9f6",
    "uint16": "8aa626e208060114a9a2da535817e3d2785a5ddaa69428939e44b5daee28b4ad",
    "uint32": "1dd10c231c02a5b8296bef9c5b1b8a10e73fecdc2dfe4c07a201f4608b68fd70",
    "uint64": "7865113fa9dc94cb71c9657fae6a115d12a9b2aa266a20610baf3978d8b111e4",
    "float32": "73341da9fa81c1e019bf0d8a8383ef3025d549ecef23822a56a1c08c36012810",
    "float64": "d96d2fa4b76ba4c2f825e35f7476eeb108a4071c0391265e6cd38e238bbdbd5f",
}


@pytest.mark.parametrize("dtype", list(LOCAL_SHA256))
def test_make_array_local_is_unchanged(dtype):
    x = make_array("local", 4099, seed=11, dtype=np.dtype(dtype))
    assert hashlib.sha256(x.tobytes()).hexdigest() == LOCAL_SHA256[dtype]
    y = local_keys(np.random.default_rng(11), 4099, np.dtype(dtype))
    assert y.dtype == x.dtype and y.tobytes() == x.tobytes()


@pytest.mark.parametrize("n", [0, 1, 2, 999, 1000])
def test_local_keys_draw_from_the_callers_stream(n):
    # make_array hands its seed to np.random.default_rng, which returns a
    # Generator unaltered: both calls read one stream
    a = local_keys(np.random.default_rng(5), n)
    b = make_array("local", n, seed=np.random.default_rng(5))
    assert a.dtype == np.int32 and a.size == n and a.tobytes() == b.tobytes()


# ----------------------------------------------------------------- stats
@pytest.mark.parametrize("dtype", ("int8", "uint32", "float32"))
def test_estimate_stats_narrow_and_unsigned(dtype):
    x = make_array("random", 20_000, seed=2, dtype=np.dtype(dtype))
    s = estimate_stats(x, num_buckets=36)
    assert s.dtype == str(x.dtype)
    assert 0.0 < s.f_max_paper <= 1.0
    assert 0.0 < s.f_max_sampled <= 1.0
    assert s.n == x.size


def test_estimate_stats_constant_array_is_dupes():
    x = np.full(5000, np.iinfo(np.int8).max, np.int8)
    s = estimate_stats(x, num_buckets=36)
    assert s.dup_top_frac == 1.0
    assert s.label == "dupes"


# ------------------------------------------------------------- sentinels
@pytest.mark.parametrize("dtype", ("int8", "int16", "int32", "uint8", "uint32"))
def test_sim_sentinels_match_dtype_bounds(dtype):
    dt = jnp.dtype(dtype)
    fill, low = _sim_fill(dt), _sim_low(dt)
    assert fill.dtype == dt and low.dtype == dt
    assert int(fill) == np.iinfo(dtype).max
    assert int(low) == np.iinfo(dtype).min


def test_sim_sentinels_float():
    assert np.isposinf(float(_sim_fill(jnp.float32)))
    assert np.isneginf(float(_sim_low(jnp.float32)))
    assert _sim_fill(jnp.float32).dtype == jnp.float32


# -------------------------------------------------- sentinel collisions
@pytest.mark.parametrize("dtype", ("uint32", "int8", "uint8", "int16"))
@pytest.mark.parametrize("bound", ("max", "min"))
def test_engine_sorts_all_sentinel_valued_arrays(dtype, bound):
    """An array made entirely of the pad-fill value (dtype max) — or the
    low sentinel — must come back intact: validity masking, not value
    comparison, is what separates payload from padding."""
    info = np.iinfo(dtype)
    v = info.max if bound == "max" else info.min
    x = np.full(333, v, dtype=dtype)
    eng = SortEngine(TOPO)
    out = eng.sort(x)
    assert out.dtype == x.dtype
    np.testing.assert_array_equal(out, x)
    assert eng.last_report["counts_sum"] == x.size


def test_engine_sorts_max_and_min_mixture():
    info = np.iinfo(np.int8)
    x = np.tile(np.array([info.min, info.max], np.int8), 200)
    eng = SortEngine(TOPO)
    out = eng.sort(x)
    np.testing.assert_array_equal(out, np.sort(x))


# ------------------------------------------------- signed-range bucketing
@pytest.mark.parametrize("dtype", ("int8", "int16", "int32"))
@pytest.mark.parametrize("method", ("paper", "sampled"))
def test_engine_sim_handles_negative_spans(dtype, method):
    """Keys spanning the negative range: unsigned-wraparound bucket ids
    must stay exact (a native signed subtraction would overflow int8)."""
    info = np.iinfo(dtype)
    rng = np.random.default_rng(4)
    x = rng.integers(info.min, info.max, 1500, dtype=np.int64).astype(dtype)
    eng = SortEngine(TOPO)
    stats = eng.stats(x)
    from repro.core import SortPlan, autotune_capacity, bucketed_length

    padded = bucketed_length(x.size)
    cap = autotune_capacity(stats, method, TOPO.total_procs, padded)
    out = eng.sort(x, plan=SortPlan("sim", method, cap, padded, "forced"))
    np.testing.assert_array_equal(out, np.sort(x))
    assert eng.last_report["counts_sum"] == x.size


@pytest.mark.parametrize("dtype", ("int8", "int16"))
def test_host_path_handles_negative_spans(dtype):
    info = np.iinfo(dtype)
    rng = np.random.default_rng(5)
    x = rng.integers(info.min, info.max, 4000, dtype=np.int64).astype(dtype)
    r = ohhc_sort_host(x, TOPO, method="paper")
    np.testing.assert_array_equal(r.sorted_array, np.sort(x))
    assert int(r.bucket_sizes.sum()) == x.size


# -------------------------------------------------------------- sort_many
@pytest.mark.parametrize("dtype", ("uint32", "int8"))
def test_sort_many_narrow_unsigned_batches(dtype):
    eng = SortEngine(TOPO)
    xs = [
        make_array(d, n, seed=n, dtype=np.dtype(dtype))
        for d, n in zip(("random", "dupes", "sorted", "local"), (300, 900, 1024, 77))
    ]
    # include an all-max row: the sentinel-collision case inside a batch
    xs.append(np.full(256, np.iinfo(dtype).max, dtype=dtype))
    outs = eng.sort_many(xs)
    assert len(outs) == len(xs)
    for x, o in zip(xs, outs):
        assert o.dtype == x.dtype
        np.testing.assert_array_equal(o, np.sort(x))
    assert eng.trace_count == 1  # one vmapped executable for the whole batch


def test_sort_many_rejects_mixed_dtypes():
    eng = SortEngine(TOPO)
    with pytest.raises(ValueError, match="homogeneous"):
        eng.sort_many([np.zeros(8, np.int8), np.zeros(8, np.uint32)])


# ------------------------------------------------ int64 sim under jax x64
_X64_SCRIPT = r"""
import os
os.environ["JAX_ENABLE_X64"] = "1"
import numpy as np
from repro.core import (
    OHHCTopology, SortEngine, SortPlan, autotune_capacity, bucketed_length, x64_enabled,
)

assert x64_enabled()
topo = OHHCTopology(1, "full")
eng = SortEngine(topo)
# Adversarial large-magnitude int64 keys: distinct values above 2^53 whose
# float32 (and even float64) images collide — integer bucket ids must not.
x = (np.int64(1) << 60) + np.arange(36 * 64, dtype=np.int64)
rng = np.random.default_rng(2); rng.shuffle(x)
stats = eng.stats(x)
padded = bucketed_length(x.size)
cap = autotune_capacity(stats, "paper", topo.total_procs, padded)
out = eng.sort(x, plan=SortPlan("sim", "paper", cap, padded, "forced"))
assert out.dtype == np.int64, out.dtype
assert np.array_equal(out, np.sort(x))
lo = int(x.min()); width = (int(x.max()) - lo) // 36 + 1
expected = np.bincount((x - lo) // width, minlength=36)
assert np.array_equal(eng.last_report["counts"], expected), (
    eng.last_report["counts"], expected)
print("X64_INT64_SIM_OK")
"""


@pytest.mark.slow
def test_int64_sim_bucket_ids_exact_under_x64():
    """Regression (ISSUE 3 satellite): with jax x64 on, the sim path takes
    int64 directly, and its paper bucket ids must be exact integer
    arithmetic for keys above 2^53 (where even float64 collapses)."""
    import subprocess
    import sys

    r = subprocess.run(
        [sys.executable, "-c", _X64_SCRIPT],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin:/usr/local/bin"},
        cwd=str(pathlib.Path(__file__).parent.parent),
    )
    assert "X64_INT64_SIM_OK" in r.stdout, r.stderr[-3000:]
