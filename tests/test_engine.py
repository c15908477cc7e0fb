"""SortEngine: dispatch policy, capacity autotune (no overflow), warm
jit cache (no recompiles within a shape bucket), batched entry points."""

import dataclasses
import os
import subprocess
import sys
import threading
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import engine as engine_module
from repro.core import (
    InputStats,
    OHHCTopology,
    SortEngine,
    SortPlan,
    autotune_capacity,
    bucketed_length,
    choose_plan,
    default_capacity,
    estimate_stats,
)
from repro.data.distributions import ALL_DISTRIBUTIONS, make_array

TOPO = OHHCTopology(1, "full")  # P = 36

# The key dtypes the segment and pairs tests sweep.
ROW_DTYPES = (np.int32, np.uint32, np.int16, np.float32)
TIE_DTYPES = (np.int32, np.uint32, np.int16)


@pytest.fixture(scope="module")
def engine():
    """One engine for the sweeps, so each shape bucket compiles once."""
    return SortEngine(TOPO)


def random_keys(rng, n, dtype):
    """``n`` keys over the dtype's whole range (normal draws for floats)."""
    dtype = np.dtype(dtype)
    if np.issubdtype(dtype, np.integer):
        info = np.iinfo(dtype)
        return rng.integers(info.min, info.max, n, dtype=dtype, endpoint=True)
    return rng.normal(size=n).astype(dtype)


def mk_stats(
    n=4096,
    sortedness=0.0,
    skew=1.3,
    dup_top_frac=0.01,
    f_max_paper=None,
    f_max_sampled=0.04,
    num_buckets=36,
):
    if f_max_paper is None:
        f_max_paper = skew / num_buckets
    return InputStats(
        n=n,
        dtype="int32",
        sample_size=1024,
        sortedness=sortedness,
        skew=skew,
        dup_top_frac=dup_top_frac,
        f_max_paper=f_max_paper,
        f_max_sampled=f_max_sampled,
        num_buckets=num_buckets,
    )


# ---------------------------------------------------------------- policy
def test_policy_uniform_small_goes_sim_paper():
    p = choose_plan(mk_stats(), TOPO)
    assert (p.path, p.method) == ("sim", "paper")
    assert p.capacity is not None and p.padded_n == 4096


def test_policy_skewed_small_goes_sim_sampled():
    p = choose_plan(mk_stats(skew=8.0), TOPO)
    assert (p.path, p.method) == ("sim", "sampled")


def test_policy_duplicate_heavy_forces_paper():
    # no splitter rule splits one repeated value — cheaper rule + capacity
    p = choose_plan(mk_stats(skew=12.0, dup_top_frac=0.4, f_max_paper=0.45), TOPO)
    assert (p.path, p.method) == ("sim", "paper")


def test_policy_huge_goes_host():
    from repro.core.engine import SIM_MAX_N

    # the paper's largest arrays (n ≤ 2^24) stay on the device; one past
    # the bound pads to a bucket one chip cannot hold and goes to the host
    assert choose_plan(mk_stats(n=SIM_MAX_N), TOPO).path == "sim"
    p = choose_plan(mk_stats(n=SIM_MAX_N + 1), TOPO)
    assert p.path == "host"


@pytest.mark.parametrize(
    "n, stats, want",
    [
        # sampled splitters balance a clustered input: the uniform floor
        (1 << 17, dict(skew=9.0, f_max_sampled=0.03), ("sim", "sampled")),
        (15_728_640, dict(skew=63.9, f_max_sampled=0.03), ("sim", "sampled")),
        # one value holds a tenth of the keys: no splitter splits it, so the
        # sampled capacity climbs above the floor
        (1 << 17, dict(skew=9.0, f_max_sampled=0.1), ("host", "paper")),
        # duplicate-dominated: the paper rule's hot bucket sizes the buffer
        (1 << 17, dict(skew=12.0, dup_top_frac=0.4, f_max_paper=0.45), ("host", "paper")),
        (15_728_640, dict(skew=12.0, dup_top_frac=0.4, f_max_paper=0.45), ("host", "paper")),
    ],
    ids=["clustered-2^17", "clustered-60mb", "heavy-value", "dupes-2^17", "dupes-60mb"],
)
def test_policy_large_skewed_input_by_its_planned_capacity(n, stats, want):
    # a large skewed input runs on the device only at the capacity a uniform
    # input of its size gets; else the host's ragged buckets, which are
    # exact under any splitter, so the cheaper equal-width rule
    p = choose_plan(mk_stats(n=n, **stats), TOPO)
    assert (p.path, p.method) == want
    floor = default_capacity(bucketed_length(n), TOPO.total_procs)
    if p.path == "sim":
        assert (p.capacity, p.padded_n) == (floor, bucketed_length(n))
        assert f"capacity={floor}, balanced floor {floor}" in p.reason
    else:
        assert p.capacity is None
        assert f"above the balanced floor {floor}" in p.reason


def test_policy_mesh_dispatch():
    # multi-axis mesh → hier, regardless of stats
    p = choose_plan(mk_stats(), TOPO, mesh_devices=8, mesh_axes=("pod", "data"))
    assert (p.path, p.method) == ("dist", "hier")
    # presorted → valiant (two-hop routing beats direct-route send skew)
    p = choose_plan(
        mk_stats(sortedness=0.95), TOPO, mesh_devices=8, mesh_axes=("data",)
    )
    assert (p.path, p.method) == ("dist", "valiant")
    # skewed → sampled splitters
    p = choose_plan(mk_stats(skew=8.0), TOPO, mesh_devices=8, mesh_axes=("data",))
    assert (p.path, p.method) == ("dist", "sample")
    # uniform → faithful paper splitters
    p = choose_plan(mk_stats(), TOPO, mesh_devices=8, mesh_axes=("data",))
    assert (p.path, p.method) == ("dist", "paper")
    # a 1-device mesh is no mesh at all
    p = choose_plan(mk_stats(), TOPO, mesh_devices=1, mesh_axes=("data",))
    assert p.path == "sim"


# ------------------------------------------------------------- autotune
def test_autotune_floor_is_deterministic_for_balanced_inputs():
    caps = {
        autotune_capacity(mk_stats(f_max_paper=f), "paper", 36, 4096)
        for f in (0.01, 0.02, 0.028)
    }
    assert len(caps) == 1  # below the 2/P floor every estimate collapses
    (cap,) = caps
    assert cap >= default_capacity(4096, 36) // 2
    assert cap % 8 == 0


def test_autotune_scales_with_measured_skew():
    cap_hot = autotune_capacity(mk_stats(f_max_paper=0.5), "paper", 36, 4096)
    cap_cold = autotune_capacity(mk_stats(f_max_paper=0.02), "paper", 36, 4096)
    assert cap_hot >= 0.5 * 4096
    assert cap_hot <= 4096
    assert cap_hot > 4 * cap_cold


def test_estimated_labels_match_generator_taxonomy():
    for dist, want in [
        ("random", "random"),
        ("sorted", "sorted"),
        ("reversed", "reversed"),
        ("local", ("local", "dupes")),  # tight cluster can read as either
        ("dupes", "dupes"),
    ]:
        s = estimate_stats(make_array(dist, 50_000, seed=3), num_buckets=36)
        want = (want,) if isinstance(want, str) else want
        assert s.label in want, (dist, s)


# ---------------------------------------------------------- correctness
@pytest.mark.parametrize("dist", ALL_DISTRIBUTIONS)
def test_engine_sort_correct_no_overflow(dist):
    """Acceptance: every input class at 1e5+ sorts exactly, model hits
    capacity on the first try (no overflow retries)."""
    eng = SortEngine(TOPO)
    x = make_array(dist, 200_000, seed=11)
    out = eng.sort(x)
    np.testing.assert_array_equal(out, np.sort(x))
    assert eng.last_report["overflow_retries"] == 0
    assert eng.last_report["counts_sum"] == x.size


@pytest.mark.parametrize("dtype", [np.int8, np.int16, np.int32, np.uint32, np.float32])
@pytest.mark.parametrize("n", [1, 2, 100, 127, 128, 129, 512, 1000, 4096, 10000])
def test_sort_shape_bucket_boundaries(n, dtype, engine, rng):
    """Lengths on both sides of the 128 floor and of pow2 shape buckets,
    over the whole range of each key dtype."""
    x = random_keys(rng, n, dtype)
    out = engine.sort(x)
    assert out.dtype == x.dtype
    np.testing.assert_array_equal(out, np.sort(x))


@given(n=st.integers(1, 3000), seed=st.integers(0, 100))
@settings(max_examples=15, deadline=None)
def test_sort_duplicate_heavy_property(n, seed, engine):
    x = np.random.default_rng(seed).integers(0, 50, n).astype(np.int32)
    np.testing.assert_array_equal(engine.sort(x), np.sort(x))


@pytest.mark.slow
@pytest.mark.parametrize("dist", ALL_DISTRIBUTIONS)
def test_engine_sort_correct_1e6(dist):
    eng = SortEngine(TOPO)
    x = make_array(dist, 1_000_000, seed=13)
    out = eng.sort(x)
    np.testing.assert_array_equal(out, np.sort(x))
    assert eng.last_report["overflow_retries"] == 0


@given(
    n=st.integers(2, 4000),
    seed=st.integers(0, 10_000),
    dist=st.sampled_from(list(ALL_DISTRIBUTIONS)),
    method=st.sampled_from(["paper", "sampled"]),
)
@settings(max_examples=30, deadline=None)
def test_autotuned_capacity_property(n, seed, dist, method):
    """Property: with autotuned capacity the sim path never loses elements
    — ``counts.sum() == n`` and the output equals the oracle — for either
    method forced on any input class."""
    eng = SortEngine(TOPO)
    x = make_array(dist, n, seed=seed)
    stats = eng.stats(x)
    plan = choose_plan(stats, TOPO)
    if plan.path != "sim" or plan.method != method:
        padded = bucketed_length(n)
        cap = autotune_capacity(stats, method, TOPO.total_procs, padded)
        plan = SortPlan("sim", method, cap, padded, "forced")
    out = eng.sort(x, plan=plan)
    np.testing.assert_array_equal(out, np.sort(x))
    assert eng.last_report["counts_sum"] == n


@pytest.mark.parametrize("dtype", [np.int32, np.float32])
@pytest.mark.parametrize("n", [1 << 17, 1 << 18])
def test_large_local_arrays_sort_on_the_sim_sampled_path(n, dtype):
    """The paper's local arrays above 2^16 run on the device with sampled
    splitters at the uniform capacity floor: exact, no retry, the fullest
    bucket near the mean."""
    eng = SortEngine(TOPO)
    x = make_array("local", n, seed=n + 19, dtype=dtype)
    out = eng.sort(x)
    np.testing.assert_array_equal(out, np.sort(x))
    rep = eng.last_report
    assert (rep["plan"].path, rep["plan"].method) == ("sim", "sampled")
    assert rep["capacity_used"] == default_capacity(n, TOPO.total_procs)
    assert rep["overflow_retries"] == 0
    assert rep["counts"].sum() == n
    assert TOPO.total_procs * rep["counts"].max() / n < 1.5


def test_host_report_counts_show_the_hot_bucket():
    eng = SortEngine(TOPO)
    x = make_array("dupes", 1 << 17, seed=23)
    out = eng.sort(x)
    np.testing.assert_array_equal(out, np.sort(x))
    rep = eng.last_report
    assert rep["plan"].path == "host"
    assert rep["counts"].sum() == x.size
    assert rep["counts"].max() / x.size > 0.25  # the dominant value's bucket


# --------------------------------------------- bucket-id precision (int)
def test_paper_bucket_ids_exact_above_float32_precision():
    """Regression (ISSUE 3 satellite): float32 bucket-id maths collapses
    adjacent keys above 2^24 onto shared bucket edges.  With integer
    arithmetic the sim path's per-bucket counts must match the exact
    equal-width computation for adversarial large-magnitude uint32 keys."""
    eng = SortEngine(TOPO)
    x = np.uint32(1 << 31) + np.arange(36 * 64, dtype=np.uint32)
    rng = np.random.default_rng(0)
    rng.shuffle(x)
    out = eng.sort(x)
    np.testing.assert_array_equal(out, np.sort(x))
    assert eng.last_report["plan"].path == "sim"
    lo, hi = int(x.min()), int(x.max())
    width = (hi - lo) // 36 + 1
    expected = np.bincount((x.astype(np.int64) - lo) // width, minlength=36)
    np.testing.assert_array_equal(eng.last_report["counts"], expected)


def test_policy_64bit_keys_without_x64_go_host():
    """int64/float64 keys would be silently downcast by jnp.asarray on the
    jit paths; dispatch must route them to the exact numpy host path (and
    the result must still match the oracle for values beyond 2^32)."""
    from repro.core import x64_enabled

    if x64_enabled():  # pragma: no cover - container default is x64 off
        pytest.skip("x64 enabled: every path is exact for 64-bit keys")
    s = dataclasses.replace(mk_stats(), dtype="int64")
    assert choose_plan(s, TOPO).path == "host"
    eng = SortEngine(TOPO)
    x = (np.int64(1) << 40) + np.random.default_rng(1).integers(
        0, 1 << 35, 5000, dtype=np.int64
    )
    out = eng.sort(x)
    assert out.dtype == np.int64
    np.testing.assert_array_equal(out, np.sort(x))
    assert eng.last_report["plan"].path == "host"


# ------------------------------------------------------------- jit cache
def test_no_recompile_within_shape_bucket():
    eng = SortEngine(TOPO)
    for n in (1025, 1400, 1777, 2048):  # all bucket to 2048
        x = make_array("random", n, seed=n)
        np.testing.assert_array_equal(eng.sort(x), np.sort(x))
    assert eng.trace_count == 1, "same-bucket traffic must share one executable"
    eng.sort(make_array("random", 5000, seed=1))  # new bucket (8192)
    assert eng.trace_count == 2


def test_explicit_plan_reuses_executable_across_calls():
    eng = SortEngine(TOPO)
    plan = eng.plan(make_array("random", 1500, seed=0))
    for seed in range(5):
        x = make_array("random", 1500, seed=seed)
        np.testing.assert_array_equal(eng.sort(x, plan=plan), np.sort(x))
    assert eng.trace_count == 1


def test_sort_pairs_bucketed_cache():
    eng = SortEngine(TOPO)
    for B in (5, 17, 40, 100):  # all bucket to 128
        keys = np.random.default_rng(B).integers(0, 1000, B).astype(np.int32)
        ks, order = eng.sort_pairs(keys, np.arange(B, dtype=np.int32))
        ks, order = np.asarray(ks), np.asarray(order)
        assert np.all(np.diff(ks) >= 0)
        np.testing.assert_array_equal(np.sort(order), np.arange(B))
        np.testing.assert_array_equal(keys[order], ks)
    assert eng.trace_count == 1


# --------------------------------------------------------------- batched
def test_sort_many_one_executable_per_batch():
    eng = SortEngine(TOPO)
    xs = [make_array("random", n, seed=n) for n in (300, 900, 1024, 77)]
    outs = eng.sort_many(xs)
    assert len(outs) == len(xs)
    for x, o in zip(xs, outs):
        np.testing.assert_array_equal(o, np.sort(x))
    assert eng.trace_count == 1  # one vmapped trace serves the whole batch


def test_sort_many_mixed_skew_batch():
    eng = SortEngine(TOPO)
    xs = [make_array(d, 2000, seed=5) for d in ALL_DISTRIBUTIONS]
    outs = eng.sort_many(xs)
    for x, o in zip(xs, outs):
        np.testing.assert_array_equal(o, np.sort(x))


def test_serve_order_by_length_uses_engine_cache():
    from repro.serve.engine import SortEngine as _SE  # re-exported dependency

    assert _SE is SortEngine


# ------------------------------------------------------ segmented batches
def test_sort_segments_mixed_lengths_exact():
    eng = SortEngine(TOPO)
    lens = [300, 900, 1024, 77, 0, 1, 2000]
    arrs = [make_array("random", n, seed=n + 1) for n in lens]
    outs = eng.sort_segments(np.concatenate(arrs), lens)
    assert len(outs) == len(arrs)
    for a, o in zip(arrs, outs):
        np.testing.assert_array_equal(o, np.sort(a))
    rep = eng.last_report
    assert rep["batch"] == len(arrs)
    assert rep["overflow_retries"] == 0
    assert rep["pad_cells"] == len(arrs) * 2048 - sum(lens)
    assert rep["batch_padded"] == 8  # batch axis bucketed to the next pow2


def test_sort_segments_every_distribution_rows():
    eng = SortEngine(TOPO)
    xs = [make_array(d, 2000, seed=5) for d in ALL_DISTRIBUTIONS]
    outs = eng.sort_segments(
        np.concatenate(xs), [a.size for a in xs]
    )
    for a, o in zip(xs, outs):
        np.testing.assert_array_equal(o, np.sort(a))
    assert eng.last_report["overflow_retries"] == 0


def test_sort_segments_one_executable_across_batch_and_length_mixes():
    """Both traced axes are bucketed: every (B ≤ 8, len ≤ 1024) mix must
    share one compiled executable."""
    eng = SortEngine(TOPO)
    for B, n in ((3, 1000), (5, 700), (8, 1024), (2, 517), (7, 800)):
        xs = [make_array("random", n, seed=B * 10 + i) for i in range(B)]
        outs = eng.sort_many(xs)
        for a, o in zip(xs, outs):
            np.testing.assert_array_equal(o, np.sort(a))
    assert eng.trace_count == 1


def test_sort_segments_return_padded_stays_on_device():
    import jax

    eng = SortEngine(TOPO)
    xs = [make_array("random", n, seed=n) for n in (300, 900, 1024, 77)]
    lens = [a.size for a in xs]
    out = eng.sort_segments(np.concatenate(xs), lens, return_padded=True)
    assert isinstance(out, jax.Array)
    assert out.shape == (4, 1024)  # batch axis sliced back to B
    host = np.asarray(out)
    for i, (a, n) in enumerate(zip(xs, lens)):
        np.testing.assert_array_equal(host[i, :n], np.sort(a))


def test_sort_segments_sentinel_valued_keys_survive_padding():
    """Keys equal to the dtype max must not be confused with pad cells."""
    eng = SortEngine(TOPO)
    hi = np.iinfo(np.int32).max
    a = np.array([hi, 5, hi, 1, hi], np.int32)
    b = np.array([hi, hi], np.int32)
    outs = eng.sort_segments(np.concatenate([a, b]), [a.size, b.size])
    np.testing.assert_array_equal(outs[0], np.sort(a))
    np.testing.assert_array_equal(outs[1], np.sort(b))


def test_sort_segments_length_mismatch_raises():
    eng = SortEngine(TOPO)
    with pytest.raises(ValueError, match="seg_lens"):
        eng.sort_segments(np.arange(10, dtype=np.int32), [4, 4])
    with pytest.raises(ValueError, match="negative"):
        eng.sort_segments(np.arange(10, dtype=np.int32), [12, -2])


def test_sort_segments_64bit_without_x64_host_fallback():
    from repro.core import x64_enabled

    if x64_enabled():  # pragma: no cover - container default is x64 off
        pytest.skip("x64 enabled: the jit path is exact for 64-bit keys")
    eng = SortEngine(TOPO)
    rng = np.random.default_rng(2)
    xs = [
        (np.int64(1) << 40) + rng.integers(0, 1 << 35, 500, dtype=np.int64)
        for _ in range(3)
    ]
    outs = eng.sort_segments(np.concatenate(xs), [a.size for a in xs])
    for a, o in zip(xs, outs):
        assert o.dtype == np.int64
        np.testing.assert_array_equal(o, np.sort(a))
    assert eng.last_report["plan"].path == "host"
    with pytest.raises(ValueError, match="return_padded"):
        eng.sort_segments(xs[0], [xs[0].size], return_padded=True)


def test_batch_plan_policy_bitonic_vs_bucket():
    from repro.core import SEGMENT_BITONIC_MAX, choose_batch_plan

    # serving-size rows → direct bitonic rows, no capacity, no stats needed
    p = choose_batch_plan(None, 36, 2048)
    assert (p.method, p.capacity) == ("bitonic", None)
    assert choose_batch_plan(None, 36, SEGMENT_BITONIC_MAX).method == "bitonic"
    # big rows → the bucket machinery with worst-row capacity
    big = SEGMENT_BITONIC_MAX * 2
    p = choose_batch_plan(mk_stats(skew=18.0), 36, big)
    assert p.method == "sampled"  # skewed, not duplicate-dominated
    assert p.capacity is not None
    # duplicate-dominated worst row → paper rule, capacity sized to its f̂
    p = choose_batch_plan(
        mk_stats(f_max_paper=0.5, skew=18.0, dup_top_frac=0.5), 36, big
    )
    assert p.method == "paper"
    assert p.capacity is not None and p.capacity >= 0.5 * big
    with pytest.raises(ValueError, match="stats"):
        choose_batch_plan(None, 36, big)


def test_segment_rows_plan_bitonic_without_compiling():
    from repro.core import choose_batch_plan

    p = choose_batch_plan(None, 36, 1024)
    assert (p.method, p.capacity) == ("bitonic", None)
    eng = SortEngine(TOPO)
    plan = eng.plan_segments(np.arange(300, dtype=np.int32), [100, 200])
    assert (plan.method, plan.capacity) == ("bitonic", None)
    assert eng.trace_count == 0


@pytest.mark.parametrize("dtype", ROW_DTYPES)
def test_sort_segments_bucket_straddle(dtype, engine, rng):
    """Batches whose longest row sits on either side of the 128 and 1024
    shape buckets, each with an empty and a length-1 row."""
    for lens in ([0, 1, 127, 128], [0, 1, 127, 128, 129],
                 [0, 1, 1000, 1023, 1024], [0, 1, 1024, 1025]):
        arrs = [random_keys(rng, n, dtype) for n in lens]
        outs = engine.sort_segments(np.concatenate(arrs), lens)
        for a, o in zip(arrs, outs):
            assert o.dtype == a.dtype
            np.testing.assert_array_equal(o, np.sort(a))
        plan = engine.last_report["plan"]
        assert (plan.method, plan.padded_n) == ("bitonic", bucketed_length(max(lens)))


@pytest.mark.parametrize("dtype", ROW_DTYPES)
def test_sort_segments_across_bitonic_max(dtype, engine, rng):
    """A batch plans on its longest row: 8192 keys is the last bitonic
    row bucket, one more key sends the whole batch to the bucket path."""
    from repro.core import SEGMENT_BITONIC_MAX

    assert SEGMENT_BITONIC_MAX == 8192
    for longest, method_is_bitonic in ((8192, True), (8193, False)):
        lens = [0, 1, 300, longest]
        arrs = [random_keys(rng, n, dtype) for n in lens]
        outs = engine.sort_segments(np.concatenate(arrs), lens)
        for a, o in zip(arrs, outs):
            np.testing.assert_array_equal(o, np.sort(a))
        plan = engine.last_report["plan"]
        assert (plan.method == "bitonic") is method_is_bitonic
        assert (plan.capacity is None) is method_is_bitonic
        assert engine.last_report["overflow_retries"] == 0


@pytest.mark.parametrize("dtype", TIE_DTYPES)
def test_sort_segments_sentinel_tie_rows(dtype, rng):
    # dtype-max keys: the valid prefix must keep exactly seg_len sentinels
    # per row (lost-element regression guard), beside an all-equal row
    hi = np.iinfo(dtype).max
    arrs = [
        np.full(300, hi, dtype),
        np.full(77, 42, dtype),
        np.where(rng.random(777) < 0.5, hi, hi - 1).astype(dtype),
    ]
    eng = SortEngine(TOPO)
    outs = eng.sort_segments(np.concatenate(arrs), [a.size for a in arrs])
    for a, o in zip(arrs, outs):
        np.testing.assert_array_equal(o, np.sort(a))


@given(seed=st.integers(0, 1000), lbits=st.integers(7, 12))
@settings(max_examples=10, deadline=None)
def test_sort_segments_property(seed, lbits, engine):
    # random (B ≤ 8, L = 2^7…2^12) batches vs the per-row oracle
    rng = np.random.default_rng(seed)
    L = 1 << lbits
    B = int(rng.integers(1, 9))
    lens = rng.integers(0, L + 1, B)
    lens[0] = L  # the batch's bucket is L
    flat = rng.integers(0, 1 << 30, int(lens.sum())).astype(np.int32)
    outs = engine.sort_segments(flat, lens)
    for o, seg in zip(outs, np.split(flat, np.cumsum(lens)[:-1])):
        np.testing.assert_array_equal(o, np.sort(seg))


@pytest.mark.parametrize("dtype", TIE_DTYPES)
@pytest.mark.parametrize("n", [10, 100, 129, 1000])
def test_sort_pairs_sentinel_ties_engine(n, dtype, engine, rng):
    # engine.sort_pairs pre-pads to the shape bucket before the traced fn;
    # the traced n_valid must keep pad zeros from displacing real payloads
    hi = np.iinfo(dtype).max
    k = np.full(n, hi, dtype)
    k[rng.random(n) < 0.5] = hi - 1  # mix of max and near-max keys
    v = np.arange(1, n + 1, dtype=np.int32)  # payloads, none zero
    ks, vs = engine.sort_pairs(k, v)
    np.testing.assert_array_equal(np.asarray(ks), np.sort(k))
    np.testing.assert_array_equal(np.sort(np.asarray(vs)), v)


@pytest.mark.parametrize("dtype", [np.int32, np.uint32])
@pytest.mark.parametrize("n", [10, 128, 1000, 5000])
def test_sort_pairs_payloads(n, dtype, engine, rng):
    # duplicate-heavy keys; each payload is its key's index, so a payload
    # that left its key shows as keys[payload] != sorted key
    keys = rng.integers(0, 64, n).astype(dtype)
    ks, vs = engine.sort_pairs(keys, np.arange(n, dtype=np.int32))
    ks, vs = np.asarray(ks), np.asarray(vs)
    np.testing.assert_array_equal(ks, np.sort(keys))
    np.testing.assert_array_equal(np.sort(vs), np.arange(n))
    np.testing.assert_array_equal(keys[vs], ks)


def test_estimate_batch_stats_worst_row_scaled():
    from repro.core import estimate_batch_stats, pack_segments

    # one constant (degenerate) row among uniform rows, all full length
    rows = [make_array("random", 1024, seed=s) for s in range(3)]
    rows.append(np.full(1024, 7, np.int32))
    lens = [r.size for r in rows]
    padded = pack_segments(np.concatenate(rows), lens, 1024)
    s = estimate_batch_stats(padded, lens, num_buckets=36)
    assert s.f_max_paper > 0.9  # the constant row dominates the reduction
    assert s.dup_top_frac > 0.9
    # the same pathological row at 1/16 the batch row length barely registers
    rows2 = rows[:3] + [np.full(64, 7, np.int32)]
    lens2 = [1024, 1024, 1024, 64]
    padded2 = pack_segments(np.concatenate(rows2), lens2, 1024)
    s2 = estimate_batch_stats(padded2, lens2, num_buckets=36)
    assert s2.f_max_paper < 0.2
    # zero-length rows are masked out entirely
    padded3 = pack_segments(rows[0], [1024, 0], 1024)
    s3 = estimate_batch_stats(padded3, [1024, 0], num_buckets=36)
    assert s3.dup_top_frac < 0.5


def test_pack_unpack_segments_roundtrip_and_errors():
    from repro.core import pack_segments, unpack_segments

    arrs = [np.arange(5, dtype=np.int32), np.zeros(0, np.int32),
            np.arange(8, dtype=np.int32)]
    lens = [a.size for a in arrs]
    packed = pack_segments(np.concatenate(arrs), lens, 8)
    assert packed.shape == (3, 8)
    for a, o in zip(arrs, unpack_segments(packed, lens)):
        np.testing.assert_array_equal(o, a)
    # left pad fill sorts to the end (dtype max default)
    assert packed[0, 5] == np.iinfo(np.int32).max
    # right alignment puts content at the row end (serving left-pad layout)
    right = pack_segments(np.concatenate(arrs), lens, 8, fill_value=0,
                          align="right")
    np.testing.assert_array_equal(right[0, 3:], arrs[0])
    assert right[0, 0] == 0
    with pytest.raises(ValueError, match="row_len"):
        pack_segments(np.arange(9, dtype=np.int32), [9], 8)
    with pytest.raises(ValueError, match="sum"):
        pack_segments(np.arange(9, dtype=np.int32), [4, 4], 8)


# ------------------------------------------------------ pad staging pool
POOL_BUCKET = 4096
POOL_DTYPES = (np.int32, np.uint32, np.float32)
# op -> (the engine's getter of its sim executables, the call, its answer)
POOL_OPS = {
    "sort": ("_get_sim_fn", lambda eng, x: eng.sort(x), np.sort),
    "top_k": ("_get_topk_fn", lambda eng, x: eng.top_k(x, x.size // 2),
              lambda x: np.sort(x)[: x.size // 2]),
}


def bucket_sequence(dtype, rng):
    """Three inputs of one shape bucket, in order: a large ``n`` of large
    keys, a smaller ``n`` of small keys, then ``n == padded_n``."""
    dtype = np.dtype(dtype)
    if dtype.kind == "f":
        large = rng.uniform(1e6, 1e7, 4000).astype(dtype)
        small = rng.uniform(1.0, 100.0, 2100).astype(dtype)
    else:
        hi = int(np.iinfo(dtype).max)
        large = rng.integers(hi // 2, hi, 4000, endpoint=True).astype(dtype)
        small = rng.integers(1, 100, 2100).astype(dtype)
    xs = [large, small, random_keys(rng, POOL_BUCKET, dtype)]
    assert {bucketed_length(x.size) for x in xs} == {POOL_BUCKET}
    return xs


def spy_executable_inputs(eng, getter):
    """Make ``eng``'s executables from ``getter`` record a host copy of the
    padded input they are called with; return the list they append to."""
    seen = []
    get = getattr(eng, getter)

    def spying_get(*args):
        fn = get(*args)

        def call(x_pad, *rest):
            seen.append(np.array(x_pad))
            return fn(x_pad, *rest)

        return call

    setattr(eng, getter, spying_get)
    return seen


def assert_exact(out, want):
    assert out.dtype == want.dtype
    assert out.tobytes() == want.tobytes()


@pytest.mark.parametrize("dtype", POOL_DTYPES)
@pytest.mark.parametrize("op", sorted(POOL_OPS))
def test_pad_buffer_reused_within_a_bucket_sends_fresh_bytes(op, dtype, rng):
    getter, run, answer = POOL_OPS[op]
    eng = SortEngine(TOPO)
    seen = spy_executable_inputs(eng, getter)
    for i, x in enumerate(bucket_sequence(dtype, rng)):
        assert_exact(run(eng, x), answer(x))
        assert eng.last_report["plan"].path == "sim"
        assert eng.last_report["pad_reused"] is (i > 0)
        # what reached the device: the keys, then zeros, as from np.zeros
        staged = np.concatenate([x, np.zeros(POOL_BUCKET - x.size, x.dtype)])
        assert seen[-1].dtype == staged.dtype
        assert seen[-1].tobytes() == staged.tobytes()
    assert list(eng._pad_pool) == [(POOL_BUCKET, np.dtype(dtype))]


@pytest.mark.parametrize("op", sorted(POOL_OPS))
def test_pad_pool_two_threads_on_one_engine_stay_exact(op):
    getter, run, answer = POOL_OPS[op]
    eng = SortEngine(TOPO)
    start = threading.Barrier(2)

    def caller(seed):
        xs = bucket_sequence(np.int32, np.random.default_rng(seed))
        start.wait()
        for i in range(12):
            x = xs[i % len(xs)]
            assert_exact(run(eng, x), answer(x))

    with ThreadPoolExecutor(2) as pool:
        for f in [pool.submit(caller, seed) for seed in (1, 2)]:
            f.result(timeout=300)
    assert list(eng._pad_pool) == [(POOL_BUCKET, np.dtype(np.int32))]
    (buf,) = eng._pad_pool.values()
    assert buf.shape == (POOL_BUCKET,) and buf.dtype == np.int32


def test_pad_pool_never_hands_one_buffer_to_two_holders():
    # More takers than cores, switching threads often: a buffer handed to
    # two holders at once shows as another holder's id inside it.
    eng = SortEngine(TOPO)
    workers = (os.cpu_count() or 1) + 2
    start = threading.Barrier(workers)

    def taker(tag):
        x = np.full(1000, tag, np.int32)
        start.wait()
        for _ in range(200):
            buf, _ = eng._stage_pad(x, 1024)
            assert np.all(buf[:1000] == tag) and not buf[1000:].any()
            eng._release_pad(buf)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with ThreadPoolExecutor(workers) as pool:
            for f in [pool.submit(taker, tag) for tag in range(1, workers + 1)]:
                f.result(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert len(eng._pad_pool) == 1


def test_pad_pool_keeps_one_idle_buffer_per_bucket_and_dtype():
    eng = SortEngine(TOPO)
    x = np.arange(1000, dtype=np.int32)
    a, a_reused = eng._stage_pad(x, 1024)
    b, b_reused = eng._stage_pad(x, 1024)  # a is still held: b is new
    assert not a_reused and not b_reused and a is not b
    eng._release_pad(a)
    eng._release_pad(b)
    assert list(eng._pad_pool.values()) == [a]
    f, f_reused = eng._stage_pad(x.astype(np.float32), 1024)  # another dtype
    g, g_reused = eng._stage_pad(x, 2048)  # another bucket
    assert not f_reused and not g_reused
    c, c_reused = eng._stage_pad(x[:10], 1024)
    assert c_reused and c is a and not eng._pad_pool
    assert_exact(c, np.concatenate([x[:10], np.zeros(1014, np.int32)]))
    for buf in (f, g, c):
        eng._release_pad(buf)
    assert sorted(eng._pad_pool) == [(1024, np.dtype(np.float32)), (1024, np.dtype(np.int32)),
                                     (2048, np.dtype(np.int32))]


@pytest.mark.parametrize("op", sorted(POOL_OPS))
def test_a_failed_call_drops_its_pad_buffer(op, rng):
    getter, run, answer = POOL_OPS[op]
    eng = SortEngine(TOPO)
    x = bucket_sequence(np.int32, rng)[0]
    run(eng, x)
    assert len(eng._pad_pool) == 1

    def lost(*args):
        raise RuntimeError("executable lost")

    setattr(eng, getter, lost)
    with pytest.raises(RuntimeError, match="lost"):
        run(eng, x)
    assert not eng._pad_pool
    delattr(eng, getter)
    assert_exact(run(eng, x), answer(x))
    assert eng.last_report["pad_reused"] is False


# ----------------------------------------------------------- split sim D2H
SPLIT_BUCKET = 4096
SPLIT_PIECE = SPLIT_BUCKET // engine_module.D2H_MAX_PIECES  # 512 keys


@pytest.fixture
def split_d2h(monkeypatch):
    """Pieces of 2 KiB: the 2^12 bucket of 4-byte keys comes back from its
    executable as the most pieces, 8 of 512 keys."""
    monkeypatch.setattr(engine_module, "D2H_PIECE_BYTES", SPLIT_PIECE * 4)
    assert engine_module.d2h_pieces(SPLIT_BUCKET, np.int32) == engine_module.D2H_MAX_PIECES


@pytest.mark.parametrize(
    "padded_n, dtype, want",
    [(1 << 21, np.int32, 1), (1 << 22, np.int32, 2), (1 << 23, np.int32, 4),
     (1 << 24, np.int32, 8), (1 << 25, np.int32, 8), (1 << 22, np.int16, 1),
     (1 << 21, np.float64, 2), (3 << 22, np.int32, 4), (1 << 10, np.int32, 1)],
)
def test_d2h_pieces_by_the_buckets_bytes(padded_n, dtype, want):
    k = engine_module.d2h_pieces(padded_n, dtype)
    assert k == want and padded_n % k == 0


def assert_owned_result(out, n):
    """A fresh, writable, C-contiguous array of exactly ``n`` keys that no
    device buffer's host copy backs."""
    assert out.shape == (n,)
    assert out.flags.c_contiguous and out.flags.writeable
    assert out.flags.owndata and out.base is None


@pytest.mark.parametrize("dtype", POOL_DTYPES)
@pytest.mark.parametrize(
    "n, copied",
    [(SPLIT_BUCKET, 8), (SPLIT_BUCKET - 1, 8), (5 * SPLIT_PIECE, 5),
     (SPLIT_PIECE - 212, 1), (SPLIT_BUCKET // 2 + 1, 5)],
    ids=["padded_n", "padded_n-1", "piece_boundary", "first_piece", "past_lower_bucket"],
)
def test_split_sim_d2h_is_exact(split_d2h, n, copied, dtype, rng):
    eng = SortEngine(TOPO)
    x = random_keys(rng, n, dtype)
    plan = SortPlan("sim", "paper", None, SPLIT_BUCKET, "split forced")
    out = eng.sort(x, plan=plan)
    assert_exact(out, np.sort(x))
    assert_owned_result(out, n)
    rep = eng.last_report
    assert rep["plan"].padded_n == SPLIT_BUCKET
    # the pieces past n hold no valid key and are not copied
    assert rep["d2h_pieces"] == copied == -(-n // SPLIT_PIECE)
    # one executable per capacity tried, each returning 8 pieces
    assert {(key[0], key[-1]) for key in eng._fn_cache} == {("sim", 8)}


def test_split_sim_d2h_planned_bucket_and_retry(split_d2h, rng):
    eng = SortEngine(TOPO)
    x = random_keys(rng, SPLIT_BUCKET - 100, np.int32)
    out = eng.sort(x)
    assert_exact(out, np.sort(x))
    assert_owned_result(out, x.size)
    assert eng.last_report["plan"].padded_n == SPLIT_BUCKET
    assert eng.last_report["d2h_pieces"] == 8
    # an overflow retry copies the pieces of the attempt that succeeded
    out = eng.sort(x, plan=SortPlan("sim", "paper", 64, None, "capacity forced too small"))
    assert_exact(out, np.sort(x))
    assert eng.last_report["overflow_retries"] >= 1 and eng.last_report["d2h_pieces"] == 8


def test_sim_d2h_below_the_split_size_is_one_piece(rng):
    eng = SortEngine(TOPO)
    x = random_keys(rng, 5000, np.int32)
    out = eng.sort(x)
    assert_exact(out, np.sort(x))
    assert eng.last_report["d2h_pieces"] == 1
    assert out.shape == (5000,) and not out.flags.writeable  # a view of the host copy
    (key,) = eng._fn_cache
    assert key[-1] == 1


def test_split_sim_d2h_eight_threads_on_one_engine(split_d2h):
    eng = SortEngine(TOPO)
    workers = 8
    start = threading.Barrier(workers)

    def caller(seed):
        rng = np.random.default_rng(seed)
        start.wait()
        for i in range(6):
            x = random_keys(rng, SPLIT_BUCKET - 97 * i * seed % 1500, np.int32)
            out = eng.sort(x)
            assert_exact(out, np.sort(x))
            assert_owned_result(out, x.size)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with ThreadPoolExecutor(workers) as pool:
            for f in [pool.submit(caller, seed) for seed in range(1, workers + 1)]:
                f.result(timeout=300)
    finally:
        sys.setswitchinterval(interval)
    assert eng._join_pool._max_workers == engine_module.D2H_MAX_PIECES


SHARED_POOL_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
from repro import compat
from repro.core import SortEngine, engine
from repro.core.engine import SortPlan

engine.D2H_PIECE_BYTES = 2048  # the 2^12 int32 bucket comes back as 8 pieces
mesh = compat.make_mesh((4,), ("data",))
rng = np.random.default_rng(3)
sim = SortPlan("sim", "paper", None, None, "forced onto one device")

def exact(eng, x, **kw):
    got = eng.sort(x, **kw)
    assert got.tobytes() == np.sort(x).tobytes()
    return eng.last_report

for sim_first in (True, False):
    eng = SortEngine(mesh=mesh)
    calls = [("sim", 4000), ("dist", 16384)]
    for path, n in (calls if sim_first else calls[::-1]):
        x = rng.integers(0, 2**31 - 1, n, dtype=np.int32)
        rep = exact(eng, x, plan=sim if path == "sim" else None)
        assert rep["plan"].path == path, rep
        if path == "sim":
            assert rep["d2h_pieces"] == 8, rep
        else:
            assert rep["unpack_writers"] == 4, rep
    assert eng._join_pool._max_workers == 8
print("SHARED_POOL_OK")
"""


def test_dist_and_split_sim_share_one_join_pool_on_4_devices():
    r = subprocess.run(
        [sys.executable, "-c", SHARED_POOL_SCRIPT],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin:/usr/local/bin", "JAX_PLATFORMS": "cpu"},
        cwd=Path(__file__).resolve().parents[1],
    )
    assert "SHARED_POOL_OK" in r.stdout, r.stderr[-3000:]
