"""SortEngine: dispatch policy, capacity autotune (no overflow), warm
jit cache (no recompiles within a shape bucket), batched entry points."""

import dataclasses

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import (
    InputStats,
    OHHCTopology,
    SortEngine,
    SortPlan,
    autotune_capacity,
    choose_plan,
    default_capacity,
    estimate_stats,
)
from repro.data.distributions import ALL_DISTRIBUTIONS, make_array

TOPO = OHHCTopology(1, "full")  # P = 36


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


def test_policy_large_skewed_goes_host():
    # ragged host buckets are exact under any splitter, so the cheaper
    # equal-width rule is always the host-path method
    p = choose_plan(mk_stats(n=1 << 17, skew=9.0), TOPO)
    assert (p.path, p.method) == ("host", "paper")


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
        from repro.kernels import ops

        padded = ops.bucketed_length(n)
        cap = autotune_capacity(stats, method, TOPO.total_procs, padded)
        plan = SortPlan("sim", method, cap, padded, "forced")
    out = eng.sort(x, plan=plan)
    np.testing.assert_array_equal(out, np.sort(x))
    assert eng.last_report["counts_sum"] == n


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


def test_batch_plan_row_backend_mapping():
    from repro.core import ROW_BACKENDS, choose_batch_plan

    want = {"vmap": "bitonic", "pallas": "bitonic_pallas", "pallas2op": "bitonic2op"}
    for backend in ROW_BACKENDS:
        p = choose_batch_plan(None, 36, 1024, row_backend=backend)
        assert p.method == want[backend]
        assert p.capacity is None
        assert f"row_backend={backend}" in p.reason
    with pytest.raises(ValueError, match="row_backend"):
        choose_batch_plan(None, 36, 1024, row_backend="cuda")


def test_choose_row_backend_env_and_probe(monkeypatch):
    from repro.core import ROW_BACKENDS, choose_row_backend

    # env override forces a backend, including the Pallas kernels
    for forced in ROW_BACKENDS:
        monkeypatch.setenv("REPRO_ROW_BACKEND", forced)
        backend, detail = choose_row_backend()
        assert backend == forced and "forced" in detail
    monkeypatch.setenv("REPRO_ROW_BACKEND", "cuda")
    with pytest.raises(ValueError, match="REPRO_ROW_BACKEND"):
        choose_row_backend()
    # the default is vmap (XLA's sort) with no probe: nothing runs, nothing
    # is compiled, and the Pallas kernels are never a default
    monkeypatch.delenv("REPRO_ROW_BACKEND", raising=False)
    assert choose_row_backend() == ("vmap", "row_backend=vmap (default)")
    eng = SortEngine(TOPO)
    plan = eng.plan_segments(np.arange(300, dtype=np.int32), [100, 200])
    assert plan.method == "bitonic" and eng.trace_count == 0
    assert "row_backend=vmap (default)" in plan.reason


def test_sort_segments_pallas_backends(monkeypatch):
    # forcing each backend through the env knob must route sort_segments
    # through the fused kernel and stay oracle-exact, with the method
    # visible in last_report (what sortd's metrics surface per bucket)
    rng = np.random.default_rng(5)
    lens = [0, 1, 100, 513, 1000]
    arrs = [rng.integers(0, 1 << 30, n).astype(np.int32) for n in lens]
    flat = np.concatenate(arrs)
    for backend, method in (
        ("pallas", "bitonic_pallas"), ("pallas2op", "bitonic2op")
    ):
        monkeypatch.setenv("REPRO_ROW_BACKEND", backend)
        eng = SortEngine(TOPO)
        outs = eng.sort_segments(flat, lens)
        for a, o in zip(arrs, outs):
            np.testing.assert_array_equal(o, np.sort(a))
        assert eng.last_report["plan"].method == method
        assert f"row_backend={backend}" in eng.last_report["plan"].reason
        assert eng.last_report["overflow_retries"] == 0


def test_sort_segments_sentinel_tie_rows(monkeypatch):
    # dtype-max keys across every row backend: the valid prefix must keep
    # exactly seg_len sentinels per row (lost-element regression guard)
    hi = np.iinfo(np.int32).max
    rng = np.random.default_rng(9)
    arrs = [
        np.full(300, hi, np.int32),
        np.where(rng.random(777) < 0.5, hi, hi - 1).astype(np.int32),
    ]
    flat = np.concatenate(arrs)
    for backend in ("vmap", "pallas", "pallas2op"):
        monkeypatch.setenv("REPRO_ROW_BACKEND", backend)
        eng = SortEngine(TOPO)
        outs = eng.sort_segments(flat, [a.size for a in arrs])
        for a, o in zip(arrs, outs):
            np.testing.assert_array_equal(o, np.sort(a))


def test_sort_pairs_sentinel_ties_engine():
    # engine.sort_pairs pre-pads to the shape bucket before the traced fn;
    # the traced n_valid must keep pad zeros from displacing real payloads
    eng = SortEngine(TOPO)
    hi = np.iinfo(np.int32).max
    k = np.full(200, hi, np.int32)
    k[::3] = hi - 1
    v = np.arange(1, 201, dtype=np.int32)
    ks, vs = eng.sort_pairs(k, v)
    np.testing.assert_array_equal(np.asarray(ks), np.sort(k))
    np.testing.assert_array_equal(np.sort(np.asarray(vs)), v)


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
