"""Workload-layer tests (DESIGN.md §12): top-k edges + property sweep,
pytree payload round-trips, streaming merge, the Sortd merge service, and
the MoE argsort-dispatch parity — the satellite battery PR 10 pins."""

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from benchmarks.common import DTYPES
from repro.core import (
    SortEngine,
    TopKTooLarge,
    autotune_capacity,
    host_bucket_ids,
    merge_sorted_arrays,
    topk_cut,
)
from repro.core import engine as engine_mod
from repro.data.distributions import make_array

# One engine for the module: the op layer shares its jit caches the same
# way the serving layer does, so the suite exercises warm-cache dispatch.
ENG = SortEngine()
P = ENG.topo.total_procs


# --------------------------------------------------------------- top-k edges


def test_top_k_zero_is_empty_and_dtype_preserved():
    x = make_array("random", 100, seed=1, dtype=np.dtype("int16"))
    out = ENG.top_k(x, 0)
    assert out.size == 0 and out.dtype == x.dtype
    assert ENG.last_report["skipped_buckets"] == P


def test_top_k_one_is_min():
    x = make_array("random", 513, seed=2)
    assert ENG.top_k(x, 1)[0] == x.min()


def test_top_k_n_is_full_sort():
    x = make_array("dupes", 300, seed=3)
    np.testing.assert_array_equal(ENG.top_k(x, x.size), np.sort(x))


def test_top_k_too_large_is_typed_error():
    x = make_array("random", 64, seed=4)
    with pytest.raises(TopKTooLarge, match="k=65 exceeds n=64"):
        ENG.top_k(x, 65)
    assert issubclass(TopKTooLarge, ValueError)  # catchable as ValueError


def test_top_k_rejects_non_int_k():
    x = make_array("random", 64, seed=4)
    with pytest.raises(TypeError):
        ENG.top_k(x, True)
    with pytest.raises(TypeError):
        ENG.top_k(x, 2.0)
    with pytest.raises(ValueError):
        ENG.top_k(x, -1)


def test_top_k_on_bucket_boundaries():
    # arange over [0, 8P) → equal-width buckets of exactly 8 elements;
    # k landing on/next to a bucket edge must not drop or duplicate ties.
    x = np.random.default_rng(5).permutation(np.arange(8 * P, dtype=np.int32))
    for k in (7, 8, 9, 16, 8 * P - 1):
        np.testing.assert_array_equal(ENG.top_k(x, k), np.arange(k))


def test_top_k_duplicate_ties_straddling_rank_k():
    x = np.concatenate(
        [np.zeros(10, np.int32), np.full(20, 5, np.int32)]
    )
    rng = np.random.default_rng(6)
    rng.shuffle(x)
    out = ENG.top_k(x, 15)
    np.testing.assert_array_equal(
        out, np.array([0] * 10 + [5] * 5, np.int32)
    )


def test_top_k_plan_reason_reports_skip_accounting():
    x = make_array("random", 2048, seed=7)
    plan = ENG.plan_top_k(x, 32)
    assert "skipped=" in plan.reason and "top_k k=32" in plan.reason


@given(
    dtype=st.sampled_from(DTYPES),
    n=st.integers(0, 400),
    kpct=st.integers(0, 100),
    dist=st.sampled_from(("random", "dupes", "local", "sorted")),
)
@settings(max_examples=60, deadline=None)
def test_top_k_matches_sorted_head_property(dtype, n, kpct, dist):
    x = make_array(dist, n, seed=n + kpct, dtype=np.dtype(dtype))
    k = (n * kpct) // 100
    out = ENG.top_k(x, k)
    np.testing.assert_array_equal(out, np.sort(x)[:k])
    assert out.dtype == x.dtype


def test_host_and_device_bucket_ids_agree_bitwise():
    import jax.numpy as jnp

    for dtype in ("int8", "int16", "int32", "uint32", "float32"):
        x = make_array("random", 257, seed=11, dtype=np.dtype(dtype))
        want = host_bucket_ids(x, P)
        got = np.asarray(
            engine_mod._paper_ids(
                jnp.asarray(x), jnp.ones(x.size, bool), P=P
            )
        )
        np.testing.assert_array_equal(got.astype(np.int64), want, err_msg=dtype)


def test_topk_cut_boundaries():
    counts = np.array([4, 0, 4, 8])
    assert topk_cut(counts, 1) == (1, 3)
    assert topk_cut(counts, 4) == (1, 3)  # k exactly on the first edge
    assert topk_cut(counts, 5) == (3, 1)  # empty bucket can't cover it
    assert topk_cut(counts, 8) == (3, 1)
    assert topk_cut(counts, 9) == (4, 0)
    assert topk_cut(counts, 16) == (4, 0)


# ------------------------------------------------- satellite 4: capacity fix


def test_top_k_plan_does_not_inherit_full_sort_capacity():
    """Red-before/green-after: 1448 duplicates of one huge value force the
    full sort's worst-row capacity to cover that bucket, but a k=64 head
    never touches it — the top-k plan must size capacity from the KEPT
    buckets only and still run overflow-free."""
    from repro.core import bucketed_length

    x = np.concatenate(
        [
            np.arange(600, dtype=np.int32),
            np.full(1448, np.iinfo(np.int32).max - 1, np.int32),
        ]
    )
    np.random.default_rng(8).shuffle(x)
    stats = ENG.stats(x)
    cap_full = autotune_capacity(
        stats, "paper", P, bucketed_length(x.size)
    )
    assert cap_full >= 1448  # the dupe bucket dominates the full sort

    plan = ENG.plan_top_k(x, 64)
    assert plan.path == "sim", plan.reason
    assert plan.capacity is not None and plan.capacity < cap_full

    out = ENG.top_k(x, 64, plan=plan)
    np.testing.assert_array_equal(out, np.arange(64, dtype=np.int32))
    assert ENG.last_report["overflow_retries"] == 0
    assert ENG.last_report["capacity_used"] == plan.capacity


# ------------------------------------------------------ pytree payload pairs


def _nested_payload(x: np.ndarray):
    n = x.size
    idx = np.arange(n, dtype=np.int64)
    return {
        "idx": idx,
        "nested": (
            x.astype(np.float64),
            ((idx * 7) % 251).astype(np.int8),
        ),
        "mat": np.arange(n * 3, dtype=np.float32).reshape(n, 3),
    }


def test_sort_pairs_pytree_round_trip_byte_exact():
    x = make_array("dupes", 500, seed=9)
    vals = _nested_payload(x)
    ks, out = ENG.sort_pairs(x, vals)
    np.testing.assert_array_equal(np.asarray(ks), np.sort(x))
    perm = np.asarray(out["idx"])
    assert np.array_equal(np.sort(perm), np.arange(x.size))
    for got, src in (
        (out["idx"], vals["idx"]),
        (out["nested"][0], vals["nested"][0]),
        (out["nested"][1], vals["nested"][1]),
        (out["mat"], vals["mat"]),
    ):
        assert np.asarray(got).tobytes() == src[perm].tobytes()
        assert np.asarray(got).dtype == src.dtype


def test_sort_pairs_pytree_shuffle_invariance():
    # Metamorphic: with UNIQUE keys the sorted (key, payload) stream is a
    # function of the multiset only — any input permutation yields
    # byte-identical output.
    rng = np.random.default_rng(10)
    keys = rng.permutation(np.arange(400, dtype=np.int32)) * 3 - 17
    vals = {"a": keys.astype(np.int64) * 5, "b": (keys.astype(np.float32),)}
    ks1, out1 = ENG.sort_pairs(keys, vals)
    sh = rng.permutation(keys.size)
    ks2, out2 = ENG.sort_pairs(
        keys[sh], {"a": vals["a"][sh], "b": (vals["b"][0][sh],)}
    )
    np.testing.assert_array_equal(np.asarray(ks1), np.asarray(ks2))
    assert np.asarray(out1["a"]).tobytes() == np.asarray(out2["a"]).tobytes()
    assert (
        np.asarray(out1["b"][0]).tobytes() == np.asarray(out2["b"][0]).tobytes()
    )


@pytest.mark.parametrize("dtype", ["int32", "uint32", "int16", "float32"])
def test_sort_pairs_pytree_sentinel_ties_keep_payload(dtype):
    # PR-8 regression, now on the pytree path: keys equal to the dtype max
    # collide with the kernel's pad sentinel; their payloads must survive.
    dt = np.dtype(dtype)
    hi = np.finfo(dt).max if dt.kind == "f" else np.iinfo(dt).max
    rng = np.random.default_rng(12)
    keys = make_array("random", 70, seed=12, dtype=dt)
    keys[rng.choice(70, 9, replace=False)] = hi
    vals = {"tag": np.arange(70, dtype=np.int64)}
    ks, out = ENG.sort_pairs(keys, vals)
    ks, tag = np.asarray(ks), np.asarray(out["tag"])
    np.testing.assert_array_equal(ks, np.sort(keys))
    np.testing.assert_array_equal(keys[tag], ks)  # pairing intact
    assert set(tag[ks == hi]) == set(np.flatnonzero(keys == hi))


def test_sort_pairs_flat_path_unchanged():
    # The serving hot path: a single flat 1-D payload must still ride the
    # tagged pair kernel and return jax arrays (warm shape-bucket cache).
    x = make_array("random", 257, seed=13)
    v = np.arange(257, dtype=np.int32)
    ks, vs = ENG.sort_pairs(x, v)
    assert hasattr(ks, "devices") and hasattr(vs, "devices")  # jax arrays
    np.testing.assert_array_equal(np.asarray(ks), np.sort(x))
    np.testing.assert_array_equal(x[np.asarray(vs)], np.asarray(ks))


def test_sort_pairs_pytree_leaf_shape_mismatch_raises():
    x = make_array("random", 64, seed=14)
    with pytest.raises(ValueError, match="leading dim"):
        ENG.sort_pairs(x, {"bad": np.arange(63)})


# ---------------------------------------------------------- streaming merge


@given(
    dtype=st.sampled_from(("int32", "uint32", "int16", "float32", "int64")),
    chunks=st.integers(1, 6),
    seed=st.integers(0, 4),
)
@settings(max_examples=30, deadline=None)
def test_merge_stream_equals_full_resort_property(dtype, chunks, seed):
    # k successive appends == one full re-sort (the §12 streaming contract)
    dt = np.dtype(dtype)
    whole = make_array("random", 257 * chunks + seed, seed=seed, dtype=dt)
    buf = np.empty(0, dt)
    for part in np.array_split(whole, chunks):
        buf = ENG.merge_sorted(buf, part)
    np.testing.assert_array_equal(buf, np.sort(whole))
    assert buf.dtype == dt


def test_merge_sorted_rejects_unsorted_buffer():
    with pytest.raises(ValueError, match="not ascending"):
        ENG.merge_sorted(np.array([3, 1, 2], np.int32), np.array([5], np.int32))


def test_merge_sorted_rejects_dtype_mismatch():
    with pytest.raises(ValueError, match="dtype"):
        ENG.merge_sorted(np.array([1], np.int32), np.array([2], np.int64))


def test_merge_sorted_arrays_tie_and_empty_edges():
    a = np.array([1, 2, 2, 9], np.int32)
    b = np.array([2, 2, 10], np.int32)
    np.testing.assert_array_equal(
        merge_sorted_arrays(a, b), np.sort(np.concatenate([a, b]))
    )
    np.testing.assert_array_equal(merge_sorted_arrays(a, a[:0]), a)
    np.testing.assert_array_equal(merge_sorted_arrays(a[:0], b), b)


def test_sortd_interleaved_merge_and_sort_never_cross_contaminate():
    """The §12 service op: merge and sort requests on the SAME
    (dtype, shape-bucket) must coalesce into separate bins — a merge
    output leaking into a sort batch (or vice versa) is exactly the
    cross-contamination this pins."""
    from repro.serve.sortd import Sortd, SortdConfig

    rng = np.random.default_rng(15)
    cfg = SortdConfig(max_batch=8, max_wait_s=0.02)
    with Sortd(SortEngine(), cfg) as sd:
        futs = []
        for i in range(6):
            x = rng.integers(0, 1 << 20, 400).astype(np.int32)
            buf = np.sort(rng.integers(0, 1 << 20, 300).astype(np.int32))
            new = rng.integers(0, 1 << 20, 400).astype(np.int32)
            futs.append(("sort", x, sd.submit(x)))
            futs.append(("merge", (buf, new), sd.submit_merge(buf, new)))
        for op, arg, fut in futs:
            out = fut.result(timeout=60)
            if op == "sort":
                np.testing.assert_array_equal(out, np.sort(arg))
            else:
                buf, new = arg
                np.testing.assert_array_equal(
                    out, np.sort(np.concatenate([buf, new]))
                )
        m = sd.metrics()
        buckets = set(m["buckets"])
    assert any(b.startswith("merge/int32/") for b in buckets), buckets
    assert any(not b.startswith("merge/") for b in buckets), buckets


def test_sortd_merge_bad_buffer_fails_alone():
    from repro.serve.sortd import Sortd, SortdConfig

    cfg = SortdConfig(max_batch=8, max_wait_s=0.02)
    with Sortd(SortEngine(), cfg) as sd:
        good = sd.submit_merge(
            np.array([1, 5], np.int32), np.array([3, 2], np.int32)
        )
        bad = sd.submit_merge(
            np.array([9, 1], np.int32), np.array([4, 7], np.int32)
        )
        np.testing.assert_array_equal(
            good.result(timeout=60), np.array([1, 2, 3, 5], np.int32)
        )
        with pytest.raises(ValueError, match="ascending"):
            bad.result(timeout=60)
        with pytest.raises(ValueError, match="dtype mismatch"):
            sd.submit_merge(np.array([1], np.int32), np.array([2], np.float32))


# ----------------------------------------------------- MoE dispatch parity


def test_moe_argsort_dispatch_is_bit_identical_to_sorted():
    import jax
    import jax.numpy as jnp

    from repro.configs.base import ModelConfig, MoEConfig
    from repro.models import moe as MOE
    from repro.models.common import NO_SHARD

    cfg = ModelConfig(
        family="moe", d_model=32, num_heads=4, dtype=jnp.float32,
        moe=MoEConfig(
            num_experts=4, num_experts_per_tok=2, expert_d_ff=64,
            dispatch="sorted", capacity_factor=1.25,
        ),
    )
    import dataclasses

    cfg_a = cfg.replace(moe=dataclasses.replace(cfg.moe, dispatch="argsort"))
    p = MOE.init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32), jnp.float32)
    y1, aux1 = MOE.apply_moe(p, x, cfg, NO_SHARD)
    y2, aux2 = MOE.apply_moe(p, x, cfg_a, NO_SHARD)
    assert np.asarray(y1).tobytes() == np.asarray(y2).tobytes()
    assert np.asarray(aux1).tobytes() == np.asarray(aux2).tobytes()


# --------------------------------------------------- conformance tier1 slice


@pytest.mark.conformance
def test_op_tier1_grid_passes_and_cross_checks():
    from repro.verify import differential, grid

    cells = grid.op_tier1_grid()
    assert cells, "tier1 op slice must not be empty"
    results = differential.run_op_grid(cells)
    fails = [(r.scenario_id, r.detail) for r in results if r.status != "pass"]
    assert not fails, fails
    assert differential.cross_check(results) == []
