"""Array Division Procedure (§3.1) properties + sampled splitters."""

import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import partition


@given(
    n=st.integers(2, 500),
    buckets=st.integers(1, 32),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=40, deadline=None)
def test_paper_buckets_are_ordered(n, buckets, seed):
    """Range partitioning's invariant: every value in bucket i ≤ every value
    in bucket j for i < j — the merge-free property."""
    rng = np.random.default_rng(seed)
    x = rng.integers(-(2**30), 2**30, n).astype(np.int32)
    ids = np.asarray(partition.paper_bucket_ids(jnp.asarray(x), buckets))
    assert ids.min() >= 0 and ids.max() < buckets
    order = np.argsort(ids, kind="stable")
    maxes = {}
    for i, b in zip(order, ids[order]):
        maxes.setdefault(b, []).append(x[i])
    keys = sorted(maxes)
    for a, b in zip(keys, keys[1:]):
        assert max(maxes[a]) <= min(maxes[b])


@given(n=st.integers(32, 2000), buckets=st.integers(2, 16))
@settings(max_examples=25, deadline=None)
def test_sampled_splitters_balance(n, buckets):
    rng = np.random.default_rng(buckets * 1000 + n)
    x = rng.normal(0, 1e6, n).astype(np.int32)  # clustered (paper's 'local')
    spl = partition.sampled_splitters(jnp.asarray(x), buckets, oversample=64)
    ids = np.asarray(partition.splitter_bucket_ids(jnp.asarray(x), spl))
    counts = np.bincount(ids, minlength=buckets)
    assert counts.max() <= max(4.0 * n / buckets, 16)


def test_scatter_unscatter_roundtrip(rng):
    x = rng.integers(0, 1 << 20, 1000).astype(np.int32)
    ids = partition.paper_bucket_ids(jnp.asarray(x), 8)
    buckets, counts = partition.scatter_to_buckets(jnp.asarray(x), ids, 8, 1000)
    assert int(counts.sum()) == 1000
    buckets = jnp.sort(buckets, axis=1)
    out = partition.unscatter(buckets, counts, 1000)
    np.testing.assert_array_equal(np.asarray(out), np.sort(x))


def test_overflow_is_detected(rng):
    x = rng.integers(0, 10, 100).astype(np.int32)  # heavy duplicates
    ids = partition.paper_bucket_ids(jnp.asarray(x), 4)
    _, counts = partition.scatter_to_buckets(jnp.asarray(x), ids, 4, 8)
    assert int(counts.sum()) < 100  # clipped counts expose the overflow


def _intermediate_sizes(jaxpr):
    """Element counts of every value a jaxpr computes, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield int(np.prod(v.aval.shape))
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else (param,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _intermediate_sizes(sub)


@pytest.mark.parametrize("method", ["paper", "sampled"])
def test_sim_sort_builds_no_rank_matrix(method):
    """The sim sort buckets without an O(n·P) intermediate: nothing it
    computes reaches n_pad × (P + 1) elements, the one-hot rank matrix."""
    import functools

    import jax

    from repro.core.engine import _sim_sort_padded

    n_pad, P = 4096, 36
    fn = functools.partial(
        _sim_sort_padded, P=P, capacity=partition.default_capacity(n_pad, P),
        method=method, sample_size=2048,
    )
    jaxpr = jax.make_jaxpr(fn)(jnp.zeros(n_pad, jnp.int32), jnp.int32(n_pad))
    assert max(_intermediate_sizes(jaxpr.jaxpr)) < n_pad * (P + 1)


def test_ranks_are_stable(rng):
    ids = jnp.asarray(rng.integers(0, 4, 64).astype(np.int32))
    ranks = np.asarray(partition.bucket_ranks(ids, 4))
    for b in range(4):
        rb = ranks[np.asarray(ids) == b]
        np.testing.assert_array_equal(rb, np.arange(len(rb)))


def _reference_scatter(x, ids, num_buckets, capacity, fill):
    """Plain numpy: each bucket's first ``capacity`` elements in order of
    appearance, the rest dropped; the row tail holds ``fill``."""
    rows = np.full((num_buckets, capacity), fill, x.dtype)
    counts = np.zeros(num_buckets, np.int32)
    for b in range(num_buckets):
        kept = x[ids == b][:capacity]
        rows[b, : kept.size] = kept
        counts[b] = kept.size
    return rows, counts


def _reference_unscatter(buckets, counts, total):
    """Plain numpy: row prefixes concatenated in bucket order, cut or
    zero-padded to ``total``."""
    flat = np.concatenate([row[:c] for row, c in zip(buckets, counts)])[:total]
    out = np.zeros(total, buckets.dtype)
    out[: flat.size] = flat
    return out


def _same_bytes(got, want):
    got = np.asarray(got)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.tobytes() == want.tobytes()


def _keys(rng, dtype, shape):
    if dtype == np.float32:
        return rng.normal(0, 1e3, shape).astype(dtype)
    return rng.integers(0, 1 << 20, shape).astype(dtype)


def _bucket_ids(rng, spread, num_buckets, shape):
    if spread == "uniform":
        ids = rng.integers(0, num_buckets, shape)
    elif spread == "skewed":
        ids = np.minimum(rng.geometric(0.3, shape) - 1, num_buckets - 1)
    else:
        ids = np.full(shape, num_buckets - 1)
    return ids.astype(np.int32)


@pytest.mark.parametrize("vmapped", [False, True])
@pytest.mark.parametrize("dtype", [np.int32, np.uint32, np.float32])
@pytest.mark.parametrize(
    "n,num_buckets,capacity,spread",
    [
        (1000, 8, 256, "uniform"),  # balanced: nothing dropped
        (1000, 8, 64, "uniform"),  # every bucket overflows
        (4096, 37, 64, "skewed"),  # a few buckets overflow, most do not
        (300, 4, 512, "single"),  # every element in one bucket
        (300, 4, 40, "single"),  # ... which overflows
    ],
)
def test_scatter_unscatter_match_reference(
    rng, vmapped, dtype, n, num_buckets, capacity, spread
):
    """Rows in order of appearance, clipped counts, the later elements
    dropped on overflow, the fill value, and unscatter's zeroed tail —
    byte for byte against numpy, alone and as rows of a ``jax.vmap``."""
    import jax

    batch = 2 if vmapped else 1
    x = _keys(rng, dtype, (batch, n))
    ids = _bucket_ids(rng, spread, num_buckets, (batch, n))
    default = np.iinfo(dtype).max if np.issubdtype(dtype, np.integer) else np.inf
    # totals past, at (for row 0) and short of sum(counts): a zeroed tail,
    # an exact fit, a cut
    kept = int(_reference_scatter(x[0], ids[0], num_buckets, capacity, 0)[1].sum())
    totals = (n + 5, kept, n // 2)

    for fill in (default, 7):
        def run(xi, idi):
            rows, counts = partition.scatter_to_buckets(
                xi, idi, num_buckets, capacity,
                fill_value=None if fill is default else np.array(fill, dtype),
            )
            return rows, counts, [partition.unscatter(rows, counts, t) for t in totals]

        if vmapped:
            rows, counts, outs = jax.vmap(run)(jnp.asarray(x), jnp.asarray(ids))
        else:
            rows, counts, outs = jax.tree.map(
                lambda a: a[None], run(jnp.asarray(x[0]), jnp.asarray(ids[0]))
            )
        for i in range(batch):
            want_rows, want_counts = _reference_scatter(
                x[i], ids[i], num_buckets, capacity, np.array(fill, dtype)
            )
            _same_bytes(rows[i], want_rows)
            _same_bytes(counts[i], want_counts)
            assert (int(counts[i].sum()) < n) == (capacity < np.bincount(ids[i]).max())
            for total, out in zip(totals, outs):
                _same_bytes(out[i], _reference_unscatter(want_rows, want_counts, total))
