"""Array Division Procedure (§3.1) + beyond-paper balanced splitters.

The paper routes element ``v`` to bucket ``⌊(v − min) / SubDivider⌋`` with
``SubDivider = (max − min) / P``.  (The paper's formula omits the ``− min``
shift; without it, any array whose minimum is far from 0 lands every
element in a handful of buckets, so we include the shift — the obvious
intended semantics.)  This is *range partitioning*: bucket i's values are
all ≤ bucket i+1's, hence concatenation after per-bucket sorting is sorted
with **no merge step** — the paper's central trick.

Weakness the paper itself measures (its "local distribution" runs reach
only ~10% speedup): equal-width value ranges collapse under skew.  The
beyond-paper fix is classic sample sort: take an oversampled random/strided
sample, sort it, use its quantiles as splitters.  Bucket population is then
balanced to within a provable factor regardless of the value distribution.

Everything here is pure ``jnp`` and jit-safe.  :func:`scatter_to_buckets`
places elements with one stable sort by bucket id, with no O(n·P)
intermediate; :func:`bucket_ranks` / :func:`bucket_counts` keep the one-hot
formulation for the MoE dispatch.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def max_sentinel(dtype):
    """Typed dtype-max scalar (pad fill that sorts to the end).

    Must carry ``dtype`` explicitly: a bare Python int (uint32's
    4294967295) is weak-typed int32 by jax and overflows at trace time
    wherever it reaches ``jnp.where``/arguments directly.
    """
    if jnp.issubdtype(dtype, jnp.integer):
        return jnp.asarray(jnp.iinfo(dtype).max, dtype)
    return jnp.asarray(jnp.inf, dtype)


def min_sentinel(dtype):
    """Typed dtype-min scalar (masked out of max computations)."""
    if jnp.issubdtype(dtype, jnp.integer):
        return jnp.asarray(jnp.iinfo(dtype).min, dtype)
    return jnp.asarray(-jnp.inf, dtype)


def default_capacity(n: int, num_buckets: int) -> int:
    """The legacy fixed bucket capacity: ``2·ceil(n/P)`` rounded up to 8.

    Safe for near-uniform inputs only; ``repro.core.engine`` replaces it
    with a measured estimate (DESIGN.md §4) and keeps this as the floor.
    """
    cap = int(-(-2 * n // num_buckets))
    cap += (-cap) % 8
    return cap


def bucketed_length(n: int) -> int:
    """Power-of-two shape bucket for ``n``, at least 128.

    The shared shape-bucketing rule: ``repro.core.engine.SortEngine`` pads
    inputs to it and keys its warm jit cache on it, so any two lengths in
    the same bucket reuse one compilation.
    """
    # The floor is the TPU's vector lane width: every length up to 128
    # shares one executable, and no padded row is shorter than a lane.
    return max(1 << max(int(n) - 1, 0).bit_length(), 128)


def pack_segments(
    keys,
    seg_lens,
    row_len: int,
    *,
    fill_value=None,
    align: str = "left",
) -> np.ndarray:
    """Pack ``B`` concatenated variable-length segments into a ``(B, row_len)``
    dense matrix — the host half of the segmented batch path (DESIGN.md §8).

    ``keys`` is the flat concatenation of the segments, ``seg_lens`` their
    lengths in order.  This is a *host* (numpy) utility on purpose: requests
    arrive as host arrays, and one vectorized boolean-mask scatter packs the
    whole batch in a single pass — the device then sees exactly one
    ``(B, row_len)`` transfer instead of ``B`` small ones.

    ``align='left'`` places each segment at the row start (the sort layout:
    the valid prefix is ``row[:len]``); ``align='right'`` right-aligns the
    content (the serving left-pad layout — token ends line up so decode
    positions agree across the batch).  ``fill_value`` defaults to the dtype
    max so left-aligned pad tails sort to the end.
    """
    keys = np.asarray(keys).ravel()
    lens = np.asarray(seg_lens, dtype=np.int64).ravel()
    if (lens < 0).any():
        raise ValueError("pack_segments: negative segment length")
    if int(lens.sum()) != keys.size:
        raise ValueError(
            f"pack_segments: seg_lens sum to {int(lens.sum())} "
            f"but keys has {keys.size} elements"
        )
    if lens.size and int(lens.max()) > row_len:
        raise ValueError(
            f"pack_segments: longest segment ({int(lens.max())}) "
            f"exceeds row_len ({row_len})"
        )
    if fill_value is None:
        fill_value = (
            np.iinfo(keys.dtype).max
            if np.issubdtype(keys.dtype, np.integer)
            else np.inf
        )
    out = np.full((lens.size, row_len), fill_value, keys.dtype)
    pos = np.arange(row_len)[None, :]
    if align == "left":
        mask = pos < lens[:, None]
    elif align == "right":
        mask = pos >= row_len - lens[:, None]
    else:
        raise ValueError(f"pack_segments: unknown align {align!r}")
    # Row-major mask assignment consumes ``keys`` in concatenation order.
    out[mask] = keys
    return out


def unpack_segments(padded, seg_lens) -> list[np.ndarray]:
    """Inverse of :func:`pack_segments` (left-aligned): row prefixes as copies."""
    padded = np.asarray(padded)
    lens = np.asarray(seg_lens, dtype=np.int64).ravel()
    if padded.shape[0] != lens.size:
        raise ValueError(
            f"unpack_segments: {padded.shape[0]} rows vs {lens.size} lengths"
        )
    return [padded[i, : int(n)].copy() for i, n in enumerate(lens)]


def paper_bucket_ids(x: jax.Array, num_buckets: int) -> jax.Array:
    """§3.1: equal-width value-range bucket ids in ``[0, num_buckets)``.

    Float-based and therefore NOT exact for integer keys above 2^24 — the
    engine's sim path uses the exact unsigned-integer rule instead
    (``engine._paper_ids``), whose bit-identical host twin is
    ``repro.core.workloads.host_bucket_ids`` (re-exported below).  Use
    that pair whenever a host-side histogram must predict the kernel's
    scatter exactly (the top-k planner's contract, DESIGN.md §12).
    """
    x = jnp.asarray(x)
    lo = jnp.min(x).astype(jnp.float64 if x.dtype == jnp.int64 else jnp.float32)
    hi = jnp.max(x).astype(lo.dtype)
    width = (hi - lo) / num_buckets
    # Degenerate constant array → everything in bucket 0 (paper leaves this
    # implicit; division by zero would occur otherwise).
    safe_width = jnp.where(width > 0, width, 1.0)
    ids = jnp.floor((x.astype(lo.dtype) - lo) / safe_width).astype(jnp.int32)
    return jnp.clip(ids, 0, num_buckets - 1)


def sampled_splitters(
    x: jax.Array, num_buckets: int, *, oversample: int = 32, key: jax.Array | None = None
) -> jax.Array:
    """Beyond-paper: ``num_buckets − 1`` splitters from an oversampled sample.

    Deterministic strided sampling by default (reproducible, collective-free
    when used per-shard); pass ``key`` for random sampling.
    """
    x = jnp.asarray(x).ravel()
    n = x.shape[0]
    s = min(n, max(num_buckets * oversample, num_buckets))
    if key is not None:
        idx = jax.random.randint(key, (s,), 0, n)
        sample = x[idx]
    else:
        # ceil-stride so the strided sample spans the WHOLE array (a floor
        # stride + truncation would sample only the head — catastrophic for
        # sorted inputs).
        stride = -(-n // s)
        sample = x[::stride]
    sample = jnp.sort(sample)
    # splitter i = quantile (i+1)/num_buckets of the sample
    pos = (jnp.arange(1, num_buckets) * sample.shape[0]) // num_buckets
    return sample[pos]


def splitter_bucket_ids(x: jax.Array, splitters: jax.Array) -> jax.Array:
    """Bucket ids via searchsorted on sorted splitters (len = buckets − 1).

    The binary search is unrolled: on a v5e, 35 splitters over 2^24 int32
    keys take 1.7 ms against the default ``scan``'s 6.6 ms.
    """
    return jnp.searchsorted(
        splitters, jnp.asarray(x), side="right", method="scan_unrolled"
    ).astype(jnp.int32)


def bucket_counts(bucket_ids: jax.Array, num_buckets: int) -> jax.Array:
    """Histogram of bucket ids, shape (num_buckets,) int32."""
    return jnp.zeros(num_buckets, jnp.int32).at[bucket_ids].add(1)


def bucket_ranks(bucket_ids: jax.Array, num_buckets: int) -> jax.Array:
    """Rank of each element within its bucket (stable, order-of-appearance).

    rank[i] = #{j < i : bucket_ids[j] == bucket_ids[i]}.  Implemented as a
    cumulative sum over the one-hot bucket matrix.
    """
    one_hot = jax.nn.one_hot(bucket_ids, num_buckets, dtype=jnp.int32)
    # exclusive cumsum along the element axis
    csum = jnp.cumsum(one_hot, axis=0) - one_hot
    return jnp.take_along_axis(csum, bucket_ids[:, None], axis=1)[:, 0]


def scatter_to_buckets(
    x: jax.Array,
    bucket_ids: jax.Array,
    num_buckets: int,
    capacity: int,
    *,
    fill_value=None,
) -> tuple[jax.Array, jax.Array]:
    """Scatter elements into a dense (num_buckets, capacity) buffer.

    Returns (buckets, counts).  Elements beyond ``capacity`` in a bucket are
    dropped (jit-safe static shape); ``counts`` is CLIPPED to capacity so it
    reflects what was actually stored — overflow is therefore detectable as
    ``counts.sum() < x.size`` (callers raise/retry; see dist_sort docs).
    ``fill_value`` defaults to the dtype max so padded tails sort to the end.

    One stable sort by bucket id lays every bucket out contiguously in
    order of appearance — the same stable ranks :func:`bucket_ranks`
    computes, without its O(n·B) one-hot matrix.  The bucket edges are
    B+1 binary searches of the sorted ids, and row ``b`` is the slice of
    ``capacity`` elements from its edge, its tail past ``counts[b]`` filled.
    """
    x = jnp.asarray(x).ravel()
    bucket_ids = jnp.asarray(bucket_ids).ravel()
    if fill_value is None:
        fill_value = (
            jnp.iinfo(x.dtype).max
            if jnp.issubdtype(x.dtype, jnp.integer)
            else jnp.inf
        )
    fill = jnp.full(capacity, fill_value, x.dtype)
    sorted_ids, sorted_x = jax.lax.sort(
        (bucket_ids, x), num_keys=1, is_stable=True
    )
    edges = jnp.searchsorted(
        sorted_ids,
        jnp.arange(num_buckets + 1, dtype=sorted_ids.dtype),
        side="left",
        method="scan",
    )
    counts = jnp.minimum(jnp.diff(edges), capacity).astype(jnp.int32)
    # ``capacity`` fill slots past the end keep every slice in bounds
    # (dynamic_slice would clamp its start instead).
    padded = jnp.concatenate([sorted_x, fill])
    rows = jax.vmap(lambda e: jax.lax.dynamic_slice(padded, (e,), (capacity,)))(
        edges[:-1]
    )
    keep = jnp.arange(capacity)[None, :] < counts[:, None]
    return jnp.where(keep, rows, fill), counts


def unscatter(
    buckets: jax.Array, counts: jax.Array, total: int
) -> jax.Array:
    """Concatenate bucket prefixes (bucket order) into a flat array of ``total``.

    Because buckets are range-partitioned and individually sorted, the
    result is globally sorted — §3.1's merge-free gather.  Each row is
    written whole at its exclusive prefix offset, in bucket order, so row
    ``b + 1`` overwrites row ``b``'s tail past ``counts[b]``; slots at or
    past ``sum(counts)`` read 0.
    """
    num_buckets, capacity = buckets.shape
    offsets = jnp.cumsum(counts) - counts  # exclusive prefix

    def put(b, out):
        return jax.lax.dynamic_update_slice(out, buckets[b], (offsets[b],))

    out = jax.lax.fori_loop(
        0, num_buckets, put, jnp.zeros(total + capacity, buckets.dtype)
    )[:total]
    return jnp.where(jnp.arange(total) < jnp.sum(counts), out, 0)


# Exact host-side twin of the engine's integer equal-width rule — lives in
# ``repro.core.workloads`` (pure numpy, no jax) and is re-exported here so
# bucket-rule callers find both variants in one module.
from repro.core.workloads import host_bucket_ids  # noqa: E402,F401
